"""Coded training of an expert model: the ``train`` driver's set-up,
timed call and checks, bound to the reference that the configuration
names (``"reference": "deepseek_v2"`` -> ``bench/refs/deepseek_v2.py``).

The ``train`` driver module is loaded once more as a private copy whose
reference and program configuration are this model's, so its
``Driver``, comparisons and steady-state check run unchanged: the first
three steps' losses, the first gradient's leaf norms, the change after
step 3 and the optimizer steps, and one step after the window.

One more number is compared, ``grad_rel_error``: the relative L2 error
of model 0's whole first gradient against the reference's.  Leaf norms
average rounding away, so lower precision hardly moves them; the
gradient itself moves with every rounding of the backward pass.

Besides the checks, the run prints on stderr the share of (token, k)
expert selections on which the program and the reference disagree on
model 0's first step from its initial weights, summed over the expert
layers: bfloat16 hidden states can flip near-ties, and a flipped
selection moves the gradient of the experts concerned.
"""

from __future__ import annotations

import importlib
import sys

import jax
import jax.numpy as jnp

from bench.harness import BENCH, finite, load_module


def program_config(cfg: dict):
    """The program's model configuration at the widths of ``cfg``."""
    from repro.configs import get_config

    ys = cfg["rope_scaling"]
    return get_config(cfg["program_arch"]).replace(
        num_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        moe_d_ff=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        held_expert_start=cfg["held_expert_start"],
        held_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        balance="seq" if cfg["seq_aux"] else "switch",
        balance_weight=cfg["aux_loss_alpha"],
        vocab_size=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(ys["factor"]),
        rope_original_max_pos=ys["original_max_position_embeddings"],
        yarn_beta_fast=float(ys["beta_fast"]),
        yarn_beta_slow=float(ys["beta_slow"]),
        yarn_mscale=ys["mscale"], yarn_mscale_all_dim=ys["mscale_all_dim"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"])


def _check_supported(cfg: dict) -> None:
    if cfg["routed_scaling_factor"] != 1 or cfg["q_lora_rank"] is not None \
            or cfg["topk_method"] != "greedy" or cfg["attention_bias"]:
        raise ValueError("the program's expert model has no query LoRA, "
                         "greedy top-k, scaling 1 and no attention bias")


def _bound(reference: str):
    """A private copy of the ``train`` driver module with this model's
    reference and program configuration."""
    mod = load_module(BENCH / "drivers" / "train.py",
                      f"bench_driver_train_{reference}")
    mod.ref = importlib.import_module(f"bench.refs.{reference}")
    mod.program_config = program_config
    mod._FirstSteps = _keeping_first_grad(mod._FirstSteps)
    return mod


def _keeping_first_grad(base):
    class FirstSteps(base):
        """Also copies model 0's first moment after its first step, which
        holds ``(1 - b1)`` times the first gradient, to the host."""

        first_m = None

        def after(self, before, params, opt):
            if self.grad is None:
                self.first_m = jax.device_get(opt.m)
            super().after(before, params, opt)

    return FirstSteps


def _leaves(tree) -> dict:
    return {"/".join(k.key for k in path): x for path, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def grad_rel_error(prog, want, scale: float = 1.0) -> float:
    """``|scale * prog - want| / |want|`` in the L2 norm over every leaf
    of two gradients (pytrees of one layout, on the host or the
    device), summed in float32 one leaf at a time."""
    prog, want = _leaves(prog), _leaves(want)
    if prog.keys() != want.keys():
        return float("inf")

    @jax.jit
    def sums(p, w):
        p, w = p.astype(jnp.float32) * scale, w.astype(jnp.float32)
        return jnp.sum((p - w) ** 2), jnp.sum(w * w)

    err = ref = 0.0
    for k, w in want.items():
        e, r = sums(prog[k], w)
        err, ref = err + float(e), ref + float(r)
    return (err / ref) ** 0.5


def program_routing(pcfg, params, toks):
    """The program's top-k expert ids of every expert layer (expert
    layers, b, s, K): its own layers, with the router's selection read
    beside each expert layer."""
    from repro.models.layers import rmsnorm_apply
    from repro.models.transformer import (
        _attention,
        _attn_layer_body,
        _expert_layer_body,
    )

    def ids(params, toks):
        x = params["embed"][toks]
        x = jax.lax.scan(lambda h, lp: _attn_layer_body(h, lp, pcfg), x,
                         params["dense_layers"])[0]

        def layer(x, lp):
            h = rmsnorm_apply(lp["norm2"], x + _attention(lp, x, pcfg))
            logits = (h.astype(jnp.float32)
                      @ lp["moe"]["router"].astype(jnp.float32))
            idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                pcfg.num_experts_per_tok)[1]
            return _expert_layer_body(x, lp, pcfg)[0], idx

        return jax.lax.scan(layer, x, params["layers"])[1]

    return jax.jit(ids)(params, toks)


def routing_disagreement(prog_ids, ref_ids) -> float:
    """Share of (token, k) selections of one side missing from the
    other's selections for the same token and layer."""
    same = (prog_ids[..., :, None] == ref_ids[..., None, :]).any(-1)
    return float(1.0 - same.mean())


def Driver(ctx):
    """The ``train`` driver bound to the configuration's reference."""
    _check_supported(ctx.cell.config)
    return _driver_class(_bound(ctx.cell.config["reference"]))(ctx)


def _driver_class(base):
    class MoEDriver(base.Driver):
        """Also keeps the chunk replication that the expert-row metrics
        divide out, compares the whole first gradient and reports the
        routing disagreement."""

        def __init__(self, ctx):
            super().__init__(ctx)
            tr = self.trainer
            self.passes_per_job = self.n * tr.slots
            # each useful routed row is computed this many times a step
            self.replication = self.passes_per_job / tr.num_chunks

        def check(self):
            checks = super().check()
            dseed, job = self.steps[0]
            toks = base.ref.tokens(dseed, job, self.batch, self.seq,
                                   self.cfg["vocab_size"])
            params = base.ref.init_weights(self.cfg, self.weight_seeds[0])
            share = routing_disagreement(
                program_routing(self.pcfg, params, toks),
                base.ref.routing(params, toks, self.cfg))
            print(f"bench: routing disagreement {share!r} of (token, k) "
                  "selections, model 0's first step", file=sys.stderr)
            want = base.ref.Trainer(self.cfg, params,
                                    blocks=self.tr["ref_blocks"], m=0,
                                    v=0).loss_and_grad(toks)[1]
            err = grad_rel_error(self.first.first_m, want,
                                 1.0 / (1.0 - self.b1))
            return checks + [{"name": "grad_rel_error", "value": finite(err),
                              "limit": self.tr["limits"]["grad_rel_error"]}]

    return MoEDriver
