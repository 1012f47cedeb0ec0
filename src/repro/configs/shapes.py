"""Assigned input shapes and ShapeDtypeStruct builders for the dry-run.

Four global input shapes (assignment):
  train_4k     seq=4096    batch=256   train_step
  prefill_32k  seq=32768   batch=32    full-sequence forward (no grad)
  decode_32k   seq=32768   batch=128   serve_step: 1 token + KV cache
  long_500k    seq=524288  batch=1     serve_step, sub-quadratic only

``input_specs`` returns ShapeDtypeStruct stand-ins for every model
input — weak-type-correct, shardable, no device allocation.
``skip_reason`` encodes the DESIGN.md §Arch-applicability skips.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.transformer import init_cache


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # "train" | "prefill" | "decode"


SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def skip_reason(cfg: ModelConfig, shape: InputShape) -> str | None:
    """None if the (arch, shape) pair runs; else the documented skip."""
    if shape.mode == "decode" and not cfg.causal:
        return "encoder-only architecture has no autoregressive decode step"
    if shape.mode == "decode" and not cfg.has_decode:
        return "latent attention has no cached decode path yet"
    if (
        shape.name == "long_500k"
        and not cfg.supports_long_context
    ):
        return (
            "full quadratic attention; 500k decode requires a sub-quadratic "
            "path (SSM/hybrid recurrence or sliding window)"
        )
    if shape.mode == "prefill" and cfg.frontend == "vision_stub" and \
            shape.seq_len <= cfg.num_prefix_tokens:
        return "sequence shorter than vision prefix"
    return None


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: InputShape | str) -> dict:
    """ShapeDtypeStruct pytree for the step function of ``shape.mode``.

    train/prefill -> batch dict for ``loss_fn`` / ``forward``;
    decode -> {"cache": ..., "token": ..., "pos": ...} for ``decode_step``.
    """
    if isinstance(shape, str):
        shape = SHAPES[shape]
    b, s = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)

    if shape.mode in ("train", "prefill"):
        if cfg.frontend == "audio_stub":
            batch = {
                "frames": _sds((b, s, cfg.d_model), dt),
                "labels": _sds((b, s), jnp.int32),
            }
        elif cfg.frontend == "vision_stub":
            text = s - cfg.num_prefix_tokens
            batch = {
                "prefix_embeds": _sds((b, cfg.num_prefix_tokens, cfg.d_model), dt),
                "tokens": _sds((b, text), jnp.int32),
                "labels": _sds((b, text), jnp.int32),
            }
        else:
            batch = {
                "tokens": _sds((b, s), jnp.int32),
                "labels": _sds((b, s), jnp.int32),
            }
        return {"batch": batch}

    cache = jax.eval_shape(
        lambda: init_cache(cfg, b, s, dtype=dt)
    )
    return {
        "cache": cache,
        "token": _sds((b, 1), jnp.int32),
        "pos": _sds((), jnp.int32),
    }
