"""Model zoo covering the six assigned architecture families."""

from .config import ModelConfig
from .transformer import (
    decode_step,
    embed_inputs,
    forward,
    generate,
    init_cache,
    init_params,
    loss_fn,
    prefill,
    sequence_losses,
)

__all__ = [
    "ModelConfig",
    "init_params",
    "forward",
    "loss_fn",
    "sequence_losses",
    "embed_inputs",
    "init_cache",
    "decode_step",
    "prefill",
    "generate",
]
