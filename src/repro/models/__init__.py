"""Model zoo: pure-functional JAX implementations of the assigned archs."""
from . import layers, mla, moe, rglru, ssm
from .model import (
    active_param_count,
    cache_specs,
    decode_step,
    embed_inputs,
    forward,
    init_cache,
    init_params,
    mtp_logits,
    next_token_logits,
    param_count,
    param_specs,
    prefill,
)

__all__ = [
    "layers", "mla", "moe", "rglru", "ssm",
    "active_param_count", "cache_specs", "decode_step", "embed_inputs",
    "forward", "init_cache", "init_params", "mtp_logits",
    "next_token_logits", "param_count",
    "param_specs", "prefill",
]
