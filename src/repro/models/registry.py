"""Family registry — a uniform API over the model zoo.

Every family exposes:
    init(key, cfg) -> params
    param_specs(cfg) -> logical sharding pytree
    loss(params, cfg, batch) -> scalar
    prefill(params, cfg, batch, max_len, seal_ctx=None) -> (logits, cache)
    decode_step(params, cfg, cache, tokens, seal_ctx=None) -> (logits, cache)
plus cache/state constructors, unified here as ``make_decode_state``.
"""
from __future__ import annotations

import functools
import types

import jax

from . import encdec, moe, rwkv, ssm, transformer

_FAMILY = {
    "dense": transformer,
    "vlm": transformer,      # patch-stub frontend handled by _embed_inputs
    "moe": moe,
    "rwkv": rwkv,
    "hybrid": ssm,
    "encdec": encdec,
}


def get_model(cfg) -> types.ModuleType:
    return _FAMILY[cfg.family]


@functools.partial(jax.jit, static_argnums=1)
def init_params(key, cfg):
    """``get_model(cfg).init`` as one compiled program.  The device then
    holds the weights and nothing beside them; run op by op, the init also
    holds each leaf's float32 draw and its intermediates."""
    return get_model(cfg).init(key, cfg)


def make_decode_state(cfg, batch: int, max_len: int, src_len: int = 0,
                      sealed: bool = False):
    """Uniform decode-state/cache constructor across families."""
    if cfg.family in ("dense", "vlm"):
        return transformer.init_cache(cfg, batch, max_len, sealed)
    if cfg.family == "moe":
        return moe.init_cache(cfg, batch, max_len, sealed)
    if cfg.family == "rwkv":
        return (rwkv.init_state_sealed(cfg, batch) if sealed
                else rwkv.init_state(cfg, batch))
    if cfg.family == "hybrid":
        return (ssm.init_state_sealed(cfg, batch, max_len) if sealed
                else ssm.init_state(cfg, batch, max_len))
    if cfg.family == "encdec":
        return encdec.init_cache(cfg, batch, max_len, src_len, sealed)
    raise ValueError(cfg.family)


def decode_state_specs(cfg, sealed: bool = False):
    """Uniform logical shardings for the decode state."""
    if cfg.family in ("dense", "vlm"):
        return transformer.cache_specs(cfg, sealed)
    if cfg.family == "moe":
        return moe.cache_specs(cfg, sealed)
    if cfg.family == "rwkv":
        return rwkv.state_specs(cfg, sealed)
    if cfg.family == "hybrid":
        return ssm.state_specs(cfg, sealed)
    if cfg.family == "encdec":
        return encdec.cache_specs(cfg, sealed)
    raise ValueError(cfg.family)
