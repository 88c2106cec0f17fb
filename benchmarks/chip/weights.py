"""Random weights for a dense GQA configuration, drawn from the run's seed.

One jitted call makes the whole tree on the device, in the dtype it is
served in, in the layout the repo's dense transformer reads:

    embed [V, D], final_norm [D], (unembed [D, V] when the head is untied),
    layers: ln1 [L, D], ln2 [L, D],
            attn {wq [L, D, H*hd], wk [L, D, K*hd], wv [L, D, K*hd],
                  wo [L, H*hd, D]},
            mlp  {w_gate [L, D, F], w_up [L, D, F], w_down [L, F, D]}

Both the program under test and the reference get their weights from here,
so the reference takes nothing the program made: it draws the same tree
again from the seed after the window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any seed up to 64 bits."""
    seed = int(seed)
    return jax.random.wrap_key_data(
        np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32))


def shapes(cfg) -> dict:
    """{path: shape} of every leaf (path joined by '/')."""
    D, F, V, L = cfg.d_model, cfg.d_ff, cfg.vocab, cfg.n_layers
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    out = {"embed": (V, D), "final_norm": (D,),
           "layers/ln1": (L, D), "layers/ln2": (L, D),
           "layers/attn/wq": (L, D, H * hd), "layers/attn/wk": (L, D, K * hd),
           "layers/attn/wv": (L, D, K * hd), "layers/attn/wo": (L, H * hd, D),
           "layers/mlp/w_gate": (L, D, F), "layers/mlp/w_up": (L, D, F),
           "layers/mlp/w_down": (L, F, D)}
    if not cfg.tie_embeddings:
        out["unembed"] = (D, V)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *heads, last = path.split("/")
        node = tree
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = leaf
    return tree


@functools.partial(jax.jit, static_argnums=(1,))
def _generate(key, cfg):
    dtype = jnp.dtype(cfg.param_dtype)
    flat = {}
    for i, (path, shape) in enumerate(sorted(shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if path.endswith(("ln1", "ln2", "final_norm")):
            flat[path] = jnp.ones(shape, dtype)
        elif path == "embed":
            flat[path] = (jax.random.normal(k, shape, jnp.float32)
                          * 0.02).astype(dtype)
        else:
            fan_in = shape[-2]
            flat[path] = (jax.random.normal(k, shape, jnp.float32)
                          * fan_in ** -0.5).astype(dtype)
    return _nest(flat)


def generate(cfg, seed: int) -> dict:
    """The weight tree for ``cfg`` drawn from ``seed``, on the device."""
    if cfg.family != "dense":
        raise ValueError(f"weights.py draws dense trees, not {cfg.family!r}")
    return _generate(seed_key(seed), cfg)
