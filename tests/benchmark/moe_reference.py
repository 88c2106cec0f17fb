"""Plain reference of the repo's ``moe`` family, kept with the tests beside
moe_family.py.

It imports nothing of the program and builds on the dense reference's
blocks (RMSNorm, RoPE, causal GQA, the linear layer and its lower-precision
control): pre-norm blocks of attention and then the expert layer, a final
RMSNorm and the untied head.  The expert layer routes each token in
float32 (softmax over the router's outputs, its ``top_k`` weights
renormalised to sum to 1) and adds the routed experts' SwiGLU outputs, so
weighted, and the shared expert's.  No expert drops a token: the program's
capacity bound drops none while a call holds at most ``n_experts`` tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.chip.references import dense as D

Q_BLOCK = D.Q_BLOCK


def _swiglu(p, h, quant):
    g = D._linear(h, p["w_gate"], quant)
    u = D._linear(h, p["w_up"], quant)
    return D._linear(jax.nn.silu(g) * u, p["w_down"], quant)


def _expert_layer(p, h, top_k, quant):
    gates = jax.nn.softmax(jnp.dot(h, p["router"].astype(jnp.float32),
                                   precision=D.HI), axis=-1)
    weight, chosen = jax.lax.top_k(gates, top_k)
    weight = weight / weight.sum(-1, keepdims=True)
    n_experts = p["w_gate"].shape[0]
    every = jnp.stack([_swiglu({w: p[w][e] for w in
                                ("w_gate", "w_up", "w_down")}, h, quant)
                       for e in range(n_experts)])            # [E, S, D]
    rows = jnp.arange(h.shape[0])[:, None]
    y = (every[chosen, rows] * weight[..., None]).sum(1)
    if "shared" in p:
        y = y + _swiglu(p["shared"], h, quant)
    return y


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def logits_at(params, tokens, rows, *, cfg, quant="f32"):
    """Logits [len(rows), V] in float32 of one sequence ``tokens`` [S]
    (S a multiple of Q_BLOCK)."""
    S = tokens.shape[0]
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(S)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, lp):
        h = D._rms(x, lp["ln1"], eps)
        q = D._rope(D._linear(h, lp["attn"]["wq"], quant).reshape(S, H, hd),
                    pos, theta)
        k = D._rope(D._linear(h, lp["attn"]["wk"], quant).reshape(S, K, hd),
                    pos, theta)
        v = D._linear(h, lp["attn"]["wv"], quant).reshape(S, K, hd)
        x = x + D._linear(D._attention(q, k, v), lp["attn"]["wo"], quant)
        h = D._rms(x, lp["ln2"], eps)
        return x + _expert_layer(lp["moe"], h, cfg["top_k"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    xr = D._rms(x[rows], params["final_norm"], eps)
    return D._linear(xr, params["unembed"], quant)


def static_config(model: dict):
    keys = ("n_heads", "n_kv_heads", "norm_eps", "rope_theta")
    hd = model["head_dim"] or model["d_model"] // model["n_heads"]
    return D._Frozen({**{k: model[k] for k in keys}, "head_dim": hd,
                      "top_k": model["moe"]["top_k"]})
