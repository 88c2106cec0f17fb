"""Plain reference of the dense GQA transformer the repo serves.

It imports nothing of the program.  It follows the layer equations of the
repo's dense family as the configuration states them: pre-norm RMSNorm
blocks, RoPE on the two halves of each head, grouped-query causal attention
scaled by head_dim**-0.5, a SwiGLU MLP, a final RMSNorm and a head that is
the embedding's transpose when the configuration ties it.  Like the repo's
layer it applies none of Granite's muP multipliers (the configuration lists
that under ``assumed``).

Everything is computed in float32 with every matrix product at HIGHEST
precision, from the bf16 weights as served.  ``quant`` puts a lower
precision in its place for the control: "int8" quantizes both operands of
every linear layer and of the head (weights per output column, activations
per row, symmetric, to 127) and "fp8" casts them, scaled the same way, to
float8_e4m3fn; attention, norms and the residual stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _quant(x, axis, quant):
    """x with both its scale and rounding of a lower precision, in f32."""
    if quant == "f32":
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown quant {quant!r}")


def _linear(x, w, quant):
    """x [S, din] @ w [din, dout] in float32 (or the control's precision)."""
    w = w.astype(jnp.float32)
    return jnp.dot(_quant(x, -1, quant), _quant(w, 0, quant), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, n, hd]: rotate the first half of each head against the second."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq[None, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _attention(q, k, v):
    """Causal GQA. q [S, H, hd], k/v [S, K, hd] -> [S, H*hd], in blocks of
    Q_BLOCK query rows so the scores of a long sequence fit."""
    S, H, hd = q.shape
    K = k.shape[1]
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, K, H // K, hd)
    tpos = jnp.arange(S)

    def block(args):
        qb, i = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HI) * hd ** -0.5
        sc = jnp.where(tpos[None, None, None, :] <= qpos[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    out = jax.lax.map(block, (qg, jnp.arange(S // Q_BLOCK)))
    return out.reshape(S, H * hd)


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def logits_at(params, tokens, rows, *, cfg, quant="f32"):
    """Logits [len(rows), V] in float32 of one sequence ``tokens`` [S]
    (S a multiple of Q_BLOCK; rows past the real sequence are padding,
    which causal attention keeps out of every earlier row)."""
    S = tokens.shape[0]
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(S)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, lp):
        h = _rms(x, lp["ln1"], eps)
        q = _rope(_linear(h, lp["attn"]["wq"], quant).reshape(S, H, hd),
                  pos, theta)
        k = _rope(_linear(h, lp["attn"]["wk"], quant).reshape(S, K, hd),
                  pos, theta)
        v = _linear(h, lp["attn"]["wv"], quant).reshape(S, K, hd)
        x = x + _linear(_attention(q, k, v), lp["attn"]["wo"], quant)
        h = _rms(x, lp["ln2"], eps)
        g = _linear(h, lp["mlp"]["w_gate"], quant)
        u = _linear(h, lp["mlp"]["w_up"], quant)
        x = x + _linear(jax.nn.silu(g) * u, lp["mlp"]["w_down"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    xr = _rms(x[rows], params["final_norm"], eps)
    head = (params["embed"].T if cfg["tie_embeddings"]
            else params["unembed"])
    return _linear(xr, head, quant)


def static_config(model: dict) -> tuple:
    """The hashable subset of a configuration's ``model`` block that the
    reference reads, as the ``cfg`` argument of ``logits_at``."""
    keys = ("n_heads", "n_kv_heads", "norm_eps", "rope_theta",
            "tie_embeddings")
    hd = model["head_dim"] or model["d_model"] // model["n_heads"]
    return _Frozen({**{k: model[k] for k in keys}, "head_dim": hd})


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))
