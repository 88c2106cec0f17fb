"""The dense GQA transformer (``repro.models.transformer``): its weight tree
and the operations and bytes of the engine's calls.

The tree is the layout the repo's dense layer reads:

    embed [V, D], final_norm [D], (unembed [D, V] when the head is untied),
    layers: ln1 [L, D], ln2 [L, D],
            attn {wq [L, D, H*hd], wk [L, D, K*hd], wv [L, D, K*hd],
                  wo [L, H*hd, D]},
            mlp  {w_gate [L, D, F], w_up [L, D, F], w_down [L, F, D]}

Counts are of useful work: the valid tokens of active lanes, attention over
each lane's valid context, logits only where a token is taken.  Padding,
idle lanes and the sealing's own integer work are not counted, so a share
of a peak read from these is the share of the chip spent on the model.
"""
from __future__ import annotations

import numpy as np


def itemsize(dtype: str) -> int:
    return np.dtype({"bfloat16": "float16"}.get(dtype, dtype)).itemsize


def head_dim(m: dict) -> int:
    return m["head_dim"] or m["d_model"] // m["n_heads"]


# -- the weight tree ---------------------------------------------------------
def leaves(cfg) -> dict:
    """{path: (shape, rule)} of every leaf, path joined by '/'."""
    D, F, V, L = cfg.d_model, cfg.d_ff, cfg.vocab, cfg.n_layers
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    out = {"embed": ((V, D), "embed"), "final_norm": ((D,), "ones"),
           "layers/ln1": ((L, D), "ones"), "layers/ln2": ((L, D), "ones"),
           "layers/attn/wq": ((L, D, H * hd), "fan_in"),
           "layers/attn/wk": ((L, D, K * hd), "fan_in"),
           "layers/attn/wv": ((L, D, K * hd), "fan_in"),
           "layers/attn/wo": ((L, H * hd, D), "fan_in"),
           "layers/mlp/w_gate": ((L, D, F), "fan_in"),
           "layers/mlp/w_up": ((L, D, F), "fan_in"),
           "layers/mlp/w_down": ((L, F, D), "fan_in")}
    if not cfg.tie_embeddings:
        out["unembed"] = ((D, V), "fan_in")
    return out


# -- counts --------------------------------------------------------------------
def attn_params(m: dict) -> int:
    """Parameters of one layer's q, k, v and o projections."""
    D, H, K, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], head_dim(m)
    return D * H * hd + 2 * D * K * hd + H * hd * D


def layer_matmul_params(m: dict) -> int:
    """Parameters of one layer's matrix products (q, k, v, o, MLP)."""
    return attn_params(m) + 3 * m["d_model"] * m["d_ff"]


def embed_params(m: dict) -> int:
    """Embedding, final norm and, when untied, the head."""
    D, V = m["d_model"], m["vocab"]
    return V * D + D + (0 if m["tie_embeddings"] else D * V)


def param_count(m: dict) -> int:
    return (m["n_layers"] * (layer_matmul_params(m) + 2 * m["d_model"])
            + embed_params(m))


def weight_bytes(m: dict) -> int:
    return param_count(m) * itemsize(m["param_dtype"])


def kv_bytes_per_token(m: dict) -> int:
    return (2 * m["n_layers"] * m["n_kv_heads"] * head_dim(m)
            * itemsize(m["dtype"]))


def attn_flops(m: dict, ctx: int) -> int:
    """Scores and weighted sum of one query over ``ctx`` positions."""
    return 4 * m["n_layers"] * m["n_heads"] * head_dim(m) * ctx


def logits_flops(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab"]


def token_flops(m: dict) -> int:
    """The matrix products one token passes through, logits aside."""
    return 2 * m["n_layers"] * layer_matmul_params(m)


def decode_flops(m: dict, contexts) -> int:
    """One decode call: each active lane's token at its context length
    (positions attended, the new one included), with its logits."""
    per_tok = token_flops(m) + logits_flops(m)
    return sum(per_tok + attn_flops(m, int(c)) for c in contexts)


def prefill_flops(m: dict, lanes) -> int:
    """One chunk call: lanes [(start, n, completes)], each prefilling n
    prompt tokens from position ``start``, causally; a lane whose prompt
    completes takes the logits of its last token."""
    total = 0
    for start, n, completes in lanes:
        ctx_sum = n * start + n * (n + 1) // 2
        total += (token_flops(m) * n + attn_flops(m, ctx_sum)
                  + (logits_flops(m) if completes else 0))
    return total


def decode_min_bytes(m: dict, contexts) -> int:
    """The least a decode call must move: the weights once, each active
    lane's valid K and V once, and the new token's K and V written."""
    kv = kv_bytes_per_token(m)
    return weight_bytes(m) + sum(int(c) * kv for c in contexts)
