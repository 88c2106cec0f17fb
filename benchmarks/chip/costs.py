"""Operations and bytes of the dense GQA engine's calls, from shapes alone.

Counts are of useful work: the valid tokens of active lanes, attention over
each lane's valid context, logits only where a token is taken.  Padding,
idle lanes and the sealing's own integer work are not counted, so a share
of a peak read from these is the share of the chip spent on the model.
"""
from __future__ import annotations

import numpy as np


def _itemsize(dtype: str) -> int:
    return np.dtype({"bfloat16": "float16"}.get(dtype, dtype)).itemsize


def layer_matmul_params(m: dict) -> int:
    """Parameters of one layer's matrix products (q, k, v, o, MLP)."""
    D, F = m["d_model"], m["d_ff"]
    H, K = m["n_heads"], m["n_kv_heads"]
    hd = m["head_dim"] or D // H
    return D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F


def param_count(m: dict) -> int:
    D, V, L = m["d_model"], m["vocab"], m["n_layers"]
    head = 0 if m["tie_embeddings"] else D * V
    return L * (layer_matmul_params(m) + 2 * D) + V * D + D + head


def weight_bytes(m: dict) -> int:
    return param_count(m) * _itemsize(m["param_dtype"])


def kv_bytes_per_token(m: dict) -> int:
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    return 2 * m["n_layers"] * m["n_kv_heads"] * hd * _itemsize(m["dtype"])


def _attn_flops(m: dict, ctx: int) -> int:
    """Scores and weighted sum of one query over ``ctx`` positions."""
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    return 4 * m["n_layers"] * m["n_heads"] * hd * ctx


def logits_flops(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab"]


def decode_flops(m: dict, contexts) -> int:
    """One decode call: each active lane's token at its context length
    (positions attended, the new one included), with its logits."""
    per_tok = 2 * m["n_layers"] * layer_matmul_params(m) + logits_flops(m)
    return sum(per_tok + _attn_flops(m, int(c)) for c in contexts)


def prefill_flops(m: dict, lanes) -> int:
    """One chunk call: lanes [(start, n, completes)], each prefilling n
    prompt tokens from position ``start``, causally; a lane whose prompt
    completes takes the logits of its last token."""
    total = 0
    for start, n, completes in lanes:
        ctx_sum = n * start + n * (n + 1) // 2
        total += (2 * m["n_layers"] * layer_matmul_params(m) * n
                  + _attn_flops(m, ctx_sum)
                  + (logits_flops(m) if completes else 0))
    return total


def decode_min_bytes(m: dict, contexts) -> int:
    """The least a decode call must move: the weights once, each active
    lane's valid K and V once, and the new token's K and V written."""
    kv = kv_bytes_per_token(m)
    return weight_bytes(m) + sum(int(c) * kv for c in contexts)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: compute or memory, whichever
    binds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
