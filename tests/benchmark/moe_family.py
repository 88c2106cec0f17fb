"""A family module for the repo's ``moe`` family (``repro.models.moe``),
kept with the tests: it shows that a family the benchmark has no module for
enters by a file of its own, with no edit to the harness.

Every layer is GQA attention and a token-choice top-k expert layer
(``moe_every`` 1), with a shared expert where the configuration has one.
The tree is the one ``moe.init`` makes:

    embed [V, D], final_norm [D], unembed [D, V] (the family never ties it),
    layers: ln1 [L, D], ln2 [L, D], attn {wq, wk, wv, wo} as the dense layer,
            moe {router [L, D, E] in float32, w_gate [L, E, D, F],
                 w_up [L, E, D, F], w_down [L, E, F, D],
                 shared {w_gate [L, D, Fs], w_up [L, D, Fs],
                         w_down [L, Fs, D]}}

where F is ``d_ff``, the width of one expert.  A token passes through the
router, its ``top_k`` experts and the shared expert, so that is what the
counts charge it.
"""
from __future__ import annotations

from benchmarks.chip.families import dense


def _moe(m) -> tuple:
    """(E, k, F, Fs) of a model dict: experts, experts per token, an
    expert's width, the shared expert's width (0 without one)."""
    e = m["moe"]
    shared = (e["d_ff_shared"] or m["d_ff"]) if e["shared_expert"] else 0
    return e["n_experts"], e["top_k"], m["d_ff"], shared


def leaves(cfg) -> dict:
    if cfg.moe.moe_every != 1:
        raise ValueError("this family module draws moe_every 1 only")
    D, V, L = cfg.d_model, cfg.vocab, cfg.n_layers
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    E, F = cfg.moe.n_experts, cfg.d_ff
    out = {"embed": ((V, D), "embed"), "final_norm": ((D,), "ones"),
           "unembed": ((D, V), "fan_in"),
           "layers/ln1": ((L, D), "ones"), "layers/ln2": ((L, D), "ones"),
           "layers/attn/wq": ((L, D, H * hd), "fan_in"),
           "layers/attn/wk": ((L, D, K * hd), "fan_in"),
           "layers/attn/wv": ((L, D, K * hd), "fan_in"),
           "layers/attn/wo": ((L, H * hd, D), "fan_in"),
           "layers/moe/router": ((L, D, E), "fan_in", "float32"),
           "layers/moe/w_gate": ((L, E, D, F), "fan_in"),
           "layers/moe/w_up": ((L, E, D, F), "fan_in"),
           "layers/moe/w_down": ((L, E, F, D), "fan_in")}
    if cfg.moe.shared_expert:
        Fs = cfg.moe.d_ff_shared or F
        out.update({"layers/moe/shared/w_gate": ((L, D, Fs), "fan_in"),
                    "layers/moe/shared/w_up": ((L, D, Fs), "fan_in"),
                    "layers/moe/shared/w_down": ((L, Fs, D), "fan_in")})
    return out


def _router(m) -> int:
    return m["n_layers"] * m["d_model"] * m["moe"]["n_experts"]


def _experts(m) -> int:
    E, _, F, _ = _moe(m)
    return m["n_layers"] * E * 3 * m["d_model"] * F


def param_count(m: dict) -> int:
    D, V, L = m["d_model"], m["vocab"], m["n_layers"]
    _, _, _, Fs = _moe(m)
    per_layer = dense.attn_params(m) + 2 * D + 3 * D * Fs
    return L * per_layer + _router(m) + _experts(m) + 2 * V * D + D


def weight_bytes(m: dict) -> int:
    return ((param_count(m) - _router(m)) * dense.itemsize(m["param_dtype"])
            + _router(m) * dense.itemsize("float32"))


def kv_bytes_per_token(m: dict) -> int:
    return dense.kv_bytes_per_token(m)


def _token_flops(m: dict) -> int:
    """Attention projections, router, the top_k experts and the shared
    expert of every layer, for one token."""
    D = m["d_model"]
    E, k, F, Fs = _moe(m)
    return 2 * m["n_layers"] * (dense.attn_params(m) + D * E
                                + 3 * D * (k * F + Fs))


def decode_flops(m: dict, contexts) -> int:
    per_tok = _token_flops(m) + dense.logits_flops(m)
    return sum(per_tok + dense.attn_flops(m, int(c)) for c in contexts)


def prefill_flops(m: dict, lanes) -> int:
    total = 0
    for start, n, completes in lanes:
        ctx_sum = n * start + n * (n + 1) // 2
        total += (_token_flops(m) * n + dense.attn_flops(m, ctx_sum)
                  + (dense.logits_flops(m) if completes else 0))
    return total


def decode_min_bytes(m: dict, contexts) -> int:
    """Every weight but the experts, and of those the top_k of each layer
    that every lane could share, once; each lane's valid K and V once."""
    E, k, _, _ = _moe(m)
    unread = _experts(m) * (E - k) // E
    return (weight_bytes(m) - unread * dense.itemsize(m["param_dtype"])
            + sum(int(c) * kv_bytes_per_token(m) for c in contexts))
