"""Decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the one
with the most served tokens and one that decoded in each decode lane, goes
through the reference: once over each prompt with its served tokens.  At
each served position the reference's logits give the gap by which the
served token lies below the reference's best token.  The widest gap over
the sample is compared with the cell's limit.  Served tokens are greedy,
so a sound run's gaps come from rounding alone.

The control (``quant``) puts the reference, computed in a lower precision,
in the program's place: at each position of the same prompts and tokens it
reads the gap of the token that the lower precision puts first.
"""
from __future__ import annotations

import importlib
import math

import jax.numpy as jnp
import numpy as np

from . import weights

SAMPLE_TOKENS = 400     # served tokens a sample holds at least
SAMPLE_MAX = 8          # requests a sample holds at most


def sample(records, seed: int) -> list:
    """Finished requests to check: the longest; then, in an order of the
    seed's, one that decoded in each decode lane (slot) no request picked
    so far decoded in, so that a fault confined to one lane is caught;
    then others until SAMPLE_TOKENS served tokens or SAMPLE_MAX
    requests."""
    done = sorted((r for r in records if r.status == "done"),
                  key=lambda r: r.req.index)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.req.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 99]))
    order = [rest[i] for i in rng.permutation(len(rest))]
    picked, covered, later = [longest], set(longest.lanes), []
    for r in order:
        if r.lanes - covered:
            picked.append(r)
            covered |= r.lanes
        else:
            later.append(r)
    n_tok = sum(len(r.tokens) for r in picked)
    for r in later:
        if n_tok >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX:
            break
        picked.append(r)
        n_tok += len(r.tokens)
    return picked


def _reference(config: dict):
    mod = importlib.import_module(
        f"{__package__}.references.{config['reference']}")
    return mod, mod.static_config(config["model"])


def shapes(mix: dict) -> dict:
    """The reference's fixed shapes for a mix: the longest sequence and the
    most served tokens it can hold."""
    return {"max_len": mix["prompt"]["max"] + mix["output"]["max"],
            "max_new": mix["output"]["max"]}


def padded_len(n: int, block: int) -> int:
    return block * max(1, math.ceil(n / block))


def gaps(config: dict, cfg, seed: int, picked, max_len: int,
         max_new: int, quant: str | None = None) -> list:
    """Per request, the gap of every served position, in serving order.

    quant=None reads the served tokens' gaps; quant="int8"/"fp8" reads the
    gaps of the tokens the reference in that precision puts first (the
    control).  Shapes are fixed by the cell (max_len tokens, max_new rows),
    so one compiled reference serves every request."""
    ref, scfg = _reference(config)
    params = weights.generate(cfg, seed)
    S = padded_len(max_len, ref.Q_BLOCK)
    out = []
    for rec in picked:
        served = np.asarray(rec.tokens, np.int64)
        seq = np.concatenate([rec.req.prompt, served[:-1]]).astype(np.int32)
        plen = len(rec.req.prompt)
        rows = plen - 1 + np.arange(len(served))
        toks = np.zeros((S,), np.int32)
        toks[:len(seq)] = seq
        rows_p = np.zeros((max_new,), np.int32)
        rows_p[:len(rows)] = rows
        args = (params, jnp.asarray(toks), jnp.asarray(rows_p))
        ref_l = np.asarray(ref.logits_at(*args, cfg=scfg))[:len(rows)]
        if quant is None:
            pick = served
        else:
            ctl = np.asarray(ref.logits_at(*args, cfg=scfg, quant=quant))
            pick = ctl[:len(rows)].argmax(-1)
        best = ref_l.max(-1)
        vocab = ref_l.shape[1]
        at = ref_l[np.arange(len(rows)), np.clip(pick, 0, vocab - 1)]
        got = np.where((pick >= 0) & (pick < vocab), at, -np.inf)
        out.append((best - got).astype(np.float64))
    del params
    return out
