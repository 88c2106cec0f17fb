"""The one traffic generator: requests drawn from a mix file and a seed.

Every seed gets the same work in the same order.  Lengths and gaps between
arrivals are drawn in blocks of ``block`` requests; each block holds the
distribution's quantiles at (i + 0.5) / block, in one fixed order
(``ORDER_SEED``'s).  The seed draws the token ids and which tenant sends each
request.  The order is not the seed's because a window of a cell above its
capacity serves a stretch of the stream that the order decides: with an
order of the seed's, the tokens a 51 s window completed on one TPU v5e changed
by about 1 % from seed to seed, as much as the bound that the cell holds.

A mix names its loop:

  open    requests arrive on a schedule, whether or not earlier ones have
          finished (independent users); the cell gives ``rate_per_s``.
  closed  a fixed set of clients, each sending its next request when the
          last one has finished (a worker pool); the cell gives ``clients``.

Lengths are ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
``{"dist": "uniform", "min", "max"}``, in tokens, bounds included.  Token
ids are uniform over the vocabulary.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

MIN_STEP_S = 0.02       # no gateway step of a served model is shorter
STREAMS = {"prompt_len": 1, "output_len": 2, "gaps": 3, "tenants": 4,
           "tokens": 5}
ORDER_SEED = 0          # orders the lengths and gaps of every seed's stream


@dataclasses.dataclass
class Request:
    index: int
    tenant: str
    prompt: np.ndarray          # int32 token ids
    max_new: int
    t_due: float | None         # seconds after the stream's start (open)


def _rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, STREAMS[stream]]))


def _quantiles(n: int, block: int, rng: np.random.Generator) -> np.ndarray:
    """n stratified uniforms: each block of ``block`` holds the quantiles
    (i + 0.5) / block once, in the order ``rng`` draws."""
    base = (np.arange(block) + 0.5) / block
    blocks = [rng.permutation(base) for _ in range(-(-n // block))]
    return np.concatenate(blocks)[:n]


def length_ppf(dist: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = np.rint(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
    elif dist["dist"] == "uniform":
        v = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def generate(mix: dict, seed: int, vocab: int, n: int,
             rate_per_s: float | None = None) -> list[Request]:
    """The first n requests of the stream this mix and seed give."""
    block = int(mix["block"])
    plen = length_ppf(mix["prompt"], _quantiles(
        n, block, _rng(ORDER_SEED, "prompt_len")))
    olen = length_ppf(mix["output"], _quantiles(
        n, block, _rng(ORDER_SEED, "output_len")))
    n_ten = int(mix["tenants"])
    t_rng = _rng(seed, "tenants")
    tenants = np.concatenate([t_rng.permutation(n_ten)
                              for _ in range(-(-n // n_ten))])[:n]
    if mix["loop"] == "open":
        if not rate_per_s or rate_per_s <= 0:
            raise ValueError("an open loop needs a rate_per_s above 0")
        u = _quantiles(n, block, _rng(ORDER_SEED, "gaps"))
        t_due = np.cumsum(-np.log1p(-u) / rate_per_s)
    elif mix["loop"] == "closed":
        t_due = [None] * n
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    tok = _rng(seed, "tokens")
    return [Request(index=i, tenant=f"tenant-{int(tenants[i])}",
                    prompt=tok.integers(0, vocab, int(plen[i]),
                                        dtype=np.int32),
                    max_new=int(olen[i]),
                    t_due=None if t_due[i] is None else float(t_due[i]))
            for i in range(n)]


def stream_length(mix: dict, seconds: float, rate_per_s: float | None,
                  clients: int) -> int:
    """How many requests a stream of ``seconds`` can use, with room: an open
    loop sends rate x seconds; a closed loop at most one request per client
    per step, and every request holds its client for at least
    (output min) steps."""
    block = int(mix["block"])
    if mix["loop"] == "open":
        n = rate_per_s * seconds * 1.25 + block
    else:
        per_client = seconds / (MIN_STEP_S * int(mix["output"]["min"]))
        n = clients * (per_client + 2)
    return int(math.ceil(n / block) * block)
