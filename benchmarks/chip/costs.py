"""Operations and bytes of the engine's calls, from shapes alone.

Each count is the family's: the module families/<family>.py that the model
block's ``family`` names (spec.family) counts its own layers, so a model
with experts is charged the experts a token passes through, not a dense
layer's.  Counts are of useful work: the valid tokens of active lanes,
attention over each lane's valid context, logits only where a token is
taken.  Padding, idle lanes and the sealing's own integer work are not
counted, so a share of a peak read from these is the share of the chip
spent on the model.
"""
from __future__ import annotations

from benchmarks.chip import spec


def _family(m: dict):
    return spec.family(m["family"])


def param_count(m: dict) -> int:
    return _family(m).param_count(m)


def weight_bytes(m: dict) -> int:
    return _family(m).weight_bytes(m)


def kv_bytes_per_token(m: dict) -> int:
    return _family(m).kv_bytes_per_token(m)


def decode_flops(m: dict, contexts) -> int:
    """One decode call: each active lane's token at its context length
    (positions attended, the new one included), with its logits."""
    return _family(m).decode_flops(m, contexts)


def prefill_flops(m: dict, lanes) -> int:
    """One chunk call: lanes [(start, n, completes)], each prefilling n
    prompt tokens from position ``start``, causally; a lane whose prompt
    completes takes the logits of its last token."""
    return _family(m).prefill_flops(m, lanes)


def decode_min_bytes(m: dict, contexts) -> int:
    """The least a decode call must move: the weights it reads once, each
    active lane's valid K and V once, and the new token's K and V written."""
    return _family(m).decode_min_bytes(m, contexts)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: compute or memory, whichever
    binds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
