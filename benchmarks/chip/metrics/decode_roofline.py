"""The decode program's share of its roofline, in %: for each traced
decode call the least time the chip could take (the weights once, each
active lane's valid K and V once, the new slot written; or its model
operations, if they bind), summed, over the decode programs' device time."""
from benchmarks.chip import costs


def read(run):
    n, ns = run.trace.program_ns("_decode_impl") if run.trace else (0, 0)
    calls = [s.decode for s in run.traced_steps if s.decode]
    if not n or not calls:
        return None
    best = sum(costs.roofline_s(costs.decode_flops(run.model, c),
                                costs.decode_min_bytes(run.model, c),
                                run.peak) for c in calls)
    return 100.0 * best / (ns * 1e-9)
