"""95th percentile (nearest rank) of every gap between consecutive tokens
of one request that came back in the window, on the client's clock.  A
request still decoding when the window closes adds the gap it has waited
so far."""
from benchmarks.chip.stats import percentile


def read(run):
    w0, w1 = run.window
    gaps = []
    for rec in run.due:
        inside = [t for t in rec.emits if w0 <= t <= w1]
        gaps += [b - a for a, b in zip(inside, inside[1:])]
        if inside and (rec.t_done is None or rec.t_done > w1):
            gaps.append(w1 - inside[-1])
    return percentile(gaps, 0.95) * 1e3 if gaps else None
