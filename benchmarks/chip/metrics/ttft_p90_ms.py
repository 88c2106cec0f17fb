"""90th percentile (nearest rank), over every request due in the window,
of the time from when it was due to when its first token came back.  A
request with no first token when the window closes enters at its wait so
far, so a stall stays in the tail."""
from benchmarks.chip.stats import percentile


def read(run):
    w0, w1 = run.window
    waits = []
    for rec in run.due:
        if not w0 <= rec.t_due < w1:
            continue
        first = rec.emits[0] if rec.emits and rec.emits[0] <= w1 else w1
        waits.append(first - rec.t_due)
    return percentile(waits, 0.90) * 1e3 if waits else None
