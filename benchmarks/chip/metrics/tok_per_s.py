"""Output tokens that came back in the window, over the window."""


def read(run):
    w0, w1 = run.window
    n = sum(1 for rec in run.due for t in rec.emits if w0 <= t <= w1)
    return n / (w1 - w0) if n else None
