"""Mean over traced steps of the wall time of the benchmark's
``gateway.step`` annotation less the device-busy time inside it: the host
work of the gateway, scheduler and sessions between and around programs."""


def read(run):
    if not run.trace:
        return None
    t0, t1 = run.trace.window
    spans = [(a, b) for name, a, b in run.trace.host
             if name == "gateway.step" and t0 <= a and b <= t1]
    if not spans:
        return None
    host = [(b - a) - run.trace.busy_within(a, b) for a, b in spans]
    return sum(host) / len(host) * 1e-6
