"""Device idle time inside the program's ``sessions.handshake`` spans (a
new tenant's attestation and key exchange, on the host) over the traced
window, in %.  0 when no tenant arrived in the window; nothing when the
program records no spans."""
from benchmarks.chip import scopes


def read(run):
    scoped = scopes.of_run(run)
    if not scoped or not scoped.spans:
        return None
    t0, t1 = scoped.window
    idle = 0.0
    for name, a, b in scoped.spans:
        if name == "sessions.handshake":
            a, b = max(a, t0), min(b, t1)
            idle += (b - a) - run.trace.busy_within(a, b)
    return 100.0 * idle / (t1 - t0)
