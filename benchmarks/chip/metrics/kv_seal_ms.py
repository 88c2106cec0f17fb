"""Device ms per decode call of the engine's ``kv_seal`` scope: the seal of
the new token slot, its slice tag and the scatters into the pool.  From the
named scopes in the trace (scopes.py)."""
from benchmarks.chip import scopes


def read(run):
    scoped = scopes.of_run(run)
    return scoped.per_call_ms("kv_seal") if scoped else None
