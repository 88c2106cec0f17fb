"""Device ms per decode call of the engine's ``kv_unseal`` scope: the page
keystream, the MAC verify of closed and open pages and each sequence's
verdict.  From the named scopes in the trace (scopes.py)."""
from benchmarks.chip import scopes


def read(run):
    scoped = scopes.of_run(run)
    return scoped.per_call_ms("kv_unseal") if scoped else None
