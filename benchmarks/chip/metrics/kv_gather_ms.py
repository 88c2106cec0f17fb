"""Device ms per decode call of the engine's ``kv_gather`` scope: the
page-table gathers of every lane's sealed pages, tags, keys and nonces,
their relayout to [L, B, T, K, hd] and the length mask.  From the named
scopes in the trace (scopes.py)."""
from benchmarks.chip import scopes


def read(run):
    scoped = scopes.of_run(run)
    return scoped.per_call_ms("kv_gather") if scoped else None
