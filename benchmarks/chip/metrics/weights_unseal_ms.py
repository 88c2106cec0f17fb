"""Device ms per decode call of the engine's ``weights_unseal`` scope: the
in-graph unseal of the whole weight tree (keystream and MAC verify of every
leaf).  From the named scopes in the trace (scopes.py)."""
from benchmarks.chip import scopes


def read(run):
    scoped = scopes.of_run(run)
    return scoped.per_call_ms("weights_unseal") if scoped else None
