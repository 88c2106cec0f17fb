"""Device ms per decode call of the engine's ``model`` scope: the embedding,
the layers, the logits, the argmax and the poison gating.  From the named
scopes in the trace (scopes.py)."""
from benchmarks.chip import scopes


def read(run):
    scoped = scopes.of_run(run)
    return scoped.per_call_ms("model") if scoped else None
