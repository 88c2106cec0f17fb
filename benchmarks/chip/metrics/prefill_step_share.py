"""Share of the window's gateway steps that ran a prefill chunk, from the
gateway registry's ``prefill_chunks`` and ``steps`` (reset at the window's
start)."""


def read(run):
    steps = run.counters.get("steps", 0)
    return 100.0 * run.counters["prefill_chunks"] / steps if steps else None
