"""Mean device time of one decode program (the engine's jitted
``_decode_impl``), from the trace."""


def read(run):
    n, ns = run.trace.program_ns("_decode_impl") if run.trace else (0, 0)
    return ns / n * 1e-6 if n else None
