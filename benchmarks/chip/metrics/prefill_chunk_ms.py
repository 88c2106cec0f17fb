"""Mean device time of one chunk-prefill program (the engine's jitted
``_chunk_prefill_impl``), from the trace."""


def read(run):
    n, ns = (run.trace.program_ns("_chunk_prefill_impl") if run.trace
             else (0, 0))
    return ns / n * 1e-6 if n else None
