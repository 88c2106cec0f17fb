"""Model operations of the valid tokens over the device time of the decode
and prefill programs times the chip's bf16 peak, in %: the whole step's
share of the peak.  Padding, idle lanes and the sealing's integer work do
not count."""
from benchmarks.chip import costs


def read(run):
    if not run.trace:
        return None
    _, dec = run.trace.program_ns("_decode_impl")
    _, pre = run.trace.program_ns("_chunk_prefill_impl")
    flops = sum(costs.decode_flops(run.model, s.decode)
                + costs.prefill_flops(run.model, s.prefill)
                for s in run.traced_steps)
    if not flops or not dec + pre:
        return None
    return 100.0 * flops / ((dec + pre) * 1e-9
                            * run.peak["bf16_flops_per_s"])
