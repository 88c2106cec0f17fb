"""Records the small trace that tests/benchmark checks trace.py against.

    python3 benchmarks/chip/record_trace.py    (on a machine with a TPU)

Inside one ``bench.traced`` annotation it runs, in this order: three
``gateway.step`` spans that each execute a jitted ``_decode_impl`` once,
a 50 ms ``wait_arrival`` sleep with nothing on the device, and two
``gateway.step`` spans that each execute a jitted ``_chunk_prefill_impl``
once.  The trace goes to benchmarks/chip/testdata/small.xplane.pb.
"""
from __future__ import annotations

import os
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
OUT = HERE / "testdata" / "small.xplane.pb"


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace.py: no TPU")

    @jax.jit
    def _decode_impl(x):
        return jnp.tanh(x @ x) + 1.0

    @jax.jit
    def _chunk_prefill_impl(x):
        return jnp.tanh(x @ x @ x) * 2.0

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((_decode_impl(x), _chunk_prefill_impl(x)))
    with tempfile.TemporaryDirectory(dir=HERE / "testdata") as tmp:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.traced"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("gateway.step"):
                    jax.block_until_ready(_decode_impl(x))
            with jax.profiler.TraceAnnotation("wait_arrival"):
                time.sleep(0.05)
            for _ in range(2):
                with jax.profiler.TraceAnnotation("gateway.step"):
                    jax.block_until_ready(_chunk_prefill_impl(x))
        jax.profiler.stop_trace()
        src = sorted(pathlib.Path(tmp).glob("plugins/profile/*/*.xplane.pb"))
        shutil.copy(src[-1], OUT)
    print(f"{OUT}: {OUT.stat().st_size} bytes")


if __name__ == "__main__":
    main()
