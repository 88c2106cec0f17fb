"""Records the small trace that tests/benchmark checks scopes.py against.

    python3 benchmarks/chip/record_scoped_trace.py    (on a machine with a TPU)

Inside one ``bench.traced`` annotation it runs three ``gateway.step``
spans that each execute a jitted ``_decode_impl`` once, then a
``sessions.handshake`` span, entered through the program's own
``Tracer.span``, over a 50 ms sleep with nothing on the device.  The
decode program's operations sit under two named scopes, as the engine's
do: ``kv_gather``, a gather of 32 pages out of a u16[64, 40, 16, 8, 64]
pool that XLA expands to a ``while`` loop, and ``model``, a matmul; a last
add lies outside both.  The trace goes to
benchmarks/chip/testdata/scoped.xplane.pb.
"""
from __future__ import annotations

import os
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
OUT = HERE / "testdata" / "scoped.xplane.pb"
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_scoped_trace.py: no TPU")
    from repro.obs import Tracer

    @jax.jit
    def _decode_impl(pool, pages, x, w):
        with jax.named_scope("kv_gather"):
            got = pool[pages]
        with jax.named_scope("model"):
            y = jnp.tanh(x @ w)
        return got, y + 1.0

    pool = jnp.ones((64, 40, 16, 8, 64), jnp.uint16)
    pages = jnp.arange(32, dtype=jnp.int32) * 2
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(_decode_impl(pool, pages, x, x))
    tracer = Tracer(enabled=False)
    with tempfile.TemporaryDirectory(dir=HERE / "testdata") as tmp:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.traced"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("gateway.step"):
                    jax.block_until_ready(_decode_impl(pool, pages, x, x))
            with tracer.span("sessions.handshake", args={"tenant": "t0"}):
                time.sleep(0.05)
        jax.profiler.stop_trace()
        src = sorted(pathlib.Path(tmp).glob("plugins/profile/*/*.xplane.pb"))
        shutil.copy(src[-1], OUT)
    print(f"{OUT}: {OUT.stat().st_size} bytes")


if __name__ == "__main__":
    main()
