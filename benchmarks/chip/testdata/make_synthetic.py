"""Writes synthetic.xplane.pb: a trace whose every number is known by hand.

    python3 benchmarks/chip/testdata/make_synthetic.py

(It needs TensorFlow's copy of the XPlane protobuf; the benchmark itself
reads traces with ``jax.profiler.ProfileData`` alone.)  Times in ms from the
start of ``bench.traced`` [0, 200]; host and device on one clock.

  host   gateway.step [0,30] [40,70] [80,110] [160,178] [180,198],
         submit [30,40], wait_arrival [110,160]
  device programs  jit__decode_impl [5,25] [45,65] [85,105],
                   jit__chunk_prefill_impl [162,176] [182,196]
         ops       fusion.1 [5,15] [45,55] [85,95],
                   fusion.2 [16,25] [56,65] [96,105],
                   fusion.3 [162,176] [182,196]
"""
import pathlib

from tensorflow.tsl.profiler.protobuf import xplane_pb2

BASE_NS = 5_000_000_000           # a trace starts near 0: float64 keeps ns
MS = 1_000_000_000          # picoseconds in a millisecond


def _plane(space, pid, name, events_by_line):
    plane = space.planes.add()
    plane.id, plane.name = pid, name
    names = sorted({n for evs in events_by_line.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    for n, i in ids.items():
        plane.event_metadata[i].id = i
        plane.event_metadata[i].name = n
    for lid, (line_name, evs) in enumerate(events_by_line.items(), 1):
        line = plane.lines.add()
        line.id, line.display_id, line.name = lid, lid, line_name
        line.timestamp_ns = BASE_NS
        for n, a, b in evs:
            e = line.events.add()
            e.metadata_id = ids[n]
            e.offset_ps, e.duration_ps = a * MS, (b - a) * MS


def main() -> None:
    space = xplane_pb2.XSpace()
    step = "gateway.step"
    _plane(space, 1, "/host:CPU", {"python": [
        ("bench.traced", 0, 200), (step, 0, 30), ("submit", 30, 40),
        (step, 40, 70), (step, 80, 110), ("wait_arrival", 110, 160),
        (step, 160, 178), (step, 180, 198)]})
    dec, pre = "jit__decode_impl(7)", "jit__chunk_prefill_impl(9)"
    _plane(space, 2, "/device:TPU:0", {
        "XLA Modules": [(dec, 5, 25), (dec, 45, 65), (dec, 85, 105),
                        (pre, 162, 176), (pre, 182, 196)],
        "XLA Ops": [("fusion.1", 5, 15), ("fusion.2", 16, 25),
                    ("fusion.1", 45, 55), ("fusion.2", 56, 65),
                    ("fusion.1", 85, 95), ("fusion.2", 96, 105),
                    ("fusion.3", 162, 176), ("fusion.3", 182, 196)]})
    out = pathlib.Path(__file__).resolve().parent / "synthetic.xplane.pb"
    out.write_bytes(space.SerializeToString())
    print(f"{out}: {out.stat().st_size} bytes")


if __name__ == "__main__":
    main()
