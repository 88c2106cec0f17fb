"""Writes nested.xplane.pb: a trace of scopes nested in the engine's own,
whose every number is known by hand.

    python3 benchmarks/chip/testdata/make_nested.py

(It needs TensorFlow's copy of the XPlane protobuf; scopes.py reads traces
with a wire-format reader of its own.)  Times in ms from the start of
``bench.traced`` [0, 100]; host and device on one clock.  Each operation's
metadata carries its path as the ``tf_op`` stat, as XLA writes it.

  host   sched.decode [10,40] [50,80], moe.route [12,13] [52,53],
         other.span [60,61]
  device programs  jit__decode_impl [10,40] [50,80],
                   jit__chunk_prefill_impl [85,95]
         ops of the decode starting at t (t = 10, 50):
           fusion.w [t,t+5]       .../weights_unseal/xor
           while.1  [t+5,t+25]    .../model/while            (the layer loop)
             fusion.a [t+6,t+10]    .../model/while/body/attn/dot_general
             fusion.e [t+10,t+18]   .../model/while/body/experts/dot_general
             while.2  [t+18,t+24]   .../model/while/body/experts/while
               gather.1 [t+19,t+23]   .../experts/while/body/gather
           fusion.s [t+25,t+28]   .../kv_seal/scatter
         ops of the prefill: fusion.p [85,95]
                                  .../model/while/body/experts/dot_general

So per decode call: weights_unseal 5, model 20 (the whole loop), kv_seal 3,
and, read by name, ``attn`` 4 and ``experts`` 14 (fusion.e and while.2;
gather.1 lies inside while.2 and counts with it; the prefill's is in no
decode call).
"""
import pathlib

from tensorflow.tsl.profiler.protobuf import xplane_pb2

BASE_NS = 5_000_000_000           # a trace starts near 0: float64 keeps ns
MS = 1_000_000_000                # picoseconds in a millisecond
DEC = "jit(_decode_impl)"


def _decode_ops(t):
    loop = f"{DEC}/model/while"
    return [("fusion.w", f"{DEC}/weights_unseal/xor", t, t + 5),
            ("while.1", loop, t + 5, t + 25),
            ("fusion.a", f"{loop}/body/attn/dot_general", t + 6, t + 10),
            ("fusion.e", f"{loop}/body/experts/dot_general", t + 10, t + 18),
            ("while.2", f"{loop}/body/experts/while", t + 18, t + 24),
            ("gather.1", f"{loop}/body/experts/while/body/gather", t + 19,
             t + 23),
            ("fusion.s", f"{DEC}/kv_seal/scatter", t + 25, t + 28)]


def _plane(space, pid, name, events_by_line, tf_op=None):
    """events_by_line: {line: [(name, start ms, end ms)]}; tf_op: {event
    name: path} written as each event metadata's ``tf_op`` stat."""
    plane = space.planes.add()
    plane.id, plane.name = pid, name
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = "tf_op"
    names = sorted({n for evs in events_by_line.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    for n, i in ids.items():
        plane.event_metadata[i].id = i
        plane.event_metadata[i].name = n
        if tf_op and n in tf_op:
            stat = plane.event_metadata[i].stats.add()
            stat.metadata_id, stat.str_value = 1, tf_op[n]
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
    _plane(space, 1, "/host:CPU", {"python": [
        ("bench.traced", 0, 100), ("sched.decode", 10, 40),
        ("moe.route", 12, 13), ("sched.decode", 50, 80),
        ("moe.route", 52, 53), ("other.span", 60, 61)]})
    ops = _decode_ops(10) + _decode_ops(50) + [
        ("fusion.p", "jit(_chunk_prefill_impl)/model/while/body/experts/"
         "dot_general", 85, 95)]
    dec, pre = "jit__decode_impl(7)", "jit__chunk_prefill_impl(9)"
    _plane(space, 2, "/device:TPU:0", {
        "XLA Modules": [(dec, 10, 40), (dec, 50, 80), (pre, 85, 95)],
        "XLA Ops": [(n, a, b) for n, _, a, b in ops]},
        tf_op={n: op for n, op, _, _ in ops})
    out = pathlib.Path(__file__).resolve().parent / "nested.xplane.pb"
    out.write_bytes(space.SerializeToString())
    print(f"{out}: {out.stat().st_size} bytes")


if __name__ == "__main__":
    main()
