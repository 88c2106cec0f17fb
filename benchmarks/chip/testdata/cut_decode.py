"""Writes decode_cut.xplane.pb: one ``_decode_impl`` execution of the chat
cell, cut from the trace of a ``run.py --trace 1`` run on a TPU v5e.

    python3 benchmarks/chip/testdata/cut_decode.py <run's .xplane.pb>

(It needs TensorFlow's copy of the XPlane protobuf; scopes.py reads traces
with its own reader.)  Kept: on the device plane, the "XLA Modules" event
of the first decode execution that starts inside ``bench.traced`` and the
"XLA Ops" events inside it, their metadata cut to the operation's name and
its ``tf_op`` stat; on the host, ``bench.traced`` and the program's spans
that overlap that execution, with their args.  Every time is as recorded.
"""
import pathlib
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

SPANS = ("serve_step", "sched.decode", "engine.decode_step")


def _copy_line(src_plane, src_line, dst_plane, keep, meta_ids):
    line = dst_plane.lines.add()
    line.id, line.display_id = src_line.id, src_line.display_id
    line.name, line.timestamp_ns = src_line.name, src_line.timestamp_ns
    for e in src_line.events:
        if keep(src_plane.event_metadata[e.metadata_id].name,
                src_line.timestamp_ns * 1000 + e.offset_ps, e.duration_ps):
            line.events.add().CopyFrom(e)
            meta_ids.add(e.metadata_id)
    return line


def main(path: str) -> None:
    src = xplane_pb2.XSpace()
    src.ParseFromString(pathlib.Path(path).read_bytes())
    host = next(p for p in src.planes if p.name == "/host:CPU")
    dev = next(p for p in src.planes if p.name == "/device:TPU:0")
    w0 = min(ln.timestamp_ns * 1000 + e.offset_ps for ln in host.lines
             for e in ln.events
             if host.event_metadata[e.metadata_id].name == "bench.traced")
    mods = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    a, dur = min((mods.timestamp_ns * 1000 + e.offset_ps, e.duration_ps)
                 for e in mods.events
                 if dev.event_metadata[e.metadata_id].name.startswith(
                     "jit__decode_impl(")
                 and mods.timestamp_ns * 1000 + e.offset_ps >= w0)
    b = a + dur

    out = xplane_pb2.XSpace()
    for plane, lines, keep in (
            (host, host.lines, lambda n, s, d: n == "bench.traced"
             or (n in SPANS and s < b and s + d > a)),
            (dev, [ln for ln in dev.lines
                   if ln.name in ("XLA Modules", "XLA Ops")],
             lambda n, s, d: a <= s and s + d <= b)):
        dst = out.planes.add()
        dst.id, dst.name = plane.id, plane.name
        meta_ids = set()
        for ln in lines:
            _copy_line(plane, ln, dst, keep, meta_ids)
        stat_ids = set()
        for e in (e for ln in dst.lines for e in ln.events):
            stat_ids |= {s.metadata_id for s in e.stats}
            stat_ids |= {s.ref_value for s in e.stats if s.ref_value}
        tf_op = [k for k, v in plane.stat_metadata.items()
                 if v.name == "tf_op"]
        for mid in meta_ids:
            m = plane.event_metadata[mid]
            d = dst.event_metadata[mid]
            d.id, d.name = m.id, m.name.split(" = ")[0]
            for s in m.stats:
                if s.metadata_id in tf_op:
                    d.stats.add().CopyFrom(s)
                    stat_ids.add(s.metadata_id)
        for sid in stat_ids:
            dst.stat_metadata[sid].CopyFrom(plane.stat_metadata[sid])
    target = pathlib.Path(__file__).resolve().parent / "decode_cut.xplane.pb"
    target.write_bytes(out.SerializeToString())
    print(f"{target}: {target.stat().st_size} bytes; decode [{a}, {b}] ps")


if __name__ == "__main__":
    main(sys.argv[1])
