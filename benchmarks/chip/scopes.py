"""Splits the traced decode program by the engine's named scopes, and reads
the program's own host spans, from the ``.xplane.pb`` of a traced run.

The engine wraps each stage of its jitted programs in a flat
``jax.named_scope`` (``weights_unseal``, ``kv_gather``, ``kv_unseal``,
``model``, ``kv_seal``).  XLA keeps the scope in each operation's
metadata: the ``tf_op`` stat of an "XLA Ops" event's metadata reads
``jit(_decode_impl)/kv_gather/...``.  ``jax.profiler.ProfileData`` gives
event stats but not metadata stats, so this module reads the file with a
small reader of the protobuf wire format (``XSpace`` > ``XPlane`` >
``XLine`` > ``XEvent``, ``XEventMetadata``, ``XStat``).

    scope time   per scope, the device time of the outermost operations
                 that carry it, inside the ``_decode_impl`` executions that
                 start within ``bench.traced``.  An operation nested in
                 another (a ``while`` and its body) counts once, with the
                 outermost; one whose own metadata names no scope takes the
                 scope of the first operation nested in it that does.
    host spans   the program's ``Tracer.span`` annotations, which
                 ``jax.profiler`` records beside the device's operations.

Device times move onto the host clock by the shift ``trace.py`` computes.
A program without scopes or spans (one older than them) gives None, never
0.  Each file is parsed once, cached on its path and modification time.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import pathlib
import struct

from benchmarks.chip import trace as trace_lib

TRACE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"
SCOPES = ("weights_unseal", "kv_gather", "kv_unseal", "model", "kv_seal")
DECODE = "jit__decode_impl"
SPANS = ("serve_step", "sched.admit", "sched.prefill", "sched.decode",
         "engine.decode_step", "engine.chunk_prefill", "engine.close_page",
         "engine.reopen_page", "engine.renonce_page", "engine.cow_page",
         "gateway.submit", "gateway.monitor", "sessions.handshake",
         "pool.alloc", "pool.free")


@dataclasses.dataclass
class Scoped:
    decode_calls: int           # _decode_impl executions in the window
    decode_ns: float            # their device time
    scope_ns: dict              # scope -> device ns of its operations
    spans: list                 # [(name, start_ns, end_ns)] in the window,
                                # by start
    window: tuple               # (start_ns, end_ns) of bench.traced

    def per_call_ms(self, scope: str):
        """Device ms of ``scope`` per decode call; None when the program
        has no scopes or no decode ran."""
        if not self.scope_ns or not self.decode_calls:
            return None
        return self.scope_ns.get(scope, 0.0) / self.decode_calls * 1e-6


# -- protobuf wire format ---------------------------------------------------
def _varint(buf, i: int) -> tuple:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, bytes for
    a length-delimited field, raw bytes for a fixed 64 or 32 bit one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf) -> tuple:
    """XStat -> (metadata id, value): str for a string, int for a
    reference, a number otherwise."""
    mid, value = 0, None
    for f, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 7):
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
    return mid, value


def _map_entry(buf) -> tuple:
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf) -> tuple:
    """XPlane -> (name, {line name: [(metadata id, start ns, end ns)]},
    {event metadata id: (name, tf_op or None)}), keeping the lines and
    events that ``_wanted`` names."""
    name, raw_lines, raw_meta, stat_names = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            raw_lines.append(v)
        elif f == 4:
            raw_meta.append(v)
        elif f == 5:
            _, sm = _map_entry(v)
            sid, sname = 0, ""
            for g, w in _fields(sm):
                if g == 1:
                    sid = w
                elif g == 2:
                    sname = bytes(w).decode()
            stat_names[sid] = sname
    tf_op = next((i for i, s in stat_names.items() if s == "tf_op"), None)
    meta = {}
    for entry in raw_meta:
        mid, em = _map_entry(entry)
        ename, op = "", None
        for g, w in _fields(em):
            if g == 2:
                ename = bytes(w).decode("utf-8", "replace")
            elif g == 5 and tf_op is not None:
                sid, value = _stat(w)
                if sid == tf_op:    # a string, or a reference to one
                    op = stat_names.get(value) if isinstance(
                        value, int) else value
        meta[mid] = (ename, op)
    device = bool(trace_lib.DEVICE.match(name))
    keep = {mid for mid, (ename, _) in meta.items()
            if device or _wanted_span(ename)}
    lines = {}
    for raw in raw_lines:
        lname, ts_ns, events = "", 0, []
        for g, w in _fields(raw):
            if g == 2:
                lname = bytes(w).decode()
            elif g == 3:
                ts_ns = _signed(w)
            elif g == 4:
                events.append(w)
        if device and lname not in ("XLA Modules", "XLA Ops"):
            continue
        out = lines.setdefault(lname, [])
        base_ps = ts_ns * 1000
        for ev in events:
            key, i = _varint(ev, 0)
            if key == 8 and _varint(ev, i)[0] not in keep:
                continue                # field 1, an event not asked for
            mid = off = dur = 0
            for g, w in _fields(ev):
                if g == 1:
                    mid = w
                elif g == 2:
                    off = _signed(w)
                elif g == 3:
                    dur = _signed(w)
            start = base_ps + off
            out.append((mid, start / 1000, (start + dur) / 1000))
    return name, lines, meta


def _wanted_span(name: str) -> bool:
    return (name in SPANS or name == trace_lib.WINDOW
            or name.startswith("PjitFunction("))


def scope_of(tf_op) -> str | None:
    """'jit(_decode_impl)/kv_gather/gather:' -> 'kv_gather'; None when the
    first path element below the jitted programs names no scope."""
    if not tf_op:
        return None
    for part in tf_op.split(":", 1)[0].split("/"):
        if not part.startswith("jit("):
            return part if part in SCOPES else None
    return None


# -- reduction ----------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _parse(path: str, _mtime_ns: int) -> Scoped:
    data = memoryview(pathlib.Path(path).read_bytes())
    planes = [_plane(v) for f, v in _fields(data) if f == 1]
    host, pjit, devices = [], [], []
    for name, lines, meta in planes:
        if trace_lib.DEVICE.match(name):
            devices.append((lines, meta))
            continue
        for events in lines.values():
            for mid, a, b in events:
                ename = meta.get(mid, ("", None))[0]
                if ename in SPANS or ename == trace_lib.WINDOW:
                    host.append((ename, a, b))
                elif ename.startswith("PjitFunction("):
                    pjit.append(("jit_" + ename[13:-1], a, b))
    marks = [(a, b) for n, a, b in host if n == trace_lib.WINDOW]
    if not marks or not devices:
        raise ValueError(f"{path}: no {trace_lib.WINDOW} span or no TPU "
                         "device plane")
    t0, t1 = min(a for a, _ in marks), max(b for _, b in marks)
    dispatches = trace_lib._outermost(pjit)
    calls, decode_ns, scope_ns = 0, 0.0, {}
    for lines, meta in devices:
        mods = [(trace_lib._program_name(meta.get(mid, ("", None))[0]), a, b)
                for mid, a, b in lines.get("XLA Modules", [])]
        shift = trace_lib.clock_shift([(n, a) for n, a, _ in mods],
                                      dispatches)
        runs = sorted((a + shift, b + shift) for n, a, b in mods
                      if n == DECODE and t0 <= a + shift < t1)
        calls += len(runs)
        decode_ns += sum(b - a for a, b in runs)
        ops = sorted(((a + shift, b + shift, meta.get(mid, ("", None))[1])
                      for mid, a, b in lines.get("XLA Ops", [])),
                     key=lambda op: (op[0], -op[1]))  # outer op first
        _charge(runs, ops, scope_ns)
    spans = sorted(((n, a, b) for n, a, b in host
                    if n != trace_lib.WINDOW and b > t0 and a < t1),
                   key=lambda span: span[1])
    return Scoped(decode_calls=calls, decode_ns=decode_ns, scope_ns=scope_ns,
                  spans=spans, window=(t0, t1))


def _charge(runs: list, ops: list, scope_ns: dict) -> None:
    """Adds to ``scope_ns`` the time of each outermost op that starts inside
    one of ``runs`` [(start, end)], under its scope."""
    starts = [a for a, _ in runs]
    outer = None                        # [start, end, scope]
    for a, b, tf_op in ops + [(float("inf"), float("inf"), None)]:
        if outer is not None and a < outer[1]:
            if outer[2] is None:
                outer[2] = scope_of(tf_op)
            continue
        if outer is not None and outer[2] is not None:
            i = bisect.bisect_right(starts, outer[0]) - 1
            if i >= 0 and outer[0] < runs[i][1]:
                scope_ns[outer[2]] = (scope_ns.get(outer[2], 0.0)
                                      + outer[1] - outer[0])
        outer = [a, b, scope_of(tf_op)]


def read(path) -> Scoped:
    """The reduction of one ``.xplane.pb``."""
    path = pathlib.Path(path)
    return _parse(str(path), path.stat().st_mtime_ns)


def of_run(run) -> Scoped | None:
    """The reduction of the trace a traced run wrote; None for a run with
    no trace."""
    if getattr(run, "trace", None) is None:
        return None
    files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    return read(files[-1]) if files else None
