"""Reduces one profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are ``/device:TPU:<n>``.  On each, the "XLA Modules" line
holds one event per program execution and the "XLA Ops" line one per
operation.  Host planes carry the benchmark's ``TraceAnnotation`` spans
(``gateway.step``, ``submit``, ``wait_arrival`` and ``bench.traced``, which
marks the traced window) and one ``PjitFunction(<program>)`` span per
dispatch.  The two clocks can disagree by a millisecond or two (a trace
recorded on a v5e had programs start up to 1.41 ms before the host
dispatched them), so the device events are shifted by the least amount
that puts no program's start before its dispatch: the k-th execution of a
program is paired with its k-th dispatch.

    busy     union of the operation intervals inside the window, averaged
             over the devices
    programs per program name: executions and device seconds
    ops      per "program/op": device seconds
    gaps     every idle interval inside the window, labelled by the
             innermost host annotation that covers its middle
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

WINDOW = "bench.traced"
LABELS = ("gateway.step", "submit", "wait_arrival")
DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Reduced:
    window: tuple               # (start_ns, end_ns)
    n_devices: int
    busy_ns: float              # averaged over devices
    programs: dict              # name -> [count, ns]
    ops: dict                   # "program/op" -> ns
    gaps: list                  # [(start_ns, end_ns, label)]
    host: list                  # [(name, start_ns, end_ns)]
    busy_intervals: list        # merged [(start, end)] of device 0

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def program_ns(self, pattern: str) -> tuple:
        """(executions, device ns) of the programs whose name holds
        ``pattern``."""
        n = t = 0
        for name, (c, ns) in self.programs.items():
            if pattern in name:
                n, t = n + c, t + ns
        return n, t

    def busy_within(self, t0: float, t1: float) -> float:
        """Device-0 busy ns inside [t0, t1]."""
        iv = self.busy_intervals
        i = max(0, bisect.bisect_right([a for a, _ in iv], t0) - 1)
        total = 0.0
        for a, b in iv[i:]:
            if a >= t1:
                break
            total += max(0.0, min(b, t1) - max(a, t0))
        return total

    def top_ops(self, n: int = 10) -> list:
        return [[k, v * 1e-9] for k, v in sorted(
            self.ops.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_label(self, n: int = 10) -> list:
        tot = collections.Counter()
        for a, b, label in self.gaps:
            tot[label] += b - a
        return [[k, v * 1e-9] for k, v in tot.most_common(n)]


def _program_name(name: str) -> str:
    """'jit__decode_impl(123)' -> 'jit__decode_impl'."""
    return re.sub(r"\(\d+\)$", "", name)


def _merge(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def _clip(intervals, t0, t1) -> list:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def clock_shift(modules, dispatches) -> float:
    """ns to add to device times so that no execution starts before its
    dispatch.  modules: [(program, start)] of one device; dispatches:
    [(program, start)] of the host's outermost PjitFunction spans.  Pairs
    the k-th execution of a program with its k-th dispatch, for programs
    whose counts agree."""
    runs, calls = (collections.defaultdict(list) for _ in range(2))
    for name, a in sorted(modules, key=lambda x: x[1]):
        runs[name].append(a)
    for name, a in sorted(dispatches, key=lambda x: x[1]):
        calls[name].append(a)
    lead = [h - d for name, starts in runs.items()
            if len(calls.get(name, ())) == len(starts)
            for h, d in zip(calls[name], starts)]
    return max([0.0] + lead)


def _outermost(spans) -> list:
    """(program, start) of spans not nested in an earlier one."""
    out, end = [], -1.0
    for name, a, b in sorted(spans, key=lambda x: x[1]):
        if a >= end:
            out.append((name, a))
            end = b
    return out


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host, dev_lines, pjit = [], [], []
    for plane in pd.planes:
        if DEVICE.match(plane.name):
            lines = {line.name: [(e.name, e.start_ns, e.end_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in ("XLA Modules", "XLA Ops")}
            dev_lines.append(lines)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in LABELS or e.name == WINDOW:
                        host.append((e.name, e.start_ns, e.end_ns))
                    elif e.name.startswith("PjitFunction("):
                        pjit.append(("jit_" + e.name[13:-1], e.start_ns,
                                     e.end_ns))
    if not dev_lines:
        raise ValueError(f"{path}: no TPU device plane")
    dispatches = _outermost(pjit)
    for d in dev_lines:
        shift = clock_shift([(_program_name(n), a) for n, a, _ in
                             d.get("XLA Modules", [])], dispatches)
        for key in d:
            d[key] = [(n, a + shift, b + shift) for n, a, b in d[key]]
    marks = [(a, b) for name, a, b in host if name == WINDOW]
    if marks:
        window = (min(a for a, _ in marks), max(b for _, b in marks))
    else:
        evs = [(a, b) for d in dev_lines for ln in d.values()
               for _, a, b in ln]
        window = (min(a for a, _ in evs), max(b for _, b in evs))
    t0, t1 = window

    programs = collections.defaultdict(lambda: [0, 0.0])
    ops = collections.Counter()
    busy_total = 0.0
    merged0 = None
    for d in dev_lines:
        mods = sorted((a, b, _program_name(n))
                      for n, a, b in d.get("XLA Modules", [])
                      if t0 <= a < t1)
        for a, b, name in mods:
            programs[name][0] += 1
            programs[name][1] += b - a
        op_events = d.get("XLA Ops") or [(n, a, b) for a, b, n in mods]
        op_iv = []
        starts = [a for a, _, _ in mods]
        for name, a, b in op_events:
            if b <= t0 or a >= t1:
                continue
            op_iv.append((a, b))
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            if merged0 is None:
                ops[f"{prog}/{name}"] += min(b, t1) - max(a, t0)
        merged = _merge(_clip(op_iv, t0, t1))
        busy_total += sum(b - a for a, b in merged)
        if merged0 is None:
            merged0 = merged

    labels = sorted((a, b, n) for n, a, b in host if n in LABELS)
    starts = [a for a, _, _ in labels]

    def label_at(t):
        """The annotation that covers t; the benchmark's host spans follow
        each other on one thread, so only the latest to start can."""
        i = bisect.bisect_right(starts, t) - 1
        return labels[i][2] if i >= 0 and labels[i][1] >= t else "none"

    gaps, prev = [], t0
    for a, b in merged0 + [(t1, t1)]:
        if a > prev:
            gaps.append((prev, a, label_at((prev + a) / 2)))
        prev = max(prev, b)
    return Reduced(window=window, n_devices=len(dev_lines),
                   busy_ns=busy_total / len(dev_lines),
                   programs=dict(programs), ops=dict(ops), gaps=gaps,
                   host=host, busy_intervals=merged0)
