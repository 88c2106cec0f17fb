"""The load offered to a gateway: a request stream in an open or closed loop.

The host clock is ``time.perf_counter``.  Each request keeps the time it
was due, the time it was submitted and the time each of its tokens came
back from ``gateway.step()``: the client's clock, since a token is the
client's once ``step`` returns it.  Every step also records the work it
did per request (prefill tokens with their start, decode positions), read
from the scheduler's request records before and after the step; the cost
model turns that into operations and bytes.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax

from . import traffic


@dataclasses.dataclass
class Record:
    req: traffic.Request
    t_due: float
    rid: int = -1
    t_submit: float = 0.0
    emits: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    t_done: float | None = None
    status: str = "queued"
    lanes: set = dataclasses.field(default_factory=set)  # decode slots held


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    prefill: list           # [(start, n_tokens, completes)] per lane
    decode: list            # [context length incl. the new token] per lane


class Load:
    """One request stream into one gateway, on one clock.

    loop "open": request i is due at ``t_zero + req.t_due``.
    loop "closed": ``clients`` requests are due at ``t_zero``; each finished
    request makes its client's next one due when it finished.
    """

    def __init__(self, gw, stream, loop: str, clients: int = 0,
                 clock=time.perf_counter):
        self.gw = gw
        self.stream = deque(stream)
        self.loop = loop
        self.clients = clients
        self.clock = clock
        self.records: list[Record] = []
        self.by_rid: dict[int, Record] = {}
        self.steps: list[Step] = []
        self.pending: deque[Record] = deque()     # due, not yet submitted
        self.inflight: dict[int, Record] = {}
        self.t_zero = None

    # -- the stream ----------------------------------------------------
    def start(self, t_zero: float) -> None:
        self.t_zero = t_zero
        if self.loop == "closed":
            for _ in range(self.clients):
                self._queue_next(t_zero)

    def _queue_next(self, t_due: float) -> None:
        if not self.stream:
            raise RuntimeError("the request stream ran out: raise its "
                               "length (traffic.stream_length)")
        self.pending.append(Record(req=self.stream.popleft(), t_due=t_due))

    def close(self, t_end: float) -> None:
        """Hold every open-loop request due by ``t_end`` (submitted or not),
        so that the wait of one the window never submitted counts too."""
        while (self.loop == "open" and self.stream
               and self.t_zero + self.stream[0].t_due <= t_end):
            req = self.stream.popleft()
            self.pending.append(Record(req=req, t_due=self.t_zero + req.t_due))

    @property
    def due(self) -> list:
        """Every request that fell due: submitted, then still pending."""
        return self.records + list(self.pending)

    def _next_due(self) -> float:
        if self.pending:
            return self.pending[0].t_due
        if self.loop == "open" and self.stream:
            return self.t_zero + self.stream[0].t_due
        return float("inf")

    def _submit(self) -> None:
        with jax.profiler.TraceAnnotation("submit"):
            while self.pending:
                rec = self.pending.popleft()
                rec.rid = self.gw.submit(rec.req.tenant, rec.req.prompt,
                                         max_new=rec.req.max_new)
                rec.t_submit = self.clock()
                self.records.append(rec)
                self.by_rid[rec.rid] = rec
                self.inflight[rec.rid] = rec

    # -- the loop ------------------------------------------------------
    def run_until(self, t_end: float) -> None:
        """Submit what falls due and step, until ``t_end``.  A step that
        starts before t_end runs to its end; none starts after it."""
        sched = self.gw.scheduler
        while True:
            now = self.clock()
            if now >= t_end:
                return
            self.close(now)
            if self.loop == "open" and not self.stream:
                raise RuntimeError("the request stream ran out: raise its "
                                   "length (traffic.stream_length)")
            if self.pending:
                self._submit()
            if sched.idle:
                wake = min(self._next_due(), t_end)
                with jax.profiler.TraceAnnotation("wait_arrival"):
                    while self.clock() < wake:
                        time.sleep(min(0.002, max(0.0, wake - self.clock())))
                continue
            before = {rid: self._state(sched.requests[rid])
                      for rid in self.inflight}
            t0 = self.clock()
            with jax.profiler.TraceAnnotation("gateway.step"):
                events = self.gw.step()
            t1 = self.clock()
            self._account(events, before, t0, t1)

    @staticmethod
    def _state(r) -> tuple:
        return r.prefill_pos, len(r.tokens_out), r.slot

    def _account(self, events: dict, before: dict, t0: float,
                 t1: float) -> None:
        sched = self.gw.scheduler
        for rid, tok in events["emitted"]:
            rec = self.by_rid.get(rid)
            if rec is not None:
                rec.emits.append(t1)
                rec.tokens.append(int(tok))
        prefill, decode = [], []
        for rid, (pos0, n0, slot0) in before.items():
            r = sched.requests[rid]
            n1 = len(r.tokens_out)
            if r.prefill_pos > pos0:
                completes = r.prefill_pos >= r.prompt_len and n1 > n0
                prefill.append((pos0, r.prefill_pos - pos0, completes))
                n0 += int(completes)
            for n in range(n0, n1):
                # the decode that emits a request's token n (from 0) holds
                # prompt + n - 1 positions and writes one: it attends over
                # prompt + n
                decode.append(r.prompt_len + n)
            if n1 > n0:
                # a request that finished in this step has left its slot
                self.by_rid[rid].lanes.add(r.slot if r.slot >= 0 else slot0)
        self.steps.append(Step(t0=t0, t1=t1, prefill=prefill, decode=decode))
        for rid in list(events["finished"]) + list(events["poisoned"]):
            rec = self.by_rid.get(rid)
            if rec is None or rec.t_done is not None:
                continue
            rec.t_done = t1
            rec.status = sched.requests[rid].status
            self.inflight.pop(rid, None)
            if self.loop == "closed":
                self._queue_next(t1)
