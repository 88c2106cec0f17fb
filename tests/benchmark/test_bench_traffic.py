"""The benchmark's traffic generator, request loop and latency arithmetic,
on the CPU with a stand-in gateway: no model runs here."""
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import load, spec, stats, traffic  # noqa: E402

BIG_SEED = 2**31 + 2**30 + 12345


def _mix(name):
    return spec._read(spec.HERE / "traffic" / f"{name}.json")


def test_same_seed_same_schedule():
    a = traffic.generate(_mix("chat"), BIG_SEED, 49155, 96, rate_per_s=2.0)
    b = traffic.generate(_mix("chat"), BIG_SEED, 49155, 96, rate_per_s=2.0)
    assert [(r.tenant, r.max_new, r.t_due) for r in a] == \
        [(r.tenant, r.max_new, r.t_due) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.generate(_mix("chat"), BIG_SEED + 1, 49155, 96,
                         rate_per_s=2.0)
    assert [(len(r.prompt), r.max_new, r.t_due) for r in a] == \
        [(len(r.prompt), r.max_new, r.t_due) for r in c]
    assert [r.tenant for r in a] != [r.tenant for r in c]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", ["chat", "long"])
def test_every_seed_gets_the_same_work(mix):
    """Every seed's stream holds the same lengths (and, open, the same
    gaps) in the same order, each block the distribution's quantiles once,
    within the mix's bounds."""
    m = _mix(mix)
    block = m["block"]
    n = 4 * block
    runs = [traffic.generate(m, s, 49155, n, rate_per_s=2.0)
            for s in (1, 7, BIG_SEED)]
    for field in ("prompt", "output"):
        sizes = [[len(r.prompt) if field == "prompt" else r.max_new
                  for r in run] for run in runs]
        assert sizes[0] == sizes[1] == sizes[2]
        blocks = [sorted(sizes[0][i:i + block]) for i in range(0, n, block)]
        assert all(b == blocks[0] for b in blocks)
        assert min(sizes[0]) >= m[field]["min"]
        assert max(sizes[0]) <= m[field]["max"]
    if m["loop"] == "open":
        gaps = [np.diff([0.0] + [r.t_due for r in run]) for run in runs]
        np.testing.assert_allclose(gaps[0], gaps[1])
        np.testing.assert_allclose(gaps[0], gaps[2])
        assert abs(np.mean(gaps[0]) - 0.5) < 0.05


def test_lognormal_median_and_clip():
    m = _mix("chat")
    reqs = traffic.generate(m, 3, 100, 32 * 8, rate_per_s=1.0)
    lens = np.array([len(r.prompt) for r in reqs])
    assert np.median(lens) == pytest.approx(m["prompt"]["median"], rel=0.05)
    assert lens.max() == m["prompt"]["max"]     # the tail reaches the clip


class _Req:
    def __init__(self, prompt, max_new):
        self.prompt_len = len(prompt)
        self.max_new = max_new
        self.status = "queued"
        self.prefill_pos = 0
        self.tokens_out = []
        self.slot = -1


class FakeGateway:
    """Admits up to ``slots`` requests, each into the lowest free slot; a
    step prefills each new request whole (its first token) and decodes one
    token for every running one; a finished request leaves its slot."""

    def __init__(self, slots=2):
        self.slots = slots
        self.scheduler = types.SimpleNamespace(requests={}, idle=True)
        self.queue, self.active, self.next_rid = [], [], 1
        self.max_active = 0

    def submit(self, tenant, prompt, max_new):
        rid, self.next_rid = self.next_rid, self.next_rid + 1
        self.scheduler.requests[rid] = _Req(prompt, max_new)
        self.queue.append(rid)
        self.scheduler.idle = False
        return rid

    def step(self):
        ev = {"emitted": [], "finished": [], "poisoned": []}
        while self.queue and len(self.active) < self.slots:
            rid = self.queue.pop(0)
            held = {self.scheduler.requests[a].slot for a in self.active}
            self.scheduler.requests[rid].slot = min(
                set(range(self.slots)) - held)
            self.active.append(rid)
        self.max_active = max(self.max_active, len(self.active))
        for rid in list(self.active):
            r = self.scheduler.requests[rid]
            if r.status == "queued":
                r.prefill_pos, r.status = r.prompt_len, "running"
            r.tokens_out.append(7)
            ev["emitted"].append((rid, 7))
            if len(r.tokens_out) >= r.max_new:
                r.status, r.slot = "done", -1
                ev["finished"].append(rid)
                self.active.remove(rid)
        self.scheduler.idle = not (self.queue or self.active)
        return ev


class Clock:
    """A host clock that moves 1 ms each time it is read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def test_closed_loop_keeps_its_concurrency():
    gw = FakeGateway(slots=4)
    stream = traffic.generate(_mix("long"), 5, 100, 64)
    d = load.Load(gw, stream, "closed", clients=3, clock=Clock())
    d.start(0.0)
    d.run_until(1.0)
    for rec in d.records:
        assert rec.t_submit >= rec.t_due
    # every finish hands its client the next request: 3 in flight always
    assert len(d.inflight) + len(d.pending) == 3
    assert gw.max_active == 3
    done = [r for r in d.records if r.status == "done"]
    assert done and all(len(r.tokens) == r.req.max_new for r in done)
    # each request decoded in the one slot it held, up to its last token
    assert all(len(r.lanes) == 1 and r.lanes <= {0, 1, 2} for r in done)


def test_open_loop_sends_on_schedule_and_logs_work():
    gw = FakeGateway(slots=8)
    stream = traffic.generate(_mix("chat"), 9, 100, 64, rate_per_s=400.0)
    d = load.Load(gw, stream, "open", clock=Clock())
    d.start(0.0)
    d.run_until(0.05)
    d.close(0.05)
    assert len(d.due) > 10
    assert all(r.t_due <= 0.05 for r in d.due)
    assert all(r.t_submit >= r.t_due for r in d.records)
    assert all(d.due[i].req.index == i for i in range(len(d.due)))
    first = [s for s in d.steps if s.prefill]
    assert first and all(c for s in first for (_, _, c) in s.prefill)


def _run(records, w0=0.0, w1=10.0):
    return types.SimpleNamespace(window=(w0, w1), due=records)


def _rec(t_due, emits, t_done=None, t_submit=None):
    r = load.Record(req=None, t_due=t_due,
                      t_submit=t_due if t_submit is None else t_submit)
    r.emits, r.t_done = list(emits), t_done
    return r


def _reader(name):
    return spec.metric_reader(name)


def test_ttft_starts_at_the_due_time():
    # submitted 2 s late (the host was busy), first token 1 s after that
    recs = [_rec(1.0, [4.0, 4.5], t_done=4.5, t_submit=3.0)]
    assert _reader("ttft_p90_ms")(_run(recs)) == pytest.approx(3000.0)


def test_a_stall_inside_the_window_enters_the_tail():
    fast = [_rec(0.5 + i * 0.1, [0.6 + i * 0.1], t_done=0.6 + i * 0.1)
            for i in range(9)]
    stalled = _rec(2.0, [], t_done=None)      # no token by the close
    got = _reader("ttft_p90_ms")(_run(fast + [stalled]))
    assert got == pytest.approx(100.0)        # 9 of 10 wait 0.1 s
    got = _reader("ttft_p90_ms")(_run(fast + [stalled] * 2))
    assert got == pytest.approx(8000.0)       # the stall is the p90 now
    # a request still decoding at the close adds the gap it waited so far
    slow = _rec(0.0, [1.0, 2.0], t_done=None)
    gaps = _reader("itl_p95_ms")(_run([slow]))
    assert gaps == pytest.approx(8000.0)


def test_tokens_per_second_counts_only_the_window():
    recs = [_rec(0.0, [-1.0, 1.0, 2.0, 11.0], t_done=11.0)]
    assert _reader("tok_per_s")(_run(recs)) == pytest.approx(0.2)


def test_percentile_is_nearest_rank():
    assert stats.percentile([1, 2, 3, 4], 0.5) == 2
    assert stats.percentile([5], 0.9) == 5
    assert stats.percentile(range(1, 101), 0.95) == 95
