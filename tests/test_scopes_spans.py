"""The serving stack on the profiler's trace: named scopes in the engine's
jitted programs, host spans through ``jax.profiler``, queue wait counted at
first admission, and TTFT scored at the first token.  CPU, smoke size."""
import pathlib
import types

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import registry
from repro.obs import Tracer
from repro.serve import SecureGateway
from repro.serve import scheduler as scheduler_lib

SCOPES = ("weights_unseal", "kv_gather", "kv_unseal", "model", "kv_seal")
SPANS = ("serve_step", "sched.admit", "sched.prefill", "sched.decode",
         "engine.decode_step", "gateway.submit", "gateway.monitor",
         "sessions.handshake", "pool.alloc")


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_config("granite-3-2b", smoke=True)
    params = registry.get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _gateway(setup, security="off", **kw):
    cfg, params = setup
    size = dict(max_slots=2, page_size=8, n_pages=16, max_pages_per_seq=4,
                prefill_chunk=16)
    size.update(kw)
    return SecureGateway(cfg, params, security=security, **size)


@pytest.mark.parametrize("security", ["trusted", "off"])
def test_engine_programs_carry_the_named_scopes(setup, security):
    """Each stage of the decode and chunk-prefill programs, and the page
    close, is lowered under its scope.  With the sealing off there are no
    weights to unseal, so that scope holds no operation."""
    gw = _gateway(setup, security)
    eng, pool = gw.engine, gw.pool
    B, P = 2, 4
    i32 = np.int32
    z = np.zeros
    texts = {
        "decode": eng._decode.lower(
            eng.params, z(B, i32), z(B, i32), z(B, bool), z((B, P), i32),
            z(B, i32), pool.arrays()),
        "prefill": eng._chunk_prefill.lower(
            eng.params, z((B, 16), i32), z(B, i32), np.ones(B, i32),
            z(B, bool), z((B, P), i32), pool.arrays()),
    }
    if security == "trusted":
        texts["close"] = eng._close.lower(pool.arrays(), i32(1))
    texts = {k: v.as_text(debug_info=True) for k, v in texts.items()}
    want = SCOPES if security == "trusted" else SCOPES[1:]
    for prog in ("decode", "prefill"):
        for scope in want:
            assert f"{scope}/" in texts[prog], (prog, scope)
    if security == "off":
        assert "weights_unseal/" not in texts["decode"]
    else:
        assert "kv_seal/" in texts["close"]


def _host_events(trace_dir: pathlib.Path) -> list:
    from jax.profiler import ProfileData
    path = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    out.append(types.SimpleNamespace(
                        name=e.name, a=e.start_ns, b=e.end_ns,
                        args=dict(e.stats)))
    return out


def test_spans_reach_the_profiler_with_a_disabled_tracer(setup, tmp_path):
    """A gateway built with trace=False still puts its host phases on a
    jax.profiler trace: one step nests serve_step > sched.decode >
    engine.decode_step, the lane spans name their requests, and a new
    tenant's submit runs sessions.handshake.  The tracer's own buffer stays
    empty."""
    gw = _gateway(setup)
    prompt = np.arange(1, 7, dtype=np.int32)
    rid = gw.submit("alice", prompt, max_new=4)
    gw.step()                       # alice prefills; decode from here on
    jax.profiler.start_trace(str(tmp_path))
    rid2 = gw.submit("bob", prompt, max_new=4)
    gw.step()
    jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    by_name = {}
    for e in ev:
        by_name.setdefault(e.name, []).append(e)
    (step,) = by_name["serve_step"]
    (sched,) = by_name["sched.decode"]
    (eng,) = by_name["engine.decode_step"]
    assert step.a <= sched.a <= eng.a and eng.b <= sched.b <= step.b
    # bob's prompt fits one chunk, so he decodes beside alice this step
    assert sched.args["rid"] == eng.args["rid"] == f"{rid} {rid2}"
    assert sched.args["lanes"] == eng.args["lanes"] == 2
    (pre,) = by_name["sched.prefill"]
    assert str(pre.args["rid"]) == str(rid2)   # one id reads as a number
    (hs,) = by_name["sessions.handshake"]
    (sub,) = by_name["gateway.submit"]
    assert hs.args["tenant"] == sub.args["tenant"] == "bob"
    assert sub.a <= hs.a and hs.b <= sub.b
    assert {"sched.admit", "gateway.monitor", "pool.alloc"} <= set(by_name)
    assert gw.tracer.events == [] and gw.tracer._open == {}


def test_a_disabled_tracer_keeps_nothing():
    tr = Tracer(enabled=False)
    with tr.span("serve_step", args={"step": 1}):
        tr.begin(("req", 1), "queued")
        tr.instant("submit")
    tr.end(("req", 1))
    assert tr.events == [] and tr._open == {} and tr.drain() == []


class _Clock:
    """Stands in for the scheduler's ``time`` module."""

    def __init__(self):
        self.t = 100.0

    def monotonic(self):
        return self.t


def test_queue_wait_of_an_admitted_and_of_a_queued_request(setup,
                                                           monkeypatch):
    """One slot: the second request waits in the queue while the first
    decodes, and is admitted k steps later; its wait is counted then.  A
    request still queued at the read enters at its wait so far."""
    clock = _Clock()
    monkeypatch.setattr(scheduler_lib, "time", clock)
    gw = _gateway(setup, max_slots=1)
    prompt = np.arange(1, 7, dtype=np.int32)
    a = gw.submit("alice", prompt, max_new=3)
    b = gw.submit("bob", prompt, max_new=3)
    k = 0
    while gw.status(b) == "queued":
        clock.t += 1.0
        gw.step()
        k += 1
    sched = gw.scheduler
    assert gw.status(a) == "done" and k >= 2
    assert sched.requests[b].t_admit == 100.0 + k
    assert sched._h_queue_wait.values == [1e3, k * 1e3]
    assert gw.metrics()["queue_wait_p90_ms"] == k * 1e3
    gw.reset_metrics()
    assert gw.metrics()["queue_wait_p90_ms"] == 0.0
    clock.t += 0.5
    gw.submit("carol", prompt, max_new=3)         # waits behind bob
    clock.t += 2.0
    assert gw.metrics()["queue_wait_p90_ms"] == 2e3
    gw.step()                                     # bob decodes, carol waits
    clock.t += 1.0
    assert gw.metrics()["queue_wait_p90_ms"] == 3e3


def test_ttft_is_scored_at_the_first_token(setup):
    """A request's TTFT enters request_ttft_ms when its first token comes
    back, not when it finishes, so a stall after it cannot hold it out."""
    gw = _gateway(setup)
    rid = gw.submit("alice", np.arange(1, 7, dtype=np.int32), max_new=4)
    gw.step()
    req = gw.scheduler.requests[rid]
    assert req.tokens_out and not req.finished
    ttft = gw.scheduler._h_ttft
    assert ttft.values == [pytest.approx((req.t_first - req.t_submit) * 1e3)]
    gw.drain()
    assert ttft.count == 1
