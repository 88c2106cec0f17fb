"""Profiler + CostLedger against the live gateway (ISSUE 9 tentpole).

The proof obligations:

  * exactness — the ledger's per-bucket sealed-byte sums equal the pool's
    windowed ``sealed_bytes_{prefill,decode,swap}`` counters to the byte,
    under forced preemption (swap out/in, close/reopen) and under
    prefix-cache COW breaks, because both are charged from the same
    ``PagedKVPool.note_*`` call sites with the same formulas;
  * the gateway's ``sealed_bytes_per_token`` metric is reproducible from
    ledger rows alone;
  * per-step jitted-dispatch counting works end to end (the ROADMAP item-1
    metric) and lands on the trace's counter tracks;
  * ``profile_report()`` emits the BENCH_profile.json document and
    tools/bench_diff.py fails (exit 1) when a doctored run adds a dispatch
    per step or inflates a phase's sealed-byte cost beyond its band.

Like test_serve_gateway.py the module shares one jitted gateway and the
tests are order-dependent: each opens a fresh measurement window with
``reset_metrics()``.
"""
import json
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import configs
from repro.core.overhead import TPU_V5E
from repro.models import registry
from repro.obs import MonitorConfig, PHASES
from repro.serve import SecureGateway

ROOT = pathlib.Path(__file__).resolve().parents[1]

PAGE = 8
MAXP = 4
N_NEW = 5
PROMPT_LENS = {"alice": 6, "bob": 9, "carol": 12}


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_config("granite-3-2b", smoke=True)
    params = registry.get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = {t: rng.randint(0, cfg.vocab, n).astype(np.int32)
               for t, n in PROMPT_LENS.items()}
    return cfg, params, prompts


@pytest.fixture(scope="module")
def gw(setup):
    cfg, params, _ = setup
    return SecureGateway(cfg, params, security="trusted", max_slots=3,
                         page_size=PAGE, n_pages=32, max_pages_per_seq=MAXP,
                         trace=True,
                         monitor_config=MonitorConfig(tamper_storm_count=0))


def _buckets_of(gw):
    m = gw.pool.stats
    return {"prefill": m["sealed_bytes_prefill"],
            "decode": m["sealed_bytes_decode"],
            "swap": m["sealed_bytes_swap"]}


def _force_preemption(gw, prompts):
    """Fill all 3 slots, then submit a priority-5 request to evict one."""
    rids = {t: gw.submit(t, prompts[t], max_new=N_NEW, priority=0)
            for t in ("alice", "bob", "carol")}
    gw.step()
    rids["dave"] = gw.submit("dave", prompts["alice"], max_new=N_NEW,
                             priority=5)
    ev = gw.step()
    assert len(ev["preempted"]) == 1
    return rids


def test_ledger_buckets_exact_under_forced_preemption(setup, gw):
    """Ledger sealed-byte sums == pool bucket counters, byte for byte,
    through a full preempt/swap/resume cycle; sealed_bytes_per_token is
    reproducible from the ledger alone."""
    cfg, params, prompts = setup
    gw.reset_metrics()
    _force_preemption(gw, prompts)
    gw.drain()
    m = gw.metrics()
    led = gw.profiler.ledger
    assert m["swap_outs"] >= 1 and m["sealed_bytes_decode"] > 0
    # THE exactness claim: same call sites, same guards, same formulas
    assert led.bucket_bytes == _buckets_of(gw)
    assert m["sealed_bytes_per_token"] == \
        led.bucket_bytes["decode"] / m["decode_tokens"]
    # every ledger byte lands in exactly one bucket: totals agree too
    total_rows = sum(r["sealed_bytes"] for r in led.rows())
    assert total_rows == sum(led.bucket_bytes.values())
    # per-phase coverage of the cycle: prefill + decode always, the swap
    # phases because a preemption happened
    phases = led.phase_totals()
    for needed in ("prefill", "decode", "swap_out", "swap_in"):
        assert needed in phases, needed
    assert set(phases) <= set(PHASES)
    if m["page_closes"]:
        assert phases["close"]["sealed_bytes"] % (2 * gw.pool.page_bytes) == 0
    # swap phases are wall-only host copies: time, no bytes, no dispatches
    for ph in ("swap_out", "swap_in"):
        assert phases[ph]["sealed_bytes"] == 0
        assert phases[ph]["dispatches"] == 0
        assert phases[ph]["wall_us"] > 0
    # per-tenant attribution: every submitting tenant shows up, and the
    # victim's swap traffic is attributed to it
    tenants = led.tenant_totals()
    for t in ("alice", "bob", "carol", "dave"):
        assert t in tenants, t
        assert tenants[t]["sealed_bytes"] > 0
    # jitted work is device-synchronized and counted: one dispatch per
    # decode call, >= 1 dispatch per step at max occupancy
    assert phases["decode"]["dispatches"] == phases["decode"]["calls"] >= 1
    assert phases["decode"]["wall_us"] > 0
    assert gw.profiler.max_occupancy == 3
    assert m["dispatches_per_step"] >= 1.0
    assert m["dispatch_total"] == gw.profiler.dispatch_total
    # the per-step counter tracks landed in the trace
    counters = [e for e in gw.tracer.drain() if e["ph"] == "C"]
    assert {e["name"] for e in counters} == {"dispatches", "sealed_bytes"}


def test_ledger_exact_under_prefix_cow(setup, gw):
    """A shared prefix with a partial tail forces a COW break on the first
    decode write; the ledger attributes it to the writing tenant and the
    buckets still reconcile exactly."""
    cfg, params, prompts = setup
    gw.reset_metrics()
    cows0 = int(gw.pool._c_cow_breaks.value)
    prefix = np.random.RandomState(77).randint(
        0, cfg.vocab, PAGE + 3).astype(np.int32)       # tail_fill = 3
    entry = gw.register_prefix(prefix)
    assert entry.tail_fill == 3
    rid = gw.submit("cora", prefix, N_NEW)             # full prefix hit
    gw.drain()
    assert gw.status(rid) == "done"
    n_cows = int(gw.pool._c_cow_breaks.value) - cows0
    assert n_cows >= 1
    m = gw.metrics()
    led = gw.profiler.ledger
    assert led.bucket_bytes == _buckets_of(gw)
    assert m["sealed_bytes_per_token"] == \
        led.bucket_bytes["decode"] / m["decode_tokens"]
    phases = led.phase_totals()
    # the COW break: 2*page_bytes per break, charged to the tenant whose
    # write broke the share, in the decode bucket
    assert phases["cow"]["sealed_bytes"] == 2 * gw.pool.page_bytes * n_cows
    assert phases["cow"]["dispatches"] == n_cows
    rows = {(r["phase"], r["tenant"]): r for r in led.rows()}
    assert rows[("cow", "cora")]["sealed_bytes"] > 0
    # the publish umbrella span: timed, but its crypto is charged to the
    # nested prefill/close phases, never to itself
    pub = rows[("prefix_publish", "_prefix")]
    assert pub["calls"] == 1 and pub["wall_us"] > 0
    assert pub["sealed_bytes"] == 0 and pub["dispatches"] == 0
    assert rows[("prefill", "_prefix")]["sealed_bytes"] > 0


def test_profile_report_document_and_drift_table(setup, gw):
    """profile_report() = the BENCH_profile.json document: dispatch
    accounting + per-phase drift rows priced by core/overhead.py."""
    rep = gw.profile_report(model=TPU_V5E)
    assert rep["benchmark"] == "profile"
    assert rep["model"] == "tpu-v5e-sealed"
    assert rep["steps"] == gw.profiler.steps > 0
    assert rep["dispatches_per_step"] >= 1.0
    assert rep["dispatch_total"] == gw.profiler.dispatch_total
    assert rep["buckets"] == _buckets_of(gw)
    by_phase = {r["phase"]: r for r in rep["phases"]}
    dec = by_phase["decode"]
    for col in ("calls", "dispatches", "sealed_bytes", "cipher_blocks",
                "mac_ops", "wall_us", "predicted_us", "ratio"):
        assert col in dec, col
    # byte-charged phases get a real prediction and a finite ratio
    assert dec["predicted_us"] > 0 and dec["ratio"] > 0
    # 8 bytes per keystream block, k+v lanes: blocks = ceil(bytes / 8)
    assert dec["cipher_blocks"] == -(-dec["sealed_bytes"] // 8)
    assert json.dumps(rep)                 # serializable as-is


def test_profile_report_prices_only_a_known_chip(setup, gw):
    """With no model given, the report prices the run with the cost model
    of the device it ran on; the CPU has none, so the rows carry only the
    measured and counted columns and no drift."""
    rep = gw.profile_report()
    assert jax.devices()[0].platform == "cpu"
    assert rep["model"] is None
    assert rep["phases"]
    for row in rep["phases"]:
        assert "predicted_us" not in row and "ratio" not in row
        assert row["wall_us"] > 0 or row["calls"] == 0


def _run_bench_diff(tmp_path, baseline: dict, current: dict):
    bp, cp = tmp_path / "base.json", tmp_path / "cur.json"
    bp.write_text(json.dumps(baseline))
    cp.write_text(json.dumps(current))
    return subprocess.run(
        [sys.executable, str(ROOT / "tools" / "bench_diff.py"),
         str(bp), str(cp), "--default-tol", "0.05"],
        capture_output=True, text=True)


def test_bench_diff_gates_dispatches_and_phase_costs(setup, gw, tmp_path):
    """The CI band: identical profile artifacts pass; a run that adds one
    dispatch per step, inflates a phase's sealed bytes beyond 5%, or drops
    a phase row entirely fails with exit 1."""
    rep = json.loads(json.dumps(gw.profile_report(), default=float))
    assert _run_bench_diff(tmp_path, rep, rep).returncode == 0

    doctored = json.loads(json.dumps(rep))
    doctored["dispatches_per_step"] += 1.0      # one extra decode dispatch
    proc = _run_bench_diff(tmp_path, rep, doctored)
    assert proc.returncode == 1
    assert "dispatches_per_step" in proc.stdout and \
        "REGRESSION" in proc.stdout

    doctored = json.loads(json.dumps(rep))
    for row in doctored["phases"]:
        if row["phase"] == "decode":
            row["sealed_bytes"] = int(row["sealed_bytes"] * 1.5)
    assert _run_bench_diff(tmp_path, rep, doctored).returncode == 1

    doctored = json.loads(json.dumps(rep))
    doctored["phases"] = [r for r in doctored["phases"]
                          if r["phase"] != "decode"]
    proc = _run_bench_diff(tmp_path, rep, doctored)
    assert proc.returncode == 1 and "MISSING" in proc.stdout

    # wall time / drift ratio are never gated — timing noise alone passes
    noisy = json.loads(json.dumps(rep))
    for row in noisy["phases"]:
        row["wall_us"] *= 40.0
        if row.get("ratio"):
            row["ratio"] *= 40.0
    assert _run_bench_diff(tmp_path, rep, noisy).returncode == 0


def test_reset_metrics_opens_fresh_profile_window(setup, gw):
    """reset_metrics() clears the profiler window with the registry: the
    report empties, lifetime dispatch totals survive."""
    total = gw.profiler.dispatch_total
    assert total > 0
    gw.reset_metrics()
    rep = gw.profile_report()
    assert rep["steps"] == 0 and rep["phases"] == []
    assert rep["dispatches_per_step"] == 0.0
    assert rep["dispatch_total"] == total      # lifetime, not windowed
    m = gw.metrics()
    assert m["dispatches_per_step"] == 0.0
