"""Streaming SLO/security-posture Monitor and its alert-driven actions.

The unit half drives ``Monitor``/rules directly with synthetic samples and
an in-memory AuditLog — no engine, no jit.  The integration half builds
*fresh* gateways (never the shared module gateway of test_serve_gateway —
quarantine and proactive spill mutate scheduler state) and checks the
paper's invariants end-to-end: alert-driven actions never change an
honest tenant's decoded tokens, and every decision lands in the verified
audit chain.  The CLI half covers tools/bench_diff.py (the CI perf gate)
and tools/obs_dash.py.
"""
import json
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import configs
from repro.core.channel import SecureChannel
from repro.models import registry
from repro.obs import (AuditLog, MetricsRegistry, Monitor, MonitorConfig,
                       parse_slo_overrides)
from repro.obs.rules import (ACT_QUARANTINE, CRITICAL, WARNING, Alert,
                             ChainRule, HeadroomRule, SloRule, StormRule)
from repro.serve import SecureGateway, ServeEngine, TenantQuarantined
from repro.serve.gateway import PROVIDER

ROOT = pathlib.Path(__file__).resolve().parent.parent
KEY = b"\x07" * 32

PAGE = 8
MAXP = 4
N_NEW = 5
PROMPT_LENS = {"alice": 6, "bob": 9, "carol": 12}


# ---------------------------------------------------------------------------
# rule units (host-side, no gateway)
# ---------------------------------------------------------------------------

def test_slo_rule_upper_bound_and_min_count():
    rule = SloRule("slo_ttft", "ttft_p95_ms", 100.0, min_count=4)
    mon = Monitor(rules=[rule])
    # too few underlying observations: a warm-up token can't page anyone
    assert mon.observe(1, slo={"ttft_p95_ms": 500.0},
                       counts={"ttft_p95_ms": 2}) == []
    fired = mon.observe(2, slo={"ttft_p95_ms": 150.0},
                        counts={"ttft_p95_ms": 8})
    assert [a.rule for a in fired] == ["slo_ttft"]
    assert fired[0].value == 150.0 and fired[0].threshold == 100.0
    # back inside the bound: silent (not a cooldown artifact — new monitor)
    assert Monitor(rules=[rule]).observe(
        1, slo={"ttft_p95_ms": 50.0}, counts={"ttft_p95_ms": 8}) == []


def test_slo_rule_lower_direction_is_a_floor():
    rule = SloRule("slo_tps", "tok_per_s", 10.0, direction="lower")
    mon = Monitor(rules=[rule])
    assert mon.observe(1, slo={"tok_per_s": 3.0},
                       counts={"tok_per_s": 5})[0].rule == "slo_tps"
    mon2 = Monitor(rules=[rule])
    assert mon2.observe(1, slo={"tok_per_s": 30.0},
                        counts={"tok_per_s": 5}) == []


def test_windowed_slo_uses_the_burn_rate_not_the_spike():
    rule = SloRule("occ", "occupancy_pct", 50.0, window=4)
    mon = Monitor(rules=[rule])
    # one spike to 100 in a window of low values: mean stays under the bound
    for step, v in enumerate((10.0, 10.0, 100.0, 10.0), start=1):
        fired = mon.observe(step, slo={"occupancy_pct": v},
                            counts={"occupancy_pct": step})
    assert fired == [] and mon.alerts == []
    # sustained high occupancy: the windowed mean breaches (once — the
    # cooldown rate-limits the persisting condition afterwards)
    for step in range(5, 9):
        mon.observe(step, slo={"occupancy_pct": 90.0},
                    counts={"occupancy_pct": step})
    assert [a.rule for a in mon.alerts] == ["occ"]
    assert mon.alerts[0].value > 50.0


def test_cooldown_rate_limits_a_persisting_condition():
    rule = SloRule("slo", "m", 1.0)
    mon = Monitor(config=MonitorConfig(cooldown_steps=5), rules=[rule])
    hot = {"m": 9.0}
    cnt = {"m": 10}
    steps_fired = [s for s in range(1, 13)
                   if mon.observe(s, slo=hot, counts=cnt)]
    assert steps_fired == [1, 6, 11]            # once per cooldown window
    assert len(mon.alerts) == 3


def test_storm_rule_attributes_the_offending_tenant():
    audit = AuditLog(KEY)
    rule = StormRule("tamper_storm", "tamper", threshold=3, window_steps=16)
    mon = Monitor(rules=[rule], audit=audit)
    for _ in range(3):
        audit.append("tamper", tenant="mallory", rid=1)
    audit.append("tamper", tenant="alice", rid=2)       # below threshold
    fired = mon.observe(1)
    assert [(a.rule, a.tenant) for a in fired] == [("tamper_storm", "mallory")]
    assert fired[0].severity == CRITICAL and fired[0].value == 3.0
    # events age out of the sliding window: far in the future, no re-fire
    assert mon.observe(100) == []
    assert mon.posture()["mallory"]["tamper"] == 3
    assert mon.posture()["alice"]["tamper"] == 1


def test_headroom_rule_skips_closed_pages():
    rule = HeadroomRule("nonce_headroom", "page_nonce", min_remaining=1)
    mon = Monitor(rules=[rule])
    headroom = [
        {"source": "page_nonce", "id": 3, "tenant": "a", "open": False,
         "remaining": 0},                       # closed: never bumps again
        {"source": "page_nonce", "id": 5, "tenant": "b", "open": True,
         "remaining": 1},                       # open tail: about to trip
        {"source": "page_nonce", "id": 6, "tenant": "b", "open": True,
         "remaining": 7},
        {"source": "reseal_lanes", "id": "train", "remaining": 0},  # other rule
    ]
    fired = mon.observe(1, headroom=headroom)
    assert [(a.rule, a.detail["id"], a.tenant) for a in fired] == \
        [("nonce_headroom", 5, "b")]
    assert "tenant" not in fired[0].detail      # detail is the report sans tenant


def test_chain_rule_detects_in_process_tamper():
    audit = AuditLog(KEY)
    for i in range(4):
        audit.append("launch", tenant="a", nonce=i)
    mon = Monitor(rules=[ChainRule(every=1)], audit=audit)
    assert mon.observe(1) == []
    audit.records[2]["detail"]["nonce"] = 99
    fired = mon.observe(2)
    assert [a.rule for a in fired] == ["audit_chain"]
    assert fired[0].detail["first_bad"] == 2


def test_warning_alerts_land_in_the_audit_chain():
    audit = AuditLog(KEY)
    reg = MetricsRegistry()
    rule = SloRule("slo_ttft", "ttft_p95_ms", 10.0, severity=WARNING)
    mon = Monitor(rules=[rule], registry=reg, audit=audit)
    mon.observe(1, slo={"ttft_p95_ms": 99.0}, counts={"ttft_p95_ms": 5})
    recs = audit.records_of("alert")
    assert len(recs) == 1 and recs[0]["detail"]["rule"] == "slo_ttft"
    assert audit.verify_chain()["ok"]           # appending kept the chain
    fam = reg.family("monitor_alerts_total")
    assert sum(m.value for m in fam.values()) == 1


def test_action_bus_dispatches_tagged_alerts():
    rule = StormRule("tamper_storm", "tamper", 1, 8, action=ACT_QUARANTINE)
    audit = AuditLog(KEY)
    mon = Monitor(rules=[rule], audit=audit)
    seen = []
    mon.on(ACT_QUARANTINE, lambda alert: seen.append(alert.tenant))
    audit.append("tamper", tenant="mallory", rid=0)
    mon.observe(1)
    assert seen == ["mallory"]
    assert mon.alerts_of("tamper_storm", tenant="mallory")


def test_monitor_config_overrides_and_cli_parse():
    cfg = MonitorConfig().overridden(ttft_p95_ms=250.0, cooldown_steps=8)
    assert cfg.ttft_p95_ms == 250.0 and cfg.cooldown_steps == 8
    with pytest.raises(ValueError):
        MonitorConfig().overridden(not_a_field=1)
    # CLI parse coerces to the field's declared type
    kv = parse_slo_overrides(["ttft_p95_ms=250", "tamper_storm_count=5"])
    assert kv == {"ttft_p95_ms": 250.0, "tamper_storm_count": 5}
    assert isinstance(kv["tamper_storm_count"], int)
    with pytest.raises(ValueError):
        parse_slo_overrides(["nope=1"])
    with pytest.raises(ValueError):
        parse_slo_overrides(["ttft_p95_ms"])


# ---------------------------------------------------------------------------
# gateway integration: alert-driven actions
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_config("granite-3-2b", smoke=True)
    params = registry.get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = {t: rng.randint(0, cfg.vocab, n).astype(np.int32)
               for t, n in PROMPT_LENS.items()}
    return cfg, params, prompts


@pytest.fixture(scope="module")
def reference(setup):
    """Fixed-slot engine outputs — the bitwise ground truth."""
    cfg, params, prompts = setup
    eng = ServeEngine(cfg=cfg, params=params, channel=SecureChannel.insecure(),
                      max_len=PAGE * MAXP)
    return {t: eng.generate({"tokens": p[None]}, n_new=N_NEW)[0]
            for t, p in prompts.items()}


def test_tamper_storm_quarantines_only_the_offending_tenant(setup, reference):
    cfg, params, prompts = setup
    gw = SecureGateway(cfg, params, security="trusted", max_slots=3,
                       page_size=PAGE, n_pages=32, max_pages_per_seq=MAXP,
                       monitor_config=MonitorConfig(tamper_storm_count=2,
                                                    tamper_storm_window=64,
                                                    cooldown_steps=4))
    # two mallory requests whose pages get corrupted, two honest tenants
    rng = np.random.RandomState(7)
    mallory = [gw.submit("mallory", rng.randint(0, cfg.vocab, 7),
                         max_new=N_NEW) for _ in range(2)]
    honest = {t: gw.submit(t, prompts[t], max_new=N_NEW)
              for t in ("alice", "bob")}
    gw.step()                                   # admit + prefill
    for rid in mallory:
        page = gw.scheduler.requests[rid].pages[0]
        idx = gw.pool.elem_index(page, 0, 0, 0, 0)
        gw.pool.k_ct = gw.pool.k_ct.at[idx].add(1)
    gw.drain()

    # the storm fired, attributed to mallory, and the handler quarantined it
    storm = gw.monitor.alerts_of("tamper_storm", tenant="mallory")
    assert storm and storm[0].severity == CRITICAL
    assert gw.quarantined() == ["mallory"]
    for rid in mallory:
        assert gw.status(rid) == "poisoned"
    # admission is now refused — and the refusal is audited
    with pytest.raises(TenantQuarantined):
        gw.submit("mallory", rng.randint(0, cfg.vocab, 5), max_new=2)
    assert gw.audit.records_of("quarantine_reject")

    # owner-only blast radius: honest tenants' tokens are bitwise-unchanged
    for t, rid in honest.items():
        assert gw.status(rid) == "done"
        np.testing.assert_array_equal(np.asarray(gw.collect(rid)),
                                      np.asarray(reference[t]))

    # the quarantine decision itself is in the verified chain
    q = gw.audit.records_of("quarantine")
    assert [r["tenant"] for r in q] == ["mallory"]
    assert q[0]["detail"]["reason"] == "tamper_storm"
    assert gw.verify_audit()["ok"]
    assert gw.monitor.posture()["mallory"]["quarantined"]

    # release: mallory can serve again (fresh requests complete cleanly)
    assert gw.release_quarantine("mallory")
    assert gw.quarantined() == []
    rid = gw.submit("mallory", rng.randint(0, cfg.vocab, 5), max_new=2)
    gw.drain()
    assert gw.status(rid) == "done"
    assert gw.audit.records_of("quarantine_release")
    assert gw.verify_audit()["ok"]


def test_occupancy_alert_drives_proactive_spill(setup, reference):
    cfg, params, prompts = setup
    # watermark set absurdly low so the burn-rate rule trips mid-drain
    gw = SecureGateway(cfg, params, security="trusted", max_slots=3,
                       page_size=PAGE, n_pages=32, max_pages_per_seq=MAXP,
                       monitor_config=MonitorConfig(occupancy_high_pct=5.0,
                                                    occupancy_window=2,
                                                    cooldown_steps=8))
    rids = {t: gw.submit(t, p, max_new=N_NEW) for t, p in prompts.items()}
    gw.drain()
    assert gw.monitor.alerts_of("occupancy_watermark")
    spills = gw.audit.records_of("proactive_spill")
    assert spills and gw.metrics()["swap_outs"] >= len(spills)
    # a proactive swap round-trip is verbatim: tokens are bitwise-identical
    for t, rid in rids.items():
        assert gw.status(rid) == "done"
        np.testing.assert_array_equal(np.asarray(gw.collect(rid)),
                                      np.asarray(reference[t]))
    assert gw.verify_audit()["ok"]


def test_nonce_headroom_alert_renonces_open_pages(setup, reference):
    cfg, params, prompts = setup
    # floor raised above the fresh-page budget: every live open tail fires,
    # forcing the early close -> re-seal-under-fresh-lane -> reopen path
    gw = SecureGateway(cfg, params, security="trusted", max_slots=3,
                       page_size=PAGE, n_pages=32, max_pages_per_seq=MAXP,
                       monitor_config=MonitorConfig(nonce_headroom_min=9,
                                                    cooldown_steps=8))
    rids = {t: gw.submit(t, p, max_new=N_NEW) for t, p in prompts.items()}
    gw.drain()
    assert gw.monitor.alerts_of("nonce_headroom")
    renonces = gw.audit.records_of("page_renonce")
    assert renonces and all(r["detail"]["ok"] for r in renonces)
    assert gw.audit.records_of("nonce_refresh")
    # re-sealing under a fresh lane never touches plaintext: bitwise-equal
    for t, rid in rids.items():
        assert gw.status(rid) == "done"
        np.testing.assert_array_equal(np.asarray(gw.collect(rid)),
                                      np.asarray(reference[t]))
    assert gw.verify_audit()["ok"]


def test_manual_quarantine_refuses_provider(setup):
    cfg, params, prompts = setup
    gw = SecureGateway(cfg, params, security="trusted", max_slots=2,
                       page_size=PAGE, n_pages=32, max_pages_per_seq=MAXP,
                       monitor=False)
    assert gw.monitor is None                   # opt-out leaves no monitor
    with pytest.raises(ValueError):
        gw.quarantine(PROVIDER)
    rid = gw.submit("alice", prompts["alice"], max_new=2)
    gw.quarantine("alice", reason="operator")
    assert gw.status(rid) == "quarantined"
    with pytest.raises(TenantQuarantined):
        gw.submit("alice", prompts["alice"], max_new=2)
    assert not gw.release_quarantine("bob")     # never quarantined
    assert gw.release_quarantine("alice")


# ---------------------------------------------------------------------------
# tools/bench_diff.py — the CI perf-regression gate
# ---------------------------------------------------------------------------

def _serve_artifact(ttft=50.0, tps=100.0, sealed=2048.0):
    metrics = {"tok_per_s": tps, "p50_token_ms": 10.0, "p95_token_ms": 20.0,
               "mean_ttft_ms": ttft, "sealed_bytes_per_token": sealed}
    return {"benchmark": "serve_gateway",
            "grid": [{"mode": "trusted", "scenario": "steady",
                      "metrics": dict(metrics)}],
            "burst": [{"write_back": "open-page", "prefill_chunk": 8,
                       "metrics": {"mean_ttft_ms": ttft,
                                   "sealed_bytes_per_token": sealed / 4}}]}


def _bench_diff(tmp_path, base, cur, *extra):
    bp, cp = tmp_path / "base.json", tmp_path / "cur.json"
    bp.write_text(json.dumps(base))
    cp.write_text(json.dumps(cur))
    return subprocess.run(
        [sys.executable, str(ROOT / "tools" / "bench_diff.py"),
         str(bp), str(cp), *map(str, extra)],
        capture_output=True, text=True)


def test_bench_diff_identical_inputs_pass(tmp_path):
    art = _serve_artifact()
    proc = _bench_diff(tmp_path, art, art)
    assert proc.returncode == 0, proc.stderr
    assert "0 regression(s)" in proc.stdout


def test_bench_diff_catches_a_20pct_ttft_regression(tmp_path):
    proc = _bench_diff(tmp_path, _serve_artifact(ttft=50.0),
                       _serve_artifact(ttft=60.0))        # +20% vs 10% band
    assert proc.returncode == 1
    assert "REGRESSION" in proc.stdout and "mean_ttft_ms" in proc.stdout
    # a wider per-metric band waves the same delta through
    proc = _bench_diff(tmp_path, _serve_artifact(ttft=50.0),
                       _serve_artifact(ttft=60.0), "--tol",
                       "mean_ttft_ms=0.5")
    assert proc.returncode == 0


def test_bench_diff_throughput_direction_is_higher_better(tmp_path):
    # +20% tok/s is an improvement, not a regression
    assert _bench_diff(tmp_path, _serve_artifact(tps=100.0),
                       _serve_artifact(tps=120.0)).returncode == 0
    proc = _bench_diff(tmp_path, _serve_artifact(tps=100.0),
                       _serve_artifact(tps=50.0))
    assert proc.returncode == 1 and "tok_per_s" in proc.stdout


def test_bench_diff_missing_row_and_report(tmp_path):
    cur = _serve_artifact()
    cur["burst"] = []                                     # row vanished
    proc = _bench_diff(tmp_path, _serve_artifact(), cur,
                       "--report", tmp_path / "diff.json")
    assert proc.returncode == 1 and "MISSING" in proc.stdout
    rep = json.loads((tmp_path / "diff.json").read_text())
    assert rep["ok"] is False
    assert any(c["status"] == "missing" for c in rep["comparisons"])
    statuses = {(c["row"], c["metric"]): c["status"]
                for c in rep["comparisons"]}
    assert statuses[("trusted/steady", "tok_per_s")] == "ok"


def test_bench_diff_kind_mismatch_is_a_usage_error(tmp_path):
    micro = {"benchmark": "micro",
             "rows": [{"name": "seal", "us_per_call": 5.0}]}
    proc = _bench_diff(tmp_path, _serve_artifact(), micro)
    assert proc.returncode == 2 and "mismatch" in proc.stderr


def test_bench_diff_micro_artifacts(tmp_path):
    base = {"benchmark": "micro",
            "rows": [{"name": "seal_page", "us_per_call": 5.0},
                     {"name": "mac", "us_per_call": 2.0}]}
    cur = {"benchmark": "micro",
           "rows": [{"name": "seal_page", "us_per_call": 5.2},
                    {"name": "mac", "us_per_call": 9.0}]}
    proc = _bench_diff(tmp_path, base, cur)
    assert proc.returncode == 1
    assert "mac" in proc.stdout and "REGRESSION" in proc.stdout
    assert _bench_diff(tmp_path, base, base, "-q").stdout == ""


# ---------------------------------------------------------------------------
# tools/obs_dash.py — offline posture snapshot
# ---------------------------------------------------------------------------

def test_obs_dash_cli_renders_files(tmp_path):
    reg = MetricsRegistry()
    reg.counter("gateway_steps_total", "steps").inc(12)
    reg.counter("tokens_total", "", tenant="alice").inc(40)
    h = reg.histogram("request_ttft_ms", "ttft")
    for v in (80.0, 120.0, 300.0):
        h.observe(v)
    (tmp_path / "m.prom").write_text(reg.to_prometheus())
    audit = AuditLog(KEY)
    audit.append("attest", tenant="alice", device="d0")
    audit.append("tamper", tenant="mallory", rid=3)
    audit.append("quarantine", tenant="mallory", reason="tamper_storm")
    audit.to_jsonl(tmp_path / "a.jsonl")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_dash.py"),
         str(tmp_path / "m.prom"), str(tmp_path / "a.jsonl"),
         "--slo", "ttft_p95_ms=100"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "mallory" in proc.stdout and "QUARANTINED" in proc.stdout
    assert "BREACH" in proc.stdout              # p95=300 vs bound 100
    # metrics only, no audit file
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_dash.py"),
         str(tmp_path / "m.prom")], capture_output=True, text=True)
    assert proc.returncode == 0 and "alice" in proc.stdout
    # unreadable input is a usage error, not a traceback
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_dash.py"),
         str(tmp_path / "nope.prom")], capture_output=True, text=True)
    assert proc.returncode == 2 and "Traceback" not in proc.stderr
