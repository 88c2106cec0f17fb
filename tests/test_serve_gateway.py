"""Multi-tenant serving gateway: paged sealed KV cache, continuous batching,
per-tenant key isolation, page tamper containment, session rotation.

Tests in this module share one gateway (jit graphs are per-engine, and the
paged decode graph is the expensive part) and are order-dependent: the
equivalence test runs first on a clean pool, the tamper and rotation tests
reuse the warm gateway afterwards.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.channel import SecureChannel
from repro.models import registry
from repro.obs import MonitorConfig
from repro.serve import (PagedKVPool, PoolExhausted, SecureGateway,
                         ServeEngine, SessionManager, TOKEN_POISON,
                         swap_object_id)
from repro.serve import kv_pager

PAGE = 8          # page_size
MAXP = 4          # max pages per sequence -> T = 32
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
def gateway(setup):
    cfg, params, _ = setup
    # tamper_storm_count=0 disables the monitor's auto-quarantine: this
    # module *deliberately* injects tampering against the same tenants over
    # and over, which is exactly the storm the rule exists to catch (the
    # quarantine path has its own tests in test_monitor.py)
    return SecureGateway(cfg, params, security="trusted", max_slots=3,
                         page_size=PAGE, n_pages=32, max_pages_per_seq=MAXP,
                         trace=True,
                         monitor_config=MonitorConfig(tamper_storm_count=0))


@pytest.fixture(scope="module")
def reference(setup):
    """Fixed-slot engine outputs, one request at a time (plain channel)."""
    cfg, params, prompts = setup
    eng = ServeEngine(cfg=cfg, params=params, channel=SecureChannel.insecure(),
                      max_len=PAGE * MAXP)
    return {t: eng.generate({"tokens": p[None]}, n_new=N_NEW)[0]
            for t, p in prompts.items()}


# ---------------------------------------------------------------------------
# pager unit tests (host-side, cheap)
# ---------------------------------------------------------------------------

def test_pool_alloc_free_reuse():
    pool = PagedKVPool(n_pages=8, page_size=4, n_layers=2, n_kv_heads=2,
                       hd=8, dtype=jnp.float32)
    a = pool.alloc(3, "A", np.array([1, 2], np.uint32), [10, 11, 12])
    b = pool.alloc(2, "B", np.array([3, 4], np.uint32), [20, 21])
    assert len(set(a)) == 3 and kv_pager.SCRATCH_PAGE not in a
    assert not set(a) & set(b)
    assert {pool.owner_of(p) for p in a} == {"A"}
    np.testing.assert_array_equal(np.asarray(pool.keys)[a[0]], [1, 2])
    assert int(pool.nonces[a[1]]) == 11
    # free + reuse: the allocator recycles returned pages and un-brands them
    pool.free(a)
    assert pool.owner_of(a[0]) is None
    np.testing.assert_array_equal(np.asarray(pool.keys)[a[0]], [0, 0])
    c = pool.alloc(4, "C", np.array([5, 6], np.uint32), [30, 31, 32, 33])
    assert set(c) & set(a)                   # freed pages get recycled
    assert not set(c) & set(b)               # ...but never B's live pages
    with pytest.raises(PoolExhausted):
        pool.alloc(5, "D", np.array([7, 8], np.uint32), [0] * 5)
    assert pool.stats["allocs"] == 9 and pool.stats["frees"] == 3


def test_page_seal_roundtrip_tamper_replay(key):
    kp = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 2, 16), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 2, 16), jnp.float32)
    kct, vct, ktags, vtags = kv_pager.seal_page(kp, vp, key, 7, 64)
    k2, v2, ok = kv_pager.unseal_page(kct, vct, ktags, vtags, key, 7,
                                      jnp.float32, 64)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(v2))
    # single-bit tamper in the ciphertext -> page fails verification
    bad = kct.at[0, 0, 0, 0].add(1)
    _, _, ok_t = kv_pager.unseal_page(bad, vct, ktags, vtags, key, 7,
                                      jnp.float32, 64)
    assert not bool(ok_t)
    # replay: the page was re-sealed under nonce 8; presenting the stale
    # (ct, tags) pair against the current nonce fails (nonce-bound MAC key)
    _, _, ok_r = kv_pager.unseal_page(kct, vct, ktags, vtags, key, 8,
                                      jnp.float32, 64)
    assert not bool(ok_r)


def test_cross_tenant_key_isolation(key):
    """Tenant B's channel key can neither read nor forge A's sealed pages."""
    key_b = jnp.array([0xB0B, 0xB0B2], jnp.uint32)
    kp = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 2, 16), jnp.float32)
    kct, vct, ktags, vtags = kv_pager.seal_page(kp, kp, key, 5, 64)
    kb, _, ok = kv_pager.unseal_page(kct, vct, ktags, vtags, key_b, 5,
                                     jnp.float32, 64)
    assert not bool(ok)                       # B cannot authenticate A's page
    assert not np.array_equal(np.asarray(kb), np.asarray(kp))  # nor decrypt


# ---------------------------------------------------------------------------
# gateway end-to-end
# ---------------------------------------------------------------------------

def test_three_tenants_mixed_lengths_match_fixed_slot(setup, gateway, reference):
    cfg, params, prompts = setup
    rids = {t: gateway.submit(t, p, max_new=N_NEW)
            for t, p in prompts.items()}
    # one step: everyone admitted (prefill) + first decode at mixed lengths
    gateway.step()
    keyset = {}
    for t, rid in rids.items():
        req = gateway.scheduler.requests[rid]
        assert req.pages, "request should hold pages mid-flight"
        kw = np.asarray(gateway.pool.keys)[req.pages[0]]
        np.testing.assert_array_equal(
            kw, gateway.sessions.channel(t).key_words)   # branded w/ own key
        keyset[t] = tuple(kw)
    assert len(set(keyset.values())) == 3    # three distinct tenant keys
    gateway.drain()
    for t, rid in rids.items():
        out = gateway.collect(rid)
        assert gateway.status(rid) == "done"
        np.testing.assert_array_equal(out, reference[t])
    m = gateway.metrics()
    assert m["tokens"] == 3 * N_NEW and m["tok_per_s"] > 0
    assert m["p95_token_ms"] >= m["p50_token_ms"] > 0
    assert gateway.pool.live_pages == 0      # all pages back in the free list


def test_tampered_page_poisons_only_owner(setup, gateway, reference):
    cfg, params, prompts = setup
    rid_a = gateway.submit("alice", prompts["alice"], max_new=N_NEW)
    rid_b = gateway.submit("bob", prompts["bob"], max_new=N_NEW)
    gateway.step()                            # both admitted + one decode
    req_a = gateway.scheduler.requests[rid_a]
    page = req_a.pages[0]                     # a page holding alice's prompt
    idx = gateway.pool.elem_index(page, 0, 0, 0, 0)
    gateway.pool.k_ct = gateway.pool.k_ct.at[idx].add(1)
    gateway.drain()
    assert gateway.status(rid_a) == "poisoned"
    assert gateway.scheduler.requests[rid_a].tokens_out[-1] == TOKEN_POISON
    # bob is untouched: finishes and matches the clean reference run
    assert gateway.status(rid_b) == "done"
    np.testing.assert_array_equal(gateway.collect(rid_b), reference["bob"])
    assert gateway.pool.live_pages == 0       # poisoned request was evicted


def test_rotation_under_traffic_preserves_output(setup, gateway, reference):
    """Rotate alice's key between requests; results are unchanged and the
    rotation is visible in session state."""
    cfg, params, prompts = setup
    gateway.sessions.rotate_every = 2
    try:
        sess = gateway.sessions.get("alice")
        old_key = np.asarray(sess.channel.key_words).copy()
        old_epoch = sess.channel.epoch
        sess.launches = 10                    # force: rotation is due
        rid = gateway.submit("alice", prompts["alice"], max_new=N_NEW)
        gateway.drain()
        assert sess.rotations >= 1
        assert not np.array_equal(np.asarray(sess.channel.key_words), old_key)
        assert sess.channel.epoch > old_epoch
        np.testing.assert_array_equal(gateway.collect(rid),
                                      reference["alice"])
    finally:
        gateway.sessions.rotate_every = 0


# ---------------------------------------------------------------------------
# preemption: sealed swap-out to the store, swap-in, resume
# ---------------------------------------------------------------------------

def _fill_slots_then_preempt(gateway, prompts):
    """Fill all 3 slots with priority-0 requests, step, then submit a
    priority-5 request ('dave', alice's prompt) and step until it preempts.
    Returns (rids dict, victim rid)."""
    rids = {t: gateway.submit(t, prompts[t], max_new=N_NEW, priority=0)
            for t in ("alice", "bob", "carol")}
    gateway.step()
    rids["dave"] = gateway.submit("dave", prompts["alice"], max_new=N_NEW,
                                  priority=5)
    ev = gateway.step()
    assert len(ev["preempted"]) == 1      # exactly one victim makes room
    victim = ev["preempted"][0]
    assert gateway.status(victim) == "swapped"
    return rids, victim


def test_preempt_swap_resume_bitwise_equal(setup, gateway, reference):
    """A preempted-and-resumed request's token stream is bitwise-identical
    to the same request run without preemption."""
    cfg, params, prompts = setup
    rids, victim = _fill_slots_then_preempt(gateway, prompts)
    vreq = gateway.scheduler.requests[victim]
    assert not vreq.pages                 # pages returned to the pool
    assert gateway.store.exists(swap_object_id(victim))
    man = gateway.store.manifest(swap_object_id(victim))
    assert man["kind"] == "kv_swap" and man["pinned"]
    assert man["tenant_id"] == vreq.tenant_id
    gateway.drain()
    assert vreq.swaps_out >= 1 and vreq.swaps_in >= 1
    for t, rid in rids.items():
        assert gateway.status(rid) == "done"
        ref = reference["alice"] if t == "dave" else reference[t]
        np.testing.assert_array_equal(gateway.collect(rid), ref)
    m = gateway.metrics()
    assert m["swap_outs"] >= 1 and m["swap_ins"] >= 1
    assert m["preempted_requests"] >= 1
    assert m["pool_occupancy_pct"] > 0
    assert gateway.pool.live_pages == 0
    assert gateway.store.objects(kind="kv_swap") == []   # nothing left behind


def test_tampered_swap_object_poisons_only_owner(setup, gateway, reference):
    """Flipping one bit of a swapped-out page in the untrusted store poisons
    the owning request at swap-in — everyone else is untouched."""
    cfg, params, prompts = setup
    rids, victim = _fill_slots_then_preempt(gateway, prompts)
    obj = gateway.store._mem[swap_object_id(victim)]     # the untrusted host
    obj.chunks["k_ct"].reshape(-1)[0] ^= 1
    gateway.drain()
    assert gateway.status(victim) == "poisoned"
    vreq = gateway.scheduler.requests[victim]
    assert vreq.tokens_out[-1] == TOKEN_POISON
    for t, rid in rids.items():
        if rid == victim:
            continue
        assert gateway.status(rid) == "done"
        ref = reference["alice"] if t == "dave" else reference[t]
        np.testing.assert_array_equal(gateway.collect(rid), ref)
    assert gateway.pool.live_pages == 0


def test_stale_swap_replay_poisons_only_owner(setup, gateway, reference):
    """Replaying an *older* swap-out (valid bytes, stale freshness) fails the
    nonce-bound page MAC at swap-in: the retained nonces moved on."""
    import copy
    cfg, params, prompts = setup
    rids, victim = _fill_slots_then_preempt(gateway, prompts)
    vreq = gateway.scheduler.requests[victim]
    stale = copy.deepcopy(
        gateway.store._mem[swap_object_id(victim)].chunks)   # swap #1 bytes
    # let the victim swap back in and make progress (nonces bump on decode)
    toks_at_swap = len(vreq.tokens_out)
    for _ in range(100):
        if vreq.swaps_in >= 1 and len(vreq.tokens_out) > toks_at_swap:
            break
        gateway.step()
    assert vreq.status == "running" and not vreq.finished
    # force a second swap-out, then replay the stale bytes into the store
    ev = {"preempted": []}
    gateway.scheduler._swap_out(vreq, ev)
    assert ev["preempted"] == [victim] and vreq.swaps_out == 2
    gateway.store._mem[swap_object_id(victim)].chunks = stale
    gateway.drain()
    assert gateway.status(victim) == "poisoned"
    for t, rid in rids.items():
        if rid != victim:
            assert gateway.status(rid) == "done"
    assert gateway.pool.live_pages == 0


def test_destroyed_swap_object_poisons_only_owner(setup, gateway, reference):
    """A store that deletes (or reshapes) a swapped-out object is the same
    attacker with a blunter instrument: the owner is poisoned at swap-in,
    the gateway and every other request keep going."""
    cfg, params, prompts = setup
    rids, victim = _fill_slots_then_preempt(gateway, prompts)
    gateway.store.delete(swap_object_id(victim))
    gateway.drain()
    assert gateway.status(victim) == "poisoned"
    assert gateway.scheduler.requests[victim].tokens_out[-1] == TOKEN_POISON
    for t, rid in rids.items():
        if rid != victim:
            assert gateway.status(rid) == "done"
            ref = reference["alice"] if t == "dave" else reference[t]
            np.testing.assert_array_equal(gateway.collect(rid), ref)
    assert gateway.pool.live_pages == 0


def test_oversubscribed_pool_completes_all(setup):
    """Total reserved pages across requests exceed the physical pool; the
    preemptive scheduler swaps sealed KV through the store and every request
    still completes."""
    cfg, params, prompts = setup
    gw = SecureGateway(cfg, params, security="trusted", max_slots=2,
                       page_size=PAGE, n_pages=5, max_pages_per_seq=2)
    rng = np.random.RandomState(7)

    def prompt():
        return rng.randint(0, cfg.vocab, int(rng.randint(5, 12)))

    lo1 = gw.submit("t0", prompt(), max_new=4, priority=0)
    lo2 = gw.submit("t1", prompt(), max_new=4, priority=0)
    gw.step()                              # both admitted: pool now full
    hi1 = gw.submit("t2", prompt(), max_new=4, priority=9)
    hi2 = gw.submit("t3", prompt(), max_new=4, priority=9)
    lo3 = gw.submit("t0", prompt(), max_new=4, priority=0)
    lo4 = gw.submit("t1", prompt(), max_new=4, priority=0)
    all_rids = [lo1, lo2, hi1, hi2, lo3, lo4]
    reserved = sum(gw.scheduler.required_pages(gw.scheduler.requests[r])
                   for r in all_rids)
    assert reserved > gw.pool.n_pages - 1  # genuinely oversubscribed
    gw.drain()
    for rid in all_rids:
        assert gw.status(rid) == "done"
        assert len(gw.scheduler.requests[rid].tokens_out) == 4
    m = gw.metrics()
    assert m["swap_outs"] >= 2 and m["swap_ins"] >= 2
    assert gw.pool.live_pages == 0
    assert gw.store.objects(kind="kv_swap") == []


# ---------------------------------------------------------------------------
# sessions + nonce domains
# ---------------------------------------------------------------------------

def test_session_manager_per_tenant_keys_and_rotation():
    mgr = SessionManager(rotate_every=3)
    a = mgr.register("a")
    b = mgr.register("b")
    assert mgr.register("a") is a            # idempotent (attestation cached)
    assert a.channel.key_bytes != b.channel.key_bytes
    assert a.channel.session_id != b.channel.session_id
    for _ in range(3):
        mgr.note_launch("a")
    assert mgr.rotation_due("a") and not mgr.rotation_due("b")
    old = a.channel.key_bytes
    mgr.rotate("a")
    assert a.channel.key_bytes != old and a.rotations == 1
    assert not mgr.rotation_due("a")         # launch counter reset


def test_nonce_domain_separation_between_channels():
    """Two channels (mis)configured with the SAME key never share a nonce."""
    from repro.core.policy import SecurityConfig
    kw = np.array([1, 2], np.uint32)
    kb = b"k" * 32

    def mk():
        return SecureChannel(key_words=kw, key_bytes=kb,
                             config=SecurityConfig())

    ch1, ch2 = mk(), mk()
    n1 = {ch1.fresh_nonce() for _ in range(200)}
    n2 = {ch2.fresh_nonce() for _ in range(200)}
    assert len(n1) == len(n2) == 200
    assert not n1 & n2                       # session-id lanes are disjoint


def test_nonce_epoch_rolls_on_counter_wrap():
    from repro.core.policy import SecurityConfig
    from repro.core.trust import SecurityError
    ch = SecureChannel(key_words=np.array([1, 2], np.uint32),
                       key_bytes=b"k" * 32, config=SecurityConfig())
    a = ch.fresh_nonce(span=60_000)
    b = ch.fresh_nonce(span=60_000)          # would overflow -> new epoch
    assert (b >> 16 & 0xFF) == (a >> 16 & 0xFF) + 1
    assert (b >> 24) == (a >> 24)            # same session lane
    with pytest.raises(SecurityError):
        ch.fresh_nonce(span=1 << 17)         # span larger than an epoch


# ---------------------------------------------------------------------------
# observability: traces, windowed metrics reset, audit trail
# (these run LAST — they reset the shared gateway's measurement window)
# ---------------------------------------------------------------------------

def test_trace_covers_request_lifecycle(setup, gateway, tmp_path):
    """The shared gateway ran with trace=True: its buffer must hold engine
    phase spans, per-request lifecycle spans on virtual request threads,
    and submit/finish instants — and export as a loadable Chrome trace."""
    import json
    from repro.obs import TID_ENGINE, TID_REQ_BASE
    ev = gateway.tracer.drain()
    names = {e["name"] for e in ev}
    assert {"serve_step", "engine.decode_step", "sched.decode"} <= names
    assert {"submit", "finish", "poison", "swap_out"} <= \
        {e["name"] for e in ev if e["ph"] == "i"}
    spans = [e for e in ev if e["ph"] == "X"]
    req_spans = {e["name"] for e in spans if e["tid"] >= TID_REQ_BASE}
    assert {"queued", "prefill", "decode"} <= req_spans
    assert any(e["name"] == "swapped" for e in spans)     # preemption visible
    assert all(e["dur"] >= 0 for e in spans)
    assert any(e["tid"] == TID_ENGINE for e in spans)
    path = tmp_path / "trace.json"
    n = gateway.export_trace(path, fmt="chrome")
    obj = json.loads(path.read_text())
    assert len(obj["traceEvents"]) == n and obj["displayTimeUnit"] == "ms"


def test_audit_chain_covers_security_events(setup, gateway):
    """Everything security-relevant that happened above left a chained
    record — and the chain still verifies end-to-end."""
    kinds = gateway.audit.kinds()
    for k in ("attest", "launch", "rotate", "nonce_spend",
              "page_close", "swap_out", "swap_in", "tamper"):
        assert kinds.get(k, 0) >= 1, f"missing audit kind {k!r}"
    assert gateway.verify_audit()["ok"]
    # tamper records carry the owning tenant: the page bit-flip poisoned
    # alice, and each swap-object attack poisoned its preemption victim
    recs = gateway.audit.records_of("tamper")
    assert len(recs) >= 3
    assert "alice" in {r["tenant"] for r in recs}


def test_reset_metrics_zeroes_every_windowed_key(setup, gateway):
    """Satellite (c): after reset_metrics(), every exported windowed key
    reads zero — no matter which object owns the underlying metric — while
    lifetime allocator/session facts survive."""
    lifetime = {"elapsed_s", "kv_pages_peak", "kv_pages_free",
                "rotations", "launches_verified", "dispatch_total"}
    before = gateway.metrics()
    assert before["tokens"] > 0 and before["swap_outs"] > 0
    assert gateway.pool.stats["allocs"] > 0
    allocs = gateway.pool.stats["allocs"]
    gateway.reset_metrics()
    m = gateway.metrics()
    for key, val in m.items():
        if key in lifetime:
            continue
        if key == "tokens_per_tenant":
            # label series persist across resets; their counts zero
            assert all(v == 0 for v in val.values()), val
        else:
            assert val == 0, f"windowed key {key!r} = {val!r} after reset"
    # lifetime facts are NOT windowed: they survive the reset
    assert m["kv_pages_peak"] > 0
    assert m["launches_verified"] > 0
    assert gateway.pool.stats["allocs"] == allocs
    assert gateway.pool.stats["peak_live"] > 0


def test_sealing_cost_accounting_under_preemption(setup, gateway, reference):
    """Satellite (d): force a swap-out/in cycle in a fresh measurement
    window and check the §3.4 sealing-cost ledger is self-consistent."""
    cfg, params, prompts = setup
    gateway.reset_metrics()
    audit_before = len(gateway.audit)
    swap_outs0 = gateway.audit.kinds().get("swap_out", 0)
    rids, victim = _fill_slots_then_preempt(gateway, prompts)
    gateway.drain()
    for t, rid in rids.items():
        assert gateway.status(rid) == "done"
        ref = reference["alice"] if t == "dave" else reference[t]
        np.testing.assert_array_equal(gateway.collect(rid), ref)
    m = gateway.metrics()
    page_bytes = gateway.pool.page_bytes
    slot_bytes = gateway.pool.slot_bytes
    assert m["swap_outs"] >= 1 and m["swap_ins"] >= m["swap_outs"]
    # each seal pass reads+writes a whole page: the swap bucket is a
    # multiple of 2*page_bytes and covers at least every reopen (a swap
    # with a page-aligned tail legitimately closes/reopens nothing)
    assert m["sealed_bytes_swap"] % (2 * page_bytes) == 0
    assert m["sealed_bytes_swap"] >= 2 * page_bytes * m["page_reopens"]
    assert m["page_closes"] >= m["page_reopens"]
    if m["page_reopens"]:
        assert m["sealed_bytes_swap"] >= 2 * page_bytes
    # decode bucket: each request's first token comes from prefill, the
    # rest from decode steps (one lane-step per token)
    assert 4 * (N_NEW - 1) <= m["decode_tokens"] <= 4 * N_NEW
    assert m["sealed_bytes_per_token"] == \
        m["sealed_bytes_decode"] / m["decode_tokens"]
    assert m["sealed_bytes_per_token"] >= 2 * slot_bytes
    assert m["sealed_bytes_prefill"] > 0
    # raw swapped ciphertext moves at least one page per swap-out
    assert m["swapped_bytes"] >= m["swap_outs"] * page_bytes
    # the window's swaps are mirrored in the (lifetime) audit log
    assert gateway.audit.kinds()["swap_out"] - swap_outs0 == m["swap_outs"]
    new = gateway.audit.records[audit_before:]
    out = next(r for r in new if r["kind"] == "swap_out")
    assert out["tenant"] == gateway.scheduler.requests[victim].tenant_id
    assert out["detail"]["bytes"] > 0 and out["detail"]["n_pages"] >= 1
    assert gateway.verify_audit()["ok"]


def test_tampered_request_emits_tamper_audit_record(setup, gateway,
                                                    reference):
    """Satellite (d): a poisoned request leaves a chained 'tamper' record
    naming its tenant, while the other tenant finishes clean."""
    cfg, params, prompts = setup
    tamper_before = gateway.audit.kinds().get("tamper", 0)
    rid_a = gateway.submit("alice", prompts["alice"], max_new=N_NEW)
    rid_b = gateway.submit("bob", prompts["bob"], max_new=N_NEW)
    gateway.step()
    page = gateway.scheduler.requests[rid_a].pages[0]
    idx = gateway.pool.elem_index(page, 0, 0, 0, 0)
    gateway.pool.k_ct = gateway.pool.k_ct.at[idx].add(1)
    gateway.drain()
    assert gateway.status(rid_a) == "poisoned"
    assert gateway.status(rid_b) == "done"
    np.testing.assert_array_equal(gateway.collect(rid_b), reference["bob"])
    recs = gateway.audit.records_of("tamper")[tamper_before:]
    assert len(recs) == 1 and recs[0]["tenant"] == "alice"
    assert recs[0]["detail"]["rid"] == rid_a
    assert gateway.verify_audit()["ok"]      # tamper record is chained too


def test_prometheus_exposition_matches_window(setup, gateway):
    text = gateway.metrics_text()
    assert "# TYPE gateway_steps_total counter" in text
    assert "kv_pool_peak_live_pages" in text
    assert "request_ttft_ms_count" in text
    m = gateway.metrics()
    assert f"sched_swap_outs_total {m['swap_outs']}" in text
