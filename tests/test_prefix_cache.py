"""Sealed prefix cache: adversarial cross-tenant battery + bitwise property.

The sharing layer (serve/prefix_cache.py + refcounted shared pages in
serve/kv_pager.py) changes the trust story of the whole paged path — one
page may now sit in many tenants' page tables under a provider-side key.
This module is the proof obligations of ISSUE 8:

  * tampering a shared page poisons only requests currently mapped to it,
    never an unrelated tenant;
  * a tenant's session key cannot unwrap another prefix's page key, and a
    wrong unwrap poisons (fails the MAC) at the copy-on-write break;
  * a quarantined tenant's drain never frees or corrupts shared pages
    still referenced by others;
  * COW-broken pages are unaffected by later tampering of the original;
  * shared-prefix token streams are bitwise-identical to the unshared
    baseline at every divergence offset (mid-page, page boundary,
    zero-length suffix), including under forced preemption of the
    private suffix pages;
  * the refcount lifecycle never double-frees or leaks, and the store
    dedups byte-identical sealed prefix pages to one object id;
  * prefix_publish / prefix_map / cow_break verify in the audit chain
    (offline, via tools/verify_audit.py).

Like test_serve_gateway.py, the module shares one jitted gateway pair
(shared + unshared baseline); tests use distinct prefixes so earlier
tampering never contaminates later entries.
"""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # tier-1 container has no hypothesis — deterministic shim
    from _hypothesis_stub import given, settings, strategies as st

from repro import configs
from repro.core import channel as channel_lib
from repro.models import registry
from repro.obs import MonitorConfig
from repro.serve import (PagedKVPool, SecureGateway, TOKEN_POISON,
                         TenantQuarantined)

ROOT = Path(__file__).resolve().parents[1]

PAGE = 8
MAXP = 4
N_NEW = 4


def _mk_gateway(cfg, params):
    # tamper_storm_count=0: this module injects tampering on purpose; the
    # storm-quarantine path has its own tests in test_monitor.py
    return SecureGateway(cfg, params, security="trusted", max_slots=3,
                         page_size=PAGE, n_pages=32, max_pages_per_seq=MAXP,
                         monitor_config=MonitorConfig(tamper_storm_count=0))


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_config("granite-3-2b", smoke=True)
    params = registry.get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def gw(setup):
    """The gateway under test — prefixes are registered here."""
    return _mk_gateway(*setup)


@pytest.fixture(scope="module")
def gw0(setup):
    """Unshared baseline gateway: same config, no prefix ever registered."""
    return _mk_gateway(*setup)


def _tokens(seed, n, vocab):
    return np.random.RandomState(seed).randint(0, vocab, n).astype(np.int32)


def _baseline(gw0, tenant, prompt, max_new=N_NEW):
    rid = gw0.submit(tenant, prompt, max_new)
    return gw0.collect(rid)


# ---------------------------------------------------------------------------
# refcount lifecycle (pool-level, no jit — cheap)
# ---------------------------------------------------------------------------

def test_refcount_churn_no_double_free_no_leak():
    pool = PagedKVPool(n_pages=16, page_size=4, n_layers=2, n_kv_heads=2,
                       hd=8, dtype=jnp.float32)
    free0 = pool.free_pages
    key = np.array([7, 9], np.uint32)
    shared = pool.alloc(3, "_prefix", key, [100, 101, 102])
    pool.make_shared(shared)
    # free() must refuse shared pages outright — mixing them into a private
    # free list is the double-free that corrupts other tenants
    with pytest.raises(ValueError):
        pool.free(shared)
    rng = np.random.RandomState(1)
    live = []
    for i in range(40):                     # map/unmap churn across "requests"
        if live and rng.rand() < 0.5:
            pool.unmap_shared(live.pop())
        else:
            pool.map_shared(shared)
            live.append(list(shared))
    refs = {p: pool.ref_count(p) for p in shared}
    assert all(r == len(live) for r in refs.values())
    # unmap below zero is a lifecycle bug, not a silent decrement
    extra = PagedKVPool(n_pages=8, page_size=4, n_layers=1, n_kv_heads=1,
                        hd=4, dtype=jnp.float32)
    with pytest.raises(ValueError):
        extra.unmap_shared([1])
    # publisher release while mapped: deferred until the last reader drains
    pool.release_shared(shared)
    assert pool.free_pages == free0 - 3 - 0  # still resident
    while live:
        pool.unmap_shared(live.pop())
    assert pool.free_pages == free0          # no leak, no double-free
    assert not pool.shared_pages
    for p in shared:
        assert pool.owner_of(p) is None


def test_release_unmapped_frees_immediately():
    pool = PagedKVPool(n_pages=8, page_size=4, n_layers=1, n_kv_heads=1,
                       hd=4, dtype=jnp.float32)
    free0 = pool.free_pages
    pages = pool.alloc(2, "_prefix", np.array([1, 2], np.uint32), [5, 6])
    pool.make_shared(pages)
    pool.release_shared(pages)
    assert pool.free_pages == free0


# ---------------------------------------------------------------------------
# key-wrap isolation (trusted-side unit + poisoned COW semantics)
# ---------------------------------------------------------------------------

def test_wrap_key_words_roundtrip_and_isolation():
    kw = np.array([0xDEAD, 0xBEEF], np.uint32)
    ka, kb = b"alice-key-bytes!", b"bob-key-bytes!!!"
    ctx = b"prefix/1|tenant/alice"
    wrapped = channel_lib.wrap_key_words(kw, ka, ctx)
    np.testing.assert_array_equal(
        channel_lib.unwrap_key_words(wrapped, ka, ctx), kw)
    # wrong tenant key -> garbage words
    assert not np.array_equal(
        channel_lib.unwrap_key_words(wrapped, kb, ctx), kw)
    # right key, transplanted context (another prefix) -> garbage words
    assert not np.array_equal(
        channel_lib.unwrap_key_words(wrapped, ka, b"prefix/2|tenant/alice"),
        kw)


def test_session_key_cannot_unwrap_other_prefix(setup, gw):
    cfg, _ = setup
    gw.register_tenant("alice")
    gw.register_tenant("bob")
    e1 = gw.register_prefix(_tokens(11, 10, cfg.vocab))
    e2 = gw.register_prefix(_tokens(12, 10, cfg.vocab))
    wrapped = gw.prefixes.wrap_for(e1, "alice")
    ch_a = gw.sessions.channel("alice")
    ch_b = gw.sessions.channel("bob")
    ctx1 = gw.prefixes.wrap_context(e1.prefix_id, "alice")
    np.testing.assert_array_equal(
        channel_lib.unwrap_key_words(wrapped, ch_a.key_bytes, ctx1),
        e1.key_words)
    # bob's session key on alice's wrap: garbage
    assert not np.array_equal(
        channel_lib.unwrap_key_words(wrapped, ch_b.key_bytes, ctx1),
        e1.key_words)
    # alice's own wrap for e1 does not open e2
    assert not np.array_equal(
        channel_lib.unwrap_key_words(
            wrapped, ch_a.key_bytes,
            gw.prefixes.wrap_context(e2.prefix_id, "alice")),
        e2.key_words)
    # a COW attempted under the wrong unwrap fails its MAC and the
    # destination page is poisoned, not silently plausible
    ps = gw.pool.page_size
    dst = gw.pool.alloc(1, "bob", ch_b.key_words,
                        [ch_b.fresh_nonce(span=ps + 2)], span=ps + 2)[0]
    gw.pool.map_shared([e1.pages[-1]])
    bad_key = channel_lib.unwrap_key_words(wrapped, ch_b.key_bytes, ctx1)
    assert not gw.engine.cow_page(e1.pages[-1], dst, bad_key, e1.tail_fill)
    gw.pool.unmap_shared([e1.pages[-1]])
    gw.pool.free([dst])
    for e in (e1, e2):
        assert gw.prefixes.evict(e.prefix_id)
    assert gw.pool.live_pages == 0


# ---------------------------------------------------------------------------
# registration: idempotency + content-hash dedup
# ---------------------------------------------------------------------------

def test_register_idempotent_same_object_id(setup, gw):
    cfg, _ = setup
    toks = _tokens(21, 12, cfg.vocab)
    free0 = gw.pool.free_pages
    e1 = gw.register_prefix(toks)
    n_objects = len(gw.store.objects(kind="prefix"))
    e2 = gw.register_prefix(toks)            # byte-identical prefix
    assert e2.prefix_id == e1.prefix_id
    assert e2.object_id == e1.object_id      # dedup: one sealed object
    assert len(gw.store.objects(kind="prefix")) == n_objects
    assert e1.object_id.startswith("prefix/")
    man = gw.store.manifest(e1.object_id)
    assert man["kind"] == "prefix" and man["pinned"]
    assert man["tenant_id"] == "_prefix"
    assert gw.prefixes.evict(e1.prefix_id)
    assert gw.pool.free_pages == free0
    assert not gw.store.exists(e1.object_id)
    assert not gw.prefixes.evict(e1.prefix_id)   # second evict is a no-op


def test_reserved_prefix_tenant_is_guarded(gw):
    with pytest.raises(ValueError):
        gw.register_tenant("_prefix")
    with pytest.raises(ValueError):
        gw.quarantine("_prefix")


# ---------------------------------------------------------------------------
# adversarial: shared-page tamper blast radius
# ---------------------------------------------------------------------------

def test_shared_tamper_poisons_only_mapped_requests(setup, gw, gw0):
    """Flipping a bit of a shared prefix page NaN-poisons the requests whose
    page tables map it — and no one else."""
    cfg, _ = setup
    prefix = _tokens(31, 16, cfg.vocab)              # 2 full pages, no tail
    other = _tokens(32, 9, cfg.vocab)                # unrelated prompt
    ref_other = _baseline(gw0, "noah", other)
    entry = gw.register_prefix(prefix)
    rid_hit = gw.submit("alice", prefix, N_NEW)      # maps the shared pages
    rid_other = gw.submit("noah", other, N_NEW)      # private pages only
    gw.step()                                        # both decoding
    assert gw.scheduler.requests[rid_hit].shared_mapped
    page = entry.pages[0]
    assert gw.pool.ref_count(page) == 1
    idx = gw.pool.elem_index(page, 0, 0, 0, 0)
    gw.pool.k_ct = gw.pool.k_ct.at[idx].add(1)
    gw.drain()
    assert gw.status(rid_hit) == "poisoned"
    assert gw.scheduler.requests[rid_hit].tokens_out[-1] == TOKEN_POISON
    assert gw.status(rid_other) == "done"
    np.testing.assert_array_equal(gw.collect(rid_other), ref_other)
    # the poisoned request's drain dropped its mapping but the shared pages
    # themselves survive (for better or worse — they are the publisher's)
    assert gw.pool.ref_count(page) == 0
    assert gw.pool.is_shared(page)
    assert gw.prefixes.evict(entry.prefix_id)
    assert gw.pool.live_pages == 0


def test_quarantine_drain_never_frees_shared_pages(setup, gw, gw0):
    """Quarantining a tenant mid-decode drops its mappings only; a second
    tenant keeps decoding over the same shared pages, bitwise-identical."""
    cfg, _ = setup
    prefix = _tokens(41, 16, cfg.vocab)
    prompt_b = np.concatenate([prefix, _tokens(42, 4, cfg.vocab)])
    ref_b = _baseline(gw0, "bella", prompt_b)
    entry = gw.register_prefix(prefix)
    rid_a = gw.submit("axel", prefix, N_NEW)
    rid_b = gw.submit("bella", prompt_b, N_NEW)
    gw.step()
    shared = entry.pages[:entry.n_full]
    assert all(gw.pool.ref_count(p) == 2 for p in shared)
    dropped = gw.quarantine("axel", reason="test")
    assert rid_a in dropped
    # axel's drain returned his mapping and private pages — nothing shared
    assert all(gw.pool.ref_count(p) == 1 for p in shared)
    assert all(gw.pool.owner_of(p) == "_prefix" for p in shared)
    with pytest.raises(TenantQuarantined):
        gw.submit("axel", prefix, N_NEW)
    gw.drain()
    assert gw.status(rid_b) == "done"
    np.testing.assert_array_equal(gw.collect(rid_b), ref_b)
    gw.release_quarantine("axel")
    assert gw.prefixes.evict(entry.prefix_id)
    assert gw.pool.live_pages == 0


def test_cow_broken_page_immune_to_later_tamper(setup, gw, gw0):
    """After the divergence page is copied-on-write under the tenant's key,
    tampering the shared ORIGINAL cannot reach it — only tenants who map
    the original afterwards are poisoned."""
    cfg, _ = setup
    prefix = _tokens(51, 11, cfg.vocab)          # 1 full page + 3-token tail
    ref = _baseline(gw0, "cora", prefix)
    entry = gw.register_prefix(prefix)
    assert entry.tail_fill == 3
    rid_a = gw.submit("cora", prefix, N_NEW)     # zero suffix -> COW at admit
    gw.step()
    req_a = gw.scheduler.requests[rid_a]
    cow_page = req_a.pages[req_a.n_shared]       # her private COW'd tail
    assert gw.pool.owner_of(cow_page) == "cora"
    assert gw.pool.ref_count(entry.tail_page) == 0   # tail mapped only for COW
    # now corrupt the shared original tail
    idx = gw.pool.elem_index(entry.tail_page, 0, 0, 0, 0)
    gw.pool.k_ct = gw.pool.k_ct.at[idx].add(1)
    gw.drain()
    assert gw.status(rid_a) == "done"
    np.testing.assert_array_equal(gw.collect(rid_a), ref)   # unaffected
    # a later tenant COWing from the tampered original is poisoned — the
    # unseal under the (correct) prefix key fails its MAC
    rid_b = gw.submit("dina", prefix, N_NEW)
    gw.drain()
    assert gw.status(rid_b) == "poisoned"
    kinds = gw.audit.kinds()
    assert kinds.get("cow_break", 0) >= 2
    assert gw.prefixes.evict(entry.prefix_id)
    assert gw.pool.live_pages == 0


# ---------------------------------------------------------------------------
# property: bitwise equivalence at every divergence offset, incl. preemption
# ---------------------------------------------------------------------------

_CASES = [
    # (prefix_len, suffixes) — suffix 0 = zero-length private suffix (COW
    # when the prefix has an open tail), >0 diverges right after the prefix
    # (mid-page when the prefix is misaligned, exact page boundary when it
    # is a multiple of PAGE)
    (8, (0, 5)),        # aligned: boundary divergence + zero suffix
    (10, (0, 6)),       # misaligned: mid-page divergence + zero suffix (COW)
    (16, (0, 3)),       # two full pages: boundary + zero suffix
    (13, (0, 7)),       # misaligned, long tail
]


def test_shared_prefix_bitwise_property(setup, gw, gw0):
    """Property: for random prefixes and every divergence offset (mid-page,
    exact page boundary, zero-length suffix), tenants mapping the shared
    prefix stream bitwise-identical tokens to the unshared baseline —
    including under forced preemption/swap of the private suffix pages.
    The stub runner visits each case twice, so the second pass also proves
    register → evict → re-register of the same bytes is clean."""
    cfg, _ = setup
    baselines: dict = {}        # (case_no, tenant) -> reference stream

    @settings(max_examples=8, deadline=None)
    @given(case_no=st.integers(0, 3))
    def run(case_no):
        plen, suffixes = _CASES[case_no]
        prefix = _tokens(100 + case_no, plen, cfg.vocab)
        free0 = gw.pool.free_pages
        entry = gw.register_prefix(prefix)
        assert entry.n_full == plen // PAGE
        rids = {}
        for k, slen in enumerate(suffixes):
            tenant = f"t{case_no}_{k}"
            prompt = (prefix if slen == 0 else np.concatenate(
                [prefix, _tokens(200 + 10 * case_no + k, slen, cfg.vocab)]))
            if (case_no, tenant) not in baselines:
                baselines[(case_no, tenant)] = _baseline(
                    gw0, tenant, prompt, max_new=3)
            rids[tenant] = gw.submit(tenant, prompt, max_new=3)
        gw.step()
        # force preemption of private suffix pages mid-flight; the shared
        # mapping must ride out the swap untouched
        spilled = gw.scheduler.proactive_spill()
        if spilled is not None:
            vreq = gw.scheduler.requests[spilled]
            assert len(vreq.pages) == vreq.n_shared     # only private spilled
            if vreq.n_shared:
                assert all(gw.pool.ref_count(p) > 0 for p in vreq.pages)
        gw.drain()
        for tenant, rid in rids.items():
            assert gw.status(rid) == "done", (case_no, tenant)
            np.testing.assert_array_equal(
                gw.collect(rid), baselines[(case_no, tenant)],
                err_msg=f"case {case_no} {tenant}")
        assert gw.prefixes.evict(entry.prefix_id)
        assert gw.pool.free_pages == free0, f"case {case_no} leaked pages"

    run()


# ---------------------------------------------------------------------------
# audit chain: prefix kinds verify offline
# ---------------------------------------------------------------------------

def test_prefix_audit_events_verify_offline(gw, tmp_path):
    """prefix_publish / prefix_map / cow_break are chained records: the
    exported log verifies via tools/verify_audit.py (exit 0) and breaks
    (exit != 0) if a prefix record is doctored."""
    import json
    kinds = gw.audit.kinds()
    for kind in ("prefix_publish", "prefix_map", "cow_break"):
        assert kinds.get(kind, 0) >= 1, f"no {kind} record emitted"
    assert gw.verify_audit()["ok"]
    jl, key = tmp_path / "audit.jsonl", tmp_path / "audit.key"
    gw.export_audit(jl, key)
    run = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "verify_audit.py"),
         str(jl), str(key)], capture_output=True, text=True)
    assert run.returncode == 0, run.stdout + run.stderr
    # doctor the first prefix_publish record -> chain must break
    lines = jl.read_text().splitlines()
    for i, line in enumerate(lines):
        rec = json.loads(line)
        if rec.get("kind") == "prefix_publish":
            rec["detail"]["object"] = "prefix/forged"
            lines[i] = json.dumps(rec)
            break
    bad = tmp_path / "doctored.jsonl"
    bad.write_text("\n".join(lines) + "\n")
    run = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "verify_audit.py"),
         str(bad), str(key)], capture_output=True, text=True)
    assert run.returncode != 0
