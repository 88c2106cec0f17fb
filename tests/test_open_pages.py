"""Open-page (slice-sealed) KV cache + chunked batched prefill.

Covers the §3.4 cost-model path end to end:

  * slice sealing is sound: a slot sealed alone is bit-identical to the
    matching slice of a whole-page seal (positional CTR keystream);
  * lifecycle: open -> append slots -> close (page-close MAC) -> reopen;
  * the gateway in open-page mode emits token streams bitwise-identical to
    the legacy whole-page-reseal gateway AND to the fixed-slot reference,
    while sealing >= 4x fewer bytes per decode token at page_size 8;
  * tamper containment: a flipped bit inside an open page's written slot
    poisons only the owner; replaying a closed page's pre-close
    (ciphertext, slice tags) fails the page-close MAC;
  * swap-out of a sequence with an open tail page closes it first and the
    resumed request is bitwise-identical;
  * Rule-3 warm restart: a restarted gateway's register file resumes at the
    persisted last-verified launch nonce instead of 0.

Gateway tests share module-scoped fixtures (the paged graphs are the
expensive part) and are order-dependent like tests/test_serve_gateway.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.channel import SecureChannel
from repro.core.registers import ReplayError
from repro.models import registry
from repro.serve import SecureGateway, ServeEngine, SessionManager, \
    TOKEN_POISON, kv_pager
from repro.store import SealedStore

PAGE = 8
MAXP = 3
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
def reference(setup):
    cfg, params, prompts = setup
    eng = ServeEngine(cfg=cfg, params=params, channel=SecureChannel.insecure(),
                      max_len=PAGE * MAXP)
    return {t: eng.generate({"tokens": p[None]}, n_new=N_NEW)[0]
            for t, p in prompts.items()}


@pytest.fixture(scope="module")
def gw_open(setup):
    cfg, params, _ = setup
    return SecureGateway(cfg, params, security="trusted", max_slots=3,
                         page_size=PAGE, n_pages=32, max_pages_per_seq=MAXP,
                         open_pages=True)


# ---------------------------------------------------------------------------
# crypto units (no engine, cheap)
# ---------------------------------------------------------------------------

def _page_pair(seed, shape=(2, 4, 2, 16)):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32),
            jax.random.normal(jax.random.PRNGKey(seed + 1), shape,
                              jnp.float32))


def test_slice_seal_matches_whole_page_seal(key):
    """A slot sealed alone == the matching slice of a whole-page seal."""
    kp, vp = _page_pair(1)
    Lc, ps, K, hd = kp.shape
    kct, vct, _, _ = kv_pager.seal_page(kp, vp, key, 9, 64)
    for slot in (0, 3):
        kcs, vcs, _, _ = kv_pager.seal_slot(
            kp[:, slot], vp[:, slot], key, 9, slot, ps, 64)
        np.testing.assert_array_equal(np.asarray(kcs),
                                      np.asarray(kct[:, slot]))
        np.testing.assert_array_equal(np.asarray(vcs),
                                      np.asarray(vct[:, slot]))


def test_open_page_lifecycle_and_close_mac(key):
    """Append slots one at a time, verify, close, reopen — and check that
    pre-close slice state is dead after the close (nonce-bound tags)."""
    kp, vp = _page_pair(3)
    Lc, ps, K, hd = kp.shape
    udt = jnp.uint32
    kct = jnp.zeros(kp.shape, udt)
    vct = jnp.zeros(vp.shape, udt)
    kst = jnp.zeros((ps,), jnp.uint32)
    vst = jnp.zeros((ps,), jnp.uint32)
    nonce = jnp.uint32(5)
    for slot in range(ps):
        kcs, vcs, kt, vt = kv_pager.seal_slot(
            kp[:, slot], vp[:, slot], key, nonce, slot, ps, 64)
        kct = kct.at[:, slot].set(kcs)
        vct = vct.at[:, slot].set(vcs)
        kst = kst.at[slot].set(kt)
        vst = vst.at[slot].set(vt)
        assert bool(kv_pager.verify_open_page(kct, vct, kst, vst, key,
                                              nonce, slot + 1, 64))
    # a flipped ciphertext bit in a written slot fails slice verification
    bad = kct.at[0, 2, 0, 0].add(1)
    assert not bool(kv_pager.verify_open_page(bad, vct, kst, vst, key,
                                              nonce, ps, 64))
    # close: page-close MAC under nonce+1, plaintext preserved exactly
    kct2, vct2, ktags, vtags, okc = kv_pager.close_page(
        kct, vct, kst, vst, key, nonce, ps, jnp.float32, 64)
    assert bool(okc)
    k2, v2, ok = kv_pager.unseal_page(kct2, vct2, ktags, vtags, key,
                                      nonce + 1, jnp.float32, 64)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(v2))
    # replaying the pre-close (ciphertext, slice tags) against the closed
    # page fails: the close MAC is what verification consults now
    _, _, ok_r = kv_pager.unseal_page(kct, vct, ktags, vtags, key,
                                      nonce + 1, jnp.float32, 64)
    assert not bool(ok_r)
    # reopen (swap-in path): verify + re-seal under nonce+2 + slice tags
    kct3, vct3, kst3, vst3, oko = kv_pager.reopen_page(
        kct2, vct2, ktags, vtags, key, nonce + 1, jnp.float32, 64)
    assert bool(oko)
    assert bool(kv_pager.verify_open_page(kct3, vct3, kst3, vst3, key,
                                          nonce + 2, ps, 64))
    # a close over tampered slices must not launder the tampered bytes
    # into a validly-MACed closed page
    kct_b, vct_b, ktags_b, vtags_b, okc_b = kv_pager.close_page(
        bad, vct, kst, vst, key, nonce, ps, jnp.float32, 64)
    assert not bool(okc_b)
    _, _, ok_b = kv_pager.unseal_page(kct_b, vct_b, ktags_b, vtags_b, key,
                                      nonce + 1, jnp.float32, 64)
    assert not bool(ok_b)


def test_pool_open_state_alloc_free():
    pool = kv_pager.PagedKVPool(n_pages=8, page_size=4, n_layers=2,
                                n_kv_heads=2, hd=8, dtype=jnp.float32,
                                open_pages=True)
    a = pool.alloc(2, "A", np.array([1, 2], np.uint32), [10, 11], span=6)
    assert bool(pool.open_flags[a[0]]) and int(pool.fill[a[0]]) == 0
    pool.mark_closed([a[0]])
    assert not bool(pool.open_flags[a[0]])
    pool.mark_open([a[0]], fill=3)
    assert bool(pool.open_flags[a[0]]) and int(pool.fill[a[0]]) == 3
    # the nonce-span guard fails closed before keystream could be reused
    from repro.core.sealed import NonceLaneExhausted
    for _ in range(5):
        pool.spend_nonce(a[1])
    with pytest.raises(NonceLaneExhausted):
        pool.spend_nonce(a[1])
    # ...and the budget survives a swap cycle (free + re-alloc with the
    # retained nonces): the accumulated spend carries over, so repeated
    # preemption cannot reset the guard and overflow the reserved lane
    spent = [pool.nonce_spent(p) for p in a]
    assert spent == [0, 5]
    pool.free(a)
    b = pool.alloc(2, "A", np.array([1, 2], np.uint32), [12, 16],
                   span=6, spent=spent)
    with pytest.raises(NonceLaneExhausted):
        pool.spend_nonce(b[1])
    pool.free(b)
    assert not bool(pool.open_flags[a[0]])


# ---------------------------------------------------------------------------
# gateway end-to-end: equivalence + cost
# ---------------------------------------------------------------------------

def test_open_gateway_matches_reference(setup, gw_open, reference):
    cfg, params, prompts = setup
    rids = {t: gw_open.submit(t, p, max_new=N_NEW)
            for t, p in prompts.items()}
    gw_open.drain()
    for t, rid in rids.items():
        np.testing.assert_array_equal(gw_open.collect(rid), reference[t])
    m = gw_open.metrics()
    assert m["page_closes"] >= 1              # at least one page filled
    assert m["prefill_chunks"] >= 1
    assert gw_open.pool.live_pages == 0


def test_legacy_gateway_matches_and_open_seals_4x_less(setup, reference,
                                                       gw_open):
    """The whole-page-reseal baseline emits the same tokens but seals >=4x
    more bytes per decode token (page_size 8) — the §3.4 claim."""
    cfg, params, prompts = setup
    gw_legacy = SecureGateway(cfg, params, security="trusted", max_slots=3,
                              page_size=PAGE, n_pages=32,
                              max_pages_per_seq=MAXP, open_pages=False)
    rids = {t: gw_legacy.submit(t, p, max_new=N_NEW)
            for t, p in prompts.items()}
    gw_legacy.drain()
    for t, rid in rids.items():
        np.testing.assert_array_equal(gw_legacy.collect(rid), reference[t])
    m_legacy = gw_legacy.metrics()
    m_open = gw_open.metrics()
    assert m_open["decode_tokens"] == m_legacy["decode_tokens"]
    assert m_open["sealed_bytes_per_token"] > 0
    ratio = (m_legacy["sealed_bytes_per_token"]
             / m_open["sealed_bytes_per_token"])
    assert ratio >= 4.0, f"sealed-bytes reduction only {ratio:.2f}x"


# ---------------------------------------------------------------------------
# open-page security (order-dependent: reuse the warm gw_open)
# ---------------------------------------------------------------------------

def test_slice_tamper_in_open_page_poisons_only_owner(setup, gw_open,
                                                      reference):
    cfg, params, prompts = setup
    rid_a = gw_open.submit("alice", prompts["alice"], max_new=N_NEW)
    rid_b = gw_open.submit("bob", prompts["bob"], max_new=N_NEW)
    gw_open.step()                       # prefill + first decode
    req_a = gw_open.scheduler.requests[rid_a]
    tail = req_a.pages[req_a.seq_len // PAGE]
    assert bool(gw_open.pool.open_flags[tail])
    fill = int(gw_open.pool.fill[tail])
    assert fill >= 1
    # flip one ciphertext bit inside a *written* slot of the open page
    idx = gw_open.pool.elem_index(tail, 0, fill - 1, 0, 0)
    gw_open.pool.k_ct = gw_open.pool.k_ct.at[idx].add(1)
    gw_open.drain()
    assert gw_open.status(rid_a) == "poisoned"
    assert gw_open.scheduler.requests[rid_a].tokens_out[-1] == TOKEN_POISON
    assert gw_open.status(rid_b) == "done"
    np.testing.assert_array_equal(gw_open.collect(rid_b), reference["bob"])
    assert gw_open.pool.live_pages == 0


def test_replaying_preclose_slice_state_fails(setup, gw_open, reference):
    """Capture an open page's (ciphertext, slice tags), let it close, then
    roll both back: the page-close MAC (bumped nonce) rejects the replay
    and poisons only the owner."""
    cfg, params, prompts = setup
    rid_a = gw_open.submit("alice", prompts["alice"], max_new=N_NEW)
    rid_b = gw_open.submit("bob", prompts["bob"], max_new=N_NEW)
    gw_open.step()
    req_a = gw_open.scheduler.requests[rid_a]
    tail = req_a.pages[0]
    assert bool(gw_open.pool.open_flags[tail])
    pre = {"k_ct": gw_open.pool.k_ct[tail], "v_ct": gw_open.pool.v_ct[tail],
           "k_st": gw_open.pool.k_stags[tail],
           "v_st": gw_open.pool.v_stags[tail]}
    # step until the tail page fills and closes (prompt 6 -> closes once
    # position 7 is written)
    for _ in range(20):
        if not bool(gw_open.pool.open_flags[tail]):
            break
        gw_open.step()
    assert not bool(gw_open.pool.open_flags[tail])   # page-close happened
    assert not req_a.finished
    # the untrusted side rolls the page back to its pre-close state
    gw_open.pool.k_ct = gw_open.pool.k_ct.at[tail].set(pre["k_ct"])
    gw_open.pool.v_ct = gw_open.pool.v_ct.at[tail].set(pre["v_ct"])
    gw_open.pool.k_stags = gw_open.pool.k_stags.at[tail].set(pre["k_st"])
    gw_open.pool.v_stags = gw_open.pool.v_stags.at[tail].set(pre["v_st"])
    gw_open.drain()
    assert gw_open.status(rid_a) == "poisoned"
    assert gw_open.status(rid_b) == "done"
    np.testing.assert_array_equal(gw_open.collect(rid_b), reference["bob"])
    assert gw_open.pool.live_pages == 0


def test_swap_with_open_tail_page_resumes_bitwise_identical(setup, gw_open,
                                                            reference):
    """Mid-decode swap-out with a partially-filled tail page: the page
    closes before export, reopens at swap-in, and the token stream matches
    the uninterrupted reference exactly."""
    cfg, params, prompts = setup
    rid_a = gw_open.submit("alice", prompts["alice"], max_new=N_NEW)
    rid_b = gw_open.submit("carol", prompts["carol"], max_new=N_NEW)
    gw_open.step()                        # prefill + first decode
    req_a = gw_open.scheduler.requests[rid_a]
    assert req_a.seq_len % PAGE != 0      # tail page genuinely open
    tail = req_a.pages[req_a.seq_len // PAGE]
    assert bool(gw_open.pool.open_flags[tail])
    ev = {"preempted": [], "emitted": [], "poisoned": [], "finished": [],
          "admitted": [], "resumed": []}
    gw_open.scheduler._swap_out(req_a, ev)
    assert ev["preempted"] == [rid_a]
    assert req_a.status == "swapped"
    m = gw_open.metrics()
    assert m["page_closes"] >= 1
    gw_open.drain()
    assert req_a.swaps_in >= 1
    assert gw_open.metrics()["page_reopens"] >= 1
    np.testing.assert_array_equal(gw_open.collect(rid_a), reference["alice"])
    np.testing.assert_array_equal(gw_open.collect(rid_b), reference["carol"])
    assert gw_open.pool.live_pages == 0


# ---------------------------------------------------------------------------
# Rule-3 warm restart (no engine, cheap)
# ---------------------------------------------------------------------------

def test_warm_restart_restores_register_nonce_floor():
    """A restarted gateway's device register file must resume at the last
    verified launch nonce — not at 0 accepting any forward nonce."""
    store = SealedStore()
    mgr1 = SessionManager(store=store)
    sess1 = mgr1.register("tenant-a")
    for i in range(5):
        sess1.channel.launch(lambda: None, {"op": "noop", "i": i})
    assert sess1.channel.device_regs.last_nonce == 5
    mgr1.note_launch("tenant-a", n=64)      # crosses the persist threshold
    # ---- restart: fresh manager over the same (untrusted) store --------
    from repro.obs import AuditLog
    mgr2 = SessionManager(store=store)
    audit = AuditLog(b"\x05" * 32)
    mgr2.attach_audit(audit)
    sess2 = mgr2.register("tenant-a")
    assert sess2.channel.device_regs.last_nonce >= 5
    # the warm restore left a chained epoch_advance record for the auditor
    adv = audit.records_of("epoch_advance")
    assert adv and adv[0]["tenant"] == "tenant-a"
    assert adv[0]["detail"]["reg_nonce"] >= 5
    assert audit.verify_chain()["ok"]
    assert sess2.channel.host_regs.nonce >= 5
    # a replayed pre-restart launch stream (nonces 1..5) is stale now
    with pytest.raises(ReplayError):
        sess2.channel.device_regs.commit({"op": "replayed"}, 3, b"\x00" * 32)
    # while fresh launches keep working and advance past the floor
    sess2.channel.launch(lambda: None, {"op": "post-restart"})
    assert sess2.channel.device_regs.last_nonce >= 6


def test_warm_restart_without_store_starts_cold():
    mgr = SessionManager()                   # no store attached
    sess = mgr.register("t")
    assert sess.channel.device_regs.last_nonce == 0
    sess.channel.launch(lambda: None, {"op": "x"})
    assert sess.channel.device_regs.last_nonce == 1
