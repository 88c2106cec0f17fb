"""The pool stores each page as one row; the sealed bytes are the page's.

``PagedKVPool.k_ct``/``v_ct`` are ``[n_pages, rows, 128]``: a page's
``[L, ps, K, hd]`` elements in row-major order, zero-padded to whole rows.
These tests hold the stored row to the 5-D page, element for
element: the ciphertext, the chunk tags and the slice tags of a closed and
of an open page, and the sealed bytes that spill to the store and come back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import SessionManager, kv_pager
from repro.serve.scheduler import SWAP_KIND, Scheduler, swap_object_id

CHUNK_WORDS = 64
# (n_layers, page_size, n_kv_heads, hd, dtype): whole rows, a padded last
# row, and a bf16 page at the served head width
SHAPES = [(2, 4, 2, 16, jnp.float32), (1, 4, 1, 8, jnp.float32),
          (3, 8, 2, 64, jnp.bfloat16)]
SHAPE_IDS = ["whole-rows", "padded", "bf16-hd64"]


def _pool(shape, n_pages=6):
    nl, ps, kh, hd, dt = shape
    return kv_pager.PagedKVPool(n_pages=n_pages, page_size=ps, n_layers=nl,
                                n_kv_heads=kh, hd=hd, dtype=dt,
                                chunk_words=CHUNK_WORDS)


def _pages(shape, seed, n=1):
    nl, ps, kh, hd, dt = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return tuple(jax.random.normal(k, (n, nl, ps, kh, hd)).astype(dt)
                 for k in ks)


def _bits(x):
    return np.asarray(x).view(np.uint8)


@pytest.mark.parametrize("shape", SHAPES, ids=SHAPE_IDS)
def test_closed_page_row_is_the_page_flattened(key, shape):
    pool = _pool(shape)
    k, v = _pages(shape, 1)
    kct, vct, ktags, vtags = kv_pager.seal_page(k[0], v[0], key, 7,
                                                CHUNK_WORDS)
    pool.write_pages([3], pool.to_rows(kct[None]), pool.to_rows(vct[None]),
                     ktags[None], vtags[None])
    n = kct.size
    assert pool.k_ct.shape == (6,) + pool.row_shape
    assert pool.row_shape[1] == kv_pager.LANES
    for stored, ct in ((pool.k_ct[3], kct), (pool.v_ct[3], vct)):
        flat = np.asarray(stored).reshape(-1)
        np.testing.assert_array_equal(_bits(flat[:n]), _bits(ct.reshape(-1)))
        assert not flat[n:].any()                     # padding stays zero
        np.testing.assert_array_equal(_bits(pool.from_rows(stored)), _bits(ct))
    # every element sits where elem_index says
    _, rows, lanes = pool.elem_index(3, *np.indices(kct.shape))
    np.testing.assert_array_equal(np.asarray(pool.k_ct)[3][rows, lanes],
                                  np.asarray(kct))
    # the stored page verifies under the tags sealed from the 5-D page
    k2, v2, ok = kv_pager.unseal_page(
        pool.from_rows(pool.k_ct[3]), pool.from_rows(pool.v_ct[3]),
        pool.k_tags[3], pool.v_tags[3], key, 7, shape[-1], CHUNK_WORDS)
    assert bool(ok)
    np.testing.assert_array_equal(_bits(k2), _bits(k[0]))
    np.testing.assert_array_equal(_bits(v2), _bits(v[0]))


@pytest.mark.parametrize("shape", SHAPES, ids=SHAPE_IDS)
def test_open_page_slots_land_as_in_the_page(key, shape):
    """Slots written through ``write_slots`` give the row of the 5-D page
    that slot writes into ``[:, slot]`` give; its slice tags, open-page
    verdict and page-close MAC are the 5-D page's."""
    nl, ps, kh, hd, dt = shape
    pool = _pool(shape)
    k, v = _pages(shape, 2)
    nonce, fill = 11, ps - 1
    ref_k = jnp.zeros(pool.page_shape, pool.k_ct.dtype)
    ref_v = jnp.zeros(pool.page_shape, pool.v_ct.dtype)
    ref_k4 = ref_k
    k_ct, v_ct = pool.k_ct, pool.v_ct
    stags = []
    for s in range(fill):
        kcs, vcs, kt, vt = kv_pager.seal_slot(k[0][:, s], v[0][:, s], key,
                                              nonce, s, ps, CHUNK_WORDS)
        ref_k = ref_k.at[:, s].set(kcs)
        ref_v = ref_v.at[:, s].set(vcs)
        ref_k4 = ref_k4.at[:, ps - 1 - s].set(kcs)
        # lanes 0 and 2 write pages 2 and 4; lanes 1 and 3 are idle on the
        # scratch page
        pages = jnp.asarray([2, kv_pager.SCRATCH_PAGE, 4,
                             kv_pager.SCRATCH_PAGE], jnp.int32)
        slots = jnp.asarray([s, 0, ps - 1 - s, 0], jnp.int32)
        k_ct = pool.write_slots(k_ct, pages, slots,
                                jnp.stack([kcs, kcs, kcs, kcs]))
        v_ct = pool.write_slots(v_ct, pages, slots,
                                jnp.stack([vcs, vcs, vcs, vcs]))
        stags.append((kt, vt))
    n = ref_k.size
    for ct, ref in ((k_ct, ref_k), (v_ct, ref_v)):
        flat = np.asarray(ct[2]).reshape(-1)
        np.testing.assert_array_equal(_bits(flat[:n]),
                                      _bits(ref.reshape(-1)))
        assert not flat[n:].any()
        # pages no lane wrote stay as they were
        assert not np.asarray(ct[1]).any() and not np.asarray(ct[5]).any()
    np.testing.assert_array_equal(_bits(pool.from_rows(k_ct[4])),
                                  _bits(ref_k4))
    kp, vp = pool.from_rows(k_ct[2]), pool.from_rows(v_ct[2])
    kst, vst = kv_pager.page_slot_tags(kp, vp, key, nonce, CHUNK_WORDS)
    rkst, rvst = kv_pager.page_slot_tags(ref_k, ref_v, key, nonce,
                                         CHUNK_WORDS)
    np.testing.assert_array_equal(kst, rkst)
    np.testing.assert_array_equal(vst, rvst)
    np.testing.assert_array_equal(kst[:fill], [t[0] for t in stags])
    np.testing.assert_array_equal(vst[:fill], [t[1] for t in stags])
    assert bool(kv_pager.verify_open_page(kp, vp, kst, vst, key, nonce,
                                          fill, CHUNK_WORDS))
    closed = kv_pager.close_page(kp, vp, kst, vst, key, nonce, fill, dt,
                                 CHUNK_WORDS)
    ref_closed = kv_pager.close_page(ref_k, ref_v, rkst, rvst, key, nonce,
                                     fill, dt, CHUNK_WORDS)
    assert bool(closed[-1]) and bool(ref_closed[-1])
    for a, b in zip(closed, ref_closed):
        np.testing.assert_array_equal(_bits(a), _bits(b))
    # a flip at (layer 0, slot fill-1) of the stored row fails the verdict
    bad = k_ct.at[pool.elem_index(2, 0, fill - 1, 0, 0)].add(1)
    assert not bool(kv_pager.verify_open_page(
        pool.from_rows(bad[2]), vp, kst, vst, key, nonce, fill, CHUNK_WORDS))


@pytest.mark.parametrize("shape", SHAPES, ids=SHAPE_IDS)
def test_spilled_pages_come_back_verifiable(shape):
    """export_pages -> sealed store -> the scheduler's shape check ->
    write_pages into fresh pages: the restored rows verify and decrypt."""
    pool = _pool(shape, n_pages=8)
    mgr = SessionManager()
    mgr.register("t")
    ch = mgr.channel("t")
    sched = Scheduler(engine=None, pool=pool, sessions=mgr, max_slots=1,
                      max_pages=4)
    k, v = _pages(shape, 3, n=2)
    nonces = [21, 40]
    pages = pool.alloc(2, "t", ch.key_words, nonces)
    tkey = jnp.asarray(ch.key_words, jnp.uint32)
    sealed = jax.vmap(lambda a, b, n: kv_pager.seal_page(
        a, b, tkey, n, CHUNK_WORDS))(k, v, jnp.asarray(nonces, jnp.uint32))
    pool.write_pages(pages, pool.to_rows(sealed[0]), pool.to_rows(sealed[1]),
                     sealed[2], sealed[3])
    chunks, kept = pool.export_pages(pages)
    assert chunks["k_ct"].shape == (2,) + pool.row_shape
    rid = sched.submit("t", np.arange(4, dtype=np.int32), max_new=1)
    req = sched.requests[rid]
    req.swap_nonces = kept
    sched.store.put(swap_object_id(rid), "t", chunks, key_bytes=ch.key_bytes,
                    kind=SWAP_KIND, pinned=True, freshness=1)
    pool.free(pages)
    back = sched._fetch_swap_chunks(req)
    assert back is not None
    fresh = pool.alloc(2, "t", ch.key_words, kept)
    pool.write_pages(fresh, back["k_ct"], back["v_ct"], back["k_tags"],
                     back["v_tags"])
    for i, p in enumerate(fresh):
        k2, v2, ok = kv_pager.unseal_page(
            pool.from_rows(pool.k_ct[p]), pool.from_rows(pool.v_ct[p]),
            pool.k_tags[p], pool.v_tags[p], tkey, kept[i], shape[-1],
            CHUNK_WORDS)
        assert bool(ok)
        np.testing.assert_array_equal(_bits(k2), _bits(k[i]))
        np.testing.assert_array_equal(_bits(v2), _bits(v[i]))
    # a swap object in the 5-D page shape is refused, not installed
    five_d = dict(back, k_ct=np.asarray(sealed[0]), v_ct=np.asarray(sealed[1]))
    sched.store.put(swap_object_id(rid), "t", five_d, key_bytes=ch.key_bytes,
                    kind=SWAP_KIND, pinned=True, freshness=2)
    assert sched._fetch_swap_chunks(req) is None
