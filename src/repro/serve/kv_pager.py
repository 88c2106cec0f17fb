"""Sealed *paged* KV cache — one physical pool shared by all tenants.

The fixed-slot engine seals a [L, B, max_len, K, hd] cache per batch, which
forces equal-length prompts and dedicates max_len slots to every sequence.
Here the unit of sealing is a fixed-size **page** holding ``page_size`` token
slots across all layers:

    k page plaintext: [n_layers, page_size, n_kv_heads, hd]   (v likewise)

and variable-length sequences map onto the shared pool through per-sequence
page tables (vLLM-style), gathered in-graph at decode time.

The pool stores each page's ciphertext page-major, as one contiguous row:
``k_ct``/``v_ct`` are ``[n_pages, rows, 128]``, a page's elements in their
``[n_layers, page_size, n_kv_heads, hd]`` row-major order filling lane-dense
rows of 128 (``PagedKVPool.row_shape``; the last row is zero-padded when
the count is not a multiple of 128).  The page index is the most-major
dimension, so a page-table gather is one row gather that moves each page
as one block.  A 5-D ``[n_pages, L, ps, K, hd]`` pool with hd = 64 takes a
page-minor layout on the TPU, where the same gather becomes a loop that
copies one page per step.  Sealing and verification work on a page's
``[L, ps, K, hd]`` view (``PagedKVPool.from_rows``/``to_rows``), so the
sealed bytes do not depend on how the pool stores them.

Security model (paper Rules 1/2, per page):
  * confidentiality — each page is CTR-encrypted under the *owning tenant's*
    session key, via k/v lane subkeys, with a per-page nonce; every re-seal
    of a page's contents bumps its nonce (freshness), so counters are never
    reused.
  * integrity — encrypt-then-MAC chunk tags over the page ciphertext, keyed
    by a (tenant key, page nonce)-bound MAC key; a tampered or replayed page
    fails verification and NaN-poisons only the *owning* request's output.
  * isolation — pages of tenant A are sealed under A's key: B's channel key
    cannot unseal or forge them, and the (session-id, epoch, counter) nonce
    lanes of the two channels are disjoint by construction (core/channel.py).

Pages exist in two states (paper §3.4 cost model — sealing is charged per
byte *written*):

  * CLOSED — the whole page is authenticated by chunk tags over its full
    ciphertext (``seal_page``/``unseal_page``).  Prefill-complete pages and
    swap-out/swap-in pages are closed.
  * OPEN — the tail page of an active sequence.  Decode appends one token
    slot per step: only that slot's bytes are encrypted (the CTR keystream
    is positional, so a slot's ciphertext equals the matching slice of a
    whole-page seal under the same nonce) and one uint32 *slice tag* per
    slot (``seal_slot``) lands in a trusted-side sidecar.  The page nonce
    does NOT move per write — each slot is encrypted exactly once under
    (nonce, its counter positions), so there is no counter reuse, and
    freshness against rollback comes from the trusted-side ``fill`` count:
    replaying an older ciphertext cannot produce a valid tag for the newest
    slot.  When the page fills (or its sequence swaps out) it CLOSES:
    slice tags are verified, the nonce bumps once, and a whole-page
    *page-close MAC* is computed (``close_page``) — per-token sealing cost
    is O(bytes written) with the close amortized over page_size tokens.

Which arrays are attacker-visible: ciphertext (k_ct/v_ct), page tags and
slice tags live in untrusted HBM.  Nonces, the open/fill state and the
slot->tenant-key branding are trusted-side bookkeeping (enclave SRAM on
real hardware; device arrays here so the page-table gather stays in-graph).

(Nonce values are not *secret* — an attacker may read them — but they are
not attacker-writable, which is what the freshness argument needs.)
"""
from __future__ import annotations

import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ..core import cipher, mac
from ..core import sealed as sealed_guard
from ..obs import MetricsRegistry, StatsView, Tracer

# data-plane lane separation: k pages, v pages and page MACs never share a
# (key, nonce) space even though all three derive from one tenant session key.
KV_K_DOMAIN = 0x4B5047   # "KPG"
KV_V_DOMAIN = 0x565047   # "VPG"
KV_MAC_DOMAIN = 0x4D5047  # "MPG"
KV_SLICE_DOMAIN = 0x534C43  # "SLC" — per-slot slice-tag key lane

SCRATCH_PAGE = 0  # physical page 0 is never allocated: pad entries in page
                  # tables and write-back lanes of idle slots target it.

LANES = 128  # minor dimension of a stored page: one TPU lane row


class PoolExhausted(RuntimeError):
    pass


def page_words(n_layers: int, page_size: int, n_kv_heads: int, hd: int,
               dtype) -> int:
    return cipher.words_for((n_layers, page_size, n_kv_heads, hd), dtype)


def page_tag_count(n_words: int, chunk_words: int) -> int:
    """Divisor-aligned chunk count — mirrors mac.block_tags chunking."""
    n = (n_words + chunk_words - 1) // chunk_words
    while n_words % n:
        n += 1
    return n


def _page_mac_key(base_key: jax.Array, nonce: jax.Array) -> jax.Array:
    """Nonce-bound MAC key: replaying a page's old (ct, tags) fails."""
    y0, y1 = cipher.threefry2x32(base_key, jnp.asarray(nonce, jnp.uint32),
                                 jnp.asarray(KV_MAC_DOMAIN, jnp.uint32))
    return jnp.stack([y0, y1])


def seal_page(k_page: jax.Array, v_page: jax.Array, base_key: jax.Array,
              nonce: jax.Array, chunk_words: int):
    """Seal one KV page under a tenant key. Returns (kct, vct, ktags, vtags).

    k_page/v_page: [n_layers, page_size, K, hd] plaintext.  vmappable over a
    leading page axis (per-page nonces / keys become vectors).
    """
    nonce = jnp.asarray(nonce, jnp.uint32)
    kk = cipher.derive_key(base_key, KV_K_DOMAIN)
    vk = cipher.derive_key(base_key, KV_V_DOMAIN)
    kct = cipher.seal_bits(k_page, kk, nonce)
    vct = cipher.seal_bits(v_page, vk, nonce)
    mk = _page_mac_key(base_key, nonce)
    ktags = mac.block_tags(kct.reshape(-1), mk, chunk_words, KV_K_DOMAIN)
    vtags = mac.block_tags(vct.reshape(-1), mk, chunk_words, KV_V_DOMAIN)
    return kct, vct, ktags, vtags


def unseal_page(kct: jax.Array, vct: jax.Array, ktags: jax.Array,
                vtags: jax.Array, base_key: jax.Array, nonce: jax.Array,
                dtype, chunk_words: int):
    """Verify + decrypt one page. Returns (k_page, v_page, ok).

    ``ok`` is a traced bool — callers gate outputs on it per *sequence* so a
    tampered page poisons exactly the requests whose page table contains it.
    """
    nonce = jnp.asarray(nonce, jnp.uint32)
    mk = _page_mac_key(base_key, nonce)
    ok_k = jnp.all(mac.verify_block_tags(kct.reshape(-1), mk, chunk_words,
                                         ktags, KV_K_DOMAIN))
    ok_v = jnp.all(mac.verify_block_tags(vct.reshape(-1), mk, chunk_words,
                                         vtags, KV_V_DOMAIN))
    kk = cipher.derive_key(base_key, KV_K_DOMAIN)
    vk = cipher.derive_key(base_key, KV_V_DOMAIN)
    k = cipher.unseal_bits(kct, kk, nonce, dtype)
    v = cipher.unseal_bits(vct, vk, nonce, dtype)
    return k, v, ok_k & ok_v


def bitcast_page(k_page: jax.Array, v_page: jax.Array):
    """Protection-off path: shape-preserving bitcast, no keystream, no tags."""
    udt = cipher.uint_dtype_for(k_page.dtype)
    return (jax.lax.bitcast_convert_type(k_page, udt),
            jax.lax.bitcast_convert_type(v_page, udt))


# ---------------------------------------------------------------------------
# open pages: slice sealing + page-close MAC
# ---------------------------------------------------------------------------

def slot_rows(n_layers: int, page_size: int, n_kv_heads: int,
              slot) -> jax.Array:
    """uint32[L, K] counter-row indices of one token slot within a page.

    A page's CTR lattice flattens the leading dims [L, page_size, K] into
    rows (cipher.keystream_like); slot ``t`` occupies the non-contiguous
    rows (l * page_size + t) * K + k.  Sealing a slice against these rows
    yields ciphertext bit-identical to the matching slice of a whole-page
    seal under the same nonce — the property that makes open pages sound.
    """
    li = jnp.arange(n_layers, dtype=jnp.uint32)[:, None]
    ki = jnp.arange(n_kv_heads, dtype=jnp.uint32)[None, :]
    return (li * jnp.uint32(page_size) + jnp.asarray(slot, jnp.uint32)) \
        * jnp.uint32(n_kv_heads) + ki


def _slice_mac_key(base_key: jax.Array, nonce: jax.Array,
                   slot) -> jax.Array:
    """Per-(page nonce, slot) slice-tag key: slots cannot be transplanted."""
    mk = _page_mac_key(base_key, nonce)
    y0, y1 = cipher.threefry2x32(mk, jnp.asarray(slot, jnp.uint32),
                                 jnp.asarray(KV_SLICE_DOMAIN, jnp.uint32))
    return jnp.stack([y0, y1])


def _slot_tag(ct_slot: jax.Array, base_key: jax.Array, nonce: jax.Array,
              slot, chunk_words: int, domain: int) -> jax.Array:
    """uint32 root tag over one slot's ciphertext words."""
    sk = _slice_mac_key(base_key, nonce, slot)
    return mac.tag_root(cipher.pack_words(ct_slot), sk, chunk_words, domain)


def seal_slot(k_slot: jax.Array, v_slot: jax.Array, base_key: jax.Array,
              nonce: jax.Array, slot, page_size: int, chunk_words: int):
    """Seal ONE token slot of an open page — cost O(slot bytes), §3.4.

    k_slot/v_slot: [n_layers, K, hd] plaintext.  Returns
    (kct_slot, vct_slot, ktag, vtag): the slot ciphertext (bit-identical to
    the matching slice of ``seal_page`` under the same nonce) and one uint32
    slice tag per lane.  The page nonce does NOT move.
    """
    nonce = jnp.asarray(nonce, jnp.uint32)
    Lc, K, _ = k_slot.shape
    rows = slot_rows(Lc, page_size, K, slot)
    kk = cipher.derive_key(base_key, KV_K_DOMAIN)
    vk = cipher.derive_key(base_key, KV_V_DOMAIN)
    kct = cipher.seal_bits_slice(k_slot, kk, nonce, rows)
    vct = cipher.seal_bits_slice(v_slot, vk, nonce, rows)
    ktag = _slot_tag(kct, base_key, nonce, slot, chunk_words, KV_K_DOMAIN)
    vtag = _slot_tag(vct, base_key, nonce, slot, chunk_words, KV_V_DOMAIN)
    return kct, vct, ktag, vtag


def page_slot_tags(kct: jax.Array, vct: jax.Array, base_key: jax.Array,
                   nonce: jax.Array, chunk_words: int):
    """Slice tags for every slot of an already-sealed page ciphertext.

    kct/vct: [n_layers, page_size, K, hd].  Returns (ktags[ps], vtags[ps]).
    Used when a page *becomes* open with existing content: the prefill
    boundary page and swap-in reopen.
    """
    ps = kct.shape[1]

    def one(slot):
        kc = jax.lax.dynamic_index_in_dim(kct, slot, axis=1, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(vct, slot, axis=1, keepdims=False)
        return (_slot_tag(kc, base_key, nonce, slot, chunk_words,
                          KV_K_DOMAIN),
                _slot_tag(vc, base_key, nonce, slot, chunk_words,
                          KV_V_DOMAIN))

    return jax.vmap(one)(jnp.arange(ps, dtype=jnp.int32))


def verify_open_page(kct: jax.Array, vct: jax.Array, k_stags: jax.Array,
                     v_stags: jax.Array, base_key: jax.Array,
                     nonce: jax.Array, fill: jax.Array,
                     chunk_words: int) -> jax.Array:
    """Verify the written slots (< fill) of an open page. Traced bool.

    Rollback freshness without a per-write nonce bump: ``fill`` is
    trusted-side, so an attacker replaying the page as it looked j writes
    ago still has to present a valid slice tag for slot fill-1 — which that
    older ciphertext does not contain.
    """
    ps = kct.shape[1]
    kt, vt = page_slot_tags(kct, vct, base_key, nonce, chunk_words)
    ok = (kt == k_stags) & (vt == v_stags)
    unused = jnp.arange(ps) >= jnp.asarray(fill, jnp.int32)
    return jnp.all(ok | unused)


def close_page(kct: jax.Array, vct: jax.Array, k_stags: jax.Array,
               v_stags: jax.Array, base_key: jax.Array, nonce: jax.Array,
               fill: jax.Array, dtype, chunk_words: int):
    """OPEN -> CLOSED: the page-close MAC.  One nonce bump per page life.

    Verifies the accumulated slice tags, re-seals the full page under
    nonce+1 and computes whole-page chunk tags (the page-close MAC).  After
    the close, the pre-close (ciphertext, slice tags) pair is dead: slice
    tags were bound to the old nonce, and verification now goes through the
    close MAC under nonce+1.  Returns (kct2, vct2, ktags, vtags, ok); on
    ok=False the emitted tags are corrupted so the page fails closed rather
    than laundering tampered ciphertext into a validly-MACed closed page.
    """
    ok = verify_open_page(kct, vct, k_stags, v_stags, base_key, nonce, fill,
                          chunk_words)
    kk = cipher.derive_key(base_key, KV_K_DOMAIN)
    vk = cipher.derive_key(base_key, KV_V_DOMAIN)
    k = cipher.unseal_bits(kct, kk, nonce, dtype)
    v = cipher.unseal_bits(vct, vk, nonce, dtype)
    n2 = jnp.asarray(nonce, jnp.uint32) + jnp.uint32(1)
    kct2, vct2, ktags, vtags = seal_page(k, v, base_key, n2, chunk_words)
    poison = jnp.where(ok, jnp.uint32(0), jnp.uint32(1))
    return kct2, vct2, ktags ^ poison, vtags ^ poison, ok


def cow_page(kct: jax.Array, vct: jax.Array, ktags: jax.Array,
             vtags: jax.Array, src_key: jax.Array, src_nonce: jax.Array,
             dst_key: jax.Array, dst_nonce: jax.Array, dtype,
             chunk_words: int):
    """Copy-on-write break of a shared prefix page.

    Verify + decrypt a CLOSED shared page under the *source* key (the
    prefix-entry key, obtained by unwrapping the tenant's key-wrap), then
    re-seal the same plaintext as an OPEN page under the *destination*
    tenant's key and a fresh nonce lane, emitting per-slot slice tags so
    decode can append at the divergence slot.  Returns (kct2, vct2,
    k_stags, v_stags, ok); the emitted slice tags are corrupted on
    ok=False, so neither a tampered shared original nor a wrongly
    unwrapped source key can launder into a valid private page.
    """
    k, v, ok = unseal_page(kct, vct, ktags, vtags, src_key, src_nonce,
                           dtype, chunk_words)
    dst_nonce = jnp.asarray(dst_nonce, jnp.uint32)
    kk = cipher.derive_key(dst_key, KV_K_DOMAIN)
    vk = cipher.derive_key(dst_key, KV_V_DOMAIN)
    kct2 = cipher.seal_bits(k, kk, dst_nonce)
    vct2 = cipher.seal_bits(v, vk, dst_nonce)
    k_stags, v_stags = page_slot_tags(kct2, vct2, dst_key, dst_nonce,
                                      chunk_words)
    poison = jnp.where(ok, jnp.uint32(0), jnp.uint32(0xA5A5A5A5))
    return kct2, vct2, k_stags ^ poison, v_stags ^ poison, ok


def reopen_page(kct: jax.Array, vct: jax.Array, ktags: jax.Array,
                vtags: jax.Array, base_key: jax.Array, nonce: jax.Array,
                dtype, chunk_words: int):
    """CLOSED -> OPEN: verify the close MAC, re-seal under nonce+1, emit
    per-slot slice tags so decode can keep appending.  Used at swap-in for
    a partially-filled tail page.  Returns (kct2, vct2, k_stags, v_stags,
    ok); tags are corrupted on ok=False (fail closed, owner-only blast
    radius).
    """
    k, v, ok = unseal_page(kct, vct, ktags, vtags, base_key, nonce, dtype,
                           chunk_words)
    n2 = jnp.asarray(nonce, jnp.uint32) + jnp.uint32(1)
    kk = cipher.derive_key(base_key, KV_K_DOMAIN)
    vk = cipher.derive_key(base_key, KV_V_DOMAIN)
    kct2 = cipher.seal_bits(k, kk, n2)
    vct2 = cipher.seal_bits(v, vk, n2)
    k_stags, v_stags = page_slot_tags(kct2, vct2, base_key, n2, chunk_words)
    poison = jnp.where(ok, jnp.uint32(0), jnp.uint32(1))
    return kct2, vct2, k_stags ^ poison, v_stags ^ poison, ok


@dataclasses.dataclass
class PagedKVPool:
    """Free-list allocator + device-resident page arrays.

    Page 0 is reserved as scratch; allocations hand out distinct pages, so
    two live requests never share a physical page and the in-graph write-back
    scatter has no index collisions among active lanes.
    """
    n_pages: int
    page_size: int
    n_layers: int
    n_kv_heads: int
    hd: int
    dtype: object
    chunk_words: int = 128
    sealed: bool = True
    open_pages: bool = True     # slice-sealed tail pages (False = legacy
                                # whole-page reseal per decode write)
    metrics: MetricsRegistry | None = None  # shared registry (gateway's)
    audit: object = None        # AuditLog sink for close/reopen/nonce events
    profiler: object = None     # obs.Profiler — its CostLedger is charged
                                # from the same note_* call sites (and with
                                # the same byte formulas) as _c_sealed, so
                                # per-bucket ledger sums reconcile exactly
    tracer: object = None       # obs.Tracer for the pool.alloc/free spans

    def __post_init__(self):
        if self.tracer is None:
            self.tracer = Tracer(enabled=False)
        shape = (self.n_pages,) + self.row_shape
        udt = cipher.uint_dtype_for(self.dtype)
        pw = page_words(self.n_layers, self.page_size, self.n_kv_heads,
                        self.hd, self.dtype)
        self.n_tags = (page_tag_count(pw, self.chunk_words)
                       if self.sealed else 1)
        self.k_ct = jnp.zeros(shape, udt)
        self.v_ct = jnp.zeros(shape, udt)
        self.k_tags = jnp.zeros((self.n_pages, self.n_tags), jnp.uint32)
        self.v_tags = jnp.zeros((self.n_pages, self.n_tags), jnp.uint32)
        # open-page sidecars: one slice tag per token slot (untrusted HBM),
        # plus trusted-side open/fill state driving the verification path.
        self.k_stags = jnp.zeros((self.n_pages, self.page_size), jnp.uint32)
        self.v_stags = jnp.zeros((self.n_pages, self.page_size), jnp.uint32)
        self.open_flags = jnp.zeros((self.n_pages,), bool)
        self.fill = jnp.zeros((self.n_pages,), jnp.int32)
        self.nonces = jnp.zeros((self.n_pages,), jnp.uint32)
        self.keys = jnp.zeros((self.n_pages, 2), jnp.uint32)
        self._free = deque(range(1, self.n_pages))
        self._owner: dict[int, str] = {}
        self._nonce_guard: dict[int, sealed_guard.NonceSpanGuard] = {}
        # shared (prefix-cache) pages: page -> count of live request
        # mappings.  A page in _refs is read-only and owned by its
        # publisher; it leaves the pool only through release_shared, and
        # only once every mapping has been dropped.
        self._refs: dict[int, int] = {}
        self._pending_release: set[int] = set()
        if self.metrics is None:
            self.metrics = MetricsRegistry()
        reg = self.metrics
        # allocator lifetime counters (survive measurement-window resets)
        self._c_allocs = reg.counter(
            "kv_pool_allocs_total", "pages handed out", windowed=False)
        self._c_frees = reg.counter(
            "kv_pool_frees_total", "pages returned", windowed=False)
        self._c_alloc_failures = reg.counter(
            "kv_pool_alloc_failures_total", "PoolExhausted raises",
            windowed=False)
        self._g_peak_live = reg.gauge(
            "kv_pool_peak_live_pages", "high-water mark of live pages",
            windowed=False)
        # §3.4 cost-model accounting (ciphertext bytes run through seal,
        # k+v, excluding tag sidecars) — windowed: reset per measurement
        self._c_sealed = {
            phase: reg.counter(f"kv_pool_sealed_bytes_{phase}_total",
                               f"sealed bytes charged to {phase}")
            for phase in ("prefill", "decode", "swap")}
        self._c_decode_tokens = reg.counter(
            "kv_pool_decode_tokens_total", "decode write-backs")
        self._c_page_closes = reg.counter(
            "kv_pool_page_closes_total", "OPEN -> CLOSED transitions")
        self._c_page_reopens = reg.counter(
            "kv_pool_page_reopens_total", "CLOSED -> OPEN transitions")
        self._c_page_renonces = reg.counter(
            "kv_pool_page_renonces_total",
            "pages re-sealed under a fresh nonce lane")
        # prefix-cache sharing (lifetime: allocator-class bookkeeping)
        self._c_shared_maps = reg.counter(
            "kv_pool_shared_maps_total",
            "shared-page mappings handed to requests", windowed=False)
        self._c_shared_unmaps = reg.counter(
            "kv_pool_shared_unmaps_total",
            "shared-page mappings returned", windowed=False)
        self._c_cow_breaks = reg.counter(
            "kv_pool_cow_breaks_total",
            "shared pages copied-on-write into private pages",
            windowed=False)
        # historical dict read surface (pool.stats["allocs"], ...)
        self.stats = StatsView(reg, {
            "allocs": "kv_pool_allocs_total",
            "frees": "kv_pool_frees_total",
            "peak_live": "kv_pool_peak_live_pages",
            "alloc_failures": "kv_pool_alloc_failures_total",
            "sealed_bytes_prefill": "kv_pool_sealed_bytes_prefill_total",
            "sealed_bytes_decode": "kv_pool_sealed_bytes_decode_total",
            "sealed_bytes_swap": "kv_pool_sealed_bytes_swap_total",
            "decode_tokens": "kv_pool_decode_tokens_total",
            "page_closes": "kv_pool_page_closes_total",
            "page_reopens": "kv_pool_page_reopens_total"})

    def reset_window(self) -> None:
        """Zero the windowed cost counters (sealing bytes, closes, tokens);
        allocator lifetime stats and the peak gauge are untouched."""
        for c in self._c_sealed.values():
            c.reset()
        self._c_decode_tokens.reset()
        self._c_page_closes.reset()
        self._c_page_reopens.reset()

    def _audit(self, kind: str, page: int | None = None, **detail) -> None:
        if self.audit is not None:
            tenant = self._owner.get(page) if page is not None else None
            if page is not None:
                detail["page"] = page
            self.audit.append(kind, tenant=tenant, **detail)

    # -- sizes -----------------------------------------------------------
    @property
    def slot_bytes(self) -> int:
        """Plaintext bytes of one token slot across all layers (k or v)."""
        return (self.n_layers * self.n_kv_heads * self.hd
                * jnp.dtype(self.dtype).itemsize)

    @property
    def page_bytes(self) -> int:
        return self.slot_bytes * self.page_size

    # -- stored form -----------------------------------------------------
    @property
    def page_shape(self) -> tuple[int, int, int, int]:
        """One page's ``[n_layers, page_size, n_kv_heads, hd]`` view."""
        return (self.n_layers, self.page_size, self.n_kv_heads, self.hd)

    @property
    def row_shape(self) -> tuple[int, int]:
        """``(rows, LANES)``: one page as ``k_ct``/``v_ct`` store it, its
        ``page_shape`` elements in row-major order, the last row
        zero-padded."""
        n = int(np.prod(self.page_shape))
        return (-(-n // LANES), LANES)

    def to_rows(self, pages: jax.Array) -> jax.Array:
        """``[..., L, ps, K, hd]`` pages -> ``[..., rows, LANES]`` stored
        form; the element order is kept, so the sealed bytes are too."""
        lead = pages.shape[:-4]
        flat = pages.reshape(lead + (-1,))
        pad = self.row_shape[0] * LANES - flat.shape[-1]
        if pad:
            flat = jnp.pad(flat, [(0, 0)] * len(lead) + [(0, pad)])
        return flat.reshape(lead + self.row_shape)

    def from_rows(self, rows: jax.Array) -> jax.Array:
        """``[..., rows, LANES]`` stored pages -> ``[..., L, ps, K, hd]``.
        Apply it to gathered pages only: the whole pool in this view takes
        the page-minor layout that the stored form avoids."""
        lead = rows.shape[:-2]
        n = int(np.prod(self.page_shape))
        return rows.reshape(lead + (-1,))[..., :n].reshape(
            lead + self.page_shape)

    def write_slots(self, ct: jax.Array, pages: jax.Array, slots: jax.Array,
                    slot_ct: jax.Array) -> jax.Array:
        """``ct`` (stored ``k_ct`` or ``v_ct``) with ``slot_ct[b]``
        ([L, K, hd]) written at slot ``slots[b]`` of page ``pages[b]``.

        Each page is read as its row, the slot set in its [L, ps, K, hd]
        view and the row written back whole, so the write is one row
        gather and one row scatter.  Where a page repeats (idle lanes on
        SCRATCH_PAGE) one of its writes wins.
        """
        def put(rows, sct, s):
            page = jax.lax.dynamic_update_slice(self.from_rows(rows),
                                                sct[:, None], (0, s, 0, 0))
            return self.to_rows(page)
        return ct.at[pages].set(jax.vmap(put)(ct[pages], slot_ct, slots))

    def elem_index(self, page: int, layer: int, slot: int, head: int,
                   dim: int) -> tuple[int, int, int]:
        """Index into ``k_ct``/``v_ct`` of one element of a page."""
        i = ((layer * self.page_size + slot) * self.n_kv_heads + head) \
            * self.hd + dim
        return (page, i // LANES, i % LANES)

    # -- allocator -------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        return self.n_pages - 1 - len(self._free)

    def alloc(self, n: int, owner: str, key_words, nonces,
              span: int | None = None,
              spent: list[int] | None = None) -> list[int]:
        """Take ``n`` pages for ``owner``; brand them with the owner's key
        words and fresh per-page nonces.  Raises PoolExhausted if short.

        ``span``: how many consecutive nonce values the caller reserved per
        page — close/reopen bumps are budgeted against it (fail closed on
        exhaustion rather than reusing keystream).  ``spent``: per-page
        bumps already consumed from that reservation — a swapped-in page
        carries its pre-swap nonce walk, so the budget survives re-alloc
        instead of silently resetting.  New pages start OPEN with fill 0
        when the pool runs open-page sealing.
        """
        if n > len(self._free):
            self._c_alloc_failures.inc()
            raise PoolExhausted(f"need {n} pages, {len(self._free)} free")
        with self.tracer.span("pool.alloc", cat="pool",
                              args={"tenant": owner, "pages": n}):
            pages = [self._free.popleft() for _ in range(n)]
            idx = jnp.asarray(pages, jnp.int32)
            kw = jnp.broadcast_to(jnp.asarray(key_words, jnp.uint32), (n, 2))
            self.keys = self.keys.at[idx].set(kw)
            self.nonces = self.nonces.at[idx].set(
                jnp.asarray(nonces, jnp.uint32))
            if self.open_pages:
                self.open_flags = self.open_flags.at[idx].set(True)
                self.fill = self.fill.at[idx].set(0)
        for i, p in enumerate(pages):
            self._owner[p] = owner
            self._nonce_guard[p] = sealed_guard.NonceSpanGuard(
                span=span if span else self.page_size + 2,
                spent=spent[i] if spent else 0)
        self._c_allocs.inc(n)
        self._g_peak_live.set_max(self.live_pages)
        return pages

    def spend_nonce(self, page: int, n: int = 1) -> None:
        """Budget a host-driven nonce bump (close/reopen) for ``page``."""
        guard = self._nonce_guard.get(page)
        if guard is not None:
            guard.spend(n)
            self._audit("nonce_spend", page=page, n=n, spent=guard.spent,
                        span=guard.span)

    def nonce_spent(self, page: int) -> int:
        """Bumps consumed from ``page``'s reserved nonce span so far."""
        guard = self._nonce_guard.get(page)
        return guard.spent if guard is not None else 0

    def free(self, pages: list[int]) -> None:
        """Return pages to the free list; un-brand them so a stale page table
        entry can never verify against a past tenant's data.

        Shared (refcounted) pages are never freed here — a caller mixing
        shared pages into a free list is a lifecycle bug that would
        corrupt other tenants' mappings, so it raises instead of freeing.
        """
        if not pages:
            return
        shared = [p for p in pages if p in self._refs]
        if shared:
            raise ValueError(
                f"free() on shared pages {shared} — use unmap_shared / "
                "release_shared for refcounted prefix pages")
        with self.tracer.span("pool.free", cat="pool",
                              args={"pages": len(pages)}):
            idx = jnp.asarray(pages, jnp.int32)
            self.keys = self.keys.at[idx].set(0)
            self.nonces = self.nonces.at[idx].set(0)
            self.k_tags = self.k_tags.at[idx].set(0)
            self.v_tags = self.v_tags.at[idx].set(0)
            self.k_stags = self.k_stags.at[idx].set(0)
            self.v_stags = self.v_stags.at[idx].set(0)
            self.open_flags = self.open_flags.at[idx].set(False)
            self.fill = self.fill.at[idx].set(0)
        for p in pages:
            self._owner.pop(p, None)
            self._nonce_guard.pop(p, None)
            self._free.append(p)
        self._c_frees.inc(len(pages))

    # -- shared (prefix-cache) pages -------------------------------------
    def make_shared(self, pages: list[int]) -> None:
        """Mark allocated pages as shared/read-only (refcount 0).

        The publisher keeps ownership (its key stays branded); requests
        take read-only mappings via ``map_shared``.  From here on the
        pages cannot be freed or re-sealed by any single tenant's
        lifecycle — only ``release_shared`` by the publisher retires them.
        """
        for p in pages:
            if p not in self._owner:
                raise ValueError(f"page {p} is not allocated")
            if p in self._refs:
                raise ValueError(f"page {p} is already shared")
            self._refs[p] = 0

    def map_shared(self, pages: list[int]) -> None:
        """Take one read-only mapping per page for a request."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"page {p} is not a shared page")
            self._refs[p] += 1
        self._c_shared_maps.inc(len(pages))

    def unmap_shared(self, pages: list[int]) -> None:
        """Drop one mapping per page.  Never double-frees: a page whose
        refcount would go negative raises, and the physical page is only
        reclaimed when the publisher has already released it AND the last
        mapping drops."""
        retire = []
        for p in pages:
            if self._refs.get(p, 0) <= 0:
                raise ValueError(
                    f"unmap_shared on page {p} with no live mapping")
            self._refs[p] -= 1
            if self._refs[p] == 0 and p in self._pending_release:
                retire.append(p)
        self._c_shared_unmaps.inc(len(pages))
        if retire:
            self._retire_shared(retire)

    def release_shared(self, pages: list[int]) -> None:
        """Publisher retires shared pages: freed now if unmapped, else
        deferred until the last reader unmaps."""
        retire = []
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"page {p} is not a shared page")
            if self._refs[p] == 0:
                retire.append(p)
            else:
                self._pending_release.add(p)
        if retire:
            self._retire_shared(retire)

    def _retire_shared(self, pages: list[int]) -> None:
        for p in pages:
            del self._refs[p]
            self._pending_release.discard(p)
        self.free(pages)

    def is_shared(self, page: int) -> bool:
        return page in self._refs

    def ref_count(self, page: int) -> int:
        return self._refs.get(page, 0)

    @property
    def shared_pages(self) -> list[int]:
        return sorted(self._refs)

    def note_cow(self, src: int, dst: int, ok: bool) -> None:
        """Record a COW break (cost: one unseal + one whole-page seal under
        the tenant key, charged to the decode bucket — it replaces the
        first decode write into the shared page)."""
        self._c_cow_breaks.inc()
        if self.sealed:
            self._c_sealed["decode"].inc(2 * self.page_bytes)
            self._charge("cow", self._owner.get(dst), 2 * self.page_bytes,
                         "decode")

    # -- §3.4 cost accounting (the engine reports, the pool owns) --------
    def _charge(self, phase: str, tenant: str | None, nbytes: int,
                bucket: str) -> None:
        """Mirror a sealed-bytes charge into the profiler's CostLedger,
        keyed (phase, tenant).  Called from the same sites (inside the
        same ``if self.sealed`` guards, with the same formulas) as the
        ``_c_sealed[bucket]`` increments — the exactness the ledger's
        reconciliation tests rely on."""
        if self.profiler is not None:
            self.profiler.ledger.charge(phase, tenant, nbytes, bucket,
                                        chunk_words=self.chunk_words)

    def note_prefill(self, pages_written: int, lanes=()) -> None:
        """Charge a batched prefill chunk: whole pages sealed, k+v.

        lanes: optional [(owner, pages)] per active lane for per-tenant
        ledger attribution; must sum to ``pages_written``."""
        if self.sealed:
            self._c_sealed["prefill"].inc(2 * self.page_bytes * pages_written)
            if lanes:
                for owner, n in lanes:
                    self._charge("prefill", owner, 2 * self.page_bytes * n,
                                 "prefill")
            elif pages_written:
                self._charge("prefill", None,
                             2 * self.page_bytes * pages_written, "prefill")

    def note_decode(self, n_tokens: int, owners=()) -> None:
        """Charge one decode step's write-backs (slot or whole-page).

        owners: optional per-token owner list (one entry per charged
        token) for per-tenant ledger attribution."""
        self._c_decode_tokens.inc(n_tokens)
        if self.sealed:
            per = 2 * (self.slot_bytes if self.open_pages
                       else self.page_bytes)
            self._c_sealed["decode"].inc(n_tokens * per)
            if owners:
                for owner in owners:
                    self._charge("decode", owner, per, "decode")
            elif n_tokens:
                self._charge("decode", None, n_tokens * per, "decode")

    def note_close(self, page: int, account: str, ok: bool) -> None:
        """Record an OPEN -> CLOSED transition (audit + cost counters).

        account: which sealed-bytes bucket the close charges to ("decode"
        for fill-triggered closes, "swap" for swap-out closes)."""
        self._c_page_closes.inc()
        if self.sealed:
            self._c_sealed[account].inc(2 * self.page_bytes)
            self._charge("close", self._owner.get(page), 2 * self.page_bytes,
                         account)
        self._audit("page_close", page=page, account=account, ok=bool(ok))

    def note_reopen(self, page: int, ok: bool) -> None:
        """Record a CLOSED -> OPEN transition (swap-in tail page)."""
        self._c_page_reopens.inc()
        if self.sealed:
            self._c_sealed["swap"].inc(2 * self.page_bytes)
            self._charge("reopen", self._owner.get(page),
                         2 * self.page_bytes, "swap")
        self._audit("page_reopen", page=page, ok=bool(ok))

    def owner_of(self, page: int) -> str | None:
        return self._owner.get(page)

    # -- trusted-side headroom (obs/monitor.py source) -------------------
    def headroom(self) -> list[dict]:
        """Per-page nonce-span budget reports for every live page.

        Each entry is the page guard's ``NonceSpanGuard.headroom()`` plus
        identity: {"source": "page_nonce", "id", "tenant", "open",
        "remaining", "span", "spent"}.  ``open`` routes the monitor's
        attention — only OPEN tail pages spend further bumps.
        """
        open_np = np.asarray(self.open_flags)
        out = []
        for page, guard in self._nonce_guard.items():
            owner = self._owner.get(page)
            if owner is None:
                continue
            h = guard.headroom()
            h.update(id=page, tenant=owner, open=bool(open_np[page]))
            out.append(h)
        return out

    def renonce_guard(self, page: int, span: int) -> None:
        """Reset ``page``'s nonce budget after a re-seal under a freshly
        reserved channel nonce lane (engine.renonce_page) — the old lane is
        abandoned, the new reservation starts unspent."""
        self._nonce_guard[page] = sealed_guard.NonceSpanGuard(span=span)
        self._audit("nonce_refresh", page=page, span=span)

    def note_renonce(self, page: int, ok: bool) -> None:
        """Record a nonce-lane re-seal (cost: one unseal + whole-page seal,
        charged to the decode bucket like the close it pre-empts)."""
        self._c_page_renonces.inc()
        if self.sealed:
            self._c_sealed["decode"].inc(2 * self.page_bytes)
            self._charge("renonce", self._owner.get(page),
                         2 * self.page_bytes, "decode")
        self._audit("page_renonce", page=page, ok=bool(ok))

    def pages_of(self, owner: str) -> list[int]:
        return [p for p, o in self._owner.items() if o == owner]

    # -- device state ----------------------------------------------------
    def write_pages(self, pages: list[int], kct, vct, ktags, vtags) -> None:
        """Install sealed CLOSED page contents in stored form (swap-in of
        what ``export_pages`` gave)."""
        idx = jnp.asarray(pages, jnp.int32)
        self.k_ct = self.k_ct.at[idx].set(kct)
        self.v_ct = self.v_ct.at[idx].set(vct)
        self.k_tags = self.k_tags.at[idx].set(ktags)
        self.v_tags = self.v_tags.at[idx].set(vtags)
        self.open_flags = self.open_flags.at[idx].set(False)
        self.fill = self.fill.at[idx].set(0)

    def mark_open(self, pages: list[int], fill: int = 0) -> None:
        """Trusted-side state flip: pages become OPEN with ``fill`` written
        slots.  No crypto — callers either just allocated the pages (fill 0)
        or reopened them through ``reopen_page`` (which re-sealed)."""
        if not pages:
            return
        idx = jnp.asarray(pages, jnp.int32)
        self.open_flags = self.open_flags.at[idx].set(True)
        self.fill = self.fill.at[idx].set(fill)

    def mark_closed(self, pages: list[int]) -> None:
        if not pages:
            return
        idx = jnp.asarray(pages, jnp.int32)
        self.open_flags = self.open_flags.at[idx].set(False)
        self.fill = self.fill.at[idx].set(0)

    def export_pages(self, pages: list[int]) -> tuple[dict, np.ndarray]:
        """Verbatim host copies of sealed pages for the spill store.

        Returns ({k_ct, v_ct, k_tags, v_tags}, nonces).  The chunk dict is
        exactly what may leave for the untrusted tier (Rules 1/2: already
        ciphertext + tags); the nonces are NOT part of it — the caller must
        retain them on the trusted side, because the nonce-bound page MAC is
        what binds a later swap-in to this exact page version.
        """
        idx = np.asarray(pages, np.int32)
        chunks = {
            "k_ct": np.asarray(self.k_ct)[idx],
            "v_ct": np.asarray(self.v_ct)[idx],
            "k_tags": np.asarray(self.k_tags)[idx],
            "v_tags": np.asarray(self.v_tags)[idx],
        }
        return chunks, np.asarray(self.nonces)[idx].copy()

    def arrays(self) -> tuple:
        """The pool state threaded through the jitted decode step."""
        return (self.k_ct, self.v_ct, self.k_tags, self.v_tags,
                self.k_stags, self.v_stags, self.nonces, self.keys,
                self.open_flags, self.fill)

    def update_arrays(self, arrays: tuple) -> None:
        (self.k_ct, self.v_ct, self.k_tags, self.v_tags,
         self.k_stags, self.v_stags, self.nonces, self.keys,
         self.open_flags, self.fill) = arrays
