"""Serving engines with sealed KV caches.

Two execution engines share this module:

``ServeEngine`` — the legacy fixed-slot engine: one sealed [L, B, max_len]
cache per batch, equal-length prompts, whole-batch nonce epochs.  Kept as the
reference path (and the baseline the paged engine is tested against).

``PagedEngine`` — the multi-tenant engine behind the gateway: decodes at
variable occupancy over a shared *paged* KV pool (serve/kv_pager.py).  Each
active slot carries its own sequence length, its own page table and its own
tenant key (via page branding), so mixed-length requests from mutually
distrusting tenants share one physical cache.  Model weights stay sealed
under the *provider* channel; KV pages are sealed under *tenant* channels.

Both engines launch through SecureChannel.launch (Rule 3) at the call sites
that drive them; the jitted bodies gate every output on the in-graph
verification predicates (tamper => NaN-poisoned logits / sentinel tokens).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core import cipher
from ..core import sealed as sealed_lib
from ..core.channel import SecureChannel
from ..models import layers as L
from ..models import registry, transformer
from . import kv_pager

# domain separator for the fixed-slot engine's KV lane — weight-upload nonces
# and KV-epoch nonces live under different derived keys, so the engine's small
# integer epochs can never collide with the channel's structured nonces.
KV_CACHE_DOMAIN = 0x4B5643  # "KVC"

TOKEN_POISON = np.iinfo(np.int32).min  # sentinel for integrity-failed slots


def unseal_params(params, key: jax.Array, sealed: bool):
    """Shared in-graph param unseal: returns (tree, ok predicate)."""
    if not sealed:
        return params, jnp.bool_(True)
    return sealed_lib.unseal_tree(params, key)


# ---------------------------------------------------------------------------
# fixed-slot engine (legacy reference path)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeEngine:
    cfg: object
    params: object                  # sealed tree if channel.config.enabled
    channel: SecureChannel
    max_len: int

    def __post_init__(self):
        self.model = registry.get_model(self.cfg)
        self._sealed = self.channel.config.enabled
        self._kv_key = self.channel.subkey(KV_CACHE_DOMAIN)
        self._nonce_epoch = 1
        self._prefill = jax.jit(partial(self._prefill_impl))
        self._decode = jax.jit(partial(self._decode_impl))

    # -- jitted bodies ---------------------------------------------------
    def _prefill_impl(self, params_in, batch, nonce):
        params, ok = unseal_params(params_in, self.channel.jkey, self._sealed)
        seal_ctx = (self._kv_key, nonce) if self._sealed else None
        logits, cache = self.model.prefill(params, self.cfg, batch,
                                           self.max_len, seal_ctx=seal_ctx)
        logits = jnp.where(ok, logits, jnp.nan)
        return logits, cache

    def _decode_impl(self, params_in, cache, tokens):
        params, ok = unseal_params(params_in, self.channel.jkey, self._sealed)
        seal_ctx = ((self._kv_key, cache.get("nonce"))
                    if self._sealed else None)
        logits, cache = self.model.decode_step(params, self.cfg, cache, tokens,
                                               seal_ctx=seal_ctx)
        logits = jnp.where(ok, logits, jnp.nan)
        return logits, cache

    # -- public API --------------------------------------------------------
    def generate(self, batch: dict, n_new: int, log=None):
        """batch: {'tokens': [B, S] int32, ...frontends}. Greedy decode."""
        nonce = jnp.asarray(self._nonce_epoch, jnp.uint32)
        self._nonce_epoch += 1 + n_new
        self.channel.launch(lambda: None, {
            "op": "prefill", "arch": self.cfg.arch_id,
            "shape": {k: list(v.shape) for k, v in batch.items()},
            "max_len": self.max_len})
        logits, cache = self._prefill(self.params, batch, nonce)
        out = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok))
        for i in range(n_new - 1):
            self.channel.launch(lambda: None, {
                "op": "decode", "arch": self.cfg.arch_id, "step": i})
            logits, cache = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(np.asarray(tok))
        return np.stack(out, axis=1)  # [B, n_new]


# ---------------------------------------------------------------------------
# paged engine (continuous batching over the shared sealed pool)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PagedEngine:
    """Variable-occupancy decode over a paged, per-tenant-sealed KV pool.

    Dense-transformer families only (the fixed-slot engine remains the path
    for recurrent / encdec families).  All shapes the jitted steps see are
    static: max_slots lanes, max_pages page-table columns, pool of n_pages —
    occupancy varies through the ``active`` mask, not through shapes.

    Two sealing disciplines for the decode write-back, selected by
    ``open_pages`` (both produce bitwise-identical token streams):

      * open_pages=True — the tail page of each sequence is OPEN: each step
        seals only the new token slot (kv_pager.seal_slot, O(slot bytes))
        and the page closes once per page_size tokens (close_page, one
        nonce bump + the page-close MAC).  Per-token seal cost is
        O(bytes written) — the paper's §3.4 model.
      * open_pages=False — legacy baseline: the whole tail page re-seals
        under a bumped nonce every step (O(page bytes) per token).

    Prefill is *chunked and batched*: ``chunk_prefill`` advances up to
    max_slots prompts by ``prefill_chunk`` tokens in one jitted call,
    splicing prefill work between decode steps (vLLM-style) instead of
    running one whole prompt at a time at admission.
    """
    cfg: object
    params: object                  # sealed under the provider channel
    channel: SecureChannel          # provider channel (weights + launches)
    pool: kv_pager.PagedKVPool
    max_slots: int
    max_pages: int                  # page-table columns per sequence
    prefill_chunk: int = 0          # tokens per prefill chunk (0 = max seq)
    tracer: object = None           # obs.Tracer for engine phase spans
    profiler: object = None         # obs.Profiler — device-synchronized
                                    # phase timing + dispatch counting

    def __post_init__(self):
        if self.tracer is None:
            from ..obs import Tracer
            self.tracer = Tracer(enabled=False)
        if self.profiler is None:
            from ..obs import Profiler
            self.profiler = Profiler(enabled=False)
        if self.cfg.family not in ("dense",):
            raise ValueError(
                f"PagedEngine supports dense transformers, got "
                f"{self.cfg.family!r}")
        ps = self.pool.page_size
        if not self.prefill_chunk:
            self.prefill_chunk = self.max_pages * ps
        if self.prefill_chunk % ps:
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must be a multiple of "
                f"page_size={ps} (chunks write whole pages)")
        self.prefill_chunk = min(self.prefill_chunk, self.max_pages * ps)
        self._sealed_params = self.channel.config.enabled
        self._decode = jax.jit(self._decode_impl)
        self._chunk_prefill = jax.jit(self._chunk_prefill_impl)
        self._close = jax.jit(self._close_impl)
        self._reopen = jax.jit(self._reopen_impl)
        self._renonce = jax.jit(self._renonce_impl)
        self._cow = jax.jit(self._cow_impl)

    @property
    def open_pages(self) -> bool:
        return self.pool.open_pages

    # -- shared gather: page-table walk + per-page verification ----------
    def _gather_unseal(self, pool_arrays, page_tables, seq_lens, active,
                      okp):
        """Gather + unseal the batch's pages.  Returns (kcache, vcache,
        ok_seq) with caches [L, B, T, K, hd] zero-masked beyond seq_lens.

        Per-page verification routes by trusted-side page state: CLOSED
        pages check the whole-page chunk tags, OPEN pages check the
        accumulated per-slot slice tags for the written prefix (< fill).

        Named scopes: ``kv_gather`` (the page-table gathers, the relayout
        and the length mask) and ``kv_unseal`` (keystream, MAC verify and
        the per-sequence verdict).
        """
        cfg = self.cfg
        pool = self.pool
        (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces, keys,
         open_flags, fill) = pool_arrays
        B, P = page_tables.shape
        ps = pool.page_size
        T = P * ps
        Lc, K, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
        with jax.named_scope("kv_gather"):
            # one stored row per page: a row gather, then the pages'
            # [B*P, L, ps, K, hd] view
            flat_pt = page_tables.reshape(-1)
            kp_ct = pool.from_rows(k_ct[flat_pt])
            vp_ct = pool.from_rows(v_ct[flat_pt])
            if self.pool.sealed:
                page_meta = (k_tags[flat_pt], v_tags[flat_pt],
                             keys[flat_pt], nonces[flat_pt])
        with jax.named_scope("kv_unseal"):
            if self.pool.sealed:
                kpl, vpl, ok_page = jax.vmap(
                    lambda kc, vc, kt, vt, kw, nn: kv_pager.unseal_page(
                        kc, vc, kt, vt, kw, nn, cfg.act_dtype,
                        self.pool.chunk_words)
                )(kp_ct, vp_ct, *page_meta)
            else:
                kpl = jax.lax.bitcast_convert_type(kp_ct, cfg.act_dtype)
                vpl = jax.lax.bitcast_convert_type(vp_ct, cfg.act_dtype)
                ok_page = jnp.ones((B * P,), bool)
            ok_page = ok_page.reshape(B, P)
            if self.pool.sealed and self.open_pages:
                # by construction only each lane's TAIL page can be OPEN
                # (full pages close, later pages are empty), so the
                # slice-tag path runs on one page per lane, not B*P: its
                # verdict overrides the whole-page check exactly where the
                # trusted state says OPEN
                tail_idx = jnp.clip(seq_lens // ps, 0, P - 1)  # [B]
                tail_pp = jnp.take_along_axis(page_tables, tail_idx[:, None],
                                              axis=1)[:, 0]
                # the tail pages are gathered as rows outside the vmap: a
                # per-lane index inside it becomes a loop over the lanes
                ok_open = jax.vmap(
                    lambda kc, vc, pp: kv_pager.verify_open_page(
                        kc, vc, k_stags[pp], v_stags[pp], keys[pp],
                        nonces[pp], fill[pp], pool.chunk_words)
                )(pool.from_rows(k_ct[tail_pp]),
                  pool.from_rows(v_ct[tail_pp]), tail_pp)
                ok_closed_tail = jnp.take_along_axis(
                    ok_page, tail_idx[:, None], axis=1)[:, 0]
                ok_tail = jnp.where(open_flags[tail_pp], ok_open,
                                    ok_closed_tail)
                ok_page = ok_page.at[jnp.arange(B), tail_idx].set(ok_tail)
            # only pages holding valid positions count toward a slot's
            # verdict, and idle lanes (scratch-page walks over garbage)
            # never fail
            page_used = (jnp.arange(P)[None, :] * ps) < seq_lens[:, None]
            ok_seq = (jnp.all(ok_page | ~page_used, axis=1) & okp) | ~active

        with jax.named_scope("kv_gather"):
            # [B*P, L, ps, K, hd] -> [L, B, T, K, hd]
            kcache = kpl.reshape(B, P, Lc, ps, K, hd).transpose(
                2, 0, 1, 3, 4, 5).reshape(Lc, B, T, K, hd)
            vcache = vpl.reshape(B, P, Lc, ps, K, hd).transpose(
                2, 0, 1, 3, 4, 5).reshape(Lc, B, T, K, hd)
            # slots beyond each sequence's length hold keystream noise —
            # zero them
            tmask = (jnp.arange(T)[None, :] < seq_lens[:, None])  # [B, T]
            kcache = jnp.where(tmask[None, :, :, None, None], kcache,
                               jnp.zeros((), cfg.act_dtype))
            vcache = jnp.where(tmask[None, :, :, None, None], vcache,
                               jnp.zeros((), cfg.act_dtype))
        return kcache, vcache, ok_seq

    # -- chunked batched prefill -----------------------------------------
    def _chunk_prefill_impl(self, params_in, tokens, start, valid, active,
                            page_tables, pool_arrays):
        """Advance up to B prompts by one fixed-size chunk, batched.

        tokens [B, C] int32 — this chunk's prompt tokens (0-padded)
        start [B]           — prompt positions already in the cache (always
                              a multiple of C, hence page-aligned)
        valid [B]           — valid tokens in this chunk (1..C; 1 for idle)
        active [B] bool     — lanes prefilling this step
        page_tables [B, P]  — physical page per logical page (pad = 0)

        Chunk KV for earlier chunks is read back from sealed pages, so the
        chunk attends over (cache < start) + in-chunk causal.  Full pages
        written by the chunk seal CLOSED; the final partial page of a
        prompt stays OPEN with slice tags (open_pages mode) so decode can
        keep appending at O(bytes written).
        """
        cfg = self.cfg
        (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces, keys,
         open_flags, fill) = pool_arrays
        B, C = tokens.shape
        P = page_tables.shape[1]
        ps = self.pool.page_size
        n_cp = C // ps
        Lc, K, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd

        with jax.named_scope("weights_unseal"):
            params, okp = unseal_params(params_in, self.channel.jkey,
                                        self._sealed_params)
        kcache, vcache, ok_seq = self._gather_unseal(
            pool_arrays, page_tables, start, active, okp)

        with jax.named_scope("model"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.act_dtype)
            positions = start[:, None] + jnp.arange(C)[None, :]  # [B, C]

            def block(carry, xs):
                (xc,) = carry
                lp, kc, vc = xs  # kc [B,T,K,hd]
                h = L.rms_norm(xc, lp["ln1"], cfg.norm_eps)
                q, kn, vn = L.project_qkv(lp["attn"], cfg, h, positions)
                # extend the cache by C rows before inserting the chunk:
                # ``start`` is page-aligned but need not be C-aligned (a
                # prefix cache hit resumes at the shared floor), and an
                # insert whose window overruns T would be silently CLAMPED
                # to fit — landing the chunk at the wrong rows.  The C
                # extension keeps any start <= T in bounds; rows past the
                # last valid query are causally masked, so the padding
                # never reaches the output.
                ext = jnp.zeros((B, C) + kc.shape[2:], kc.dtype)
                kc2 = jax.vmap(
                    lambda c, n, p: jax.lax.dynamic_update_slice(
                        c, n, (p, 0, 0))
                )(jnp.concatenate([kc, ext], axis=1), kn, start)
                vc2 = jax.vmap(
                    lambda c, n, p: jax.lax.dynamic_update_slice(
                        c, n, (p, 0, 0))
                )(jnp.concatenate([vc, ext], axis=1), vn, start)
                a = L.gqa_attention(q, kc2, vc2, causal=True,
                                    q_block=cfg.q_block, base_pos=start)
                xc = xc + L.attn_out(lp["attn"], a, B, C)
                h2 = L.rms_norm(xc, lp["ln2"], cfg.norm_eps)
                xc = xc + L.swiglu(lp["mlp"], h2)
                return (xc,), (kn, vn)

            (x,), (nk, nv) = jax.lax.scan(
                block, (x,), (params["layers"], kcache, vcache))

            # first-token logits for lanes whose prompt completes in this chunk
            x_last = jax.vmap(
                lambda xb, v: jax.lax.dynamic_slice(xb, (v - 1, 0),
                                                    (1, xb.shape[-1]))
            )(x, valid)  # [B, 1, D]
            logits = transformer.logits_of(params, cfg, x_last)[:, 0]  # [B, V]
            logits = jnp.where(ok_seq[:, None], logits, jnp.nan)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok = jnp.where(ok_seq, tok, TOKEN_POISON)
            tok = jnp.where(active, tok, 0)

        with jax.named_scope("kv_seal"):
            # -- write back the chunk's pages -------------------------------
            nk_b = nk.transpose(1, 0, 2, 3, 4)  # [B,L,C,K,hd]
            nv_b = nv.transpose(1, 0, 2, 3, 4)
            kp = nk_b.reshape(B, Lc, n_cp, ps, K, hd).transpose(
                0, 2, 1, 3, 4, 5)
            vp = nv_b.reshape(B, Lc, n_cp, ps, K, hd).transpose(
                0, 2, 1, 3, 4, 5)
            kp_f = kp.reshape(B * n_cp, Lc, ps, K, hd)
            vp_f = vp.reshape(B * n_cp, Lc, ps, K, hd)
            cp_j = jnp.arange(n_cp)[None, :]  # [1, n_cp]
            lpid = jnp.clip(start[:, None] // ps + cp_j, 0, P - 1)
            ppid = jnp.take_along_axis(page_tables, lpid, axis=1)  # [B, n_cp]
            vip = jnp.clip(valid[:, None] - cp_j * ps, 0, ps)  # [B, n_cp]
            written = (vip > 0) & active[:, None]
            # unwritten chunk pages (prompt ended earlier) divert to scratch
            target = jnp.where(written, ppid, kv_pager.SCRATCH_PAGE)
            tgt = target.reshape(-1)
            if self.pool.sealed:
                kct, vct, ktags, vtags = jax.vmap(
                    lambda k_, v_, kw, nn: kv_pager.seal_page(
                        k_, v_, kw, nn, self.pool.chunk_words)
                )(kp_f, vp_f, keys[tgt], nonces[tgt])
            else:
                kct, vct = jax.vmap(kv_pager.bitcast_page)(kp_f, vp_f)
                ktags = jnp.zeros((B * n_cp, self.pool.n_tags), jnp.uint32)
                vtags = jnp.zeros((B * n_cp, self.pool.n_tags), jnp.uint32)
            k_ct = k_ct.at[tgt].set(self.pool.to_rows(kct))
            v_ct = v_ct.at[tgt].set(self.pool.to_rows(vct))
            k_tags = k_tags.at[tgt].set(ktags)
            v_tags = v_tags.at[tgt].set(vtags)
            if self.open_pages:
                # the page containing a prompt's boundary stays OPEN (decode
                # appends into it); full pages close with their chunk tags
                is_boundary = (vip > 0) & (vip < ps) & active[:, None]
                open_flags = open_flags.at[tgt].set(is_boundary.reshape(-1))
                fill = fill.at[tgt].set(
                    jnp.where(is_boundary, vip, 0).reshape(-1))
                if self.pool.sealed:
                    bj = jnp.clip(valid // ps, 0, n_cp - 1)  # [B]
                    has_b = ((valid % ps) > 0) & active
                    b_tgt = jnp.where(
                        has_b,
                        jax.vmap(lambda t, j: t[j])(target, bj),
                        kv_pager.SCRATCH_PAGE)
                    kct_p = kct.reshape(B, n_cp, Lc, ps, K, hd)
                    vct_p = vct.reshape(B, n_cp, Lc, ps, K, hd)
                    kct_b = jax.vmap(lambda c, j: c[j])(kct_p, bj)
                    vct_b = jax.vmap(lambda c, j: c[j])(vct_p, bj)
                    kst, vst = jax.vmap(
                        lambda kc, vc, kw, nn: kv_pager.page_slot_tags(
                            kc, vc, kw, nn, self.pool.chunk_words)
                    )(kct_b, vct_b, keys[b_tgt], nonces[b_tgt])
                    k_stags = k_stags.at[b_tgt].set(kst)
                    v_stags = v_stags.at[b_tgt].set(vst)
        return tok, ok_seq, (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags,
                             nonces, keys, open_flags, fill)

    def chunk_prefill(self, tokens, start, valid, active, page_tables,
                      rid: str = ""):
        """Host-side wrapper for one batched prefill-chunk step.

        Returns (tok [B], ok [B]): ``tok`` is each lane's first generated
        token, meaningful only for lanes whose prompt completed this chunk.
        ``rid`` names the lanes' requests on the trace span.
        """
        active = np.asarray(active, bool)
        valid = np.asarray(valid, np.int32)
        start_np = np.asarray(start, np.int32)
        pt_np = np.asarray(page_tables, np.int32)
        n_lanes = int(active.sum())
        with self.tracer.span("engine.chunk_prefill", cat="engine",
                              args={"rid": rid, "lanes": n_lanes}), \
                self.profiler.phase("prefill") as ph:
            tok, ok, arrays = self._chunk_prefill(
                self.params, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(start, jnp.int32), jnp.asarray(valid, jnp.int32),
                jnp.asarray(active), jnp.asarray(page_tables, jnp.int32),
                self.pool.arrays())
            self.pool.update_arrays(arrays)
            ph.dispatch(arrays)
        # per-lane page counts with the lane's owner (the tenant branded on
        # its first written page) for the ledger's per-tenant attribution
        ps = self.pool.page_size
        lanes = [(self.pool.owner_of(int(pt_np[b, start_np[b] // ps])),
                  -(-int(valid[b]) // ps))
                 for b in range(active.shape[0]) if active[b]]
        self.pool.note_prefill(sum(n for _, n in lanes), lanes=lanes)
        return np.asarray(tok), np.asarray(ok)

    # -- page close / reopen (open-page lifecycle) -----------------------
    def _close_impl(self, pool_arrays, page):
        (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces, keys,
         open_flags, fill) = pool_arrays
        pool = self.pool
        with jax.named_scope("kv_seal"):
            kct2, vct2, ktags, vtags, ok = kv_pager.close_page(
                pool.from_rows(k_ct[page]), pool.from_rows(v_ct[page]),
                k_stags[page], v_stags[page], keys[page], nonces[page],
                fill[page], self.cfg.act_dtype, pool.chunk_words)
            k_ct = k_ct.at[page].set(pool.to_rows(kct2))
            v_ct = v_ct.at[page].set(pool.to_rows(vct2))
            k_tags = k_tags.at[page].set(ktags)
            v_tags = v_tags.at[page].set(vtags)
            k_stags = k_stags.at[page].set(0)
            v_stags = v_stags.at[page].set(0)
            nonces = nonces.at[page].add(1)
            open_flags = open_flags.at[page].set(False)
            fill = fill.at[page].set(0)
        return ok, (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces,
                    keys, open_flags, fill)

    def close_page(self, page: int, account: str = "decode") -> bool:
        """Close an open page (page-close MAC + one nonce bump).

        account: which sealed-bytes bucket the close charges to ("decode"
        for fill-triggered closes, "swap" for swap-out closes).  Returns
        False if the page's slice tags failed verification — the caller
        must poison the owner; the written tags are already corrupted.
        """
        if not self.open_pages:
            return True
        if not self.pool.sealed:
            self.pool.mark_closed([page])
            self.pool.note_close(page, account, True)
            return True
        with self.tracer.span("engine.close_page", cat="engine",
                              args={"page": int(page), "account": account}), \
                self.profiler.phase("close",
                                    tenant=self.pool.owner_of(page)) as ph:
            self.pool.spend_nonce(page)
            ok, arrays = self._close(self.pool.arrays(),
                                     jnp.asarray(page, jnp.int32))
            self.pool.update_arrays(arrays)
            ph.dispatch(arrays)
        self.pool.note_close(page, account, bool(ok))
        return bool(ok)

    def _reopen_impl(self, pool_arrays, page, fill_n):
        (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces, keys,
         open_flags, fill) = pool_arrays
        pool = self.pool
        kct2, vct2, kst, vst, ok = kv_pager.reopen_page(
            pool.from_rows(k_ct[page]), pool.from_rows(v_ct[page]),
            k_tags[page], v_tags[page], keys[page], nonces[page],
            self.cfg.act_dtype, pool.chunk_words)
        k_ct = k_ct.at[page].set(pool.to_rows(kct2))
        v_ct = v_ct.at[page].set(pool.to_rows(vct2))
        k_tags = k_tags.at[page].set(0)
        v_tags = v_tags.at[page].set(0)
        k_stags = k_stags.at[page].set(kst)
        v_stags = v_stags.at[page].set(vst)
        nonces = nonces.at[page].add(1)
        open_flags = open_flags.at[page].set(True)
        fill = fill.at[page].set(fill_n)
        return ok, (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces,
                    keys, open_flags, fill)

    def reopen_page(self, page: int, fill: int) -> bool:
        """Reopen a closed partial page so decode can append (swap-in)."""
        if not self.open_pages:
            return True
        if not self.pool.sealed:
            self.pool.mark_open([page], fill)
            self.pool.note_reopen(page, True)
            return True
        with self.tracer.span("engine.reopen_page", cat="engine",
                              args={"page": int(page)}), \
                self.profiler.phase("reopen",
                                    tenant=self.pool.owner_of(page)) as ph:
            self.pool.spend_nonce(page)
            ok, arrays = self._reopen(self.pool.arrays(),
                                      jnp.asarray(page, jnp.int32),
                                      jnp.asarray(fill, jnp.int32))
            self.pool.update_arrays(arrays)
            ph.dispatch(arrays)
        self.pool.note_reopen(page, bool(ok))
        return bool(ok)

    def _renonce_impl(self, pool_arrays, page, fresh):
        (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces, keys,
         open_flags, fill) = pool_arrays
        pool = self.pool
        k, v, ok = kv_pager.unseal_page(
            pool.from_rows(k_ct[page]), pool.from_rows(v_ct[page]),
            k_tags[page], v_tags[page], keys[page], nonces[page],
            self.cfg.act_dtype, pool.chunk_words)
        kct2, vct2, ktags2, vtags2 = kv_pager.seal_page(
            k, v, keys[page], fresh, pool.chunk_words)
        # fail closed: a page that did not verify under its old nonce must
        # not come back verifiable under the fresh one
        poison = jnp.where(ok, jnp.uint32(0), jnp.uint32(0xA5A5A5A5))
        k_ct = k_ct.at[page].set(pool.to_rows(kct2))
        v_ct = v_ct.at[page].set(pool.to_rows(vct2))
        k_tags = k_tags.at[page].set(ktags2 ^ poison)
        v_tags = v_tags.at[page].set(vtags2 ^ poison)
        nonces = nonces.at[page].set(jnp.asarray(fresh, jnp.uint32))
        return ok, (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces,
                    keys, open_flags, fill)

    def renonce_page(self, page: int, fresh_nonce: int, span: int) -> bool:
        """Re-seal ``page`` under a freshly reserved channel nonce lane.

        The nonce-headroom alert path (ROADMAP item 5): a tail page about
        to exhaust its reserved nonce span is closed (the last old-lane
        bump), whole-page re-sealed under the fresh lane's base nonce, its
        guard restarted at the new span, and reopened (the first new-lane
        bump).  The plaintext never changes, so the token stream is
        bitwise-identical to a run that never renonced.
        """
        was_open = bool(np.asarray(self.pool.open_flags)[page])
        fill_n = int(np.asarray(self.pool.fill)[page])
        if not self.pool.sealed:
            self.pool.renonce_guard(page, span)
            self.pool.note_renonce(page, True)
            return True
        if was_open and fill_n == 0:
            # nothing written under the old lane yet — point the page at
            # the fresh lane directly, no crypto to carry over
            self.pool.nonces = self.pool.nonces.at[page].set(
                jnp.asarray(fresh_nonce, jnp.uint32))
            self.pool.renonce_guard(page, span)
            self.pool.note_renonce(page, True)
            return True
        ok = True
        if was_open:
            ok = self.close_page(page, account="decode")
        with self.tracer.span("engine.renonce_page", cat="engine",
                              args={"page": int(page)}), \
                self.profiler.phase("renonce",
                                    tenant=self.pool.owner_of(page)) as ph:
            ok2, arrays = self._renonce(self.pool.arrays(),
                                        jnp.asarray(page, jnp.int32),
                                        jnp.asarray(fresh_nonce, jnp.uint32))
            self.pool.update_arrays(arrays)
            ph.dispatch(arrays)
        ok = ok and bool(ok2)
        self.pool.renonce_guard(page, span)
        self.pool.note_renonce(page, ok)
        if was_open:
            ok = self.reopen_page(page, fill_n) and ok
        return ok

    # -- copy-on-write break of a shared prefix page ---------------------
    def _cow_impl(self, pool_arrays, src, dst, src_key, fill_n):
        (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces, keys,
         open_flags, fill) = pool_arrays
        pool = self.pool
        kct2, vct2, kst, vst, ok = kv_pager.cow_page(
            pool.from_rows(k_ct[src]), pool.from_rows(v_ct[src]),
            k_tags[src], v_tags[src], src_key, nonces[src], keys[dst],
            nonces[dst], self.cfg.act_dtype, pool.chunk_words)
        k_ct = k_ct.at[dst].set(pool.to_rows(kct2))
        v_ct = v_ct.at[dst].set(pool.to_rows(vct2))
        k_tags = k_tags.at[dst].set(0)
        v_tags = v_tags.at[dst].set(0)
        k_stags = k_stags.at[dst].set(kst)
        v_stags = v_stags.at[dst].set(vst)
        open_flags = open_flags.at[dst].set(True)
        fill = fill.at[dst].set(fill_n)
        return ok, (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces,
                    keys, open_flags, fill)

    def cow_page(self, src: int, dst: int, src_key_words, fill: int) -> bool:
        """Copy-on-write: unseal shared page ``src`` under the (unwrapped)
        prefix key and re-seal its contents into the tenant-owned page
        ``dst`` as an OPEN page with ``fill`` valid slots.

        ``src_key_words`` comes from unwrapping the prefix entry's wrapped
        key with the tenant's session key — a tenant holding the wrong wrap
        gets garbage words here, the unseal MAC fails, and the destination
        tags are written corrupted (poison-on-use).  The shared original is
        read-only and untouched.
        """
        if not self.pool.sealed:
            self.pool.k_ct = self.pool.k_ct.at[dst].set(self.pool.k_ct[src])
            self.pool.v_ct = self.pool.v_ct.at[dst].set(self.pool.v_ct[src])
            self.pool.mark_open([dst], fill)
            self.pool.note_cow(src, dst, True)
            return True
        with self.tracer.span("engine.cow_page", cat="engine",
                              args={"src": int(src), "dst": int(dst)}), \
                self.profiler.phase("cow",
                                    tenant=self.pool.owner_of(dst)) as ph:
            ok, arrays = self._cow(
                self.pool.arrays(), jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32),
                jnp.asarray(np.asarray(src_key_words, np.uint32)),
                jnp.asarray(fill, jnp.int32))
            self.pool.update_arrays(arrays)
            ph.dispatch(arrays)
        ok = bool(ok)
        self.pool.note_cow(src, dst, ok)
        return ok

    # -- decode ----------------------------------------------------------
    def _decode_impl(self, params_in, tokens, seq_lens, active, page_tables,
                     write_pp, pool_arrays):
        """One continuous-batching decode step at variable occupancy.

        tokens [B] int32 — last emitted token per slot (0 for idle lanes)
        seq_lens [B]     — tokens already in the cache; the new KV lands here
        active [B] bool  — live-slot mask
        page_tables [B, P] int32 — physical page per logical page (pad = 0)
        write_pp [B]     — physical page receiving this step's KV
                           (SCRATCH_PAGE for idle lanes)
        pool_arrays      — PagedKVPool.arrays()
        """
        cfg = self.cfg
        (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags, nonces, keys,
         open_flags, fill) = pool_arrays
        B, P = page_tables.shape
        ps = self.pool.page_size
        Lc, K, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd

        with jax.named_scope("weights_unseal"):
            params, okp = unseal_params(params_in, self.channel.jkey,
                                        self._sealed_params)
        kcache, vcache, ok_seq = self._gather_unseal(
            pool_arrays, page_tables, seq_lens, active, okp)

        with jax.named_scope("model"):
            x = jnp.take(params["embed"], tokens[:, None],
                         axis=0).astype(cfg.act_dtype)  # [B, 1, D]
            positions = seq_lens[:, None]  # [B, 1]

            def block(carry, xs):
                (xc,) = carry
                lp, kc, vc = xs  # kc [B,T,K,hd]
                h = L.rms_norm(xc, lp["ln1"], cfg.norm_eps)
                q, kn, vn = L.project_qkv(lp["attn"], cfg, h, positions)
                kc2 = jax.vmap(
                    lambda c, n, p: jax.lax.dynamic_update_slice(
                        c, n, (p, 0, 0))
                )(kc, kn, seq_lens)
                vc2 = jax.vmap(
                    lambda c, n, p: jax.lax.dynamic_update_slice(
                        c, n, (p, 0, 0))
                )(vc, vn, seq_lens)
                a = L.gqa_attention(q, kc2, vc2, causal=False,
                                    t_valid=seq_lens + 1)
                xc = xc + L.attn_out(lp["attn"], a, B, 1)
                h2 = L.rms_norm(xc, lp["ln2"], cfg.norm_eps)
                xc = xc + L.swiglu(lp["mlp"], h2)
                # open mode writes back just the new slot; legacy needs the
                # full updated cache to re-seal the whole tail page
                ys = (kn, vn) if self.open_pages else (kc2, vc2)
                return (xc,), ys

            (x,), (nk, nv) = jax.lax.scan(
                block, (x,), (params["layers"], kcache, vcache))

            logits = transformer.logits_of(params, cfg, x)[:, 0]  # [B, V]
            logits = jnp.where(ok_seq[:, None], logits, jnp.nan)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok = jnp.where(ok_seq, tok, TOKEN_POISON)
            tok = jnp.where(active, tok, 0)  # idle lanes

        with jax.named_scope("kv_seal"):
            if self.open_pages:
                # -- write-back: seal ONLY the new token slot (§3.4) --------
                # nk: [L, B, 1, K, hd] new-token slices from the scan
                slot = seq_lens % ps  # [B]
                k_slot = nk[:, :, 0].transpose(1, 0, 2, 3)  # [B,L,K,hd]
                v_slot = nv[:, :, 0].transpose(1, 0, 2, 3)
                keys_w = keys[write_pp]
                nonce_w = nonces[write_pp]  # no bump
                if self.pool.sealed:
                    kct_s, vct_s, ktag, vtag = jax.vmap(
                        lambda k_, v_, kw, nn, sl: kv_pager.seal_slot(
                            k_, v_, kw, nn, sl, ps, self.pool.chunk_words)
                    )(k_slot, v_slot, keys_w, nonce_w, slot)
                    k_stags = k_stags.at[write_pp, slot].set(ktag)
                    v_stags = v_stags.at[write_pp, slot].set(vtag)
                else:
                    udt = cipher.uint_dtype_for(cfg.act_dtype)
                    kct_s = jax.lax.bitcast_convert_type(k_slot, udt)
                    vct_s = jax.lax.bitcast_convert_type(v_slot, udt)
                # idle lanes hit (SCRATCH_PAGE, slot 0); live lanes hold
                # distinct pages, so no meaningful scatter collisions
                k_ct = self.pool.write_slots(k_ct, write_pp, slot, kct_s)
                v_ct = self.pool.write_slots(v_ct, write_pp, slot, vct_s)
                fill = fill.at[write_pp].set(slot + 1)
            else:
                # -- legacy write-back: reseal the whole tail page ----------
                page_off = (seq_lens // ps) * ps  # [B]
                nk_b = nk.transpose(1, 0, 2, 3, 4)  # [B,L,T,K,hd]
                nv_b = nv.transpose(1, 0, 2, 3, 4)
                k_new = jax.vmap(
                    lambda c, o: jax.lax.dynamic_slice(c, (0, o, 0, 0),
                                                       (Lc, ps, K, hd))
                )(nk_b, page_off)  # [B,L,ps,K,hd]
                v_new = jax.vmap(
                    lambda c, o: jax.lax.dynamic_slice(c, (0, o, 0, 0),
                                                       (Lc, ps, K, hd))
                )(nv_b, page_off)
                keys_w = keys[write_pp]  # [B, 2]
                nonce_w = nonces[write_pp] + jnp.uint32(1)  # freshness
                if self.pool.sealed:
                    kct_n, vct_n, ktags_n, vtags_n = jax.vmap(
                        lambda k_, v_, kw, nn: kv_pager.seal_page(
                            k_, v_, kw, nn, self.pool.chunk_words)
                    )(k_new, v_new, keys_w, nonce_w)
                else:
                    kct_n, vct_n = jax.vmap(kv_pager.bitcast_page)(
                        k_new, v_new)
                    ktags_n = jnp.zeros((B, self.pool.n_tags), jnp.uint32)
                    vtags_n = jnp.zeros((B, self.pool.n_tags), jnp.uint32)
                # idle lanes target SCRATCH_PAGE; live lanes hold distinct
                # pages, so the scatter has no meaningful index collisions.
                k_ct = k_ct.at[write_pp].set(self.pool.to_rows(kct_n))
                v_ct = v_ct.at[write_pp].set(self.pool.to_rows(vct_n))
                k_tags = k_tags.at[write_pp].set(ktags_n)
                v_tags = v_tags.at[write_pp].set(vtags_n)
                nonces = nonces.at[write_pp].set(nonce_w)
        return tok, ok_seq, (k_ct, v_ct, k_tags, v_tags, k_stags, v_stags,
                             nonces, keys, open_flags, fill)

    def decode_step(self, tokens, seq_lens, active, page_tables, write_pp,
                    rid: str = ""):
        """Host-side wrapper: threads the pool through the jitted body.
        ``rid`` names the lanes' requests on the trace span."""
        active_np = np.asarray(active, bool)
        wp_np = np.asarray(write_pp, np.int32)
        n_act = int(active_np.sum())
        with self.tracer.span("engine.decode_step", cat="engine",
                              args={"rid": rid, "lanes": n_act}), \
                self.profiler.phase("decode") as ph:
            tok, ok, arrays = self._decode(
                self.params, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(seq_lens, jnp.int32), jnp.asarray(active, bool),
                jnp.asarray(page_tables, jnp.int32),
                jnp.asarray(write_pp, jnp.int32), self.pool.arrays())
            self.pool.update_arrays(arrays)
            ph.dispatch(arrays)
        # one charged token per active lane, attributed to the tenant that
        # owns the lane's write page (seal_slot is fused in this dispatch)
        owners = [self.pool.owner_of(int(p))
                  for p, a in zip(wp_np, active_np) if a]
        self.pool.note_decode(n_act, owners=owners)
        return np.asarray(tok), np.asarray(ok)
