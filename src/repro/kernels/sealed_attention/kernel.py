"""Pallas TPU kernel: flash-decode attention over a SEALED KV cache.

One new token (GQA query [B, K, G, hd]) attends to a ciphertext-at-rest cache
k_ct/v_ct uint16[B, T, K, hd].  Per T-block:

  * the ciphertext tile is DMA'd HBM->VMEM (same bytes a plain decode moves),
  * keystream is regenerated in-register from the cache's (row, word) counter
    lattice (row = (b*T + t)*K + k, matching core.cipher.seal_bits) and XOR'd,
  * optional per-row MAC verification against the tag sidecar
    (chunk = one row's hd words — "verify every fetched piece"),
  * online-softmax (running max / normalizer / f32 accumulator in VMEM
    scratch) — the classic flash-decoding recurrence.

This closes the paper's within-step exposure window for serving: plaintext KV
exists only tile-by-tile in VMEM, never in HBM — the jnp path, by contrast,
materializes a decrypted copy of the whole cache.

TPU layout: the kernel reads the cache as 32-bit words (two bf16 per word,
even head dim in the low half) in a head-major [B, K, T, hd/2] view, so a
block is a (bt, hd/2) tile of one head.  Even and odd head dims are scored
and accumulated apart, so no lane is ever interleaved inside the kernel.
The wrapper builds that view with one transpose of the cache — a layout
copy the paged serving path does not pay, because it does not use this
kernel yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import common
from ..common import chunk_tag, halves

BT = 512
_NEG = -1e30


def _kernel(keys_ref, tv_ref, mkeys_ref, qe_ref, qo_ref, kct_ref, vct_ref,
            ktag_ref, vtag_ref, oe_ref, oo_ref, bad_ref, m_ref, l_ref,
            acc_e_ref, acc_o_ref, *, bt, T, K, G, hd, nt, verify):
    b = pl.program_id(0)
    kk = pl.program_id(1)
    t = pl.program_id(2)
    kk0, kk1, kv0, kv1, m0 = (keys_ref[n] for n in range(5))
    t_valid = tv_ref[0]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_e_ref[...] = jnp.zeros_like(acc_e_ref)
        acc_o_ref[...] = jnp.zeros_like(acc_o_ref)
        bad_ref[...] = jnp.zeros_like(bad_ref)

    hw = hd // 2
    rows = (((b * T + t * bt).astype(jnp.uint32)
             + jax.lax.broadcasted_iota(jnp.uint32, (bt, 1), 0))
            * np.uint32(K) + kk.astype(jnp.uint32))           # [bt, 1]
    words = jax.lax.broadcasted_iota(jnp.uint32, (bt, hw), 1)
    kct = kct_ref[0, 0]                                        # [bt, hw]
    vct = vct_ref[0, 0]
    valid = (t * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
             < t_valid)                                        # [bt, 1]

    def plain(ct, k0, k1):
        lo, hi = halves(ct ^ common.keystream_words(k0, k1, rows, words))
        zero = jnp.zeros((), jnp.float32)
        return (jnp.where(valid, lo.astype(jnp.float32), zero),
                jnp.where(valid, hi.astype(jnp.float32), zero))

    k_lo, k_hi = plain(kct, kk0, kk1)
    v_lo, v_hi = plain(vct, kv0, kv1)

    if verify:
        mk = mkeys_ref[...]                                    # [1, hw]
        pos = common.canon(rows * common.POS_MIX)
        badk = (chunk_tag(kct, mk, pos, m0) != ktag_ref[0, 0]) & valid
        badv = (chunk_tag(vct, mk, pos, m0) != vtag_ref[0, 0]) & valid
        bad = jnp.sum(badk.astype(jnp.int32) + badv.astype(jnp.int32),
                      keepdims=True)
        bad_ref[0, 0] += jnp.broadcast_to(bad, bad_ref.shape[2:])

    def nt_dot(x, y):                                          # x @ y.T
        return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def nn_dot(x, y):
        return jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    qe = qe_ref[0, 0].astype(jnp.float32)                      # [G, hw]
    qo = qo_ref[0, 0].astype(jnp.float32)
    s = (nt_dot(qe, k_lo) + nt_dot(qo, k_hi)) * (hd ** -0.5)   # [G, bt]
    tpos = t * bt + jax.lax.broadcasted_iota(jnp.int32, (G, bt), 1)
    s = jnp.where(tpos < t_valid, s, _NEG)

    m_prev = m_ref[...]                                        # [G, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                     # [G, bt]
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_e_ref[...] = acc_e_ref[...] * alpha + nn_dot(p, v_lo)
    acc_o_ref[...] = acc_o_ref[...] * alpha + nn_dot(p, v_hi)
    m_ref[...] = m_new

    @pl.when(t == nt - 1)
    def _fin():
        inv = 1.0 / jnp.maximum(l_ref[...], 1e-30)
        oe_ref[0, 0] = (acc_e_ref[...] * inv).astype(jnp.bfloat16)
        oo_ref[0, 0] = (acc_o_ref[...] * inv).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("bt", "verify", "interpret"))
def sealed_decode_attention(q, k_ct, v_ct, k_tags, v_tags, key_k, key_v,
                            mkeys, t_valid, *, bt: int = BT,
                            verify: bool = True, interpret: bool = False):
    """q: bf16[B, K, G, hd]; k_ct/v_ct: uint16[B, T, K, hd];
    k_tags/v_tags: uint32[B, T, K, 1]; key_k/key_v: uint32[2] tensor keys;
    mkeys: uint32[hd//2]; t_valid: int32 scalar.
    Returns (out bf16[B, K, G, hd], bad int32[B, K])."""
    B, K, G, hd = q.shape
    T = k_ct.shape[1]
    hw = hd // 2
    assert T % bt == 0
    nt = T // bt
    kern = functools.partial(_kernel, bt=bt, T=T, K=K, G=G, hd=hd, nt=nt,
                             verify=verify)

    def head_major(x):                       # [B, T, K, n] -> [B, K, T, n]
        return x.transpose(0, 2, 1, 3)

    keys = jnp.concatenate([key_k, key_v, mkeys[:1]]).astype(jnp.uint32)
    q4 = lambda: pl.BlockSpec((1, 1, G, hw), lambda b, k, t: (b, k, 0, 0))
    kv = lambda n: pl.BlockSpec((1, 1, bt, n), lambda b, k, t: (b, k, t, 0))
    oe, oo, bad = pl.pallas_call(
        kern,
        grid=(B, K, nt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # cipher keys, mac key 0
            pl.BlockSpec(memory_space=pltpu.SMEM),   # t_valid
            pl.BlockSpec((1, hw), lambda b, k, t: (0, 0)),
            q4(), q4(), kv(hw), kv(hw), kv(1), kv(1),
        ],
        out_specs=[q4(), q4(),
                   pl.BlockSpec((1, 1, 8, 128), lambda b, k, t: (b, k, 0, 0))],
        out_shape=(jax.ShapeDtypeStruct((B, K, G, hw), jnp.bfloat16),
                   jax.ShapeDtypeStruct((B, K, G, hw), jnp.bfloat16),
                   jax.ShapeDtypeStruct((B, K, 8, 128), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, hw), jnp.float32),
                        pltpu.VMEM((G, hw), jnp.float32)],
        interpret=interpret,
    )(keys, jnp.asarray(t_valid, jnp.int32).reshape(1),
      mkeys.reshape(1, -1).astype(jnp.uint32), q[..., 0::2], q[..., 1::2],
      head_major(common.words_of(k_ct)), head_major(common.words_of(v_ct)),
      head_major(k_tags), head_major(v_tags))
    out = jnp.stack([oe, oo], axis=-1).reshape(B, K, G, hd)
    return out, bad[:, :, 0, 0]
