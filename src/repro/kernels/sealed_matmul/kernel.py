"""Pallas TPU kernel: fused decrypt -> MXU matmul -> verify over sealed tiles.

C = unseal(A_ct) @ unseal(B_ct), with A, B bf16 tensors stored in untrusted
HBM as same-shape uint16 ciphertext (counter mode).  This is the TPU-native
expression of the paper's "decrypt on demand at the SRAM boundary":

  * each (bm x bk) / (bk x bn) ciphertext tile is DMA'd HBM->VMEM exactly as
    a plain matmul would move it — sealing adds ZERO extra HBM traffic;
  * the keystream is regenerated in-register from the (row, word) counter
    lattice (Threefry ARX on the VPU) and XOR'd before the MXU dot;
  * each fetched tile's chunk MAC (Mersenne-31 multilinear, chunk = one tile
    row-segment, i.e. the paper's piece size s = bk words) is recomputed and
    compared against the tag sidecar — "verify every fetched piece";
  * the f32 accumulators live in VMEM scratch across the K grid dimension;
    mismatch counts accumulate into an i32 output (nonzero => poisoned launch).

The kernel works on 32-bit words, two bf16 per word (low half = even
column).  It never interleaves lanes: A's even and odd K columns multiply
B's even and odd K rows (a sublane-strided load), and C's even and odd
columns accumulate apart and are packed back into words on the way out.

Chunk/tag layout: tags_a uint32[M, K/bk] (chunk c of row r covers A words
[r, c*bk/2 : (c+1)*bk/2]), tags_b uint32[K, N/bn] likewise.  Tag position
mixing matches core.mac.block_tags with n_chunks = K/bk (resp. N/bn).  A
grid step reads whole tag rows and picks its chunk's lane by mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import common
from ..common import halves

BM, BK, BN = 256, 256, 256


def _bits(x):
    """bf16-valued f32 -> its 16 bits in the high half of a word."""
    return jax.lax.bitcast_convert_type(
        x.astype(jnp.bfloat16).astype(jnp.float32), jnp.uint32) & common.HI16


def _unseal(ct, k0, k1, rows, word0):
    words = word0 + jax.lax.broadcasted_iota(jnp.uint32, ct.shape, 1)
    return ct ^ common.keystream_words(k0, k1, rows, words)


def _sealed_matmul_kernel(keys_ref, mkeys_ref, a_ref, b_ref, tag_a_ref,
                          tag_b_ref, o_ref, bad_ref, acc_e_ref, acc_o_ref, *,
                          bm, bk, bn, nk, n_chunks_a, n_chunks_b, verify):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    ka0, ka1, kb0, kb1, m0 = (keys_ref[n] for n in range(5))

    @pl.when(k == 0)
    def _zero():
        acc_e_ref[...] = jnp.zeros_like(acc_e_ref)
        acc_o_ref[...] = jnp.zeros_like(acc_o_ref)
        bad_ref[...] = jnp.zeros_like(bad_ref)

    row_a = ((i * bm).astype(jnp.uint32)
             + jax.lax.broadcasted_iota(jnp.uint32, (bm, 1), 0))
    a_lo, a_hi = halves(_unseal(a_ref[...], ka0, ka1, row_a,
                                (k * (bk // 2)).astype(jnp.uint32)))
    half_k = jax.lax.broadcasted_iota(jnp.uint32, (bk // 2, 1), 0)
    word_b = (j * (bn // 2)).astype(jnp.uint32)
    row_b = (k * bk).astype(jnp.uint32) + 2 * half_k
    be_lo, be_hi = halves(_unseal(b_ref[pl.ds(0, bk // 2, stride=2), :],
                                  kb0, kb1, row_b, word_b))
    bo_lo, bo_hi = halves(_unseal(b_ref[pl.ds(1, bk // 2, stride=2), :],
                                  kb0, kb1, row_b + np.uint32(1), word_b))

    def dot(x, y):
        return jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    acc_e_ref[...] += dot(a_lo, be_lo) + dot(a_hi, bo_lo)
    acc_o_ref[...] += dot(a_lo, be_hi) + dot(a_hi, bo_hi)

    if verify:
        mk = mkeys_ref[...]                                  # [1, bk//2]

        def mismatches(ct, tags, rows, chunk, n_chunks):
            pos = common.canon((rows * np.uint32(n_chunks)
                                + chunk.astype(jnp.uint32)) * common.POS_MIX)
            want = common.chunk_tag(ct, mk, pos, m0)         # [R, 1]
            lane = jax.lax.broadcasted_iota(jnp.int32, tags.shape, 1)
            return jnp.sum(((lane == chunk) & (tags != want)).astype(jnp.int32),
                           keepdims=True)

        row_bt = ((k * bk).astype(jnp.uint32)
                  + jax.lax.broadcasted_iota(jnp.uint32, (bk, 1), 0))
        bad = (mismatches(a_ref[...], tag_a_ref[...], row_a, k, n_chunks_a)
               + mismatches(b_ref[...], tag_b_ref[...], row_bt, j,
                            n_chunks_b))
        bad_ref[...] += jnp.broadcast_to(bad, bad_ref.shape)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = ((_bits(acc_e_ref[...]) >> np.uint32(16))
                      | _bits(acc_o_ref[...]))


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "verify",
                                             "interpret"))
def sealed_matmul(a_ct: jax.Array, b_ct: jax.Array, tags_a: jax.Array,
                  tags_b: jax.Array, key_a: jax.Array, key_b: jax.Array,
                  mac_keys_arr: jax.Array, *, bm: int = BM, bk: int = BK,
                  bn: int = BN, verify: bool = True, interpret: bool = False):
    """a_ct: uint16[M, K]; b_ct: uint16[K, N]; tags_*: uint32 chunk tags.

    key_a/key_b: uint32[2] per-tensor keys (derive_tensor_key(master, nonce)).
    mac_keys_arr: uint32[bk//2] canonical M31 keys (mac.mac_keys of the
    nonce-bound MAC key).  Returns (C bf16[M, N], bad int32[gm, gn]).
    """
    M, K = a_ct.shape
    K2, N = b_ct.shape
    assert K == K2 and M % bm == 0 and K % bk == 0 and N % bn == 0
    nk = K // bk
    gm, gn = M // bm, N // bn
    kern = functools.partial(
        _sealed_matmul_kernel, bm=bm, bk=bk, bn=bn, nk=nk,
        n_chunks_a=K // bk, n_chunks_b=N // bn, verify=verify)
    keys = jnp.concatenate([key_a, key_b, mac_keys_arr[:1]]).astype(jnp.uint32)
    c_words, bad = pl.pallas_call(
        kern,
        grid=(gm, gn, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # key_a, key_b, mac key 0
            pl.BlockSpec((1, bk // 2), lambda i, j, k: (0, 0)),
            pl.BlockSpec((bm, bk // 2), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn // 2), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, K // bk), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bk, N // bn), lambda i, j, k: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn // 2), lambda i, j, k: (i, j)),
            pl.BlockSpec((8, 128), lambda i, j, k: (i, j)),
        ],
        out_shape=(jax.ShapeDtypeStruct((M, N // 2), jnp.uint32),
                   jax.ShapeDtypeStruct((gm * 8, gn * 128), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((bm, bn // 2), jnp.float32),
                        pltpu.VMEM((bm, bn // 2), jnp.float32)],
        interpret=interpret,
    )(keys, mac_keys_arr.reshape(1, -1).astype(jnp.uint32),
      common.words_of(a_ct), common.words_of(b_ct), tags_a, tags_b)
    return common.bf16_of_words(c_words), bad[::8, ::128]
