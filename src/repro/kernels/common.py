"""Shared in-kernel primitives: Threefry-2x32 rounds and M31 modular ops.

These are plain jnp expressions usable both inside Pallas kernel bodies and
in the jnp reference paths — guaranteeing bit-exact agreement between the
kernel and its oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_ROTS_A = (13, 15, 26, 6)
_ROTS_B = (17, 29, 16, 24)
_PARITY = np.uint32(0x1BD11BDA)


def rotl(x, r: int):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """Scalar keys k0,k1 (uint32); array counters x0,x1. 20 rounds."""
    k2 = k0 ^ k1 ^ _PARITY
    ks = (k0, k1, k2)
    x0 = x0 + k0
    x1 = x1 + k1
    for block in range(5):
        rots = _ROTS_A if block % 2 == 0 else _ROTS_B
        for r in rots:
            x0 = x0 + x1
            x1 = rotl(x1, r)
            x1 = x0 ^ x1
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
    return x0, x1


def keystream_tile(k0, k1, rows, blocks):
    """rows/blocks: uint32 [R, NB] counter lattices -> uint32 [R, 2*NB] words."""
    y0, y1 = threefry2x32(k0, k1, rows, blocks)
    R, NB = y0.shape
    return jnp.stack([y0, y1], axis=-1).reshape(R, 2 * NB)


def keystream_words(k0, k1, rows, words):
    """rows/words: uint32 counter lattices of one shape -> keystream words.

    Word w is half (w % 2) of block w // 2, evaluated lane by lane: the
    same values as ``keystream_tile`` without its lane interleave, which a
    TPU kernel cannot afford; each block is computed twice instead.
    """
    y0, y1 = threefry2x32(k0, k1, rows, words >> np.uint32(1))
    return jnp.where((words & np.uint32(1)) == 0, y0, y1)


# --- Mersenne-31 ops (see core.mac) ---------------------------------------

P31 = np.uint32(0x7FFFFFFF)
_M15 = np.uint32(0x7FFF)
_M16 = np.uint32(0xFFFF)


def fold32(x):
    return (x >> np.uint32(31)) + (x & P31)


def addmod(a, b):
    return fold32(fold32(fold32(a)) + fold32(fold32(b)))


def times2_16(x):
    """(x * 2^16) mod p for any uint32 x (lazy result)."""
    x = fold32(x)
    return (x >> np.uint32(15)) + ((x & _M15) << np.uint32(16))


def mulmod(a, b):
    a = fold32(a)
    b = fold32(b)
    a0, a1 = a & _M16, a >> np.uint32(16)
    b0, b1 = b & _M16, b >> np.uint32(16)
    hi = a1 * b1
    mid = fold32(a1 * b0) + fold32(a0 * b1)
    lo = a0 * b0
    mid_red = times2_16(fold32(mid))
    hi_red = fold32(fold32(hi) * np.uint32(2))
    out = fold32(hi_red + mid_red)
    return fold32(out + fold32(lo))


def canon(x):
    x = fold32(fold32(x))
    return jnp.where(x == P31, jnp.uint32(0), x)


# --- chunk tags (see core.mac.block_tags) ----------------------------------

LANES = 128
POS_MIX = np.uint32(0x9E3779B1)        # chunk position mixing


def lane_sum_mod(v):
    """[N, L] uint32 residues -> [N, 1] their sum mod p (lazy).

    Two exact int32 sums of 16-bit limbs, recombined mod p: the residue of
    a tree of ``addmod``, without lane slices narrower than a register."""
    v = fold32(fold32(v))                               # each <= p
    lo = jnp.sum((v & _M16).astype(jnp.int32), axis=1, keepdims=True)
    hi = jnp.sum((v >> np.uint32(16)).astype(jnp.int32), axis=1,
                 keepdims=True)
    return addmod(times2_16(hi.astype(jnp.uint32)), lo.astype(jnp.uint32))


def chunk_tag(words, keys, pos, k0):
    """Multilinear tag of each row of ``words`` [N, cw] with ``keys`` [1, cw]
    and position residues ``pos`` [N, 1]."""
    v = mulmod(fold32(fold32(words) + np.uint32(1)), keys)
    while v.shape[1] > LANES:                           # fold lane tiles
        half = v.shape[1] // 2
        v = addmod(v[:, :half], v[:, half:])
    return canon(addmod(lane_sum_mod(v), mulmod(pos + np.uint32(1), k0)))


# --- bf16 pairs in 32-bit words (element 2j in the low half) ---------------

HI16 = np.uint32(0xFFFF0000)


def words_of(x16):
    """uint16[..., 2n] -> uint32[..., n]."""
    return jax.lax.bitcast_convert_type(
        x16.reshape(*x16.shape[:-1], x16.shape[-1] // 2, 2), jnp.uint32)


def bf16_of_words(w):
    """uint32[..., n] -> bf16[..., 2n], the inverse of ``words_of``."""
    x16 = jax.lax.bitcast_convert_type(w, jnp.uint16)
    return jax.lax.bitcast_convert_type(
        x16.reshape(*w.shape[:-1], 2 * w.shape[-1]), jnp.bfloat16)


def halves(w):
    """Plaintext words -> (low-half bf16, high-half bf16), exactly, with no
    lane interleave: each half is shifted into a float32's top bits."""
    lo = jax.lax.bitcast_convert_type(w << np.uint32(16), jnp.float32)
    hi = jax.lax.bitcast_convert_type(w & HI16, jnp.float32)
    return lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)
