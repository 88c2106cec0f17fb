"""Chunked message authentication — the TPU-native analogue of the paper's GMAC.

The paper's GFM module computes GMAC with a serial Horner chain over GF(2^128)
(8 cycles per 128-bit block, strong data dependency) and that serialization is
*the* cause of the 5.4x FC-layer slowdown in Table 1.  The paper's own fix list
(§4.3) proposes tree-structured authentication with O(log s) depth.

We implement exactly that, natively for the TPU VPU:

  * per-word map:    m_i = (w_i + 1) * k_i  mod  p,   p = 2^31 - 1  (Mersenne)
  * chunk tag:       tree-sum of m_i mod p                  (O(log s) depth)
  * cross-chunk tag: the chunk tags are themselves a word vector, hashed again
                     (a 2-level -> recursively O(log m) tree)

Multilinear hashing over a prime field is a classical eps-almost-universal MAC
family (Halevi-Krawczyk MMH); keys k_i are a per-tensor keystream derived from
the session key via the Threefry cipher, so tags are unforgeable without K and
the whole construction is encrypt-then-MAC over the ciphertext words.

Why Mersenne-31: products of 31-bit residues need 62-bit arithmetic; we do it
with 16-bit limb decomposition in uint32 lanes (mul/add/shift only), which maps
onto the VPU with no 64-bit or carry-less-multiply primitive required.

All functions are lazy-reduction: intermediate values may be in [0, 2^31+eps)
and are folded; ``canon`` produces the canonical residue.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import cipher

P31 = np.uint32(0x7FFFFFFF)  # 2^31 - 1
_MASK15 = np.uint32(0x7FFF)
_MASK16 = np.uint32(0xFFFF)


def fold32(x: jax.Array) -> jax.Array:
    """Reduce a uint32 value mod 2^31-1, lazily (result < 2^31 + 1)."""
    return (x >> 31) + (x & P31)


def canon(x: jax.Array) -> jax.Array:
    """Canonical residue in [0, p)."""
    x = fold32(x)
    x = fold32(x)
    return jnp.where(x == P31, jnp.uint32(0), x)


def times2_16(x: jax.Array) -> jax.Array:
    """(x * 2^16) mod p for any uint32 x (lazy result): with x folded below
    2^31 + 1, x = xh*2^15 + xl  =>  x*2^16 = xh + xl*2^16 (mod p)."""
    x = fold32(x)
    return (x >> 15) + ((x & _MASK15) << 16)


def mulmod(a: jax.Array, b: jax.Array) -> jax.Array:
    """(a * b) mod 2^31-1 for a, b < 2^31, via 16-bit limbs (lazy result).

    a*b = a1*b1*2^32 + (a1*b0 + a0*b1)*2^16 + a0*b0, with 2^32 = 2 (mod p).
    """
    a = fold32(a)
    b = fold32(b)
    a0 = a & _MASK16
    a1 = a >> 16
    b0 = b & _MASK16
    b1 = b >> 16
    hi = a1 * b1                       # < 2^32, exact in uint32? (2^16-1)^2 < 2^32: yes
    mid = fold32(a1 * b0) + fold32(a0 * b1)   # each < 2^31+1; sum < 2^32
    lo = a0 * b0                              # < 2^32, exact

    hi_red = fold32(fold32(hi) * jnp.uint32(2))        # *2^32 == *2 mod p
    mid_red = times2_16(fold32(mid))
    lo_red = fold32(lo)
    out = fold32(hi_red + mid_red)     # < 2^32 before fold
    out = fold32(out + lo_red)
    return out


def addmod(a: jax.Array, b: jax.Array) -> jax.Array:
    # fold each operand twice (fold32(2^32-1) = 2^31 needs a second
    # pass) so the uint32 add can never wrap
    return fold32(fold32(fold32(a)) + fold32(fold32(b)))


def _tree_sum_mod(v: jax.Array) -> jax.Array:
    """Sum a uint32 vector mod p with an O(log n) balanced tree."""
    n = v.shape[0]
    while n > 1:
        half = (n + 1) // 2
        pad = half * 2 - n
        if pad:
            v = jnp.concatenate([v, jnp.zeros((pad,), jnp.uint32)])
        v = addmod(v[0::2], v[1::2])
        n = half
    return v[0]


def mac_keys(key: jax.Array, n_words: int, domain: int = 0xA11CE) -> jax.Array:
    """Derive n_words multilinear keys in [0, p) from the session key."""
    sub = cipher.derive_key(key, domain)
    ks = cipher.keystream_words(sub, jnp.uint32(0), n_words)
    return canon(ks)


def chunk_tags(words: jax.Array, keys: jax.Array) -> jax.Array:
    """Per-chunk multilinear tags.

    words: uint32[m, s] ciphertext chunks (s words each, zero-padded).
    keys:  uint32[s]    multilinear keys (reused across chunks; chunk index is
                        mixed in as an affine term so identical chunks at
                        different positions get distinct tags).
    Returns uint32[m] canonical tags.
    """
    m, s = words.shape
    w = fold32(fold32(words) + jnp.uint32(1))          # (w_i + 1): avoid zero-absorption
    prod = mulmod(w, keys[None, :])                    # [m, s]
    # tree reduce along axis 1
    v = prod
    n = s
    while n > 1:
        half = (n + 1) // 2
        pad = half * 2 - n
        if pad:
            v = jnp.concatenate([v, jnp.zeros((m, pad), jnp.uint32)], axis=1)
        v = addmod(v[:, 0::2], v[:, 1::2])
        n = half
    pos = canon(jnp.arange(m, dtype=jnp.uint32) * jnp.uint32(0x9E3779B1))
    return canon(addmod(v[:, 0], mulmod(pos + jnp.uint32(1), keys[0])))


def combine_tags(tags: jax.Array, keys: jax.Array) -> jax.Array:
    """Combine per-chunk tags into one root tag (Merkle-style tree of hashes).

    Recursively multilinear-hash the tag vector in groups of len(keys) until a
    single word remains — O(log m) depth overall, the paper's §4.3 suggestion.
    """
    s = keys.shape[0]
    while tags.shape[0] > 1:
        m = tags.shape[0]
        groups = (m + s - 1) // s
        pad = groups * s - m
        if pad:
            tags = jnp.concatenate([tags, jnp.zeros((pad,), jnp.uint32)])
        tags = chunk_tags(tags.reshape(groups, s), keys)
    return tags[0]


def mac_tensor_words(words: jax.Array, key: jax.Array, chunk_words: int,
                     domain: int = 0xA11CE):
    """MAC a flat uint32 word array in chunks (paper §3.3.2 chunked scheme).

    Returns (tags uint32[m], root uint32 scalar).  ``chunk_words`` is the
    paper's piece size ``s`` (in 4-byte words); m = ceil(n / s).
    """
    n = words.shape[0]
    m = (n + chunk_words - 1) // chunk_words
    pad = m * chunk_words - n
    if pad:
        words = jnp.concatenate([words, jnp.zeros((pad,), jnp.uint32)])
    keys = mac_keys(key, chunk_words, domain)
    tags = chunk_tags(words.reshape(m, chunk_words), keys)
    root = combine_tags(tags, keys)
    return tags, root


def verify_tags(words: jax.Array, key: jax.Array, chunk_words: int,
                tags: jax.Array, domain: int = 0xA11CE) -> jax.Array:
    """Recompute chunk tags and compare. Returns bool[] per chunk."""
    got, _ = mac_tensor_words(words, key, chunk_words, domain)
    return got == tags


def tag_root(words: jax.Array, key: jax.Array, chunk_words: int,
             domain: int = 0xA11CE) -> jax.Array:
    """One uint32 root tag over a flat word array (chunk tags + tree combine).

    The unit of authentication for *slices*: an open KV page accumulates one
    such root per written token slot (serve/kv_pager.py), and the roots are
    folded into the whole-page MAC when the page closes.  Cost is
    O(len(words)) — exactly the bytes being written, the paper's §3.4 model.
    """
    _, root = mac_tensor_words(words, key, chunk_words, domain)
    return root


# ---------------------------------------------------------------------------
# SHAPED (shard-local) chunked MAC — tags along the last axis.
#
# The paper's accelerator verifies each fetched *piece*; on TPU the fetched
# piece is a tile of the tensor, which is always local to a device under any
# sharding of the leading/last axes.  Chunking along the last axis keeps tag
# computation collective-free inside a distributed step (chunk_words must
# divide the per-shard last-dim word count; all our config dims are multiples
# of 128 so this holds for the default chunk sizes).
# ---------------------------------------------------------------------------

def sum_mod(v: jax.Array, axis: int = -1) -> jax.Array:
    """Sum residues mod p along ``axis`` (lazy result).

    Two exact uint32 sums of 16-bit limbs (exact for up to 2^16 terms)
    recombined mod p: the same residue as a tree of ``addmod``, as one
    reduction the compiler fuses with its producer.
    """
    assert v.shape[axis] <= 1 << 16, v.shape
    v = fold32(fold32(v))                                   # each <= p
    lo = jnp.sum(v & _MASK16, axis=axis, dtype=jnp.uint32)
    hi = jnp.sum(v >> 16, axis=axis, dtype=jnp.uint32)
    return addmod(times2_16(hi), lo)


def block_tags(ct: jax.Array, key: jax.Array, chunk_words: int,
               domain: int = 0xA11CE) -> jax.Array:
    """Per-chunk tags, chunked along the last axis.

    ct: uintN[..., last].  Returns uint32[..., n_chunks] canonical tags.
    Each tag authenticates one contiguous run of ``chunk_words`` 4-byte words
    (the paper's piece size s), keyed by position so chunks cannot be swapped.

    Sub-word ciphertext is hashed element by element: word w_i packs its
    elements little-endian, so (w_i + 1) * k_i = (e_0 + 1) * k_i
    + sum_j e_j * (2^(bits*j) * k_i) mod p.  That is the same residue as
    hashing the packed words, without ever packing them.
    """
    isz = jnp.dtype(ct.dtype).itemsize
    epw = 4 // isz                                  # elements per word
    pad = (-ct.shape[-1]) % epw
    if pad:
        ct = jnp.pad(ct, [(0, 0)] * (ct.ndim - 1) + [(0, pad)])
    last_w = ct.shape[-1] // epw
    # divisor-aligned chunking: pick the smallest chunk count >= words/s that
    # divides the word count exactly, so the reshape is layout-only and never
    # pads across shard boundaries (keeps tag computation shard-local).
    n_chunks = (last_w + chunk_words - 1) // chunk_words
    while last_w % n_chunks:
        n_chunks += 1
    chunk_words = last_w // n_chunks
    keys = mac_keys(key, chunk_words, domain)                       # [cw]
    x = ct.reshape(*ct.shape[:-1], n_chunks, chunk_words * epw)
    if epw == 1:
        v = mulmod(fold32(fold32(x) + jnp.uint32(1)), keys)
    else:
        scale = np.array([(1 << (8 * isz * j)) % int(P31)
                          for j in range(epw)], np.uint32)
        kel = mulmod(keys[:, None], scale[None, :]).reshape(-1)    # [cw*epw]
        one = (np.arange(chunk_words * epw) % epw == 0).astype(np.uint32)
        v = mulmod(x.astype(jnp.uint32) + one, kel)
    tag = sum_mod(v)                                                # [..., nc]
    # position mixing: global chunk index = row * n_chunks + chunk
    row = jnp.zeros(tag.shape, jnp.uint32)
    stride = 1
    for d in range(tag.ndim - 1, -1, -1):
        row = row + jax.lax.broadcasted_iota(jnp.uint32, tag.shape, d) * np.uint32(stride)
        stride *= tag.shape[d]
    pos = canon(row * jnp.uint32(0x9E3779B1))
    return canon(addmod(tag, mulmod(pos + jnp.uint32(1), keys[0])))


def verify_block_tags(ct: jax.Array, key: jax.Array, chunk_words: int,
                      tags: jax.Array, domain: int = 0xA11CE) -> jax.Array:
    """Elementwise tag comparison; reduce with .all() for a scalar verdict."""
    return block_tags(ct, key, chunk_words, domain) == tags
