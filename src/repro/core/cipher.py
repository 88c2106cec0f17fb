"""Counter-mode cipher for sealing tensors — the TPU-native analogue of AES-CTR.

The paper (§3.3.2) requires a counter-mode scheme so that decryption of a fetched
piece has no data dependency ("the ciphertext is XORed with AES(counter)").  AES's
byte-oriented S-box does not map to TPU 8x128 32-bit vector lanes, so we use an
ARX block function instead: Threefry-2x32 (the Skein/Threefish reduction used by
JAX's own PRNG), which needs only 32-bit add / xor / rotate — all native VPU ops.

Security role is identical to AES-CTR in the paper:
  * keystream block i  =  threefry2x32(key, (nonce, i))          (2 words / block)
  * seal / unseal      =  XOR with keystream                      (size-preserving)
  * counter uniqueness =  (tensor nonce, block index) never reused; re-encryption
                          bumps the nonce (see sealed.py).

This module is the *reference / jnp* path; the Pallas kernel in
``repro.kernels.ctr_cipher`` implements the same function tile-by-tile in VMEM and
is validated bit-exactly against ``keystream_blocks`` below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Threefry-2x32 constants (Salmon et al., SC'11), as in JAX's PRNG.
_ROTATIONS = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = np.uint32(0x1BD11BDA)
_N_ROUNDS = 20  # full-strength; 5 injection points


def _rotl(x: jax.Array, r: int) -> jax.Array:
    r = r % 32
    return (x << r) | (x >> (32 - r))


def threefry2x32(key: jax.Array, x0: jax.Array, x1: jax.Array):
    """Threefry-2x32 block function.

    key: uint32[2] (k0, k1).  x0, x1: uint32 arrays (the counter words).
    Returns (y0, y1) uint32 arrays of the same shape.
    """
    k0 = key[0]
    k1 = key[1]
    k2 = k0 ^ k1 ^ _PARITY
    ks = (k0, k1, k2)

    x0 = x0 + k0
    x1 = x1 + k1
    for block in range(5):  # 5 blocks of 4 rounds
        rots = _ROTATIONS[:4] if block % 2 == 0 else _ROTATIONS[4:]
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x0 ^ x1
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
    return x0, x1


def keystream_blocks(key: jax.Array, nonce: jax.Array, block_ids: jax.Array):
    """Keystream for a run of counter blocks.

    key: uint32[2]; nonce: uint32 scalar; block_ids: uint32[n].
    Returns uint32[n, 2] — two keystream words per counter block.
    """
    y0, y1 = threefry2x32(key, jnp.broadcast_to(nonce, block_ids.shape), block_ids)
    return jnp.stack([y0, y1], axis=-1)


def keystream_words(key: jax.Array, nonce: jax.Array, n_words: int,
                    word_offset: int | jax.Array = 0) -> jax.Array:
    """Flat uint32 keystream of length ``n_words`` starting at ``word_offset``.

    word_offset must be block-aligned when used for partial streams (callers in
    sealed.py always use 0); we still handle odd offsets by generating the
    covering blocks and slicing.
    """
    word_offset = jnp.asarray(word_offset, jnp.uint32)
    first_block = word_offset // 2
    n_blocks = (n_words + 1 + 1) // 2  # cover a possible leading odd word
    ids = first_block + jnp.arange(n_blocks + 1, dtype=jnp.uint32)
    ks = keystream_blocks(key, nonce, ids).reshape(-1)
    start = word_offset % 2
    return jax.lax.dynamic_slice(ks, (start,), (n_words,))


# ---------------------------------------------------------------------------
# dtype <-> uint32 word packing
# ---------------------------------------------------------------------------

def _itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def words_for(shape, dtype) -> int:
    """Number of uint32 words a tensor packs into (padded)."""
    n_bytes = int(np.prod(shape)) * _itemsize(dtype) if len(shape) else _itemsize(dtype)
    return (n_bytes + 3) // 4


def pack_words(x: jax.Array) -> jax.Array:
    """Bitcast any-dtype tensor to a flat uint32 word array (zero-padded)."""
    dtype = x.dtype
    flat = x.reshape(-1)
    isz = _itemsize(dtype)
    if isz == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if isz == 8:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(-1)
    # sub-word dtypes: pad element count to a word boundary, group, bitcast
    per_word = 4 // isz
    pad = (-flat.shape[0]) % per_word
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), dtype)])
    grouped = flat.reshape(-1, per_word)
    return jax.lax.bitcast_convert_type(grouped, jnp.uint32)


def unpack_words(w: jax.Array, shape, dtype) -> jax.Array:
    """Inverse of pack_words."""
    dtype = jnp.dtype(dtype)
    isz = dtype.itemsize
    n_elems = int(np.prod(shape)) if len(shape) else 1
    if isz == 4:
        flat = jax.lax.bitcast_convert_type(w, dtype)
    elif isz == 8:
        flat = jax.lax.bitcast_convert_type(w.reshape(-1, 2), dtype)
    else:
        per_word = 4 // isz
        flat = jax.lax.bitcast_convert_type(w, dtype)  # uint32 -> [n, per_word]
        flat = flat.reshape(-1)
    return flat[:n_elems].reshape(shape)


# ---------------------------------------------------------------------------
# seal / unseal (XOR with keystream) — Rule 1 & Rule 2 of the paper
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=())
def xor_words(words: jax.Array, key: jax.Array, nonce: jax.Array) -> jax.Array:
    """XOR a flat uint32 word array with the (key, nonce) keystream.

    Involutive: applying twice recovers the input.  This is the whole data path
    of counter-mode — identical cost for seal and unseal, no data dependency.
    """
    n = words.shape[0]
    n_blocks = (n + 1) // 2
    ids = jnp.arange(n_blocks, dtype=jnp.uint32)
    ks = keystream_blocks(key, nonce, ids).reshape(-1)[:n]
    return words ^ ks


def encrypt(x: jax.Array, key: jax.Array, nonce) -> jax.Array:
    """Counter-mode encrypt a tensor -> flat uint32 ciphertext words."""
    nonce = jnp.asarray(nonce, jnp.uint32)
    return xor_words(pack_words(x), key, nonce)


def decrypt(ct_words: jax.Array, key: jax.Array, nonce, shape, dtype) -> jax.Array:
    """Counter-mode decrypt flat uint32 ciphertext words -> tensor."""
    nonce = jnp.asarray(nonce, jnp.uint32)
    return unpack_words(xor_words(ct_words, key, nonce), shape, dtype)


def derive_key(master: jax.Array, domain: int) -> jax.Array:
    """Derive a (uint32[2]) subkey from a master key for a domain separator."""
    y0, y1 = threefry2x32(master, jnp.asarray(domain, jnp.uint32),
                          jnp.asarray(0x5EA1ED, jnp.uint32))
    return jnp.stack([y0, y1])


def derive_tensor_key(master: jax.Array, nonce: jax.Array) -> jax.Array:
    """Per-(tensor, version) key: counter space is then (row, word) within it."""
    y0, y1 = threefry2x32(master, jnp.asarray(nonce, jnp.uint32),
                          jnp.asarray(0x7E4503, jnp.uint32))
    return jnp.stack([y0, y1])


# ---------------------------------------------------------------------------
# SHAPED sealing — ciphertext keeps the tensor shape so PartitionSpecs apply.
#
# Counter block for element [i0,...,ik, e] (last axis e):
#     row   = flattened leading index (i0..ik)      (< 2^31 in all our configs)
#     block = (e // elems_per_word) // 2
# threefry(tensor_key, row, block) -> 2 words, interleaved to the word stream
# of that row.  (row, block) pairs are unique within a tensor; tensor_key is
# unique per (master key, nonce); re-sealing bumps the nonce => no counter
# reuse, the CTR-mode requirement (paper §3.3.2).
# ---------------------------------------------------------------------------

_UINT_FOR_SIZE = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def uint_dtype_for(dtype):
    return _UINT_FOR_SIZE[jnp.dtype(dtype).itemsize]


def _row_index(shape) -> jax.Array:
    """uint32 flattened-leading-dims index, broadcast to ``shape``."""
    if len(shape) <= 1:
        return jnp.zeros(shape, jnp.uint32)
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in range(len(shape) - 2, -1, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, d) * np.uint32(stride)
        stride *= shape[d]
    return idx


def _keystream_elems(tkey: jax.Array, rows: jax.Array, elems: jax.Array,
                     dtype) -> jax.Array:
    """Keystream for elements at (row, element index along the last axis).

    Element e lives in word e // epw, which is half (word % 2) of block
    word // 2.  Each element evaluates its own block, so the keystream is
    one elementwise expression with no interleave or repeat between blocks
    and elements: the compiler fuses it into the XOR that consumes it, and
    never holds a keystream-sized temporary.
    """
    isz = jnp.dtype(dtype).itemsize
    epw = 4 // isz
    word = elems // np.uint32(epw) if epw > 1 else elems
    y0, y1 = threefry2x32(tkey, rows, word >> np.uint32(1))
    w = jnp.where((word & np.uint32(1)) == 0, y0, y1)
    if epw == 1:
        return w
    shift = (elems % np.uint32(epw)) * np.uint32(8 * isz)
    mask = np.uint32((1 << (8 * isz)) - 1)
    return ((w >> shift) & mask).astype(_UINT_FOR_SIZE[isz])


def keystream_like(key: jax.Array, nonce, shape, dtype) -> jax.Array:
    """Keystream with the tensor's own shape, as the matching unsigned dtype."""
    shape = tuple(shape) if len(shape) else (1,)
    tkey = derive_tensor_key(key, jnp.asarray(nonce, jnp.uint32))
    elems = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
    return _keystream_elems(tkey, _row_index(shape), elems, dtype)


def keystream_for_rows(key: jax.Array, nonce, rows: jax.Array, last: int,
                       dtype) -> jax.Array:
    """Keystream for an arbitrary row-slice of a sealed tensor.

    rows: uint32[...] explicit row indices into the full tensor's leading-dim
    lattice; returns keystream of shape rows.shape + (last,) in the matching
    unsigned dtype.  Used to seal/unseal KV-cache *slices* (one token's slot)
    without touching the rest — write cost proportional to bytes written,
    exactly the paper's §3.4 cost model.
    """
    tkey = derive_tensor_key(key, jnp.asarray(nonce, jnp.uint32))
    shape = rows.shape + (last,)
    row_b = jnp.broadcast_to(rows[..., None].astype(jnp.uint32), shape)
    elems = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
    return _keystream_elems(tkey, row_b, elems, dtype)


def seal_bits_slice(x: jax.Array, key: jax.Array, nonce, rows: jax.Array):
    """Seal a row-slice (x: rows.shape + (last,)) against full-tensor counters."""
    udt = uint_dtype_for(x.dtype)
    raw = jax.lax.bitcast_convert_type(x, udt)
    return raw ^ keystream_for_rows(key, nonce, rows, x.shape[-1], x.dtype)


def seal_bits(x: jax.Array, key: jax.Array, nonce) -> jax.Array:
    """Shaped CTR encryption: same-shape unsigned-int ciphertext (shardable)."""
    shape = x.shape if x.ndim else (1,)
    udt = uint_dtype_for(x.dtype)
    raw = jax.lax.bitcast_convert_type(x.reshape(shape), udt)
    return raw ^ keystream_like(key, nonce, shape, x.dtype)


def unseal_bits(ct: jax.Array, key: jax.Array, nonce, dtype) -> jax.Array:
    """Inverse of seal_bits."""
    ks = keystream_like(key, nonce, ct.shape, dtype)
    return jax.lax.bitcast_convert_type(ct ^ ks, jnp.dtype(dtype))
