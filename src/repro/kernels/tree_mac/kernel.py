"""Pallas TPU kernel: chunked multilinear (Mersenne-31) MAC.

Computes the per-chunk tags of core.mac.block_tags for a word lattice
uint32[R, W] chunked along the last axis into W/CW chunks:

    tag[r, c] = canon( sum_j mulmod(fold(w[r, c*CW+j]) + 1, key[j])
                       + mulmod(pos(r,c) + 1, key[0]) )

The per-word multiply vectorizes across lanes.  The chunk sum folds
128-lane tiles together mod p and then reduces across lanes as two exact
int32 sums of 16-bit limbs, recombined mod p — the same residue as the
paper's §4.3 tree, without lane slices narrower than a vector register
(contrast: the paper's serial GFM costs 8 cycles per 128-bit block and is
why FC layers slow down 5.4x).

Layout: each grid step owns BLOCK_R whole rows and up to 128 chunks, so
the tag block is as wide as the tag array (or a multiple of 128 lanes);
tags of one step are assembled in registers and stored once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import common

BLOCK_R = 256
LANES = common.LANES
BLOCK_BYTES = 2 << 20        # cap on one input block in VMEM


def _mac_kernel(k0_ref, keys_ref, x_ref, o_ref, *, block_r: int, cw: int,
                cb: int, n_chunks_total: int):
    k0 = k0_ref[0]
    group = max(cw, LANES)                              # words per load
    per_group = group // cw                             # chunks per load
    keys = keys_ref[...]                                # [1, group]
    rows = ((pl.program_id(0) * block_r).astype(jnp.uint32)
            + jax.lax.broadcasted_iota(jnp.uint32, (block_r, 1), 0))
    chunk0 = pl.program_id(1) * cb
    lane_out = jax.lax.broadcasted_iota(jnp.int32, (block_r, cb), 1)
    seg = jax.lax.broadcasted_iota(jnp.int32, (block_r, group), 1) // cw

    def one_group(g, acc):
        off = pl.multiple_of(g * group, LANES)
        w = x_ref[:, pl.ds(off, group)]
        for s in range(per_group):
            c = g * per_group + s
            pos = common.canon(
                (rows * np.uint32(n_chunks_total)
                 + (chunk0 + c).astype(jnp.uint32)) * common.POS_MIX)
            ws = w if per_group == 1 else jnp.where(seg == s, w, 0)
            ks = keys if per_group == 1 else jnp.where(seg[:1] == s, keys, 0)
            acc = jnp.where(lane_out == c,
                            common.chunk_tag(ws, ks, pos, k0), acc)
        return acc

    o_ref[...] = jax.lax.fori_loop(0, cb // per_group, one_group,
                                   jnp.zeros((block_r, cb), jnp.uint32))


def _block_rows(R: int, row_bytes: int, block_r: int) -> int:
    br = min(block_r, R)
    while br > 8 and (br * row_bytes > BLOCK_BYTES or R % br):
        br //= 2
    assert R % br == 0, (R, br)
    return br


@functools.partial(jax.jit,
                   static_argnames=("chunk_words", "block_r", "interpret"))
def mac_tags_words(x: jax.Array, keys: jax.Array, *, chunk_words: int,
                   block_r: int = BLOCK_R, interpret: bool = False):
    """x: uint32[R, W] (W % chunk_words == 0, chunk_words a power of two);
    keys: uint32[chunk_words] canonical M31 keys. Returns uint32[R, W/cw]."""
    R, W = x.shape
    cw = chunk_words
    assert W % cw == 0 and (cw & (cw - 1)) == 0, (W, cw)
    n_chunks = W // cw
    # a tag block spans the whole tag row, or 128 lanes of it
    cb = LANES if n_chunks % LANES == 0 else n_chunks
    group = max(cw, LANES)
    assert (cb * cw) % group == 0, (cb, cw)
    br = _block_rows(R, cb * cw * 4, block_r)
    keys_row = jnp.tile(keys.astype(jnp.uint32), group // cw).reshape(1, group)
    return pl.pallas_call(
        functools.partial(_mac_kernel, block_r=br, cw=cw, cb=cb,
                          n_chunks_total=n_chunks),
        grid=(R // br, n_chunks // cb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # key[0]
            pl.BlockSpec((1, group), lambda i, j: (0, 0)),
            pl.BlockSpec((br, cb * cw), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((br, cb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, n_chunks), jnp.uint32),
        interpret=interpret,
    )(keys[:1].astype(jnp.uint32), keys_row, x)
