"""Pallas TPU kernel: counter-mode keystream generation + XOR (seal/unseal).

Operates on the canonical word lattice: x is uint32[R, W] (rows x words); the
keystream word at (r, w) is word (w % 2) of threefry2x32(tkey, r, w // 2).
Involutive — the same kernel seals and unseals.

Tiling: (BLOCK_R, BLOCK_W) uint32 tiles move HBM<->VMEM; the keystream is
generated in registers from the (row, block) iota lattice, one STRIP_R-row
strip at a time — no keystream traffic to HBM, which is the whole point of
adapting counter mode to the TPU: crypto rides on the existing HBM<->VMEM
tile movement exactly as the paper's crypto engine rides on the DRAM
interface (§3.4).

Each lane evaluates the block of its own word and keeps y0 or y1 by word
parity, so no lane interleave is needed; the price is that every Threefry
block is evaluated twice.  The tile key is a scalar pair read from SMEM.
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
BLOCK_W = 256
STRIP_R = 8          # rows of keystream held in registers at a time


def _ctr_kernel(key_ref, x_ref, o_ref, *, block_r: int, block_w: int):
    k0 = key_ref[0]
    k1 = key_ref[1]
    row0 = (pl.program_id(0) * block_r).astype(jnp.uint32)
    word0 = (pl.program_id(1) * block_w).astype(jnp.uint32)
    shape = (STRIP_R, block_w)
    words = word0 + jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    rows_in_strip = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)

    def strip(s, carry):
        r = pl.multiple_of(s * STRIP_R, STRIP_R)
        rows = row0 + r.astype(jnp.uint32) + rows_in_strip
        ks = common.keystream_words(k0, k1, rows, words)
        o_ref[pl.ds(r, STRIP_R), :] = x_ref[pl.ds(r, STRIP_R), :] ^ ks
        return carry

    jax.lax.fori_loop(0, block_r // STRIP_R, strip, 0)


@functools.partial(jax.jit, static_argnames=("block_r", "block_w", "interpret"))
def ctr_xor_words(x: jax.Array, tkey: jax.Array, *, block_r: int = BLOCK_R,
                  block_w: int = BLOCK_W, interpret: bool = False) -> jax.Array:
    """x: uint32[R, W] with R % block_r == 0 == W % block_w. tkey: uint32[2]."""
    R, W = x.shape
    assert R % block_r == 0 and W % block_w == 0, (R, W, block_r, block_w)
    assert block_r % STRIP_R == 0 and block_w % 2 == 0
    grid = (R // block_r, W // block_w)
    return pl.pallas_call(
        functools.partial(_ctr_kernel, block_r=block_r, block_w=block_w),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # key pair
            pl.BlockSpec((block_r, block_w), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_r, block_w), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, W), jnp.uint32),
        interpret=interpret,
    )(tkey.astype(jnp.uint32), x)
