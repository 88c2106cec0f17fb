"""The Pallas kernels compile for a described TPU v5e at granite-3-2b widths.

Nothing runs: the TPU compiler, which is installed beside the CPU backend,
compiles each kernel for a chip that is described and not attached.  That
catches what interpret mode cannot — block shapes the chip's tiling
refuses, and kernels that need more VMEM than the default scoped limit.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time, and the
test workers import every test file.  All such tests stay in this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.ctr_cipher.kernel import ctr_xor_words
from repro.kernels.sealed_attention.kernel import sealed_decode_attention
from repro.kernels.sealed_matmul.kernel import sealed_matmul
from repro.kernels.tree_mac.kernel import mac_tags_words

CFG = configs.get_config("granite-3-2b")
u32, u16, bf16 = jnp.uint32, jnp.uint16, jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# a [d_model, d_ff] bf16 weight as its uint32 word lattice
WORDS = ((CFG.d_model, CFG.d_ff // 2), u32)


def test_ctr_cipher_compiles_at_weight_width(one_chip):
    _compile(ctr_xor_words, one_chip, WORDS, ((2,), u32))


@pytest.mark.parametrize("chunk_words", [128, 4096])
def test_tree_mac_compiles_at_weight_width(one_chip, chunk_words):
    _compile(lambda x, k: mac_tags_words(x, k, chunk_words=chunk_words),
             one_chip, WORDS, ((chunk_words,), u32))


def test_sealed_matmul_compiles_for_the_up_projection(one_chip):
    D, F, M, bk = CFG.d_model, CFG.d_ff, 256, 256
    _compile(sealed_matmul, one_chip,
             ((M, D), u16), ((D, F), u16), ((M, D // bk), u32),
             ((D, F // bk), u32), ((2,), u32), ((2,), u32),
             ((bk // 2,), u32))


def test_sealed_attention_compiles_for_decode(one_chip):
    B, T, K, hd = 4, 2048, CFG.n_kv_heads, CFG.hd
    G = CFG.n_heads // K
    _compile(sealed_decode_attention, one_chip,
             ((B, K, G, hd), bf16), ((B, T, K, hd), u16),
             ((B, T, K, hd), u16), ((B, T, K, 1), u32), ((B, T, K, 1), u32),
             ((2,), u32), ((2,), u32), ((hd // 2,), u32), ((), jnp.int32))
