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
import re

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


# -- the paged engine's pool layout -----------------------------------------
# The chat cell's gateway: 4 slots, pages of 16 tokens, 44 pages a sequence,
# 197 pages, prefill chunks of 256 tokens, granite-3-2b's 40 layers.  The
# page-table gather lowers to a loop over pages only at this depth.
SLOTS, PAGE, PAGES_PER_SEQ, N_PAGES, CHUNK = 4, 16, 44, 197, 256


@pytest.fixture(scope="module")
def engine_hlo(one_chip):
    """Compiled text of the trusted decode and chunk-prefill programs."""
    from repro.core import sealed
    from repro.core.policy import SecurityConfig
    from repro.models import registry
    from repro.serve.engine import PagedEngine
    from repro.serve.kv_pager import PagedKVPool
    from repro.serve.sessions import SessionManager

    sec = SecurityConfig()
    provider = SessionManager("tpu-0", config=sec).register("_provider") \
        .channel
    pool = PagedKVPool(n_pages=2, page_size=PAGE, n_layers=CFG.n_layers,
                       n_kv_heads=CFG.n_kv_heads, hd=CFG.hd,
                       dtype=CFG.act_dtype)
    engine = PagedEngine(cfg=CFG, params=None, channel=provider, pool=pool,
                         max_slots=SLOTS, max_pages=PAGES_PER_SEQ,
                         prefill_chunk=CHUNK)
    params = jax.eval_shape(lambda: sealed.seal_tree(
        registry.init_params(jax.random.PRNGKey(0), CFG), provider.jkey,
        sec.weights))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), params)
    pool_arrays = tuple(sds((N_PAGES,) + a.shape[1:], a.dtype)
                        for a in pool.arrays())
    B, P, i32 = SLOTS, PAGES_PER_SEQ, jnp.int32
    decode = engine._decode.lower(
        params, sds((B,), i32), sds((B,), i32), sds((B,), bool),
        sds((B, P), i32), sds((B,), i32), pool_arrays).compile()
    prefill = engine._chunk_prefill.lower(
        params, sds((B, CHUNK), i32), sds((B,), i32), sds((B,), i32),
        sds((B,), bool), sds((B, P), i32), pool_arrays).compile()
    stored = "u16[%d,%s]" % (N_PAGES, ",".join(map(str, pool.row_shape)))
    return {"decode": decode.as_text(), "chunk_prefill": prefill.as_text(),
            "stored": stored}


@pytest.mark.parametrize("program", ["decode", "chunk_prefill"])
def test_no_loop_carries_the_kv_pool(engine_hlo, program):
    text = engine_hlo[program]
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert loops, "expected the layer loop at least"
    carrying = [ln for ln in loops if f"u16[{N_PAGES}," in ln]
    assert not carrying, carrying[0][:300]


@pytest.mark.parametrize("program", ["decode", "chunk_prefill"])
def test_kv_pool_is_stored_page_major(engine_hlo, program):
    text = engine_hlo[program]
    entry = text[text.index("\nENTRY"):]
    stored = engine_hlo["stored"]
    # the k_ct and v_ct parameters, with the layouts the compiler gave them
    params = re.findall(
        r"= (u16\[%d,[0-9,]+\])\{([0-9,]+)[:}][^\n]*parameter\(" % N_PAGES,
        entry)
    assert len(params) == 2, params
    for shape, layout in params:
        assert shape == stored
        # minor-to-major: the page dimension (0) is the most major
        assert layout.split(",")[-1] == "0", (shape, layout)
