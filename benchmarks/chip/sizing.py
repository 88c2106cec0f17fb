"""Sizes a cell's gateway from a compile for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/sizing.py <cell> [slots:chunk]...

Nothing runs: the cell's trusted decode, chunk-prefill and page-close
programs are compiled for one chip of a described v5e:2x2 topology at the
cell's sizes, once for each number of slots and prefill chunk given (the
cell's own when none is), with the pool that ``pool_pages`` gives, and
``memory_analysis`` gives their argument, output and temporary bytes.  The gateway keeps the sealed weights and the pool across
calls, and a call's pool output is live beside its input, so a call needs
about argument + output + temporary bytes; the cell takes the most slots
whose largest call leaves room on the chip's 16 GiB.
"""
from __future__ import annotations

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
GiB = 1 << 30


def _sds(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def pool_pages(slots: int, max_pages_per_seq: int) -> int:
    """Pages for every slot at its longest request, at 90% of the usable
    pool (page 0 is scratch), so the monitor's 95% occupancy watermark
    never spills."""
    return -(-slots * max_pages_per_seq * 10 // 9) + 1


def compile_cell(cell, slots: int, chunk: int, one_chip) -> dict:
    """{program: memory_analysis} for the cell at ``slots`` slots and
    prefill chunks of ``chunk`` tokens."""
    import jax
    import jax.numpy as jnp
    from repro.core import sealed
    from repro.core.policy import SecurityConfig
    from repro.obs import MetricsRegistry
    from repro.serve.engine import PagedEngine
    from repro.serve.kv_pager import PagedKVPool
    from repro.serve.sessions import SessionManager

    from benchmarks.chip import spec, weights

    cfg = spec.model_config(cell.config)
    g = cell.gateway
    sec = SecurityConfig() if cell.security == "trusted" else \
        SecurityConfig.off()
    provider = SessionManager("tpu-0", config=sec).register("_provider") \
        .channel
    pool = PagedKVPool(n_pages=2, page_size=g["page_size"],
                       n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                       hd=cfg.hd, dtype=cfg.act_dtype, sealed=sec.enabled,
                       metrics=MetricsRegistry())
    engine = PagedEngine(cfg=cfg, params=None, channel=provider, pool=pool,
                         max_slots=slots, max_pages=g["max_pages_per_seq"],
                         prefill_chunk=chunk)
    plain = jax.eval_shape(lambda: weights.generate(cfg, 0))
    params = (jax.eval_shape(lambda p: sealed.seal_tree(
        p, provider.jkey, sec.weights), plain) if sec.enabled else plain)
    params = _sds(params, one_chip)
    pool_arrays = tuple(
        jax.ShapeDtypeStruct((pool_pages(slots, g["max_pages_per_seq"]),)
                             + a.shape[1:], a.dtype,
                             sharding=one_chip) for a in pool.arrays())
    B, P, C = slots, g["max_pages_per_seq"], engine.prefill_chunk

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    i32 = jnp.int32
    out = {}
    out["decode"] = engine._decode.lower(
        params, arr((B,), i32), arr((B,), i32), arr((B,), bool),
        arr((B, P), i32), arr((B,), i32), pool_arrays).compile()
    out["chunk_prefill"] = engine._chunk_prefill.lower(
        params, arr((B, C), i32), arr((B,), i32), arr((B,), i32),
        arr((B,), bool), arr((B, P), i32), pool_arrays).compile()
    out["close_page"] = engine._close.lower(pool_arrays, arr((), i32)) \
        .compile()
    return {k: v.memory_analysis() for k, v in out.items()}


def main() -> None:
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.chip import spec
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(sys.argv[1])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    g = cell.gateway
    sizes = [tuple(int(x) for x in a.split(":")) for a in sys.argv[2:]]
    for slots, chunk in sizes or [(g["slots"], g["prefill_chunk"])]:
        n_pages = pool_pages(slots, g["max_pages_per_seq"])
        for name, m in compile_cell(cell, slots, chunk, one_chip).items():
            a, o, t = (m.argument_size_in_bytes, m.output_size_in_bytes,
                       m.temp_size_in_bytes)
            print(f"{cell.name} slots {slots} chunk {chunk} pages {n_pages} "
                  f"{name}: arguments "
                  f"{a / GiB:.3f} GiB, output {o / GiB:.3f} GiB, temporaries "
                  f"{t / GiB:.3f} GiB, sum {(a + o + t) / GiB:.3f} GiB",
                  flush=True)


if __name__ == "__main__":
    main()
