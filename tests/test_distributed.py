"""Multi-device tests (8 fake host devices, spawned in subprocesses because
XLA's device count is locked at first jax init)."""
import os
import subprocess
import sys
import textwrap

import pytest

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(code: str):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sealed_crosspod_allreduce_matches_plain():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.parallel import collectives
    from repro.launch.mesh import make_smoke_mesh
    mesh = make_smoke_mesh(8, pods=2)
    key = jnp.array([5, 9], jnp.uint32)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32), jnp.float32)
    for quant, tol in ((False, 1e-6), (True, 0.02)):
        f = jax.jit(jax.shard_map(
            lambda xl: collectives.sealed_allreduce_pod(
                xl, key, jnp.uint32(7), 2, mean=True, quantize=quant),
            mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
            axis_names={"pod"}, check_vma=False))
        out = np.asarray(f(x))
        want = np.stack([np.asarray(x[:8]), np.asarray(x[8:])]).mean(0)
        ref = np.concatenate([want, want], 0)
        assert np.abs(out - ref).max() < tol, (quant, np.abs(out-ref).max())
    print("OK")
    """)


def test_sharded_sealed_train_step_runs():
    """Numerically EXECUTE one sealed train step on a 4x2 mesh and compare
    the loss against the single-device run (same seed/batch)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.models import registry
    from repro.optim import AdamW
    from repro.core import SecurityConfig
    from repro.launch import steps
    from repro.launch.mesh import make_smoke_mesh
    from repro.parallel import sharding as shd
    from repro.data import SyntheticLM

    cell = steps.make_cell("granite-3-2b", "train_4k", smoke=True)
    mesh = make_smoke_mesh(8)
    data = SyntheticLM(vocab=cell.cfg.vocab, seq_len=16, batch=8, seed=0)
    mb = {k: jnp.asarray(v) for k, v in data.microbatches_at(0, 2).items()}

    params = cell.model.init(jax.random.PRNGKey(0), cell.cfg)
    from repro.train import trainer as T
    state = T.seal_state(cell.opt.init(params), cell.key, cell.sec)
    fn = steps.make_train_step_fn(cell)

    # single device
    s1, m1 = jax.jit(fn)(state, mb)

    # 8 devices
    sh = steps.train_state_shardings(cell, mesh, jax.eval_shape(lambda: state))
    bsh = steps.batch_shardings(cell, mesh,
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in mb.items()},
        stacked=True)
    with shd.use(shd.make_ctx(mesh)):
        s8, m8 = jax.jit(fn, in_shardings=(sh, bsh),
                         out_shardings=(sh, None))(state, mb)
    print("losses:", float(m1["loss"]), float(m8["loss"]))
    assert abs(float(m1["loss"]) - float(m8["loss"])) < 1e-3
    assert bool(m8["seal_ok"])
    print("OK")
    """)


def test_elastic_restore_onto_mesh():
    _run("""
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_smoke_mesh
    from repro.train import checkpoint
    from repro.train.fault import elastic_restore
    mesh = make_smoke_mesh(8)
    state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
             "b": jnp.ones((8,), jnp.float32)}
    specs = {"w": ("data", "model"), "b": (None,)}
    with tempfile.TemporaryDirectory() as d:
        p = checkpoint.save(d, 5, state, b"k"*32)
        restored, step = elastic_restore(p, state, b"k"*32, mesh, specs)
        assert step == 5
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(state["w"]))
        assert len(restored["w"].sharding.device_set) == 8
    print("OK")
    """)
