"""Expert-parallel MoE dispatch (manual shard_map) == global dispatch."""
import os
import subprocess
import sys
import textwrap

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def test_ep_dispatch_matches_global_loss_and_grads():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro import configs
    from repro.models import registry
    from repro.parallel import sharding as shd
    from repro.models.config import MoEConfig

    # ample capacity => EP and global dispatch drop the same (zero) tokens
    cfg = configs.get_config("moonshot-v1-16b-a3b", smoke=True)
    cfg = cfg.with_(moe=MoEConfig(n_experts=8, top_k=2, capacity_factor=8.0,
                                  shared_expert=True, d_ff_shared=128))
    m = registry.get_model(cfg)
    params = m.init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": tok}
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with shd.use(shd.make_ctx(mesh)):
        l0, g0 = jax.jit(jax.value_and_grad(
            lambda p: m.loss(p, cfg, batch)))(params)
        l1, g1 = jax.jit(jax.value_and_grad(
            lambda p: m.loss(p, cfg.with_(moe_ep=True), batch)))(params)
    assert abs(float(l0) - float(l1)) < 1e-4, (float(l0), float(l1))
    errs = [float(jnp.abs(a - b).max()) for a, b in
            zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1))]
    assert max(errs) < 1e-3, max(errs)
    print("OK", float(l0), max(errs))
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
