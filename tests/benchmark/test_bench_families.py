"""A configuration of another family enters the benchmark by files alone.

The repo's ``moe`` family has no module under benchmarks/chip/families/;
the tests register moe_family.py and moe_reference.py, kept beside this
file, under the names the harness looks for, and read moe_smoke.json (the
smoke preset of moonshot-v1-16b-a3b, heads of 64).  Then the harness's own
code, unedited, draws the program's tree, counts it, checks the file's
published widths and runs the correctness check against the reference.
The serving engine does not take the family yet, so no window is served.
"""
import json
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import moe_family  # noqa: E402
import moe_reference  # noqa: E402
from benchmarks.chip import check, costs, spec, weights  # noqa: E402
from test_bench_costs import check_published_widths  # noqa: E402

CONFIG = json.loads((HERE / "moe_smoke.json").read_text())
SEED = 2**33 + 17


@pytest.fixture(autouse=True)
def moe_registered(monkeypatch):
    monkeypatch.setitem(sys.modules, "benchmarks.chip.families.moe",
                        moe_family)
    monkeypatch.setitem(sys.modules,
                        "benchmarks.chip.references.moe_reference",
                        moe_reference)


def _init_tree(cfg):
    from repro.models import registry
    return jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))


def test_the_tree_drawn_is_the_program_init():
    cfg = spec.model_config(CONFIG)
    assert cfg.family == "moe" and cfg.moe.shared_expert
    ours = jax.eval_shape(lambda: weights.generate(cfg, SEED))
    theirs = _init_tree(cfg)
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(theirs)
    assert [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(ours)] \
        == [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(theirs)]
    # the router is float32 whatever the weights are served in
    bf16 = spec.model_config(dict(CONFIG, model=dict(
        CONFIG["model"], param_dtype="bfloat16")))
    ours = jax.eval_shape(lambda: weights.generate(bf16, SEED))
    assert [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(ours)] \
        == [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(
            _init_tree(bf16))]


def test_the_count_is_the_tree_size():
    for dtype in ("float32", "bfloat16"):
        m = dict(CONFIG["model"], param_dtype=dtype)
        leaves = jax.tree_util.tree_leaves(
            _init_tree(spec.model_config(dict(CONFIG, model=m))))
        assert costs.param_count(m) == sum(x.size for x in leaves)
        assert costs.weight_bytes(m) == sum(
            x.size * x.dtype.itemsize for x in leaves)


def test_the_width_check_takes_the_files_keys_and_head_dim():
    m = CONFIG["model"]
    assert m["head_dim"] != m["d_model"] // m["n_heads"]
    check_published_widths(CONFIG)
    # without its own keys the expert's width is held to the dense width
    plain = {k: v for k, v in CONFIG.items() if k != "published_keys"}
    with pytest.raises(AssertionError, match="intermediate_size"):
        check_published_widths(plain)
    # a published head size the model does not keep fails
    wrong = dict(CONFIG, published=dict(CONFIG["published"], head_dim=32))
    with pytest.raises(AssertionError):
        check_published_widths(wrong)


def _serve(cfg, params, prompt, n_new):
    """Greedy tokens of the program's own moe prefill and cached decode."""
    from repro.models import moe
    max_len = len(prompt) + n_new
    logits, cache = moe.prefill(params, cfg,
                                {"tokens": jnp.asarray(prompt)[None]},
                                max_len)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n_new - 1):
        logits, cache = moe.decode_step(params, cfg, cache,
                                        jnp.asarray(out[-1:], jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_the_check_runs_against_a_reference_of_the_family():
    """The program's greedy tokens lie within float32 rounding of the
    reference's best; the fp8 control's do not.  Prompts of at most
    n_experts tokens, so the program's capacity bound drops no token."""
    cfg = spec.model_config(CONFIG)
    params = weights.generate(cfg, SEED)
    rng = np.random.default_rng(5)
    picked = []
    for plen, n_new in ((8, 12), (5, 9), (3, 6)):
        prompt = rng.integers(0, cfg.vocab, plen, dtype=np.int32)
        picked.append(types.SimpleNamespace(
            req=types.SimpleNamespace(prompt=prompt),
            tokens=_serve(cfg, params, prompt, n_new)))
    del params
    shapes = {"max_len": 20, "max_new": 12}
    gaps = check.gaps(CONFIG, cfg, SEED, picked, **shapes)
    assert [len(g) for g in gaps] == [12, 9, 6]
    worst = max(float(g.max()) for g in gaps)
    assert worst < 1e-3
    ctl = check.gaps(CONFIG, cfg, SEED, picked, **shapes, quant="fp8")
    assert max(float(g.max()) for g in ctl) > 10 * max(worst, 1e-4)


def test_a_family_may_give_a_rule_of_its_own(monkeypatch):
    """Leaves come in sorted order, leaf i from fold_in(key, i); a rule is
    one of weights.RULES by name or the family's own function, and a leaf
    may name its dtype."""
    def decay(key, shape, dtype):
        return -jnp.exp(jax.random.uniform(key, shape)).astype(dtype)

    fam = types.ModuleType("decay_family")
    fam.leaves = lambda cfg: {"z/bias": ((3,), "zeros"),
                              "a/decay": ((2, 4), decay, "float32"),
                              "m/w": ((4, 5), "fan_in")}
    monkeypatch.setitem(sys.modules, "benchmarks.chip.families.decay", fam)
    cfg = spec.model_config(dict(CONFIG, model=dict(
        CONFIG["model"], family="decay", param_dtype="bfloat16")))
    tree = weights.generate(cfg, SEED)
    key = weights.seed_key(SEED)
    assert tree["a"]["decay"].dtype == jnp.float32
    np.testing.assert_array_equal(
        tree["a"]["decay"], decay(jax.random.fold_in(key, 0), (2, 4),
                                  jnp.float32))
    assert tree["m"]["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        tree["m"]["w"], weights.RULES["fan_in"](jax.random.fold_in(key, 1),
                                                (4, 5), jnp.bfloat16))
    assert tree["z"]["bias"].dtype == jnp.bfloat16
    assert not np.asarray(tree["z"]["bias"], np.float32).any()
