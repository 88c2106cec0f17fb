"""The benchmark's operation and byte counts, its table of peaks, and its
configuration files, from shapes alone (nothing is allocated but one small
weight tree)."""
import hashlib
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from benchmarks.chip import costs, spec, weights  # noqa: E402

CONFIGS = sorted(p.stem for p in (spec.HERE / "configs").glob("*.json"))

# model field (a dotted path into a nested group) -> published key; a
# configuration's ``published_keys`` overrides a pair or adds one
PUBLISHED = {"d_model": "hidden_size", "d_ff": "intermediate_size",
             "n_layers": "num_hidden_layers",
             "n_heads": "num_attention_heads",
             "n_kv_heads": "num_key_value_heads", "vocab": "vocab_size",
             "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
             "tie_embeddings": "tie_word_embeddings"}


def _config(name):
    return spec._read(spec.HERE / "configs" / f"{name}.json")


def _field(model: dict, path: str):
    for key in path.split("."):
        model = model[key]
    return model


def check_published_widths(c: dict) -> None:
    """Every size the program runs is the published one, except the keys
    the file lists as reduced; the head size is the published
    ``head_dim`` where the published config gives one."""
    pub, m = c["published"], c["model"]
    for ours, hf in {**PUBLISHED, **c.get("published_keys", {})}.items():
        if hf in c["reduced"]:
            assert _field(m, ours) == c["reduced"][hf]["here"] != pub[hf], hf
        else:
            assert _field(m, ours) == pub[hf], hf
    head = (pub.get("head_dim")
            or pub["hidden_size"] // pub["num_attention_heads"])
    assert (m["head_dim"] or m["d_model"] // m["n_heads"]) == head


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_equals_the_program_init(name):
    from repro.models import registry
    cfg = spec.model_config(_config(name))
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg)))
    assert costs.param_count(_config(name)["model"]) == \
        sum(x.size for x in leaves)


@pytest.mark.parametrize("name", CONFIGS)
def test_benchmark_weights_have_the_program_layout(name):
    """The weights the benchmark draws are the tree the program's own init
    makes: the same paths, shapes and dtypes."""
    from repro.models import registry
    cfg = spec.model_config(_config(name))
    ours = jax.eval_shape(lambda: weights.generate(cfg, 2**33 + 5))
    theirs = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(theirs)
    assert [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(ours)] \
        == [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(theirs)]


@pytest.mark.parametrize("name", CONFIGS)
def test_config_keeps_the_published_widths(name):
    """Every size the program runs is the published one, except the keys
    the file lists as reduced, and BENCHMARK.json lists the same keys."""
    c = _config(name)
    check_published_widths(c)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(x for x in bench["configs"] if x["name"] == name)
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    assert entry["source"] == c["source"]


def test_a_call_at_the_roofline_reads_one():
    m = _config("granite-3-2b")["model"]
    peak = spec.peaks("TPU v5 lite")
    ctx = [100, 700, 33]
    flops = costs.decode_flops(m, ctx)
    nbytes = costs.decode_min_bytes(m, ctx)
    t = costs.roofline_s(flops, nbytes, peak)
    assert t == pytest.approx(nbytes / peak["hbm_bytes_per_s"])  # HBM binds
    assert costs.roofline_s(flops, nbytes, peak) / t == 1.0
    big = costs.prefill_flops(m, [(0, 4096, True)] * 8)
    assert costs.roofline_s(big, nbytes, peak) == pytest.approx(
        big / peak["bf16_flops_per_s"])                          # FLOPs bind


def test_counts_by_hand():
    m = {"family": "dense", "d_model": 4, "d_ff": 8, "n_heads": 2,
         "n_kv_heads": 1, "head_dim": 2, "vocab": 10, "n_layers": 3,
         "tie_embeddings": True, "param_dtype": "bfloat16",
         "dtype": "bfloat16"}
    # q 4x4, k 4x2, v 4x2, o 4x4, mlp 3x4x8
    assert spec.family("dense").layer_matmul_params(m) == \
        16 + 8 + 8 + 16 + 96
    assert costs.param_count(m) == 3 * (144 + 8) + 40 + 4
    assert costs.kv_bytes_per_token(m) == 2 * 3 * 1 * 2 * 2
    # one token at context 5: 2 x 3 x 144 matmul, 4 x 3 x 2 x 2 x 5 scores
    # and sums, 2 x 4 x 10 logits
    assert costs.decode_flops(m, [5]) == 864 + 240 + 80
    # two prompt tokens from 3: contexts 4 and 5
    assert costs.prefill_flops(m, [(3, 2, False)]) == 2 * 864 + 48 * 9


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_granite_counts_are_the_dense_layers():
    """The counts of granite-3-2b.json, as costs.py gave them when it held
    the dense layer's arithmetic itself."""
    m = _config("granite-3-2b")["model"]
    ctx = [1, 100, 700, 4096]
    assert costs.param_count(m) == 2533531648
    assert costs.weight_bytes(m) == 5067063296
    assert costs.kv_bytes_per_token(m) == 81920
    assert costs.decode_flops(m, ctx) == 21871575040
    assert costs.decode_min_bytes(m, ctx) == 5468225536
    assert costs.prefill_flops(m, [(0, 256, False), (256, 100, True),
                                   (3, 1, True)]) == 1758171914240


# sha256 (first 16 hex digits) of each leaf's bytes: the tree weights.py
# drew for granite-3-2b cut to 2 layers at seed 2**33 + 5 when it held the
# dense leaf list itself
GRANITE_2_LAYERS = {
    "embed": "ae1db276c70403a3", "final_norm": "045ad01d502fc9de",
    "layers/attn/wk": "50e1ad37a00a460d", "layers/attn/wo": "d695e37064fc6a11",
    "layers/attn/wq": "9e1a4723e0ae69ec", "layers/attn/wv": "1f5484845dcb4d53",
    "layers/ln1": "8676bed4ca36c6e3", "layers/ln2": "8676bed4ca36c6e3",
    "layers/mlp/w_down": "48f2470d8dd44af1",
    "layers/mlp/w_gate": "d491e37427dbb95f",
    "layers/mlp/w_up": "61058d8b86c3ef04"}


def test_granite_tree_is_drawn_bit_for_bit_as_before():
    """At its published widths, cut to 2 layers: the same leaves, in
    bfloat16, with the same bytes."""
    c = _config("granite-3-2b")
    cfg = spec.model_config(dict(c, model=dict(c["model"], n_layers=2)))
    flat = jax.tree_util.tree_flatten_with_path(
        weights.generate(cfg, 2**33 + 5))[0]
    got = {}
    for path, leaf in flat:
        a = np.asarray(leaf)
        assert a.dtype == jax.numpy.bfloat16
        got["/".join(k.key for k in path)] = hashlib.sha256(
            a.view(np.uint16).tobytes()).hexdigest()[:16]
    assert got == GRANITE_2_LAYERS


def test_an_unknown_family_raises():
    """No family module: an error, never the dense tree or counts."""
    m = dict(_config("granite-3-2b")["model"], family="no-such-family")
    with pytest.raises(KeyError, match="no-such-family"):
        costs.param_count(m)
    cfg = spec.model_config(dict(_config("granite-3-2b"), model=m))
    with pytest.raises(KeyError, match="no-such-family"):
        jax.eval_shape(lambda: weights.generate(cfg, 1))
