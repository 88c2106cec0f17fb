"""The benchmark's operation and byte counts, its table of peaks, and its
configuration files, from shapes alone (nothing is allocated)."""
import json
import pathlib
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from benchmarks.chip import costs, spec, weights  # noqa: E402

CONFIGS = sorted(p.stem for p in (spec.HERE / "configs").glob("*.json"))


def _config(name):
    return spec._read(spec.HERE / "configs" / f"{name}.json")


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
    pub, m = c["published"], c["model"]
    pairs = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_hidden_layers": "n_layers",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
             "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
             "tie_word_embeddings": "tie_embeddings"}
    for hf, ours in pairs.items():
        if hf in c["reduced"]:
            assert m[ours] == c["reduced"][hf]["here"] != pub[hf]
        else:
            assert m[ours] == pub[hf], hf
    assert (m["head_dim"] or m["d_model"] // m["n_heads"]) == \
        pub["hidden_size"] // pub["num_attention_heads"]
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
    m = {"d_model": 4, "d_ff": 8, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 2, "vocab": 10, "n_layers": 3, "tie_embeddings": True,
         "param_dtype": "bfloat16", "dtype": "bfloat16"}
    # q 4x4, k 4x2, v 4x2, o 4x4, mlp 3x4x8
    assert costs.layer_matmul_params(m) == 16 + 8 + 8 + 16 + 96
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
