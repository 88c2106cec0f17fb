"""The benchmark harness end to end on the CPU, at a tiny size: a cell, a
mix, a configuration and a metric that no code names are found from their
files; the command refuses to run without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from benchmarks.chip import run, spec  # noqa: E402

TINY = {"arch_id": "tiny", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab": 256,
        "q_block": 16}


def make_data(tmp: pathlib.Path, *, dtype="float32", security="trusted",
              limit=1e-3, model=None) -> pathlib.Path:
    """A data directory with one new configuration, mix, cell and metric,
    and the benchmark's own readers and peaks beside them."""
    for sub in ("configs", "traffic", "cells"):
        (tmp / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(spec.HERE / "metrics", tmp / "metrics",
                    dirs_exist_ok=True)
    shutil.copy(spec.HERE / "peaks.json", tmp / "peaks.json")
    base = spec._read(spec.HERE / "configs" / "granite-3-2b.json")
    model = {**base["model"], **TINY, "dtype": dtype, "param_dtype": dtype,
             **(model or {})}
    (tmp / "configs" / "tiny.json").write_text(
        json.dumps(dict(base, name="tiny", model=model)))
    (tmp / "traffic" / "burst.json").write_text(json.dumps({
        "why": "a test mix", "loop": "open", "tenants": 3, "block": 8,
        "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.8,
                   "min": 4, "max": 40},
        "output": {"dist": "uniform", "min": 3, "max": 10}}))
    (tmp / "cells" / "tiny.burst.json").write_text(json.dumps({
        "config": "tiny", "traffic": "burst", "security": security,
        "why": "a test cell",
        "gateway": {"slots": 4, "page_size": 8, "n_pages": 64,
                    "max_pages_per_seq": 8, "prefill_chunk": 16},
        "rate_per_s": 40.0, "warm_s": 3.0, "trace_s": 1.0,
        "limits": {"max_logit_gap": limit, "checked_tokens": 10}}))
    (tmp / "metrics" / "done_per_s.py").write_text(
        '"""Requests finished in the window, per second."""\n\n\n'
        "def read(run):\n"
        "    w0, w1 = run.window\n"
        "    n = sum(1 for r in run.due if r.t_done is not None\n"
        "            and w0 <= r.t_done <= w1)\n"
        "    return n / (w1 - w0)\n")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "tiny.burst", "config": "tiny",
                           "traffic": "burst", "chips": 1, "why": "test"}]
    bench["end_to_end"] = [
        m for m in bench["end_to_end"] if m["name"] == "setup_s"] + [
        {"name": "ttft_p90_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": ["tiny.burst"]},
        {"name": "done_per_s", "unit": "1/s", "better": "higher",
         "bound": 0.25, "source": "host_clock", "workloads": ["tiny.burst"]}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run_tiny(data: pathlib.Path, seed=2**31 + 77, seconds=4.0):
    cell = spec.load_cell("tiny.burst", data, data / "BENCHMARK.json")
    return run.judge(run.serve(cell, seed, seconds, trace=False))


def test_new_cell_mix_config_and_metric_need_no_code(tmp_path):
    """The trusted path at a tiny size: files alone define the cell, and
    its served tokens agree with the reference."""
    res = run_tiny(make_data(tmp_path))
    assert set(res["metrics"]) == {"setup_s", "ttft_p90_ms", "done_per_s"}
    assert res["metrics"]["done_per_s"]["value"] > 0
    assert res["correct"], res["checks"]
    assert res["attempted"] > 10 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["checked_tokens"]["value"] >= 10


def _cli(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_with_no_result_line():
    p = _cli(["--workload", "granite-3-2b.chat.trusted", "--seed",
              str(2**31 + 3), "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode == 2
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr


def test_unknown_workload_exits_nonzero(tmp_path):
    p = _cli(["--workload", "no-such-cell", "--seed", "1", "--seconds",
              "1"], ROOT)
    assert p.returncode == 2 and not p.stdout.strip()


def test_benchmark_alone_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files (no
    program) runs nothing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(["--workload", "granite-3-2b.chat.trusted", "--seed", "5",
              "--seconds", "1"], tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


@pytest.mark.parametrize("name", [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_committed_cells_load(name):
    cell = spec.load_cell(name)
    assert cell.per_layer and cell.end_to_end
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for m in cell.per_layer + cell.end_to_end:
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] in names if "moves" in m else True
