"""The benchmark's output check fails a broken timed path and its control.

Each fault breaks the served path underneath an otherwise whole run of the
harness (on the CPU, at a tiny size, past the look for a chip), with the
sealing off and on:

  state_unchanged  a decode step that leaves the KV pool as it found it
  half_batch       half of the active lanes left out of the decode step:
                   they return the token they were given
  token_altered    one lane's token changed where the step produces it

The control puts the reference, computed in fp8, in the program's place:
at each served position the token it puts first must lie further below
the float32 reference's best than the sound bf16 program's served tokens.
"""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from benchmarks.chip import check, run, spec  # noqa: E402
from test_bench_harness import make_data  # noqa: E402


def _state_unchanged(orig):
    def step(self, *a):
        before = self.pool.arrays()
        out = orig(self, *a)
        self.pool.update_arrays(before)
        return out
    return step


def _half_batch(orig):
    def step(self, tokens, seq_lens, active, *a):
        tok, ok = orig(self, tokens, seq_lens, active, *a)
        tok = np.array(tok)
        lanes = np.flatnonzero(np.asarray(active))
        left_out = lanes[len(lanes) // 2:] if len(lanes) > 1 else lanes[:0]
        tok[left_out] = np.asarray(tokens)[left_out]
        return tok, ok
    return step


def _token_altered(orig):
    def step(self, tokens, seq_lens, active, *a):
        tok, ok = orig(self, tokens, seq_lens, active, *a)
        tok = np.array(tok)
        lane = int(np.flatnonzero(np.asarray(active))[0])
        tok[lane] = (tok[lane] + 1) % self.cfg.vocab
        return tok, ok
    return step


FAULTS = {"none": None, "state_unchanged": _state_unchanged,
          "half_batch": _half_batch, "token_altered": _token_altered}


CASES = [pytest.param(f, "off", id=f) for f in FAULTS] + [
    pytest.param(f, "trusted", id=f"trusted-{f}") for f in FAULTS if FAULTS[f]]


@pytest.mark.parametrize("fault,security", CASES)
def test_a_broken_timed_path_is_not_correct(fault, security, tmp_path,
                                            monkeypatch):
    """With the sealing on, the faults break the path the committed cells
    serve: weights unsealed in-graph, pages unsealed from the sealed pool
    and the new slot sealed back into it."""
    from repro.serve.engine import PagedEngine
    if FAULTS[fault] is not None:
        monkeypatch.setattr(PagedEngine, "decode_step",
                            FAULTS[fault](PagedEngine.decode_step))
    data = make_data(tmp_path, security=security)
    cell = spec.load_cell("tiny.burst", data, data / "BENCHMARK.json")
    res = run.judge(run.serve(cell, 2**32 + 9, 4.0, trace=False))
    assert res["correct"] is (fault == "none"), res["checks"]


def test_the_fp8_control_fails_where_bf16_passes(tmp_path):
    """At a small size in bf16 (4 layers of width 256, where the sound
    program's widest gap read 0.006 and the fp8 control's 0.10 on the CPU,
    the int8 one's 0.015-0.029): the program is under the limit, the
    control above it."""
    limit = 0.02
    data = make_data(tmp_path, dtype="bfloat16", security="off",
                     limit=limit,
                     model={"d_model": 256, "d_ff": 512, "n_heads": 8,
                            "n_kv_heads": 2, "head_dim": 32, "vocab": 1024,
                            "n_layers": 4})
    cell = spec.load_cell("tiny.burst", data, data / "BENCHMARK.json")
    served = run.serve(cell, 2**31 + 1234, 4.0, trace=False)
    res = run.judge(served)
    assert res["correct"], res["checks"]
    ctl = check.gaps(cell.config, served.cfg, served.seed, served.picked,
                     **check.shapes(cell.mix), quant="fp8")
    assert max(float(g.max()) for g in ctl) > limit


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**33 + 17])
def test_the_sample_holds_a_request_of_every_lane(seed):
    """A fault confined to one decode lane alters only the requests that
    decoded there: the sample takes one of them, however few there are,
    beside the longest request and the seed's draw."""
    import types
    recs = [types.SimpleNamespace(
        req=types.SimpleNamespace(index=i), status="done",
        tokens=[0] * (100 + i), lanes={i % 3}) for i in range(30)]
    recs[7].lanes = {1, 3}          # the one request that held lane 3
    picked = check.sample(recs, seed)
    assert recs[29] in picked and recs[7] in picked
    assert set().union(*(r.lanes for r in picked)) == {0, 1, 2, 3}
    assert len(picked) <= check.SAMPLE_MAX
    assert sum(len(r.tokens) for r in picked) >= check.SAMPLE_TOKENS
