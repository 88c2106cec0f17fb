"""trace.py and the trace readers against a trace whose numbers are known
by hand: benchmarks/chip/testdata/synthetic.xplane.pb, written by
make_synthetic.py beside it (its docstring lists every span).  Times in ms
from the start of the 200 ms ``bench.traced`` window."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import spec, trace  # noqa: E402

MS = 1e6


@pytest.fixture(scope="module")
def syn():
    return trace.reduce(spec.HERE / "testdata" / "synthetic.xplane.pb")


def test_window_busy_and_programs(syn):
    assert syn.window_s == pytest.approx(0.200)
    # ops: 3 x (10 + 9) ms of decode, 2 x 14 ms of prefill
    assert syn.busy_ns == pytest.approx(85 * MS)
    assert syn.program_ns("_decode_impl") == (3, pytest.approx(60 * MS))
    assert syn.program_ns("_chunk_prefill_impl") == (2, pytest.approx(28 * MS))
    assert syn.n_devices == 1


def test_idle_gaps_by_what_the_host_was_doing(syn):
    idle = dict(syn.idle_by_label())
    # [105,162] in wait_arrival; [25,45] in submit; [65,85] and [176,182]
    # between spans; [0,5] [15,16] [55,56] [95,96] [196,200] inside steps
    assert idle == pytest.approx({"wait_arrival": 0.057, "none": 0.026,
                                  "submit": 0.020, "gateway.step": 0.012})
    assert sum(b - a for a, b, _ in syn.gaps) + syn.busy_ns == \
        pytest.approx(200 * MS)


def test_top_ops_name_their_program(syn):
    assert syn.top_ops() == [
        ["jit__decode_impl/fusion.1", pytest.approx(0.030)],
        ["jit__chunk_prefill_impl/fusion.3", pytest.approx(0.028)],
        ["jit__decode_impl/fusion.2", pytest.approx(0.027)]]


def test_readers(syn):
    run = types.SimpleNamespace(trace=syn, traced_steps=[])
    read = spec.metric_reader
    assert read("decode_step_ms.chat")(run) == pytest.approx(20.0)
    assert read("prefill_chunk_ms.long")(run) == pytest.approx(14.0)
    assert read("device_idle_share.chat")(run) == pytest.approx(57.5)
    # steps of 30, 30, 30, 18, 18 ms hold 19, 19, 19, 14, 14 ms of ops
    assert read("host_ms_per_step.chat")(run) == pytest.approx(8.2)
    # nothing to read: no value, never 0
    assert read("decode_roofline.chat")(run) is None
    assert read("engine_mfu.chat")(run) is None
    untraced = types.SimpleNamespace(trace=None, traced_steps=[])
    for name in ("decode_step_ms.chat", "prefill_chunk_ms.chat",
                 "device_idle_share.long", "host_ms_per_step.chat"):
        assert read(name)(untraced) is None


def test_roofline_and_mfu_from_the_steps_traced(syn):
    """Three decode calls and two prefill calls of known work, timed by the
    synthetic trace's 60 ms of decode and 28 ms of prefill."""
    from benchmarks.chip import costs
    model = spec._read(spec.HERE / "configs" / "granite-3-2b.json")["model"]
    peak = spec.peaks("TPU v5 lite")
    steps = [types.SimpleNamespace(decode=[300, 410], prefill=[])] * 3 + \
        [types.SimpleNamespace(decode=[], prefill=[(0, 128, False)])] * 2
    run = types.SimpleNamespace(trace=syn, traced_steps=steps, model=model,
                                peak=peak)
    least = 3 * costs.roofline_s(costs.decode_flops(model, [300, 410]),
                                 costs.decode_min_bytes(model, [300, 410]),
                                 peak)
    got = spec.metric_reader("decode_roofline.chat")(run)
    assert got == pytest.approx(100 * least / 0.060)
    flops = 3 * costs.decode_flops(model, [300, 410]) + \
        2 * costs.prefill_flops(model, [(0, 128, False)])
    got = spec.metric_reader("engine_mfu.long")(run)
    assert got == pytest.approx(100 * flops / (0.088 * 197e12))


@pytest.fixture(scope="module")
def chip():
    return trace.reduce(spec.HERE / "testdata" / "small.xplane.pb")


def test_a_trace_recorded_on_a_v5e(chip):
    """benchmarks/chip/testdata/small.xplane.pb, written by record_trace.py
    on one TPU v5e: three gateway.step spans of one _decode_impl each, a
    50 ms wait_arrival, two steps of one _chunk_prefill_impl each.  By
    hand from its events (ns): the device started the programs 1247607,
    1409467, 1396531 (decode) and 1242488, 1367019 (prefill) ns before the
    host's PjitFunction spans that dispatched them, so every device event
    moves by 1409467 ns and all five fall inside the bench.traced span
    [48635576, 103698051]."""
    assert chip.window_s == pytest.approx(0.055062475)
    assert chip.program_ns("_decode_impl") == (
        3, pytest.approx((91037 + 91091 + 91031) * 1.0, abs=8))
    assert chip.program_ns("_chunk_prefill_impl") == (
        2, pytest.approx((180899 + 180972) * 1.0, abs=8))
    # the op union leaves a few ns between each program's copy and fusion
    assert 634900 < chip.busy_ns <= 635030 + 8
    idle = dict(chip.idle_by_label())
    assert set(idle) == {"wait_arrival", "gateway.step"}
    assert sum(idle.values()) * 1e9 + chip.busy_ns == \
        pytest.approx(55062475, abs=8)
    # the host slept 50.656 ms in wait_arrival; the device was idle in all
    # of it, and in the steps' host work around each program
    assert idle["wait_arrival"] == pytest.approx(0.0514449, abs=2e-6)


def test_clock_shift_pairs_each_program_with_its_dispatch():
    mods = [("a", 10.0), ("b", 20.0), ("a", 30.0)]
    assert trace.clock_shift(mods, [("a", 12.0), ("b", 21.0),
                                    ("a", 35.0)]) == 5.0
    # device after its dispatch: no shift; counts that disagree: ignored
    assert trace.clock_shift(mods, [("a", 8.0), ("a", 29.0)]) == 0.0
    assert trace.clock_shift(mods, [("a", 50.0)]) == 0.0
