"""scopes.py and the readers of the engine's named scopes, the program's
host spans and its queue-wait counter."""
import pathlib
import shutil
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import scopes, spec, trace  # noqa: E402

SCOPE_METRICS = ("weights_unseal_ms.chat", "kv_gather_ms.chat",
                 "kv_unseal_ms.chat", "kv_seal_ms.chat", "model_ms.chat")
TRACE_METRICS = SCOPE_METRICS + ("handshake_idle_share.chat",)


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(_decode_impl)/kv_gather/gather:", "kv_gather"),
    ("jit(_decode_impl)/model/while/body/dot_general:", "model"),
    ("jit(_decode_impl)/weights_unseal/jit(_seal)/xor:", "weights_unseal"),
    ("jit(_decode_impl)/dot_general:", None),
    ("jit(_decode_impl)/modelx/add:", None),
    ("", None),
    (None, None),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def _in_trace_dir(monkeypatch, tmp_path, name):
    """Lays testdata/<name> out as run.py's trace directory does."""
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(spec.HERE / "testdata" / name, where / name)
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    return trace.reduce(where / name)


def test_readers_of_an_untraced_run_give_nothing(monkeypatch, tmp_path):
    """No trace, no value, even with a trace file left on disk by an
    earlier run; the queue-wait counter is read from the gateway's metrics
    and is absent where the program does not count it."""
    _in_trace_dir(monkeypatch, tmp_path, "scoped.xplane.pb")
    run = types.SimpleNamespace(trace=None, counters={})
    for name in TRACE_METRICS:
        assert spec.metric_reader(name)(run) is None, name
    assert spec.metric_reader("queue_wait_p90_ms.chat")(run) is None
    run.counters["queue_wait_p90_ms"] = 1234.5
    assert spec.metric_reader("queue_wait_p90_ms.chat")(run) == 1234.5


def test_a_program_without_scopes_or_spans_gives_nothing(monkeypatch,
                                                         tmp_path):
    """small.xplane.pb was recorded from programs with no named scope and
    no program span, as the parent of the scoped engine runs: every reader
    finds nothing, never 0."""
    red = _in_trace_dir(monkeypatch, tmp_path, "small.xplane.pb")
    run = types.SimpleNamespace(trace=red, counters={})
    for name in TRACE_METRICS:
        assert spec.metric_reader(name)(run) is None, name
    got = scopes.of_run(run)
    assert got.scope_ns == {} and got.spans == []
    assert got.decode_calls == 3


def test_the_wire_reader_agrees_with_profile_data():
    """The decode executions the protobuf reader finds are those that
    jax.profiler.ProfileData gives, to the nanosecond."""
    from jax.profiler import ProfileData
    path = spec.HERE / "testdata" / "small.xplane.pb"
    pd = ProfileData.from_file(str(path))
    want = []
    for plane in pd.planes:
        if trace.DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    want += [(e.start_ns, e.end_ns) for e in line.events
                             if "_decode_impl" in e.name]
    data = memoryview(path.read_bytes())
    got = []
    for f, v in scopes._fields(data):
        if f != 1:
            continue
        name, lines, meta = scopes._plane(v)
        if trace.DEVICE.match(name):
            got += [(a, b) for mid, a, b in lines["XLA Modules"]
                    if "_decode_impl" in meta[mid][0]]
            ops = {meta[mid] for mid, _, _ in lines["XLA Ops"]}
            assert ("%fusion", "jit(_decode_impl)/dot_general:") in {
                (n.split(" = ")[0], op) for n, op in ops}
    assert len(got) == len(want) == 3
    for (a, b), (c, d) in zip(sorted(got), sorted(want)):
        assert a == pytest.approx(c, abs=1) and b == pytest.approx(d, abs=1)


def test_a_scoped_trace_recorded_on_a_v5e(monkeypatch, tmp_path):
    """benchmarks/chip/testdata/scoped.xplane.pb, written by
    record_scoped_trace.py on one TPU v5e: three gateway.step spans of one
    _decode_impl each, then a sessions.handshake span over a 50 ms sleep.
    By hand from its events (ps): the decode executions last 262442578,
    262539844 and 262686328; under ``kv_gather`` only ``%fusion.1`` names
    its scope (313672, 313828, 312578), for XLA left the ``while`` it made
    of the gather, and the operations inside it, without metadata; under
    ``model``, ``%fusion`` (91706172, 91928750, 91947578).  The device
    started each execution up to 799965.25 ns before its dispatch, so the
    first, which the device started before bench.traced [44425390,
    99667369] ns, counts once shifted.  The handshake [48581099, 99658919]
    ns holds no device work."""
    red = _in_trace_dir(monkeypatch, tmp_path, "scoped.xplane.pb")
    got = scopes.of_run(types.SimpleNamespace(trace=red))
    assert got.window == (44425390.0, 99667369.0)
    assert got.decode_calls == 3
    assert got.decode_ns == pytest.approx(787668.75)
    assert got.scope_ns == pytest.approx({"kv_gather": 940.078,
                                          "model": 275582.5})
    assert got.per_call_ms("model") == pytest.approx(0.0918608333)
    assert got.per_call_ms("kv_unseal") == 0.0
    assert got.spans == [("sessions.handshake", 48581099.0, 99658919.0)]
    run = types.SimpleNamespace(trace=red, counters={})
    share = spec.metric_reader("handshake_idle_share.chat")(run)
    assert share == pytest.approx(100 * 51077820 / 55241979)


def test_a_decode_of_the_chat_cell_cut_from_a_v5e_trace(monkeypatch,
                                                        tmp_path):
    """benchmarks/chip/testdata/decode_cut.xplane.pb, cut by cut_decode.py
    from a traced run of granite-3-2b.chat.trusted on one TPU v5e: one
    521034041.25 ns _decode_impl and its 5711 operations, 1071 of them
    outermost.  The two page-gather loops ``%while.41`` and ``%while.42``
    (205320035.078 and 205317331.172 ns) carry no tf_op of their own; the
    528 operations inside each name ``kv_gather``.  By hand (ns):
    weights_unseal 80450263.906 over 186 outermost operations, kv_gather
    419434601.016 over 17, kv_unseal 6463026.56 over 252, model
    8889556.172 over 11 (the layer loop ``%while.40`` is 8579840 of it),
    kv_seal 179206.094 over 161, and 5593709.528 in 444 operations with no
    scope (the weights' layout copies among them)."""
    red = _in_trace_dir(monkeypatch, tmp_path, "decode_cut.xplane.pb")
    run = types.SimpleNamespace(trace=red, counters={})
    want = {"weights_unseal": 80.450263906, "kv_gather": 419.434601016,
            "kv_unseal": 6.46302656, "model": 8.889556172,
            "kv_seal": 0.179206094}
    for scope, ms in want.items():
        assert spec.metric_reader(f"{scope}_ms.chat")(run) == \
            pytest.approx(ms, abs=1e-6), scope
    got = scopes.of_run(run)
    assert got.decode_calls == 1
    assert got.decode_ns == pytest.approx(521034041.25)
    assert sum(want.values()) / 521.03404125 > 0.98
    names = [n for n, _, _ in got.spans]
    assert names == ["serve_step", "sched.decode", "engine.decode_step"]
    (_, a0, b0), (_, a1, b1), (_, a2, b2) = got.spans
    assert a0 <= a1 <= a2 and b2 <= b1 <= b0
    # spans, and no tenant arrived: the share reads 0, not nothing
    assert spec.metric_reader("handshake_idle_share.chat")(run) == 0.0


@pytest.mark.parametrize("name", ["decode_cut.xplane.pb", "scoped.xplane.pb"])
def test_a_nested_scope_leaves_the_engines_readings(name):
    """The five scope readings, the decode calls and the spans of the
    traces recorded on a v5e are the same when a reader asks for a nested
    scope and a span of its own (which these programs do not have); the
    nested scope reads nothing."""
    path = spec.HERE / "testdata" / name
    plain = scopes.read(path)
    asked = scopes.read(path, spans=("moe.route",))
    assert asked.scope_ns == plain.scope_ns and plain.scope_ns
    assert (asked.decode_calls, asked.decode_ns, asked.spans) == \
        (plain.decode_calls, plain.decode_ns, plain.spans)
    assert asked.per_call_ms("experts") is None
    for scope in scopes.SCOPES:
        assert asked.per_call_ms(scope) == plain.per_call_ms(scope)
    assert plain.per_call_ms("experts") is None


NESTED = spec.HERE / "testdata" / "nested.xplane.pb"


def test_a_scope_nested_in_the_layer_loop():
    """testdata/nested.xplane.pb (make_nested.py lists its events): two
    decode calls whose layer loop holds operations scoped ``attn`` and
    ``experts``, one of them a loop of its own around a gather; a prefill
    with one more."""
    got = scopes.read(NESTED, spans=("moe.route",))
    assert got.decode_calls == 2
    assert got.decode_ns == pytest.approx(60e6)
    assert got.scope_ns == pytest.approx({
        "weights_unseal": 10e6, "model": 40e6, "kv_seal": 6e6})
    assert got.per_call_ms("experts") == pytest.approx(14.0)
    assert got.per_call_ms("attn") == pytest.approx(4.0)
    assert got.per_call_ms("model") == pytest.approx(20.0)
    assert got.per_call_ms("kv_gather") == 0.0
    assert got.per_call_ms("router") is None    # no operation carries it
    assert [n for n, _, _ in got.spans] == [
        "sched.decode", "moe.route", "sched.decode", "moe.route"]
    plain = scopes.read(NESTED)
    assert plain.per_call_ms("experts") == pytest.approx(14.0)
    assert plain.per_call_ms("model") == pytest.approx(20.0)
    assert [n for n, _, _ in plain.spans] == ["sched.decode"] * 2


@pytest.mark.parametrize("tf_op, scope, carried", [
    ("jit(_decode_impl)/model/while/body/experts/dot_general:", "experts",
     True),
    ("jit(_decode_impl)/model/while/body/experts_x/dot_general:", "experts",
     False),
    ("jit(_decode_impl)/model/while/body/dot_general:experts", "experts",
     False),
    (None, "experts", False),
])
def test_carries(tf_op, scope, carried):
    assert scopes.carries(tf_op, scope) is carried


def test_a_reader_of_a_nested_scope_is_a_file(monkeypatch, tmp_path):
    """A new reader file reads the nested ``experts`` scope from a traced
    run through scopes.of_run, and a reader asks there for a span of its
    own, with no edit to scopes.py and nothing listed in the
    configuration."""
    red = _in_trace_dir(monkeypatch, tmp_path / "trace", "nested.xplane.pb")
    data = tmp_path / "data"
    (data / "metrics").mkdir(parents=True)
    (data / "metrics" / "experts_ms.py").write_text(
        '"""Device ms per decode call of the nested ``experts`` scope."""\n'
        "from benchmarks.chip import scopes\n\n\n"
        "def read(run):\n"
        "    scoped = scopes.of_run(run)\n"
        '    return scoped.per_call_ms("experts") if scoped else None\n')
    read = spec.metric_reader("experts_ms.moe", data)
    run = types.SimpleNamespace(trace=red, cell=None)
    assert read(run) == pytest.approx(14.0)
    assert read(types.SimpleNamespace(trace=None, cell=None)) is None
    assert spec.metric_reader("model_ms.moe")(run) == pytest.approx(20.0)
    routes = scopes.of_run(run, spans=("moe.route",)).spans
    assert [n for n, _, _ in routes].count("moe.route") == 2
