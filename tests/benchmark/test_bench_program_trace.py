"""The reduction of the program's own spans and device scopes, on
synthesized traces and on a CPU trace of `Engine.fit`; the scopes each
kernel holds, from the trace's HLO; and the eight metric readers on top
of it."""
import pathlib
import types

import jax
import pytest

from bench import program_trace as P
from bench import suite as S
from bench import trace as T

ROOT = pathlib.Path(__file__).resolve().parents[2]
READERS = ["build_slice_ms", "build_adjacency_ms", "build_gather_ms",
           "epoch_end_ms_per_step", "host_syncs_per_step", "xw_fusions_ms",
           "aggregate_fusions_ms", "idle_unattributed_pct"]


def _span(start, end, name, thread="main", **stats):
    return P.Span(start, end, name, tuple(stats.items()), thread)


def _build_spans():
    # the main thread waits 0-10.5 for a build of 0-10 whose children
    # cover 1-3, 3-7 and 7-9.5; a producer thread runs 2-4
    return [_span(0.0, 10.5, "engine.wait"),
            _span(0.0, 10.0, "batch.build"),
            _span(1.0, 3.0, "batch.slice"),
            _span(3.0, 7.0, "batch.adjacency"),
            _span(7.0, 9.5, "batch.gather"),
            _span(2.0, 4.0, "prefetch.produce", thread="producer")]


def test_self_time_is_the_duration_less_the_nested_spans():
    got = P.self_seconds(_build_spans(), 0.0, 20.0)
    assert got == pytest.approx({"engine.wait": 0.5, "batch.build": 1.5,
                                 "batch.slice": 2.0, "batch.adjacency": 4.0,
                                 "batch.gather": 2.5,
                                 "prefetch.produce": 2.0})
    depth, _ = P.nest(_build_spans())
    assert depth == [0, 1, 2, 2, 2, 0]


def test_self_time_counts_only_the_window():
    got = P.self_seconds(_build_spans(), 2.0, 8.0)
    assert got == pytest.approx({"batch.slice": 1.0, "batch.adjacency": 4.0,
                                 "batch.gather": 1.0,
                                 "prefetch.produce": 2.0})


def test_a_child_that_outlives_its_parent_is_cut_at_its_end():
    spans = [_span(0.0, 4.0, "engine.hooks"), _span(3.0, 6.0, "x")]
    assert P.self_seconds(spans, 0.0, 10.0) == pytest.approx(
        {"engine.hooks": 3.0, "x": 1.0})


def test_scope_is_the_innermost_one_an_op_name_carries():
    assert P.scope_of(["jit(step)/transpose(jvp(gcn.xw))/dot_general"]) \
        == "gcn.xw"
    assert P.scope_of(
        ["jit(shard_fn)/shard_map/jvp(vmap(gcn.aggregate))/dot"]) \
        == "gcn.aggregate"
    assert P.scope_of(["jit(step)/optim.update/mul"]) == "optim.update"
    assert P.scope_of(["dp.allreduce/psum"]) == "dp.allreduce"
    hlo = ('%fusion.3 = f32[8] fusion(%p), kind=kLoop, metadata='
           '{op_name="jit(step)/jvp(gcn.activation)/max"}')
    assert P.scope_of([hlo]) == "gcn.activation"
    assert P.scope_of(["jit(step)/jit(_threefry_split)/add"]) is None
    assert P.scope_of(["fusion.12", "jit(step)/gcn.loss/mul",
                       "jit(step)/gcn.xw/add"]) == "gcn.loss"
    assert P.scope_of(["jit(step)/xgcn.xw/add", "gcn.xw-1"]) is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    """One protobuf field: an int as a varint, bytes or str
    length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, stat_names, events):
    """An XPlane: `stat_names` {id: name}; `events` {id: (name, [stat])},
    a stat being {field: value} of an XStat."""
    out = _field(2, name)
    for key, (ev_name, stats) in events.items():
        meta = _field(1, key) + _field(2, ev_name) + b"".join(
            _field(5, b"".join(_field(f, v) for f, v in st.items()))
            for st in stats)
        out += _field(4, _field(1, key) + _field(2, meta))
    for key, st_name in stat_names.items():
        out += _field(5, _field(1, key) + _field(2, _field(1, key)
                                                 + _field(2, st_name)))
    return out


def test_op_names_read_the_event_metadata_of_device_planes(tmp_path):
    stat_names = {1: "tf_op", 2: "flops", 3: "jit(step)/optim.update/mul:"}
    device = _plane("/device:TPU:0", stat_names, {
        7: ("%fusion.1 = f32[8] fusion(%p)",
            [{1: 2, 3: 99}, {1: 1, 5: "jit(step)/jvp(gcn.xw)/dot:"}]),
        8: ("%add.2 = f32[8] add(%a, %b)", [{1: 1, 7: 3}]),
        9: ("%copy.3 = f32[8] copy(%a)", [{1: 2, 3: 0}])})
    host = _plane("/host:CPU", stat_names, {
        4: ("repro.batch.build", [{1: 1, 5: "not an op"}])})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host) + _field(4, "h"))
    names = P.op_names(str(path))
    assert names == {"%fusion.1 = f32[8] fusion(%p)":
                     "jit(step)/jvp(gcn.xw)/dot:",
                     "%add.2 = f32[8] add(%a, %b)":
                     "jit(step)/optim.update/mul:"}
    assert [P.scope_of([n]) for n in names.values()] == ["gcn.xw",
                                                         "optim.update"]


def _instruction(name, opcode, op_name="", called=()):
    out = _field(1, name) + _field(2, opcode)
    if op_name:
        out += _field(7, _field(2, op_name))
    if called:      # packed, as protobuf writes a repeated int64
        out += _field(38, b"".join(_varint(c) for c in called))
    return out


def _computation(cid, *instructions):
    """A computation as an HloModuleProto field."""
    return _field(3, _field(1, f"c{cid}") + b"".join(
        _field(2, i) for i in instructions) + _field(5, cid))


def test_kernel_scopes_read_the_hlo_of_the_metadata_plane(tmp_path):
    # the entry (1) runs a fusion of computation 2, which nests a fusion
    # of computation 3, and an unfused optimizer op; computation 4 is a
    # reduction's body, called by a non-fusion op
    module = (
        _computation(1,
                     _instruction("fusion.1", "fusion",
                                  "jit(step)/transpose(jvp(gcn.xw))/dot",
                                  called=[2]),
                     _instruction("add.3", "add",
                                  "jit(step)/optim.update/add"),
                     _instruction("reduce.9", "reduce",
                                  "jit(step)/jvp(gcn.loss)/reduce_sum",
                                  called=[4]),
                     _instruction("copy.8", "copy")) +
        _computation(2,
                     _instruction("convolution.4", "convolution",
                                  "jit(step)/transpose(jvp(gcn.xw))/dot"),
                     _instruction("mul.5", "multiply",
                                  "jit(step)/optim.update/mul"),
                     _instruction("fusion.6", "fusion", called=[3])) +
        _computation(3, _instruction("max.7", "maximum",
                                     "jit(step)/jvp(gcn.activation)/max")) +
        _computation(4, _instruction("add.10", "add",
                                     "jit(step)/optim.update/add")))
    hlo = _field(1, module)
    meta = _plane("/host:metadata", {1: "Hlo Proto"},
                  {1: ("jit_step(1)", [{1: 1, 6: hlo}])})
    other = _plane("/host:CPU", {1: "Hlo Proto"},
                   {1: ("x", [{1: 1, 6: _field(1, _computation(
                       1, _instruction("fusion.1", "fusion",
                                       "jit(step)/gcn.loss/x")))}])})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, other) + _field(1, meta))
    held = P.kernel_scopes(str(path))
    assert held["fusion.1"] == {"gcn.xw", "optim.update", "gcn.activation"}
    assert held["fusion.6"] == {"gcn.activation"}
    assert held["add.3"] == {"optim.update"}
    # only a fusion runs what it calls
    assert held["reduce.9"] == {"gcn.loss"}
    assert held["copy.8"] == frozenset()
    assert P.held_label(held, "%fusion.1 = f32[8] fusion(%p), calls=c2") \
        == "gcn.activation+gcn.xw+optim.update"
    assert P.held_label(held, "%copy.8 = f32[8] copy(%a)") is None
    assert P.held_label(held, "%fusion.99 = f32[8] fusion(%a)") is None


def _program():
    # device 0 runs 0-2 (X·W) and 5-6 (optimizer) in a 0-10 window and is
    # idle 2-5 and 6-10; device 1 is another chip
    ops = {0: [(0.0, 1.5, "gcn.xw"), (1.5, 2.0, "gcn.aggregate"),
               (5.0, 6.0, "optim.update"), (-1.0, 0.5, "gcn.xw")],
           1: [(0.0, 10.0, "gcn.xw")]}
    spans = [_span(2.0, 3.4, "engine.epoch_end", steps=50, syncs=200),
             _span(6.0, 10.0, "engine.wait"),
             _span(6.5, 9.5, "batch.build"),
             _span(7.0, 8.0, "batch.adjacency"),
             _span(-3.0, -2.0, "engine.epoch_end", steps=50, syncs=200)]
    return P.Program(window=(0.0, 10.0), spans=spans, ops=ops)


def test_scope_and_span_times_per_step():
    r = P.reduce_program(_program(), device_ids=[0], steps=4)
    assert r.window_s == pytest.approx(10.0)
    # the op that began before the window counts its part inside
    assert r.scope_ms_per_step("gcn.xw") == pytest.approx(1e3 * 2.0 / 4)
    assert r.scope_ms_per_step("gcn.xw", "gcn.xw_aggregate") == \
        pytest.approx(1e3 * 2.0 / 4)
    assert r.scope_ms_per_step("optim.update") == pytest.approx(250.0)
    assert r.scope_ms_per_step("gcn.xw_aggregate") is None
    assert r.span_ms_per_step("batch.build") == pytest.approx(1e3 * 2 / 4)
    assert r.span_ms_per_step("batch.slice") is None


def test_epoch_end_and_syncs_read_the_window_spans():
    r = P.reduce_program(_program(), device_ids=[0], steps=4)
    assert [sp.start for sp in r.epoch_ends] == [2.0]
    assert r.syncs_per_step() == pytest.approx(4.0)
    assert r.epoch_end_ms_per_step() == pytest.approx(1e3 * 1.4 / 50)


def test_unattributed_idle_is_idle_with_no_span_open():
    r = P.reduce_program(_program(), device_ids=[0], steps=4)
    # idle 2-5 and 6-10; spans cover 2-3.4 and 6-10
    assert r.unattributed_s == pytest.approx(1.6)
    assert r.unattributed_pct() == pytest.approx(16.0)


def test_idle_gaps_are_the_harness_gaps_named_by_the_innermost_span():
    prog = _program()
    r = P.reduce_program(prog, device_ids=[0], steps=4)
    # 6-10: engine.wait covers all, batch.build 3 of 4 s, the
    # adjacency 1 s (under half); 2-5: the epoch end covers 1.4 of 3 s
    assert r.idle_gaps_by_span == [("batch.build", pytest.approx(4.0)),
                                   ("other", pytest.approx(3.0))]
    ops = {d: [(s, e, f"op{i}") for i, (s, e, _) in enumerate(v)]
           for d, v in prog.ops.items()}
    theirs = T.reduce_events(ops, [(0.0, 10.0, T.WINDOW_SPAN)],
                             device_ids=[0], steps=4).idle_gaps
    assert [d for _, d in r.idle_gaps_by_span] == \
        pytest.approx([d for _, d in theirs])


def test_a_trace_without_program_spans_or_scopes_reads_nothing():
    prog = P.Program(window=(0.0, 10.0), spans=[],
                     ops={0: [(0.0, 1.0, None)]})
    r = P.reduce_program(prog, device_ids=[0], steps=4)
    assert r.unattributed_pct() is None
    assert r.syncs_per_step() is None
    assert r.epoch_end_ms_per_step() is None
    assert r.scope_ms_per_step("gcn.xw") is None
    assert r.span_ms_per_step("batch.slice") is None
    assert r.idle_gaps_by_span == [("other", pytest.approx(9.0))]


def _traced_fit(trace_dir, program_spans=True):
    """A CPU trace of two epochs of `ppi_tiny` in a `bench.window` span;
    without `program_spans`, a window over work of no program span."""
    from repro.core.experiment import build_experiment, preset
    spec = preset("ppi_tiny")
    spec.run.epochs = 2
    spec.run.eval_every = 0
    exp = build_experiment(spec)
    with jax.profiler.trace(str(trace_dir)):
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            if program_spans:
                exp.fit()
            else:
                jax.numpy.ones(8).block_until_ready()
    return exp


def _run_of(trace_dir, steps):
    path = P.newest_trace(trace_dir)
    window = P.program_events(
        jax.profiler.ProfileData.from_file(str(path))).window
    return types.SimpleNamespace(
        chips=1, steps=steps,
        trace=types.SimpleNamespace(window_s=window[1] - window[0]))


def test_readers_on_a_cpu_trace_of_fit(tmp_path, monkeypatch):
    exp = _traced_fit(tmp_path)
    steps = exp.engine.global_step
    run = _run_of(tmp_path, steps)
    got = P.read(run, root=tmp_path)
    assert got is not None and got.steps == steps
    monkeypatch.setattr(P, "TRACE_ROOT", tmp_path)
    suite = S.Suite(ROOT)
    values = {name: suite.reader(name)(run) for name in READERS}
    for name in ("build_slice_ms", "build_adjacency_ms",
                 "build_gather_ms", "epoch_end_ms_per_step"):
        assert values[name] > 0, name
    assert values["host_syncs_per_step"] == 4.0
    assert 0 <= values["idle_unattributed_pct"] <= 100
    # the CPU has no device plane: no operation carries a scope
    assert values["xw_fusions_ms"] is None
    assert values["aggregate_fusions_ms"] is None
    # the trace keeps the step's HLO, whose instructions carry the scopes
    held = P.kernel_scopes(str(P.newest_trace(tmp_path)))
    assert {"gcn.xw", "gcn.aggregate", "optim.update"} <= \
        set().union(*held.values())
    # another run's window is not read
    run.trace.window_s += 1e-3
    assert P.read(run, root=tmp_path) is None
    assert all(suite.reader(n)(run) is None for n in READERS)


def test_readers_on_a_program_without_spans_read_nothing(tmp_path,
                                                         monkeypatch):
    _traced_fit(tmp_path, program_spans=False)
    run = _run_of(tmp_path, steps=4)
    monkeypatch.setattr(P, "TRACE_ROOT", tmp_path)
    suite = S.Suite(ROOT)
    assert all(suite.reader(n)(run) is None for n in READERS)
    untraced = types.SimpleNamespace(chips=1, steps=4, trace=None)
    assert all(suite.reader(n)(untraced) is None for n in READERS)


def test_every_new_reader_is_listed_for_both_cells():
    spec = S.Suite(ROOT).spec
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        assert per_layer[name]["workloads"] == ["ppi_sota.train",
                                                "amazon2m.train"]
        assert per_layer[name]["moves"] == "train_nodes_per_s"
