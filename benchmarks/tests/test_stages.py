"""stages.py on hand-made profiles (built like test_trace_reduce.py's): stage
sums over whole executions only, operations outside the markers or outside
the step programs ignored, `unattributed`, two programs averaged by their
executions, a nested event's time taken out of its parent's, and unscoped
programs read as None and never as 0. Then the trace viewer's json as the
profiler writes it, and the readers on runs that have nothing to read. Not
part of tier-1."""

import gzip
import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import registry  # noqa: E402
import stages  # noqa: E402
from test_trace_reduce import _profile  # noqa: E402

STAGE_METRICS = [m["name"] for m in registry.manifest()["per_layer"]
                 if m["name"].startswith("stage.")]
SATELLITES = ["ingress.intern_cpu_ms", "dispatch.cpu_ms",
              "dispatch.drop_sync_ms", "idle.worker_intern_pct"]
MARKS = {"python": [("bench_trace_open", 100, 1),
                    ("bench_trace_close", 2000, 1)]}
STEP = "jit_step(7)"


def _scopes(program, **ops):
    return {(program, op): name for op, name in ops.items()}


def test_an_op_name_names_its_first_stage_and_a_declared_part_of_it():
    of = stages.scope_of
    assert of("jit(step)/siddhi.selector/siddhi.selector/sort/sort:") == (
        "selector", "selector/sort")
    assert of("jit(step)/siddhi.selector/scatter-add") == ("selector", None)
    assert of("jit(step)/siddhi.emit/jit(_where)/select_n:") == ("emit", None)
    # a shared kernel's scope does not take the op from the step's stage
    assert of("jit(s)/siddhi.append/siddhi.match/sort") == ("append", None)
    assert of("jit(s)/siddhi.window/siddhi.window/append/dynamic_update_slice") \
        == ("window", "window/append")
    assert of("jit(s)/siddhi.match/siddhi.match/expire/gt") == (
        "match", "match/expire")
    assert of("jit(step)/gather") == (None, None)
    assert of("reduce_window_sum") == (None, None)
    assert of(None) == (None, None) and of("") == (None, None)


def test_stage_sums_take_whole_executions_inside_the_markers_only():
    prof = _profile({
        "/device:TPU:0": {
            "XLA Modules": [
                (STEP, 50, 200),            # cut by the opening marker
                (STEP, 300, 400),           # whole: 300-700
                ("jit__wire_pack(3)", 720, 50),  # no step program
                (STEP, 800, 400),           # whole: 800-1200
                (STEP, 1900, 300)],         # cut by the closing marker
            "XLA Ops": [
                ("f", 60, 100),             # in the cut execution
                ("f", 300, 50), ("w", 350, 150), ("s", 500, 100),
                ("bare", 600, 60),
                ("f", 730, 30),             # in another program
                ("f", 800, 70), ("w", 870, 130), ("s", 1000, 200),
                ("f", 1950, 40)],           # in the cut execution
        },
        "/host:CPU": MARKS,
    })
    scopes = _scopes(STEP, f="jit(step)/siddhi.filter/and",
                     w="jit(step)/siddhi.window/siddhi.window/fetch/gather",
                     s="jit(step)/siddhi.selector/add", bare=None)
    out = stages.reduce_profile(prof, scopes)
    assert out["family"] == "query" and out["executions"] == 2
    assert out["scoped"] is True
    assert out["programs"] == {STEP: [pytest.approx(800e-9), 2]}
    assert out["module_ms"] == pytest.approx(400e-6)
    ms = {k: v[0] for k, v in out["stage_ms"].items()}
    assert ms == {"filter": pytest.approx(60e-6),
                  "window": pytest.approx(140e-6),
                  "selector": pytest.approx(150e-6),
                  "emit": 0.0,   # a stage of the family that took no time
                  "unattributed": pytest.approx(30e-6)}
    assert out["stage_ms"]["filter"][1] == 2
    assert out["sub_ms"] == {"window/fetch": [pytest.approx(140e-6), 2]}
    # the stages and `unattributed` add up to the programs' operation time
    assert sum(ms.values()) == pytest.approx(out["op_ms"])
    assert out["op_ms"] == pytest.approx(380e-6)
    assert out["unattributed_pct"] == pytest.approx(100 * 30 / 380)
    assert out["widest_ops"]["unattributed"] == [
        ["bare", pytest.approx(30e-6), 1]]
    assert out["widest_ops"]["window/fetch"] == [
        ["w", pytest.approx(140e-6), 2]]
    assert "window" not in out["widest_ops"]  # all of it in its part


def test_two_programs_are_averaged_by_their_executions():
    left, right = "jit_join_probe_left(1)", "jit_join_probe_right(2)"
    prof = _profile({
        "/device:TPU:0": {
            "XLA Modules": [(left, 200, 100), (right, 400, 300),
                            (right, 800, 300),
                            ("jit_step(9)", 1200, 50)],  # the lesser family
            "XLA Ops": [("p", 200, 100), ("p", 400, 200), ("c", 600, 100),
                        ("p", 800, 200), ("c", 1000, 100),
                        ("x", 1200, 50)],
        },
        "/host:CPU": MARKS,
    })
    scopes = {(left, "p"): "jit(join_probe_left)/siddhi.probe/gather",
              (right, "p"): "jit(join_probe_right)/siddhi.probe/gather",
              (right, "c"): "jit(join_probe_right)/siddhi.compact/cummax",
              ("jit_step(9)", "x"): "jit(step)/siddhi.filter/and"}
    out = stages.reduce_profile(prof, scopes)
    assert out["family"] == "join" and out["executions"] == 3
    assert out["module_ms"] == pytest.approx(700e-6 / 3)
    assert out["stage_ms"]["probe"][0] == pytest.approx(500e-6 / 3)
    assert out["stage_ms"]["compact"][0] == pytest.approx(200e-6 / 3)
    assert set(out["stage_ms"]) == {"filter", "window", "probe", "compact",
                                    "frames", "selector", "emit",
                                    "unattributed"}
    assert out["stage_ms"]["filter"] == [0.0, 0]  # jit_step's is not ours


def test_of_several_jit_step_programs_the_costliest_is_the_step():
    prof = _profile({
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(1)", 200, 10), ("jit_step(1)", 220, 10),
                            ("jit_step(2)", 300, 500)],
            "XLA Ops": [("a", 200, 10), ("a", 220, 10), ("b", 300, 500)],
        },
        "/host:CPU": MARKS,
    })
    out = stages.reduce_profile(prof, {
        ("jit_step(1)", "a"): "jit(step)/siddhi.filter/gt",
        ("jit_step(2)", "b"): "jit(step)/siddhi.selector/add"})
    assert list(out["programs"]) == ["jit_step(2)"]
    assert out["stage_ms"]["selector"][0] == pytest.approx(500e-6)
    assert out["stage_ms"]["filter"] == [0.0, 0]


def test_a_nested_events_time_is_taken_out_of_its_parents():
    prof = _profile({
        "/device:TPU:0": {
            "XLA Modules": [(STEP, 200, 1000)],
            "XLA Ops": [("while", 200, 800), ("body", 300, 100),
                        ("body", 500, 200), ("tail", 1000, 200)],
        },
        "/host:CPU": MARKS,
    })
    out = stages.reduce_profile(prof, _scopes(
        STEP, **{"while": "jit(step)/siddhi.window/while",
                 "body": "jit(step)/siddhi.selector/add",
                 "tail": "jit(step)/siddhi.emit/select_n"}))
    ms = {k: v[0] for k, v in out["stage_ms"].items()}
    assert ms["window"] == pytest.approx(500e-6)
    assert ms["selector"] == pytest.approx(300e-6)
    assert ms["emit"] == pytest.approx(200e-6)
    assert out["op_ms"] == pytest.approx(1000e-6)


def test_unscoped_programs_read_none_and_not_zero():
    """An executable from a cache an unscoped build wrote: the operations
    are there, no `op_name` names a stage."""
    prof = _profile({
        "/device:TPU:0": {"XLA Modules": [(STEP, 200, 300)],
                          "XLA Ops": [("a", 200, 100), ("b", 300, 200)]},
        "/host:CPU": MARKS,
    })
    out = stages.reduce_profile(prof, _scopes(
        STEP, a="jit(step)/jit(_where)/select_n", b=None))
    assert out["scoped"] is False and out["op_ms"] == pytest.approx(300e-6)
    run = {"stages": out}
    for stage in ("filter", "window", "selector", "emit", "probe"):
        assert stages.stage_ms(run, stage) is None
    assert stages.unattributed_pct(run) is None
    for name in STAGE_METRICS:
        assert registry.load_module("layer_metrics", name).read(run) is None
    # scoped, and a stage that took no time: 0, and None only for a stage
    # the family does not have
    scoped = stages.reduce_profile(prof, _scopes(
        STEP, a="jit(step)/siddhi.filter/gt", b=None))
    run = {"stages": scoped}
    assert stages.stage_ms(run, "filter") == pytest.approx(100e-6)
    assert stages.stage_ms(run, "emit") == 0.0
    assert stages.stage_ms(run, "probe") is None
    assert stages.unattributed_pct(run) == pytest.approx(100 * 200 / 300)


def test_no_step_program_no_reduction():
    prof = _profile({
        "/device:TPU:0": {"XLA Modules": [("jit__wire_pack(3)", 200, 50)],
                          "XLA Ops": [("a", 200, 50)]},
        "/host:CPU": MARKS,
    })
    assert stages.reduce_profile(prof, {}) is None
    # the CPU backend's trace has no device plane
    assert stages.reduce_profile(_profile({"/host:CPU": MARKS}), {}) is None


def _trace_json(path, step=STEP):
    """The trace viewer's json as the profiler writes it beside a TPU
    xplane (my chip run, PR 34): `M` events name processes and threads, an
    `XLA Ops` event carries `long_name` and, where the HLO has one,
    `tf_op`."""
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 701, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 701, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 3, "tid": 2, "ts": 10.0, "dur": 5.0,
         "name": step},
        {"ph": "X", "pid": 3, "tid": 2, "ts": 20.0, "dur": 5.0,
         "name": "jit__wire_pack(3)"},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 10.5, "dur": 1.0,
         "name": "fusion.3", "args": {
             "long_name": "%fusion.3 = s32[8]{0} fusion(...)",
             "tf_op": "jit(step)/siddhi.selector/scatter-add:"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 12.0, "dur": 1.0,
         "name": "copy-done",
         "args": {"long_name": "%copy-done = s32[8]{0} copy-done(...)"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 21.0, "dur": 1.0,
         "name": "fusion.3", "args": {
             "long_name": "%fusion.3 = s32[8]{0} fusion(...)",
             "tf_op": "jit(_wire_pack)/siddhi.emit/min:"}},
        {"ph": "X", "pid": 701, "tid": 3, "ts": 10.5, "dur": 1.0,
         "name": "host", "args": {"long_name": "h", "tf_op": "x"}},
    ]
    with gzip.open(path, "wt") as fh:
        json.dump({"displayTimeUnit": "ns", "traceEvents": events}, fh)


def test_the_trace_json_gives_each_programs_operations_their_op_name(
        tmp_path):
    path = str(tmp_path / "vm.trace.json.gz")
    _trace_json(path)
    assert stages.load_scopes(path) == {
        (STEP, "%fusion.3 = s32[8]{0} fusion(...)"):
            "jit(step)/siddhi.selector/scatter-add:",
        (STEP, "%copy-done = s32[8]{0} copy-done(...)"): None,
        ("jit__wire_pack(3)", "%fusion.3 = s32[8]{0} fusion(...)"):
            "jit(_wire_pack)/siddhi.emit/min:"}
    assert stages.trace_json_beside(str(tmp_path / "vm.xplane.pb")) == path
    assert stages.trace_json_beside(str(tmp_path / "x" / "vm.xplane.pb")) \
        is None


@pytest.mark.parametrize("name", STAGE_METRICS + SATELLITES)
def test_a_run_with_nothing_to_read_reads_none_and_does_not_raise(name):
    """Untraced, off the chip, or a parent commit's statistics: no cell, no
    section, no event."""
    read = registry.load_module("layer_metrics", name).read
    cell = {"total_ms": 3.0, "batches": 2, "mean_ms": 1.5}
    stats = {"ingress_pipeline": {"S": {"stage_ms": {
        k: dict(cell) for k in ("decode", "intern", "h2d", "device")}}}}
    run = {"events": NS(plans=[{"stream": "S"}]), "trace": None,
           "trace_dir": os.path.join(HERE, "no_such_dir"),
           "device": {"platform": "tpu"}, "cache": {},
           "stats0": stats, "stats1": stats}
    assert read(dict(run)) is None
    assert read({**run, "device": {"platform": "cpu"},
                 "trace": {"open_ns": 0}, "reduced_trace": {"gaps": []},
                 "host_spans": {}}) is None
    # traced on the chip, and the profiler left nothing in the directory
    traced = {**run, "trace": {"open_ns": 0}, "host_spans": {},
              "reduced_trace": {"gaps": [(0, 1_000_000)]}}
    assert read(traced) is None


def test_the_manifest_lists_the_ten_stage_metrics_and_their_cells():
    by_name = {m["name"]: m for m in registry.manifest()["per_layer"]}
    assert len(STAGE_METRICS) == 10
    four = {"groupby_1m.saturate", "distinct_60s.saturate",
            "join_100k.saturate", "pattern_ab.saturate"}
    for name in STAGE_METRICS:
        m = by_name[name]
        assert (m["source"], m["layer"], m["moves"], m["better"]) == (
            "device_trace", "device step", "events_per_s", "lower")
        stage = name[len("stage."):].rsplit("_", 1)[0]
        cells = {c.split(".")[0] for c in m["workloads"]}
        for family, config in (("query", "groupby_1m"),
                               ("query", "distinct_60s"),
                               ("join", "join_100k"),
                               ("pattern", "pattern_ab")):
            if stage != "unattributed":
                assert (config in cells) == (
                    stage in stages.FAMILIES[family][1]), (name, config)
        assert set(m["workloads"]) <= four
    assert set(by_name["stage.unattributed_pct"]["workloads"]) == four


def test_satellite_readers_read_their_cells_as_deltas():
    def cell(total, n, cpu=None):
        out = {"total_ms": total, "batches": n}
        if cpu is not None:
            out["cpu_ms"] = cpu
        return out

    def stats(k):
        return {
            "ingress_pipeline": {"S": {"stage_ms": {
                "intern": cell(10.0 * k, 2 * k, 6.0 * k),
                "dispatch": cell(8.0 * k, 2 * k, 1.0 * k)}}},
            "joins": {"j": {"steps": {"left": 64 * k, "right": 64 * k},
                            "stage_ms": {"drop_sync": cell(40.0 * k, 2 * k)}}},
            "windows": {"w": {"steps": 128 * k, "stage_ms": {
                "drop_sync": cell(24.0 * k, 2 * k)}}},
        }

    run = {"events": NS(plans=[{"stream": "S"}]), "stats0": stats(1),
           "stats1": stats(3)}

    def read(name):
        return registry.load_module("layer_metrics", name).read(run)

    assert read("ingress.intern_cpu_ms") == pytest.approx(12.0 / 4)
    assert read("dispatch.cpu_ms") == pytest.approx(2.0 / 4)
    # (80 + 48) ms of fetches over (256 + 256) steps
    assert read("dispatch.drop_sync_ms") == pytest.approx(128.0 / 512)
    run.update(
        reduced_trace={"gaps": [(0, 1_000_000), (2_000_000, 3_000_000),
                                (5_000_000, 5_050_000)]},  # one short
        host_spans={"siddhi.feeder.h2d": [(0, 3_000_000)],
                    "siddhi.ingress.intern": [(100_000, 600_000),
                                              (2_500_000, 4_000_000)]})
    assert read("idle.worker_intern_pct") == pytest.approx(50.0)
