"""The readers of the program's layers (`chipbench.scopes` and the
`self_ms.*` and `serve_host_ms_per_dispatch` metrics): what they read
from a recorded trace, what they leave to the harness's reduction, and
that only a traced run builds the program's scope tables."""
from __future__ import annotations

import os
import sys
import types

import pytest

from benchkit import BENCH, REPO, run_tiny, tiny  # noqa: F401

from chipbench import scopes, trace
from chipbench.registry import Registry
from test_bench_harness import recorded_trace

LU = ('%custom-call.7 = f32[4,4] custom-call(f32[4,4] %a), '
      'custom_call_target="LuDecompositionBlock"')
ADD = "%fusion.3 = f32[8] fusion(f32[8] %x), kind=kLoop"
GRAD = "%convolution.5 = f32[8,8] convolution(f32[8,8] %p)"
COPY = "%copy.9 = f32[8] copy(f32[8] %q)"
LOOP = "%while.1 = (f32[2]) while(f32[2] %t), body=%b"
TABLES = [{"name": "fused_segment", "module": "jit_seg",
           "scopes": {"custom-call.7": "p4", "fusion.3": "fleet",
                      "convolution.5": "step", "while.1": "sched"}}]


def layer_trace():
    """One device: a loop holding a P4 solve, a fleet op and a model
    step, an op of no layer, and a P4 solve that the window cuts (ns)."""
    return {"devices": {"/device:TPU:0": [
        [100, 500, LOOP, ""], [100, 200, LU, ""], [300, 50, ADD, ""],
        [350, 150, GRAD, ""], [600, 40, COPY, ""], [900, 200, LU, ""]]},
        "spans": [[0, 1000, "window"]], "stat_names": []}


@pytest.fixture
def program(monkeypatch):
    """The program's scope tables replaced by `TABLES`."""
    from repro import obs
    monkeypatch.setattr(obs, "scope_tables", lambda name=None: TABLES)


def _run(tr, cell_rounds=2):
    return types.SimpleNamespace(raw=tr, span=trace.window_of(tr),
                                 window={"cell_rounds": cell_rounds})


@pytest.mark.parametrize("layer,ns", [
    ("p4", 200 + 100), ("fleet", 50), ("step", 150), ("sched", 0),
    ("exchange", 0), ("dt_score", 0)])
def test_self_ms_reads_each_layers_leaf_ops_per_cell_round(program, layer,
                                                           ns):
    """A loop's own time is its body's, an op outside every layer is
    left out, and an op the window cuts counts up to the window's end."""
    reader = Registry(REPO, BENCH).reader(f"self_ms.{layer}")
    assert reader.read(_run(layer_trace())) == pytest.approx(ns * 1e-6 / 2)


def test_layer_times_cover_busy_time_and_are_computed_once(program,
                                                           monkeypatch):
    run = _run(layer_trace())
    times = scopes.layer_times(run)
    assert times["busy_ns"] == pytest.approx(540.0)
    assert times["unscoped_ns"] == pytest.approx(40.0)
    assert sum(times["layers"].values()) + times["unscoped_ns"] == \
        pytest.approx(times["busy_ns"])
    from repro import obs
    monkeypatch.setattr(obs, "scope_tables", lambda name=None: 1 / 0)
    assert scopes.layer_times(run) is times


def test_self_ms_reads_nothing_from_a_program_without_layers(monkeypatch):
    """A checkout from before the program named its layers has no
    `repro.obs`: the readers report nothing and do not raise."""
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    reader = Registry(REPO, BENCH).reader("self_ms.p4")
    assert reader.read(_run(layer_trace())) is None


def test_self_ms_leaves_out_an_instruction_two_programs_disagree_on(
        monkeypatch):
    from repro import obs
    other = {"name": "other", "module": "jit_other",
             "scopes": {"custom-call.7": "step"}}
    monkeypatch.setattr(obs, "scope_tables",
                        lambda name=None: TABLES + [other])
    layers, table = scopes.program_scopes()
    assert "custom-call.7" not in table and table["fusion.3"] == "fleet"
    assert layers == obs.LAYERS


def test_host_ms_per_dispatch_is_the_idle_time_inside_dispatches():
    """The recorded trace's one dispatch, [380, 630): device 0 runs in
    [380, 400) and [600, 630), so it idles 200 ns; device 1 runs
    throughout. The mean over devices is 100 ns."""
    tr = recorded_trace()
    run = types.SimpleNamespace(raw=tr, span=trace.window_of(tr))
    reader = Registry(REPO, BENCH).reader("serve_host_ms_per_dispatch")
    assert reader.read(run) == pytest.approx(100e-6)
    # the whole window: device 0 idles 500 ns, device 1 300 ns
    assert scopes.idle_inside(tr, [[0, 1000]]) == [pytest.approx(400.0)]
    tr["spans"] = [s for s in tr["spans"] if s[2] != "dispatch"]
    assert reader.read(run) is None


# what the harness's reduction reads from the recorded trace, as it read
# it before the program had spans of its own
RECORDED_REDUCTION = {
    "busy_s": 600e-9, "window_s": 1000e-9, "devices": 2,
    "device_ops": [["%fusion.3 (jit(step)/add)", 350e-9],
                   ["%fusion.3", 150e-9],
                   ["%custom-call.7 LuDecompositionBlock", 100e-9]],
    "idle_gaps": [["dispatch", 200e-9], ["wait", 200e-9],
                  ["wait", 200e-9], ["await_arrival", 100e-9],
                  ["await_arrival", 100e-9]],
}


def test_harness_reduction_reads_the_recorded_trace_as_before():
    tr = recorded_trace()
    red = trace.reduce(tr, trace.window_of(tr))
    assert red["devices"] == RECORDED_REDUCTION["devices"]
    for key in ("busy_s", "window_s"):
        assert red[key] == pytest.approx(RECORDED_REDUCTION[key])
    for key in ("device_ops", "idle_gaps"):
        want = RECORDED_REDUCTION[key]
        assert [k for k, _ in red[key]] == [k for k, _ in want]
        assert [v for _, v in red[key]] == pytest.approx(
            [v for _, v in want])
    assert trace._label([390, 400], tr["spans"]) == "dispatch"
    assert trace._label([0, 100], tr["spans"]) == "await_arrival"


# the recorded trace as the profiler writes it, with a program span (a
# run_batch around the harness's dispatch) and a module line
XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 390000 duration_ps: 410000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 200000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_seg(4242)" } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.3 = f32[8] fusion(f32[8] %x), kind=kLoop" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 380000 duration_ps: 250000 }
    events { metadata_id: 3 offset_ps: 381000 duration_ps: 248000 }
    events { metadata_id: 4 offset_ps: 385000 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "repro.serve.run_batch" } }
  event_metadata { key: 4 value { id: 4 name: "repro.serve.segment" } } }
"""


def test_extract_program_reads_program_spans_and_modules(tmp_path):
    """The program's spans and the module line, in the form of the
    harness's spans; the harness's own extraction reads the same file as
    it did before."""
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    prog = scopes.extract_program(str(tmp_path))
    assert prog["program_spans"] == [[381.0, 248.0, "serve.run_batch"],
                                     [385.0, 10.0, "serve.segment"]]
    assert prog["modules"] == {"/device:TPU:0": [[390.0, 410.0, "jit_seg",
                                                  "4242"]]}
    tr = trace.extract(str(tmp_path))
    assert set(tr) == {"devices", "spans", "stat_names"}
    assert tr["spans"] == [[0.0, 1000.0, "window"],
                           [380.0, 250.0, "dispatch"]]
    assert tr["devices"] == {"/device:TPU:0": [
        [600.0, 200.0, "%fusion.3 = f32[8] fusion(f32[8] %x), kind=kLoop",
         ""]]}
    os.remove(d / "host.xplane.pb")
    with pytest.raises(FileNotFoundError):
        scopes.extract_program(str(tmp_path))


def test_an_untraced_run_builds_no_scope_table(tiny, monkeypatch):
    """The program's scope tables compile its programs again: only the
    readers of a traced run, after its window and its check, ask."""
    from repro import obs

    def refuse(name=None):
        raise AssertionError("scope tables built in an untraced run")
    monkeypatch.setattr(obs, "scope_tables", refuse)
    reg = tiny("veds_cnn.train1")
    result, _ = run_tiny(reg, "veds_cnn.train1")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"cell_rounds_per_s", "setup_s"}
