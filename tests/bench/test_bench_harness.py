"""The chip benchmark's pieces that need no chip: the trace reduction,
the operation and byte counts, the open-loop schedule, the registry,
the shape of a run's last line, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

from benchkit import BENCH, REPO, real_spec, run_tiny, tiny  # noqa: F401

from chipbench import checks, flops, schedule, trace
from chipbench.registry import Registry, RegistryError

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ------------------------------------------------------------ the trace

def recorded_trace():
    """Two devices, a loop holding two leaf ops and a gap after them, a
    gap in which the host dispatched and one in which it waited (ns)."""
    loop = "%while.1 = (f32[2]) while(f32[2] %t), body=%b"
    lu = ('%custom-call.7 = f32[4,4] custom-call(f32[4,4] %a), '
          'custom_call_target="LuDecompositionBlock"')
    add = "%fusion.3 = f32[8] fusion(f32[8] %x), kind=kLoop"
    return {
        "devices": {
            "/device:TPU:0": [[100, 400, loop, ""], [100, 200, lu, ""],
                              [300, 100, add, ""], [600, 200, add, ""]],
            "/device:TPU:1": [[100, 700, add, "jit(step)/add"]]},
        "spans": [[0, 1000, "window"], [380, 250, "dispatch"],
                  [850, 150, "wait"], [0, 1000, "await_arrival"]],
        "stat_names": []}


def test_trace_reduction_busy_idle_ops_and_gaps():
    tr = recorded_trace()
    red = trace.reduce(tr, trace.window_of(tr))
    # device 0 busy [100, 400) and [600, 800) = 500 ns, device 1 700 ns;
    # the loop's own [400, 500), where none of its ops ran, is idle
    assert red["busy_s"] == pytest.approx(600e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    ops = dict(red["device_ops"])
    # the loop holds the others: it counts neither as busy nor as an op
    assert not any(k.startswith("%while") for k in ops)
    assert ops["%custom-call.7 LuDecompositionBlock"] == pytest.approx(
        100e-9)
    assert ops["%fusion.3"] == pytest.approx(150e-9)
    assert ops["%fusion.3 (jit(step)/add)"] == pytest.approx(350e-9)
    gaps = red["idle_gaps"]
    # a dispatch or a wait that covers a gap outranks the generator's
    # sleep around it; a gap only the sleep covers is the sleep's
    assert [g[0] for g in gaps] == ["dispatch", "wait", "wait",
                                    "await_arrival", "await_arrival"]
    assert gaps[0][1] == pytest.approx(200e-9)
    reader = Registry(REPO, BENCH).reader("idle_share.rounds")
    share = reader.read(types.SimpleNamespace(trace=red))
    assert share == pytest.approx(40.0)


def test_merge_and_clip():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert trace.clip([[0, 3], [5, 8]], 2, 6) == [[2, 3], [5, 6]]


# ---------------------------------------------------- the comparison

def test_norm_gap_sees_a_gradient_that_points_elsewhere():
    """A leaf of the right norm in another direction passes the gap of
    norms and fails the norm of the difference; a tiny leaf is left out
    and a small one is measured against the median leaf."""
    ref = [np.array([3.0, 4.0]), np.array([0.0, 10.0]),
           np.array([1e-6, 0.0]), np.array([0.0, 0.5])]
    prog = [np.array([4.0, 3.0]), np.array([0.0, 10.0]),
            np.array([1.0, 0.0]), np.array([0.0, 0.6])]
    # the median leaf's norm is (0.5 + 5) / 2
    assert checks.norm_gap(prog, ref) == pytest.approx(0.1 / 2.75)
    assert checks.norm_gap(prog, ref, diff=True) == pytest.approx(
        np.sqrt(2.0) / 5.0)
    assert checks.norm_gap([np.array([np.nan])], [np.array([1.0])]) == \
        np.inf


def test_training_comparison_reports_both_gradient_numbers():
    p0 = {"w": np.ones((2, 2)), "b": np.ones(3)}
    step = {"w": np.array([[0.1, 0.0], [0.0, 0.1]]), "b": np.full(3, 0.01)}
    turned = {"w": np.array([[0.0, 0.1], [0.1, 0.0]]), "b": np.full(3, 0.01)}

    def rounds(delta):
        p = {k: p0[k] - delta[k] for k in p0}
        return {"success": np.zeros((1, 1, 2), bool),
                "energy_sov": np.ones((1, 1, 2)),
                "energy_opv": np.ones((1, 1, 2)), "qs": np.ones((1, 1, 2)),
                "qu": np.ones((1, 1, 2)), "loss": np.ones((1, 1)),
                "params": [[p]]}
    out = checks.compare_training(rounds(turned), rounds(step), p0, 1.0,
                                  False)
    assert out["grad1_gap"] == pytest.approx(0.0, abs=1e-12)
    assert out["step3_gap"] == pytest.approx(0.0, abs=1e-12)
    # the weight leaf turned by 90 degrees: |diff| = sqrt(2) |w|
    assert out["grad1_diff"] == pytest.approx(np.sqrt(2.0))
    assert out["step3_diff"] == pytest.approx(np.sqrt(2.0))


def test_serve_comparison_holds_long_sessions_to_masks_and_queues():
    p0 = {"w": np.zeros(4)}
    ref = [{"success": np.zeros((3, 2), bool), "loss": np.ones(3),
            "queue": np.ones(5), "params": {"w": np.ones(4)}}
           for _ in range(2)]
    prog = [dict(r) for r in ref]
    # the long session's weights drifted: reported, not in params_gap
    prog[1] = dict(ref[1], params={"w": np.full(4, 1.5)},
                   loss=np.full(3, 9.0))
    out = checks.compare_serve(prog, ref, p0, [False, True])
    assert out["params_gap"] == 0.0 and out["loss_gap"] == 0.0
    assert out["params_gap_long"] == pytest.approx(0.5)
    assert out["mask_mismatch"] == 0.0 and out["queue_gap"] == 0.0
    # but its masks and queues are compared
    prog[1] = dict(prog[1], success=np.ones((3, 2), bool),
                   queue=np.full(5, 2.0))
    out = checks.compare_serve(prog, ref, p0, [False, True])
    assert out["mask_mismatch"] == 6.0 and out["queue_gap"] == 1.0
    assert "params_gap_long" not in checks.compare_serve(
        prog[:1], ref[:1], p0, [False])


def test_serve_pick_takes_short_sessions_and_the_most_served():
    driver = Registry(REPO, BENCH).driver("serve_open")
    traffic = {"check_history_rounds": 4, "check_rounds": 5,
               "check_long_rounds": 12}
    cell = driver.build({}, None, traffic, 3)
    served = {"a": [8, 2], "b": [4, 4, 1], "c": [1, 1], "d": [2], "e": [1],
              "f": [4, 1]}
    cell.reqs = [{"session": s, "n_rounds": n}
                 for s, ns in served.items() for n in ns]
    picked = cell._pick()
    long = [s for s, lg in picked if lg]
    short = [s for s, lg in picked if not lg]
    # most-served first while 12 rounds last: a (10), then b (9) no more
    assert long == ["a"]
    # the short ones start with the longest request (d), fit in 5 rounds
    assert short[0] == "d" and set(short) <= {"c", "d", "e"}
    assert sum(sum(served[s]) for s in short) <= 5
    cell.traffic = dict(traffic, check_long_rounds=30)
    assert [s for s, lg in cell._pick() if lg] == ["a", "b", "f"]


# ---------------------------------------------------- operations, bytes

def test_cnn_flops_match_the_hand_count():
    cfg = json.loads((BENCH / "configs" / "veds_cnn_paper.json")
                     .read_text())
    m = cfg["model"]
    cnn6 = Registry(REPO, BENCH).model(m["name"])
    fwd = cnn6.forward_flops(m)
    convs = [2 * 32 * 32 * 9 * 3 * 32, 2 * 32 * 32 * 9 * 32 * 32,
             2 * 16 * 16 * 9 * 32 * 64, 2 * 16 * 16 * 9 * 64 * 64,
             2 * 8 * 8 * 9 * 64 * 128, 2 * 8 * 8 * 9 * 128 * 128]
    assert fwd == sum(convs) + 2 * 2048 * 10
    assert fwd == pytest.approx(77.3e6, rel=1e-3)
    assert cnn6.train_flops_per_sample(m) == 3 * fwd
    # one cell-round: S = 10 clients x a minibatch of 32
    assert flops.cell_round_flops(cfg, cnn6) == pytest.approx(74.2e9,
                                                              rel=1e-3)


@pytest.mark.parametrize("n,tiles", [(10, 128), (128, 128), (129, 256),
                                     (1024, 1024), (2560, 3072)])
def test_veds_score_tiles_and_bytes(n, tiles):
    assert flops.veds_score_tiles(n) == tiles
    cost = flops.veds_score_cost(n)
    # reads gain, queue, weight (f32) and eligibility (1 byte), writes
    # objective, power and bits (f32)
    assert cost["bytes"] == tiles * 25
    assert cost["flops"] == tiles * flops.VEDS_SCORE_OPS_PER_CANDIDATE


def test_mfu_reader_counts_every_chip():
    reg = Registry(REPO, BENCH)
    cfg = json.loads((BENCH / "configs" / "madca_cnn_paper.json")
                     .read_text())
    model = reg.model(cfg["model"]["name"])
    run = types.SimpleNamespace(
        cfg=cfg, model=model, window={"cell_rounds": 100},
        trace={"window_s": 2.0}, chips=4, peaks={"bf16_flops_per_s": 1e15})
    want = 100 * flops.cell_round_flops(cfg, model) / 2.0 / 4e15 * 100
    assert reg.reader("mfu").read(run) == pytest.approx(want)


# ------------------------------------------------------------ schedule

TRAFFIC = {"rate_hz": 10.0, "schedule_seed": 1, "rounds": [1, 2, 4, 8],
           "round_weights": [8, 4, 2, 1], "sessions": 64, "zipf_s": 1.0}


def test_open_loop_is_fixed_by_the_traffic_and_the_seed():
    """The traffic file fixes when requests are due, for which session
    and how many rounds; the run's seed fixes what each asks for. The
    schedule never sees the service, so its speed cannot move it."""
    a = schedule.open_loop(2 ** 33 + 5, 30.0, TRAFFIC)
    assert a == schedule.open_loop(2 ** 33 + 5, 30.0, TRAFFIC)
    b = schedule.open_loop(2 ** 33 + 6, 30.0, TRAFFIC)
    assert [q["seed"] for q in a] != [q["seed"] for q in b]
    for key in ("due_s", "session", "n_rounds"):
        assert [q[key] for q in a] == [q[key] for q in b]
    c = schedule.open_loop(2 ** 33 + 5, 30.0, dict(TRAFFIC, schedule_seed=2))
    assert [q["due_s"] for q in a] != [q["due_s"] for q in c]
    assert len(a) == 300
    due = [q["due_s"] for q in a]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30.0
    assert all(0 <= q["seed"] < 2 ** 31 for q in a)


def test_open_loop_is_the_mix_of_its_traffic_file():
    """Gaps are the quantiles of an exponential at the rate, round
    counts and sessions exact shares of their weights."""
    a = schedule.open_loop(7, 30.0, TRAFFIC)
    gaps = np.r_[np.diff([q["due_s"] for q in a]), 30.0 - a[-1]["due_s"]]
    assert gaps.sum() == pytest.approx(30.0)
    assert np.median(gaps) == pytest.approx(np.log(2) / 10.0, rel=0.05)
    counts = np.bincount([q["n_rounds"] for q in a])
    assert [counts[1], counts[2], counts[4], counts[8]] == [160, 80, 40, 20]
    per = np.bincount([int(q["session"].split("-")[1]) for q in a])
    assert per[0] == max(per) and per.sum() == 300


def test_shares_and_zipf():
    assert schedule.shares([8, 4, 2, 1], 15).tolist() == [8, 4, 2, 1]
    assert schedule.shares([1, 1, 1], 10).sum() == 10
    w = schedule.zipf_weights(4, 1.0)
    np.testing.assert_allclose(w, [1, 1 / 2, 1 / 3, 1 / 4])


# ------------------------------------------------------------- registry

def test_registry_finds_pieces_from_files_alone(tmp_path):
    """A new configuration, model, traffic mix, cell and metric are files
    and entries: nothing that is there is edited."""
    bench = tmp_path / "b"
    for sub in ("configs", "models", "traffic", "limits", "drivers",
                "metrics"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs" / "m.json").write_text(
        '{"n_sov": 3, "model": {"name": "toy", "width": 4}}')
    (bench / "models" / "toy.py").write_text(
        "def weights(key, m):\n    return [key] * m['width']\n")
    (bench / "traffic" / "t.json").write_text('{"driver": "d", "x": 1}')
    (bench / "limits" / "m.t.json").write_text('{"limits": {"a": 0}}')
    (bench / "drivers" / "d.py").write_text(
        "def build(cfg, model, traffic, seed, fault=''):\n"
        "    return (cfg['n_sov'], model.weights(0, cfg['model']),\n"
        "            traffic['x'], seed)\n")
    (bench / "metrics" / "q.share.py").write_text(
        "def read(run):\n    return run\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "m", "file": "b/configs/m.json"}],
        "workloads": [{"name": "m.t", "config": "m", "traffic": "t",
                       "chips": 1}],
        "end_to_end": [{"name": "e", "unit": "s"}],
        "per_layer": [{"name": "q.share", "unit": "%",
                       "workloads": ["m.t"]},
                      {"name": "other", "unit": "%", "workloads": ["x"]}]}))
    reg = Registry(tmp_path, bench)
    w = reg.workload("m.t")
    cfg, tr = reg.config(w["config"]), reg.traffic(w["traffic"])
    model = reg.model(cfg["model"]["name"])
    assert reg.driver(tr["driver"]).build(cfg, model, tr, 7) == \
        (3, [0, 0, 0, 0], 1, 7)
    assert [m["name"] for m in reg.metrics_of("m.t", "per_layer")] == \
        ["q.share"]
    assert [m["name"] for m in reg.metrics_of("m.t", "end_to_end")] == ["e"]
    assert reg.reader("q.share").read(5) == 5
    assert reg.limits("m.t") == {"a": 0}
    with pytest.raises(RegistryError):
        reg.workload("nope")
    with pytest.raises(RegistryError):
        reg.reader("nope")
    with pytest.raises(RegistryError):
        reg.model("nope")


def test_benchmark_json_keeps_to_its_contract():
    spec = real_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= spec["run_seconds"] <= 51
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
        reported = [m for m in spec["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2
    for m in spec["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        moved = next(x for x in spec["end_to_end"] if x["name"] == m["moves"])
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w])


# ----------------------------------------------------------------- runs

def test_last_line_has_the_contract_keys(tiny):
    reg = tiny("madca_cnn.grid16")
    result, lines = run_tiny(reg, "madca_cnn.grid16")
    assert result["correct"] is True
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["metrics"]) == {"cell_rounds_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    # the numbers compared close the standard error, each by its limit
    assert lines[-len(result["checks"]):] == [
        f"check {k}: {v['value']!r} limit {v['limit']!r}"
        for k, v in result["checks"].items()]
    assert any(line.startswith("compiles_in_window: 0") for line in lines)
    json.dumps(result, allow_nan=False)


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "veds_cnn.train1", "--seed", str(2 ** 33), "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr
