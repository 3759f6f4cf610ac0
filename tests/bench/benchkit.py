"""Helpers of the chip benchmark's CPU tests: the harness on the path,
and tiny copies of its cells built from the real files, changed only in
size, so that a whole run fits a CPU test."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

# the real configurations at test size: widths, depth and scenario cut
TINY_CONFIG = {"model": {"name": "cnn6", "channels": [4, 4, 8, 8, 8, 8],
                         "image": [8, 8, 3], "classes": 10, "flat": 8},
               "n_sov": 4, "n_opv": 3, "n_slots": 10, "batch_size": 8}
TINY_TRAFFIC = {
    "train1": {"clients_per_cell": 8, "samples_per_client": 20,
               "segment_rounds": 3},
    "grid16": {"cells": 4, "clients_per_cell": 8, "samples_per_client": 20,
               "segment_rounds": 3},
    "serve_steady": {"clients": 8, "samples_per_client": 20, "batch": 2,
                     "tiers": [2], "batch_tiers": [2], "sessions": 4,
                     "rounds": [1, 2], "round_weights": [2, 1],
                     "rate_hz": 20.0, "check_rounds": 8,
                     "check_history_rounds": 6, "check_long_rounds": 16},
}


# On the CPU the program and the reference agree to float32 rounding
# (~1e-7); the gaps the cells' limits allow for come from the chip's
# one-pass bfloat16 convolutions. So the tiny cells hold every number to
# the cell's limit or this, whichever is less.
CPU_GAP = 0.01


def real_spec():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def tiny_bench(root: Path, workloads) -> Path:
    """A checkout under `root` holding the named cells of BENCHMARK.json
    at test size: their configurations, traffic and limits (at most
    `CPU_GAP`), with the real drivers, model files and metric readers.
    Returns the benchmark directory."""
    spec = real_spec()
    bench = root / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    for sub in ("drivers", "metrics", "models"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    works = [w for w in spec["workloads"] if w["name"] in workloads]
    configs = []
    for c in spec["configs"]:
        if any(w["config"] == c["name"] for w in works):
            cfg = json.loads((REPO / c["file"]).read_text())
            cfg.update(TINY_CONFIG)
            path = bench / "configs" / f"{c['name']}.json"
            path.write_text(json.dumps(cfg))
            configs.append(dict(c, file=str(path.relative_to(root))))
    for w in works:
        tr = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                        .read_text())
        tr.update(TINY_TRAFFIC[w["traffic"]])
        (bench / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(tr))
        limits = json.loads((BENCH / "limits" / f"{w['name']}.json")
                            .read_text())
        limits["limits"] = {k: min(v, CPU_GAP) for k, v in
                            limits["limits"].items()}
        (bench / "limits" / f"{w['name']}.json").write_text(
            json.dumps(limits))
    (root / "BENCHMARK.json").write_text(json.dumps(
        dict(spec, configs=configs, workloads=works)))
    return bench


@pytest.fixture
def tiny(tmp_path):
    """`tiny(*workloads)` -> a Registry over a tiny copy of those cells."""
    from chipbench.registry import Registry

    def make(*workloads):
        return Registry(tmp_path, tiny_bench(tmp_path, workloads))
    return make


def run_tiny(reg, workload: str, fault: str = "", seconds: float = 0.5,
             seed: int = 2 ** 31 + 12345):
    """The harness's run of one tiny cell on this process's CPU, past its
    look for a chip: set-up, window, release and check."""
    import time

    import jax
    from chipbench.registry import load_module
    harness = load_module(BENCH / "run.py", "chipbench_run_")
    peaks = {jax.devices()[0].device_kind: {"bf16_flops_per_s": 1e12,
                                            "hbm_bytes_per_s": 1e11}}
    return harness.run_cell(reg, workload, seed, seconds, False,
                            jax.devices()[:1], peaks, time.perf_counter(),
                            fault)
