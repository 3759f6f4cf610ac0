"""A model other than the CNN joins the benchmark by files alone: a tiny
checkout gains a model file over token sequences (`toy_models/tokmlp.py`),
a configuration naming it, traffic, limits and the entries of its cells,
and the harness drives it through both kinds of traffic, with nothing it
copied edited."""
from __future__ import annotations

import hashlib
import json
import shutil
from pathlib import Path

import pytest

from benchkit import CPU_GAP, run_tiny, tiny_bench

TOY = Path(__file__).resolve().parent / "toy_models" / "tokmlp.py"
TOY_MODEL = {"name": "tokmlp", "vocab": 32, "seq": 6, "embed": 4,
             "hidden": 16, "classes": 10}
TRAIN_LIMITS = {k: CPU_GAP for k in ("energy_gap", "queue_gap", "loss_gap",
                                     "grad1_gap", "grad1_diff",
                                     "step3_gap")}
SERVE_LIMITS = {"queue_gap": CPU_GAP, "params_gap": CPU_GAP,
                "unanswered": 0}
# each toy cell: the traffic it copies and the metrics it reports
CELLS = {"tok_train": ("train1", TRAIN_LIMITS, ["cell_rounds_per_s"]),
         "tok_serve": ("serve_steady", SERVE_LIMITS,
                       ["serve_p95_ms", "serve_p50_ms"])}


def digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def add_toy(root: Path, bench: Path) -> None:
    """The files and entries a model's PR brings: models/, configs/,
    traffic/ and limits/ files, a configuration and two cells."""
    shutil.copy(TOY, bench / "models" / "tokmlp.py")
    cfg = json.loads((bench / "configs" / "madca_cnn_paper.json")
                     .read_text())
    (bench / "configs" / "madca_tok.json").write_text(
        json.dumps(dict(cfg, model=TOY_MODEL)))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "madca_tok", "source": "toy",
        "file": str((bench / "configs" / "madca_tok.json")
                    .relative_to(root)),
        "reduced": [], "why": "a model over token sequences"})
    for traffic, (base, limits, metrics) in CELLS.items():
        shutil.copy(bench / "traffic" / f"{base}.json",
                    bench / "traffic" / f"{traffic}.json")
        name = f"madca_tok.{traffic}"
        (bench / "limits" / f"{name}.json").write_text(json.dumps(
            {"limits": dict(limits, mask_mismatch=0)}))
        spec["workloads"].append({"name": name, "config": "madca_tok",
                                  "traffic": traffic, "chips": 1,
                                  "why": "toy"})
        for m in spec["end_to_end"]:
            if m["name"] in metrics:
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("traffic,fault,correct", [
    ("tok_train", "", True), ("tok_serve", "", True),
    ("tok_train", "half_batch", False)])
def test_a_model_added_by_files_alone_runs(tmp_path, traffic, fault,
                                           correct):
    import jax
    import jax.numpy as jnp
    from chipbench.registry import Registry
    bench = tiny_bench(tmp_path, ["veds_cnn.train1",
                                  "madca_cnn.serve_steady"])
    before = digests(bench)
    add_toy(tmp_path, bench)
    reg = Registry(tmp_path, bench)
    model = reg.model("tokmlp")
    data, _ = model.shards(jax.random.key(0), 2, reg.traffic(traffic),
                           TOY_MODEL)
    # leaves of another rank and dtype than the CNN's images
    assert data["tok"].ndim == 3 and data["tok"].dtype == jnp.int32

    cell = f"madca_tok.{traffic}"
    result, _ = run_tiny(reg, cell, fault=fault,
                         seconds=1.0 if traffic == "tok_serve" else 0.5)
    assert result["correct"] is correct, result["checks"]
    if correct:
        assert result["failed"] == 0
        assert set(result["metrics"]) == set(CELLS[traffic][2]) | {"setup_s"}
    after = digests(bench)
    assert {k: after[k] for k in before} == before
