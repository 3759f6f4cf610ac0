"""The CNN's cells through the model-file seam reproduce, bit for bit,
what the harness computed while the CNN was built into it: each tiny
cell's `correct` numbers and the reference's per-round losses, masks and
queues, at one fixed seed, recorded on the CPU in
`golden_cnn6_tiny.json` before the CNN moved to `models/cnn6.py`."""
from __future__ import annotations

import json

import numpy as np
import pytest

from benchkit import REPO, tiny  # noqa: F401

GOLDEN = REPO / "tests" / "bench" / "golden_cnn6_tiny.json"
SEED = 2 ** 31 + 12345


def readings(cell) -> dict:
    """The cell's check numbers and its float32 reference's outputs:
    per round of a training cell; per replayed session of a serving
    cell."""
    def host(x):
        return np.asarray(x).tolist()
    checks = {k: float(v) for k, v in cell.check().items()}
    if hasattr(cell, "_ref"):
        ref = {k: host(cell._ref[k]) for k in ("loss", "success", "qs",
                                               "qu")}
    else:
        ref = {s: {k: host(v) for k, v in r.items()
                   if k in ("loss", "success", "queue")}
               for (s, _), r in sorted(cell._replays.items())}
    return json.loads(json.dumps({"checks": checks, "reference": ref}))


@pytest.mark.parametrize("workload,seconds", [
    ("veds_cnn.train1", 0.5), ("madca_cnn.grid16", 0.5),
    ("madca_cnn.serve_steady", 1.0)])
def test_cnn6_cells_reproduce_their_recording(tiny, workload, seconds):
    reg = tiny(workload)
    w = reg.workload(workload)
    traffic = reg.traffic(w["traffic"])
    cfg = reg.config(w["config"])
    cell = reg.driver(traffic["driver"]).build(
        cfg, reg.model(cfg["model"]["name"]), traffic, SEED)
    cell.setup()
    cell.window(seconds)
    cell.release()
    assert readings(cell) == json.loads(GOLDEN.read_text())[workload]
