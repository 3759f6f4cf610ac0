"""The control of `correct`: the plain reference computed in bfloat16,
put in the program's place, has to come out as not correct under each
cell's own limits, at a size a CPU test can hold. On the chip the same
comparison runs at the cells' own sizes (`calibrate.py --control`)."""
from __future__ import annotations

import json

import pytest

from benchkit import BENCH, run_tiny, tiny  # noqa: F401

from chipbench.checks import judge


def real_limits(cell: str):
    return json.loads((BENCH / "limits" / f"{cell}.json").read_text())[
        "limits"]


@pytest.mark.parametrize("cell,seconds", [("madca_cnn.grid16", 0.5),
                                          ("madca_cnn.serve_steady", 1.0)])
def test_the_bfloat16_control_is_not_correct(tiny, cell, seconds):
    reg = tiny(cell)
    w = reg.workload(cell)
    traffic = reg.traffic(w["traffic"])
    cfg = reg.config(w["config"])
    c = reg.driver(traffic["driver"]).build(
        cfg, reg.model(cfg["model"]["name"]), traffic, 2 ** 32 + 99)
    c.setup()
    c.window(seconds)
    c.release()
    sound, _ = judge(c.check(), reg.limits(cell))
    control, table = judge(c.check(control=True), real_limits(cell))
    assert sound is True
    assert control is False, table
