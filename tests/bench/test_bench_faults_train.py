"""A training cell's whole run on the CPU, past the look for a chip, with
the timed path broken underneath: `correct` has to come out false for
each fault the cell can have, and true for the sound program."""
from __future__ import annotations

import pytest

from benchkit import run_tiny, tiny  # noqa: F401

CELL = "madca_cnn.grid16"


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "flip",
                                   "no_handoff"])
def test_a_broken_step_is_not_correct(tiny, fault):
    """A step that returns its state unchanged; half of each minibatch
    left out; one upload decision altered where it is produced; the
    exchange of vehicles between cells left out."""
    result, lines = run_tiny(tiny(CELL), CELL, fault=fault)
    assert result["correct"] is False, result["checks"]
    failed = [k for k, v in result["checks"].items()
              if v["value"] > v["limit"]]
    assert failed


def test_the_sound_step_is_correct(tiny):
    result, _ = run_tiny(tiny(CELL), CELL)
    assert result["correct"] is True, result["checks"]
