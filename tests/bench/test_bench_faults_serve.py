"""The serving cell's whole run on the CPU, past the look for a chip,
with the service broken underneath: `correct` has to come out false for
each fault the cell can have, and true for the sound service."""
from __future__ import annotations

import pytest

from benchkit import run_tiny, tiny  # noqa: F401

CELL = "madca_cnn.serve_steady"


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "flip"])
def test_a_broken_service_is_not_correct(tiny, fault):
    """A session's state left as it was before each request; half of
    each minibatch left out; one upload decision of an answer altered
    where it is produced."""
    result, lines = run_tiny(tiny(CELL), CELL, fault=fault, seconds=1.0)
    assert result["attempted"] == 20
    assert result["correct"] is False, result["checks"]


def test_the_sound_service_is_correct(tiny):
    result, lines = run_tiny(tiny(CELL), CELL, seconds=1.0)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"serve_p95_ms", "serve_p50_ms",
                                      "setup_s"}
    assert any(line.startswith("generator_late_p95_ms: ") for line in lines)
