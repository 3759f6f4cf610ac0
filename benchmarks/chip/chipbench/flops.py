"""Operations and bytes of the timed work, counted from shapes. A
model's own count comes from its model file (`models/<model>.py`)."""
from __future__ import annotations

from typing import Dict

# The DT-score kernel, per candidate: the closed-form power (divide,
# reciprocal, two clips, a multiply-subtract), the rate (a log1p and
# three multiplies), the bits and the objective (five multiplies and a
# subtract), the mask (a compare, an and, three selects): 20 operations.
VEDS_SCORE_OPS_PER_CANDIDATE = 20
LANES = 128
BLOCK_ROWS = 8


def cell_round_flops(cfg: Dict, model) -> int:
    """One cell-round trains S clients on a minibatch each; `model` is
    the configuration's model file, which counts a sample's forward and
    backward."""
    return cfg["n_sov"] * cfg["batch_size"] * \
        model.train_flops_per_sample(cfg["model"])


def veds_score_tiles(n_candidates: int) -> int:
    """Candidates the kernel touches: the count padded to [rows, 128]
    tiles, rows a multiple of 8 once they exceed one block."""
    rows = max(1, -(-n_candidates // LANES))
    block = min(BLOCK_ROWS, rows)
    return -(-rows // block) * block * LANES


def veds_score_cost(n_candidates: int) -> Dict[str, int]:
    """Operations and HBM bytes of one kernel call: reads gain, queue
    and weight (f32) and eligibility (bool, one byte), writes objective,
    power and bits (f32)."""
    n = veds_score_tiles(n_candidates)
    return {"flops": VEDS_SCORE_OPS_PER_CANDIDATE * n,
            "bytes": n * (3 * 4 + 1 + 3 * 4)}
