"""Operations and bytes of the timed work, counted from shapes."""
from __future__ import annotations

from typing import Dict, Sequence

# The DT-score kernel, per candidate: the closed-form power (divide,
# reciprocal, two clips, a multiply-subtract), the rate (a log1p and
# three multiplies), the bits and the objective (five multiplies and a
# subtract), the mask (a compare, an and, three selects): 20 operations.
VEDS_SCORE_OPS_PER_CANDIDATE = 20
LANES = 128
BLOCK_ROWS = 8


def cnn_forward_flops(channels: Sequence[int], image: Sequence[int],
                      classes: int, kernel: int = 3) -> int:
    """Multiply-adds x 2 of one image through the six 3x3 SAME convs
    (2x2 pooling after every pair) and the linear head."""
    h, w, cin = image
    total = 0
    for i, cout in enumerate(channels):
        total += 2 * h * w * kernel * kernel * cin * cout
        cin = cout
        if i % 2 == 1:
            h, w = h // 2, w // 2
    return total + 2 * h * w * cin * classes


def cnn_train_flops(cfg: Dict) -> int:
    """Forward and backward of one image: three times the forward."""
    m = cfg["model"]
    return 3 * cnn_forward_flops(m["channels"], m["image"], m["classes"])


def cell_round_flops(cfg: Dict) -> int:
    """One cell-round trains S clients on a minibatch each."""
    return cfg["n_sov"] * cfg["batch_size"] * cnn_train_flops(cfg)


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
