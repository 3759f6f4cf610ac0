"""The comparison that decides `correct`, and its numbers.

A training cell compares the first checked rounds of the timed program
with the plain reference on the same inputs:

  mask_mismatch   success-mask entries that differ (which SOVs uploaded)
  energy_gap      largest gap of an SOV or OPV round energy, over the
                  largest reference energy of that round's array
  queue_gap       the same for the virtual energy queues after the round
  loss_gap        largest relative gap of a cell's round loss
  grad1_gap       the first gradient as the optimizer applied it, worked
                  out from the weights after round 1: per leaf, the gap
                  between the program's norm and the reference's, over
                  the larger of the reference's norm of that leaf and of
                  the median leaf; the worst leaf of any cell
  step3_gap       the same for the change of the weights over the
                  checked rounds
  grad1_diff      the first gradient again, per leaf the norm of the
                  difference of the two vectors (so also a gradient of
                  the right size that points elsewhere), over the same
                  scale; the worst leaf of any cell
  step3_diff      the same for the change of the weights
  fleet_mismatch  (handoff cells) vehicles whose cell or coverage flag
                  differs after the checked rounds

A serving cell replays sampled sessions through every request they
were served: sessions with a short history and the most-served ones
with their whole history. It compares mask_mismatch over the served
rounds and queue_gap over the queues each session holds afterwards, of
both, and over the short ones loss_gap and

  params_gap      the change of each session's weights over the window,
                  measured as step3_gap is
  params_gap_long the same over the most-served sessions

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of the gradient and weight numbers: they move by
round-off.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

ORDER = ("mask_mismatch", "energy_gap", "queue_gap", "loss_gap",
         "grad1_gap", "grad1_diff", "step3_gap", "step3_diff", "params_gap",
         "params_gap_long", "fleet_mismatch")


def _leaves(tree) -> List[np.ndarray]:
    import jax
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def array_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """max |prog - ref| over max |ref| of one array (0 when both are 0)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if not np.isfinite(prog).all():
        return math.inf
    scale = np.abs(ref).max(initial=0.0)
    diff = np.abs(prog - ref).max(initial=0.0)
    return 0.0 if diff == 0 else diff / scale if scale > 0 else math.inf


def norm_gap(prog_leaves: Sequence[np.ndarray],
             ref_leaves: Sequence[np.ndarray], diff: bool = False) -> float:
    """Worst leaf's ||prog| - |ref|| (with `diff`: |prog - ref|) over
    max(|ref|, median |ref|), over the leaves whose reference norm is at
    least a thousandth of the median's."""
    rn = np.array([np.linalg.norm(r) for r in ref_leaves])
    pn = np.array([np.linalg.norm(p) for p in prog_leaves])
    if not np.isfinite(pn).all():
        return math.inf
    gap = np.array([np.linalg.norm(p - r) for p, r in
                    zip(prog_leaves, ref_leaves)]) if diff else \
        np.abs(pn - rn)
    med = float(np.median(rn))
    keep = rn >= 1e-3 * med
    if med == 0 or not keep.any():
        return 0.0 if np.allclose(pn, 0) else math.inf
    return float(np.max(gap[keep] / np.maximum(rn, med)[keep]))


def compare_training(prog: Dict, ref: Dict, p0, lr: float,
                     handoff: bool) -> Dict[str, float]:
    """`prog` and `ref` hold the checked rounds: success/energy_sov/
    energy_opv/qs/qu/loss [R, B, ...], params[r][b] the weights after
    round r, and the final fleet ({"cell", "covered"} [B, N])."""
    R = ref["success"].shape[0]
    out = {"mask_mismatch": float(
        (np.asarray(prog["success"]) != ref["success"]).sum())}
    out["energy_gap"] = max(
        array_gap(prog[k][r], ref[k][r])
        for k in ("energy_sov", "energy_opv") for r in range(R))
    out["queue_gap"] = max(array_gap(prog[k][r], ref[k][r])
                           for k in ("qs", "qu") for r in range(R))
    lp, lr_ = np.asarray(prog["loss"], np.float64), ref["loss"]
    both_nan = np.isnan(lp) & np.isnan(lr_)
    rel = np.abs(lp - lr_) / np.maximum(np.abs(lr_), 1e-12)
    out["loss_gap"] = float(np.where(both_nan, 0.0, np.nan_to_num(
        rel, nan=math.inf)).max())
    base = _leaves(p0)
    gaps = {k: [] for k in ("grad1_gap", "grad1_diff", "step3_gap",
                            "step3_diff")}
    for b in range(ref["success"].shape[1]):
        first_p = [(a - x) / lr for a, x in zip(
            base, _leaves(prog["params"][0][b]))]
        first_r = [(a - x) / lr for a, x in zip(
            base, _leaves(ref["params"][0][b]))]
        step_p = [x - a for a, x in zip(base, _leaves(prog["params"][-1][b]))]
        step_r = [x - a for a, x in zip(base, _leaves(ref["params"][-1][b]))]
        for name, p, r in (("grad1", first_p, first_r),
                           ("step3", step_p, step_r)):
            gaps[name + "_gap"].append(norm_gap(p, r))
            gaps[name + "_diff"].append(norm_gap(p, r, diff=True))
    out.update({k: max(v) for k, v in gaps.items()})
    if handoff:
        pf, rf = prog["fleet"], ref["fleet"]
        out["fleet_mismatch"] = float(
            ((np.asarray(pf["cell"]) != rf["cell"])
             | (np.asarray(pf["covered"]) != rf["covered"])).sum())
    return out


def compare_serve(prog: List[Dict], ref: List[Dict], p0,
                  long: Sequence[bool]) -> Dict[str, float]:
    """Sampled sessions of a served run, each replayed by the reference
    from its creation through every request it was served: `success`
    [R, S] and `loss` [R] over the session's rounds in order, the final
    weights `params` and the virtual queues `queue` [N] the session
    holds after the window. `long[i]` marks a most-served session."""
    base = _leaves(p0)
    out = {"mask_mismatch": float(sum(
        (np.asarray(p["success"]) != r["success"]).sum()
        for p, r in zip(prog, ref)))}
    out["queue_gap"] = max(array_gap(p["queue"], r["queue"])
                           for p, r in zip(prog, ref))
    short = [(p, r) for p, r, lg in zip(prog, ref, long) if not lg]
    rel = [np.abs(np.asarray(p["loss"], np.float64) - r["loss"])
           / np.maximum(np.abs(r["loss"]), 1e-12) for p, r in short]
    out["loss_gap"] = float(np.nan_to_num(np.concatenate(rel),
                                          nan=math.inf).max())

    def params_gap(pairs):
        return max(norm_gap(
            [x - a for a, x in zip(base, _leaves(p["params"]))],
            [x - a for a, x in zip(base, _leaves(r["params"]))])
            for p, r in pairs)
    out["params_gap"] = params_gap(short)
    if any(long):
        out["params_gap_long"] = params_gap(
            [(p, r) for p, r, lg in zip(prog, ref, long) if lg])
    return out


def judge(values: Dict[str, float],
          limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict]]:
    """Every number that has a limit against it (value <= limit passes);
    a number the cell's limits leave out is not compared. Returns
    (correct, {name: {"value", "limit"}}) in a fixed order."""
    table, ok = {}, True
    for name in [n for n in ORDER if n in limits] + sorted(
            set(limits) - set(ORDER)):
        if name not in values:
            raise KeyError(f"the check gave no {name}")
        v, lim = float(values[name]), limits[name]
        table[name] = {"value": v, "limit": lim}
        ok &= bool(v <= lim)
    return ok, table
