"""Offered-load sweep of a serving cell, to find the highest rate it
sustains (the knee) once, on the chip.

  python benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
      --rates 4,8,16 --seconds 20 [--out <file.jsonl>]

One process: the cell's set-up once, then for each rate in turn the
cell's open loop at that rate for `--seconds`. Per rate it prints the
latency percentiles and whether the backlog grew: the mean number of
requests due but unanswered over the last quarter of the schedule
against the first quarter; the sweep stops at the first rate whose
backlog grows. The benchmark's own runs never run this; the
rate a cell runs at is a number in its traffic file.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parents[1]
# a backlog this many times larger at the end than at the start grows
GROWTH = 3.0


def backlog(reqs, results, t_end: float):
    """Requests due but unanswered at each due time."""
    due = np.array([q["due_s"] for q in reqs])
    done = np.array([q["due_s"] + r["latency_s"] if "latency_s" in r
                     else t_end for q, r in zip(reqs, results)])
    return np.array([(due <= t).sum() - (done <= t).sum() for t in due])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    for p in (str(BENCH_DIR), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax

    from chipbench.registry import Registry

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    reg = Registry(CHECKOUT, BENCH_DIR)
    work = reg.workload(args.workload)
    traffic = reg.traffic(work["traffic"])
    cfg = reg.config(work["config"])
    cell = reg.driver(traffic["driver"]).build(
        cfg, reg.model(cfg["model"]["name"]), traffic, args.seed)
    cell.setup()
    out = open(args.out, "a") if args.out else None
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            cell.traffic = dict(traffic, rate_hz=rate)
            win = cell.window(args.seconds)
            q = backlog(cell.reqs, cell.results, win["seconds"])
            k = max(1, len(q) // 4)
            row = {"workload": args.workload, "rate_hz": rate,
                   "requests": win["attempted"], "failed": win["failed"],
                   "window_s": win["seconds"],
                   "backlog_first_quarter": float(q[:k].mean()),
                   "backlog_last_quarter": float(q[-k:].mean()),
                   "generator_late_p95_ms": win["generator_late_p95_ms"],
                   "occupancy_mean": win["occupancy_mean"],
                   **win["metrics"]}
            print(json.dumps(row), flush=True)
            if out:
                out.write(json.dumps(row) + "\n")
                out.flush()
            if row["backlog_last_quarter"] > GROWTH * max(
                    row["backlog_first_quarter"], 1.0):
                break               # past the knee: higher rates only queue
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
