"""Readings that the limits of `correct` are set from, on the chip.

  python benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \\
      [--control 3] [--fault half_batch:3] [--seconds 0] [--out <f.jsonl>]

For each seed, in one process: the cell's set-up (which drives the
timed program through the checked rounds of a training cell), a window
of `--seconds` at the cell's own load where the cell checks what its
window served, and the comparison with the float32 reference.
`--control n` also reads the control, the reference run in bfloat16 in
the program's place, on the first n seeds; `--fault name:n` plants a
fault of the driver's in the program on the first n seeds. One JSON
line per reading goes to standard output and to `--out`. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
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
    cfg = reg.config(work["config"])
    traffic = reg.traffic(work["traffic"])
    driver = reg.driver(traffic["driver"])
    model = reg.model(cfg["model"]["name"])
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = [(s, "", i < args.control) for i, s in enumerate(seeds)]
    for f in args.fault:
        name, n = f.split(":")
        runs += [(s, name, False) for s in seeds[:int(n)]]
    out = open(args.out, "a") if args.out else None
    try:
        for seed, fault, control in runs:
            t0 = time.perf_counter()
            cell = driver.build(cfg, model, traffic, seed, fault)
            cell.setup()
            if args.seconds:
                cell.window(args.seconds)
            jax.block_until_ready(getattr(cell, "carry", None))
            t1 = time.perf_counter()
            cell.release()
            readings = [(fault or "program", cell.check())]
            jax.block_until_ready(readings)
            t2 = time.perf_counter()
            if control:
                readings.append(("control", cell.check(control=True)))
            for variant, values in readings:
                row = {"workload": args.workload, "seed": seed,
                       "variant": variant, "values": values,
                       "setup_s": t1 - t0, "check_s": t2 - t1}
                print(json.dumps(row), flush=True)
                if out:
                    out.write(json.dumps(row) + "\n")
                    out.flush()
            del cell
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
