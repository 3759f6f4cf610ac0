"""One traced run of a cell, read layer by layer: what the per-layer
metrics read, and what they leave out.

  python3 benchmarks/chip/layers.py --workload <cell> --seed <n> \\
      --out <file.json> [--seconds <s>]

Runs the cell as `run.py --trace 1` does, and its last line of standard
output is the same result object. It also keeps what the harness's
trace reduction leaves out (`chipbench.scopes.extract_program`: the
program's `repro.` spans and each device's module executions) and
writes to `--out`:

- `layers`: device time of each program layer in the traced window (s),
  the busy time, the share of it the layers cover, the self-time
  readings times the cell-rounds against that covered time, and the
  operations that took most time in each layer and outside every layer;
- `kernel`: the DT-score kernel's calls and device time;
- `serve` (a serving cell): the device idle time inside the program's
  `repro.serve.run_batch` spans, split by their child spans; the same
  mean per dispatch as the reader's `serve_host_ms_per_dispatch`; and
  how far each execution of the service's segment program starts
  outside the `run_batch` span around it;
- `scope_tables_s`: the time each `repro.obs.scope_tables` call took,
  and `memory_plan`: the compiler's buffers of every registered program;
- `window`: the traced window's own counts and end-to-end numbers (with
  the profiler on), beside an untraced run's at the same seed.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parents[1]
SEGMENT_MODULE = "jit_seg"


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out", required=True)
    return ap.parse_args(argv)


def _top(counter, n=5):
    return [[k, v * 1e-9] for k, v in counter.most_common(n)]


def layer_report(tr, window, cell_rounds, metrics, scopes, T) -> dict:
    """Device time by layer, from the program's tables."""
    layers, table = scopes.program_scopes()
    lo, hi = window
    per = collections.Counter()
    ops = collections.defaultdict(collections.Counter)
    for dev in tr["devices"].values():
        for s, d, name, op in dev:
            dd = min(s + d, hi) - max(s, lo)
            if T.is_container(name) or dd <= 0:
                continue
            layer = table.get(scopes.instruction(name), "(no layer)")
            per[layer] += dd
            ops[layer][T.op_label(name, op)] += dd
    busy = sum(per.values())
    covered = busy - per["(no layer)"]
    read = sum(metrics[f"self_ms.{x}"]["value"] for x in layers
               if f"self_ms.{x}" in metrics)
    return {"seconds": {k: v * 1e-9 for k, v in per.items()},
            "busy_s": busy * 1e-9, "covered_share": covered / busy,
            "readings_times_cell_rounds_s": read * cell_rounds * 1e-3,
            "covered_s": covered * 1e-9, "cell_rounds": cell_rounds,
            "top_ops": {k: _top(v) for k, v in ops.items()}}


def serve_report(tr, prog, window, scopes, T) -> dict:
    """The device idle time inside each `serve.run_batch` by child span,
    and where the segment program's executions start."""
    lo, hi = window
    runs = [[s, s + d] for s, d, name in prog["program_spans"]
            if name == "serve.run_batch" and lo <= s < hi]
    kids = [[s, s + d, name] for s, d, name in prog["program_spans"]
            if name.startswith("serve.") and name != "serve.run_batch"]
    busy = [scopes.Busy(ops) for ops in tr["devices"].values()]
    split = collections.Counter()
    for a, b in runs:
        inside = [k for k in kids if k[0] < b and k[1] > a]
        cuts = sorted({a, b} | {x for k in inside for x in k[:2]
                                if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            mid = 0.5 * (x + y)
            holders = [k for k in inside if k[0] <= mid < k[1]]
            label = min(holders, key=lambda k: k[1] - k[0])[2] \
                if holders else "serve.run_batch (no child)"
            split[label] += (y - x) - sum(d.within(x, y) for d in busy) \
                / len(busy)
    idle = scopes.idle_inside(tr, runs)
    starts = [s for dev in prog["modules"].values()
              for s, _, module, _ in dev
              if module == SEGMENT_MODULE and lo <= s < hi]
    outside = [min((0.0 if a <= s <= b else min(abs(s - a), abs(s - b)))
                   for a, b in runs) if runs else None for s in starts]
    # device start of each segment execution after the host dispatched it
    # (the `serve.segment` span): negative where the device clock leads
    dispatched = sorted(s for s, _, name in kids if name == "serve.segment")
    lead = [s - max((d for d in dispatched if d <= s + 5e6), default=s)
            for s in starts]
    return {"dispatches": len(runs),
            "idle_s_by_child": {k: v * 1e-9 for k, v in split.items()},
            "idle_ms_per_run_batch": (sum(idle) / len(idle) * 1e-6
                                      if idle else None),
            "segment_executions": len(starts),
            "segment_start_outside_run_batch_max_ms":
                max(outside) * 1e-6 if outside and None not in outside
                else None,
            "segment_start_after_dispatch_ms": sorted(x * 1e-6
                                                      for x in lead)}


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = _args(argv)
    for p in (str(BENCH_DIR), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax

    from chipbench import scopes
    from chipbench import trace as T
    from chipbench.registry import Registry, load_module
    from repro import obs

    harness = load_module(BENCH_DIR / "run.py", "chipbench_run_")
    reg = Registry(CHECKOUT, BENCH_DIR)
    devices = jax.devices()
    chips = reg.workload(args.workload)["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chips, JAX sees {len(devices)} "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())
    kept, timed = {}, []
    extract, tables = T.extract, obs.scope_tables

    def extract_and_keep(logdir):
        kept.update(scopes.extract_program(logdir))
        kept["trace"] = extract(logdir)
        return kept["trace"]

    def timed_tables():
        # host work alone: lowering, compiling and reading HLO text
        t0 = time.perf_counter()  # reprolint: disable=timer-no-block
        try:
            return tables()
        finally:
            t1 = time.perf_counter()  # reprolint: disable=timer-no-block
            timed.append(t1 - t0)

    driver = reg.driver

    def driver_keeping_the_window(name):
        mod = driver(name)
        build = mod.build

        def build_and_keep(*a, **kw):
            cell = build(*a, **kw)
            window = cell.window

            def window_and_keep(*wa, **wkw):
                kept["window"] = window(*wa, **wkw)
                return kept["window"]
            cell.window = window_and_keep
            return cell
        mod.build = build_and_keep
        return mod

    reg.driver = driver_keeping_the_window
    T.extract, obs.scope_tables = extract_and_keep, timed_tables
    try:
        result, lines = harness.run_cell(
            reg, args.workload, args.seed, args.seconds, True,
            devices[:chips], peaks, t_start)
    finally:
        T.extract, obs.scope_tables = extract, tables
    tr = kept["trace"]
    window = T.window_of(tr)
    report = {"workload": args.workload, "seed": args.seed,
              "metrics": result["metrics"],
              "scope_tables_s": list(timed),
              "window": {k: v for k, v in kept["window"].items()
                         if k in ("seconds", "cell_rounds", "attempted",
                                  "failed", "metrics", "notes")},
              "memory_plan": obs.memory_plan()}
    kernel = T.op_events(tr, window, lambda n, o: "veds_dt_score" in n)
    report["kernel"] = {"calls": len(kernel),
                        "device_s": sum(k[1] for k in kernel) * 1e-9}
    if reg.traffic(reg.workload(args.workload)["traffic"])["driver"] == \
            "serve_open":
        report["serve"] = serve_report(tr, kept, window, scopes, T)
    else:
        report["layers"] = layer_report(tr, window, result["attempted"],
                                        result["metrics"], scopes, T)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1))
    for line in lines:
        print(line, file=sys.stderr)
    summary = {k: v for k, v in report.items() if k not in ("layers",)}
    if "layers" in report:
        summary["layers"] = {k: v for k, v in report["layers"].items()
                             if k != "top_ops"}
    print(json.dumps(summary), file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
