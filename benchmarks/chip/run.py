"""The chip benchmark: one cell of BENCHMARK.json, one run.

  python benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

Builds the cell's weights, data and traffic from the seed on the
device, compiles and warms its program (set-up), measures for
`--seconds` (with `--trace 1`: traces a shorter window that the cell's
traffic file sets and reads the cell's per-layer metrics from the trace
instead), then checks what the timed path produced against the plain
reference. The last line of standard output is one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown`
when traced), and `checks`, each number compared beside its limit; the
same numbers close standard error. It refuses to run without a TPU or
with fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parents[1]
# a generator later than this (p95) makes a window no clean reading
LATE_MS = 5.0


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(devices) -> dict:
    d = devices[0]
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def run_cell(reg, workload: str, seed: int, seconds: float, trace: bool,
             devices, peaks: dict, t_start: float, fault: str = ""):
    """Set-up, window and check of one cell on `devices`. Returns the
    result object and the lines for standard error."""
    import jax

    from chipbench import trace as T
    from chipbench.checks import judge
    from chipbench.session import CompileCounter, span

    work = reg.workload(workload)
    cfg = reg.config(work["config"])
    traffic = reg.traffic(work["traffic"])
    limits = reg.limits(workload)
    counter = CompileCounter()
    model = reg.model(cfg["model"]["name"])
    cell = reg.driver(traffic["driver"]).build(cfg, model, traffic, seed,
                                               fault)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    counter.counting = True
    if trace:
        logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            jax.profiler.start_trace(logdir)
            with span("window"):
                win = cell.window(seconds, traced=True)
            jax.profiler.stop_trace()
            tr = T.extract(logdir)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
    else:
        win = cell.window(seconds)
    counter.counting = False
    device = device_info(devices)
    cell.release()
    correct, table = judge(cell.check(), limits)

    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": {}, "device": device}
    if trace:
        window = T.window_of(tr)
        red = T.reduce(tr, window)
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        run = types.SimpleNamespace(
            cfg=cfg, model=model, traffic=traffic, window=win, trace=red,
            raw=tr, span=window, chips=len(devices),
            peaks=peaks[devices[0].device_kind])
        for m in reg.metrics_of(workload, "per_layer"):
            v = reg.reader(m["name"]).read(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        measured = dict(win["metrics"], setup_s=setup_s)
        for m in reg.metrics_of(workload, "end_to_end"):
            result["metrics"][m["name"]] = {"value": measured[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = table

    lines = []
    if trace:
        lines.append(f"stats on device ops in the trace: {tr['stat_names']}")
    late = win.get("generator_late_p95_ms")
    lines += win.get("notes", [])
    lines += [f"generator_late_p95_ms: "
              f"{'none (no open-loop generator)' if late is None else late}",
              f"compiles_in_window: {counter.count}"]
    if counter.count or (late is not None and late > LATE_MS):
        lines.append("not a clean reading: the window compiled or the "
                     "generator ran late")
    lines += [f"check {name}: {row['value']!r} limit {row['limit']!r}"
              for name, row in table.items()]
    return result, lines


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = _args(argv)
    for p in (str(BENCH_DIR), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax

    from chipbench.registry import Registry

    reg = Registry(CHECKOUT, BENCH_DIR)
    chips = reg.workload(args.workload)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX sees {devices[0].platform}; this benchmark "
              "measures the chip only", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"{args.workload} needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())
    if devices[0].device_kind not in peaks:
        print(f"no peaks for device kind {devices[0].device_kind!r} in "
              "peaks.json", file=sys.stderr)
        return 2
    result, lines = run_cell(reg, args.workload, args.seed, args.seconds,
                             bool(args.trace), devices[:chips], peaks,
                             t_start)
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
