"""Device self time by program layer, and the host's part of a service
dispatch, from a traced run.

The program names its device layers with `jax.named_scope`s and its host
stages with `repro.` spans (`repro.obs`). A device operation in the
trace carries its HLO instruction's name; the program's scope tables
(`repro.obs.scope_tables`, built after the window from the programs it
ran) give each instruction its innermost layer. A layer's self time is
the sum of the durations of its leaf operations in the traced window.
Where the program has no scope tables (a checkout from before it named
its layers), the readers over this module report nothing.

`extract_program` reads what `chipbench.trace.extract` leaves out of a
trace: the program's own host spans and each device's "XLA Modules"
line (which program ran when).
"""
from __future__ import annotations

import bisect
import glob
import importlib
import os
import re
import sys
import traceback
from typing import Dict, List, Optional, Tuple

from chipbench.trace import is_container, merge

PROGRAM_PREFIX = "repro."
MODULE_LINE = "XLA Modules"
_MODULE = re.compile(r"^(.*)\((\d+)\)$")


def instruction(name: str) -> str:
    """The HLO instruction of a device operation's event name
    ("%fusion.3 = f32[8] fusion(...)" -> "fusion.3")."""
    return name.split(" = ", 1)[0].lstrip("%")


def program_scopes() -> Optional[Tuple[Tuple[str, ...], Dict[str, str]]]:
    """(the program's layers, {instruction: layer}) over every program
    it registered, or None where it names no layers. An instruction that
    two programs put in different layers is left out."""
    # import_module honours a None entry in sys.modules, which `from
    # repro import obs` does not once the package holds the attribute
    try:
        obs = importlib.import_module("repro.obs")
    except ImportError:
        return None
    table: Dict[str, str] = {}
    clash = set()
    for t in obs.scope_tables():
        for instr, layer in t["scopes"].items():
            if table.setdefault(instr, layer) != layer:
                clash.add(instr)
    for instr in clash:
        del table[instr]
    return (obs.LAYERS, table) if table else None


def layer_times(run) -> Optional[Dict]:
    """Device time of each layer's leaf operations inside the traced
    window, summed over devices (ns), with the busy and the unscoped
    time; None where the program names no layers. Computed once a run
    (the readers share it)."""
    if "layer_times" in run.__dict__:
        return run.layer_times
    try:
        scoped = program_scopes()
    except Exception:           # noqa: BLE001 — a reader leaves it out
        traceback.print_exc(file=sys.stderr)
        scoped = None
    out = None
    if scoped is not None:
        layers, table = scoped
        lo, hi = run.span
        per = dict.fromkeys(layers, 0.0)
        busy = other = 0.0
        for ops in run.raw["devices"].values():
            for s, d, name, _ in ops:
                if is_container(name):
                    continue
                dd = min(s + d, hi) - max(s, lo)
                if dd <= 0:
                    continue
                busy += dd
                layer = table.get(instruction(name))
                if layer is None:
                    other += dd
                else:
                    per[layer] += dd
        out = {"layers": per, "busy_ns": busy, "unscoped_ns": other}
    run.layer_times = out
    return out


def self_ms(run, layer: str) -> Optional[float]:
    """Device self time of `layer` in the traced window per cell-round
    (ms), or None where the program names no layers."""
    times = layer_times(run)
    if times is None or not run.window.get("cell_rounds"):
        return None
    return times["layers"][layer] * 1e-6 / run.window["cell_rounds"]


class Busy:
    """The union of one device's leaf-operation intervals, and the busy
    time inside any [x, y) by bisection."""

    def __init__(self, ops):
        iv = merge([(s, s + d) for s, d, name, _ in ops
                    if not is_container(name)])
        self.starts = [s for s, _ in iv]
        self.ends = [e for _, e in iv]
        self.cum = [0.0]
        for s, e in iv:
            self.cum.append(self.cum[-1] + e - s)

    def within(self, x: float, y: float) -> float:
        i = bisect.bisect_right(self.ends, x)   # first to end after x
        j = bisect.bisect_left(self.starts, y)  # those that start before y
        if i >= j:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, x - self.starts[i])
                - max(0.0, self.ends[j - 1] - y))


def idle_inside(tr: Dict, spans: List[List[float]]) -> List[float]:
    """For each [start, end) span, the time inside it in which no leaf
    operation ran on the device (ns, mean over devices)."""
    busy = [Busy(ops) for ops in tr["devices"].values()]
    return [(b - a) - sum(x.within(a, b) for x in busy) / len(busy)
            for a, b in spans]


def host_ms_per_dispatch(run, label: str = "dispatch") -> Optional[float]:
    """Mean, over the spans named `label` that start in the traced
    window, of the time inside the span in which no leaf operation ran
    on the device (ms); None without such spans."""
    lo, hi = run.span
    spans = [[s, s + d] for s, d, name in run.raw["spans"]
             if name == label and lo <= s < hi]
    if not spans or not run.raw["devices"]:
        return None
    idle = idle_inside(run.raw, spans)
    return sum(idle) / len(idle) * 1e-6


def extract_program(logdir: str) -> Dict:
    """The program's host spans and the devices' module executions in
    the newest trace under `logdir`: {"program_spans": [[start_ns,
    dur_ns, label]] (names without the `repro.` prefix), "modules":
    {plane: [[start_ns, dur_ns, module, program_id]]}}."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(files[-1])
    spans: List = []
    modules: Dict[str, List] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != MODULE_LINE:
                    continue
                for e in line.events:
                    m = _MODULE.match(e.name)
                    module, pid = (m.group(1), m.group(2)) if m else \
                        (e.name, "")
                    modules.setdefault(plane.name, []).append(
                        [float(e.start_ns), float(e.duration_ns), module,
                         pid])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        spans.append([float(e.start_ns),
                                      float(e.duration_ns),
                                      e.name[len(PROGRAM_PREFIX):]])
    return {"program_spans": sorted(spans), "modules": modules}
