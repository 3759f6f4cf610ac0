"""From a profiler trace to the numbers the per-layer metrics read.

`extract` turns the `.xplane.pb` that `jax.profiler` writes into plain
lists: the operations of each TPU (start, duration, HLO name, the JAX
op name of its metadata) and the harness's own host spans (names that
start with `bench.`). `reduce` computes, inside the traced window, the
union of each device's busy intervals, the idle share, the operations
that took most time and the longest idle gaps, each gap labelled by the
host span that overlaps it most. All of these count leaf operations
only: a loop, a branch or a call spans its whole body, gaps between the
body's operations included, so its time is its children's.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

from chipbench.session import SPAN_PREFIX

OP_LINES = ("XLA Ops",)
# where a device op's event carries the JAX operation it came from
OP_STATS = ("tf_op", "op_name", "jax_op", "source")
# ops that only hold others (a loop, a branch, a call): their time is
# their children's, so they count neither as busy time nor as an op
CONTAINERS = ("while", "conditional", "call")
# the harness's host spans, most telling first
GAP_PRIORITY = ("dispatch", "generate", "wait", "setup", "await_arrival")


def extract(logdir: str) -> Dict:
    """Device operations and harness spans of the newest trace under
    `logdir`, as {"devices": {plane: [[start_ns, dur_ns, name, op]]},
    "spans": [[start_ns, dur_ns, label]]}."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(files[-1])
    devices: Dict[str, List] = {}
    spans: List = []
    stat_names = set()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    st = dict(e.stats)
                    stat_names.update(st)
                    op = next((st[k] for k in OP_STATS if st.get(k)), "")
                    ops.append([float(e.start_ns), float(e.duration_ns),
                                e.name, str(op)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([float(e.start_ns),
                                      float(e.duration_ns),
                                      e.name[len(SPAN_PREFIX):]])
    return {"devices": devices, "spans": spans,
            "stat_names": sorted(stat_names)}


def merge(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def window_of(tr: Dict, label: str = "window") -> Tuple[float, float]:
    """The traced window: the harness span named `label`."""
    for s, d, name in tr["spans"]:
        if name == label:
            return s, s + d
    raise ValueError(f"no span {SPAN_PREFIX}{label} in the trace")


def op_label(name: str, op: str = "") -> str:
    """A short label for a device op: its HLO instruction name, the
    target of a custom call, and the JAX operation when the trace has
    it ("%custom-call.157 LuDecompositionBlock", not the whole HLO
    text)."""
    head = name.split(" = ", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    label = head + (f" {target.group(1)}" if target else "")
    return f"{label} ({op})" if op else label


def is_container(name: str) -> bool:
    head = name.split(" = ", 1)[0].lstrip("%")
    return head.split(".", 1)[0] in CONTAINERS


def _label(gap: Sequence[float], spans) -> str:
    """What the host was doing in an idle gap: of the harness spans that
    cover at least half of it, the first in `GAP_PRIORITY` (the host
    can dispatch on one thread while another awaits arrivals, and the
    dispatch is what holds the device back); else the span that
    overlaps it most."""
    best, label, covering = 0.0, "untraced host", []
    for s, d, name in spans:
        if name == "window":
            continue
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best:
            best, label = ov, name
        if ov >= 0.5 * (gap[1] - gap[0]) and name in GAP_PRIORITY:
            covering.append(name)
    return min(covering, key=GAP_PRIORITY.index) if covering else label


def reduce(tr: Dict, window: Tuple[float, float], top: int = 10) -> Dict:
    """Busy and idle time of the devices inside `window` (ns), averaged
    over devices, with the operations that took most device time and the
    longest idle gaps, all from leaf operations. Seconds throughout."""
    lo, hi = window
    if not tr["devices"]:
        raise ValueError("the trace holds no TPU operations")
    busy, per_op, gaps = [], collections.Counter(), []
    for ops in tr["devices"].values():
        leaves = [o for o in ops if not is_container(o[2])]
        iv = merge(clip([(s, s + d) for s, d, _, _ in leaves], lo, hi))
        busy.append(sum(e - s for s, e in iv))
        for s, d, name, op in leaves:
            dd = min(s + d, hi) - max(s, lo)
            if dd > 0:
                per_op[op_label(name, op)] += dd
        edges = [lo] + [x for se in iv for x in se] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, [a, b]))
    n = len(tr["devices"])
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": sum(busy) / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[k, v / n * 1e-9] for k, v in per_op.most_common(top)],
        "idle_gaps": [[_label(g, tr["spans"]), d * 1e-9]
                      for d, g in gaps[:top]],
        "op_seconds": {k: v / n * 1e-9 for k, v in per_op.items()},
        "devices": n,
    }


def op_events(tr: Dict, window: Tuple[float, float], match) -> List:
    """Device operations inside `window` whose (name, op) `match`
    accepts, over all devices."""
    lo, hi = window
    return [o for ops in tr["devices"].values() for o in ops
            if lo <= o[0] and o[0] + o[1] <= hi and match(o[2], o[3])]
