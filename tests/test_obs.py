"""The program's tracing (`repro.obs`): layer scopes read back from the
compiled HLO, the executable registry, and the service's host spans in
a profiler trace."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.channel.mobility import ManhattanParams
from repro.channel.v2x import ChannelParams
from repro.core.lyapunov import VedsParams
from repro.core.scenario import ScenarioParams
from repro.core.streaming import StreamConfig
from repro.fl.engine import fused_segment, init_carry
from repro.launch.serve import (SchedulingService, ServeConfig, ServeRequest,
                                default_problem)

# instructions that only hold or name others: no device operation runs
# for them, so they are not leaf operations of a trace
BOOKKEEPING = {"parameter", "get-tuple-element", "tuple", "constant",
               "bitcast", "while", "conditional", "call", "after-all"}
_LINE = re.compile(r"^\s+(?:ROOT )?%(\S+) = .*? ([a-z][\w\-]*)\(")
_COMP = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")


def _computations(text):
    """{computation: [(instruction, opcode, line)]} of an HLO text."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _LINE.match(line)
        if m and cur is not None:
            cur.append((m.group(1), m.group(2), line))
    return comps


@pytest.mark.parametrize("op_name,layer", [
    ("jit(seg)/while/body/closed_call/sched/p4/jit(solve)/vmap()/lu", "p4"),
    ("jit(seg)/while/body/sched/fleet/add", "fleet"),
    ("jit(seg)/transpose(jvp(step))/mul", "step"),
    ("jit(step)/while/body/add", None),
    ("jit(seg)/while/body/dynamic_update_slice", None),
])
def test_layer_of_is_the_innermost_layer_scope(op_name, layer):
    assert obs.layer_of(op_name) == layer


def test_scope_rejects_an_unknown_layer():
    with pytest.raises(ValueError):
        obs.scope("mobility")


def test_named_scope_in_a_jitted_scan_is_its_innermost_scope():
    def f(x):
        def body(c, _):
            with obs.scope("sched"):
                c = c * 3.0 + 1.0
                with obs.scope("p4"):
                    c = jnp.sin(c)
            return c, None
        return jax.lax.scan(body, x, None, length=3)[0]

    fn = obs.register("scan_probe", jax.jit(f))
    fn(jnp.ones(4))
    fn(jnp.ones(4))
    # one signature recorded, and the callable's own attributes pass
    assert len(fn.signatures) == 1 and hasattr(fn, "_cache_size")
    tables = obs.scope_tables("scan_probe")
    assert len(tables) == 1 and tables[0]["module"] == "jit_f"
    scopes = tables[0]["scopes"]
    text = fn.lower(jnp.ones(4)).compile().as_text()
    ops = {name: op for c in _computations(text).values()
           for name, op, _ in c}
    sines = [n for n, op in ops.items() if op == "sine"]
    muls = [n for n, op in ops.items() if op == "multiply"]
    assert sines and muls
    assert all(scopes[n] == "p4" for n in sines)
    assert all(scopes[n] == "sched" for n in muls)
    # a second shape is a second signature, and a second table
    fn(jnp.ones(8))
    assert len(obs.scope_tables("scan_probe")) == 2
    plans = obs.memory_plan("scan_probe")
    assert len(plans) == 2 and all(p["argument_bytes"] > 0 for p in plans)


def test_fused_segment_leaf_instructions_map_to_layers():
    """A toy VEDS+COT segment with handoff, compiled for the CPU: at
    least 95% of the leaf instructions its round loop runs (the loop's
    body, and every loop and branch inside it) have a layer."""
    sc = ScenarioParams(n_sov=3, n_opv=3, n_slots=4, batch_size=4)
    mob, ch, prm = ManhattanParams(), ChannelParams(), VedsParams(
        ipm_iters=4)
    B, R = 2, 2
    params, loss_fn, shards = default_problem(8)
    cfg = StreamConfig(n_rounds=0, batch=B, carry_queues=True,
                       handoff=True)
    seg = fused_segment(loss_fn, "veds", sc, mob, ch, prm, cfg, 0.05, 1)
    k = jax.random.key(0)
    args = (init_carry(k, sc, mob, cfg, params, ch=ch),
            jax.random.split(k, R),
            jnp.tile(jnp.arange(3)[None, None], (R, B, 1)),
            jax.random.uniform(k, (R, B, 3, 4)), shards, jnp.arange(R),
            jnp.ones((R,), bool), jnp.zeros((R,), bool))
    jax.block_until_ready(seg(*args))
    text = seg.lower(*args).compile().as_text()
    comps = _computations(text)
    entry = re.search(r"^ENTRY %(\S+) ", text, re.M).group(1)
    outer = [line for _, op, line in comps[entry] if op == "while"]
    assert len(outer) == 1
    todo = [re.search(r"body=%([\w.\-]+)", outer[0]).group(1)]
    seen, leaves = set(), []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, op, line in comps[comp]:
            if op in ("while", "conditional", "call"):
                todo += re.findall(r"(?:body|condition|to_apply)="
                                   r"%([\w.\-]+)", line)
                for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                        line):
                    todo += re.findall(r"%([\w.\-]+)", group)
            if op not in BOOKKEEPING:
                leaves.append(name)
    table = obs.parse_scopes(text)
    covered = [n for n in leaves if n in table]
    assert len(leaves) > 200
    assert len(covered) >= 0.95 * len(leaves), \
        f"{len(covered)} of {len(leaves)} leaf instructions have a layer"
    assert {table[n] for n in covered} == set(obs.LAYERS)


def _host_events(logdir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)
    pd = ProfileData.from_file(path[0])
    return [(plane.name, line.name, e.name, e.start_ns, e.duration_ns,
             dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(obs.PREFIX)]


def test_serve_spans_nest_under_run_batch(tmp_path):
    """One traced `run_batch`: its `repro.serve.*` stages, the session
    store's spill and restore among them, lie inside its span, on its
    thread, on the `/host:CPU` plane."""
    params, loss_fn, shards = default_problem(6)
    svc = SchedulingService(ServeConfig(batch=2, max_rounds=2,
                                        max_sessions=1, n_slots=4),
                            params=params, loss_fn=loss_fn,
                            client_data=shards)
    svc.warmup()
    svc.run_batch([ServeRequest("a", 2, 1)])
    svc.run_batch([ServeRequest("b", 1, 2)])      # spills a
    jax.profiler.start_trace(str(tmp_path))
    try:
        # restores a, spills b
        out = svc.run_batch([ServeRequest("a", 1, 3)])
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    runs = [e for e in events if e[2] == "repro.serve.run_batch"]
    assert len(runs) == 1
    plane, line, _, start, dur, stats = runs[0]
    assert plane == "/host:CPU"
    assert stats["tier"] == out[0].tier and int(stats["requests"]) == 1
    kids = [e for e in events if e is not runs[0]]
    assert {e[2] for e in kids} == {
        "repro.serve." + s for s in ("store", "draws", "assemble",
                                     "segment", "split", "to_host",
                                     "spill", "restore")}
    for k in kids:
        assert k[:2] == (plane, line)
        assert start <= k[3] and k[3] + k[4] <= start + dur
