"""The program's tracing: host spans, device layer scopes, and the tables
that map a device trace's operations back to those scopes.

One mechanism, the JAX profiler. `span` is a `TraceAnnotation` named
`repro.<name>`: it lands in the same trace as the device operations (and
the benchmark's own `bench.` spans), on the trace's clock, and costs
about a microsecond when no trace runs. `scope` is a `jax.named_scope`
named after one of `LAYERS`: it writes only the `op_name` metadata of
the HLO the scoped code lowers to, so it costs nothing at run time. A
device operation belongs to its innermost layer scope.

The trace names a device operation by its HLO instruction, not by the
scope it came from, so the hot path's jitted callables are `register`ed:
each records the shapes of the arguments it is called with, the first
time it sees them. `scope_tables` later compiles each recorded program
again (a hit of the compilation cache) and reads its instructions'
scopes from the compiled HLO text; `memory_plan` reads its buffers.
Both are for after a measured window, never inside one.
"""
from __future__ import annotations

import re
import weakref
from typing import Any, Callable, Dict, List, Optional

import jax

PREFIX = "repro."
# the device layers, each a `jax.named_scope` (DESIGN.md §15):
#   sched     core/streaming.sched_round_step: the scheduler's selection
#             and the queue gathers and scatters around it
#   exchange  core/scenario.exchange_fleet: the handoff between cells
#   fleet     core/scenario.fleet_round: mobility and channel draws
#   dt_score  core/veds._dt_candidates, the DT-score kernel included
#   p4        core/solver.solve_p4: the P4 interior-point solves
#   step      the fused engine's per-cell local gradients and FedAvg
LAYERS = ("sched", "exchange", "fleet", "dt_score", "p4", "step")

# automatic-differentiation and batching transforms wrap a scope's name
# in the op_name path ("transpose(jvp(step))"); other wrappers, such as
# a nested jit's "jit(step)", name a function and not a scope
_WRAPPED = re.compile(r"^(?:(?:jvp|transpose|vmap|checkpoint|remat)\()+"
                      r"(\w+)\)+$")
_COMP = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r"metadata=\{op_name=\"([^\"]*)\"")
_CALLS = re.compile(r"(?:body|condition|calls|to_apply)=(%[\w.\-]+)|"
                    r"branch_computations=\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")
_INHERIT = "<inherit>"
_MAX_HOPS = 32


def span(name: str, **stats):
    """A host span `repro.<name>` in the profiler's trace, with `stats`
    as its event stats. Thread-scoped: open and close it on one thread,
    never across an `await`."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


def scope(layer: str):
    """The device layer scope `layer` (one of `LAYERS`), as a context
    manager or a decorator."""
    if layer not in LAYERS:
        raise ValueError(f"{layer!r} is not one of the layers {LAYERS}")
    return jax.named_scope(layer)


def layer_of(op_name: str) -> Optional[str]:
    """The innermost layer scope on an HLO `op_name` path, or None."""
    for part in reversed(op_name.split("/")):
        m = _WRAPPED.match(part)
        name = m.group(1) if m else part
        if name in LAYERS:
            return name
    return None


def _spec(x):
    # a one-device array lowers as an uncommitted one: giving its
    # sharding would lower (and compile) a different program
    sharding = getattr(x, "sharding", None)
    if sharding is not None and len(sharding.device_set) == 1:
        sharding = None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding,
                                weak_type=getattr(x, "weak_type", False))


class Registered:
    """A jitted callable that records the argument shapes it is called
    with. Calls, `lower` and every other attribute go to the callable."""

    def __init__(self, name: str, fn: Callable):
        self.name, self.fn = name, fn
        self.signatures: Dict[Any, Any] = {}

    def __call__(self, *args):
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple((x.shape, x.dtype) for x in leaves))
        if key not in self.signatures:
            self.signatures[key] = jax.tree.unflatten(
                tree, [_spec(x) for x in leaves])
        return self.fn(*args)

    def __getattr__(self, attr):
        return getattr(self.fn, attr)

    def compiled(self) -> List:
        """The executables of every signature seen, compiled again."""
        return [self.fn.lower(*specs).compile()
                for specs in self.signatures.values()]


_REGISTRY: "weakref.WeakSet[Registered]" = weakref.WeakSet()


def register(name: str, fn: Callable) -> Registered:
    """Put the jitted `fn` on the registry `scope_tables` and
    `memory_plan` read; the registry holds it weakly."""
    reg = Registered(name, fn)
    _REGISTRY.add(reg)
    return reg


def parse_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction: innermost layer scope} of a compiled HLO text.

    An instruction's scope is read from its `op_name`. One the compiler
    inserted has no `op_name` (a copy, a tuple of a loop's initial
    values): it takes the scope of the instruction that calls its
    computation (a loop, a branch, a fusion); failing that, the one
    scope of the instructions it reads; failing that, the one scope of
    those that read it."""
    own: Dict[str, Optional[str]] = {}
    comp_of: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    called_by: Dict[str, str] = {}            # computation -> instruction
    comp = ""
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = layer_of(op.group(1)) if op else _INHERIT
        comp_of[name] = comp
        operands[name] = _REF.findall(line[m.end():])
        for callees in _CALLS.findall(line):
            for callee in _REF.findall(" ".join(callees)):
                called_by[callee] = name
    users: Dict[str, List[str]] = {}
    for name, refs in operands.items():
        for ref in refs:
            users.setdefault(ref, []).append(name)
    memo: Dict[str, Optional[str]] = {}

    def resolve(name: str, depth: int = 0) -> Optional[str]:
        if own[name] is not _INHERIT:
            return own[name]
        if name in memo or depth > _MAX_HOPS:
            return memo.get(name)
        memo[name] = None                     # cuts cycles
        caller = called_by.get(comp_of[name])
        memo[name] = (caller is not None and resolve(caller, depth + 1)) \
            or one(operands[name], depth) or one(users.get(name, ()), depth)
        return memo[name]

    def one(names, depth: int) -> Optional[str]:
        found = {resolve(n, depth + 1) for n in names if n in own} - {None}
        return found.pop() if len(found) == 1 else None

    out = {}
    for name in own:
        layer = resolve(name)
        if layer is not None:
            out[name] = layer
    return out


def _registered(name: Optional[str]) -> List[Registered]:
    return [r for r in list(_REGISTRY) if name is None or r.name == name]


def scope_tables(name: Optional[str] = None) -> List[Dict]:
    """For every program registered (under `name`, if given) and every
    argument signature it has seen: its module name, and {instruction:
    innermost layer scope} read from its compiled HLO. Compiles (from
    the compilation cache where it holds them): call it outside any
    timed window."""
    tables = []
    for reg in _registered(name):
        for compiled in reg.compiled():
            text = compiled.as_text()
            module = text.split(None, 2)[1].rstrip(",")
            tables.append({"name": reg.name, "module": module,
                           "scopes": parse_scopes(text)})
    return tables


def memory_plan(name: Optional[str] = None) -> List[Dict]:
    """For every program registered (under `name`, if given) and every
    argument signature it has seen: the compiler's argument, output and
    temporary bytes."""
    plans = []
    for reg in _registered(name):
        for compiled in reg.compiled():
            m = compiled.memory_analysis()
            plans.append({"name": reg.name,
                          "argument_bytes": int(m.argument_size_in_bytes),
                          "output_bytes": int(m.output_size_in_bytes),
                          "temp_bytes": int(m.temp_size_in_bytes)})
    return plans
