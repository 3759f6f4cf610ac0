"""Mesh-native execution of the fused rollout / streaming engine.

PRs 1-5 collapsed the whole VFL loop into one `lax.scan` program; this
module runs that program on a DEVICE MESH (DESIGN.md §12). The strategy
is committed input shardings, not per-device code: every carry/xs leaf is
`device_put` under the NamedSharding its logical axes dictate
(`fleet_spec` / `fused_batch_spec` from `repro.sharding.rules`), and the
whole-run step is a plain `jax.jit` — GSPMD propagates the placements
through the scan, keeps per-cell work on the cell's shard, and lowers
the §11 `exchange_fleet` permutation to an all-to-all over the vehicle
axis when the cell axis is sharded (the contract documented on
`rules.fleet_spec`).

Axis placement (1-D "data" mesh; `default_rules(multi_pod=True)` folds a
"pod" axis into the same entries):

  leaf                      layout           spec
  FleetState.*              [B, N, ...]      P("data", None, ...)
  FleetState.rsu_xy         [B, 2]           P()   (replicated: every
                                             shard scores all RSUs in
                                             the nearest-RSU argmin)
  SchedulerCarry.qs/qu/p4   [B, S|U, ...]    P("data", None, ...)
  params / opt_state        [B, ...]         P("data", None, ...)
  sel / mb_u (scan xs)      [R, B, ...]      P(None, "data", ...)
  ClientShards.*            [C, n_max, ...]  P("data", None, ...) when
                                             C divides the mesh, else
                                             replicated
  keys / steps / active     [R, ...]         replicated (left unplaced)

The jitted steps donate the carry argument by default, so the `[B, N]`
fleet state and `[B, ...]` model/optimizer buffers are updated IN PLACE
across calls instead of doubling peak memory. A donated carry is dead
after the call — re-place it (`place_carry`) before reusing, and never
pass the same buffer as two arguments of one donating call.

`cfg.batch` must divide evenly over the mesh's data axes: NamedSharding
rejects uneven shards (`ValueError`), so we check up front with the
actionable message.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.scenario import FleetState
from repro.core.scheduler import RolloutCarry
from repro.core.streaming import (StreamConfig, StreamResult, sched_state0,
                                  stream_rounds, validate_stream_config)
from repro.fl.engine import ClientShards, FusedResult, fused_rollout
from repro.sharding.rules import (LogicalRules, data_axis_names,
                                  default_rules, fleet_spec,
                                  fused_batch_spec, num_vehicles)


def fleet_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    """1-D device mesh over the cell/batch axis — the only axis the VFL
    rollout shards (vehicles inside a cell couple through the per-slot
    argmax, so the pool axis stays local; see `rules.fleet_spec`)."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)} "
                         f"({devs[0].platform})")
    return Mesh(np.asarray(devs[:n]), (axis,))


def check_batch_divisible(mesh: Mesh, batch: int) -> None:
    n = num_vehicles(mesh)
    if int(batch) % n:
        raise ValueError(
            f"batch={int(batch)} cells cannot shard evenly over the "
            f"{n}-device data axes {data_axis_names(mesh)} of the mesh "
            "(NamedSharding rejects uneven shards); pick batch as a "
            "multiple of the device count")


def cell_spec(rules: LogicalRules, ndim: int) -> P:
    """Spec for a leading-[B] leaf (params/opt_state/queue carries)."""
    return P(rules.mesh_axis("cell"), *([None] * max(ndim - 1, 0)))


def fleet_shardings(mesh: Mesh, fleet: FleetState,
                    rules: Optional[LogicalRules] = None) -> FleetState:
    """The NamedSharding of every FleetState leaf: `fleet_spec`, with
    `rsu_xy` replicated (the exchange's distance matrix reads every RSU
    position on every shard — see `rules.fleet_spec`). Leaves may be
    arrays or shapes."""
    rules = rules or default_rules()
    return FleetState(**{
        f.name: NamedSharding(mesh, P() if f.name == "rsu_xy" else
                              fleet_spec(rules, getattr(fleet, f.name).ndim))
        for f in dataclasses.fields(fleet)})


def carry_shardings(mesh: Mesh, carry: RolloutCarry,
                    rules: Optional[LogicalRules] = None) -> RolloutCarry:
    """Shardings of a fused-rollout carry: FleetState under `fleet_spec`
    (queue carries / params / optimizer state under the cell spec)."""
    rules = rules or default_rules()

    def cell(t):
        return jax.tree.map(
            lambda x: NamedSharding(mesh, cell_spec(rules, x.ndim)), t)

    sched = (fleet_shardings(mesh, carry.sched, rules)
             if isinstance(carry.sched, FleetState) else cell(carry.sched))
    return RolloutCarry(sched=sched, params=cell(carry.params),
                        opt_state=cell(carry.opt_state))


def batch_shardings(mesh: Mesh, tree,
                    rules: Optional[LogicalRules] = None):
    """Shardings of `[R, B, ...]` scan xs (sel / mb_u) under
    `fused_batch_spec`: round axis scanned, cell axis sharded."""
    rules = rules or default_rules()
    return jax.tree.map(
        lambda x: NamedSharding(mesh, fused_batch_spec(rules, x.ndim)),
        tree)


def shards_shardings(mesh: Mesh, shards: ClientShards,
                     rules: Optional[LogicalRules] = None) -> ClientShards:
    """Shardings of the padded client data: the "client" rule when the
    client count divides the mesh, replicated otherwise (the per-round
    minibatch gather indexes arbitrary clients per cell, so GSPMD emits
    a collective gather from a sharded layout — correct either way)."""
    rules = rules or default_rules()
    ax = (rules.mesh_axis("client")
          if shards.n_clients % num_vehicles(mesh) == 0 else None)
    return jax.tree.map(
        lambda x: NamedSharding(mesh, P(ax, *([None] * (x.ndim - 1)))),
        shards)


def place_fleet(mesh: Mesh, fleet: FleetState,
                rules: Optional[LogicalRules] = None) -> FleetState:
    """Commit a FleetState to the mesh under `fleet_shardings`."""
    return jax.device_put(fleet, fleet_shardings(mesh, fleet, rules))


def place_carry(mesh: Mesh, carry: RolloutCarry,
                rules: Optional[LogicalRules] = None) -> RolloutCarry:
    """Commit a fused-rollout carry under `carry_shardings`."""
    return jax.device_put(carry, carry_shardings(mesh, carry, rules))


def place_batch(mesh: Mesh, tree,
                rules: Optional[LogicalRules] = None):
    """Commit `[R, B, ...]` scan xs under `batch_shardings`."""
    return jax.device_put(tree, batch_shardings(mesh, tree, rules))


def place_shards(mesh: Mesh, shards: ClientShards,
                 rules: Optional[LogicalRules] = None) -> ClientShards:
    """Commit the padded client data under `shards_shardings`."""
    return jax.device_put(shards, shards_shardings(mesh, shards, rules))


# Whole-run steps, cached so repeated rollouts (benchmark sweeps, CI
# parity runs) reuse the compiled executable. Keyed entirely on
# hashables: schedulers and the param dataclasses are frozen.
@functools.lru_cache(maxsize=16)
def _fused_exec(sched, sc, mob, ch, prm, cfg: StreamConfig, loss_fn,
                lr: float, clip: float, opt, unroll: int,
                history_chunk: int, state_dtype, eval_fn, donate: bool):
    def step(carry, keys, sel, mb_u, shards, steps, active, ev):
        return fused_rollout(keys, sel, mb_u, sched, sc, mob, ch, prm,
                             cfg, loss_fn, shards, carry, lr=lr,
                             clip=clip, opt=opt, steps=steps,
                             active=active, eval_fn=eval_fn,
                             eval_mask=ev, unroll=unroll,
                             history_chunk=history_chunk,
                             state_dtype=state_dtype)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=16)
def _stream_exec(sched, sc, mob, ch, prm, cfg: StreamConfig,
                 donate: bool):
    def step(key, fleet):
        return stream_rounds(key, sched, sc, mob, ch, prm, cfg, fleet)

    return jax.jit(step, donate_argnums=(1,) if donate else ())


def mesh_fused_rollout(mesh: Mesh, keys, sel, mb_u, sched, sc, mob, ch,
                       prm, cfg: StreamConfig, loss_fn,
                       shards: ClientShards, carry: RolloutCarry, *,
                       rules: Optional[LogicalRules] = None,
                       lr: float = 0.05, clip: float = 5.0, opt=None,
                       steps=None, active=None, eval_fn=None,
                       eval_mask=None, unroll: int = 1,
                       history_chunk: int = 1, state_dtype=None,
                       donate: bool = True,
                       place: bool = True) -> FusedResult:
    """`fused_rollout` on a device mesh: commit the carry/xs/shards to
    their NamedShardings (skip with `place=False` when the caller
    already placed them) and run the cached whole-run jit. With `donate`
    the carry buffers are consumed — re-place before reusing. Outputs
    inherit the input shardings through GSPMD propagation: the final
    params/fleet stay sharded by cell, the `[R, ...]` history stacks
    with its cell axis sharded."""
    rules = rules or default_rules()
    validate_stream_config(cfg, threads_params=True)
    check_batch_divisible(mesh, int(cfg.batch))
    R = keys.shape[0]
    if steps is None:
        steps = jnp.arange(R)
    if active is None:
        active = jnp.ones((R,), bool)
    if eval_mask is None:
        eval_mask = jnp.zeros((R,), bool)
    if place:
        carry = place_carry(mesh, carry, rules)
        sel = place_batch(mesh, sel, rules)
        mb_u = place_batch(mesh, mb_u, rules)
        shards = place_shards(mesh, shards, rules)
    step = _fused_exec(sched, sc, mob, ch, prm, cfg, loss_fn, lr, clip,
                       opt, int(unroll), int(history_chunk), state_dtype,
                       eval_fn, bool(donate))
    return step(carry, keys, sel, mb_u, shards, steps, active, eval_mask)


def mesh_stream_rounds(mesh: Mesh, key, sched, sc, mob, ch, prm,
                       cfg: StreamConfig, fleet: Optional[FleetState] = None,
                       *, rules: Optional[LogicalRules] = None,
                       donate: bool = True,
                       place: bool = True) -> StreamResult:
    """Scheduling-only `stream_rounds` on a device mesh. The persistent
    fleet is built (or taken from `fleet`), committed under `fleet_spec`,
    and donated into the cached whole-run jit; fresh-fleet mode has no
    fleet to shard and runs the plain program on the mesh's devices."""
    rules = rules or default_rules()
    validate_stream_config(cfg)
    check_batch_divisible(mesh, int(cfg.batch))
    state0 = sched_state0(key, sc, mob, cfg, fleet, ch)
    persistent = isinstance(state0, FleetState)
    if persistent and place:
        state0 = place_fleet(mesh, state0, rules)
    step = _stream_exec(sched, sc, mob, ch, prm, cfg,
                        bool(donate) and persistent)
    return step(key, state0 if persistent else None)
