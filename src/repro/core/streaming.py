"""Streaming multi-round rollout engine: R x B rounds as ONE lax.scan.

The paper's stochastic optimization is *long-term*: vehicles drive
continuously through RSU coverage while the drift-plus-penalty virtual
energy queues (eqs. 19-20) track cumulative budget violation across
rounds. The blocked path (`make_round_batch` -> `solve_round` per round,
host-side Python loop) re-draws an independent fleet every round and
resets the queues, so no cross-round dynamics exist and every round pays
an XLA dispatch.

`stream_rounds` fuses the whole training run into one compiled program:
each scan step advances the persistent `FleetState` (mobility + residual
energy + per-vehicle virtual queues), re-selects SOVs/OPVs by coverage,
draws channels, runs the scheduler with the carried queues, and scatters
queue/energy updates back into the fleet. Axes of configuration:

  fresh_fleet    True  -> re-draw an independent fleet per round with the
                          blocked path's exact per-round RNG schedule
                          (`fold_in(key, r)` -> `make_round_batch`); with
                          `carry_queues=False` this reproduces the blocked
                          results while paying ONE dispatch for R rounds.
                 False -> thread one persistent fleet (time-correlated
                          trajectories, coverage-driven re-selection).
  carry_queues   True  -> virtual queues persist round-to-round (the
                          long-term energy constraint is actually
                          long-term). False -> queues reset each round
                          (seed semantics, default).
  handover_delay persistent mode: vehicles entering coverage mid-round
                 become eligible only the *next* round (one-round lag on
                 coverage re-selection).
  handoff        persistent mode: the B cells are B RSUs on one shared
                 road network; each scan step starts with the §11
                 cross-cell exchange (`exchange_fleet`): every vehicle —
                 position, battery, virtual queue, coverage memory —
                 migrates to its nearest RSU's cell, capacity-limited.
                 `handoff=False` is bit-for-bit the B-independent-worlds
                 behavior.

Queue freeze/restore rule (eqs. 19-20 across coverage gaps): a vehicle's
virtual queue updates only in rounds it actually plays (selected with
`valid_* = True`); while it is out of coverage, unselected, or parked by
the handoff capacity policy, the queue is FROZEN at its last value in
`FleetState.queue` — time out of coverage neither drains nor grows the
long-term energy debt. On re-admission the frozen value is RESTORED as
the round-start queue, whatever role the vehicle now plays. Under
handoff the queue field migrates with the vehicle in `exchange_fleet`,
so the debt follows the vehicle into its new cell instead of leaving a
ghost queue behind (`tests/test_handoff.py` pins all three legs).
  round_chunk    fresh-fleet, carry_queues=False only: solve `round_chunk`
                 rounds per scan step as one widened cell batch, so the
                 per-candidate P4 interior-point solves are batched
                 *across rounds* inside the scan — this is what makes
                 full VEDS+COT streaming cheap enough to measure
                 (`benchmarks/fig4_speed.cot_stream_sweep`).

Warm-started interior point (persistent VEDS+COT, DESIGN.md §3): with
`VedsParams.ipm_warm_iters > 0` the per-vehicle P4 warm-start table
(`FleetState.p4_tab`, seeded with the solver's cold starting point) rides
the scan carry: each round gathers the SOV slots' tables into
`SchedulerCarry.p4`, VEDS re-solves every candidate from the previous
optimum with the shortened warm budget (the table also chains
slot-to-slot inside the round), and the refreshed table scatters back
under the queue freeze rule — only slots that played update, and under
handoff the table migrates with the vehicle. This removes the dominant
per-round IPM cost that `round_chunk` cannot touch in persistent mode
(`benchmarks/fig4_speed.warm_ipm_sweep`).

The per-round scheduling step is exposed as `sched_state0` /
`sched_round_step` / `round_keys` so the fused training engine
(`repro.fl.engine`) can run the *same* scheduling program with model
parameters threaded alongside (DESIGN.md §9/§10).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.channel.mobility import ManhattanParams
from repro.channel.v2x import ChannelParams
from repro.core.lyapunov import VedsParams
from repro.core.scenario import (FleetState, ScenarioParams,
                                 exchange_fleet, fleet_round, init_fleet,
                                 make_round_batch, rsu_grid)
from repro.core.scheduler import RoundOutputs, Scheduler, SchedulerCarry
from repro.obs import scope


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Static configuration of a streaming rollout (closed over by jit)."""
    n_rounds: int = 50
    batch: int = 1                  # B parallel cells per round
    carry_queues: bool = False      # thread eqs. (19)-(20) across rounds
    fresh_fleet: bool = False       # blocked-parity mode (see module doc)
    hetero_fleet: bool = False      # fresh-fleet mode: pad fleets per cell
    n_fleet: Optional[int] = None   # persistent pool size (default 2(S+U))
    energy_horizon: Optional[float] = None  # battery, in rounds of budget
    handover_delay: bool = False    # persistent mode: one-round lag on entry
    handoff: bool = False           # persistent mode: cross-cell exchange
    round_chunk: int = 1            # fresh mode: rounds solved per scan step


class StreamResult(NamedTuple):
    """One streaming rollout's results.

      outputs  RoundOutputs stacked [R, B, ...] (`.carry` stacked too —
               the per-round virtual-queue trace comes for free)
      fleet    final FleetState (None in fresh-fleet mode)
      carry    final round's queue state [B, S]/[B, U]
    """
    outputs: RoundOutputs
    fleet: Optional[FleetState]
    carry: SchedulerCarry


def _zero_carry(sc: ScenarioParams, B: int) -> SchedulerCarry:
    return SchedulerCarry(qs=jnp.zeros((B, sc.n_sov)),
                          qu=jnp.zeros((B, sc.n_opv)))


SchedState = Union[FleetState, SchedulerCarry]


def validate_stream_config(cfg: StreamConfig, *,
                           threads_params: bool = False) -> None:
    """Reject silently-ignorable flag combinations up front.

    The single home of every `round_chunk` rejection: all callers —
    `stream_rounds`, the fused engine's (possibly segmented)
    `fused_rollout` — validate here before any construction happens, so
    a bad combination fails with the same message regardless of the
    entry point instead of blowing up mid-build. `threads_params` marks
    callers that thread model parameters round-to-round (the fused
    training engine): those cannot honor `round_chunk > 1` because the
    chunk's rounds are solved in parallel, with no sequential carry for
    the params to ride."""
    if cfg.fresh_fleet and cfg.handover_delay:
        raise ValueError("handover_delay needs the persistent fleet's "
                         "coverage memory (fresh_fleet=False)")
    if cfg.fresh_fleet and cfg.handoff:
        raise ValueError("handoff moves vehicles between persistent "
                         "cells (fresh_fleet=False)")
    C = int(cfg.round_chunk)
    if C < 1:
        raise ValueError(f"round_chunk={C} must be >= 1")
    if C > 1:
        if threads_params:
            raise ValueError("fused_rollout threads params round-to-round "
                             "and cannot honor round_chunk > 1")
        if not cfg.fresh_fleet:
            raise ValueError("round_chunk > 1 requires fresh_fleet=True")
        if cfg.carry_queues:
            raise ValueError("round_chunk > 1 solves chunk rounds in "
                             "parallel and cannot thread carry_queues")
        if int(cfg.n_rounds) % C:
            raise ValueError(f"n_rounds={int(cfg.n_rounds)} not "
                             f"divisible by round_chunk={C}")


# bf16 storage lever (DESIGN.md §12): the FleetState fields that tolerate
# reduced-precision carry storage. Only the P4 warm-start table
# qualifies — and it is the field that matters: at [B, N, U, 1+U] it is
# ~95% of FleetState bytes (U = 10 makes it 110 floats per vehicle vs 5
# for everything else), and the solver re-projects and polishes from
# the seed, so quantization perturbs only the warm path's low bits.
# Every [B, N] world field stays a full-precision master: positions,
# speeds, jitter and allowances feed hard per-round thresholds
# (coverage radius, t_cp eligibility, energy budgets), where one bf16
# ulp measurably flips scheduling decisions — demoting them changes
# the simulated world, not just numeric noise.
FLEET_CAST_FIELDS = ("p4_tab",)


def cast_sched_state(state: SchedState, dtype) -> SchedState:
    """Demote the cast-tolerant fields of a persistent `FleetState` to
    `dtype` for carry storage. A `SchedulerCarry` (fresh mode) passes
    through untouched — its virtual queues ARE the masters. No-op when
    `dtype` is None."""
    if dtype is None or not isinstance(state, FleetState):
        return state
    return dataclasses.replace(state, **{
        f: getattr(state, f).astype(dtype) for f in FLEET_CAST_FIELDS})


def promote_sched_state(state: SchedState,
                        dtype=jnp.float32) -> SchedState:
    """Inverse of `cast_sched_state`: promote the stored fields back to
    the compute dtype at round start so every round's math runs fp32."""
    if not isinstance(state, FleetState):
        return state
    return dataclasses.replace(state, **{
        f: getattr(state, f).astype(dtype) for f in FLEET_CAST_FIELDS})


def round_keys(key: jax.Array, cfg: StreamConfig, n_rounds: int,
               r0: int = 0) -> jax.Array:
    """Per-round scheduling keys [n_rounds] — the xs of the rollout scan.

    Fresh-fleet mode uses the blocked path's exact per-round RNG schedule
    (`fold_in(key, r)` for the *absolute* round index); persistent mode
    splits the key once for the whole run. Segmented callers (e.g. the
    fused engine between eval points) build the full run's keys once and
    slice, so a segmented rollout replays the one-scan schedule.
    """
    if cfg.fresh_fleet:
        return jax.vmap(lambda r: jax.random.fold_in(key, r))(
            jnp.arange(r0, r0 + n_rounds))
    assert r0 == 0, "persistent mode: build the full run's keys and slice"
    return jax.random.split(key, n_rounds)


def sched_state0(key: jax.Array, sc: ScenarioParams, mob: ManhattanParams,
                 cfg: StreamConfig,
                 fleet: Optional[FleetState] = None,
                 ch: Optional[ChannelParams] = None) -> SchedState:
    """Initial scheduling-side scan carry: a zero `SchedulerCarry` in
    fresh-fleet mode, a (possibly freshly initialized) `FleetState` in
    persistent mode. `key` must be the same key later given to
    `round_keys` so a rollout is reproducible from its arguments.

    With `cfg.handoff` the default fleet's RSUs sit on the
    overlapping-coverage grid (`rsu_grid`) — the B cells share one road
    network, so independent random placements would make migration an
    accident of the draw. Pass an explicit `fleet` to override. `ch`
    seeds the P4 warm-start table at the rollout's actual `p_max`
    (defaulting keeps the §3 full-budget bit-for-bit-cold contract only
    for the default `ChannelParams`)."""
    if cfg.fresh_fleet:
        return _zero_carry(sc, int(cfg.batch))
    if fleet is None:
        rsu = rsu_grid(int(cfg.batch), mob) if cfg.handoff else None
        fleet = init_fleet(jax.random.fold_in(key, 0xF1EE7), sc, mob,
                           int(cfg.batch), n_fleet=cfg.n_fleet,
                           energy_horizon=cfg.energy_horizon, rsu_xy=rsu,
                           p_max=None if ch is None else ch.p_max)
    return fleet


def pack_cells(states, pad_to: Optional[int] = None) -> SchedState:
    """Concatenate per-session B=1 scheduling states (or any pytree with
    a leading cell axis — `RolloutCarry`, `FleetState`, `SchedulerCarry`)
    into one packed state along the `[B]` cell axis.

    The serving layer (DESIGN.md §13) keeps every client session as a
    B=1 state and gathers the scheduled batch's sessions into the packed
    program's cell axis per dispatch; `unpack_cell` slices each
    session's refreshed state back out on response. Cells of a packed
    persistent rollout never interact (no handoff in packed mode), so
    pack -> rollout -> unpack is bit-for-bit the solo B=1 rollout.

    `pad_to` packs at a tier occupancy larger than the live session
    count: the spare cell slots are filled with replicas of the first
    state. The caller must deactivate those slots (all-`False` per-cell
    active columns) so the replicas compute-and-discard."""
    states = list(states)
    if pad_to is not None:
        if pad_to < len(states):
            raise ValueError(f"pad_to={pad_to} smaller than the "
                             f"{len(states)} states to pack")
        states = states + [states[0]] * (pad_to - len(states))
    if len(states) == 1:
        return states[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *states)


def unpack_cell(state, b: int):
    """Slice cell `b` back out of a packed state as a B=1 state — the
    inverse of `pack_cells` for one session."""
    return jax.tree.map(lambda x: x[b:b + 1], state)


def warm_p4(sched: Scheduler, prm: VedsParams) -> bool:
    """Whether this rollout threads the P4 warm-start table: VEDS with
    cooperation enabled (the only scheduler that solves P4) and a
    nonzero warm budget. Persistent fleets only — fresh-fleet rounds
    draw independent channels, so there is no correlation to seed from."""
    return prm.ipm_warm_iters > 0 and bool(
        getattr(sched, "enable_cot", False))


@scope("sched")
def sched_round_step(state: SchedState, k: jax.Array, sched: Scheduler,
                     sc: ScenarioParams, mob: ManhattanParams,
                     ch: ChannelParams, prm: VedsParams, cfg: StreamConfig):
    """One round of scheduling inside the scan: advance the fleet (or
    draw a fresh one from `k`), run the scheduler with the carried
    queues, scatter queue/energy updates back. Returns
    (state', RoundOutputs).

    Persistent mode with `warm_p4(sched, prm)`: the per-vehicle P4
    warm-start table (`FleetState.p4_tab`) is gathered for this round's
    SOV slots, threaded through the scheduler (`SchedulerCarry.p4`), and
    the refreshed table scattered back under the same freeze rule as the
    virtual queue — only slots that actually played update.

    `k` may be one key per round (the rollout default) or a `[B]` batch
    of per-cell keys (the serving layer's packed sessions, DESIGN.md
    §13) — per-cell keys need the persistent fleet's per-cell RNG split
    (`fleet_round`), so fresh-fleet mode rejects them."""
    if cfg.fresh_fleet:
        if k.ndim != 0:
            raise ValueError("per-cell keys [B] need a persistent fleet "
                             "(fresh_fleet draws the whole batch from "
                             "one round key)")
        rnd = make_round_batch(k, sc, mob, ch, prm, int(cfg.batch),
                               hetero_fleet=cfg.hetero_fleet)
        out = sched.solve_round(rnd, prm, ch,
                                state if cfg.carry_queues else None)
        return out.carry, out

    if cfg.handoff:
        state = exchange_fleet(state, mob)
    fl, rnd, sel = fleet_round(k, state, sc, mob, ch, prm,
                               handover_delay=cfg.handover_delay,
                               handoff=cfg.handoff)
    B = fl.batch_size
    rows = jnp.arange(B)[:, None]
    qs_old = jnp.take_along_axis(fl.queue, sel.sov_idx, axis=1)
    qu_old = jnp.take_along_axis(fl.queue, sel.opv_idx, axis=1)
    warm = warm_p4(sched, prm)
    p4_old = fl.p4_tab[rows, sel.sov_idx] if warm else None  # [B,S,U,1+U]
    if cfg.carry_queues:
        c_in = SchedulerCarry(qs=qs_old, qu=qu_old, p4=p4_old)
    elif warm:
        # warm table without queue carry: queues start at zero each
        # round (seed semantics), only the P4 seeds thread through
        c_in = SchedulerCarry(qs=jnp.zeros_like(qs_old),
                              qu=jnp.zeros_like(qu_old), p4=p4_old)
    else:
        c_in = None
    out = sched.solve_round(rnd, prm, ch, c_in)
    # Freeze/restore (module doc): round-end queues scatter back ONLY to
    # the fleet slots that actually played this round — a vehicle in a
    # padded selection slot (valid_* False) keeps its frozen queue, and
    # unselected vehicles are never written at all. The frozen value is
    # what the gather above restores when the vehicle is re-admitted;
    # under handoff it already migrated with the vehicle in
    # exchange_fleet. Batteries likewise drain only by energy actually
    # spent (valid slots).
    queue = fl.queue
    if cfg.carry_queues:
        queue = queue.at[rows, sel.sov_idx].set(
            jnp.where(rnd.valid_sov, out.carry.qs, qs_old))
        queue = queue.at[rows, sel.opv_idx].set(
            jnp.where(rnd.valid_opv, out.carry.qu, qu_old))
    p4_tab = fl.p4_tab
    if warm:
        p4_tab = p4_tab.at[rows, sel.sov_idx].set(
            jnp.where(rnd.valid_sov[..., None, None],
                      out.carry.p4, p4_old))
    energy = fl.energy.at[rows, sel.sov_idx].add(
        -jnp.where(rnd.valid_sov, out.energy_sov, 0.0))
    energy = energy.at[rows, sel.opv_idx].add(
        -jnp.where(rnd.valid_opv, out.energy_opv, 0.0))
    fl = dataclasses.replace(fl, queue=queue, p4_tab=p4_tab,
                             energy=jnp.maximum(energy, 0.0))
    return fl, out


def stream_rounds(key: jax.Array, sched: Scheduler, sc: ScenarioParams,
                  mob: ManhattanParams, ch: ChannelParams, prm: VedsParams,
                  cfg: StreamConfig,
                  fleet: Optional[FleetState] = None) -> StreamResult:
    """Roll out `cfg.n_rounds` FL rounds of `cfg.batch` cells as one
    `lax.scan` XLA program. Resumable: pass the returned `fleet` (and
    seed the queues via `fleet.queue`) to continue a rollout.
    """
    B = int(cfg.batch)
    R = int(cfg.n_rounds)
    validate_stream_config(cfg)
    if int(cfg.round_chunk) > 1:
        return _stream_fresh_chunked(key, sched, sc, mob, ch, prm, cfg,
                                     B, R)
    state0 = sched_state0(key, sc, mob, cfg, fleet, ch)
    state, outs = jax.lax.scan(
        lambda s, k: sched_round_step(s, k, sched, sc, mob, ch, prm, cfg),
        state0, round_keys(key, cfg, R))
    if cfg.fresh_fleet:
        return StreamResult(outputs=outs, fleet=None, carry=state)
    return StreamResult(outputs=outs, fleet=state,
                        carry=jax.tree.map(lambda x: x[-1], outs.carry))


def _stream_fresh_chunked(key, sched, sc, mob, ch, prm, cfg: StreamConfig,
                          B: int, R: int) -> StreamResult:
    """Fresh-fleet mode with `round_chunk = C > 1`: the scan runs R / C
    steps, each drawing C rounds' cells (per-round RNG schedule intact:
    cell block j of chunk c is round c * C + j) and solving them as ONE
    widened [C * B] batch — the P4 interior-point candidate solves are
    batched across rounds, which is what makes full VEDS+COT streaming
    tractable. Incompatible with `carry_queues` (rounds inside a chunk
    are solved in parallel, so queues cannot thread through them); every
    flag rejection lives in `validate_stream_config`, which the caller
    already ran."""
    C = int(cfg.round_chunk)

    def body(carry, c0):
        rs = c0 * C + jnp.arange(C)
        keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(rs)
        rnds = jax.vmap(lambda k: make_round_batch(
            k, sc, mob, ch, prm, B, hetero_fleet=cfg.hetero_fleet))(keys)
        wide = jax.tree.map(
            lambda x: x.reshape((C * B,) + x.shape[2:]), rnds)
        out = sched.solve_round(wide, prm, ch, None)
        out = jax.tree.map(lambda x: x.reshape((C, B) + x.shape[1:]), out)
        return carry, out

    _, outs = jax.lax.scan(body, jnp.zeros((), jnp.int32),
                           jnp.arange(R // C))
    outs = jax.tree.map(
        lambda x: x.reshape((R,) + x.shape[2:]), outs)
    return StreamResult(outputs=outs, fleet=None,
                        carry=jax.tree.map(lambda x: x[-1], outs.carry))
