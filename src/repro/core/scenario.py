"""Scenario generation: mobility rollout + channel draws -> RoundInputs.

This is the simulation substrate behind every paper figure: a fleet of
vehicles on the Manhattan grid; per round, the first S in-coverage vehicles
are SOVs (they hold data and train) and the next U are OPVs (relays).

`make_round` builds one cell ([T, ...] layout); `make_round_batch` rolls
out B cells with independent RSU placements, heterogeneous fleet sizes via
padding + validity masks, and per-cell energy/clock draws — the batched
[B, T, ...] layout every scheduler consumes in one XLA program. Both draw
an *independent* fleet per call.

The streaming engine instead threads a persistent `FleetState`
round-to-round: `init_fleet` seeds a pool of vehicles per cell,
`fleet_round` drives them for one round's worth of slots and re-selects
SOVs/OPVs from the vehicles in coverage (padding + `valid_*` masks when
fewer than S/U qualify), and `rollout_rounds` scans that into an
`[R, B, T, ...]` block of time-correlated rounds. See DESIGN.md §9.

Multi-RSU handoff (DESIGN.md §11): when the B cells are B RSUs on one
shared road network (`rsu_grid` builds an overlapping-coverage grid),
`exchange_fleet` re-assigns every vehicle to its nearest RSU between
rounds — a fixed-shape gather/scatter over the `[B, N]` slot layout
that migrates the vehicle's full state (position, speed, battery,
virtual queue, `covered` flag) to the new cell, capacity-limited with
overflow vehicles parked out of coverage so the program stays one XLA
dispatch.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.channel.mobility import (ManhattanParams, init_mobility,
                                    rollout_positions)
from repro.channel.v2x import ChannelParams, channel_gain
from repro.core.lyapunov import VedsParams
from repro.core.solver import p4_seed_table
from repro.core.veds import RoundInputs
from repro.obs import scope


@dataclasses.dataclass(frozen=True)
class ScenarioParams:
    n_sov: int = 10
    n_opv: int = 10
    n_slots: int = 100
    n_flop: float = 2.0e7        # FLOPs per sample (paper's computation model)
    batch_size: int = 32
    clock_hz: float = 1.0e9      # vehicle processor clock
    rho: float = 1.0e-28         # energy coefficient (Table I)
    e_min: float = 0.05          # energy budget low [J]  (Table I)
    e_max: float = 0.10          # energy budget high [J]


def compute_model(sc: ScenarioParams) -> Tuple[float, float]:
    """Returns (t_cp, e_cp) for the standard computation model."""
    work = sc.n_flop * sc.batch_size
    t_cp = work / sc.clock_hz
    e_cp = sc.rho * sc.clock_hz ** 2 * work
    return t_cp, e_cp


def _cell_fields(key: jax.Array, sc: ScenarioParams, mob: ManhattanParams,
                 ch: ChannelParams, prm: VedsParams,
                 rsu_xy: jax.Array) -> Dict[str, jax.Array]:
    """One cell's gains/budgets around a (possibly traced) RSU position."""
    S, U, T = sc.n_sov, sc.n_opv, sc.n_slots
    k_mob, k_ch, k_e, k_cp = jax.random.split(key, 4)
    st = init_mobility(k_mob, S + U, mob, rsu_xy=rsu_xy)
    _, traj = rollout_positions(jax.random.fold_in(k_mob, 1), st, mob, T,
                                prm.slot)                       # [T,N,2]
    d_rsu = jnp.linalg.norm(traj - rsu_xy, axis=-1)             # [T,N]
    cov = d_rsu <= mob.coverage
    d_sov_opv = jnp.linalg.norm(
        traj[:, :S, None, :] - traj[:, None, S:, :], axis=-1)   # [T,S,U]

    ks = jax.random.split(k_ch, 3)
    g_sr = channel_gain(ks[0], d_rsu[:, :S], ch, in_range=cov[:, :S])
    g_or = channel_gain(ks[1], d_rsu[:, S:], ch, in_range=cov[:, S:])
    g_so = channel_gain(ks[2], d_sov_opv, ch)

    t_cp_s, e_cp_s = compute_model(sc)
    # small heterogeneity across vehicles in clock speed
    jitter = jax.random.uniform(k_cp, (S,), minval=0.8, maxval=1.2)
    t_cp = t_cp_s / jitter
    e_cp = e_cp_s * jitter ** 2
    e_sov = jax.random.uniform(k_e, (S,), minval=sc.e_min, maxval=sc.e_max)
    e_opv = jax.random.uniform(jax.random.fold_in(k_e, 1), (U,),
                               minval=sc.e_min, maxval=sc.e_max)
    return dict(g_sr=g_sr, g_or=g_or, g_so=g_so, t_cp=t_cp,
                e_cp=e_cp, e_sov=e_sov, e_opv=e_opv)


def make_round(key: jax.Array, sc: ScenarioParams, mob: ManhattanParams,
               ch: ChannelParams, prm: VedsParams) -> RoundInputs:
    """One round's gains/budgets. Vehicles: [0:S] SOVs, [S:S+U] OPVs."""
    return RoundInputs(**_cell_fields(key, sc, mob, ch, prm,
                                      jnp.asarray(mob.rsu_xy)))


def make_round_batch(key: jax.Array, sc: ScenarioParams,
                     mob: ManhattanParams, ch: ChannelParams,
                     prm: VedsParams, batch: int, *,
                     hetero_fleet: bool = True,
                     rsu_xy: Optional[jax.Array] = None) -> RoundInputs:
    """B cells in one batched RoundInputs ([B, T, ...] layout).

    Each cell gets an independent RSU placement (uniform over the central
    half of the road network unless `rsu_xy` [B,2] is given), independent
    mobility/channel/energy/clock draws, and — with `hetero_fleet` — a
    heterogeneous fleet size: cell b has s_b in [ceil(S/2), S] real SOVs
    and u_b in [ceil(U/2), U] real OPVs, the rest being padding. Padded
    vehicles carry zero gains, zero budgets and `valid_*` False, so every
    scheduler ignores them and `n_success` counts only real SOVs.
    """
    B = int(batch)
    S, U = sc.n_sov, sc.n_opv
    k_cell, k_rsu, k_s, k_u = jax.random.split(key, 4)
    if rsu_xy is None:
        rsu = jax.random.uniform(k_rsu, (B, 2), minval=0.25 * mob.extent,
                                 maxval=0.75 * mob.extent)
    else:
        rsu = jnp.broadcast_to(jnp.asarray(rsu_xy, jnp.float32), (B, 2))
    keys = jax.random.split(k_cell, B)
    fields = jax.vmap(
        lambda k, r: _cell_fields(k, sc, mob, ch, prm, r))(keys, rsu)

    if hetero_fleet:
        s_cnt = jax.random.randint(k_s, (B,), (S + 1) // 2, S + 1)
        u_cnt = jax.random.randint(k_u, (B,), (U + 1) // 2, U + 1)
        valid_sov = jnp.arange(S)[None] < s_cnt[:, None]        # [B,S]
        valid_opv = jnp.arange(U)[None] < u_cnt[:, None]        # [B,U]
    else:
        valid_sov = jnp.ones((B, S), bool)
        valid_opv = jnp.ones((B, U), bool)

    vs, vo = valid_sov[:, None, :], valid_opv[:, None, :]       # [B,1,·]
    return RoundInputs(
        g_sr=fields["g_sr"] * vs,
        g_or=fields["g_or"] * vo,
        g_so=fields["g_so"] * (valid_sov[:, None, :, None]
                               & valid_opv[:, None, None, :]),
        t_cp=fields["t_cp"] * valid_sov,
        e_cp=fields["e_cp"] * valid_sov,
        e_sov=fields["e_sov"] * valid_sov,
        e_opv=fields["e_opv"] * valid_opv,
        valid_sov=valid_sov, valid_opv=valid_opv)


# ---------------------------------------------------------------------------
# Persistent fleets for the streaming multi-round engine (DESIGN.md §9)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FleetState:
    """Per-cell vehicle pool threaded round-to-round by the streaming
    engine. N is the pool size (>= S + U); all fields are batched [B, ...].

      pos [B,N,2], dir [B,N], speed [B,N]  mobility state (resumable)
      jitter [B,N]     persistent clock-speed heterogeneity (0.8..1.2)
      allowance [B,N]  per-round energy budget draw [J] (e_min..e_max)
      energy [B,N]     residual battery [J]; +inf when not tracked
      queue [B,N]      per-vehicle virtual energy queue (eqs. 19-20),
                       gathered into the scheduler carry for whichever
                       role the vehicle plays this round
      rsu_xy [B,2]     static RSU placement per cell
      covered [B,N]    bool: in coverage at the *previous* round start —
                       with `handover_delay`, vehicles entering coverage
                       mid-round become eligible only the next round
      cell_id [B,N]    int32: the RSU this vehicle is associated with.
                       Without handoff this is constantly the row index.
                       `exchange_fleet` rewrites it: an admitted vehicle
                       in row b has cell_id == b; a capacity-overflow
                       vehicle is parked with cell_id == -1 (ineligible
                       until a later exchange re-admits it)
      p4_tab [B,N,U,1+U]  P4 warm-start table: the last interior-point
                       optima solved with this vehicle as the SOV
                       (sorted-prefix candidate layout, DESIGN.md §3).
                       Seeded with the solver's cold starting point,
                       gathered/scattered by the streaming engine only
                       when `VedsParams.ipm_warm_iters > 0`, and —
                       like the virtual queue — it migrates with the
                       vehicle under handoff.
    """
    pos: jax.Array
    dir: jax.Array
    speed: jax.Array
    jitter: jax.Array
    allowance: jax.Array
    energy: jax.Array
    queue: jax.Array
    rsu_xy: jax.Array
    covered: jax.Array
    cell_id: jax.Array
    p4_tab: jax.Array

    @property
    def batch_size(self) -> int:
        return self.pos.shape[0]

    @property
    def n_vehicles(self) -> int:
        return self.pos.shape[1]


class FleetSelection(NamedTuple):
    """Round role assignment: fleet indices of this round's SOVs/OPVs."""
    sov_idx: jax.Array   # [B, S]
    opv_idx: jax.Array   # [B, U]


def init_fleet(key: jax.Array, sc: ScenarioParams, mob: ManhattanParams,
               batch: int, *, n_fleet: Optional[int] = None,
               rsu_xy: Optional[jax.Array] = None,
               energy_horizon: Optional[float] = None,
               p_max: Optional[float] = None) -> FleetState:
    """Seed B persistent vehicle pools of `n_fleet` vehicles each.

    `energy_horizon = H` gives every vehicle a battery of H rounds' worth
    of its per-round allowance; None disables battery tracking (+inf).
    RSU placements are drawn like `make_round_batch`'s unless given.
    `p_max` seeds the P4 warm-start table (default: `ChannelParams`'s);
    a warm solve from the seed at the full budget is bit-for-bit cold.
    """
    B = int(batch)
    N = int(n_fleet) if n_fleet is not None else 2 * (sc.n_sov + sc.n_opv)
    if N < sc.n_sov + sc.n_opv:
        raise ValueError(f"n_fleet={N} < S + U = {sc.n_sov + sc.n_opv}")
    k_cell, k_rsu, k_j, k_a = jax.random.split(key, 4)
    if rsu_xy is None:
        rsu = jax.random.uniform(k_rsu, (B, 2), minval=0.25 * mob.extent,
                                 maxval=0.75 * mob.extent)
    else:
        rsu = jnp.broadcast_to(jnp.asarray(rsu_xy, jnp.float32), (B, 2))
    st = jax.vmap(lambda k, r: init_mobility(k, N, mob, rsu_xy=r))(
        jax.random.split(k_cell, B), rsu)
    jitter = jax.random.uniform(k_j, (B, N), minval=0.8, maxval=1.2)
    allowance = jax.random.uniform(k_a, (B, N), minval=sc.e_min,
                                   maxval=sc.e_max)
    energy = (jnp.full((B, N), jnp.inf) if energy_horizon is None
              else allowance * float(energy_horizon))
    covered = jnp.linalg.norm(st["pos"] - rsu[:, None], axis=-1) \
        <= mob.coverage
    cell_id = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None],
                               (B, N))
    U = sc.n_opv
    p4_tab = p4_seed_table((B, N, U, U + 1),
                           ChannelParams().p_max if p_max is None
                           else float(p_max))
    return FleetState(pos=st["pos"], dir=st["dir"], speed=st["speed"],
                      jitter=jitter, allowance=allowance, energy=energy,
                      queue=jnp.zeros((B, N)), rsu_xy=rsu, covered=covered,
                      cell_id=cell_id, p4_tab=p4_tab)


def rsu_grid(batch: int, mob: ManhattanParams, *,
             pitch: Optional[float] = None) -> jax.Array:
    """[B,2] RSU placements on a square grid over the road network.

    The default pitch (`0.75 * coverage`) puts neighboring RSUs well
    inside each other's coverage radius — the overlapping-coverage
    multi-RSU topology the handoff machinery is built for: a vehicle
    leaving one cell is usually already coverable by the next. When the
    grid would overrun the road network, the pitch shrinks to fit (even
    more overlap) so RSU positions stay distinct — clipping would stack
    duplicate RSUs on the boundary, and `exchange_fleet`'s argmin would
    then starve every higher-indexed duplicate cell.
    """
    B = int(batch)
    g = int(jnp.ceil(jnp.sqrt(B)))
    rows = (B + g - 1) // g
    p = float(pitch) if pitch is not None else 0.75 * mob.coverage
    span = max(g - 1, rows - 1, 1)
    p = min(p, mob.extent / span)
    idx = jnp.arange(B)
    gx, gy = (idx % g).astype(jnp.float32), (idx // g).astype(jnp.float32)
    x = 0.5 * mob.extent + (gx - 0.5 * (g - 1)) * p
    y = 0.5 * mob.extent + (gy - 0.5 * (rows - 1)) * p
    return jnp.stack([x, y], -1)


def migrated_fraction(fleet0: FleetState, fleet1: FleetState) -> float:
    """Fraction of vehicles whose cell (row) differs between two fleet
    snapshots, tracking identity by the persistent per-vehicle `jitter`
    value — `exchange_fleet` permutes it with the vehicle and nothing
    rewrites it, so it serves as a tag (random draws: collisions have
    measure zero; tests inject unique tags outright)."""
    import numpy as np
    j0, j1 = np.asarray(fleet0.jitter), np.asarray(fleet1.jitter)
    B = j1.shape[0]
    row_of = {float(t): b for b in range(B) for t in j1[b]}
    return float(np.mean([[row_of[float(t)] != b for t in j0[b]]
                          for b in range(B)]))


@scope("exchange")
def exchange_fleet(fleet: FleetState, mob: ManhattanParams) -> FleetState:
    """Cross-cell vehicle exchange: hand every vehicle to its nearest RSU.

    The B cells are read as B RSUs (`fleet.rsu_xy`) on one shared road
    network. Each of the M = B * N vehicles targets the cell of its
    nearest RSU (`argmin` over cells); the full per-vehicle state —
    position, heading, speed, jitter, allowance, residual battery,
    virtual queue, P4 warm-start table, `covered` flag — migrates to a
    slot of the target
    row via one fixed-shape gather (a permutation of the flat [M]
    layout), so shapes stay static and the whole exchange is a few
    vector ops inside the rollout scan. No RNG is consumed.

    Capacity policy: a cell admits at most N vehicles, first-come by
    flat (cell, slot) order; the overflow fills the rows left short, in
    row-major order, parked with `cell_id = -1` and `covered = False` —
    out of coverage as far as role selection is concerned, state frozen
    until a later exchange re-admits them. Since overflow count always
    equals free-slot count, the mapping is a bijection: no vehicle is
    ever duplicated or lost.

    Handover latency: a vehicle that changed cells gets
    `covered = False`, so under `handover_delay` a migrant sits out
    exactly one round in its new cell before becoming eligible (without
    the delay flag, `covered` is refreshed at round start and migration
    costs nothing).

    For B = 1 the exchange is the identity permutation — `handoff=True`
    is then bit-for-bit `handoff=False`.
    """
    B, N = fleet.batch_size, fleet.n_vehicles
    M = B * N

    def flat(x):
        return x.reshape((M,) + x.shape[2:])

    pos = flat(fleet.pos)                                       # [M,2]
    dist = jnp.linalg.norm(pos[:, None] - fleet.rsu_xy[None], axis=-1)
    tgt = jnp.argmin(dist, axis=-1).astype(jnp.int32)           # [M]
    src_cell = flat(jnp.broadcast_to(
        jnp.arange(B, dtype=jnp.int32)[:, None], (B, N)))       # [M]
    moved = tgt != src_cell

    # stable sort by target cell: vehicles for cell 0 first, then 1, ...
    order = jnp.argsort(tgt, stable=True).astype(jnp.int32)     # [M]
    tgt_s = tgt[order]
    counts = jnp.zeros((B,), jnp.int32).at[tgt].add(1)          # [B]
    start = jnp.cumsum(counts) - counts
    rank = jnp.arange(M, dtype=jnp.int32) - start[tgt_s]        # in-cell
    admitted = rank < N

    # overflow <-> free-slot bijection (|overflow| == |free| == M - sum
    # min(counts, N)): the o-th overflow vehicle (sorted order) takes the
    # o-th free slot (row-major), found by inverting the running count of
    # free slots per cell
    filled = jnp.minimum(counts, N)
    free_before = jnp.cumsum(N - filled) - (N - filled)         # [B]
    ovf_ord = jnp.cumsum(~admitted) - 1                         # [M]
    c_of = jnp.clip(jnp.searchsorted(free_before, ovf_ord,
                                     side="right") - 1, 0, B - 1)
    j_of = filled[c_of] + (ovf_ord - free_before[c_of])
    dest = jnp.where(admitted, tgt_s * N + rank,
                     c_of * N + j_of).astype(jnp.int32)         # [M] perm

    # invert: which source vehicle lands in each flat slot
    src_of_slot = jnp.zeros((M,), jnp.int32).at[dest].set(order)
    cell_id = jnp.zeros((M,), jnp.int32).at[dest].set(
        jnp.where(admitted, tgt_s, -1)).reshape(B, N)

    def take(x):
        return flat(x)[src_of_slot].reshape((B, N) + x.shape[2:])

    covered = take(fleet.covered) & ~moved[src_of_slot].reshape(B, N) \
        & (cell_id >= 0)
    return FleetState(pos=take(fleet.pos), dir=take(fleet.dir),
                      speed=take(fleet.speed), jitter=take(fleet.jitter),
                      allowance=take(fleet.allowance),
                      energy=take(fleet.energy), queue=take(fleet.queue),
                      rsu_xy=fleet.rsu_xy, covered=covered,
                      cell_id=cell_id, p4_tab=take(fleet.p4_tab))


def _fleet_cell_round(key: jax.Array, pos, d, speed, jitter, allowance,
                      energy, rsu_xy, covered_prev, active,
                      sc: ScenarioParams,
                      mob: ManhattanParams, ch: ChannelParams,
                      prm: VedsParams, handover_delay: bool = False):
    """One cell, one round: drive the pool T slots, select roles by
    coverage at round start, draw channels for the selected vehicles.

    With `handover_delay`, a vehicle is eligible only if it was already
    in coverage at the *previous* round start (`covered_prev`): vehicles
    entering coverage mid-round sit out the round after their handover
    completes and join the round after (one-round lag). `active` gates
    eligibility further — under handoff it excludes vehicles parked by
    the capacity policy (`cell_id == -1`); without handoff it is all
    True and a no-op."""
    S, U, T = sc.n_sov, sc.n_opv, sc.n_slots
    k_mob, k_ch = jax.random.split(key)
    st, traj = rollout_positions(k_mob, {"pos": pos, "dir": d,
                                         "speed": speed}, mob, T, prm.slot)
    # coverage-driven re-selection: eligible vehicles first (stable sort
    # keeps index order, so vehicles keep their role while they stay in
    # coverage); the first S are SOVs, the next U are OPVs
    cov0 = (jnp.linalg.norm(pos - rsu_xy, axis=-1) <= mob.coverage) \
        & active
    elig = cov0 & covered_prev if handover_delay else cov0
    order = jnp.argsort(jnp.where(elig, 0, 1), stable=True)
    sov_idx, opv_idx = order[:S], order[S:S + U]
    valid_sov, valid_opv = elig[sov_idx], elig[opv_idx]

    traj_s, traj_u = traj[:, sov_idx], traj[:, opv_idx]     # [T,S,2]/[T,U,2]
    d_rsu_s = jnp.linalg.norm(traj_s - rsu_xy, axis=-1)     # [T,S]
    d_rsu_u = jnp.linalg.norm(traj_u - rsu_xy, axis=-1)     # [T,U]
    cov_s = (d_rsu_s <= mob.coverage) & valid_sov[None]
    cov_u = (d_rsu_u <= mob.coverage) & valid_opv[None]
    d_so = jnp.linalg.norm(traj_s[:, :, None] - traj_u[:, None], axis=-1)

    ks = jax.random.split(k_ch, 3)
    g_sr = channel_gain(ks[0], d_rsu_s, ch, in_range=cov_s)
    g_or = channel_gain(ks[1], d_rsu_u, ch, in_range=cov_u)
    g_so = channel_gain(ks[2], d_so, ch) \
        * (valid_sov[None, :, None] & valid_opv[None, None, :])

    t_cp_s, e_cp_s = compute_model(sc)
    jit_s = jitter[sov_idx]
    budget = jnp.minimum(allowance, jnp.maximum(energy, 0.0))
    rnd = RoundInputs(
        g_sr=g_sr, g_or=g_or, g_so=g_so,
        t_cp=(t_cp_s / jit_s) * valid_sov,
        e_cp=(e_cp_s * jit_s ** 2) * valid_sov,
        e_sov=budget[sov_idx] * valid_sov,
        e_opv=budget[opv_idx] * valid_opv,
        valid_sov=valid_sov, valid_opv=valid_opv)
    return st, rnd, sov_idx, opv_idx, cov0


@scope("fleet")
def fleet_round(key: jax.Array, fleet: FleetState, sc: ScenarioParams,
                mob: ManhattanParams, ch: ChannelParams,
                prm: VedsParams, *,
                handover_delay: bool = False,
                handoff: bool = False
                ) -> Tuple[FleetState, RoundInputs, FleetSelection]:
    """Advance every cell's pool one round and build the batched
    RoundInputs for the selected SOVs/OPVs. Queue/energy fields of the
    returned FleetState are untouched — the streaming engine scatters the
    scheduler's outputs back (see `repro.core.streaming`); `covered` is
    refreshed to this round's start-of-round coverage.

    With `handoff`, vehicles parked by `exchange_fleet`'s capacity
    policy (`cell_id == -1`) are ineligible for role selection; the
    caller is expected to have run `exchange_fleet` first.

    `key` may be one key (split into B per-cell keys, the rollout
    default) or a `[B]` batch of per-cell keys — the serving layer packs
    independent sessions into the cell axis, each bringing its own round
    key. A batched cell b consumes `split(key[b], 1)[0]`, exactly what
    the scalar path hands cell 0 at B = 1, so a packed cell is
    bit-for-bit the same request run alone (DESIGN.md §13)."""
    B = fleet.batch_size
    if key.ndim == 0:
        keys = jax.random.split(key, B)
    else:
        keys = jax.vmap(lambda k: jax.random.split(k, 1)[0])(key)
    active = (fleet.cell_id >= 0 if handoff
              else jnp.ones(fleet.covered.shape, bool))
    st, rnd, sov_idx, opv_idx, cov0 = jax.vmap(
        lambda k, p, d, s, j, a, e, r, c, m: _fleet_cell_round(
            k, p, d, s, j, a, e, r, c, m, sc, mob, ch, prm,
            handover_delay=handover_delay))(
        keys, fleet.pos, fleet.dir, fleet.speed, fleet.jitter,
        fleet.allowance, fleet.energy, fleet.rsu_xy, fleet.covered,
        active)
    new_fleet = dataclasses.replace(fleet, pos=st["pos"], dir=st["dir"],
                                    speed=st["speed"], covered=cov0)
    return new_fleet, rnd, FleetSelection(sov_idx, opv_idx)


def rollout_rounds(key: jax.Array, fleet: FleetState, sc: ScenarioParams,
                   mob: ManhattanParams, ch: ChannelParams, prm: VedsParams,
                   n_rounds: int, *, handover_delay: bool = False,
                   handoff: bool = False
                   ) -> Tuple[FleetState, RoundInputs, FleetSelection]:
    """R resumable rounds of one persistent fleet, as one scan: returns
    (final fleet, RoundInputs [R, B, T, ...], FleetSelection [R, B, ...]).

    This is the scenario-layer view of the streaming engine — scheduling
    not included (use `repro.core.streaming.stream_rounds` to fuse it).
    With `handoff`, each scan step runs the §11 cross-cell exchange
    before the round."""
    def body(fl, k):
        if handoff:
            fl = exchange_fleet(fl, mob)
        fl, rnd, sel = fleet_round(k, fl, sc, mob, ch, prm,
                                   handover_delay=handover_delay,
                                   handoff=handoff)
        return fl, (rnd, sel)
    fleet, (rnds, sels) = jax.lax.scan(
        body, fleet, jax.random.split(key, n_rounds))
    return fleet, rnds, sels
