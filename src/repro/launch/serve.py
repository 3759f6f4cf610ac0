"""Scheduling-as-a-service: continuous batching of per-cell rollout
requests under live traffic (DESIGN.md §13).

The paper's VEDS algorithm is an *online* scheduler: each round the edge
must answer "which vehicles upload, with what cooperation and power"
against the current fleet state, under latency pressure. This module
serves that question. Many concurrent clients submit per-cell
scheduling/rollout requests (`ServeRequest`: a session id, a round
count, a seed); a `BatchServer` packs the requests that arrive within a
configurable batching window into the `[B]` cell axis of ONE compiled
fused program (`repro.fl.engine.fused_rollout` via the simulator's
lru-cached jitted segment) and slices each client's results back out.

Cost is proportional to *requested* work, not padded work: instead of
ONE `[L, B]` executable that pads every request to the worst-case
horizon and occupancy, the service compiles a small ladder of tiers —
horizons `ServeConfig.tiers` x occupancy buckets
`ServeConfig.batch_tiers` — and routes each window's batch to the
smallest tier that fits its max `n_rounds` and its request count
(`warmup()` pays each trace once). A 5-round request on an L=64 single
program burns ~92% of its compute on inactive no-op rounds; on an L=8
tier it burns ~37%. `ServeMetrics.pad_frac_rounds`/`pad_frac_cells` and
per-tier hit counts make the saving observable, not inferred.

Session state is bounded, not an unbounded host dict of device arrays:
`SessionStore` keeps at most `ServeConfig.max_sessions` sessions
device-resident (LRU), spilling cold `RolloutCarry`s to host numpy
(device->host, `checkpoint/np_ckpt`-style) and restoring them bitwise on
the session's next request — 10^4+ sessions no longer pin device memory.

Exactness contract: a packed cell is bit-for-bit the same request run
alone at B = 1 — at ANY tier (the tier only changes how much padding is
computed-and-discarded around it). Three pieces make that hold (pinned
in `tests/test_serve.py`):

  per-cell keys      the packed program's `keys [L, B]` gives every cell
                     its own request's round-key column; `fleet_round`
                     consumes batched keys exactly as the scalar B = 1
                     path does (`split(k, 1)[0]` per cell).
  per-cell active    requests of ragged round counts pack at the common
                     compiled horizon L = `ServeConfig.max_rounds`:
                     `active [L, B]` keeps cell b live for its own R_b
                     rounds; inactive (and padding) cells compute and
                     discard, their carry passing through untouched.
  session cache      each session's state — persistent fleet with the
                     PR-5 P4 warm-start table (`FleetState.p4_tab`),
                     model params, optimizer state — lives server-side
                     as a B=1 `RolloutCarry`, gathered into the packed
                     batch (`pack_cells`) and scattered back on response
                     (`unpack_cell`): the per-client KV-cache analogue.
                     Repeat clients therefore ride the warm-IPM path
                     (~2.5x rounds/s for VEDS+COT) across requests.

Observability: `ServeMetrics` decomposes every request into queue-wait /
compute / total latency and tracks batch occupancy; `summary()` reports
p50/p99 latency, aggregate rounds/s, and mean occupancy. `poisson_load`
(open-loop arrivals) and `closed_loop_load` (saturating: one request in
flight per client) drive the fig4 `serve_sweep`.

  PYTHONPATH=src python -m repro.launch.serve --clients 8 --batch 8
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import concurrent.futures
import dataclasses
import functools
import json
import sys
import time
import zlib
from typing import (Any, Dict, Iterator, List, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.channel.mobility import ManhattanParams
from repro.channel.v2x import ChannelParams
from repro.core.lyapunov import VedsParams
from repro.core.scenario import ScenarioParams
from repro.core.scheduler import RolloutCarry
from repro.core.streaming import StreamConfig, pack_cells, unpack_cell
# the engine's tier-keyed segment cache IS the server's compiled-program
# ladder: sharing it means a service, the simulator, and a test with
# matching shapes share one executable per (occupancy entry, horizon)
from repro.fl.engine import ClientShards, fused_segment, init_carry
from repro.obs import span


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static service configuration (fixes the compiled tier ladder).

      batch        B: max packed cell slots per dispatch
      max_rounds   L: compiled round horizon; requests with fewer rounds
                   pad with inactive tail rounds, more are rejected.
                   Ignored when `tiers` is set (the ladder's max wins)
      tiers        optional ascending horizon ladder, e.g. (8, 32, 128):
                   each batch routes to the smallest horizon >= its max
                   `n_rounds`, so short requests stop paying for the
                   worst case's padding. None = the single `max_rounds`
                   horizon (the PR-7 behavior)
      batch_tiers  optional ascending occupancy ladder (max must equal
                   `batch`): each batch routes to the smallest bucket
                   >= its request count. None = powers of two up to
                   `batch` when `tiers` is set, else the single full
                   `batch`
      max_sessions optional bound on DEVICE-resident sessions: beyond
                   it the LRU session's carry spills to host numpy and
                   restores bitwise on its next request. None = every
                   session stays on device (the PR-7 behavior)
      window_s     batching window: after the first request of a batch
                   arrives, how long the server waits for more
      bucket_rounds round-count-aware window formation: the BatchServer
                   splits a collected window by horizon rung before
                   routing, so one long request no longer drags every
                   short co-arrival up to its padded horizon (each
                   bucket dispatches to its own smallest tier, shortest
                   first). Off = one dispatch per window, routed to the
                   max rung (the PR-8 behavior)
    """
    batch: int = 4
    max_rounds: int = 4
    tiers: Optional[Tuple[int, ...]] = None
    batch_tiers: Optional[Tuple[int, ...]] = None
    max_sessions: Optional[int] = None
    bucket_rounds: bool = True
    window_s: float = 0.002
    scheduler: str = "madca"
    n_sov: int = 4
    n_opv: int = 3
    n_slots: int = 10
    batch_size: int = 8          # minibatch size per selected client
    n_clients: int = 10          # default service-wide dataset size
    n_fleet: Optional[int] = None
    carry_queues: bool = True
    ipm_warm_iters: int = 0      # VEDS+COT: warm P4 budget per candidate
    ipm_iters: Optional[int] = None
    lr: float = 0.05
    alpha: float = 2.0
    V: float = 0.2
    q_bits: float = 1e7
    seed: int = 0

    @property
    def horizons(self) -> Tuple[int, ...]:
        """The ascending horizon ladder (a single rung without tiers)."""
        if self.tiers is None:
            return (int(self.max_rounds),)
        return tuple(sorted({int(t) for t in self.tiers}))

    @property
    def occupancies(self) -> Tuple[int, ...]:
        """The ascending occupancy ladder. Defaults to powers of two up
        to `batch` when horizon tiers are on (partial windows then pay
        for their bucket, not for B), else the single full `batch`."""
        B = int(self.batch)
        if self.batch_tiers is not None:
            return tuple(sorted({int(b) for b in self.batch_tiers}))
        if self.tiers is None:
            return (B,)
        ladder = []
        b = 1
        while b < B:
            ladder.append(b)
            b *= 2
        return tuple(ladder) + (B,)


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    """One client request: roll `n_rounds` scheduling+training rounds of
    the session's cell forward, with RNG derived from `seed`."""
    session: str
    n_rounds: int
    seed: int = 0


@dataclasses.dataclass
class ServeResponse:
    """Per-request results sliced out of the packed dispatch, plus the
    request's latency decomposition (filled by `BatchServer`)."""
    session: str
    n_rounds: int
    success: np.ndarray          # [R, S] bool upload-success masks
    n_success: np.ndarray        # [R]
    loss: np.ndarray             # [R] weighted mean local training loss
    tier: str = ""               # "L{L}xB{B}" executable that served it
    queue_wait_s: float = 0.0
    compute_s: float = 0.0
    total_s: float = 0.0


@functools.lru_cache(maxsize=8)
def default_problem(n_clients: int = 10, dim: int = 8, classes: int = 3,
                    seed: int = 42):
    """Tiny linear-softmax FL problem the service trains by default (the
    serving benchmarks' workload); cached so every service built from the
    same shape shares one `loss_fn` identity — and therefore one
    compiled-segment cache entry per (B, L) shape."""
    key = jax.random.key(seed)
    ks = jax.random.split(key, n_clients + 1)
    protos = jax.random.normal(ks[-1], (classes, dim))
    data = []
    for i in range(n_clients):
        n = 24 + 4 * (i % 3)
        y = jax.random.randint(ks[i], (n,), 0, classes)
        x = protos[y] + 0.5 * jax.random.normal(
            jax.random.fold_in(ks[i], 1), (n, dim))
        data.append({"x": x, "y": y})
    params = {"w": jnp.zeros((dim, classes))}

    def loss_fn(p, b):
        logits = b["x"] @ p["w"]
        return -jnp.mean(jax.nn.log_softmax(logits)[
            jnp.arange(b["y"].shape[0]), b["y"]])

    return params, loss_fn, ClientShards.from_ragged(data)


def request_draws(key: jax.Array, n_rounds: int, n_clients: int,
                  n_sov: int, batch_size: int):
    """A request's on-device draw contract (mirrors the simulator's
    `_stream_draws`): per-round scheduling keys, client selections, and
    uniform minibatch draws. The solo B=1 reference run and the packed
    cell consume byte-identical draws because both call this."""
    k_r, k_sel, k_mb = jax.random.split(key, 3)
    keys = jax.random.split(k_r, n_rounds)                   # [R]
    sel = jax.vmap(
        lambda k: jax.random.permutation(k, n_clients)[:n_sov]
    )(jax.random.split(k_sel, n_rounds))                     # [R, S]
    mb_u = jax.random.uniform(k_mb, (n_rounds, n_sov, batch_size))
    return keys, sel, mb_u


def _pad_rows(x: jax.Array, length: int) -> jax.Array:
    """Pad `[R, ...]` to `[length, ...]` by repeating the last row — the
    tail rows belong to inactive rounds, computed then discarded."""
    R = x.shape[0]
    if R == length:
        return x
    reps = (length - R,) + (1,) * (x.ndim - 1)
    return jnp.concatenate([x, jnp.tile(x[-1:], reps)], axis=0)


# Host-side packing is latency-critical: at B=8 the eager per-request
# draw/pad/stack/slice ops cost several times the packed XLA dispatch
# itself, so each stage is a single jitted call instead.

@functools.lru_cache(maxsize=128)
def _padded_draws(R: int, L: int, n_clients: int, n_sov: int,
                  batch_size: int):
    """Jitted per-request draw column: `request_draws` padded from the
    request's R rounds to the compiled horizon L, plus its active mask.
    Cached per shape so a request costs one dispatch, not ~10 eager ops."""

    @jax.jit
    def go(seed):
        keys, sel, mb_u = request_draws(jax.random.key(seed), R,
                                        n_clients, n_sov, batch_size)
        return (_pad_rows(keys, L), _pad_rows(sel, L), _pad_rows(mb_u, L),
                jnp.arange(L) < R)

    return go


@jax.jit
def _assemble(carries, cols, actives):
    """One fused dispatch for batch assembly: pack the session carries
    along the cell axis and stack the per-request draw columns into the
    tier's `[L, B_tier, ...]` inputs. The caller pads every list to the
    tier occupancy on the host (replicas of slot 0, all-inactive active
    columns), so the trace is keyed by the tier's (L, B) shapes alone —
    occupancy changes within a bucket NEVER retrace, and `warmup()`'s
    single-request rung covers the only trace each tier ever needs."""
    carry = pack_cells(carries)
    keys = jnp.stack([c[0] for c in cols], axis=1)           # [L, B]
    sel = jnp.stack([c[1] for c in cols], axis=1)            # [L, B, S]
    mb_u = jnp.stack([c[2] for c in cols], axis=1)           # [L, B, S, bs]
    active = jnp.stack(actives, axis=1)                      # [L, B]
    return carry, keys, sel, mb_u, active


@functools.partial(jax.jit, static_argnames="n")
def _split_cells(state, n: int):
    """Slice the first `n` cells back out as B=1 states in one dispatch."""
    return tuple(unpack_cell(state, b) for b in range(n))


def _pct(xs: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else \
        float("nan")


@dataclasses.dataclass
class ServeMetrics:
    """Per-request latency decomposition + batch occupancy counters +
    padding/tier accounting (what fraction of the computed round-slots
    and cell slots was padding, and which tier served each dispatch) +
    session spill/restore counts."""
    queue_wait_s: List[float] = dataclasses.field(default_factory=list)
    compute_s: List[float] = dataclasses.field(default_factory=list)
    total_s: List[float] = dataclasses.field(default_factory=list)
    rounds: List[int] = dataclasses.field(default_factory=list)
    occupancy: List[int] = dataclasses.field(default_factory=list)
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    rounds_active: int = 0       # requested rounds over real cells
    rounds_computed: int = 0     # L_tier x real cells: round-slots paid
    cells_active: int = 0        # real cells packed
    cells_computed: int = 0      # B_tier per dispatch: cell slots paid
    tier_hits: Dict[str, int] = dataclasses.field(
        default_factory=lambda: collections.defaultdict(int))
    n_spills: int = 0            # session carries spilled device->host
    n_restores: int = 0          # spilled carries restored host->device

    def observe_dispatch(self, reqs: Sequence["ServeRequest"], L: int,
                         B: int) -> None:
        """Account one packed dispatch's padding against the tier
        (L, B) that served it."""
        self.rounds_active += sum(int(r.n_rounds) for r in reqs)
        self.rounds_computed += L * len(reqs)
        self.cells_active += len(reqs)
        self.cells_computed += B
        self.tier_hits[f"L{L}xB{B}"] += 1

    def observe_batch(self, reqs: Sequence[ServeRequest],
                      t_submit: Sequence[float], t_start: float,
                      t_end: float) -> None:
        for r, ts in zip(reqs, t_submit):
            self.queue_wait_s.append(t_start - ts)
            self.compute_s.append(t_end - t_start)
            self.total_s.append(t_end - ts)
            self.rounds.append(int(r.n_rounds))
            self.t_first = ts if self.t_first is None \
                else min(self.t_first, ts)
        self.t_last = t_end if self.t_last is None \
            else max(self.t_last, t_end)
        self.occupancy.append(len(reqs))

    def summary(self) -> Dict[str, float]:
        """Aggregate view: p50/p99 total latency, mean queue-wait and
        compute, aggregate rounds/s over the observed wall span, and
        mean batch occupancy (packed cells per dispatch)."""
        wall = (self.t_last - self.t_first
                if self.total_s and self.t_last > self.t_first else
                float("nan"))
        return {
            "n_requests": len(self.total_s),
            "n_batches": len(self.occupancy),
            "p50_ms": 1e3 * _pct(self.total_s, 50),
            "p99_ms": 1e3 * _pct(self.total_s, 99),
            "mean_queue_wait_ms": 1e3 * float(
                np.mean(self.queue_wait_s)) if self.queue_wait_s
            else float("nan"),
            "mean_compute_ms": 1e3 * float(np.mean(self.compute_s))
            if self.compute_s else float("nan"),
            "rounds_per_s": sum(self.rounds) / wall,
            "mean_occupancy": float(np.mean(self.occupancy))
            if self.occupancy else float("nan"),
            # padding actually paid for: fraction of real cells'
            # computed round-slots that were inactive tail rounds, and
            # fraction of computed cell slots that were inactive
            # replicas (both 0 in a perfectly-fitted tier)
            "pad_frac_rounds": 1.0 - self.rounds_active
            / self.rounds_computed if self.rounds_computed
            else float("nan"),
            "pad_frac_cells": 1.0 - self.cells_active
            / self.cells_computed if self.cells_computed
            else float("nan"),
            "tier_hits": dict(self.tier_hits),
            "n_spills": self.n_spills,
            "n_restores": self.n_restores,
        }


class SessionStore:
    """Bounded session KV-cache: at most `max_sessions` carries stay
    device-resident (LRU); colder sessions spill to host numpy and
    restore bitwise on their next touch.

    The PR-7 cache was a plain host dict of device arrays — every
    session ever seen pinned its `RolloutCarry` (FleetState incl. the
    warm `p4_tab`, params, opt_state) in device memory for the process
    lifetime. Here the device working set is flat in session count:
    `get`/`put` move the session to the LRU front; overflowing carries
    are flattened leaf-by-leaf to numpy (one device->host transfer per
    leaf, `checkpoint/np_ckpt`-style) and re-uploaded with identical
    dtypes on restore, so an evict->restore roundtrip is bitwise — a
    spilled session's next request behaves exactly as if it had stayed
    hot (pinned in `tests/test_serve.py`). `max_sessions=None` keeps
    every session on device (the PR-7 behavior). Mapping-style access
    (`store[s]`, `s in store`, `iter`, `pop`) spans hot and spilled
    sessions alike.

    Not thread-safe by itself: the service's batches are serialized
    (BatchServer's one-thread executor), which is also what makes the
    LRU order meaningful.
    """

    def __init__(self, max_sessions: Optional[int] = None,
                 metrics: Optional[ServeMetrics] = None):
        if max_sessions is not None and int(max_sessions) < 1:
            raise ValueError("max_sessions must be >= 1 (or None)")
        self.max_sessions = (None if max_sessions is None
                             else int(max_sessions))
        self.metrics = metrics
        self._hot: "collections.OrderedDict[str, RolloutCarry]" = \
            collections.OrderedDict()
        self._spilled: Dict[str, Any] = {}

    @property
    def n_device(self) -> int:
        """Sessions currently holding device memory."""
        return len(self._hot)

    @property
    def n_spilled(self) -> int:
        return len(self._spilled)

    def __len__(self) -> int:
        return len(self._hot) + len(self._spilled)

    def __iter__(self) -> Iterator[str]:
        return iter(list(self._hot) + list(self._spilled))

    def __contains__(self, session: str) -> bool:
        return session in self._hot or session in self._spilled

    def get(self, session: str) -> Optional[RolloutCarry]:
        """The session's device-resident carry (restored from a spill if
        needed), refreshed to most-recently-used; None if unknown."""
        if session in self._hot:
            self._hot.move_to_end(session)
            return self._hot[session]
        host = self._spilled.pop(session, None)
        if host is None:
            return None
        with span("serve.restore"):
            carry = jax.tree.map(jnp.asarray, host)
        if self.metrics is not None:
            self.metrics.n_restores += 1
        self.put(session, carry)
        return carry

    def put(self, session: str, carry: RolloutCarry) -> None:
        """Store/refresh the session at the LRU front, spilling the
        least-recently-used carries past `max_sessions` to host."""
        self._spilled.pop(session, None)
        self._hot[session] = carry
        self._hot.move_to_end(session)
        while (self.max_sessions is not None
               and len(self._hot) > self.max_sessions):
            cold, c = self._hot.popitem(last=False)
            with span("serve.spill"):
                self._spilled[cold] = jax.tree.map(np.asarray, c)
            if self.metrics is not None:
                self.metrics.n_spills += 1

    def pop(self, session: str, default=None):
        if session in self._hot:
            return self._hot.pop(session)
        return self._spilled.pop(session, default)

    def __getitem__(self, session: str) -> RolloutCarry:
        carry = self.get(session)
        if carry is None:
            raise KeyError(session)
        return carry

    def __setitem__(self, session: str, carry: RolloutCarry) -> None:
        self.put(session, carry)


class SchedulingService:
    """The packing core: sessions, the compiled tier ladder, `run_batch`.

    Synchronous and event-loop-free so it is directly testable; the
    asyncio front-end (`BatchServer`) owns windows and futures. A custom
    FL workload plugs in via (`params`, `loss_fn`, `client_data`);
    omitted, the service trains `default_problem()`.
    """

    def __init__(self, cfg: ServeConfig, *, params=None, loss_fn=None,
                 client_data=None):
        self.cfg = cfg
        if int(cfg.batch) < 1 or int(cfg.max_rounds) < 1:
            raise ValueError("batch and max_rounds must be >= 1")
        if cfg.horizons[0] < 1:
            raise ValueError(f"tiers must be >= 1, got {cfg.tiers}")
        if cfg.occupancies[0] < 1 or cfg.occupancies[-1] != int(cfg.batch):
            raise ValueError(f"batch_tiers must be within 1..batch and "
                             f"top out at batch={cfg.batch}, got "
                             f"{cfg.batch_tiers}")
        self.mob = ManhattanParams()
        self.ch = ChannelParams()
        prm_kw = {} if cfg.ipm_iters is None else \
            {"ipm_iters": int(cfg.ipm_iters)}
        self.prm = VedsParams(alpha=cfg.alpha, V=cfg.V, Q=cfg.q_bits,
                              slot=0.1,
                              ipm_warm_iters=cfg.ipm_warm_iters, **prm_kw)
        self.sc = ScenarioParams(n_sov=cfg.n_sov, n_opv=cfg.n_opv,
                                 n_slots=cfg.n_slots,
                                 batch_size=cfg.batch_size)
        if loss_fn is None:
            params, loss_fn, client_data = default_problem(cfg.n_clients)
        self.params0, self.loss_fn = params, loss_fn
        self.shards = (client_data if isinstance(client_data, ClientShards)
                       else ClientShards.from_ragged(client_data))
        # no handoff in packed mode: cells are independent sessions, and
        # per-cell active masks cannot compose with the exchange
        self._stream = StreamConfig(n_rounds=0, batch=int(cfg.batch),
                                    carry_queues=cfg.carry_queues,
                                    n_fleet=cfg.n_fleet)
        # one segment-cache entry per occupancy tier (B lives in the
        # StreamConfig key); each horizon tier then compiles one
        # executable under its entry on first dispatch (warmup() pays
        # every (L, B) trace up front)
        self._seg = {
            b: fused_segment(loss_fn, cfg.scheduler, self.sc, self.mob,
                             self.ch, self.prm,
                             dataclasses.replace(self._stream, batch=b),
                             cfg.lr, 1, None, 1)
            for b in cfg.occupancies}
        self.metrics = ServeMetrics()
        self.sessions = SessionStore(cfg.max_sessions,
                                     metrics=self.metrics)
        # per-horizon constants: absolute step ids, the (empty) in-scan
        # eval mask, and the padding cells' all-inactive active column
        self._steps = {L: jnp.arange(L) for L in cfg.horizons}
        self._ev = {L: jnp.zeros((L,), bool) for L in cfg.horizons}
        self._off = {L: jnp.zeros((L,), bool) for L in cfg.horizons}
        self._warming = False
        # session creation sits on the serving path (every first-contact
        # request pays it, eagerly ~10x a packed dispatch) — jit it; the
        # warmup session triggers the one-time compile
        stream1 = dataclasses.replace(self._stream, batch=1)
        self._init = jax.jit(lambda k: init_carry(
            k, self.sc, self.mob, stream1, self.params0, ch=self.ch))

    def session_carry(self, session: str) -> RolloutCarry:
        """The session's B=1 carry — persistent fleet (incl. the P4
        warm-start table), model params, optimizer state — created
        deterministically from (service seed, session id) on first use,
        restored from a host spill on re-use past `max_sessions`."""
        carry = self.sessions.get(session)
        if carry is None:
            k = jax.random.fold_in(jax.random.key(self.cfg.seed),
                                   zlib.crc32(session.encode()))
            carry = self._init(k)
            self.sessions.put(session, carry)
        return carry

    def route(self, reqs: Sequence[ServeRequest]) -> Tuple[int, int]:
        """The tier that serves this batch: the smallest horizon >= the
        batch's max `n_rounds` x the smallest occupancy bucket >= its
        request count (both ladders validated to cover the range)."""
        R = max(int(r.n_rounds) for r in reqs)
        L = next(h for h in self.cfg.horizons if h >= R)
        B = next(b for b in self.cfg.occupancies if b >= len(reqs))
        return L, B

    def warmup(self, rounds: Sequence[int] = ()) -> None:
        """Compile every tier's executable outside any timed load (one
        trace per (horizon, occupancy) rung); leaves metrics untouched.

        `rounds` hints the expected request round counts: each rung's
        dispatch only traces the R = L draw column, so a mixed load's
        R < L draw/pad programs (`_padded_draws`) would otherwise
        compile inside the first timed window that sees them."""
        self._warming = True
        try:
            for L in self.cfg.horizons:
                for B in self.cfg.occupancies:
                    self.run_batch([ServeRequest("__warmup__",
                                                 n_rounds=L)],
                                   _tier=(L, B))
                    self.sessions.pop("__warmup__", None)
            for R in sorted({int(r) for r in rounds}):
                for L in self.cfg.horizons:
                    if R <= L:
                        _padded_draws(R, L, self.shards.n_clients,
                                      self.cfg.n_sov,
                                      self.cfg.batch_size)(0)
        finally:
            self._warming = False

    def run_batch(self, reqs: Sequence[ServeRequest], *,
                  _tier: Optional[Tuple[int, int]] = None
                  ) -> List[ServeResponse]:
        """Pack the requests into the cell axis of ONE dispatch of the
        smallest fitting tier's executable and slice responses back out.

        Ragged batches pad on both axes of their tier: occupancy < B_t
        fills the spare cell slots with a replica of the first session
        under an all-inactive column, and R_b < L_t rounds pad with
        inactive tail rounds — padding is computed and discarded, never
        perturbing a real cell. Horizon routing and padding are
        bitwise-inert at any L (L is only the scan trip count);
        occupancy has an XLA boundary: B > 1 executables fuse/tile
        differently than the B = 1 program on CPU and per-cell float
        bits can drift from solo at large shapes (present since the
        first single-B=8 executable; see DESIGN.md §13). Every
        executable is itself deterministic — an identical dispatch
        sequence replays to identical bits at any B — and co-batched
        neighbors/padding never perturb a cell within one executable.
        Each session's refreshed carry is scattered back to the
        (bounded) store before responses return."""
        cfg = self.cfg
        S = cfg.n_sov
        max_B, max_L = cfg.occupancies[-1], cfg.horizons[-1]
        reqs = list(reqs)
        if not 0 < len(reqs) <= max_B:
            raise ValueError(f"{len(reqs)} requests for {max_B} cell "
                             "slots")
        if len({r.session for r in reqs}) != len(reqs):
            raise ValueError("duplicate sessions in one batch: packed "
                             "cells would race on one session's state")
        for r in reqs:
            if not 0 < int(r.n_rounds) <= max_L:
                raise ValueError(f"n_rounds={r.n_rounds} outside the "
                                 f"compiled horizon 1..{max_L}")
        L, B = self.route(reqs) if _tier is None else _tier
        tier = f"L{L}xB{B}"
        # host spans (DESIGN.md §15): the dispatch and each of
        # its host stages, on the thread that runs it
        with span("serve.run_batch", tier=tier, requests=len(reqs),
                  dispatch=sum(self.metrics.tier_hits.values())):
            with span("serve.store"):
                carries = [self.session_carry(r.session) for r in reqs]
            with span("serve.draws"):
                cols = [_padded_draws(int(r.n_rounds), L,
                                      self.shards.n_clients, S,
                                      cfg.batch_size)(int(r.seed))
                        for r in reqs]
            # pad to the tier occupancy HERE, on the host (replicas of
            # slot 0 under all-inactive columns): `_assemble` then always
            # traces at arity B, so a window of any occupancy reuses the
            # rung's one warmed trace instead of compiling per occupancy
            # mid-load
            n_pad = B - len(reqs)
            actives = [c[3] for c in cols] + [self._off[L]] * n_pad
            carries = carries + [carries[0]] * n_pad
            cols = cols + [cols[0]] * n_pad
            with span("serve.assemble"):
                carry, keys, sel, mb_u, active = _assemble(
                    tuple(carries), tuple(cols), tuple(actives))
            with span("serve.segment"):
                res = self._seg[B](carry, keys, sel, mb_u, self.shards,
                                   self._steps[L], active, self._ev[L])
            # always split the tier's full B cells (padding slices are
            # lazy views): a static per-tier arity means occupancy changes
            # within a bucket never re-trace
            with span("serve.split"):
                fleets = _split_cells(res.fleet, B)
                params = _split_cells(res.params, B)
                opts = (None,) * B if res.opt_state is None else \
                    _split_cells(res.opt_state, B)
            # one device->host transfer per output array, numpy slicing
            # after: the host waits here for the dispatch to finish
            with span("serve.to_host"):
                succ = np.asarray(res.outputs.success)
                n_succ = np.asarray(res.outputs.n_success)
                loss = np.asarray(res.loss)
            with span("serve.store"):
                for b, r in enumerate(reqs):
                    self.sessions.put(r.session, RolloutCarry(
                        sched=fleets[b], params=params[b],
                        opt_state=opts[b]))
        out = []
        for b, r in enumerate(reqs):
            R = int(r.n_rounds)
            out.append(ServeResponse(
                session=r.session, n_rounds=R, success=succ[:R, b],
                n_success=n_succ[:R, b], loss=loss[:R, b], tier=tier))
        if not self._warming:
            self.metrics.observe_dispatch(reqs, L, B)
        return out


class BatchServer:
    """Continuous-batching front-end over a `SchedulingService`.

    `submit` enqueues a request and awaits its response. A collector
    task takes the first queued request, waits up to `window_s` for more
    (up to `max_batch`), then executes the packed dispatch on a
    single-thread executor — off the event loop, so arrivals keep
    flowing during compute, and serialized, so two in-flight batches can
    never race on one session's state.

    Deferral fairness: a request sharing a session with one already in
    the forming batch is deferred (sessions are sequential by contract),
    but deferred requests seed the NEXT batch FIFO-first, ahead of any
    newer arrivals — a session whose requests keep coming can be
    deferred at most one window, never starved by fresh traffic
    (regression-pinned in `tests/test_serve.py`).

    Round bucketing (`ServeConfig.bucket_rounds`): a collected window
    is split by horizon rung before routing, shortest rung first —
    `route()` pads every cell of a dispatch to the batch's max
    `n_rounds` rung, so co-batching a 1-round request with an L-round
    one burns (L-1)/L of the short cell's compute on inactive padding.
    Bucketed, each group dispatches to its own smallest tier and
    `pad_frac_rounds` collapses toward the ladder's quantization error.
    On a single-rung ladder every request shares the one rung and the
    split is a no-op."""

    def __init__(self, service: SchedulingService, *,
                 window_s: Optional[float] = None,
                 max_batch: Optional[int] = None):
        self.service = service
        self.window_s = float(service.cfg.window_s if window_s is None
                              else window_s)
        self.max_batch = int(service.cfg.batch if max_batch is None
                             else max_batch)
        if not 0 < self.max_batch <= int(service.cfg.batch):
            raise ValueError(f"max_batch={self.max_batch} outside "
                             f"1..{service.cfg.batch}")
        self._queue: asyncio.Queue = asyncio.Queue()
        self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        self._task: Optional[asyncio.Task] = None

    async def __aenter__(self) -> "BatchServer":
        self._task = asyncio.get_running_loop().create_task(self._run())
        return self

    async def __aexit__(self, *exc) -> None:
        self._queue.put_nowait(None)
        if self._task is not None:
            await self._task
        self._pool.shutdown(wait=True)

    async def submit(self, req: ServeRequest) -> ServeResponse:
        fut = asyncio.get_running_loop().create_future()
        self._queue.put_nowait((req, fut, time.perf_counter()))
        return await fut

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        deferred: List = []       # FIFO of session-conflicted holdovers
        stopping = False          # sentinel seen: drain, take no more
        while True:
            # deferred requests seed the batch FIRST, in arrival order —
            # a duplicate-session request is never starved behind newer
            # traffic, it waits exactly the batches its own session's
            # predecessors occupy (plus bucket-full overflow)
            batch: List = []
            sessions = set()
            keep: List = []
            for it in deferred:
                if (len(batch) < self.max_batch
                        and it[0].session not in sessions):
                    sessions.add(it[0].session)
                    batch.append(it)
                else:
                    keep.append(it)
            deferred = keep
            if not batch:
                if stopping:
                    return
                item = await self._queue.get()
                if item is None:
                    return
                batch = [item]
                sessions = {item[0].session}
            deadline = loop.time() + self.window_s
            while not stopping and len(batch) < self.max_batch:
                timeout = deadline - loop.time()
                try:
                    nxt = (self._queue.get_nowait() if timeout <= 0 else
                           await asyncio.wait_for(self._queue.get(),
                                                  timeout))
                except (asyncio.QueueEmpty, asyncio.TimeoutError):
                    break
                if nxt is None:
                    # drain mode: finish this batch, then keep looping
                    # on the deferred FIFO until it is empty — a stop
                    # never abandons a deferred request's future
                    stopping = True
                    break
                if nxt[0].session in sessions:
                    # a session's requests are sequential by contract
                    # (each resumes the state the previous one left) —
                    # defer the duplicate to the NEXT batch's front
                    deferred.append(nxt)
                    continue
                sessions.add(nxt[0].session)
                batch.append(nxt)
            for group in self._round_buckets(batch):
                await self._dispatch(loop, group)

    def _round_buckets(self, batch: List) -> List[List]:
        """The window's dispatch groups: split by horizon rung
        (ascending) when `bucket_rounds` is on, else the whole window
        as one group. A request beyond the ladder keeps the top rung's
        group so `run_batch` raises its ValueError into that request's
        future instead of the collector dying on routing."""
        if not self.service.cfg.bucket_rounds or len(batch) <= 1:
            return [batch]
        horizons = self.service.cfg.horizons
        by_rung: Dict[int, List] = {}
        for it in batch:
            rung = next((h for h in horizons
                         if h >= int(it[0].n_rounds)), horizons[-1])
            by_rung.setdefault(rung, []).append(it)
        return [by_rung[h] for h in sorted(by_rung)]

    async def _dispatch(self, loop, batch: List) -> None:
        reqs = [b[0] for b in batch]
        t_start = time.perf_counter()
        try:
            resps = await loop.run_in_executor(
                self._pool, self.service.run_batch, reqs)
            # run_batch materializes every output via np.asarray
            # before returning, so the device work is already
            # flushed when the executor future resolves
            t_end = time.perf_counter()  # reprolint: disable=timer-no-block
            self.service.metrics.observe_batch(
                reqs, [b[2] for b in batch], t_start, t_end)
            for (req, fut, ts), resp in zip(batch, resps):
                resp.queue_wait_s = t_start - ts
                resp.compute_s = t_end - t_start
                resp.total_s = t_end - ts
                if not fut.done():
                    fut.set_result(resp)
        except Exception as e:          # noqa: BLE001 — fail the batch
            for _, fut, _ in batch:
                if not fut.done():
                    fut.set_exception(e)


def _rounds_of(n_rounds: Union[int, Sequence[int]], i: int) -> int:
    """A request's round count under a mixed-`n_rounds` load: an int is
    every request's count; a sequence is cycled deterministically by
    request index — every client's i-th request draws `seq[i % len]`,
    so the load moves through phases of like-sized work (the job-type
    mix tier routing can exploit; cycling per (client + index) instead
    would put a long request in nearly every window and degrade all
    horizon routing to the max tier)."""
    if isinstance(n_rounds, int):
        return n_rounds
    seq = list(n_rounds)
    return int(seq[i % len(seq)])


async def closed_loop_load(server: BatchServer, *, n_clients: int,
                           n_requests: int,
                           n_rounds: Union[int, Sequence[int]],
                           seed: int = 0) -> List[ServeResponse]:
    """Saturating load: every client keeps exactly one request in flight
    (submits the next the moment its response lands). This is the load
    the batched-vs-sequential rounds/s acceptance is measured under.
    `n_rounds` may be a sequence — a deterministic mixed-round-count
    load, the tiered-routing workload."""
    async def client(c: int) -> List[ServeResponse]:
        out = []
        for i in range(n_requests):
            out.append(await server.submit(ServeRequest(
                session=f"client-{c}", n_rounds=_rounds_of(n_rounds, i),
                seed=seed + 1000 * c + i)))
        return out

    res = await asyncio.gather(*(client(c) for c in range(n_clients)))
    return [r for rs in res for r in rs]


async def poisson_load(server: BatchServer, *, n_clients: int,
                       rate_hz: float, n_requests: int,
                       n_rounds: Union[int, Sequence[int]],
                       seed: int = 0) -> List[ServeResponse]:
    """Open-loop Poisson arrivals: each client draws exponential
    inter-arrival gaps at `rate_hz / n_clients`, so the aggregate is a
    Poisson process at `rate_hz` requests/s. Latency under this load —
    not the saturating closed loop — is what the batching-window
    tail-latency tradeoff is measured on. `n_rounds` may be a sequence
    (deterministic mixed round counts, as for `closed_loop_load`)."""
    gap = n_clients / float(rate_hz)

    async def client(c: int) -> List[ServeResponse]:
        rng = np.random.default_rng(seed + c)
        out = []
        for i in range(n_requests):
            await asyncio.sleep(float(rng.exponential(gap)))
            out.append(await server.submit(ServeRequest(
                session=f"client-{c}", n_rounds=_rounds_of(n_rounds, i),
                seed=seed + 1000 * c + i)))
        return out

    res = await asyncio.gather(*(client(c) for c in range(n_clients)))
    return [r for rs in res for r in rs]


def drive(cfg: ServeConfig, *, n_clients: int = 8, n_requests: int = 4,
          n_rounds: Union[int, Sequence[int], None] = None,
          rate_hz: float = 0.0, window_s: Optional[float] = None,
          baseline: bool = True, seed: int = 0) -> Dict[str, object]:
    """Build a service, drive it under synthetic load, and return the
    metrics summary and the batched service's `responses` — plus the
    sequential per-request baseline (a `batch=1` service dispatching
    every request alone, the B=1 lower bound) and the aggregate rounds/s
    speedup over it. `n_rounds` may be a sequence for a
    mixed-round-count load (the tiered workload)."""
    if n_rounds is None:
        n_rounds = cfg.horizons[-1]

    def load(service: SchedulingService, w: float, mb: int):
        service.warmup(rounds=(n_rounds,) if isinstance(n_rounds, int)
                       else n_rounds)

        async def go():
            async with BatchServer(service, window_s=w,
                                   max_batch=mb) as srv:
                if rate_hz > 0:
                    return await poisson_load(srv, n_clients=n_clients,
                                              rate_hz=rate_hz,
                                              n_requests=n_requests,
                                              n_rounds=n_rounds, seed=seed)
                return await closed_loop_load(srv, n_clients=n_clients,
                                              n_requests=n_requests,
                                              n_rounds=n_rounds, seed=seed)

        responses = asyncio.run(go())
        return service.metrics.summary(), responses

    w = float(cfg.window_s if window_s is None else window_s)
    out: Dict[str, object] = {}
    out["batched"], out["responses"] = load(SchedulingService(cfg), w,
                                            int(cfg.batch))
    if baseline:
        # the B=1 lower bound keeps the horizon ladder but has no
        # occupancy to bucket (an explicit batch_tiers would not fit)
        seq = SchedulingService(dataclasses.replace(cfg, batch=1,
                                                    batch_tiers=None))
        out["sequential"], _ = load(seq, 0.0, 1)
        out["speedup"] = (out["batched"]["rounds_per_s"]
                          / out["sequential"]["rounds_per_s"])
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Batched scheduling service under synthetic load")
    ap.add_argument("--batch", type=int, default=8,
                    help="B: packed cell slots per dispatch")
    ap.add_argument("--max-rounds", type=int, default=4,
                    help="L: compiled round horizon per dispatch")
    ap.add_argument("--tiers", type=str, default=None,
                    help="comma-separated horizon ladder (e.g. 8,32,128)"
                         ": route each batch to the smallest tier that "
                         "fits instead of padding to one max horizon")
    ap.add_argument("--max-sessions", type=int, default=None,
                    help="bound on device-resident sessions (LRU spill "
                         "to host beyond it; default unbounded)")
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="batching window after the first request")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=4,
                    help="requests per client")
    ap.add_argument("--rounds", type=int, default=None,
                    help="rounds per request (default: max-rounds)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="aggregate Poisson arrival rate in requests/s "
                         "(0 = saturating closed loop)")
    ap.add_argument("--scheduler", default="madca")
    ap.add_argument("--warm-iters", type=int, default=0,
                    help="VEDS+COT: warm P4 budget per candidate")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the sequential B=1 baseline")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line instead of text")
    args = ap.parse_args(argv)

    tiers = (None if args.tiers is None else
             tuple(int(t) for t in args.tiers.split(",")))
    cfg = ServeConfig(batch=args.batch, max_rounds=args.max_rounds,
                      tiers=tiers, max_sessions=args.max_sessions,
                      window_s=1e-3 * args.window_ms,
                      scheduler=args.scheduler,
                      ipm_warm_iters=args.warm_iters, seed=args.seed)
    out = drive(cfg, n_clients=args.clients, n_requests=args.requests,
                n_rounds=args.rounds, rate_hz=args.rate,
                baseline=not args.no_baseline, seed=args.seed)
    if args.json:
        print(json.dumps({k: v for k, v in out.items()
                          if k != "responses"}))
        return 0
    b = out["batched"]
    print(f"batched  B={args.batch} window={args.window_ms}ms: "
          f"{b['rounds_per_s']:8.1f} rounds/s  p50={b['p50_ms']:.1f}ms "
          f"p99={b['p99_ms']:.1f}ms  occupancy={b['mean_occupancy']:.1f}")
    if "sequential" in out:
        s = out["sequential"]
        print(f"sequential B=1:          {s['rounds_per_s']:8.1f} rounds/s"
              f"  p50={s['p50_ms']:.1f}ms p99={s['p99_ms']:.1f}ms")
        print(f"speedup: {out['speedup']:.1f}x aggregate rounds/s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
