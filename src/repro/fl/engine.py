"""Fused on-device FL training engine: one XLA program per run.

The paper's pipeline is one loop — schedule (Algorithm 2), train locally
(eq. 2), aggregate (eq. 11) — and this module compiles it as one loop:
`fused_rollout` runs the *same* per-round scheduling step as
`repro.core.streaming` (mobility -> coverage re-selection -> channels ->
`solve_round` -> queue/energy carry) and, inside the same `lax.scan`
step, gathers each selected client's minibatch from the padded
`[C, n_max, ...]` shard layout, takes one local SGD step per client
(FedSGD batching: for one local step, FedAvg of models == FedSGD of
gradients), and applies the mask-weighted aggregation. The scan carry is
a `RolloutCarry`: the scheduler-side state (virtual queues / persistent
fleet) threaded alongside the global model parameters and optimizer
state. See DESIGN.md §10.

Because the engine runs the same `sched_round_step`, the P4 warm-start
table (persistent VEDS+COT, `VedsParams.ipm_warm_iters`) rides the fused
carry for free. Evaluation can also run *inside* the scan
(`fused_rollout(eval_fn=..., eval_mask=...)`): a `lax.cond` branch
evaluates the post-aggregation params on the flagged rounds, so
`run_fl(streaming=True)` with eval is one dispatch with a single
trailing device sync instead of per-segment host round-trips.

Client data is padded, not ragged: `ClientShards` holds every client's
shard at a common `n_max` with the true sample counts in `n_samples`.
Minibatch indices are drawn against the true counts and aggregation
weights are the true counts, so padding rows are never sampled and a
client with zero samples never moves the global model (its weight is 0
and its gradient is hard-zeroed before the weighted average — even NaNs
from garbage padding cannot leak in).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.channel.mobility import ManhattanParams
from repro.channel.v2x import ChannelParams
from repro.core.lyapunov import VedsParams
from repro.core.scenario import FleetState, ScenarioParams
from repro.core.scheduler import (RolloutCarry, RoundOutputs, Scheduler,
                                  SchedulerCarry)
from repro.core.streaming import (StreamConfig, cast_sched_state,
                                  promote_sched_state, sched_round_step,
                                  sched_state0, validate_stream_config)
from repro.data.synthetic import pad_client_shards
from repro.obs import register, scope


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ClientShards:
    """Padded client shards: every leaf of `data` is `[C, n_max, ...]`;
    `n_samples [C]` holds the true (unpadded) per-client counts used for
    minibatch index draws and aggregation weights."""
    data: Dict[str, jax.Array]
    n_samples: jax.Array

    @property
    def n_clients(self) -> int:
        return self.n_samples.shape[0]

    @property
    def n_max(self) -> int:
        return next(iter(self.data.values())).shape[1]

    @staticmethod
    def from_ragged(client_data) -> "ClientShards":
        """Pad a list of per-client dict-of-arrays shards."""
        data, n = pad_client_shards(client_data)
        return ClientShards(data=data, n_samples=n)


class FusedResult(NamedTuple):
    """One fused rollout segment's results.

      params     global model, leading [B] cell axis
      opt_state  optimizer state, leading [B] cell axis (None for SGD)
      outputs    RoundOutputs stacked [R, B, ...]
      loss       [R, B] weighted mean local training loss per round
      fleet      final FleetState (None in fresh-fleet mode)
      carry      final round's queue state [B, S]/[B, U]
      metric     [R, B] in-scan eval values (NaN on rounds the eval
                 branch did not run), or None without `eval_fn`
    """
    params: Any
    opt_state: Any
    outputs: RoundOutputs
    loss: jax.Array
    fleet: Optional[FleetState]
    carry: SchedulerCarry
    metric: Optional[jax.Array] = None


def replicate(tree, batch: int):
    """Broadcast a pytree to a leading [B] cell axis (fused layout)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (batch,) + x.shape), tree)


def fedavg_grads(grads_stack, mask: jax.Array, weights: jax.Array,
                 clip: float = 5.0):
    """Mask-weighted FedSGD gradient average (eq. 11 on gradients).

    grads_stack: per-client grads, leading [S] axis on every leaf;
    mask [S] success indicators; weights [S] true sample counts.
    Returns (avg, scale): the weighted average gradient and the scalar
    `ok * clip_factor` to fold into the update (ok = 0 when every upload
    failed, keeping the previous global model). Clients with zero weight
    are hard-zeroed before the average so NaN gradients (e.g. from an
    empty padded client) cannot poison the update.
    """
    w = mask * weights
    den = jnp.maximum(w.sum(), 1e-9)

    def _avg(g):
        wb = w.reshape(w.shape + (1,) * (g.ndim - 1))
        return jnp.einsum("s,s...->...", w, jnp.where(wb > 0, g, 0.0)) / den

    avg = jax.tree.map(_avg, grads_stack)
    gn = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                      for g in jax.tree.leaves(avg)))
    c = jnp.minimum(1.0, clip / (gn + 1e-9))
    ok = (w.sum() > 0).astype(jnp.float32)
    return avg, ok * c


def fedavg_apply(params, grads_stack, mask, weights, *, lr: float,
                 clip: float = 5.0, opt=None, opt_state=None, step=0):
    """One aggregated global update from a stack of per-client grads.

    With `opt=None` this is the plain SGD rule the blocked simulator
    uses; with an `(init, update)` optimizer pair from `repro.optim` the
    clipped weighted-average gradient is fed through `update` instead.
    Returns (new_params, new_opt_state).
    """
    avg, scale = fedavg_grads(grads_stack, mask, weights, clip=clip)
    gsc = jax.tree.map(lambda g: scale * g, avg)
    if opt is None:
        return jax.tree.map(lambda p, g: p - lr * g, params, gsc), opt_state
    return opt[1](params, gsc, opt_state, step)


def minibatch_indices(u: jax.Array, n: jax.Array) -> jax.Array:
    """Uniform draws `u [..., batch]` -> sample indices against the true
    per-client counts `n [...]` (empty clients pin to row 0, which their
    zero aggregation weight then discards)."""
    nf = n.astype(jnp.float32)[..., None]
    idx = (u * nf).astype(jnp.int32)
    return jnp.minimum(idx, jnp.maximum(n[..., None] - 1, 0))


def _cast_opt_state(os_, dtype):
    """Demote an optimizer state's floating leaves (momentum/second-moment
    accumulators) to `dtype` for carry storage; integer leaves (step
    counters) pass through. None/None-dtype are no-ops."""
    if os_ is None or dtype is None:
        return os_
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, os_)


def _promote_opt_state(os_, dtype=jnp.float32):
    """Inverse of `_cast_opt_state`: floating leaves back to fp32 so the
    optimizer update itself always runs full precision."""
    if os_ is None:
        return os_
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, os_)


def local_grads(params, loss_fn: Callable, shards: ClientShards,
                sel: jax.Array, u: jax.Array):
    """Gather each selected client's minibatch from the padded layout and
    take per-client loss + gradient (eq. 2, one local step, vmapped over
    the [S] selected clients). sel [S] client ids; u [S, batch] uniforms.
    Returns (losses [S], grads with leading [S], weights [S])."""
    n = shards.n_samples[sel]                                # [S]
    idx = minibatch_indices(u, n)                            # [S, bs]
    mb = jax.tree.map(lambda a: a[sel[:, None], idx], shards.data)
    losses, grads = jax.vmap(jax.value_and_grad(loss_fn),
                             in_axes=(None, 0))(params, mb)
    return losses, grads, n.astype(jnp.float32)


def init_carry(key: jax.Array, sc: ScenarioParams, mob: ManhattanParams,
               cfg: StreamConfig, params, *, opt=None,
               fleet: Optional[FleetState] = None,
               ch: Optional[ChannelParams] = None) -> RolloutCarry:
    """Initial fused-rollout carry: scheduling state (per `cfg`) plus the
    model replicated over the [B] cell axis (and optimizer state when an
    `(init, update)` pair is given). `key` must match the key later fed
    to `round_keys` for the rollout to be reproducible. Pass the
    rollout's `ch` so the P4 warm-start table seeds at its `p_max`."""
    B = int(cfg.batch)
    opt_state = None if opt is None else replicate(opt[0](params), B)
    return RolloutCarry(sched=sched_state0(key, sc, mob, cfg, fleet, ch),
                        params=replicate(params, B), opt_state=opt_state)


def fused_rollout(keys: jax.Array, sel: jax.Array, mb_u: jax.Array,
                  sched: Scheduler, sc: ScenarioParams,
                  mob: ManhattanParams, ch: ChannelParams, prm: VedsParams,
                  cfg: StreamConfig, loss_fn: Callable,
                  shards: ClientShards, carry: RolloutCarry, *,
                  lr: float = 0.05, clip: float = 5.0, opt=None,
                  steps: Optional[jax.Array] = None,
                  active: Optional[jax.Array] = None,
                  eval_fn: Optional[Callable] = None,
                  eval_mask: Optional[jax.Array] = None,
                  unroll: int = 1, history_chunk: int = 1,
                  state_dtype=None) -> FusedResult:
    """One `lax.scan` for a (segment of a) training run: scheduling +
    minibatch gather + local SGD + aggregation per step.

      keys  [R] | [R, B]   per-round scheduling keys (`round_keys`). The
                           [R, B] layout gives every cell its own key
                           per round — the serving layer (DESIGN.md §13)
                           packs independent client sessions into the
                           cell axis, each bringing its own key
                           schedule, and a packed cell reproduces the
                           same request run alone at B = 1 bit-for-bit.
                           Persistent fleets only (`sched_round_step`
                           rejects per-cell keys in fresh-fleet mode).
      sel   [R, B, S]      client id of each cell's SOV slot per round
      mb_u  [R, B, S, bs]  uniform minibatch draws
      carry                `init_carry(...)` or a previous segment's
                           (sched=fleet-or-queues, params, opt_state)
      steps [R]            absolute round indices (optimizer schedules);
                           defaults to arange(R)
      active [R] | [R, B]  no-op mask: an inactive round's scan step
                           computes and then discards everything — the
                           carry (scheduling state, params, optimizer
                           state) passes through untouched, bit-for-bit.
                           `run_fl` pads every eval segment to ONE
                           common length with inactive tail rounds, so a
                           whole run compiles a single segment shape
                           instead of up to three (1 / eval_every /
                           remainder). The [R, B] layout deactivates
                           per CELL and per round: the serving layer
                           packs requests of ragged round counts (cell b
                           active for its own R_b rounds, padding slots
                           all-inactive), and an inactive cell's carry
                           passes through untouched while its neighbors
                           train. Incompatible with `cfg.handoff` (the
                           exchange moves vehicles between cells, which
                           a per-cell no-op mask cannot revert).
                           Defaults to all-active; outputs and losses of
                           inactive rounds are garbage and must be
                           ignored by the caller.
      eval_fn              traceable per-cell eval `params -> scalar`.
                           Runs INSIDE the scan as a `lax.cond` branch
                           on the rounds flagged by `eval_mask`
                           (evaluating the post-aggregation params), so
                           a run with eval is still ONE dispatch with a
                           single trailing device sync — no segmentation
                           (DESIGN.md §10). Results in
                           `FusedResult.metric [R, B]`; non-eval rounds
                           hold NaN.
      eval_mask [R] bool   which rounds run the eval branch (ANDed with
                           `active`); ignored without `eval_fn`.
      unroll               rounds unrolled per scan iteration. XLA CPU
                           executes `while`-loop bodies with degraded
                           intra-op threading, so compute-bound local
                           models (convs) can run an order of magnitude
                           slower inside the scan than dispatched from
                           the host; unrolling restores multithreaded
                           execution at linear compile cost. Leave at 1
                           for dispatch-bound (small-model) runs and on
                           accelerator backends.
      history_chunk        memory lever (DESIGN.md §12): with k > 1 the
                           scan runs as R/k outer steps of k inner
                           rounds each, writing every k-round history
                           block into preallocated [R, ...] buffers via
                           `lax.dynamic_update_slice_in_dim` instead of
                           letting one monolithic scan stack all R
                           steps. The buffers thread through the outer
                           carry, so a jitted whole-run step that
                           donates its carry updates the history IN
                           PLACE — chunked output is bit-for-bit equal
                           to unchunked (same body, same order). R must
                           divide by k.
      state_dtype          memory lever (DESIGN.md §12): storage dtype
                           (e.g. jnp.bfloat16) for the cast-tolerant
                           carry state between rounds — the persistent
                           fleet's P4 warm-start table
                           (`streaming.FLEET_CAST_FIELDS`, ~95% of
                           FleetState bytes) and the optimizer
                           accumulators. Params, virtual queues,
                           batteries, and the [B, N] world fields stay
                           fp32 masters, and every round's compute runs
                           fp32 (promote at round start, demote at
                           round end); results come back promoted.
                           None = fp32 throughout.

    Resumable: feed `FusedResult`'s (fleet-or-carry, params, opt_state)
    back as the next segment's carry with the next slice of keys/sel/mb_u
    — a segmented rollout replays the one-scan program exactly.
    """
    # chunked round_chunk mode solves rounds in parallel — params cannot
    # thread through them; validate_stream_config owns the rejection
    validate_stream_config(cfg, threads_params=True)
    R = keys.shape[0]
    if steps is None:
        steps = jnp.arange(R)
    if active is None:
        active = jnp.ones((R,), bool)
    if active.ndim == 2 and cfg.handoff:
        raise ValueError("per-cell active masks [R, B] cannot compose "
                         "with handoff: the cross-cell exchange moves "
                         "vehicles between cells, which an inactive "
                         "cell's carry pass-through cannot revert")
    if eval_mask is None:
        eval_mask = jnp.zeros((R,), bool)

    @scope("step")
    def train_cell(p, os_, sel_c, u_c, mask_c, r):
        losses, grads, nf = local_grads(p, loss_fn, shards, sel_c, u_c)
        new_p, new_os = fedavg_apply(p, grads, mask_c, nf, lr=lr,
                                     clip=clip, opt=opt, opt_state=os_,
                                     step=r)
        w = mask_c * nf
        den = jnp.maximum(w.sum(), 1e-9)
        loss = jnp.sum(jnp.where(w > 0, losses * w, 0.0)) / den
        return new_p, new_os, loss

    B = int(cfg.batch)

    def body(c: RolloutCarry, x):
        k, sel_r, u_r, r, a, ev = x
        # bf16 lever: the carry is STORED demoted; every round's compute
        # runs on the promoted fp32 view (no-ops when state_dtype=None)
        st_in = promote_sched_state(c.sched) if state_dtype else c.sched
        os_in = (_promote_opt_state(c.opt_state) if state_dtype
                 else c.opt_state)
        st, out = sched_round_step(st_in, k, sched, sc, mob, ch, prm,
                                   cfg)
        mask = out.success.astype(jnp.float32)               # [B, S]
        in_axes = (0, None if os_in is None else 0, 0, 0, 0, None)
        new_p, new_os, loss = jax.vmap(train_cell, in_axes=in_axes)(
            c.params, os_in, sel_r, u_r, mask, r)
        if os_in is None:
            new_os = None
        new_c = RolloutCarry(sched=cast_sched_state(st, state_dtype),
                             params=new_p,
                             opt_state=_cast_opt_state(new_os,
                                                       state_dtype))

        # inactive (padding) rounds are pure no-ops: the whole carry is
        # selected back, so padded segments are bit-for-bit equal to
        # unpadded ones on the rounds that count. With a per-cell mask
        # (`a` is [B]) the select broadcasts against each leaf's leading
        # cell axis, so only the inactive CELLS pass through.
        def keep(n, o):
            return jnp.where(
                a.reshape(a.shape + (1,) * (n.ndim - a.ndim)), n, o)

        new_c = jax.tree.map(keep, new_c, c)
        if eval_fn is None:
            return new_c, (out, loss)
        # eval as a scanned branch: `cond` skips the eval computation
        # entirely on non-eval rounds — no per-segment host round-trip
        met = jax.lax.cond(
            ev & (a if a.ndim == 0 else a.any()),
            lambda p: jax.vmap(
                lambda q: jnp.asarray(eval_fn(q), jnp.float32))(p),
            lambda p: jnp.full((B,), jnp.nan, jnp.float32),
            new_c.params)
        if a.ndim:
            met = jnp.where(a, met, jnp.nan)
        return new_c, (out, loss, met)

    if state_dtype is not None:
        carry = RolloutCarry(
            sched=cast_sched_state(carry.sched, state_dtype),
            params=carry.params,
            opt_state=_cast_opt_state(carry.opt_state, state_dtype))

    xs = (keys, sel, mb_u, steps, active, eval_mask)
    K = int(history_chunk)
    if K <= 1 or K >= R:
        end, ys = jax.lax.scan(body, carry, xs,
                               unroll=min(int(unroll), R))
    else:
        if R % K:
            raise ValueError(f"segment length {R} not divisible by "
                             f"history_chunk={K}")
        # chunked emission: R/K outer steps, each scanning K rounds and
        # writing the block into the preallocated [R, ...] buffers. Same
        # body in the same order -> bit-for-bit equal to the plain scan;
        # the buffers live in the outer carry, so a donating jit updates
        # them in place instead of stacking a fresh [R, ...] history.
        ys_shape = jax.eval_shape(
            lambda c, x: jax.lax.scan(body, c, x)[1], carry, xs)
        bufs0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             ys_shape)

        def chunk_body(cb, c0):
            c, bufs = cb
            xs_c = jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, c0 * K, K, 0),
                xs)
            c2, ys_c = jax.lax.scan(body, c, xs_c,
                                    unroll=min(int(unroll), K))
            bufs = jax.tree.map(
                lambda b, y: jax.lax.dynamic_update_slice_in_dim(
                    b, y, c0 * K, 0), bufs, ys_c)
            return (c2, bufs), None

        (end, ys), _ = jax.lax.scan(chunk_body, (carry, bufs0),
                                    jnp.arange(R // K))

    if state_dtype is not None:
        end = RolloutCarry(sched=promote_sched_state(end.sched),
                           params=end.params,
                           opt_state=_promote_opt_state(end.opt_state))
    if eval_fn is None:
        (outs, losses), metric = ys, None
    else:
        outs, losses, metric = ys
    fleet = None if cfg.fresh_fleet else end.sched
    # `.carry` reports the last ACTIVE round's queues — with a padded
    # segment the trailing scan steps are no-ops whose outputs are junk.
    # Per-cell active masks report per-cell last-active rounds (an
    # all-inactive padding cell gathers junk its caller never reads).
    if active.ndim == 2:
        last = jnp.max(jnp.where(active, jnp.arange(R)[:, None], -1), 0)
        carry_out = jax.tree.map(
            lambda x: x[last, jnp.arange(B)], outs.carry)
    else:
        last = jnp.max(jnp.where(active, jnp.arange(R), -1))
        carry_out = jax.tree.map(lambda x: x[last], outs.carry)
    return FusedResult(params=end.params, opt_state=end.opt_state,
                       outputs=outs, loss=losses, fleet=fleet,
                       carry=carry_out, metric=metric)


# The tier-keyed segment cache. One entry per (loss_fn, scheduler,
# params, StreamConfig, lr, unroll, eval_fn, history_chunk): the entry's
# jitted wrapper then compiles ONE executable per horizon shape it is
# called at (the segment length L arrives via `keys`, never via the
# key). The serving layer's executable tiers (DESIGN.md §13) are exactly
# this contract: each occupancy tier B is its own cache entry (B lives
# in `cfg.batch`), each horizon tier L its own XLA compile under that
# entry — so a tiered service, the simulator, and a test with matching
# shapes all share executables instead of re-tracing.
@functools.lru_cache(maxsize=32)
def fused_segment(loss_fn: Callable, sched_name: str, sc, mob, ch, prm,
                  cfg: StreamConfig, lr: float, unroll: int,
                  eval_fn: Optional[Callable] = None,
                  history_chunk: int = 1):
    """Jitted fused-rollout segment, cached across callers (per-call jit
    wrappers would re-trace every invocation). Callers normalize
    `cfg.n_rounds` to 0 — the segment's length comes from the `keys`
    argument, so runs that differ only in total round count share one
    cache entry (and one compiled program when their segment lengths
    match). `eval_fn` (in-scan eval) joins the cache key; the rounds it
    fires on arrive as the `ev` array argument."""
    from repro.core.baselines import get_scheduler
    sched = get_scheduler(sched_name)

    @jax.jit
    def seg(carry, keys, sel, mb_u, shards, steps, active, ev):
        return fused_rollout(keys, sel, mb_u, sched, sc, mob, ch, prm,
                             cfg, loss_fn, shards, carry, lr=lr,
                             steps=steps, active=active, eval_fn=eval_fn,
                             eval_mask=ev, unroll=unroll,
                             history_chunk=history_chunk)

    return register("fused_segment", seg)
