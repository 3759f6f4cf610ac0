"""Single-host VFL simulator for the paper-scale experiments (Figs 10-12).

40 clients hold data partitions; each round, S of them are the SOVs
(vehicles currently in coverage), U others relay as OPVs. One local SGD step
per round (eq. 2), success decided by the chosen scheduler, aggregation by
(11). For one local step, FedAvg of models == FedSGD of gradients, which is
how we batch clients: one vmapped gradient call over the stacked per-client
minibatches per round.

Client data is held in the padded `[C, n_max, ...]` `ClientShards` layout
(ragged per-client dicts are padded on entry); per-client aggregation
weights always use the true (unpadded) sample counts.

With `round_batch = B > 1`, scenario generation and scheduling run for B
rounds per dispatch: the block is a vmapped stack of the *same* per-round
draws the B = 1 path makes (`fold_in(key, r)` per round), so the history
is identical for every `round_batch` — the knob only amortizes XLA
dispatch. A trailing partial block schedules exactly the remaining rounds,
never a padded batch.

With `streaming = True`, the whole run — scheduling AND training — is the
fused engine's single `lax.scan` program (`repro.fl.engine.fused_rollout`):
a persistent fleet drives through coverage round-to-round, the virtual
energy queues carry (`carry_queues`), client sampling is on-device
(`jax.random` permutation per round + uniform minibatch draws), and the
model parameters thread the scan carry alongside the queues. Evaluation
runs inside the same scan by default (`eval_in_scan`): a whole run with
eval is ONE dispatch with a single trailing sync; `eval_in_scan=False`
keeps the segmented host-eval path. `fused=False` keeps the previous
host-gather streaming path (one-scan scheduling, per-round host loop for
gather + update) as a compatibility/benchmark reference; the blocked
(`streaming=False`) path is the thin per-round-dispatch compatibility
mode.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.channel.mobility import ManhattanParams
from repro.channel.v2x import ChannelParams
from repro.core.baselines import get_scheduler
from repro.core.lyapunov import VedsParams
from repro.core.scenario import ScenarioParams, make_round
from repro.core.scheduler import RolloutCarry
from repro.core.streaming import (StreamConfig, round_keys,
                                  stream_rounds)
from repro.fl.engine import (ClientShards, fedavg_apply, fused_rollout,
                             fused_segment, init_carry)


@dataclasses.dataclass(frozen=True)
class FLSimConfig:
    n_clients: int = 40
    n_sov: int = 10
    n_opv: int = 10
    n_slots: int = 60
    rounds: int = 50
    round_batch: int = 1         # rounds scheduled per XLA dispatch (B)
    batch_size: int = 32
    lr: float = 0.05
    scheduler: str = "veds"
    v_max: float = 10.0
    alpha: float = 2.0
    V: float = 0.2
    q_bits: float = 1e7
    seed: int = 0
    streaming: bool = False      # one-scan rollout + on-device sampling
    carry_queues: bool = True    # streaming: thread eqs. (19)-(20)
    n_fleet: int = 0             # streaming: pool size (0 -> 2 (S + U))
    fused: bool = True           # streaming: train inside the same scan
    fused_unroll: int = 1        # rounds unrolled per fused scan step —
    #                              raise for compute-bound local models on
    #                              CPU (loop bodies lose intra-op threads)
    handover_delay: bool = False  # streaming: one-round coverage lag
    ipm_warm_iters: int = 0      # streaming VEDS+COT: warm-started P4
    #                              budget (VedsParams.ipm_warm_iters);
    #                              0 keeps the cold full-budget solves
    eval_in_scan: bool = True    # streaming+fused: run eval_fn INSIDE
    #                              the rollout scan (whole run = ONE
    #                              dispatch + one trailing sync). Needs a
    #                              jax-traceable eval_fn; set False to
    #                              keep the segmented host-eval path
    fused_history_chunk: int = 1  # streaming+fused memory lever: emit the
    #                              per-round history in chunks of this
    #                              many rounds into preallocated [R,...]
    #                              buffers (fused_rollout history_chunk;
    #                              DESIGN.md §12). Bit-for-bit equal to 1;
    #                              segment lengths must divide by it
    # (No handoff knob: run_fl trains ONE cell (batch=1), where the §11
    # cross-cell exchange is the identity by construction. Multi-cell
    # handoff rollouts go through stream_rounds / fused_rollout, which
    # take a full StreamConfig.)


# Bounded: keyed partly on the user's loss_fn, so a caller passing a
# fresh lambda per run_fl call (fig10/fig12 style) gets no reuse — the
# bound keeps those entries (compiled executables + loss closures) from
# accumulating for the process lifetime.
@functools.lru_cache(maxsize=32)
def _vgrad(loss_fn: Callable):
    """All S per-client gradients in one vmapped call (FedSGD batching);
    cached per loss function so repeated `run_fl` calls reuse the
    compiled program."""
    return jax.jit(jax.vmap(jax.grad(loss_fn), in_axes=(None, 0)))


@functools.lru_cache(maxsize=32)
def _apply(lr: float):
    return jax.jit(lambda params, grads, mask, weights: fedavg_apply(
        params, grads, mask, weights, lr=lr)[0])


# The jitted fused-rollout segment cache now lives in the engine
# (`repro.fl.engine.fused_segment` — the tier-keyed contract the serving
# layer's executable ladder builds on); this alias keeps the simulator's
# historical import surface.
_fused_segment = fused_segment


def run_fl(key: jax.Array, params, loss_fn: Callable,
           client_data: Union[List[Dict[str, jax.Array]], ClientShards],
           sim: FLSimConfig, eval_fn: Callable | None = None,
           eval_every: int = 5) -> Dict[str, list]:
    """Generic FL loop. client_data: per-client dict of arrays (padded on
    entry) or an already-padded `ClientShards`.

    Returns history: round, sim_time, n_success, eval metric, plus
    `scheduled_rounds` — the total number of rounds actually scheduled
    (== sim.rounds: trailing partial blocks are trimmed, not padded).
    The fused streaming path also reports `dispatches` — how many jitted
    rollout segments the run launched (1 with in-scan eval or no eval:
    the whole run is one XLA program with a single trailing
    `block_until_ready`) — and `loss`, the weighted mean local training
    loss of each recorded round.
    """
    sc, mob, ch, prm = _sim_params(sim)
    sched = get_scheduler(sim.scheduler)

    if sim.streaming and sim.fused:
        shards = (client_data if isinstance(client_data, ClientShards)
                  else ClientShards.from_ragged(client_data))
        return _run_fused(key, params, loss_fn, shards, sim, sc, mob, ch,
                          prm, eval_fn, eval_every)

    vgrad_fn = _vgrad(loss_fn)
    apply_update = _apply(sim.lr)
    # the gather paths stay host-side and zero-copy: per-client numpy
    # views (ragged input as-is, padded input sliced back to its true
    # counts) with explicit true-count weights — never a padded copy,
    # never a device upload
    if isinstance(client_data, ClientShards):
        np_n = np.asarray(client_data.n_samples)
        host = {k: np.asarray(v) for k, v in client_data.data.items()}
        np_clients = [{k: v[c, :np_n[c]] for k, v in host.items()}
                      for c in range(client_data.n_clients)]
    else:
        np_clients = [{k: np.asarray(v) for k, v in d.items()}
                      for d in client_data]
        np_n = np.array([next(iter(d.values())).shape[0] if d else 0
                         for d in np_clients], np.int64)
    # minibatch schema for empty clients (a client may be a bare {})
    schema = next(({k: (v.shape[1:], v.dtype) for k, v in d.items()}
                   for d in np_clients if d), {})

    history = {"round": [], "time": [], "n_success": [], "metric": [],
               "scheduled_rounds": 0}
    sim_time = 0.0

    if sim.streaming:
        masks, n_succ, sel, mb_u = _streaming_schedule(key, sim, sc, mob,
                                                       ch, prm, sched)
        rng = None
    else:
        rng = np.random.default_rng(sim.seed)

    def round_step(r, mask, n_success, sel_r, mb_u_r, params):
        nonlocal sim_time
        mbs, weights = [], []
        for s, ci in enumerate(sel_r):
            n = int(np_n[int(ci)])
            if n == 0:                               # empty client: zero
                mbs.append({                         # batch, weight 0
                    k: np.zeros((sim.batch_size,) + shp, dt)
                    for k, (shp, dt) in schema.items()})
                weights.append(0.0)
                continue
            if mb_u_r is None:                       # host-RNG contract
                idx = rng.choice(max(n, 1), size=sim.batch_size,
                                 replace=n < sim.batch_size)
            else:                                    # on-device uniforms
                idx = np.minimum((mb_u_r[s] * n).astype(np.int64),
                                 max(n - 1, 0))
            mbs.append({k: v[idx] for k, v in np_clients[int(ci)].items()})
            weights.append(float(n))                 # true sample count
        mb_stack = jax.tree.map(lambda *x: jnp.stack(x), *mbs)
        grads_stack = vgrad_fn(params, mb_stack)
        params = apply_update(params, grads_stack, mask,
                              jnp.asarray(weights, jnp.float32))
        sim_time += sim.n_slots * prm.slot
        if eval_fn is not None and (r % eval_every == 0 or
                                    r == sim.rounds - 1):
            history["round"].append(r)
            history["time"].append(sim_time)
            history["n_success"].append(n_success)
            history["metric"].append(float(eval_fn(params)))
        return params

    if sim.streaming:
        for r in range(sim.rounds):
            params = round_step(r, masks[r], int(n_succ[r]), sel[r],
                                mb_u[r], params)
        history["scheduled_rounds"] = sim.rounds
        jax.block_until_ready(params)
        return history

    B = max(1, sim.round_batch)
    mk_round = jax.jit(lambda k: make_round(k, sc, mob, ch, prm))
    # a block vmap-stacks the per-round cells, so cell j of the block is
    # bit-for-bit round r0 + j of the B = 1 path; the last (possibly
    # partial) block stacks exactly the remaining rounds
    mk_block = jax.jit(jax.vmap(mk_round))
    run_sched = jax.jit(lambda r: sched.solve_round(r, prm, ch))
    for r0 in range(0, sim.rounds, B):
        n_block = min(B, sim.rounds - r0)
        keys = jnp.stack([jax.random.fold_in(key, r)
                          for r in range(r0, r0 + n_block)])
        out = run_sched(mk_block(keys) if B > 1 else mk_round(keys[0]))
        history["scheduled_rounds"] += n_block
        for j in range(n_block):
            cell = out.cell(j) if B > 1 else out
            mask = jnp.asarray(cell.success, jnp.float32)
            sel_r = rng.choice(sim.n_clients, size=sim.n_sov,
                               replace=False)
            params = round_step(r0 + j, mask, int(cell.n_success), sel_r,
                                None, params)
    jax.block_until_ready(params)
    return history


def _sim_params(sim: FLSimConfig):
    """The scenario, mobility, channel and VEDS parameters of a run."""
    mob = ManhattanParams(v_max=sim.v_max)
    ch = ChannelParams()
    prm = VedsParams(alpha=sim.alpha, V=sim.V, Q=sim.q_bits, slot=0.1,
                     ipm_warm_iters=sim.ipm_warm_iters)
    sc = ScenarioParams(n_sov=sim.n_sov, n_opv=sim.n_opv,
                        n_slots=sim.n_slots, batch_size=sim.batch_size)
    return sc, mob, ch, prm


def _stream_cfg(sim: FLSimConfig) -> StreamConfig:
    return StreamConfig(n_rounds=sim.rounds, batch=1,
                        carry_queues=sim.carry_queues,
                        n_fleet=sim.n_fleet or None,
                        handover_delay=sim.handover_delay)


def _stream_draws(key: jax.Array, sim: FLSimConfig):
    """The streaming RNG contract shared by the fused and host-gather
    paths: (k_sched, sel [R, S], mb_u [R, S, bs]) — a client permutation
    per round plus uniform minibatch draws, all on-device."""
    R = sim.rounds
    k_sched, k_sel, k_mb = jax.random.split(key, 3)
    sel = jax.vmap(
        lambda k: jax.random.permutation(k, sim.n_clients)[:sim.n_sov]
    )(jax.random.split(k_sel, R))                            # [R, S]
    mb_u = jax.random.uniform(k_mb, (R, sim.n_sov, sim.batch_size))
    return k_sched, sel, mb_u


def _fused_evals(sim: FLSimConfig, eval_fn, eval_every: int):
    R = sim.rounds
    return ([] if eval_fn is None else
            [r for r in range(R) if r % eval_every == 0 or r == R - 1])


def _fused_inputs(key, params, sim: FLSimConfig, sc, mob, ch):
    """(cfg, carry, keys [R], sel [R, 1, S], mb_u [R, 1, S, bs]) of a
    fused run: the streaming draws plus the B = 1 rollout carry."""
    cfg = _stream_cfg(sim)
    k_sched, sel, mb_u = _stream_draws(key, sim)
    keys = round_keys(k_sched, cfg, sim.rounds)
    carry = init_carry(k_sched, sc, mob, cfg, params, ch=ch)
    return cfg, carry, keys, sel[:, None], mb_u[:, None]


def fused_program(key, params, loss_fn, shards: ClientShards,
                  sim: FLSimConfig, eval_fn=None, eval_every: int = 5):
    """The one-dispatch fused run (in-scan eval, or no eval) as
    `(seg, args)`: `seg(*args)` is the whole run `run_fl` dispatches, so
    `seg.lower(*args)` is the program it compiles."""
    sc, mob, ch, prm = _sim_params(sim)
    R = sim.rounds
    cfg, carry, keys, sel, mb_u = _fused_inputs(key, params, sim, sc,
                                                mob, ch)
    seg = _fused_segment(loss_fn, sim.scheduler, sc, mob, ch, prm,
                         dataclasses.replace(cfg, n_rounds=0), sim.lr,
                         max(1, sim.fused_unroll), eval_fn,
                         max(1, sim.fused_history_chunk))
    ev = jnp.zeros((R,), bool)
    evals = _fused_evals(sim, eval_fn, eval_every)
    if evals:
        ev = ev.at[jnp.asarray(evals)].set(True)
    return seg, (carry, keys, sel, mb_u, shards, jnp.arange(R),
                 jnp.ones((R,), bool), ev)


def _run_fused(key, params, loss_fn, shards: ClientShards,
               sim: FLSimConfig, sc, mob, ch, prm, eval_fn, eval_every):
    """The fused path. Default (`eval_in_scan`, or no eval_fn): the whole
    run — scheduling, training, AND eval — is ONE `fused_rollout` scan:
    eval runs as a `lax.cond` branch inside the program, so the run is a
    single dispatch with a single trailing `block_until_ready`. With
    `eval_in_scan=False` the run is segmented at eval points (host-side
    eval_fn per segment, kept for non-traceable eval functions), every
    segment padded to one compiled shape."""
    R = sim.rounds
    evals = _fused_evals(sim, eval_fn, eval_every)
    history = {"round": [], "time": [], "n_success": [], "metric": [],
               "loss": [], "scheduled_rounds": R, "dispatches": 0}

    if eval_fn is None or sim.eval_in_scan:
        seg_fn, args = fused_program(key, params, loss_fn, shards, sim,
                                     eval_fn, eval_every)
        res = seg_fn(*args)
        history["dispatches"] = 1
        # the ONE trailing sync: everything read below is a materialized
        # buffer, not a new device round-trip
        jax.block_until_ready(res)
        if evals:
            n_succ = np.asarray(res.outputs.n_success[:, 0])
            met = np.asarray(res.metric[:, 0])
            loss = np.asarray(res.loss[:, 0])
            for r in evals:
                history["round"].append(r)
                history["time"].append((r + 1) * sim.n_slots * prm.slot)
                history["n_success"].append(int(n_succ[r]))
                history["metric"].append(float(met[r]))
                history["loss"].append(float(loss[r]))
        return history

    cfg, carry, keys, sel, mb_u = _fused_inputs(key, params, sim, sc, mob,
                                                ch)
    K = max(1, sim.fused_history_chunk)
    seg_fn = _fused_segment(loss_fn, sim.scheduler, sc, mob, ch, prm,
                            dataclasses.replace(cfg, n_rounds=0),
                            sim.lr, max(1, sim.fused_unroll), None, K)
    cuts = [e + 1 for e in evals]
    # one compiled segment length for the whole run: every segment is
    # padded to the longest with no-op (inactive) tail rounds, so the
    # run compiles ONE program instead of up to three (the 1-round
    # r=0-eval segment, the eval_every middle, and the remainder).
    # Chunked history emission (`fused_history_chunk`) needs the padded
    # length to divide by the chunk — extend the no-op tail to the next
    # multiple, which the active mask makes bit-for-bit free.
    L = max(cut - r0 for r0, cut in zip([0] + cuts[:-1], cuts))
    L = -(-L // K) * K

    def padded(x, r0, n):
        s = x[r0:r0 + n]
        if n < L:
            s = jnp.concatenate(
                [s, jnp.broadcast_to(s[-1:], (L - n,) + s.shape[1:])])
        return s

    no_ev = jnp.zeros((L,), bool)
    r0 = 0
    for cut in cuts:
        n = cut - r0
        res = seg_fn(carry, padded(keys, r0, n), padded(sel, r0, n),
                     padded(mb_u, r0, n), shards,
                     padded(jnp.arange(R), r0, n), jnp.arange(L) < n,
                     no_ev)
        carry = RolloutCarry(
            sched=res.fleet if res.fleet is not None else res.carry,
            params=res.params, opt_state=res.opt_state)
        history["dispatches"] += 1
        r = cut - 1
        history["round"].append(r)
        history["time"].append((r + 1) * sim.n_slots * prm.slot)
        history["n_success"].append(
            int(res.outputs.n_success[n - 1, 0]))
        history["loss"].append(float(res.loss[n - 1, 0]))
        history["metric"].append(float(eval_fn(
            jax.tree.map(lambda x: x[0], res.params))))
        r0 = cut
    jax.block_until_ready(carry.params)
    return history


def _streaming_schedule(key, sim: FLSimConfig, sc, mob, ch, prm, sched):
    """Host-gather streaming compatibility path: one compiled program for
    the whole run's scheduling + on-device client sampling, then a host
    loop trains. Returns (masks [R,S], n_success [R], sel [R,S],
    mb_u [R,S,batch]) as host arrays. Shares `_stream_draws` with the
    fused path, so both paths consume identical selections/minibatches."""
    cfg = _stream_cfg(sim)
    k_sched, sel, mb_u = _stream_draws(key, sim)

    @jax.jit
    def program(k_sched):
        res = stream_rounds(k_sched, sched, sc, mob, ch, prm, cfg)
        return (res.outputs.success[:, 0].astype(jnp.float32),
                res.outputs.n_success[:, 0])

    masks, n_succ = program(k_sched)
    return (np.asarray(masks), np.asarray(n_succ), np.asarray(sel),
            np.asarray(mb_u))
