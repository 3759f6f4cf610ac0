"""Plain reference of the federated-learning round the benchmark times.

It restates, from the paper (arXiv:2406.17470, Sec. III-V and Table I)
and the configuration file alone, what one round of the system does:

  mobility   vehicles drive a Manhattan grid, turning at intersections
  channels   3GPP TR 37.885 urban V2X pathloss, shadowing and fading
  roles      the first S vehicles in RSU coverage are SOVs, the next U OPVs
  handoff    with a grid of RSUs, every vehicle moves to its nearest RSU
             before the round, at most N vehicles per cell
  scheduler  VEDS+COT (Algorithms 1 and 2, P4 by an interior point) or
             MADCA (best instantaneous V2I channel, direct uploads only)
  queues     virtual energy queues, eqs. (19)-(20), carried per vehicle
  training   one local SGD step per SOV on its minibatch of the
             configuration's model (the `reference_loss` of its model
             file), mask-weighted FedAvg of the gradients, clipped at 5

It imports nothing of the program and is written for clarity: one cell
at a time, one compiled program per cell-round, float32 at the highest
matmul precision, no kernels and no packing. The random draws
(mobility, channels, fleet) follow the key schedule the configuration's
system uses, so that both see the same world: `jax.random` is the draw,
the arithmetic is restated here.

`dtype` other than float32 runs the whole reference in that dtype: the
benchmark's control, which must come out as not correct.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

LN2 = 0.6931471805599453
NEG = -1e30
HIGHEST = jax.lax.Precision.HIGHEST
_DIRS = ((1.0, 0.0), (-1.0, 0.0), (0.0, 1.0), (0.0, -1.0))
PER_SLOT = ("g_sr", "g_or", "g_so")


# ---------------------------------------------------------------- world

def init_vehicles(key, n: int, mob: Dict, rsu):
    """n vehicles on the street grid, within 0.8 x coverage of `rsu`."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    block, extent = mob["block"], mob["extent"]
    line = jax.random.randint(k1, (n,), 0, int(extent // block) + 1)
    line = line.astype(jnp.float32)
    offset = jax.random.uniform(k2, (n,), minval=0.0, maxval=extent)
    r = 0.8 * mob["coverage"]
    cx, cy = rsu[0], rsu[1]
    line = jnp.clip(line, jnp.floor(jnp.maximum(cx - r, 0.0) / block),
                    jnp.ceil(jnp.minimum(cx + r, extent) / block))
    offset = jnp.clip(offset, cy - r, cy + r)
    horiz = jax.random.bernoulli(k3, 0.5, (n,))
    x = jnp.where(horiz, offset, line * block)
    y = jnp.where(horiz, line * block, offset)
    heading = jnp.where(horiz, jax.random.randint(k4, (n,), 0, 2),
                        2 + jax.random.randint(k4, (n,), 0, 2))
    speed = jax.random.uniform(jax.random.fold_in(key, 9), (n,),
                               minval=0.3 * mob["v_max"],
                               maxval=max(mob["v_max"], 1e-3))
    return jnp.stack([x, y], -1), heading, speed


def drive_slot(key, pos, heading, speed, mob: Dict, dt: float):
    """One slot of driving: straight on, a turn at a crossed
    intersection with probability `turn_prob`, a bounce at the edge."""
    block, extent = mob["block"], mob["extent"]
    dirs = jnp.asarray(_DIRS, jnp.float32)
    new = pos + speed[:, None] * dt * dirs[heading]
    rows = jnp.arange(pos.shape[0])
    axis = jnp.where(heading < 2, 0, 1)
    old_c, new_c = pos[rows, axis], new[rows, axis]
    old_cell, new_cell = jnp.floor(old_c / block), jnp.floor(new_c / block)
    turn = jax.random.bernoulli(key, mob["turn_prob"], heading.shape) \
        & (old_cell != new_cell)
    snap = jnp.where(new_c > old_c, new_cell, old_cell) * block
    snapped = new.at[rows, axis].set(snap)
    turned = jnp.where(
        heading < 2,
        2 + jax.random.randint(jax.random.fold_in(key, 1), heading.shape,
                               0, 2),
        jax.random.randint(jax.random.fold_in(key, 2), heading.shape, 0, 2))
    heading = jnp.where(turn, turned, heading)
    new = jnp.where(turn[:, None], snapped, new)
    hit = ((new > extent) | (new < 0.0)).any(-1)
    new = jnp.clip(new, 0.0, extent)
    back = jnp.asarray([1, 0, 3, 2], jnp.int32)
    return new, jnp.where(hit, back[heading], heading)


def drive(key, pos, heading, speed, mob: Dict, n_slots: int, dt: float):
    """`n_slots` slots; returns the end state and positions [T, N, 2]."""
    def slot(state, k):
        p, h = drive_slot(k, state[0], state[1], speed, mob, dt)
        return (p, h), p
    (pos, heading), traj = jax.lax.scan(slot, (pos, heading),
                                        jax.random.split(key, n_slots))
    return pos, heading, traj


def gain(key, d, ch: Dict, in_range=None):
    """Linear power gain of links at distances `d` (Table I channel)."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p_los = jnp.exp(-jnp.maximum(d - 10.0, 0.0) / ch["los_d0"])
    los = jax.random.bernoulli(k1, jnp.clip(p_los, 0.05, 1.0))
    blocked = jax.random.bernoulli(k2, 0.3, d.shape)
    block_db = jnp.maximum(0.0, ch["blockage_mean_db"] + ch["blockage_std_db"]
                           * jax.random.normal(k3, d.shape))
    lg = jnp.log10(jnp.maximum(d, 1.0))
    lf = math.log10(ch["fc_ghz"])
    pl = jnp.where(los, 38.77 + 16.7 * lg + 18.2 * lf,
                   36.85 + 30.0 * lg + 18.9 * lf)
    pl = pl + jnp.where(los & blocked, block_db, 0.0)
    sigma = jnp.where(los, ch["shadow_los_db"], ch["shadow_nlos_db"])
    shadow = sigma * jax.random.normal(k4, d.shape)
    g = 10.0 ** (-(pl + shadow) / 10.0) * jax.random.exponential(k5, d.shape)
    return g if in_range is None else jnp.where(in_range, g, 0.0)


def noise_power(ch: Dict) -> float:
    return 10.0 ** (ch["noise_dbm_hz"] / 10.0) * 1e-3 * ch["bandwidth"]


def rsu_grid(B: int, mob: Dict) -> np.ndarray:
    """RSUs on a square grid at pitch 0.75 x coverage, shrunk to fit."""
    g = int(math.ceil(math.sqrt(B)))
    rows = (B + g - 1) // g
    pitch = min(0.75 * mob["coverage"],
                mob["extent"] / max(g - 1, rows - 1, 1))
    i = np.arange(B)
    x = 0.5 * mob["extent"] + ((i % g) - 0.5 * (g - 1)) * pitch
    y = 0.5 * mob["extent"] + ((i // g) - 0.5 * (rows - 1)) * pitch
    return np.stack([x, y], -1).astype(np.float32)


def init_fleet(key, c: Dict, B: int, handoff: bool) -> Dict:
    """B pools of 2(S+U) vehicles (numpy dict of [B, N, ...] arrays)."""
    S, U, mob = c["n_sov"], c["n_opv"], c["mobility"]
    N = c.get("n_fleet") or 2 * (S + U)
    k_cell, k_rsu, k_j, k_a = jax.random.split(
        jax.random.fold_in(key, 0xF1EE7), 4)
    if handoff:
        rsu = jnp.asarray(rsu_grid(B, mob))
    else:
        rsu = jax.random.uniform(k_rsu, (B, 2), minval=0.25 * mob["extent"],
                                 maxval=0.75 * mob["extent"])
    cells = [init_vehicles(k, N, mob, rsu[b])
             for b, k in enumerate(jax.random.split(k_cell, B))]
    pos = jnp.stack([p for p, _, _ in cells])
    fleet = {
        "pos": pos, "dir": jnp.stack([h for _, h, _ in cells]),
        "speed": jnp.stack([s for _, _, s in cells]),
        "jitter": jax.random.uniform(k_j, (B, N), minval=0.8, maxval=1.2),
        "allowance": jax.random.uniform(k_a, (B, N), minval=c["e_min_j"],
                                        maxval=c["e_max_j"]),
        "energy": jnp.full((B, N), jnp.inf), "queue": jnp.zeros((B, N)),
        "rsu": rsu,
        "covered": jnp.linalg.norm(pos - rsu[:, None], axis=-1)
        <= mob["coverage"],
        "cell": jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None],
                                 (B, N)),
    }
    return {k: np.asarray(v) for k, v in fleet.items()}


def handoff(fleet: Dict, mob: Dict) -> Dict:
    """Every vehicle to its nearest RSU's cell. A cell keeps at most N:
    the first N in (cell, slot) order. The rest fill the free slots in
    row order, parked (cell -1, not covered) until a later handoff."""
    B, N = fleet["covered"].shape
    pos = fleet["pos"].reshape(B * N, 2)
    dist = np.asarray(jnp.linalg.norm(
        jnp.asarray(pos)[:, None] - jnp.asarray(fleet["rsu"])[None], axis=-1))
    target = dist.argmin(-1)
    fill = [0] * B
    slot_of = np.empty(B * N, np.int64)     # vehicle -> new flat slot
    cell = np.full(B * N, -1, np.int64)
    overflow: List[int] = []
    for v in sorted(range(B * N), key=lambda v: (target[v], v)):
        t = target[v]
        if fill[t] < N:
            slot_of[v] = t * N + fill[t]
            cell[slot_of[v]] = t
            fill[t] += 1
        else:
            overflow.append(v)
    free = [b * N + j for b in range(B) for j in range(fill[b], N)]
    for v, s in zip(overflow, free):
        slot_of[v] = s
    src = np.empty(B * N, np.int64)
    src[slot_of] = np.arange(B * N)
    moved = target != np.repeat(np.arange(B), N)
    out = {k: v for k, v in fleet.items()}
    for k in ("pos", "dir", "speed", "jitter", "allowance", "energy",
              "queue", "covered"):
        flat = fleet[k].reshape((B * N,) + fleet[k].shape[2:])
        out[k] = flat[src].reshape(fleet[k].shape)
    out["covered"] = out["covered"] & ~moved[src].reshape(B, N) \
        & (cell.reshape(B, N) >= 0)
    out["cell"] = cell.reshape(B, N).astype(np.int32)
    return out


def cell_round_inputs(c: Dict, key, pos0, heading, speed, rsu, jitter,
                      allowance, energy, active):
    """One cell's round: drive the pool T slots, pick SOVs/OPVs by
    coverage at round start, draw their channels for every slot."""
    S, U, T, dt = c["n_sov"], c["n_opv"], c["n_slots"], c["slot_s"]
    mob, ch = c["mobility"], c["channel"]
    k_mob, k_ch = jax.random.split(key)
    pos, heading, traj = drive(k_mob, pos0, heading, speed, mob, T, dt)
    cov0 = (jnp.linalg.norm(pos0 - rsu, axis=-1) <= mob["coverage"]) \
        & active
    order = jnp.argsort(jnp.where(cov0, 0, 1), stable=True)
    sov, opv = order[:S], order[S:S + U]
    v_s, v_o = cov0[sov], cov0[opv]
    tr_s, tr_o = traj[:, sov], traj[:, opv]
    d_s = jnp.linalg.norm(tr_s - rsu, axis=-1)
    d_o = jnp.linalg.norm(tr_o - rsu, axis=-1)
    d_so = jnp.linalg.norm(tr_s[:, :, None] - tr_o[:, None], axis=-1)
    ks = jax.random.split(k_ch, 3)
    work = c["n_flop_per_sample"] * c["batch_size"]
    jit = jitter[sov]
    budget = jnp.minimum(allowance, jnp.maximum(energy, 0.0))
    rnd = {
        "g_sr": gain(ks[0], d_s, ch, (d_s <= mob["coverage"]) & v_s[None]),
        "g_or": gain(ks[1], d_o, ch, (d_o <= mob["coverage"]) & v_o[None]),
        "g_so": gain(ks[2], d_so, ch) * (v_s[None, :, None]
                                         & v_o[None, None, :]),
        "t_cp": (work / c["clock_hz"] / jit) * v_s,
        "e_cp": (c["rho"] * c["clock_hz"] ** 2 * work * jit ** 2) * v_s,
        "e_sov": budget[sov] * v_s, "e_opv": budget[opv] * v_o,
        "valid_sov": v_s, "valid_opv": v_o,
    }
    return rnd, sov, opv, pos, heading, cov0


# ------------------------------------------------------------ schedulers

def _sigmoid_weight(zeta, c):
    s = jax.nn.sigmoid(c["alpha"] * (zeta - c["Q_bits"]) / c["Q_bits"])
    return c["alpha"] * s * (1.0 - s) / c["Q_bits"]


def _queue_sov(q, e_cm, e_budget, e_cp, T):
    return jnp.maximum(q + e_cm - (e_budget - e_cp) / T, 0.0)


def _queue_opv(q, e_cm, e_budget, T):
    return jnp.maximum(q + e_cm - e_budget / T, 0.0)


def _feasible(p, d, p_max, margin=0.999):
    """Clip into the box; scale the OPV powers so that d.p <= 0."""
    p = jnp.clip(p, 1e-9, p_max - 1e-9)
    room = jnp.maximum(-d[0] * p[0], 1e-30)
    load = jnp.dot(d[1:], p[1:], precision=HIGHEST)
    scale = jnp.minimum(1.0, margin * room / jnp.maximum(load, 1e-30))
    return jnp.concatenate([p[:1], p[1:] * scale])


def solve_p4(cw, a, q, d, p_max, iters: int, mu_final: float):
    """max cw ln(1 + a.p) - q.p  s.t. 0 <= p <= p_max, d.p <= 0, by a
    log-barrier damped Newton ascent (mu from 0.1 down to `mu_final` in
    `iters` steps) and 10 projected gradient steps; never worse than
    p = 0."""
    n = a.shape[0]
    dot = lambda u, v: jnp.dot(u, v, precision=HIGHEST)   # noqa: E731
    p = jnp.full((n,), 0.25, a.dtype) * p_max
    p = _feasible(p.at[0].set(0.5 * p_max[0]), d, p_max, margin=0.5)
    for mu in np.geomspace(1e-1, mu_final, iters).astype(np.float32):
        s = 1.0 + dot(a, p)
        slack = -dot(d, p)
        lo, hi = jnp.maximum(p, 1e-12), jnp.maximum(p_max - p, 1e-12)
        sl = jnp.maximum(slack, 1e-12)
        grad = cw * a / s - q + mu / lo - mu / hi - mu * d / sl
        hess = (-cw * jnp.outer(a, a) / (s * s)
                + jnp.diag(-mu / lo ** 2 - mu / hi ** 2)
                - mu * jnp.outer(d, d) / sl ** 2) - 1e-9 * jnp.eye(n)
        step = jnp.linalg.solve(hess.astype(jnp.float32),
                                -grad.astype(jnp.float32)).astype(a.dtype)
        norm = jnp.linalg.norm(step)
        step = step * jnp.minimum(1.0, 0.5 * jnp.max(p_max) / (norm + 1e-12))
        p = _feasible(p + step, d, p_max)
    for _ in range(10):
        g = cw * a / (1.0 + dot(a, p)) - q
        lr = 0.05 * jnp.max(p_max) / (jnp.linalg.norm(g) + 1e-12)
        p = _feasible(p + lr * g, d, p_max)
    val = cw * jnp.log1p(dot(a, p)) - dot(q, p)
    return jnp.where(val >= 0.0, p, jnp.zeros_like(p))


def veds_slot(c: Dict, rnd: Dict, t, zeta, qs, qu):
    """Algorithm 1 for one slot of one cell: every DT candidate in
    closed form (Prop. 1) and every COT candidate (SOV m with the prefix
    of its OPVs sorted by V2V gain, Prop. 2) through P4; the best
    objective (21a) transmits if it is positive. `rnd` holds this
    slot's gains. Returns (zeta, qs, qu, SOV energy, OPV energy)."""
    ch, V, kappa = c["channel"], c["V"], c["slot_s"]
    bw, pmax, noise = ch["bandwidth"], ch["p_max"], noise_power(ch)
    dt_ = rnd["g_sr"].dtype
    S, U = rnd["g_sr"].shape[0], rnd["g_or"].shape[0]
    T = float(c["n_slots"])
    g_sr, g_or, g_so = rnd["g_sr"], rnd["g_or"], rnd["g_so"]
    w = _sigmoid_weight(zeta, c)
    ok = (rnd["t_cp"] <= t * kappa) & (zeta < c["Q_bits"]) \
        & rnd["valid_sov"]
    # direct transmission, Proposition 1
    cw = V * w * kappa * bw / LN2
    a_dt = g_sr / noise
    p_dt = jnp.clip(cw / jnp.maximum(qs * kappa, 1e-9)
                    - 1.0 / jnp.maximum(a_dt, 1e-30), 0.0, pmax)
    z_dt = kappa * bw * jnp.log2(1.0 + p_dt * a_dt)
    y_dt = V * w * z_dt - qs * kappa * p_dt
    good = ok & (g_sr > 0)
    y_dt = jnp.where(good, y_dt, NEG)
    p_dt, z_dt = jnp.where(good, p_dt, 0.0), jnp.where(good, z_dt, 0.0)
    # cooperative transmission, P4 for every (SOV, OPV prefix)
    prefix = jnp.arange(U)[:, None] >= jnp.arange(U)[None, :]   # [i, j]
    order = jnp.argsort(-g_so, axis=1)                          # [S, U]
    g_min = jnp.take_along_axis(g_so, order, axis=1)            # [S, i]
    a_o = jnp.where(prefix[None], (g_or[order] / noise)[:, None, :], 0.0)
    a = jnp.concatenate([jnp.broadcast_to((g_sr / noise)[:, None, None],
                                          (S, U, 1)), a_o], -1)
    d0 = (g_sr[:, None] - g_min) / noise                        # [S, i]
    d = jnp.concatenate([d0[..., None], a_o], -1)
    q = jnp.concatenate(
        [jnp.broadcast_to((qs * kappa / 2)[:, None, None], (S, U, 1)),
         (qu[order] * kappa / 2)[:, None, :] * prefix[None]], -1)
    q = jnp.maximum(q, 1e-9)
    cw2 = V * w * (kappa / 2) * bw / LN2
    p_box = jnp.full((U + 1,), pmax, dt_)
    p = jax.vmap(jax.vmap(
        lambda cw_, a_, q_, d_: solve_p4(cw_, a_, q_, d_, p_box,
                                         c["ipm_iters"], c["ipm_mu"]),
        in_axes=(None, 0, 0, 0)))(cw2, a, q, d)                 # [S,U,1+U]
    z_c = (kappa / 2) * bw * jnp.log2(1.0 + jnp.sum(a * p, -1))
    y_c = (V * w[:, None] * z_c - qs[:, None] * (kappa / 2) * p[..., 0]
           - jnp.sum((kappa / 2) * p[..., 1:] * qu[order][:, None, :], -1))
    y_c = jnp.where((d0 < 0.0) & ok[:, None], y_c, NEG)
    # the slot's transmission
    m_dt = jnp.argmax(y_dt)
    best = jnp.argmax(y_c.reshape(-1))
    m_c, i_c = best // U, best % U
    y_best_dt, y_best_c = y_dt[m_dt], y_c.reshape(-1)[best]
    use = jnp.maximum(y_best_dt, y_best_c) > 0.0
    use_c = use & (y_best_c > y_best_dt)
    use_dt = use & ~use_c
    z = jnp.zeros((S,), dt_)
    z = jnp.where(use_dt, z.at[m_dt].add(z_dt[m_dt]),
                  jnp.where(use_c, z.at[m_c].add(z_c[m_c, i_c]), z))
    es = jnp.zeros((S,), dt_)
    es = jnp.where(use_dt, es.at[m_dt].add(kappa * p_dt[m_dt]),
                   jnp.where(use_c, es.at[m_c].add(
                       kappa / 2 * p[m_c, i_c, 0]), es))
    p_o = jnp.where(jnp.arange(U) <= i_c, p[m_c, i_c, 1:], 0.0)
    eo = jnp.where(use_c, jnp.zeros((U,), dt_).at[order[m_c]].add(
        kappa / 2 * p_o), 0.0)
    zeta = jnp.minimum(zeta + z, c["Q_bits"])
    qs = _queue_sov(qs, es, rnd["e_sov"], rnd["e_cp"], T)
    qu = _queue_opv(qu, eo, rnd["e_opv"], T)
    return zeta, qs, qu, es, eo


def madca_slot(c: Dict, rnd: Dict, t, zeta, qs, qu, left):
    """MADCA [7] for one slot of one cell: the eligible SOV with the best
    V2I gain uploads at full power while its budget lasts; no relays."""
    ch, kappa = c["channel"], c["slot_s"]
    dt_ = rnd["g_sr"].dtype
    g = rnd["g_sr"]
    ok = (rnd["t_cp"] <= t * kappa) & (zeta < c["Q_bits"]) & (g > 0) \
        & (left > 0) & rnd["valid_sov"]
    score = jnp.where(ok, g, -1.0)
    m = jnp.argmax(score)
    go = score[m] > 0
    p = jnp.where(go, jnp.minimum(ch["p_max"], left[m] / kappa), 0.0)
    z = kappa * ch["bandwidth"] * jnp.log2(1.0 + p * g[m] / noise_power(ch))
    zeta = zeta.at[m].add(jnp.where(go, z, 0.0))
    e = jnp.zeros(zeta.shape, dt_).at[m].add(jnp.where(go, kappa * p, 0.0))
    qs = _queue_sov(qs, e, rnd["e_sov"], rnd["e_cp"], float(c["n_slots"]))
    return zeta, qs, qu, left - e


# -------------------------------------------------------------- training

def fedavg_step(loss_fn, params, batch, weights, c: Dict):
    """Every client's `loss_fn` and gradient on its minibatch (`batch` a
    dict of [S, bs, ...] leaves), their mean weighted by `weights`
    (uploaded x sample count; a client of weight 0 adds nothing),
    clipped to global norm `clip`, and one SGD step. Returns (params,
    round loss), the loss weighted alike. A round in which no upload
    succeeded keeps the weights and reports the loss 0, as the system
    defines that round's loss. Sums are elementwise in float32."""
    losses, grads = jax.vmap(jax.value_and_grad(loss_fn),
                             in_axes=(None, 0))(params, batch)
    total = jnp.sum(weights)
    ok = total > 0
    den = jnp.where(ok, total, 1.0)

    def mean(g):
        w = weights.reshape((-1,) + (1,) * (g.ndim - 1))
        return jnp.sum(jnp.where(w > 0, w * g.astype(jnp.float32), 0.0),
                       0) / den
    avg = jax.tree.map(mean, grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(avg)))
    scale = jnp.minimum(1.0, c["clip"] / (norm + 1e-9))
    new = jax.tree.map(
        lambda p, g: jnp.where(ok, (p.astype(jnp.float32)
                                    - c["lr"] * scale * g).astype(p.dtype),
                               p), params, avg)
    loss = jnp.sum(jnp.where(weights > 0, weights * losses, 0.0)) / den
    return new, jnp.where(ok, loss, 0.0)


def cast(tree, dtype):
    """Floating leaves of `tree` in `dtype`."""
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def schedule(c: Dict, rnd: Dict, qs, qu):
    """One round of the configured scheduler over its T slots, slot after
    slot. Returns (success, SOV energy, OPV energy, qs, qu)."""
    dt_ = rnd["g_sr"].dtype
    S, U = rnd["g_sr"].shape[1], rnd["g_or"].shape[1]
    fixed = {k: v for k, v in rnd.items() if k not in PER_SLOT}
    xs = (rnd["g_sr"], rnd["g_or"], rnd["g_so"],
          jnp.arange(c["n_slots"], dtype=jnp.float32))
    zeta = jnp.zeros((S,), dt_)
    e_o = jnp.zeros((U,), dt_)
    if c["scheduler"] == "veds":
        def body(st, x):
            zeta, qs, qu, e_s, e_o = st
            r_t = dict(fixed, g_sr=x[0], g_or=x[1], g_so=x[2])
            zeta, qs, qu, es, eo = veds_slot(c, r_t, x[3], zeta, qs, qu)
            return (zeta, qs, qu, e_s + es, e_o + eo), None
        (zeta, qs, qu, e_s, e_o), _ = jax.lax.scan(
            body, (zeta, qs, qu, jnp.zeros((S,), dt_), e_o), xs)
    else:
        left0 = jnp.maximum(rnd["e_sov"] - rnd["e_cp"], 0.0)

        def body(st, x):
            r_t = dict(fixed, g_sr=x[0], g_or=x[1], g_so=x[2])
            return madca_slot(c, r_t, x[3], *st), None
        (zeta, qs, qu, left), _ = jax.lax.scan(body, (zeta, qs, qu, left0),
                                               xs)
        e_s = left0 - left
        qu = jnp.maximum(qu - rnd["e_opv"], 0.0)
    valid = rnd["valid_sov"]
    e_cp = jnp.where(valid, rnd["e_cp"], 0.0)
    return (zeta >= c["Q_bits"]) & valid, e_s + e_cp, e_o, qs, qu


def cell_round(c: Dict, dtype, loss_fn, key, cell: Dict, active, params,
               data, n_samples, sel, u):
    """One round of one cell: drive its vehicles and draw their channels,
    schedule the T slots, carry the queues and batteries of the vehicles
    that played, and train the cell's model (`loss_fn`) on the clients
    `sel` (sample index = floor(u x n), capped at n - 1), weighted by who
    uploaded; every leaf of `data` is gathered alike.
    `cell` holds the cell's rows of the fleet. Returns (the cell's new
    fleet rows, the round's outputs, new params, round loss)."""
    f32 = lambda x: x.astype(jnp.float32)                    # noqa: E731
    rnd, sov, opv, pos, heading, cov0 = cell_round_inputs(
        c, key, cell["pos"], cell["dir"], cell["speed"], cell["rsu"],
        cell["jitter"], cell["allowance"], cell["energy"], active)
    rnd = cast(rnd, dtype)
    q = cell["queue"]
    succ, e_s, e_o, qs, qu = schedule(c, rnd, q[sov].astype(dtype),
                                      q[opv].astype(dtype))
    v_s, v_o = rnd["valid_sov"], rnd["valid_opv"]
    queue = q.at[sov].set(jnp.where(v_s, f32(qs), q[sov]))
    queue = queue.at[opv].set(jnp.where(v_o, f32(qu), q[opv]))
    energy = cell["energy"].at[sov].add(-jnp.where(v_s, f32(e_s), 0.0))
    energy = energy.at[opv].add(-jnp.where(v_o, f32(e_o), 0.0))
    n = n_samples[sel]
    idx = jnp.minimum((u.astype(jnp.float32) * f32(n)[:, None])
                      .astype(jnp.int32), jnp.maximum(n - 1, 0)[:, None])
    batch = cast(jax.tree.map(lambda a: a[sel[:, None], idx], data), dtype)
    new_params, loss = fedavg_step(loss_fn, params, batch,
                                   f32(succ) * f32(n), c)
    rows = {"pos": pos, "dir": heading, "covered": cov0, "queue": queue,
            "energy": jnp.maximum(energy, 0.0)}
    outs = {"success": succ, "energy_sov": f32(e_s), "energy_opv": f32(e_o),
            "qs": f32(qs), "qu": f32(qu)}
    return rows, outs, new_params, loss


class Reference:
    """The reference for one configuration: one compiled program per
    cell-round. `loss_fn` is the model file's `reference_loss`, `dtype`
    the compute dtype (float32; bfloat16 is the control)."""

    def __init__(self, c: Dict, loss_fn, dtype=jnp.float32):
        self.c, self.dtype = c, dtype
        self._round = jax.jit(lambda *a: cell_round(c, dtype, loss_fn, *a))

    def run(self, key, round_keys, params, shards, n_samples, sel, mb_u,
            n_rounds: int, B: int, handoff_on: bool) -> Dict:
        """`n_rounds` rounds of B cells. `key` seeds the fleet,
        `round_keys[r]` round r; `params` are the initial weights of
        every cell, `shards` the client data (a dict of [C, n, ...] leaves),
        `sel [R, B, S]` and `mb_u [R, B, S, bs]` the harness's draws.
        Returns per-round [R, B, ...] masks, energies, queues and losses,
        every cell's weights after each round, and the final fleet."""
        c = self.c
        fleet = init_fleet(key, c, B, handoff_on)
        cell_params = [cast(params, self.dtype)] * B
        n_samples = jnp.asarray(n_samples)
        names = ("success", "energy_sov", "energy_opv", "qs", "qu")
        out = {k: [] for k in names + ("loss", "params")}
        for r in range(n_rounds):
            if handoff_on:
                fleet = handoff(fleet, c["mobility"])
            active = fleet["cell"] >= 0 if handoff_on else \
                np.ones(fleet["covered"].shape, bool)
            cell_keys = jax.random.split(round_keys[r], B)
            rounds = []
            for b in range(B):
                cell = {k: v[b] for k, v in fleet.items()}
                rows, outs, cell_params[b], loss = self._round(
                    cell_keys[b], cell, active[b], cell_params[b], shards,
                    n_samples, jnp.asarray(sel[r, b]),
                    jnp.asarray(mb_u[r, b]))
                rounds.append((rows, outs, loss))
            rounds, params_r = jax.device_get((rounds, cell_params))
            new = {k: np.array(v) for k, v in fleet.items()}
            for b, (rows, _, _) in enumerate(rounds):
                for k, v in rows.items():
                    new[k][b] = v
            fleet = new
            for k in names:
                out[k].append(np.stack([o[k] for _, o, _ in rounds]))
            out["loss"].append(np.asarray([l for _, _, l in rounds],
                                          np.float64))
            out["params"].append([jax.tree.map(
                lambda x: np.asarray(x, np.float32), p) for p in params_r])
        res = {k: np.stack(out[k]) for k in names + ("loss",)}
        res["params"] = out["params"]
        res["fleet"] = fleet
        return res
