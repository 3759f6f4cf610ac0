"""V2V-Enhanced Dynamic Scheduling (VEDS) — Algorithms 1 and 2, batched.

The paper's Algorithm 1 loops over SOVs, then over OPV prefixes, solving a
small convex program per candidate with CVX. Here every candidate is solved
in parallel (vmap over the [S] DT candidates and the [S, U] COT candidates),
the whole round is one `lax.scan` over slots, and a leading batch axis `B`
(independent RSU cells) rides through the entire program — B rounds are one
XLA dispatch.

Round inputs (precomputed from mobility + channel draws), single-cell
layout on the left, batched layout on the right:
  g_sr [T, S]    / [B, T, S]    SOV->RSU power gains per slot (0 = no link)
  g_or [T, U]    / [B, T, U]    OPV->RSU gains
  g_so [T, S, U] / [B, T, S, U] SOV->OPV gains
  t_cp [S]       / [B, S]       local-update latency [s]
  e_cp [S]       / [B, S]       local-update energy [J]
  e_sov [S], e_opv [U]  (+ [B]) energy budgets [J]
  valid_sov/valid_opv           optional padding masks for heterogeneous
                                fleets (None = all vehicles real)

DT candidate scoring (Prop. 1 + objective (21a)) is routed through the
`veds_score` Pallas kernel: the [B, S] candidate grid is flattened into the
kernel's lane-dense [rows, 128] candidate tiles. `use_kernel=False` keeps the pure-jnp
reference path, which tests check against the kernel (see DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.channel.v2x import ChannelParams
from repro.core import lyapunov as lyp
from repro.core.scheduler import (RoundOutputs, SchedulerCarry, init_queues,
                                  masked_e_cp, unbatch)
from repro.core.solver import dt_power_opt, solve_p4
from repro.kernels.veds_score.ops import veds_dt_score_tpu
from repro.obs import scope

LN2 = 0.6931471805599453
NEG = -1e30


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RoundInputs:
    g_sr: jax.Array
    g_or: jax.Array
    g_so: jax.Array
    t_cp: jax.Array
    e_cp: jax.Array
    e_sov: jax.Array
    e_opv: jax.Array
    valid_sov: Optional[jax.Array] = None
    valid_opv: Optional[jax.Array] = None

    @property
    def batched(self) -> bool:
        return self.g_sr.ndim == 3

    @property
    def batch_size(self) -> int:
        return self.g_sr.shape[0] if self.batched else 1

    def with_batch_axis(self) -> "RoundInputs":
        """Add a leading B=1 axis to every field (no-op when batched)."""
        if self.batched:
            return self
        return jax.tree.map(lambda x: x[None], self)

    def cell(self, b: int) -> "RoundInputs":
        """Slice one cell out of a batched round."""
        if not self.batched:
            return self
        return jax.tree.map(lambda x: x[b], self)


@scope("dt_score")
def _dt_candidates(w, qs, g_sr, eligible, prm: lyp.VedsParams,
                   ch: ChannelParams, use_kernel: bool = True):
    """Closed-form DT (Prop. 1) for the whole [B, S] candidate grid.

    Returns (y, p, z), each [B, S]. With `use_kernel` the grid is flattened
    into the Pallas kernel's [rows, 128] candidate tiles (interpret mode off
    TPU); otherwise the pure-jnp reference math runs. Both zero p/z on
    ineligible candidates and pin their objective to NEG.
    """
    if use_kernel:
        y, p, z = veds_dt_score_tpu(
            g_sr, qs, w, eligible, V=prm.V, kappa=prm.slot,
            bw=ch.bandwidth, noise=ch.noise_power, p_max=ch.p_max)
        return y, p, z
    cw = prm.V * w * prm.slot * ch.bandwidth / LN2
    q_eff = jnp.maximum(qs * prm.slot, 1e-9)
    p = dt_power_opt(cw, q_eff, g_sr, ch.noise_power, ch.p_max)
    rate = ch.bandwidth * jnp.log2(1.0 + p * g_sr / ch.noise_power)
    z = prm.slot * rate
    y = prm.V * w * z - qs * prm.slot * p
    valid = eligible & (g_sr > 0)
    return (jnp.where(valid, y, NEG), jnp.where(valid, p, 0.0),
            jnp.where(valid, z, 0.0))


def _cot_candidates(w, qs, qu, g_sr, g_or, g_so, eligible,
                    prm: lyp.VedsParams, ch: ChannelParams, p_init=None):
    """P4 for every (SOV m, prefix size i) of one cell. Proposition 2: only
    prefixes of OPVs sorted by h_{m,n} descending need be enumerated.

    `p_init [S, U, 1+U]` warm-starts every candidate's interior-point
    solve from the previous slot/round's optimum with the shortened
    `prm.ipm_warm_iters` budget (None = cold, full `prm.ipm_iters`).

    Returns y [S,U], p_m [S,U], p_opv [S,U,U] (in *sorted* OPV order),
    order [S,U], z [S,U], p_all [S,U,1+U] (this slot's warm-start table).
    """
    S = g_sr.shape[0]
    U = g_or.shape[0]
    order = jnp.argsort(-g_so, axis=1)                     # [S,U]
    g_so_sorted = jnp.take_along_axis(g_so, order, axis=1)  # [S,U]
    g_or_sorted = g_or[order]                               # [S,U]
    qu_sorted = qu[order]                                   # [S,U]

    noise = ch.noise_power
    cw = prm.V * w * (prm.slot / 2.0) * ch.bandwidth / LN2  # [S]

    prefix = (jnp.arange(U)[None, :, None]
              >= jnp.arange(U)[None, None, :])              # [1,i,j] j<i+1
    a_opv = jnp.where(prefix, (g_or_sorted / noise)[:, None, :], 0.0)
    g_min = g_so_sorted                                     # [S,i] weakest=ith
    a0 = (g_sr / noise)[:, None]                            # [S,1]
    d0 = (g_sr[:, None] - g_min) / noise                    # [S,U]
    feasible = d0 < 0.0                                     # strict interior

    a_full = jnp.concatenate(
        [jnp.broadcast_to(a0, (S, U))[..., None], a_opv], axis=-1)
    d_full = jnp.concatenate([d0[..., None], a_opv], axis=-1)
    q_full = jnp.concatenate(
        [jnp.broadcast_to((qs * prm.slot / 2.0)[:, None], (S, U))[..., None],
         jnp.broadcast_to((qu_sorted * prm.slot / 2.0)[:, None, :],
                          (S, U, U)) * prefix], axis=-1)
    q_full = jnp.maximum(q_full, 1e-9)
    pmax_full = jnp.full((S, U, U + 1), ch.p_max)

    def solve_one(cw_m, a, q, d, pm, p0):
        return solve_p4(cw_m, a, q, d, pm, iters=prm.ipm_iters,
                        mu_final=prm.ipm_mu, p_init=p0,
                        warm_iters=prm.ipm_warm_iters,
                        far_iters=prm.ipm_far_iters,
                        far_grad_tol=prm.ipm_far_grad_tol)

    px = None if p_init is None else 0
    p_all, _ = jax.vmap(jax.vmap(solve_one, in_axes=(None, 0, 0, 0, 0, px)),
                        in_axes=(0, 0, 0, 0, 0, px))(cw, a_full, q_full,
                                                     d_full, pmax_full,
                                                     p_init)
    # evaluate the exact objective y (21a) for each candidate
    sinr = jnp.einsum("sik,sik->si", a_full, p_all)
    rate = ch.bandwidth * jnp.log2(1.0 + sinr)
    z = (prm.slot / 2.0) * rate                              # [S,U]
    e_sov_cm = (prm.slot / 2.0) * p_all[..., 0]
    e_opv_cm = (prm.slot / 2.0) * p_all[..., 1:]             # [S,U,U] sorted
    y = (prm.V * w[:, None] * z - qs[:, None] * e_sov_cm
         - (e_opv_cm * qu_sorted[:, None, :]).sum(-1))
    y = jnp.where(feasible & eligible[:, None], y, NEG)
    return y, p_all[..., 0], p_all[..., 1:], order, z, p_all


def _select_slot(y_dt, p_dt, z_dt, y_cot, pm_cot, po_cot, order, z_cot,
                 prm: lyp.VedsParams):
    """Pick the slot's transmission for one cell (Algorithm 1 lines 9-13).

    Inputs are the candidate tables of a single cell: y_dt/p_dt/z_dt [S],
    y_cot/pm_cot/z_cot [S,U], po_cot [S,U,U], order [S,U].
    """
    S = y_dt.shape[0]
    U = y_cot.shape[1]
    best_dt = jnp.argmax(y_dt)
    y_dt_best = y_dt[best_dt]
    flat = y_cot.reshape(-1)
    best_cot = jnp.argmax(flat)
    y_cot_best = flat[best_cot]
    m_cot, i_cot = best_cot // U, best_cot % U

    use_any = jnp.maximum(y_dt_best, y_cot_best) > 0.0
    use_cot = use_any & (y_cot_best > y_dt_best)
    use_dt = use_any & ~use_cot

    m_sel = jnp.where(use_cot, m_cot, best_dt)
    # per-SOV delivered bits and energy this slot
    z_vec = jnp.zeros((S,))
    e_sov_vec = jnp.zeros((S,))
    e_opv_vec = jnp.zeros((U,))

    z_vec = jnp.where(
        use_dt, z_vec.at[best_dt].add(z_dt[best_dt]),
        jnp.where(use_cot, z_vec.at[m_cot].add(z_cot[m_cot, i_cot]), z_vec))
    e_sov_vec = jnp.where(
        use_dt, e_sov_vec.at[best_dt].add(prm.slot * p_dt[best_dt]),
        jnp.where(use_cot,
                  e_sov_vec.at[m_cot].add(prm.slot / 2 * pm_cot[m_cot, i_cot]),
                  e_sov_vec))
    # OPV energies: scheduled prefix i_cot in sorted order for SOV m_cot
    sched = jnp.arange(U) <= i_cot                          # prefix mask
    p_sched = jnp.where(sched, po_cot[m_cot, i_cot], 0.0)   # sorted order
    e_opv_sorted = prm.slot / 2 * p_sched
    e_opv_cot = jnp.zeros((U,)).at[order[m_cot]].add(e_opv_sorted)
    e_opv_vec = jnp.where(use_cot, e_opv_cot, e_opv_vec)
    return m_sel, use_dt, use_cot, z_vec, e_sov_vec, e_opv_vec


def solve_slot(t: jax.Array, state: Dict[str, jax.Array], rnd: RoundInputs,
               prm: lyp.VedsParams, ch: ChannelParams, *,
               enable_cot: bool = True, use_kernel: bool = True):
    """Algorithm 1 for slot t, batch-native. `rnd` must be batched; state
    leaves carry the batch axis: zeta [B,S], qs [B,S], qu [B,U]. An
    optional state["p4"] [B,S,U,1+U] threads the P4 warm-start table
    slot-to-slot (DESIGN.md §3): each slot's candidate solves start from
    the previous slot's optima and write their own back.

    Returns decision dict + per-vehicle (z, e_sov_cm, e_opv_cm), all [B,...].
    """
    B, _, S = rnd.g_sr.shape
    U = rnd.g_or.shape[-1]
    warm = "p4" in state
    zeta, qs, qu = state["zeta"], state["qs"], state["qu"]
    g_sr, g_or, g_so = rnd.g_sr[:, t], rnd.g_or[:, t], rnd.g_so[:, t]
    w = lyp.sigmoid_weight(zeta, prm)
    eligible = (rnd.t_cp <= t.astype(jnp.float32) * prm.slot) \
        & (zeta < prm.Q)
    if rnd.valid_sov is not None:
        eligible &= rnd.valid_sov

    y_dt, p_dt, z_dt = _dt_candidates(w, qs, g_sr, eligible, prm, ch,
                                      use_kernel=use_kernel)
    if enable_cot:
        y_cot, pm_cot, po_cot, order, z_cot, p_all = jax.vmap(
            _cot_candidates,
            in_axes=(0, 0, 0, 0, 0, 0, 0, None, None,
                     0 if warm else None))(
                w, qs, qu, g_sr, g_or, g_so, eligible, prm, ch,
                state["p4"] if warm else None)
    else:
        y_cot = jnp.full((B, S, U), NEG)
        pm_cot = jnp.zeros((B, S, U))
        po_cot = jnp.zeros((B, S, U, U))
        order = jnp.broadcast_to(jnp.arange(U)[None, None], (B, S, U))
        z_cot = jnp.zeros((B, S, U))
        # no P4 solves without COT: a threaded table passes through
        # untouched so the scan carry structure (and its values) hold
        p_all = state.get("p4")

    m_sel, use_dt, use_cot, z_vec, e_sov_vec, e_opv_vec = jax.vmap(
        functools.partial(_select_slot, prm=prm))(
            y_dt, p_dt, z_dt, y_cot, pm_cot, po_cot, order, z_cot)

    new_state = {
        "zeta": lyp.update_zeta(zeta, z_vec, prm),
        "qs": lyp.update_queue_sov(qs, e_sov_vec, rnd.e_sov, rnd.e_cp,
                                   state["T"]),
        "qu": lyp.update_queue_opv(qu, e_opv_vec, rnd.e_opv, state["T"]),
        "T": state["T"],
    }
    if warm:
        new_state["p4"] = p_all
    info = {
        "m": m_sel, "use_dt": use_dt, "use_cot": use_cot,
        "z": z_vec, "e_sov": e_sov_vec, "e_opv": e_opv_vec,
    }
    return new_state, info


def veds_round(rnd: RoundInputs, prm: lyp.VedsParams, ch: ChannelParams, *,
               enable_cot: bool = True, use_kernel: bool = True,
               carry: Optional[SchedulerCarry] = None) -> RoundOutputs:
    """Algorithm 2: scan slots, return success mask + diagnostics.

    Accepts single-cell or batched rounds; outputs match the input layout.
    `carry` seeds the virtual energy queues (eqs. 19-20) with their state
    from previous rounds — the long-term constraint the drift-plus-penalty
    machinery is built for; None starts them at zero (seed semantics). The
    round-end queues always come back in `RoundOutputs.carry`.

    When `carry.p4` holds a warm-start table AND `prm.ipm_warm_iters > 0`
    the P4 candidate solves run warm-started (the table threads
    slot-to-slot through the scan and the final slot's table comes back
    in `RoundOutputs.carry.p4` for the next round); otherwise the cold
    path runs bit-for-bit the seed semantics and `carry.p4` stays None.
    """
    batched = rnd.batched
    rb = rnd.with_batch_axis()
    B, T, S = rb.g_sr.shape
    U = rb.g_or.shape[-1]
    qs0, qu0 = init_queues(rb, carry)
    state = {"zeta": jnp.zeros((B, S)), "qs": qs0,
             "qu": qu0, "T": jnp.asarray(float(T))}
    warm = (enable_cot and prm.ipm_warm_iters > 0
            and carry is not None and carry.p4 is not None)
    if warm:
        state["p4"] = jnp.broadcast_to(carry.p4, (B, S, U, U + 1))

    def body(st, t):
        st, info = solve_slot(t, st, rb, prm, ch, enable_cot=enable_cot,
                              use_kernel=use_kernel)
        return st, info

    state, infos = jax.lax.scan(body, state, jnp.arange(T))
    success = state["zeta"] >= prm.Q
    if rb.valid_sov is not None:
        success &= rb.valid_sov
    out = RoundOutputs(
        success=success,
        n_success=success.sum(-1),
        zeta=state["zeta"],
        energy_sov=infos["e_sov"].sum(0) + masked_e_cp(rb),
        energy_opv=infos["e_opv"].sum(0),
        n_cot_slots=infos["use_cot"].sum(0),
        n_dt_slots=infos["use_dt"].sum(0),
        carry=SchedulerCarry(qs=state["qs"], qu=state["qu"],
                             p4=state.get("p4")),
    )
    return unbatch(out, batched)
