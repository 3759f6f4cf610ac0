"""Convex solvers for the per-slot subproblems.

* P3.1 (direct transmission): closed form (Proposition 1).
* P4 (cooperative transmission, fixed OPV prefix): log-barrier damped-Newton
  interior-point method, branch-free with a fixed iteration budget so it can
  be jit'ed and vmapped over all (SOV, prefix) candidates. Each Newton step
  solves the diagonal-plus-rank-2 Hessian in closed form (elementwise work,
  no factorization; `_solve_diag_rank2`). This replaces the
  paper's CVX call — same convex program, TPU-native solver (see DESIGN.md §3).

The P4 solver supports a *warm start* (`p_init` + `warm_iters`): streaming
rollouts thread the previous round's per-vehicle optimum through the scan
carry and re-solve with a shortened tail of the barrier schedule, cutting
the per-candidate Newton cost that dominates persistent VEDS+COT streaming
(`VedsParams.ipm_warm_iters`, DESIGN.md §3/§9).

P4 in our canonical form, variables p in R^{1+U} (index 0 = the SOV):
  maximize  cw * ln(1 + a.p) - q.p
  s.t.      0 <= p <= pmax,   d.p <= 0
with d = a - g_min * e0 (decodability constraint (28), reduced to the
weakest scheduled OPV), entries of a zeroed for unscheduled OPVs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs import scope


def dt_power_opt(cw: jax.Array, q: jax.Array, gain: jax.Array,
                 noise: float, p_max: float) -> jax.Array:
    """Proposition 1: water-filling style closed form for P3.1.

    Maximizes the objective (21a) restricted to one DT candidate,
        cw * ln(1 + gain * p / noise) - q * p      over p in [0, p_max],
    where cw = V * dsigma/dzeta * kappa * beta / ln(2) (nats) and q is
    the *slot-scaled* queue weight the call sites pass in
    (q = kappa * Q_m(t), so the kappa factor lives in q — it is NOT
    applied again here). Interior optimum p* = cw/q - noise/gain,
    clipped to the box.
    """
    a = gain / noise
    p = cw / jnp.maximum(q, 1e-12) - 1.0 / jnp.maximum(a, 1e-30)
    return jnp.clip(p, 0.0, p_max)


def _phi_grad_hess(p, a, q, cw, d, p_max, mu):
    """Barrier objective phi = F + mu * barriers; returns (grad, hess)."""
    s = 1.0 + jnp.dot(a, p)
    gF = cw * a / s - q
    HF = -cw * jnp.outer(a, a) / (s * s)
    # box barriers
    g_lo = mu / jnp.maximum(p, 1e-12)
    g_hi = -mu / jnp.maximum(p_max - p, 1e-12)
    H_lo = -mu / jnp.maximum(p, 1e-12) ** 2
    H_hi = -mu / jnp.maximum(p_max - p, 1e-12) ** 2
    # decodability barrier: ln(-d.p), requires d.p < 0
    slack = -jnp.dot(d, p)
    g_c = -mu * d / jnp.maximum(slack, 1e-12)
    H_c = -mu * jnp.outer(d, d) / jnp.maximum(slack, 1e-12) ** 2
    grad = gF + g_lo + g_hi + g_c
    hess = HF + jnp.diag(H_lo + H_hi) + H_c
    return grad, hess


def _phi_grad_parts(p, a, q, cw, d, p_max, mu):
    """`_phi_grad_hess` in parts, by the same expressions. With
    u = sqrt(cw)/s * a and v = sqrt(mu)/slack * d,
        grad  = g_box + sqrt(cw) * u - sqrt(mu) * v,
        -hess = diag(lam) + u u^T + v v^T,
    where g_box = -q + mu/p - mu/(p_max - p) and lam = mu/p^2 +
    mu/(p_max - p)^2 are the box barriers' (P4's cw >= 0, so -hess is
    SPD). Returns (g_box, lam, u, v, sqrt(cw), -sqrt(mu)). The gradient's
    a and d terms stay apart so that the Newton solve takes them through
    u and v exactly: near the decodability boundary mu/slack reaches 1e11
    and more, and the rounding of their sum would swamp the step."""
    s = 1.0 + jnp.dot(a, p)
    lo = jnp.maximum(p, 1e-12)
    hi = jnp.maximum(p_max - p, 1e-12)
    slack = jnp.maximum(-jnp.dot(d, p), 1e-12)
    g_box = -q + mu / lo - mu / hi
    lam = mu / lo ** 2 + mu / hi ** 2
    root_cw, root_mu = jnp.sqrt(cw), jnp.sqrt(mu)
    return (g_box, lam, root_cw / s * a, root_mu / slack * d, root_cw,
            -root_mu)


def _solve_diag_rank2(g, lam, u, v, cu, cv):
    """Solve (diag(lam) + u u^T + v v^T) x = g + cu * u + cv * v for
    lam > 0, in closed form.

    In scaled coordinates (r = lam^-1/2, u~ = u r, v~ = v r) the matrix
    is A = I + u~u~^T + v~v~^T and x = r * A^-1 (r g + cu u~ + cv v~).
    Gram-Schmidt (twice, so the basis stays orthogonal when u~ and v~ are
    nearly parallel, as P4's a and d are) gives an orthonormal e1, e2 with
    u~ = t_u e1 and v~ = t_v (c e1 + n e2). In that basis the rank-2 part
    is S = R R^T, R = [[t_u, t_v c], [0, t_v n]], so with K = I + S
        A^-1 y = y - E (S + det(S) I) E^T y / det(K),
        A^-1 u~ = t_u E [1 + t_v^2 n^2, -t_v^2 c n] / det(K),
        A^-1 v~ = t_v E [c, n (1 + t_u^2)] / det(K),
        det(K) = 1 + t_u^2 + t_v^2 (c^2 + n^2) + t_u^2 t_v^2 n^2,
    a sum of positive terms, and det(S) = (t_u t_v n)^2 the Gram
    determinant, as a product: nothing cancels when u~ and v~ are nearly
    parallel. Every coefficient is divided by (1 + t_u^2)(1 + t_v^2), so
    nothing overflows float32 when lam spans many decades or a barrier's
    slack is at its 1e-12 floor. Elementwise ops and sums over the last
    axis only (no factorization, no matmul, no n x n intermediate).
    """
    r = jax.lax.rsqrt(lam)

    def unit(w):
        m = jnp.max(jnp.abs(w))
        return w / jnp.where(m > 0, m, 1.0), m

    def ratios(t2):
        # (1/(1 + t2), t2/(1 + t2)); t2 = 0 gives (1, 0), t2 = inf (0, 1)
        return 1.0 / (1.0 + t2), 1.0 / (1.0 + 1.0 / t2)

    uh, m_u = unit(u * r)
    vh, m_v = unit(v * r)
    nu = jnp.sqrt(jnp.sum(uh * uh))
    e1 = uh / jnp.where(nu > 0, nu, 1.0)
    c = jnp.sum(vh * e1)
    vp = vh - c * e1
    c2 = jnp.sum(vp * e1)
    vp, c = vp - c2 * e1, c + c2
    n = jnp.sqrt(jnp.sum(vp * vp))
    e2 = vp / jnp.where(n > 0, n, 1.0)
    tu = m_u * nu
    ru, wu = ratios(tu * tu)
    rv, wv = ratios(m_v * m_v)
    det = rv + ru * wv * (c * c + n * n) + wu * wv * n * n
    gs = g * r
    g1, g2 = jnp.sum(e1 * gs), jnp.sum(e2 * gs)
    su, sv = cu * tu * ru, cv * m_v * rv
    y1 = (su * (rv + wv * n * n) + sv * ru * c
          - (wu * rv + ru * wv * c * c + wu * wv * n * n) * g1
          - ru * wv * c * n * g2)
    y2 = (sv * n - su * wv * c * n - ru * wv * c * n * g1
          - wv * n * n * g2)
    return (gs + (y1 * e1 + y2 * e2) / det) * r


def _newton_direction(p, a, q, cw, d, p_max, mu):
    """The damped Newton direction of phi at p: x with
    (1e-9 I - hess) x = grad, from the parts of both."""
    g_box, lam, u, v, cu, cv = _phi_grad_parts(p, a, q, cw, d, p_max, mu)
    return _solve_diag_rank2(g_box, lam + 1e-9, u, v, cu, cv)


def _project_feasible(p, d, p_max, margin=0.999):
    """Clip into the box and scale OPV powers to satisfy d.p <= 0."""
    p = jnp.clip(p, 1e-9, p_max - 1e-9)
    p_m = p[0]
    rest = p[1:]
    # d0 <= 0 when feasible candidate; headroom = -d0 * p_m
    headroom = jnp.maximum(-d[0] * p_m, 1e-30)
    load = jnp.dot(d[1:], rest)
    scale = jnp.minimum(1.0, margin * headroom / jnp.maximum(load, 1e-30))
    return jnp.concatenate([p[:1], rest * scale])


def p4_seed_table(shape, p_max: float) -> jax.Array:
    """The cold starting point of `solve_p4`, broadcast to `shape` (whose
    trailing axis is the P4 power vector [1+U]). Warm-start tables are
    seeded with this so a warm solve at the full iteration budget from an
    untouched table is bit-for-bit the cold solve (DESIGN.md §3)."""
    tab = jnp.full(shape, 0.25 * p_max)
    return tab.at[..., 0].set(0.5 * p_max)


def _polish_count(n_it: int, iters: int) -> int:
    """Gradient-polish steps for a Newton budget of `n_it` out of the cold
    `iters`: the full 10 at the full budget (bit-for-bit cold contract),
    proportionally fewer on a shortened warm budget."""
    return 10 if n_it == iters else max(2, (10 * n_it) // iters)


@scope("p4")
def solve_p4(cw: jax.Array, a: jax.Array, q: jax.Array, d: jax.Array,
             p_max: jax.Array, *, iters: int = 25,
             mu_final: float = 1e-3, p_init=None, warm_iters: int = 0,
             far_iters: int = 0, far_grad_tol: float = 0.0):
    """Interior-point solve of P4. All args vectors [1+U] except cw scalar.

    Unscheduled OPVs must have a=0, q arbitrary, p_max>0; their optimum is 0.
    Returns (p_opt, value) with value = cw*ln(1+a.p) - q.p.

    Warm start (DESIGN.md §3): `p_init` seeds the Newton iteration from a
    previous solve of a correlated instance (round-to-round / slot-to-slot
    channel correlation makes the last optimum an excellent interior
    point). The seed is pulled strictly into the interior by the same
    margin-0.5 projection the cold start uses, and the barrier schedule
    becomes the *tail* of the cold schedule: the last `warm_iters` of the
    cold path's mu values (a near-optimal start does not need the
    high-mu exploration phase). The gradient-polish phase shortens
    proportionally. `warm_iters <= 0` keeps the full budget, so
    `p_init = p4_seed_table(...)` + full budget is bit-for-bit the
    cold solve.

    Adaptive two-tier budget (warm path only; `far_iters > warm_iters`
    and `far_grad_tol > 0` enable it): candidates whose projected seed is
    already near-stationary (raw-objective gradient norm <= tol) apply
    only the last `warm_iters` steps of the schedule; far-from-stationary
    seeds (a migrated vehicle, a channel jump) apply the full `far_iters`
    tail. The selection is a branch-free `where` on masked updates, so
    the program shape is one `far_iters`-length scan for every vmapped
    candidate lane: the *applied* steps of a near lane are bit-for-bit
    the plain `warm_iters` schedule, and a far lane with
    `far_iters == iters` is bit-for-bit the cold solve from the seed.
    (Uniform lanes mean compute scales with `far_iters`; the lever is
    that `warm_iters` can drop far lower than a single-tier budget could
    afford, because stragglers keep full-budget quality.)
    """
    n = a.shape[0]
    adaptive = (p_init is not None and warm_iters > 0
                and far_iters > warm_iters and far_grad_tol > 0.0)
    if p_init is None:
        p0 = jnp.full((n,), 0.25) * p_max
        p0 = p0.at[0].set(0.5 * p_max[0])
        n_it = iters
    else:
        p0 = p_init
        n_it = min(int(warm_iters), iters) if warm_iters > 0 else iters
    p0 = _project_feasible(p0, d, p_max, margin=0.5)

    if adaptive:
        n_run = min(int(far_iters), iters)
        s0 = 1.0 + jnp.dot(a, p0)
        g0 = jnp.linalg.norm(cw * a / s0 - q)
        far = g0 > far_grad_tol
        budget = jnp.where(far, n_run, n_it)
        budget_pol = jnp.where(far, _polish_count(n_run, iters),
                               _polish_count(n_it, iters))
    else:
        n_run = n_it
        budget = n_run
        budget_pol = _polish_count(n_it, iters)

    mus = jnp.geomspace(1e-1, mu_final, iters)[iters - n_run:]

    def step(p, x):
        mu, i = x
        # damped Newton ascent on the concave barrier objective
        dlt = _newton_direction(p, a, q, cw, d, p_max, mu)
        # keep steps inside the trust region of the barrier
        norm = jnp.linalg.norm(dlt)
        dlt = dlt * jnp.minimum(1.0, 0.5 * jnp.max(p_max) / (norm + 1e-12))
        p_new = _project_feasible(p + dlt, d, p_max)
        # two-tier select: a lane applies only the last `budget` steps of
        # the schedule (all of them when budget == n_run)
        return jnp.where(i >= n_run - budget, p_new, p), None

    p, _ = jax.lax.scan(step, p0, (mus, jnp.arange(n_run)))
    # gradient polish: a few projected-ascent steps on the raw objective.
    # The warm path shortens it with the Newton budget (a near-optimal
    # seed needs less sharpening); n_it == iters keeps the cold count,
    # preserving the bit-for-bit full-budget equivalence.
    n_pol = _polish_count(n_run, iters)

    def polish(p, j):
        s = 1.0 + jnp.dot(a, p)
        g = cw * a / s - q
        lr = 0.05 * jnp.max(p_max) / (jnp.linalg.norm(g) + 1e-12)
        p_new = _project_feasible(p + lr * g, d, p_max)
        return jnp.where(j >= n_pol - budget_pol, p_new, p), None

    p, _ = jax.lax.scan(polish, p, jnp.arange(n_pol))
    val = cw * jnp.log1p(jnp.dot(a, p)) - jnp.dot(q, p)
    # zero-power value as a floor (solver never worse than not transmitting)
    val0 = jnp.zeros(())
    better = val >= val0
    p = jnp.where(better, p, jnp.zeros_like(p))
    val = jnp.maximum(val, val0)
    return p, val
