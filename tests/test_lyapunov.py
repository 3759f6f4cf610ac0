"""Direct tests for `repro.core.lyapunov` — the drift-plus-penalty
machinery (paper eqs. 16-20) every scheduler leans on.

Deterministic invariants run always; the hypothesis property tests ride
on the dev extra (importorskip, same contract as test_channel_mobility).
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lyapunov import (VedsParams, psi, relax_queue,
                                 sigmoid_shifted, sigmoid_weight,
                                 update_queue_opv, update_queue_sov,
                                 update_zeta)

PRM = VedsParams(alpha=2.0, Q=1e7, slot=0.1)


# ---- deterministic invariants ------------------------------------------

def test_queue_updates_nonnegative():
    q = jnp.array([0.0, 0.1, 2.0])
    e_cm = jnp.array([0.0, 0.01, 0.0])
    e = jnp.array([5.0, 5.0, 5.0])      # huge budget drains the queue
    assert float(update_queue_sov(q, e_cm, e, jnp.zeros(3), 1.0).min()) >= 0
    assert float(update_queue_opv(q, e_cm, e, 1.0).min()) >= 0


def test_queue_update_monotone_in_e_cm():
    """(19)/(20): more communication energy never shrinks the queue."""
    q = jnp.full((64,), 0.05)
    e = jnp.full((64,), 0.07)
    e_cp = jnp.full((64,), 0.01)
    e_cm = jnp.linspace(0.0, 0.5, 64)
    qs = update_queue_sov(q, e_cm, e, e_cp, 10.0)
    qu = update_queue_opv(q, e_cm, e, 10.0)
    assert bool(jnp.all(jnp.diff(qs) >= 0))
    assert bool(jnp.all(jnp.diff(qu) >= 0))


def test_update_zeta_saturates_at_Q():
    zeta = jnp.array([0.0, 0.5 * PRM.Q, PRM.Q])
    z = jnp.full((3,), 0.8 * PRM.Q)
    out = update_zeta(zeta, z, PRM)
    assert float(out.max()) <= PRM.Q
    np.testing.assert_allclose(np.asarray(out),
                               [0.8 * PRM.Q, PRM.Q, PRM.Q], rtol=1e-6)


def test_sigmoid_weight_peaks_at_Q():
    """sigma'(zeta) is maximal exactly where the indicator flips."""
    zeta = jnp.linspace(0.0, 2.0 * PRM.Q, 2001)
    w = np.asarray(sigmoid_weight(zeta, PRM))
    assert abs(float(zeta[w.argmax()]) - PRM.Q) <= float(zeta[1] - zeta[0])
    # analytic peak value: alpha / (4 Q)
    np.testing.assert_allclose(w.max(), PRM.alpha / (4.0 * PRM.Q),
                               rtol=1e-6)
    # symmetric falloff around Q
    np.testing.assert_allclose(w[:1000], w[-1:-1001:-1], rtol=1e-4)


def test_sigmoid_shifted_is_half_at_Q():
    assert float(sigmoid_shifted(jnp.asarray(PRM.Q), PRM)) == \
        pytest.approx(0.5)


def test_psi_matches_definition():
    s0 = 1.0 / (1.0 + math.exp(PRM.alpha))
    assert psi(PRM) == pytest.approx(s0 * (1 - s0) / 0.25)


def test_relax_queue_matches_iterated_updates():
    """Closed form == T zero-transmission steps of (19)/(20), both signs
    of the per-slot net drain."""
    T = 7
    q0 = jnp.array([0.0, 0.3, 1.0, 2.0])
    e_net = jnp.array([-0.5, 0.2, 1.5, -1.0])   # drain and growth cases
    q = q0
    for _ in range(T):
        q = jnp.maximum(q - e_net / T, 0.0)
    np.testing.assert_allclose(np.asarray(relax_queue(q0, e_net)),
                               np.asarray(q), rtol=1e-6, atol=1e-9)


# ---- hypothesis property tests (dev extra) -----------------------------
# Guarded so the deterministic tests above still run when the dev extra is
# absent (importorskip at module level would skip the whole file).

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAS_HYPOTHESIS = False

if HAS_HYPOTHESIS:
    finite = dict(allow_nan=False, allow_infinity=False)

    @settings(max_examples=50, deadline=None)
    @given(st.floats(0.0, 10.0, **finite), st.floats(0.0, 1.0, **finite),
           st.floats(0.0, 1.0, **finite), st.floats(0.0, 1.0, **finite),
           st.integers(1, 200))
    def test_queue_sov_nonnegative_property(q, e_cm, e, e_cp, T):
        out = float(update_queue_sov(jnp.asarray(q), jnp.asarray(e_cm),
                                     jnp.asarray(e), jnp.asarray(e_cp),
                                     float(T)))
        assert out >= 0.0

    @settings(max_examples=50, deadline=None)
    @given(st.floats(0.0, 10.0, **finite), st.floats(0.0, 1.0, **finite),
           st.floats(0.0, 1.0, **finite), st.floats(0.0, 0.5, **finite),
           st.integers(1, 200))
    def test_queue_sov_monotone_in_e_cm_property(q, e_cm, e, delta, T):
        """q(e_cm + delta) >= q(e_cm) for any nonneg delta (holds for OPV
        queues too, (20) being (19) with e_cp = 0)."""
        lo = update_queue_sov(jnp.asarray(q), jnp.asarray(e_cm),
                              jnp.asarray(e), jnp.asarray(0.0), float(T))
        hi = update_queue_sov(jnp.asarray(q), jnp.asarray(e_cm + delta),
                              jnp.asarray(e), jnp.asarray(0.0), float(T))
        assert float(hi) >= float(lo) - 1e-12

    @settings(max_examples=50, deadline=None)
    # f32 draws: update_zeta computes in f32, and a wider zeta would be
    # rounded on the way in, before the function sees it
    @given(st.floats(0.0, 5e7, width=32, **finite),
           st.floats(0.0, 5e7, width=32, **finite))
    def test_update_zeta_saturates_property(zeta, z):
        out = float(update_zeta(jnp.asarray(zeta), jnp.asarray(z), PRM))
        assert out <= PRM.Q + 1e-3
        assert out >= min(zeta, PRM.Q) - 1e-3  # never loses delivered bits

    @settings(max_examples=50, deadline=None)
    @given(st.floats(0.0, 2e7, **finite))
    def test_sigmoid_weight_bounded_by_peak_property(zeta):
        w = float(sigmoid_weight(jnp.asarray(zeta), PRM))
        assert 0.0 <= w <= PRM.alpha / (4.0 * PRM.Q) * (1 + 1e-6)
else:
    @pytest.mark.skip(reason="dev extra; pip install -r "
                      "requirements-dev.txt")
    def test_lyapunov_hypothesis_properties():
        pass
