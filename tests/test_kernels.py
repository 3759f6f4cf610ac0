"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fedavg_agg.ops import fedavg_agg_tpu
from repro.kernels.flash_attention.ops import flash_attention_tpu
from repro.kernels.ssd_scan.ops import ssd_scan_tpu
from repro.kernels.veds_score.ops import (tile_rows, to_tiles,
                                          veds_dt_score_tpu)
from repro.kernels.veds_score.veds_score import (LANES, NEG,
                                                 veds_dt_score_pallas)

KEY = jax.random.key(0)


@pytest.mark.parametrize("t,s,h,kv,d,causal,window,dtype", [
    (128, 128, 4, 2, 32, True, None, jnp.float32),
    (256, 256, 4, 4, 64, True, 64, jnp.float32),
    (64, 256, 8, 2, 32, False, None, jnp.float32),
    (100, 200, 4, 1, 16, True, None, jnp.float32),
    (128, 128, 2, 2, 64, True, None, jnp.bfloat16),
])
def test_flash_attention(t, s, h, kv, d, causal, window, dtype):
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (2, t, h, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (2, s, kv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (2, s, kv, d), dtype)
    off = s - t if causal else 0
    a = flash_attention_tpu(q, k, v, causal=causal, window=window,
                            block_q=64, block_kv=64, q_offset=off)
    b = flash_attention_tpu(q, k, v, causal=causal, window=window,
                            force_ref=True, q_offset=off)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("bh,t,p,n,chunk,dtype", [
    (4, 64, 16, 8, 16, jnp.float32),
    (6, 96, 32, 16, 32, jnp.float32),
    (2, 40, 8, 4, 16, jnp.float32),   # ragged T -> pad path
    (2, 64, 16, 8, 32, jnp.bfloat16),
])
def test_ssd_scan(bh, t, p, n, chunk, dtype):
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (bh, t, p), dtype)
    b = jax.random.normal(jax.random.fold_in(KEY, 5), (bh, t, n), dtype)
    c = jax.random.normal(jax.random.fold_in(KEY, 6), (bh, t, n), dtype)
    la = -jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(KEY, 7), (bh, t))).astype(
            jnp.float32)
    y1 = ssd_scan_tpu(v, b, c, la, chunk=chunk)
    y2 = ssd_scan_tpu(v, b, c, la, force_ref=True)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("v,l,block", [(4, 1000, 256), (8, 4096, 512),
                                       (2, 37, 64)])
def test_fedavg_agg(v, l, block):
    x = jax.random.normal(jax.random.fold_in(KEY, 8), (v, l))
    w = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 9), (v,)))
    w = w * (jax.random.uniform(jax.random.fold_in(KEY, 10), (v,)) > 0.3)
    old = jax.random.normal(jax.random.fold_in(KEY, 11), (l,))
    a = fedavg_agg_tpu(x, w, old, block_l=block)
    b = fedavg_agg_tpu(x, w, old, force_ref=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_fedavg_all_failed_keeps_old():
    x = jax.random.normal(jax.random.fold_in(KEY, 12), (4, 100))
    old = jax.random.normal(jax.random.fold_in(KEY, 13), (100,))
    out = fedavg_agg_tpu(x, jnp.zeros(4), old, block_l=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(old))


@pytest.mark.parametrize("c", [100, 256, 17, 300, 2560])
def test_veds_score(c):
    """Lane-dense tiles: one block spanning every row (100 / 256 / 17 /
    300 -> 1 / 2 / 1 / 3 rows) or several 8-row blocks (2560 -> 20 rows
    padded to 24), every padded candidate ineligible."""
    g = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 14), (c,))) * 1e-11
    q = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 15), (c,))) * 0.1
    w = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 16), (c,))) * 1e-7
    e = jax.random.bernoulli(jax.random.fold_in(KEY, 17), 0.8, (c,))
    kw = dict(V=0.2, kappa=0.1, bw=20e6, noise=8e-14, p_max=0.3)
    outs_k = veds_dt_score_tpu(g, q, w, e, **kw)
    outs_r = veds_dt_score_tpu(g, q, w, e, force_ref=True, **kw)
    for a, b in zip(outs_k, outs_r):
        assert a.shape == (c,)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # the tail padding the count up to the tiling scores as masked
    rows, blk = tile_rows(c)
    assert rows % blk == 0 and rows * LANES >= c
    tiles = veds_dt_score_pallas(*(to_tiles(x, rows) for x in (g, q, w, e)),
                                 block_rows=blk, **kw)
    y, p, z = (np.asarray(t).reshape(-1)[c:] for t in tiles)
    assert (y == NEG).all() and not p.any() and not z.any()


@pytest.mark.parametrize("b,s,block", [(1, 8, 8), (4, 8, 16), (16, 24, 128),
                                       (7, 13, 64)])
def test_veds_score_matches_dt_candidates(b, s, block):
    """Kernel (interpret) vs the scheduler's jnp reference math on batched
    [B, S] candidate grids, incl. the eligible/g_sr==0 masking edges."""
    from repro.channel.v2x import ChannelParams
    from repro.core.lyapunov import VedsParams
    from repro.core.veds import _dt_candidates, NEG

    ch = ChannelParams()
    prm = VedsParams(alpha=2.0, V=0.2, Q=1e7, slot=0.1)
    g = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 20),
                                  (b, s))) * 1e-11
    # masking edge cases: dead links and an all-ineligible cell
    g = g * (jax.random.uniform(jax.random.fold_in(KEY, 21), (b, s)) > 0.25)
    q = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 22), (b, s))) * 0.1
    zeta = jax.random.uniform(jax.random.fold_in(KEY, 23), (b, s),
                              maxval=prm.Q)
    from repro.core.lyapunov import sigmoid_weight
    w = sigmoid_weight(zeta, prm)
    e = jax.random.bernoulli(jax.random.fold_in(KEY, 24), 0.7, (b, s))
    e = e.at[0].set(False)

    ref = _dt_candidates(w, q, g, e, prm, ch, use_kernel=False)
    kern = jax.jit(lambda *a: _dt_candidates(
        *a, prm, ch, use_kernel=True))(w, q, g, e)
    for a_, b_ in zip(kern, ref):
        assert a_.shape == (b, s)
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                   rtol=1e-5, atol=1e-6)
    # ineligible / dead-link candidates are pinned to NEG with zero p/z
    dead = ~(np.asarray(e) & (np.asarray(g) > 0))
    assert (np.asarray(kern[0])[dead] == NEG).all()
    assert not np.asarray(kern[1])[dead].any()
    assert not np.asarray(kern[2])[dead].any()
