"""Pallas TPU kernel: batched DT candidate scoring (Prop. 1 + objective 21a).

The RSU evaluates, every slot, the direct-transmission candidate for each
eligible SOV: closed-form optimal power, resulting rate, delivered bits and
the drift-plus-penalty objective value. On the RSU's accelerator this is a
single fused VMEM pass over the candidate arrays (the paper's Algorithm 1
inner loop, batched).

Candidates are laid out lane-dense: `[rows, 128]` tiles, `block_rows`
rows per grid step. Mosaic tiles a 2-D f32 block as (8, 128), so
`block_rows` must be a multiple of 8 unless it spans every row; the
caller (`ops.veds_dt_score_tpu`) pads the candidate count to that tiling
with ineligible candidates, which score exactly as masked ones do.

Inputs (per candidate): gain g, queue q, sigmoid weight w, eligibility e.
Constants: V, kappa, bandwidth, noise, p_max.
Outputs: y (objective), p (power), z (bits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LN2 = 0.6931471805599453
NEG = -1e30
LANES = 128


def _kernel(g_ref, q_ref, w_ref, e_ref, y_ref, p_ref, z_ref, *,
            V: float, kappa: float, bw: float, noise: float, p_max: float):
    g = g_ref[...].astype(jnp.float32)
    q = q_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    e = e_ref[...]
    a = g / noise
    cw = V * w * kappa * bw / LN2
    q_eff = jnp.maximum(q * kappa, 1e-9)
    p = jnp.clip(cw / q_eff - 1.0 / jnp.maximum(a, 1e-30), 0.0, p_max)
    rate = bw * jnp.log1p(p * a) / LN2
    z = kappa * rate
    y = V * w * z - q * kappa * p
    valid = e & (g > 0)
    y_ref[...] = jnp.where(valid, y, NEG)
    p_ref[...] = jnp.where(valid, p, 0.0)
    z_ref[...] = jnp.where(valid, z, 0.0)


def veds_dt_score_pallas(g, q, w, e, *, V: float, kappa: float, bw: float,
                         noise: float, p_max: float, block_rows: int,
                         interpret: bool = True):
    """Score `[rows, 128]` candidate tiles; `rows` must divide by
    `min(block_rows, rows)`."""
    rows = g.shape[0]
    block_rows = min(block_rows, rows)
    if g.shape[1] != LANES or rows % block_rows:
        raise ValueError(f"candidate tiles {g.shape} are not [rows, "
                         f"{LANES}] with rows divisible by {block_rows}")
    kern = functools.partial(_kernel, V=V, kappa=kappa, bw=bw, noise=noise,
                             p_max=p_max)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        kern,
        grid=(rows // block_rows,),
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct(g.shape, jnp.float32)] * 3,
        interpret=interpret,
        name="veds_dt_score",
    )(g, q, w, e)
