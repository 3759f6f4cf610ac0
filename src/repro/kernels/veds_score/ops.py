"""Jit'd wrapper for the DT scoring kernel.

Accepts candidate grids of any shape — the scheduler's batched [B, S]
grid included — by flattening into the kernel's lane-dense `[rows, 128]`
candidate tiles and restoring the shape on the way out. The tail that
pads the count up to the tiling is ineligible (`e = False`, zero gain),
so the kernel scores it `NEG`/0 like any masked candidate and the slice
back to the true count drops it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.veds_score.ref import veds_dt_score_ref
from repro.kernels.veds_score.veds_score import LANES, veds_dt_score_pallas

BLOCK_ROWS = 8      # one (8, 128) f32 tile of candidates per grid step


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def tile_rows(n_candidates: int):
    """(rows, block) of the padded tile layout for `n_candidates`: one
    block spanning every row when they fit in `BLOCK_ROWS`, else
    `BLOCK_ROWS`-row blocks with the row count rounded up to them."""
    rows = max(1, -(-int(n_candidates) // LANES))
    block = min(BLOCK_ROWS, rows)
    return -(-rows // block) * block, block


def to_tiles(x: jax.Array, rows: int) -> jax.Array:
    """Flatten `x` into `[rows, 128]`, zero/False-padding the tail."""
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, rows * LANES - flat.size)).reshape(rows, LANES)


@functools.partial(jax.jit, static_argnames=(
    "V", "kappa", "bw", "noise", "p_max", "force_ref"))
def veds_dt_score_tpu(g, q, w, e, *, V, kappa, bw, noise, p_max,
                      force_ref: bool = False):
    if force_ref:
        return veds_dt_score_ref(g, q, w, e, V=V, kappa=kappa, bw=bw,
                                 noise=noise, p_max=p_max)
    shape, n = g.shape, g.size
    rows, block = tile_rows(n)
    outs = veds_dt_score_pallas(*(to_tiles(x, rows) for x in (g, q, w, e)),
                                V=V, kappa=kappa, bw=bw, noise=noise,
                                p_max=p_max, block_rows=block,
                                interpret=not _on_tpu())
    return tuple(o.reshape(-1)[:n].reshape(shape) for o in outs)
