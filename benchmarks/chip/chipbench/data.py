"""Draws made from the seed, on the device, whatever the model: the
root key and the per-round and per-request draws. The weights and the
client data come from the configuration's model file
(`models/<model>.py`). The program receives them; the reference gets
the same arrays, so neither takes anything the other made."""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


def root_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, also past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=("L", "B", "S", "per_cell",
                                             "bs"))
def round_draws(key, r0, L: int, B: int, S: int, per_cell: int, bs: int):
    """Draws of rounds r0 .. r0 + L - 1: a scheduling key per round, the
    S clients each cell trains (a permutation of its own `per_cell`
    clients, cell b owning ids b*per_cell ..), and the uniforms that
    pick their minibatches. Round r depends on (key, r) alone."""
    def one(r):
        k = jax.random.fold_in(key, r)
        k_r, k_sel, k_mb = jax.random.split(k, 3)
        sel = jax.vmap(lambda kk: jax.random.permutation(kk, per_cell)[:S])(
            jax.random.split(k_sel, B))
        sel = sel + per_cell * jnp.arange(B)[:, None]
        return k_r, sel.astype(jnp.int32), jax.random.uniform(
            k_mb, (B, S, bs))
    return jax.vmap(one)(r0 + jnp.arange(L))


@functools.partial(jax.jit, static_argnames=("n_rounds", "n_clients",
                                             "S", "bs"))
def request_draws(seed, n_rounds: int, n_clients: int, S: int, bs: int):
    """The draws of one served request, as the service makes them from
    the request's 31-bit `seed`: a scheduling key per round, the S
    clients each round trains (a permutation of all `n_clients`) and
    the uniforms that pick their minibatches."""
    k_r, k_sel, k_mb = jax.random.split(jax.random.key(seed), 3)
    keys = jax.random.split(k_r, n_rounds)
    sel = jax.vmap(lambda k: jax.random.permutation(k, n_clients)[:S])(
        jax.random.split(k_sel, n_rounds))
    return keys, sel, jax.random.uniform(k_mb, (n_rounds, S, bs))


def draws_of(key, r0: int, L: int, traffic: Dict, cfg: Dict):
    return round_draws(key, r0, L, traffic["cells"], cfg["n_sov"],
                       traffic["clients_per_cell"], cfg["batch_size"])
