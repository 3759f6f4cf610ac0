"""Inputs made from the seed, on the device: weights, client data and
the per-round draws. The program receives them; the reference gets the
same arrays, so neither takes anything the other made."""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp


def root_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, also past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=("channels", "classes",
                                             "flat"))
def cnn_weights(key, channels: Sequence[int], classes: int, flat: int):
    """The six-conv CNN's weights in its parameter layout: He-normal 3x3
    kernels over the true fan-in, zero biases, a head drawn from a
    normal truncated at 2 sigma and scaled by 1/sqrt(fan-in)."""
    ks = jax.random.split(key, len(channels) + 1)
    convs, cin = [], 3
    for k, cout in zip(ks, channels):
        std = math.sqrt(2.0 / (9 * cin))
        convs.append({"w": std * jax.random.normal(k, (3, 3, cin, cout)),
                      "b": jnp.zeros((cout,))})
        cin = cout
    head = jax.random.truncated_normal(ks[-1], -2.0, 2.0, (flat, classes))
    return {"convs": convs,
            "head": {"w": head / math.sqrt(flat), "b": jnp.zeros((classes,))}}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _client_shards(key, n_clients: int, n_per: int, classes: int,
                   per_client: int, image: Sequence[int]):
    k_proto, k_noise = jax.random.split(key)
    protos = jax.random.normal(k_proto, (classes,) + tuple(image))
    c = jnp.arange(n_clients)[:, None]
    part = jnp.arange(n_per)[None, :] * per_client // n_per
    y = ((c * per_client + part) % classes).astype(jnp.int32)
    x = protos[y] + 0.6 * jax.random.normal(
        k_noise, (n_clients, n_per) + tuple(image))
    return {"x": x, "y": y}, jnp.full((n_clients,), n_per, jnp.int32)


def client_shards(key, n_clients: int, n_per: int, classes: int,
                  per_client: int, image: Sequence[int]):
    """Non-IID image shards: client c holds `per_client` classes, equal
    parts of each; an image is its class prototype plus 0.6 x N(0, 1)
    noise. Returns ({"x": [C, n, H, W, 3], "y": [C, n]}, n_samples)."""
    return _client_shards(key, n_clients, n_per, classes, per_client,
                          tuple(image))


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
