"""A toy model over integer token sequences, for the test that a model
is added to the benchmark by files alone: an embedding of each token,
the sequence's embeddings flattened, and a two-layer MLP to the class.

`m` is the configuration's `model` group: `vocab`, `seq`, `embed`,
`hidden` and `classes`. Its client data is `{"tok": int32 [C, n, seq],
"y": int32 [C, n]}`: of another rank and dtype than images."""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _weights(key, vocab: int, seq: int, embed: int, hidden: int,
             classes: int):
    k_e, k_1, k_2 = jax.random.split(key, 3)
    return {"embed": jax.random.normal(k_e, (vocab, embed)),
            "l1": {"w": jax.random.normal(k_1, (seq * embed, hidden))
                   / math.sqrt(seq * embed), "b": jnp.zeros((hidden,))},
            "l2": {"w": jax.random.normal(k_2, (hidden, classes))
                   / math.sqrt(hidden), "b": jnp.zeros((classes,))}}


def weights(key, m: Dict):
    return _weights(key, m["vocab"], m["seq"], m["embed"], m["hidden"],
                    m["classes"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _shards(key, n_clients: int, n_per: int, classes: int,
            per_client: int, vocab: int, seq: int):
    c = jnp.arange(n_clients)[:, None]
    part = jnp.arange(n_per)[None, :] * per_client // n_per
    y = ((c * per_client + part) % classes).astype(jnp.int32)
    # a class's tokens cluster around a vocabulary offset of its own
    noise = jax.random.randint(key, (n_clients, n_per, seq), 0, vocab // 2)
    tok = (y[..., None] * (vocab // classes) + noise) % vocab
    return {"tok": tok.astype(jnp.int32), "y": y}, \
        jnp.full((n_clients,), n_per, jnp.int32)


def shards(key, n_clients: int, traffic: Dict, m: Dict):
    return _shards(key, n_clients, traffic["samples_per_client"],
                   m["classes"], traffic["classes_per_client"], m["vocab"],
                   m["seq"])


def _loss(params, batch, precision=None):
    x = params["embed"][batch["tok"]].reshape(batch["tok"].shape[0], -1)
    h = jax.nn.relu(jnp.dot(x, params["l1"]["w"], precision=precision)
                    + params["l1"]["b"])
    logits = jnp.dot(h, params["l2"]["w"], precision=precision) \
        + params["l2"]["b"]
    y = batch["y"]
    return jnp.mean(jax.nn.logsumexp(logits, -1)
                    - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


def program_loss(m: Dict):
    return _loss


def reference_loss(params, batch):
    return _loss(params, batch, jax.lax.Precision.HIGHEST)


def train_flops_per_sample(m: Dict) -> int:
    fwd = 2 * (m["seq"] * m["embed"] * m["hidden"]
               + m["hidden"] * m["classes"])
    return 3 * fwd
