"""The paper's six-conv CNN (arXiv:2406.17470, Sec. VI): three stages of
two 3x3 SAME convs with ReLU and a 2x2 max-pool, and a linear head, on
CIFAR-10-shaped images.

`m` is the configuration's `model` group: `channels`, `image`
([H, W, 3]), `classes` and `flat` (the head's fan-in)."""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("channels", "classes",
                                             "flat"))
def _weights(key, channels: Sequence[int], classes: int, flat: int):
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


def weights(key, m: Dict):
    """The weights in the program's parameter layout: He-normal 3x3
    kernels over the true fan-in, zero biases, a head drawn from a
    normal truncated at 2 sigma and scaled by 1/sqrt(fan-in)."""
    return _weights(key, tuple(m["channels"]), m["classes"], m["flat"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _shards(key, n_clients: int, n_per: int, classes: int,
            per_client: int, image: Sequence[int]):
    k_proto, k_noise = jax.random.split(key)
    protos = jax.random.normal(k_proto, (classes,) + tuple(image))
    c = jnp.arange(n_clients)[:, None]
    part = jnp.arange(n_per)[None, :] * per_client // n_per
    y = ((c * per_client + part) % classes).astype(jnp.int32)
    x = protos[y] + 0.6 * jax.random.normal(
        k_noise, (n_clients, n_per) + tuple(image))
    return {"x": x, "y": y}, jnp.full((n_clients,), n_per, jnp.int32)


def shards(key, n_clients: int, traffic: Dict, m: Dict):
    """Non-IID image shards of the traffic's `samples_per_client`:
    client c holds `classes_per_client` classes, equal parts of each; an
    image is its class prototype plus 0.6 x N(0, 1) noise. Returns
    ({"x": [C, n, H, W, 3], "y": [C, n]}, n_samples [C])."""
    return _shards(key, n_clients, traffic["samples_per_client"],
                   m["classes"], traffic["classes_per_client"],
                   tuple(m["image"]))


def program_loss(m: Dict):
    """The program's loss, `loss(params, {"x", "y"})`."""
    from repro.models.cnn import cnn_loss
    return cnn_loss


def _logits(params, x):
    for i, layer in enumerate(params["convs"]):
        x = jax.lax.conv_general_dilated(
            x, layer["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST) + layer["b"]
        x = jax.nn.relu(x)
        if i % 2 == 1:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    return jnp.dot(x, params["head"]["w"], precision=HIGHEST) \
        + params["head"]["b"]


def reference_loss(params, batch):
    """Mean softmax cross-entropy of one minibatch, the plain reference
    at the highest matmul precision."""
    logits = _logits(params, batch["x"]).astype(jnp.float32)
    y = batch["y"]
    return jnp.mean(jax.nn.logsumexp(logits, -1)
                    - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


def forward_flops(m: Dict, kernel: int = 3) -> int:
    """Multiply-adds x 2 of one image through the six 3x3 SAME convs
    (2x2 pooling after every pair) and the linear head."""
    h, w, cin = m["image"]
    total = 0
    for i, cout in enumerate(m["channels"]):
        total += 2 * h * w * kernel * kernel * cin * cout
        cin = cout
        if i % 2 == 1:
            h, w = h // 2, w // 2
    return total + 2 * h * w * cin * m["classes"]


def train_flops_per_sample(m: Dict) -> int:
    """Forward and backward of one image: three times the forward."""
    return 3 * forward_flops(m)
