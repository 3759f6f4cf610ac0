"""FL runtime: optimizers, checkpointing, data partitioning, and the
distributed vfl_round (run in a subprocess with 8 fake devices)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.data.synthetic import lm_batch, partition_labels
from repro.optim import adam, momentum, sgd


def _quad_min(opt_factory):
    init, update = opt_factory
    params = {"w": jnp.array([3.0, -2.0])}
    state = init(params)
    for step in range(200):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state = update(params, g, state, step)
    return float(jnp.abs(params["w"]).max())


def test_optimizers_minimize_quadratic():
    assert _quad_min(sgd(0.1)) < 1e-3
    assert _quad_min(momentum(0.05)) < 1e-3
    assert _quad_min(adam(0.1)) < 1e-2


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)},
            "blocks": [{"w": jnp.zeros((2, 2))}]}
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, tree, meta={"arch": "t"}, step=3)
    back = load_checkpoint(path, tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y))


def test_partition_noniid_two_classes():
    labels = np.repeat(np.arange(10), 100)
    parts = partition_labels(labels, 40, iid=False, classes_per_client=2)
    assert len(parts) == 40
    assert sum(len(p) for p in parts) == len(labels)
    for p in parts:
        assert len(np.unique(labels[p])) <= 2


def test_partition_iid_covers_all():
    labels = np.repeat(np.arange(10), 40)
    parts = partition_labels(labels, 8, iid=True)
    got = np.sort(np.concatenate(parts))
    np.testing.assert_array_equal(got, np.arange(len(labels)))


def test_lm_batch_shift_property():
    b = lm_batch(jax.random.key(0), 4, 32, 101)
    assert b["tokens"].shape == (4, 32) and b["labels"].shape == (4, 32)
    assert int(b["tokens"].max()) < 101


_DISTRIBUTED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs.registry import get_smoke_config
    from repro.models import engine
    from repro.models.module import materialize, axes_of
    from repro.fl.vfl import make_vfl_round, _local_sgd, lm_loss
    from repro.sharding.rules import default_rules, spec_for
    from repro.data.synthetic import lm_batch

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,)*2)
    cfg = get_smoke_config("qwen3-32b").replace(num_vehicles=4, grad_accum=2,
                                                compute_dtype="float32",
                                                param_dtype="float32")
    decl = engine.model_decl(cfg, tp="head")
    params = materialize(jax.random.key(0), decl)
    V = 4
    params_v = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (V,) + x.shape), params)
    batch = lm_batch(jax.random.key(1), V * 4, 64, cfg.vocab_size)
    batch_v = jax.tree.map(lambda x: x.reshape(V, 4, *x.shape[1:]), batch)
    mask = jnp.array([1., 0., 1., 1.])
    weights = jnp.array([1., 1., 2., 1.])
    with jax.set_mesh(mesh):
        round_fn = make_vfl_round(cfg, mesh, "head", lr=0.1)
        out = jax.jit(round_fn)(params_v, batch_v, mask, weights)
    # reference: per-vehicle local sgd + masked weighted mean, single device
    locals_ = []
    for v in range(V):
        b = jax.tree.map(lambda x: x[v], batch_v)
        locals_.append(_local_sgd(params, b, cfg, "head", lm_loss, 0.1))
    w = (mask * weights)
    ref = jax.tree.map(
        lambda *xs: sum(float(wi) * x for wi, x in zip(w, xs)) / float(
            w.sum()), *locals_)
    err = max(float(jnp.max(jnp.abs(a[0] - b)))
              for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)))
    agree = max(float(jnp.max(jnp.abs(l[0] - l[1])))
                for l in jax.tree.leaves(out))
    assert agree < 1e-6, f"vehicle replicas diverge: {agree}"
    assert err < 2e-4, f"distributed aggregation mismatch: {err}"
    print("DISTRIBUTED_OK")
""")


@pytest.mark.slow
def test_vfl_round_distributed_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"        # fake host devices, never a chip
    r = subprocess.run([sys.executable, "-c", _DISTRIBUTED], env=env,
                       capture_output=True, text=True, timeout=900)
    assert "DISTRIBUTED_OK" in r.stdout, r.stdout + r.stderr
