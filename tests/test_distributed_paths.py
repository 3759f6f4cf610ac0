"""Subprocess tests (8-16 fake devices): the distributed code paths the
single-device suite cannot reach — seq-sharded attention numerics,
flash-decode with a sequence-sharded cache, and build_case lowering on a
reduced production-like mesh."""
import os
import subprocess
import sys
import textwrap

import pytest

# subprocess device farms + full compiles
pytestmark = pytest.mark.slow


def _run(src: str, timeout: int = 1200) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"        # fake host devices, never a chip
    r = subprocess.run([sys.executable, "-c", src], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return r.stdout


def test_seq_sharded_attention_matches_dense():
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.models.attention import (
            seq_sharded_flash_attention, _flash_attention_dense)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        q = jax.random.normal(jax.random.key(0), (2, 256, 2, 2, 16))
        k = jax.random.normal(jax.random.key(1), (2, 256, 2, 16))
        v = jax.random.normal(jax.random.key(2), (2, 256, 2, 16))
        with jax.set_mesh(mesh):
            a = jax.jit(lambda q, k, v: seq_sharded_flash_attention(
                q, k, v, q_chunk=32, kv_chunk=32))(q, k, v)
        b = _flash_attention_dense(q, k, v, causal=True, window=None,
                                   q_chunk=32, kv_chunk=32, q_offset=0)
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < 1e-5, err
        print("SEQSHARD_OK", err)
    """))
    assert "SEQSHARD_OK" in out


def test_flash_decode_sharded_cache_matches_local():
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.models.attention import (decode_attention,
                                            decode_attention_local)
        mesh = jax.make_mesh((1, 8), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        B, S, KV, G, D = 2, 64, 2, 2, 16
        key = jax.random.key(0)
        ck = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, D))
        cv = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, D))
        q = jax.random.normal(jax.random.fold_in(key, 3), (B, KV, G, D))
        kn = jax.random.normal(jax.random.fold_in(key, 4), (B, KV, D))
        vn = jax.random.normal(jax.random.fold_in(key, 5), (B, KV, D))
        pos = jnp.int32(37)
        with jax.set_mesh(mesh):
            o1, k1_, v1_ = jax.jit(lambda *a: decode_attention(
                mesh, *a))(q, ck, cv, kn, vn, pos)
        o2, k2_, v2_ = decode_attention_local(q, ck, cv, kn, vn, pos)
        for a, b in ((o1, o2), (k1_, k2_), (v1_, v2_)):
            err = float(jnp.max(jnp.abs(a - b)))
            assert err < 1e-5, err
        print("DECODE_OK")
    """))
    assert "DECODE_OK" in out


@pytest.mark.parametrize("arch,shape", [
    ("granite-moe-1b-a400m", "train_4k"),
    ("zamba2-2.7b", "decode_32k"),
])
def test_build_case_lowers_on_reduced_mesh(arch, shape):
    """build_case must produce consistent (args, shardings) trees and lower
    on a reduced 4x4 mesh (full 16x16 is covered by the dry-run sweep)."""
    out = _run(textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import jax
        from repro.configs.base import SHAPES_BY_NAME
        from repro.configs.registry import get_smoke_config
        from repro.launch.specs import build_case
        import dataclasses
        mesh = jax.make_mesh((4, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        cfg = get_smoke_config("{arch}").replace(num_vehicles=4)
        shape = SHAPES_BY_NAME["{shape}"]
        shape = dataclasses.replace(shape, seq_len=256, global_batch=16)
        with jax.set_mesh(mesh):
            step, args, shardings = build_case(cfg, shape, mesh)
            assert jax.tree.structure(
                jax.tree.map(lambda _: 0, args)) == jax.tree.structure(
                jax.tree.map(lambda _: 0, shardings))
            lowered = jax.jit(step, in_shardings=shardings).lower(*args)
            lowered.compile()
        print("BUILDCASE_OK")
    """))
    assert "BUILDCASE_OK" in out
