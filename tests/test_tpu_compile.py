"""Compile-only rehearsals for a TPU v5e, all in this one file.

The TPU compiler is installed without the chip, and it compiles for a
topology that is described rather than attached: these tests compile the
main path's kernel and programs for a `v5e:2x2` and check what the
compiler put in (the Pallas kernel as a `tpu_custom_call`, the handoff
exchange as an all-to-all). Nothing runs, so they say nothing about
results or times — only that Mosaic and XLA:TPU accept the programs.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture, never while a module is imported, and
every compile happens in the test's own process. The persistent
compilation cache is off around these compiles: an executable for a
described chip is written to it but cannot be read back without one.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.channel.mobility import ManhattanParams
from repro.channel.v2x import ChannelParams
from repro.core.baselines import get_scheduler
from repro.core.lyapunov import VedsParams
from repro.core.scenario import ScenarioParams
from repro.core.streaming import StreamConfig, round_keys
from repro.fl.engine import ClientShards, init_carry
from repro.fl.simulator import FLSimConfig, fused_program
from repro.kernels.veds_score import ops
from repro.kernels.veds_score.veds_score import LANES, veds_dt_score_pallas
from repro.launch.serve import default_problem
from repro.models.cnn import cnn_accuracy, cnn_decl, cnn_loss
from repro.models.module import materialize
from repro.sharding.mesh_exec import (_fused_exec, batch_shardings,
                                      carry_shardings, shards_shardings)

KEY = jax.random.key(0)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                 # noqa: BLE001 — any refusal
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _shapes(tree, sharding):
    """Shapes of `tree`'s leaves, each placed by `sharding` (one sharding
    for every leaf, or a matching tree of them)."""
    if not isinstance(sharding, (SingleDeviceSharding, NamedSharding)):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sharding)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("c", [10, 300, 2560])
def test_veds_score_kernel_compiles(topo, c):
    """Mosaic refused the old 1-D [block_c] layout above 256 candidates;
    the lane-dense tiles compile at every count."""
    one = SingleDeviceSharding(topo.devices[0])
    rows, block = ops.tile_rows(c)
    kern = functools.partial(veds_dt_score_pallas, V=0.2, kappa=0.1,
                             bw=20e6, noise=8e-14, p_max=0.3,
                             block_rows=block, interpret=False)
    f32, b = ((rows, LANES), jnp.float32), ((rows, LANES), jnp.bool_)
    args = [jax.ShapeDtypeStruct(*s, sharding=one) for s in (f32, f32, f32, b)]
    text = jax.jit(kern).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_paper_scale_fused_segment_compiles(topo, monkeypatch):
    """The paper-scale fused run — six-conv CNN at its published widths,
    VEDS+COT, 40 clients, S = U = 10, T = 60, batch 32, in-scan eval —
    compiles for one v5e with the Pallas kernel, not the interpreter.
    Code that asks for the backend still sees the CPU here, so the test
    steers the kernel branch itself, with the jit caches cleared on both
    sides so no CPU trace is reused and none leaks out."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    try:
        one = SingleDeviceSharding(topo.devices[0])
        sim = FLSimConfig(rounds=3, scheduler="veds", streaming=True)
        params = materialize(jax.random.fold_in(KEY, 3), cnn_decl())
        n_max = 100
        shards = ClientShards(
            data={"x": jax.ShapeDtypeStruct(
                      (sim.n_clients, n_max, 32, 32, 3), jnp.float32),
                  "y": jax.ShapeDtypeStruct((sim.n_clients, n_max),
                                            jnp.int32)},
            n_samples=jax.ShapeDtypeStruct((sim.n_clients,), jnp.int32))
        test = {"x": jnp.zeros((64, 32, 32, 3)),
                "y": jnp.zeros((64,), jnp.int32)}
        seg, args = fused_program(KEY, params, cnn_loss, shards, sim,
                                  lambda p: cnn_accuracy(p, test), 1)
        text = seg.lower(*_shapes(args, one)).compile().as_text()
        assert "tpu_custom_call" in text
    finally:
        jax.clear_caches()


def test_handoff_mesh_step_compiles_with_all_to_all(topo):
    """`mesh_fused_rollout`'s whole-run step with handoff, on the four
    chips of the 2x2 slice as the cell ("data") axis: 16 cells of
    S = U = 10, T = 60 under madca. With the cell axis sharded, the §11
    exchange must lower to an all-to-all."""
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    R, B = 2, 16
    sc = ScenarioParams(n_sov=10, n_opv=10, n_slots=60, batch_size=8)
    mob, ch = ManhattanParams(), ChannelParams()
    prm = VedsParams()
    cfg = StreamConfig(n_rounds=R, batch=B, carry_queues=True,
                       handoff=True)
    params, loss_fn, shards = default_problem(40)
    carry = init_carry(KEY, sc, mob, cfg, params, ch=ch)
    sel = jnp.zeros((R, B, sc.n_sov), jnp.int32)
    mb_u = jnp.zeros((R, B, sc.n_sov, sc.batch_size))
    rep = NamedSharding(mesh, P())
    step = _fused_exec(get_scheduler("madca"), sc, mob, ch, prm, cfg,
                       loss_fn, 0.1, 5.0, None, 1, 1, None, None, True)
    args = (_shapes(carry, carry_shardings(mesh, carry)),
            _shapes(round_keys(KEY, cfg, R), rep),
            _shapes(sel, batch_shardings(mesh, sel)),
            _shapes(mb_u, batch_shardings(mesh, mb_u)),
            _shapes(shards, shards_shardings(mesh, shards)),
            _shapes(jnp.arange(R), rep), _shapes(jnp.ones((R,), bool), rep),
            _shapes(jnp.zeros((R,), bool), rep))
    text = step.lower(*args).compile().as_text()
    assert "all-to-all" in text
