"""End-to-end VFL training driver (host-scale).

Trains a reduced variant of any assigned architecture with the full paper
pipeline: Manhattan mobility -> 3GPP channels -> VEDS scheduling -> local SGD
-> masked aggregation, on synthetic LM data.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
      --arch qwen3-32b --rounds 20 --devices 8 --vehicles 4

With `JAX_PLATFORMS=cpu` the mesh is `--devices` fake host devices;
otherwise it takes `--devices` of the accelerators JAX finds.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--vehicles", type=int, default=4)
    ap.add_argument("--batch-per-vehicle", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--scheduler", default="veds",
                    choices=["veds", "optimal", "v2i_only", "madca", "sa"])
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # fake host devices stand in for the mesh on CPU only; on an
        # accelerator the mesh is the chips JAX finds
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")
    import jax
    import jax.numpy as jnp
    from repro.channel.mobility import ManhattanParams
    from repro.channel.v2x import ChannelParams
    from repro.configs.registry import get_smoke_config
    from repro.core.baselines import SCHEDULERS
    from repro.core.lyapunov import VedsParams
    from repro.core.scenario import ScenarioParams, make_round
    from repro.data.synthetic import lm_batch
    from repro.fl.vfl import lm_loss, make_vfl_round
    from repro.models import engine
    from repro.models.module import materialize, param_bytes
    from repro.sharding.policy import attention_tp_mode

    V = args.vehicles
    model_par = max(1, args.devices // V)
    mesh = jax.make_mesh(
        (V, model_par), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_smoke_config(args.arch).replace(num_vehicles=V, grad_accum=1)
    tp = attention_tp_mode(cfg.num_heads, model_par)
    key = jax.random.key(args.seed)

    decl = engine.model_decl(cfg, tp)
    params = materialize(key, decl)
    params_v = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (V,) + x.shape), params)
    q_bits = 8.0 * param_bytes(decl)
    print(f"arch={cfg.name} reduced: {param_bytes(decl)/1e6:.1f} MB params "
          f"-> Q={q_bits:.3g} bits, mesh=({V},{model_par}), tp={tp}")

    mob = ManhattanParams()
    ch = ChannelParams()
    prm = VedsParams(Q=min(q_bits, 2e7), slot=0.1)
    sc = ScenarioParams(n_sov=V, n_opv=8, n_slots=50)
    sched = SCHEDULERS[args.scheduler]
    mk_round = jax.jit(lambda k: make_round(k, sc, mob, ch, prm))
    run_sched = jax.jit(lambda r: sched(r, prm, ch))

    with jax.set_mesh(mesh):
        round_fn = jax.jit(make_vfl_round(cfg, mesh, tp, lr=args.lr))

        @jax.jit
        def eval_loss(params_v, batch):
            p = jax.tree.map(lambda x: x[0], params_v)
            return lm_loss(p, batch, cfg, tp)

        weights = jnp.ones((V,))
        eval_batch = lm_batch(jax.random.fold_in(key, 999), 8, args.seq,
                              cfg.vocab_size)
        for r in range(args.rounds):
            t0 = time.time()
            rnd = mk_round(jax.random.fold_in(key, 2 * r))
            mask = run_sched(rnd)["success"].astype(jnp.float32)[:V]
            batch = lm_batch(jax.random.fold_in(key, 2 * r + 1),
                             V * args.batch_per_vehicle, args.seq,
                             cfg.vocab_size)
            batch_v = jax.tree.map(
                lambda x: x.reshape(V, args.batch_per_vehicle, *x.shape[1:]),
                batch)
            params_v = round_fn(params_v, batch_v, mask, weights)
            loss = float(eval_loss(params_v, eval_batch))
            print(f"round {r:3d} succ={int(mask.sum())}/{V} "
                  f"loss={loss:.4f}  ({time.time()-t0:.1f}s)")

    if args.ckpt:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt,
                        jax.tree.map(lambda x: x[0], params_v),
                        meta={"arch": cfg.name}, step=args.rounds)
        print("saved", args.ckpt)
    return 0


if __name__ == "__main__":
    sys.exit(main())
