"""Smoke run of the system's main path on a TPU, through its entry points.

  python chip_smoke.py              one chip: the train, sched and serve
                                    phases below
  python chip_smoke.py --chips 4    four chips: only the handoff mesh
                                    phase, 4 devices against 1
  JAX_PLATFORMS=cpu python chip_smoke.py --tiny
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python chip_smoke.py --tiny --chips 4
                                    rehearsal of the same phases on the
                                    CPU at toy sizes (never reports ok)

Phases (one process; weights and data are random, made from --seed):

  train  `run_fl(streaming=True)`: the fused engine trains the paper's
         six-conv CNN at its published widths on the CIFAR-like task,
         VEDS+COT, 40 clients, S = U = 10, T = 60, batch 32, 3 rounds
         with in-scan eval. One dispatch; finite loss and accuracy; at
         least one upload; the params moved; the program holds the
         Pallas DT-score kernel (`tpu_custom_call`), not its interpreter.
  sched  VEDS `stream_rounds` over 256 cells (2560 DT candidates per
         slot) with a persistent fleet, with the kernel and with
         `use_kernel=False`: equal success masks, and no padded or
         out-of-coverage vehicle ever succeeds. Then one round of the
         same stream on the host CPU: the mask entries that disagree
         with the chip are counted and printed.
  serve  `repro.launch.serve.drive`, closed loop, 4 clients x 3 requests,
         batch 8, madca: every request answered with finite losses; a
         request served at occupancy 1 is bit-for-bit the solo B = 1
         service's answer. Whether B > 1 packing is bitwise too is
         printed.
  mesh   (--chips 4 only) `mesh_fused_rollout` with handoff, madca,
         16 cells of S = U = 10, T = 60 on `fleet_mesh(4)` and on
         `fleet_mesh(1)`: success masks bit for bit, floats to fp32
         tolerance, and an all-to-all in the 4-device program.

Any exception or failed check exits non-zero. Timings printed on the way
are smoke timings (compilation included), not measurements. The last
line of stdout is one JSON object, `{"ok": true, "device": {...}}`,
printed only on a TPU at the full sizes. Without a TPU the script exits
non-zero before any phase (or, with --tiny, after them).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    clients: int = 40
    sov: int = 10
    opv: int = 10
    slots: int = 60
    batch_size: int = 32
    n_train: int = 4000
    n_test: int = 512
    rounds: int = 3
    cells: int = 256            # sched phase: 2560 DT candidates per slot
    sched_rounds: int = 2
    mesh_cells: int = 16


TINY = Sizes(clients=8, sov=4, opv=3, slots=10, batch_size=8, n_train=240,
             n_test=64, rounds=2, cells=4, sched_rounds=2, mesh_cells=8)


class CheckFailed(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def report(phase: str, **values) -> None:
    print(f"[{phase}] " + "  ".join(f"{k}={v}" for k, v in values.items()),
          flush=True)


def _finite(xs) -> bool:
    import numpy as np
    return bool(np.isfinite(np.asarray(xs, np.float64)).all())


def phase_train(z: Sizes, seed: int, on_tpu: bool) -> None:
    import jax
    import numpy as np
    from repro.data.synthetic import cifar_like_dataset, partition_labels
    from repro.fl.engine import ClientShards
    from repro.fl.simulator import FLSimConfig, fused_program, run_fl
    from repro.models.cnn import cnn_accuracy, cnn_decl, cnn_loss
    from repro.models.module import materialize

    key = jax.random.key(seed)
    x, y = cifar_like_dataset(jax.random.fold_in(key, 1), z.n_train)
    xt, yt = cifar_like_dataset(jax.random.fold_in(key, 2), z.n_test)
    parts = partition_labels(np.asarray(y), z.clients, iid=False)
    shards = ClientShards.from_ragged(
        [{"x": x[i], "y": y[i]} for i in parts])
    params = materialize(jax.random.fold_in(key, 3), cnn_decl())
    sim = FLSimConfig(n_clients=z.clients, n_sov=z.sov, n_opv=z.opv,
                      n_slots=z.slots, batch_size=z.batch_size,
                      rounds=z.rounds, scheduler="veds", streaming=True,
                      fused_unroll=1)
    test = {"x": xt, "y": yt}

    def eval_fn(p):
        return cnn_accuracy(p, test)

    k_run = jax.random.fold_in(key, 4)
    t0 = time.perf_counter()
    hist = run_fl(k_run, params, cnn_loss, shards, sim, eval_fn=eval_fn,
                  eval_every=1)
    t_first = time.perf_counter() - t0
    seg, args = fused_program(k_run, params, cnn_loss, shards, sim,
                              eval_fn, 1)
    kernel = "tpu_custom_call" in seg.lower(*args).as_text()
    t0 = time.perf_counter()
    res = jax.block_until_ready(seg(*args))
    t_again = time.perf_counter() - t0
    moved = any(bool((np.asarray(a[0]) != np.asarray(b)).any())
                for a, b in zip(jax.tree.leaves(res.params),
                                jax.tree.leaves(params)))
    same = np.array_equal(np.asarray(res.loss[:, 0]), hist["loss"])
    report("train", dispatches=hist["dispatches"],
           uploads=hist["n_success"], loss=hist["loss"],
           accuracy=hist["metric"], params_moved=moved,
           tpu_custom_call=kernel, rerun_bitwise=same,
           first_call_s=round(t_first, 3), rerun_s=round(t_again, 3))
    check(hist["dispatches"] == 1, "train: the run is not one dispatch")
    check(_finite(hist["loss"]) and _finite(hist["metric"]),
          "train: non-finite loss or accuracy")
    check(sum(hist["n_success"]) > 0, "train: no upload succeeded")
    check(moved, "train: the params did not move")
    check(same, "train: the program rerun differs from run_fl's")
    check(kernel or not on_tpu,
          "train: the DT-score kernel is not compiled into the program")


def phase_sched(z: Sizes, seed: int) -> None:
    import jax
    import numpy as np
    from repro.channel.mobility import ManhattanParams
    from repro.channel.v2x import ChannelParams
    from repro.core.baselines import VedsScheduler
    from repro.core.lyapunov import VedsParams
    from repro.core.scenario import ScenarioParams, rollout_rounds
    from repro.core.streaming import (StreamConfig, sched_state0,
                                      stream_rounds)

    sc = ScenarioParams(n_sov=z.sov, n_opv=z.opv, n_slots=z.slots)
    mob, ch, prm = ManhattanParams(), ChannelParams(), VedsParams()
    key = jax.random.fold_in(jax.random.key(seed), 5)

    def masks(use_kernel: bool, n_rounds: int, device=None):
        cfg = StreamConfig(n_rounds=n_rounds, batch=z.cells,
                           carry_queues=True)
        sched = VedsScheduler(use_kernel=use_kernel)
        fn = jax.jit(lambda k: stream_rounds(k, sched, sc, mob, ch, prm,
                                             cfg).outputs.success)
        k = key if device is None else jax.device_put(key, device)
        t0 = time.perf_counter()
        out = np.asarray(fn(k))
        return out, time.perf_counter() - t0

    R = z.sched_rounds
    kern, t_kern = masks(True, R)
    ref, t_ref = masks(False, R)
    cfg = StreamConfig(n_rounds=R, batch=z.cells, carry_queues=True)
    _, rnds, _ = jax.jit(lambda k: rollout_rounds(
        k, sched_state0(k, sc, mob, cfg, ch=ch), sc, mob, ch, prm, R))(key)
    valid = np.asarray(rnds.valid_sov)                       # [R, B, S]
    cpu, t_cpu = masks(False, 1, jax.devices("cpu")[0])
    differ = int((cpu[0] != kern[0]).sum())
    report("sched", cells=z.cells, candidates=z.cells * z.sov, rounds=R,
           uploads=int(kern.sum()), padded_slots=int((~valid).sum()),
           kernel_eq_reference=bool(np.array_equal(kern, ref)),
           invalid_successes=int((kern & ~valid).sum()),
           cpu_round0_mask_entries_differing=f"{differ}/{cpu[0].size}",
           kernel_first_call_s=round(t_kern, 3),
           reference_first_call_s=round(t_ref, 3),
           cpu_round_s=round(t_cpu, 3))
    check(np.array_equal(kern, ref),
          "sched: kernel and use_kernel=False masks differ")
    check(not (kern & ~valid).any(),
          "sched: a padded or invalid vehicle succeeded")
    check(kern.any(), "sched: no upload succeeded")


def phase_serve(seed: int) -> None:
    import numpy as np
    from repro.launch.serve import (SchedulingService, ServeConfig,
                                    ServeRequest, drive)

    cfg = ServeConfig(batch=8, batch_tiers=(1, 2, 4, 8), scheduler="madca",
                      seed=seed)
    n_clients, n_requests = 4, 3
    t0 = time.perf_counter()
    out = drive(cfg, n_clients=n_clients, n_requests=n_requests,
                baseline=False, seed=seed)
    t_drive = time.perf_counter() - t0
    resps, summ = out["responses"], out["batched"]

    def same(a, b):
        return (np.array_equal(a.success, b.success)
                and np.array_equal(a.n_success, b.n_success)
                and np.array_equal(a.loss, b.loss))

    def solo(req):
        svc = SchedulingService(dataclasses.replace(cfg, batch=1,
                                                    batch_tiers=None))
        return svc.run_batch([req])[0]

    # strict: one request alone is routed to the occupancy-1 rung
    req = ServeRequest("probe", n_rounds=cfg.max_rounds, seed=seed + 7)
    one = SchedulingService(cfg).run_batch([req])[0]
    # informational: client 0's first request, packed with the others
    first = resps[0]
    packed_eq = same(first, solo(ServeRequest(
        first.session, first.n_rounds, seed=seed)))
    report("serve", requests=len(resps), tiers=sorted({r.tier for r in
                                                       resps}),
           mean_occupancy=summ["mean_occupancy"], p50_ms=summ["p50_ms"],
           p99_ms=summ["p99_ms"], occupancy1_tier=one.tier,
           occupancy1_eq_solo=same(one, solo(req)),
           packed_tier=first.tier, packed_eq_solo_bitwise=packed_eq,
           drive_s=round(t_drive, 3))
    check(len(resps) == n_clients * n_requests == summ["n_requests"],
          "serve: not every request was answered")
    check(all(_finite(r.loss) and r.success.shape[0] == r.n_rounds
              for r in resps), "serve: a response is malformed")
    check(one.tier.endswith("xB1"), "serve: probe not at occupancy 1")
    check(same(one, solo(req)),
          "serve: occupancy-1 response differs from solo B=1")


def phase_mesh(z: Sizes, seed: int, n_devices: int) -> None:
    import jax
    import numpy as np
    from repro.channel.mobility import ManhattanParams
    from repro.channel.v2x import ChannelParams
    from repro.core.baselines import get_scheduler
    from repro.core.lyapunov import VedsParams
    from repro.core.scenario import ScenarioParams
    from repro.core.streaming import StreamConfig, round_keys
    from repro.fl.engine import init_carry
    from repro.launch.serve import default_problem
    from repro.sharding.mesh_exec import (_fused_exec, fleet_mesh,
                                          mesh_fused_rollout, place_batch,
                                          place_carry, place_shards)

    R, B = 3, z.mesh_cells
    sc = ScenarioParams(n_sov=z.sov, n_opv=z.opv, n_slots=z.slots,
                        batch_size=8)
    mob, ch, prm = ManhattanParams(), ChannelParams(), VedsParams()
    sched = get_scheduler("madca")
    cfg = StreamConfig(n_rounds=R, batch=B, carry_queues=True,
                       handoff=True)
    params, loss_fn, shards = default_problem(z.clients)
    key = jax.random.fold_in(jax.random.key(seed), 6)
    keys = round_keys(key, cfg, R)
    sel = jax.random.randint(jax.random.fold_in(key, 1), (R, B, z.sov), 0,
                             z.clients)
    mb_u = jax.random.uniform(jax.random.fold_in(key, 2),
                              (R, B, z.sov, sc.batch_size))

    def run(n):
        mesh = fleet_mesh(n)
        carry = init_carry(key, sc, mob, cfg, params, ch=ch)
        t0 = time.perf_counter()
        res = jax.block_until_ready(mesh_fused_rollout(
            mesh, keys, sel, mb_u, sched, sc, mob, ch, prm, cfg, loss_fn,
            shards, carry, lr=0.1))
        return mesh, res, time.perf_counter() - t0

    _, r1, t1 = run(1)
    mesh, rn, tn = run(n_devices)
    step = _fused_exec(sched, sc, mob, ch, prm, cfg, loss_fn, 0.1, 5.0,
                       None, 1, 1, None, None, True)
    hlo = step.lower(
        place_carry(mesh, init_carry(key, sc, mob, cfg, params, ch=ch)),
        keys, place_batch(mesh, sel), place_batch(mesh, mb_u),
        place_shards(mesh, shards), jax.numpy.arange(R),
        jax.numpy.ones((R,), bool), jax.numpy.zeros((R,), bool)
    ).compile().as_text()
    a2a = "all-to-all" in hlo
    masks_eq = np.array_equal(np.asarray(r1.outputs.success),
                              np.asarray(rn.outputs.success))

    def close(a, b):
        return np.allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                           atol=1e-6)

    floats = (close(r1.params["w"], rn.params["w"])
              and close(r1.loss, rn.loss)
              and close(r1.fleet.pos, rn.fleet.pos))
    report("mesh", devices=n_devices, cells=B, rounds=R,
           uploads=int(np.asarray(rn.outputs.success).sum()),
           masks_bitwise=masks_eq, floats_close=floats, all_to_all=a2a,
           first_call_1dev_s=round(t1, 3),
           first_call_ndev_s=round(tn, 3))
    check(masks_eq, "mesh: success masks differ between 1 and n devices")
    check(floats, "mesh: floats differ beyond fp32 tolerance")
    check(a2a or n_devices == 1, "mesh: no all-to-all in the program")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 4-vs-1-device handoff mesh phase")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes, to rehearse the phases off the chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not (on_tpu or args.tiny):
        print(f"no TPU found (JAX sees {dev.platform}); use --tiny to "
              "rehearse on another platform", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    z = TINY if args.tiny else Sizes()
    report("device", platform=dev.platform, kind=dev.device_kind,
           count=len(devices), jax=jax.__version__,
           compile_cache=jax.config.jax_compilation_cache_dir)
    phases = ([("mesh", lambda: phase_mesh(z, args.seed, args.chips))]
              if args.chips > 1 else
              [("train", lambda: phase_train(z, args.seed, on_tpu)),
               ("sched", lambda: phase_sched(z, args.seed)),
               ("serve", lambda: phase_serve(args.seed))])
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        report(name, wall_s=round(time.perf_counter() - t0, 3))
    if not on_tpu or args.tiny:
        print("rehearsal finished: not a TPU run at full size, no result",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
