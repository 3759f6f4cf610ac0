"""Traffic driver: an open loop of requests at the scheduling service.

The traffic file gives the service's ladder (batch, horizon and
occupancy tiers, batching window), the client data every session trains
on, the sessions and their Zipf popularity, the round counts requests
ask for, and the arrival rate. Set-up builds the weights and data from
the seed with the configuration's model file (`models/<model>.py`), the
service (`SchedulingService` behind a `BatchServer`), warms every tier
the traffic reaches and creates every session. The window then sends
each request when it is due (`chipbench.schedule`), whether or not
earlier ones were answered, and times it from its due time to its
response. A request that fails, or is still unanswered
`drain_timeout_s` after the last was due, counts as missing.

`correct` replays sampled sessions through the plain reference: every
request the session was served, in order, from the session's creation.
Two kinds are sampled. Sessions with a short history, drawn from the
seed and holding the longest request served, are compared in full,
weights included. The most-served sessions are compared in their masks
and queues over their whole history; their weights are reported beside
(`params_gap_long`), since over tens of rounds two trainings drift apart
by their rounding alone.
"""
from __future__ import annotations

import asyncio
import inspect
import time
import zlib
from typing import Dict, List, Tuple

import jax
import numpy as np

from chipbench import data as D
from chipbench.checks import compare_serve
from chipbench.program import half_batch_loss, program_params
from chipbench.reference import Reference
from chipbench.schedule import open_loop, session_name
from chipbench.session import span


def _percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


class Cell:
    """The service under one cell's open loop. `fault` plants a fault for
    the benchmark's own tests and calibration: "half_batch", "frozen"
    (a session's state is left as it was before each request) or
    "flip" (one upload decision of the first answer of every dispatch
    altered where it is produced)."""

    def __init__(self, cfg: Dict, model, traffic: Dict, seed: int,
                 fault: str = ""):
        self.cfg, self.model, self.traffic = cfg, model, traffic
        self.seed, self.fault = seed, fault
        key = D.root_key(seed)
        self.k_w, self.k_data = (jax.random.fold_in(key, i) for i in (1, 2))
        self.svc_seed = int(np.random.default_rng([seed, 1]).integers(
            0, 2 ** 31 - 1))
        self.svc = None
        self.reqs: List[Dict] = []
        self.results: List[Dict] = []

    # ------------------------------------------------------------ set-up
    def _service_config(self):
        from repro.launch.serve import ServeConfig
        cfg, tr = self.cfg, self.traffic
        return ServeConfig(
            batch=tr["batch"], max_rounds=max(tr["tiers"]),
            tiers=tuple(tr["tiers"]), batch_tiers=tuple(tr["batch_tiers"]),
            window_s=1e-3 * tr["window_ms"], scheduler=cfg["scheduler"],
            n_sov=cfg["n_sov"], n_opv=cfg["n_opv"], n_slots=cfg["n_slots"],
            batch_size=cfg["batch_size"], carry_queues=cfg["carry_queues"],
            ipm_warm_iters=cfg["ipm_warm_iters"],
            ipm_iters=cfg["ipm_iters"], lr=cfg["lr"], alpha=cfg["alpha"],
            V=cfg["V"], q_bits=cfg["Q_bits"], seed=self.svc_seed)

    def _check_program(self, svc) -> None:
        """The service takes some settings from the program's defaults:
        refuse to measure it where they depart from the configuration."""
        from repro.fl.engine import fused_rollout
        sc, mob, ch, prm = program_params(self.cfg)
        clip = inspect.signature(fused_rollout).parameters["clip"].default
        for name, mine, theirs in (("scenario", sc, svc.sc),
                                   ("mobility", mob, svc.mob),
                                   ("channel", ch, svc.ch),
                                   ("scheduler", prm, svc.prm),
                                   ("clip", self.cfg["clip"], clip)):
            if mine != theirs:
                raise RuntimeError(f"the service's {name} settings depart "
                                   f"from the configuration: {theirs} != "
                                   f"{mine}")

    def setup(self) -> None:
        from repro.fl.engine import ClientShards
        from repro.launch.serve import SchedulingService
        cfg, tr = self.cfg, self.traffic
        m, model = cfg["model"], self.model
        with span("setup"):
            self.p0 = model.weights(self.k_w, m)
            data, n = model.shards(self.k_data, tr["clients"], tr, m)
            self.shards = ClientShards(data=data, n_samples=n)
            loss = model.program_loss(m)
            if self.fault == "half_batch":
                loss = half_batch_loss(loss)
            svc = SchedulingService(self._service_config(), params=self.p0,
                                    loss_fn=loss, client_data=self.shards)
            self._check_program(svc)
            svc.warmup(rounds=tr["rounds"])
            for i in range(tr["sessions"]):
                svc.session_carry(session_name(i))
            jax.block_until_ready([svc.sessions[s] for s in svc.sessions])
            self._plant(svc)
            self.svc = svc

    def _plant(self, svc) -> None:
        if self.fault == "frozen":
            svc.sessions.put = lambda session, carry: None
        if self.fault == "flip":
            run = svc.run_batch

            def flipped(reqs, **kw):
                out = run(reqs, **kw)
                out[0].success = out[0].success.copy()
                out[0].success[0, 0] ^= True
                return out
            svc.run_batch = flipped

    # ------------------------------------------------------------ window
    def window(self, seconds: float, traced: bool = False) -> Dict:
        """Send the schedule of `seconds` (a traced window: of at most
        `trace_seconds`) and wait for every answer."""
        from repro.launch.serve import BatchServer, ServeRequest
        tr, svc = self.traffic, self.svc
        if traced:
            seconds = min(seconds, tr["trace_seconds"])
        reqs = open_loop(self.seed, seconds, tr)
        results: List[Dict] = [{} for _ in reqs]
        run = svc.run_batch

        def dispatch(batch, **kw):
            with span("dispatch"):
                return run(batch, **kw)
        svc.run_batch = dispatch

        async def one(i: int, r: Dict, due: float, srv) -> None:
            t_send = time.perf_counter()
            results[i]["late_s"] = t_send - due
            try:
                resp = await srv.submit(ServeRequest(
                    r["session"], r["n_rounds"], r["seed"]))
            except Exception as e:          # noqa: BLE001 — counted
                results[i]["error"] = repr(e)
                return
            # the service hands back host arrays (run_batch ends in
            # np.asarray), so an answer's arrival is its work's end
            done = time.perf_counter()  # reprolint: disable=timer-no-block
            results[i].update(latency_s=done - due,
                              wait_s=t_send - due + resp.queue_wait_s,
                              resp=resp)

        async def go():
            async with BatchServer(svc) as srv:
                t0 = time.perf_counter()  # reprolint: disable=timer-no-block
                tasks = []
                for i, r in enumerate(reqs):
                    due = t0 + r["due_s"]
                    ahead = due - time.perf_counter()
                    if ahead > 0:
                        with span("await_arrival"):
                            await asyncio.sleep(ahead)
                    tasks.append(asyncio.ensure_future(one(i, r, due, srv)))
                await asyncio.wait(tasks, timeout=tr["drain_timeout_s"])
                end = time.perf_counter()  # reprolint: disable=timer-no-block
                for t in tasks:
                    t.cancel()
            return t0, end

        try:
            t0, t_end = asyncio.run(go())
        finally:
            svc.run_batch = run
        self.reqs, self.results = reqs, results
        ok = [r for r in results if "resp" in r]
        # a missing answer counts with the time until it was given up on
        lat = [r.get("latency_s", t_end - t0 - q["due_s"])
               for q, r in zip(reqs, results)]
        occ = svc.metrics.occupancy
        return {"seconds": t_end - t0, "attempted": len(reqs),
                "failed": len(reqs) - len(ok),
                "generator_late_p95_ms": 1e3 * _percentile(
                    [r["late_s"] for r in results if "late_s" in r], 95),
                "queue_wait_p95_ms": 1e3 * _percentile(
                    [r["wait_s"] for r in ok], 95) if ok else None,
                "occupancy_mean": float(np.mean(occ)) if occ else None,
                "metrics": {"serve_p95_ms": 1e3 * _percentile(lat, 95),
                            "serve_p50_ms": 1e3 * _percentile(lat, 50)}}

    # ------------------------------------------------------------- check
    def _pick(self) -> List[Tuple[str, bool]]:
        """Sessions to replay, as (session, long). The short ones, drawn
        from the seed: each served at most `check_history_rounds` rounds
        in the window, together at most `check_rounds`; first the one
        holding the longest request among those, at the least cost, then
        others in a seeded order while they fit. The long ones: the
        most-served sessions past that history, in order of their
        rounds, while together at most `check_long_rounds` (the first
        always)."""
        rounds: Dict[str, int] = {}
        longest: Dict[str, int] = {}
        for q in self.reqs:
            s = q["session"]
            rounds[s] = rounds.get(s, 0) + q["n_rounds"]
            longest[s] = max(longest.get(s, 0), q["n_rounds"])
        cap = self.traffic["check_history_rounds"]
        short = [s for s in rounds if rounds[s] <= cap] or \
            [min(rounds, key=lambda s: (rounds[s], s))]
        top = max(longest[s] for s in short)
        first = min((s for s in short if longest[s] == top),
                    key=lambda s: (rounds[s], s))
        picked, budget = [first], self.traffic["check_rounds"] - rounds[first]
        order = np.random.default_rng([self.seed, 2]).permutation(
            sorted(short))
        for s in order:
            if s != first and rounds[s] <= budget:
                picked.append(str(s))
                budget -= rounds[s]
        out = [(s, False) for s in picked]
        hot = sorted((s for s in rounds if s not in short),
                     key=lambda s: (-rounds[s], s))
        budget = self.traffic["check_long_rounds"]
        for i, s in enumerate(hot):
            if i and rounds[s] > budget:
                break
            out.append((s, True))
            budget -= rounds[s]
        return out

    def release(self) -> None:
        """Keep the sampled sessions' final state on the host, then free
        the service before the reference runs."""
        self._picked = self._pick()
        self._held = {}
        for s, _ in self._picked:
            carry = self.svc.sessions[s]
            self._held[s] = {
                "params": jax.tree.map(lambda x: np.asarray(x[0]),
                                       carry.params),
                "queue": np.asarray(carry.sched.queue[0])}
        self.svc = None

    def check(self, control: bool = False) -> Dict[str, float]:
        """The sampled sessions against the float32 reference; with
        `control` the bfloat16 reference stands in the program's place."""
        cfg = self.cfg
        S, bs = cfg["n_sov"], cfg["batch_size"]
        n_clients = int(self.shards.n_samples.shape[0])
        unanswered = sum(1 for r in self.results if "resp" not in r)
        prog, ref = [], []
        for s, _ in self._picked:
            hist = [(q, r) for q, r in zip(self.reqs, self.results)
                    if q["session"] == s]
            draws = [D.request_draws(q["seed"], q["n_rounds"], n_clients, S,
                                     bs) for q, _ in hist]
            keys = np.concatenate([np.asarray(jax.random.key_data(d[0]))
                                   for d in draws])
            keys = jax.random.wrap_key_data(keys)
            sel = np.concatenate([np.asarray(d[1]) for d in draws])[:, None]
            mb_u = np.concatenate([np.asarray(d[2]) for d in draws])[:, None]
            key = jax.random.fold_in(jax.random.key(self.svc_seed),
                                     zlib.crc32(s.encode()))
            args = (key, keys, self.p0, self.shards.data,
                    self.shards.n_samples, sel, mb_u, len(sel), 1, False)
            r32 = self._replay(s, args, jax.numpy.float32)
            ref.append(r32)
            if control:
                prog.append(self._replay(s, args, jax.numpy.bfloat16))
            elif any("resp" not in r for _, r in hist):
                prog.append(dict(r32, success=~r32["success"],
                                 loss=np.full_like(r32["loss"], np.inf)))
            else:
                prog.append({
                    "success": np.concatenate([r["resp"].success
                                               for _, r in hist]),
                    "loss": np.concatenate([r["resp"].loss
                                            for _, r in hist]),
                    **self._held[s]})
        out = compare_serve(prog, ref, self.p0,
                            [lg for _, lg in self._picked])
        out["unanswered"] = float(0 if control else unanswered)
        return out

    def _replay(self, session: str, args, dtype) -> Dict:
        cache = self.__dict__.setdefault("_replays", {})
        refs = self.__dict__.setdefault("_refs", {})
        if (session, dtype) not in cache:
            if dtype not in refs:
                refs[dtype] = Reference(self.cfg, self.model.reference_loss,
                                        dtype)
            res = refs[dtype].run(*args)
            cache[(session, dtype)] = {
                "success": res["success"][:, 0], "loss": res["loss"][:, 0],
                "params": res["params"][-1][0],
                "queue": res["fleet"]["queue"][0]}
        return cache[(session, dtype)]


def build(cfg: Dict, model, traffic: Dict, seed: int,
          fault: str = "") -> Cell:
    return Cell(cfg, model, traffic, seed, fault)
