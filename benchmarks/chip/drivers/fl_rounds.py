"""Traffic driver: federated-learning rounds of B RSU cells.

The traffic file gives the cells B, each cell's clients and their
shards, whether the cells hand vehicles off to their neighbours, and
how many rounds one dispatch of the fused engine runs. Set-up builds
the weights and data from the seed with the configuration's model file
(`models/<model>.py`), compiles the segment program and drives it
through the first `checked_rounds` rounds through its own call (single
rounds made active by the segment's round mask). The same carry then
trains on, segment after segment, until the window's time is up.
Every round's draws depend on (seed, round) alone.
"""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import RolloutCarry

from chipbench import data as D
from chipbench.checks import compare_training
from chipbench.program import half_batch_loss, program_params
from chipbench.reference import Reference
from chipbench.session import span


class Cell:
    """One cell's program, state and checked rounds. `fault` plants a
    fault for the benchmark's own tests and calibration: "half_batch",
    "frozen" (the step returns its state unchanged), "flip" (one upload
    decision altered where it is produced) or "no_handoff" (the exchange
    of vehicles between cells left out)."""

    def __init__(self, cfg: Dict, model, traffic: Dict, seed: int,
                 fault: str = ""):
        self.cfg, self.model, self.traffic, self.fault = cfg, model, \
            traffic, fault
        self.B = int(traffic["cells"])
        self.L = int(traffic["segment_rounds"])
        self.R0 = int(traffic["checked_rounds"])
        self._ref = None
        key = D.root_key(seed)
        self.k_w, self.k_data, self.k_sched, self.k_draw = (
            jax.random.fold_in(key, i) for i in range(1, 5))

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import dataclasses

        from repro.core.streaming import StreamConfig
        from repro.fl.engine import ClientShards, fused_segment, init_carry
        cfg, tr, B, L = self.cfg, self.traffic, self.B, self.L
        m, model = cfg["model"], self.model
        with span("setup"):
            self.p0 = model.weights(self.k_w, m)
            data, n = model.shards(self.k_data, B * tr["clients_per_cell"],
                                   tr, m)
            self.shards = ClientShards(data=data, n_samples=n)
            sc, mob, ch, prm = program_params(cfg)
            scfg = StreamConfig(n_rounds=0, batch=B,
                                carry_queues=cfg["carry_queues"],
                                handoff=tr["handoff"])
            loss = model.program_loss(m)
            if self.fault == "half_batch":
                loss = half_batch_loss(loss)
            run_cfg = scfg if self.fault != "no_handoff" else \
                dataclasses.replace(scfg, handoff=False)
            self.seg = fused_segment(loss, cfg["scheduler"], sc, mob, ch,
                                     prm, run_cfg, cfg["lr"], 1, None, 1)
            carry = init_carry(self.k_sched, sc, mob, scfg, self.p0, ch=ch)
            self.steps = jnp.arange(L)
            self.ev = jnp.zeros((L,), bool)
            self.all_on = jnp.ones((L,), bool)
            # the checked rounds, one at a time through the segment's own
            # call: round r runs as the first active round of a segment
            # starting at r, and inactive rounds pass the carry through
            self.checked = {k: [] for k in ("success", "energy_sov",
                                            "energy_opv", "qs", "qu",
                                            "loss", "params")}
            r = 0
            while r < self.R0:
                n_on = 1 if r == 0 else self.R0 - r
                res = self._segment(carry, r, jnp.arange(L) < n_on)
                out = jax.device_get(res)
                for i in range(n_on):
                    o = out.outputs
                    for k, v in (("success", o.success),
                                 ("energy_sov", o.energy_sov),
                                 ("energy_opv", o.energy_opv),
                                 ("qs", o.carry.qs), ("qu", o.carry.qu),
                                 ("loss", out.loss)):
                        self.checked[k].append(np.asarray(v[i]))
                self.checked["params"].append(
                    [jax.tree.map(lambda x: x[b], out.params)
                     for b in range(B)])
                carry = RolloutCarry(sched=res.fleet, params=res.params,
                                     opt_state=res.opt_state)
                r += n_on
            self.checked["fleet"] = {
                "cell": np.asarray(res.fleet.cell_id),
                "covered": np.asarray(res.fleet.covered)}
            self.carry, self.r_next = carry, r

    def _segment(self, carry, r0: int, active):
        d = D.draws_of(self.k_draw, r0, self.L, self.traffic, self.cfg)
        return self.seg(carry, d[0], d[1], d[2], self.shards, self.steps,
                        active, self.ev)

    # ------------------------------------------------------------ window
    def window(self, seconds: float, traced: bool = False) -> Dict:
        """Train segment after segment until `seconds` have passed (a
        traced window: until `trace_segments` ran). Returns the counts
        and the window's time."""
        max_segments = self.traffic["trace_segments"] if traced else 0
        losses, n_seg, ends = [], 0, []
        t0 = time.perf_counter()
        while True:
            with span("generate"):
                d = D.draws_of(self.k_draw, self.r_next, self.L,
                               self.traffic, self.cfg)
            with span("dispatch"):
                res = self.seg(self.carry, d[0], d[1], d[2], self.shards,
                               self.steps, self.all_on, self.ev)
            with span("wait"):
                jax.block_until_ready(res)
            self.carry = RolloutCarry(sched=res.fleet, params=res.params,
                                      opt_state=res.opt_state)
            losses.append(res.loss)
            self.r_next += self.L
            n_seg += 1
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if elapsed >= seconds or n_seg == max_segments:
                break
        loss = np.concatenate([np.asarray(x) for x in losses])
        rounds = n_seg * self.L
        seg_s = np.diff([0.0] + ends)
        return {"seconds": elapsed, "rounds": rounds,
                "notes": [f"segment_s ({n_seg}): "
                          + " ".join(f"{x:.4f}" for x in seg_s)],
                "cell_rounds": rounds * self.B,
                "attempted": rounds * self.B,
                "failed": int((~np.isfinite(loss)).sum()),
                "metrics": {"cell_rounds_per_s": rounds * self.B / elapsed}}

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.carry = self.seg = None

    # ------------------------------------------------------------- check
    def check(self, control: bool = False) -> Dict[str, float]:
        """The checked rounds against the float32 reference. With
        `control` the bfloat16 reference stands in the program's place."""
        cfg, B, R0 = self.cfg, self.B, self.R0
        d = D.draws_of(self.k_draw, 0, R0, self.traffic, cfg)
        keys, sel, mb_u = d[0], np.asarray(d[1]), np.asarray(d[2])
        shards = self.shards.data
        args = (self.k_sched, keys, self.p0, shards,
                self.shards.n_samples, sel, mb_u, R0, B,
                self.traffic["handoff"])
        if self._ref is None:
            self._ref = Reference(cfg, self.model.reference_loss).run(*args)
            self._ref["params"] = [self._ref["params"][0],
                                   self._ref["params"][-1]]
        ref = self._ref
        if control:
            prog = Reference(cfg, self.model.reference_loss,
                             jnp.bfloat16).run(*args)
            prog["params"] = [prog["params"][0], prog["params"][-1]]
        else:
            prog = {k: (np.stack(v) if k not in ("params", "fleet") else v)
                    for k, v in self.checked.items()}
            prog["params"] = [prog["params"][0], prog["params"][-1]]
            if self.fault == "frozen":
                prog["params"] = [[self.p0] * B] * 2
            if self.fault == "flip":
                prog["success"] = prog["success"].copy()
                prog["success"][0, 0, 0] ^= True
        return compare_training(prog, ref, self.p0, cfg["lr"],
                                self.traffic["handoff"])


def build(cfg: Dict, model, traffic: Dict, seed: int,
          fault: str = "") -> Cell:
    return Cell(cfg, model, traffic, seed, fault)

