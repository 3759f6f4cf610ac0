"""The program's objects for a configuration file, and the faults the
benchmark's own tests and calibration plant in the program."""
from __future__ import annotations

from typing import Dict

import jax


def program_params(cfg: Dict):
    """The program's (ScenarioParams, ManhattanParams, ChannelParams,
    VedsParams) for a configuration file."""
    from repro.channel.mobility import ManhattanParams
    from repro.channel.v2x import ChannelParams
    from repro.core.lyapunov import VedsParams
    from repro.core.scenario import ScenarioParams
    sc = ScenarioParams(n_sov=cfg["n_sov"], n_opv=cfg["n_opv"],
                        n_slots=cfg["n_slots"],
                        n_flop=cfg["n_flop_per_sample"],
                        batch_size=cfg["batch_size"],
                        clock_hz=cfg["clock_hz"], rho=cfg["rho"],
                        e_min=cfg["e_min_j"], e_max=cfg["e_max_j"])
    mob = ManhattanParams(**cfg["mobility"])
    ch = ChannelParams(**cfg["channel"])
    prm = VedsParams(alpha=cfg["alpha"], V=cfg["V"], Q=cfg["Q_bits"],
                     slot=cfg["slot_s"], ipm_iters=cfg["ipm_iters"],
                     ipm_mu=cfg["ipm_mu"],
                     ipm_warm_iters=cfg["ipm_warm_iters"])
    return sc, mob, ch, prm


def half_batch_loss(loss_fn):
    """A planted fault: the loss over the first half of each minibatch."""
    def loss(p, b):
        return loss_fn(p, jax.tree.map(lambda x: x[:x.shape[0] // 2], b))
    return loss
