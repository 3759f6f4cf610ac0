"""Model FLOP utilization of the traced window: the model's forward and
backward operations for every client minibatch the window trained, over
the window's time, the chips and the chip's bf16 peak."""
from chipbench.flops import cell_round_flops


def read(run):
    done = cell_round_flops(run.cfg, run.model) * run.window["cell_rounds"]
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * done / run.trace["window_s"] / peak
