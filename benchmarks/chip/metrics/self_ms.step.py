"""Device self time of the model step: each cell's local gradients,
FedAvg and its loss (the program's `step` scope in fl/engine), in the
traced window, per cell-round. None where the program names no
layers."""
from chipbench.scopes import self_ms


def read(run):
    return self_ms(run, "step")
