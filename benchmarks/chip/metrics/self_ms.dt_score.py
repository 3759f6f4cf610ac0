"""Device self time of the DT candidate scoring, its Pallas kernel
included (the program's `dt_score` scope: core/veds._dt_candidates), in
the traced window, per cell-round. None where the program names no
layers."""
from chipbench.scopes import self_ms


def read(run):
    return self_ms(run, "dt_score")
