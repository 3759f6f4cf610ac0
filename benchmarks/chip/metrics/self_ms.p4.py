"""Device self time of the P4 interior-point solves (the program's `p4`
scope: core/solver.solve_p4) in the traced window, per cell-round. None
where the program names no layers."""
from chipbench.scopes import self_ms


def read(run):
    return self_ms(run, "p4")
