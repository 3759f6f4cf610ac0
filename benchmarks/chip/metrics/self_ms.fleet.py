"""Device self time of the mobility and channel draws (the program's
`fleet` scope: core/scenario.fleet_round) in the traced window, per
cell-round. None where the program names no layers."""
from chipbench.scopes import self_ms


def read(run):
    return self_ms(run, "fleet")
