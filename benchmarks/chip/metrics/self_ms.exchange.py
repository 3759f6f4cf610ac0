"""Device self time of the handoff exchange between cells (the
program's `exchange` scope: core/scenario.exchange_fleet) in the traced
window, per cell-round. None where the program names no layers."""
from chipbench.scopes import self_ms


def read(run):
    return self_ms(run, "exchange")
