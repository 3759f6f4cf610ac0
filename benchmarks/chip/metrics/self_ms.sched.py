"""Device self time of the scheduler's round outside its named
sub-layers: the VEDS or MADCA selection, the slot scan and the queue
gathers and scatters (the program's `sched` scope:
core/streaming.sched_round_step), in the traced window, per cell-round.
None where the program names no layers."""
from chipbench.scopes import self_ms


def read(run):
    return self_ms(run, "sched")
