"""Host time on a service dispatch's critical path: the mean, over the
dispatches that start in the traced window, of the time inside the
dispatch in which no leaf operation ran on the device. A dispatch is
the harness's `dispatch` span, which wraps one `run_batch` call and
nothing else (the program's `repro.serve.run_batch` span inside it
starts and ends within microseconds of it). None without dispatches."""
from chipbench.scopes import host_ms_per_dispatch


def read(run):
    return host_ms_per_dispatch(run)
