"""Share of the traced window in which no leaf operation ran on the
device (averaged over the chips), in a serving cell: the gaps between a
loop's operations count as idle."""


def read(run):
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
