"""Mean number of requests packed into one dispatch of the service, from
the service's own occupancy counter (`ServeMetrics.occupancy`). None
when nothing was dispatched."""


def read(run):
    return run.window.get("occupancy_mean")
