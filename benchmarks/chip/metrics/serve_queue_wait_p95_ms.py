"""95th percentile of the time from a request's due time to the start of
the dispatch that served it (the generator's lateness plus the wait in
the service's queue and batching window), from the harness's own
timestamps. None when no request was answered."""


def read(run):
    return run.window.get("queue_wait_p95_ms")
