"""90th percentile (nearest rank), from the gateway's own count, of the
wait from submit to first admission of the requests first admitted in the
window, with each request still waiting at the close entered at its wait
so far (``queue_wait_p90_ms`` of ``gateway.metrics()``)."""


def read(run):
    return run.counters.get("queue_wait_p90_ms")
