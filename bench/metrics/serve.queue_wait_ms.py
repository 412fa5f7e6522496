"""serve.queue_wait_ms: the mean wait of a request in the server's admission
queue, from its admission to a worker's dequeue, as
``repro_serve_queue_wait_seconds``' sum over its count grew in the window.
Reads nothing where the program keeps no such histogram."""


def read(run):
    waited = run.counter(run.counters, "repro_serve_queue_wait_seconds_count")
    if not waited:
        return None
    return 1e3 * run.counter(run.counters,
                             "repro_serve_queue_wait_seconds_sum") / waited
