"""serve.batch_fill: how full the worker's coalesced batches are, as the mean
``n`` of the ``serve.request_batch`` spans over the cell's client count."""


def read(run):
    ns = [s["args"]["n"] for s in run.spans if s["name"] == "serve.request_batch"]
    if not ns:
        return None
    return 100.0 * sum(ns) / len(ns) / run.mix["clients"]
