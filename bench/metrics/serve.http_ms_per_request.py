"""serve.http_ms_per_request: host time of the HTTP front door per request
decoded: the ``serve.http_decode`` spans (body read, JSON, problem and
policy from their dicts) and the ``serve.http_encode`` spans (the artifact's
JSON and the write back), over the requests decoded."""

HTTP = ("serve.http_decode", "serve.http_encode")


def read(run):
    decoded = sum(s["name"] == "serve.http_decode" for s in run.spans)
    if not decoded:
        return None
    return sum(s["dur_us"] for s in run.spans
               if s["name"] in HTTP) / 1e3 / decoded
