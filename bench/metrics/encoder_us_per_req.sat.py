"""Query encoder: device-busy us inside the ``bench.embed`` spans, per
routed request."""


def read(run):
    n = run.rec["routed"]
    return run.trace.span_device_s("embed") / n * 1e6 if n else None
