"""Query encoder: device-busy ms inside the ``bench.embed`` spans, per
formed batch."""


def read(run):
    n = run.trace.span_count("embed")
    return run.trace.span_device_s("embed") / n * 1e3 if n else None
