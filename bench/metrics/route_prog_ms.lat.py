"""Route program: device-busy ms inside the ``bench.route`` spans, per
formed batch."""


def read(run):
    n = run.trace.span_count("route")
    return run.trace.span_device_s("route") / n * 1e3 if n else None
