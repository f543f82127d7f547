"""Feedback fold: device-busy ms inside the ``bench.fold`` spans, per
``feedback_stream`` call."""


def read(run):
    calls = run.rec["fold_calls"]
    return run.trace.span_device_s("fold") / calls * 1e3 if calls else None
