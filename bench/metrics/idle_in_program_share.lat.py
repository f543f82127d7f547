"""Device: the share of the traced window, in %, in which no operation ran
on the chip while the host was inside a ``router.*`` span (a call into
the program, compiles and dispatch included). At most the device idle
share; the rest of the idle time the host spent outside the program."""
from harness import program_trace as pt


def read(run):
    s = pt.idle_in_spans_s(run)
    return 100.0 * s / run.trace.window_s if s is not None else None
