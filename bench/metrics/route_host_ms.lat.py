"""Route program: host ms of a ``router.route`` span (pad, mask, key, the
AOT call's dispatch, slice), averaged over the window's route calls."""
from harness import program_trace as pt


def read(run):
    sp = pt.spans(run, "route")
    return sum(s.end_ns - s.start_ns for s in sp) / len(sp) * 1e-6 \
        if sp else None
