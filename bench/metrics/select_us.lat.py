"""Selection: device us per ``router.route`` call in the operations named
``dueling_select`` (the fused scoring and pair-argmax ``pallas_call``)."""
from harness import program_trace as pt


def read(run):
    n = len(pt.spans(run, "route"))
    s = pt.kernel_s(run, "dueling_select")
    return s / n * 1e6 if n and s else None
