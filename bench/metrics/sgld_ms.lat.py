"""SGLD kernel: device ms per ``router.route`` call in the operations
named ``sgld_potential_*`` (the fused potential's forward and backward
``pallas_call``s)."""
from harness import program_trace as pt


def read(run):
    n = len(pt.spans(run, "route"))
    s = pt.kernel_s(run, "sgld_potential_")
    return s / n * 1e3 if n and s else None
