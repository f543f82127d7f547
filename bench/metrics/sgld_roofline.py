"""SGLD kernel: its share of the roofline, in %. The least time the chip
needs for every SGLD potential kernel call in the traced window (the larger
of the copied cost model's FLOPs over the bf16 peak and its bytes over the
HBM bandwidth, at the real arm count) over the time those calls took on
the device. The calls are the ``tpu_custom_call`` operations that take the
minibatch rows of every chain (``f32[C,m,d]``); the backward one writes a
``(C, tiles, 1, d)`` gradient, the forward one a ``(C, tiles, 1, 128)``
partial. Which bound applies stands in the run's standard error
(``sgld_roofline_bound``)."""
import re

from harness import costs


def read(run):
    s = run.sess
    fg = s.fgts_cfg
    c, m, d, k = fg.n_chains, fg.sgld_minibatch, s.d, len(s.pool.names)
    rows = f"f32[{c},{m},{d}]"
    bwd = re.compile(rf"= f32\[{c},\d+,1,{d}\]")
    least = took = 0.0
    for e in run.trace.op_events(lambda n: "tpu_custom_call" in n
                                 and rows in n):
        kind = "bwd" if bwd.search(e.name.split(" custom-call(")[0]) \
            else "fwd"
        flops, bytes_ = costs.sgld_kernel_cost(kind, m, k, d, c)
        least += costs.roofline_seconds(flops, bytes_, run.peaks)[0]
        took += e.dur_ns * 1e-9
    return 100.0 * least / took if took else None
