"""Query encoder: host ms a formed batch's ``router.embed`` span spent in
JAX tracing, lowering and compiling (a compile-cache fetch included), from
the span's own ``trace_s + lower_s + compile_s``, averaged over the
window's embed calls."""
from harness import program_trace as pt


def read(run):
    made = [s.stats["trace_s"] + s.stats["lower_s"] + s.stats["compile_s"]
            for s in pt.spans(run, "embed") if "compile_s" in s.stats]
    return sum(made) / len(made) * 1e3 if made else None
