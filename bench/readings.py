#!/usr/bin/env python3
"""Read the numbers that decide ``correct``, for the program and for the
control, on many seeds in one process: the readings the limits in a
configuration's ``check`` are set from.

    python3 bench/readings.py --workload <cell> --seconds <s> --seeds a,b,...

For each seed the cell is set up from that seed and served for
``--seconds``; then the program's numbers are read, and the control's: the
reference put in the program's place at the next precision below the
configuration's (int8 for the encoder it states at one bf16 pass, three
bf16 passes for the kernels it states at "highest"), and, for scale, one
bf16 pass for the kernels. Each set of numbers is judged against the
configuration's limits (``check.verdict``), so the line shows the program
correct and the control not. One JSON line per seed. Not part of the
benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from harness.cell import load
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load(args.workload)
    bench_run.find_chips(cell.chips)
    from harness import check
    from harness.serve import Session, Spans
    spans = Spans(False)
    for seed in (int(s) for s in args.seeds.split(",")):
        sess = Session(cell.config, cell.mix, seed, args.seconds, spans)
        sess.warm_up()
        spans.reset()
        rec = sess.window(args.seconds)
        final = check.program_final(sess)
        ev = check.Evidence(sess, seed)
        limits = cell.config["check"]
        out = {"seed": seed, "routed": rec["routed"],
               "batches": len(rec["batches"]),
               "host_s_per_span": dict(spans.seconds)}
        for name, nums in (
                ("program", check.numbers(ev, final)),
                ("control", check.control_numbers(ev, "int8", "high")),
                ("kernels_bf16", check.control_numbers(ev, "highest",
                                                       "bf16"))):
            out[name] = nums
            out[name + "_correct"] = check.verdict(nums, limits)[0]
        picked = check._sampled_calls(ev)
        reps = ev.replays({ev.calls[i].call for i in picked})
        out["sgld_program_exact"] = check._sgld_ratios(
            ev, picked, reps, lambda i, rep: ev.calls[i].theta)["exact"]
        out["sgld_control_exact"] = check._sgld_ratios(
            ev, picked, reps, lambda i, rep: check._sgld(
                ev, i, rep, "high", "float32")[0])["exact"]
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
