#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` -> ``configs/`` + ``traffic/``, and the
encoder module its configuration names, ``harness/encoders/``) is served
open loop for ``--seconds`` after set-up, and what the timed path produced
is compared with the plain references (``harness/check.py``). With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of the window gives its per-layer metrics
(``metrics/<name>.py``) and a breakdown. The last line of standard output
is one JSON object; the numbers compared stand beside their limits as the
last lines of standard error. A host with no TPU, or with fewer chips than
the cell asks for, exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TRACE_DIR = ROOT / ".bench_trace"


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    ticks = os.sysconf("SC_CLK_TCK")
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19]) / ticks
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start


class NoChip(RuntimeError):
    pass


def find_chips(chips: int):
    """The TPU devices the cell asks for; NoChip where JAX finds none."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0].platform}"
                     f" ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


class Run:
    """What one run measured, as the per-layer readers see it."""

    def __init__(self, sess, rec, compiles, trace, peaks):
        self.sess, self.rec, self.compiles = sess, rec, compiles
        self.trace, self.peaks = trace, peaks


def end_to_end(rec: dict, setup_s: float) -> dict:
    import numpy as np
    lat = rec["latency_s"]
    out = {"setup_s": (setup_s, "s"),
           "routed_per_s": (rec["routed"] / rec["window_s"], "req/s")}
    if lat.size:
        out["route_p50_ms"] = (float(np.percentile(lat, 50)) * 1e3, "ms")
        out["route_p95_ms"] = (float(np.percentile(lat, 95)) * 1e3, "ms")
    return out


def serve_cell(cell, seed: int, seconds: float, traced: bool):
    """Set up, warm, measure and compare one run; returns the result
    dict (without printing)."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # every program, however quick to compile, goes to the cache, so that
    # only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = find_chips(cell.chips)
    from harness import check, peaks as peaks_mod
    from harness.serve import Session, Spans
    from harness.trace import Reduction

    spans = Spans(traced)
    sess = Session(cell.config, cell.mix, seed, seconds, spans)
    sess.warm_up()
    setup_s = process_age_s()
    spans.reset()
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    rec = sess.window(seconds)
    if traced:
        jax.profiler.stop_trace()
    compiles = dict(spans.compiles)
    dev = devices[0]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    correct, shown, failed = check.judge(sess, seed, cell.config["check"])
    due = int((sess.reqs.arrival <= rec["window_s"]).sum())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    result = {"correct": correct, "attempted": rec["routed"],
              "failed": failed}
    if traced:
        red = Reduction.from_dir(TRACE_DIR)
        run = Run(sess, rec, compiles, red,
                  peaks_mod.peaks_for(dev.device_kind))
        from harness.cell import reader
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = red.breakdown()
    else:
        e2e = end_to_end(rec, setup_s)
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]][0],
                                         "unit": e2e[m["name"]][1]}
                             for m in cell.end_to_end if m["name"] in e2e}
        result["device"] = device
    result["check"] = shown
    info = {"setup_s": setup_s, "window_s": rec["window_s"],
            "routed": rec["routed"], "batches": len(rec["batches"]),
            "mean_batch": (rec["routed"] / max(len(rec["batches"]), 1)),
            "backlog": due - rec["routed"],
            "latency_ms": {k: v[0] for k, v in end_to_end(rec, 0).items()
                           if k.startswith("route_p")},
            "votes_folded": rec["votes_folded"],
            "compiles_in_window": compiles,
            "compile_s_in_window": dict(spans.compile_s),
            "host_s_per_span": dict(spans.seconds)}
    if traced:
        from harness import costs
        fg = sess.fgts_cfg
        f, b = costs.sgld_kernel_cost("bwd", fg.sgld_minibatch,
                                      len(sess.pool.names), sess.d,
                                      fg.n_chains)
        info["sgld_roofline_bound"] = costs.roofline_seconds(
            f, b, peaks_mod.peaks_for(dev.device_kind))[1]
    return result, info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="offer the cell's mix at this rate (for the knee "
                         "sweep, bench/sweep.py; not for the benchmark)")
    args = ap.parse_args(argv)
    from harness.cell import load
    try:
        cell = load(args.workload)
        if args.rate is not None:
            cell.mix = dict(cell.mix, rate=args.rate)
        result, info = serve_cell(cell, args.seed, args.seconds,
                                  bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; the benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    print(f"bench: {json.dumps(info)}", file=sys.stderr)
    for k, v in result["check"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
