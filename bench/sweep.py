#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate the service sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates r1,r2,...

For each rate in turn, a benchmark run (``bench/run.py --rate``, its own
process, set up afresh) offers the cell's traffic mix at that rate for
``--seconds``; one JSON line per rate gives what was routed per second,
the median and 95th-percentile routing latency, the backlog left when the
window closed and the mean time a formed batch took. A rate is sustained
while what is routed keeps up with what is offered (within 5 %) and the
backlog stays near what one service time brings. Not part of the
benchmark's runs: the knee is written into the traffic file as ``knee``.
This process never touches JAX, so each run has the chip to itself.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys

RUN = pathlib.Path(__file__).resolve().parent / "run.py"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        p = subprocess.run(
            [sys.executable, str(RUN), "--workload", args.workload,
             "--seed", str(args.seed + i), "--seconds", str(args.seconds),
             "--rate", str(rate)], capture_output=True, text=True)
        out = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
        info = [ln for ln in p.stderr.splitlines()
                if ln.startswith("bench: ")]
        if p.returncode or not out or not info:
            print(p.stderr[-2000:], file=sys.stderr)
            return p.returncode or 1
        res, info = json.loads(out[-1]), json.loads(info[-1][7:])
        m = info["latency_ms"]
        print(json.dumps({
            "rate": rate, "routed_per_s": info["routed"] / info["window_s"],
            "p50_ms": m.get("route_p50_ms"), "p95_ms": m.get("route_p95_ms"),
            "backlog": info["backlog"], "batches": info["batches"],
            "mean_batch": info["mean_batch"],
            "s_per_batch": info["window_s"] / max(info["batches"], 1),
            "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
