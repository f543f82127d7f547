"""Benchmark orchestrator — one harness per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,roofline] [--fast]

Emits ``name,us_per_call,derived`` CSV rows (also written to
results/bench.csv). Mapping to the paper:

    fig1      bench_mmlu_naive      Fig. 1 / Fig. 4 (naive phi fails)
    tab1      bench_scores_table    Tab. 1 (scores i/ii/iii)
    fig2      bench_routerbench     Fig. 2a/2b + Fig. 6 (RouterBench)
    fig2cd    bench_generalization  Fig. 2c/2d + Fig. 7 (unseen benchmark)
    fig3      bench_mixinstruct     Fig. 3 + Fig. 8 (MixInstruct)
    b3        bench_baselines       App. B.3 (MixLLM) + ablations
    delayed   bench_delayed         regret vs feedback delay (async, beyond
                                    the paper's synchronous protocol)
    sharded   bench_sharded_serving mesh-sharded serving queries/sec vs
                                    devices vs batch (+ dispatch/compute
                                    split)
    streaming bench_streaming       event-time streaming serving: QPS +
                                    p50/p99 latency vs devices x bucket
                                    policy x arrival process; AOT+donation
                                    vs lazy jit
    dynamic_pool bench_dynamic_pool regret recovery after a mid-stream
                                    model arrival (warm vs cold hot-add)
    autopilot bench_autopilot       closed-loop pool management: dominance
                                    auto-retirement + cost governor vs
                                    static pool vs manual schedule
    kernels   bench_kernels         Pallas-vs-oracle numerics + timing
    sgld      bench_sgld            fused SGLD posterior-update kernel vs
                                    the XLA paths (roofline-backed)
    pareto    bench_pareto          one pref-conditioned posterior vs
                                    per-tilt retrained FGTS (regret-vs-cost
                                    front + zero-retrace contract)
    refresh   bench_refresh         online representation refresh: logged
                                    duels -> IPW-calibrated CCFT retrain ->
                                    retrace-free table swap, vs frozen /
                                    oracle tables under drift
    roofline  roofline              EXPERIMENTS.md §Roofline source

Benches that emit paired ``<shape>:kernel`` / ``<shape>:xla`` rows get a
one-line kernel-vs-XLA speedup summary (median over shapes) after the run.
"""
from __future__ import annotations

import argparse
import os
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of bench names")
    ap.add_argument("--fast", action="store_true",
                    help="fewer seeds (REPRO_RUNS=2)")
    args = ap.parse_args()
    if args.fast:
        os.environ["REPRO_RUNS"] = "2"
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (bench_autopilot, bench_baselines, bench_delayed,
                   bench_dynamic_pool, bench_generalization, bench_kernels,
                   bench_mixinstruct, bench_mmlu_naive, bench_pareto,
                   bench_refresh, bench_routerbench, bench_scores_table,
                   bench_sgld, bench_sharded_serving, bench_streaming,
                   roofline)
    benches = {
        "tab1": bench_scores_table.run,
        "kernels": bench_kernels.run,
        "sgld": bench_sgld.run,
        "pareto": bench_pareto.run,
        "refresh": bench_refresh.run,
        "fig1": bench_mmlu_naive.run,
        "fig2": bench_routerbench.run,
        "fig2cd": bench_generalization.run,
        "fig3": bench_mixinstruct.run,
        "b3": bench_baselines.run,
        "delayed": bench_delayed.run,
        "sharded": bench_sharded_serving.run,
        "streaming": bench_streaming.run,
        "dynamic_pool": bench_dynamic_pool.run,
        "autopilot": bench_autopilot.run,
        "roofline": roofline.run,
    }
    wanted = (args.only.split(",") if args.only else list(benches))

    print("name,us_per_call,derived")
    all_rows, failures = [], []
    for name in wanted:
        t0 = time.time()
        try:
            rows = benches[name]()
            all_rows.extend(rows or [])
            print(f"# {name}: ok in {time.time()-t0:.1f}s")
        except Exception:  # noqa: BLE001
            failures.append(name)
            print(f"# {name}: FAILED\n{traceback.format_exc()}")

    from .common import RESULTS
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "bench.csv"), "w") as f:
        f.write("name,us_per_call,derived\n")
        f.write("\n".join(all_rows) + "\n")
    _speedup_summary(all_rows)
    if failures:
        raise SystemExit(f"failed benches: {failures}")


def _speedup_summary(all_rows: list) -> None:
    """One line per bench with paired <shape>:kernel / <shape>:xla rows:
    the median (and range of) kernel-vs-XLA per-shape speedup."""
    times: dict = {}
    for row in all_rows:
        name, us = row.split(",")[:2]
        base, _, variant = name.rpartition(":")
        if variant in ("kernel", "xla") and base:
            times.setdefault(base, {})[variant] = float(us)
    by_bench: dict = {}
    for base, t in times.items():
        if "kernel" in t and "xla" in t and t["kernel"] > 0:
            by_bench.setdefault(base.split("/")[0], []).append(
                t["xla"] / t["kernel"])
    for bench, ratios in sorted(by_bench.items()):
        ratios.sort()
        med = ratios[len(ratios) // 2]
        print(f"# speedup {bench}: kernel {med:.2f}x vs xla "
              f"(median of {len(ratios)} shapes, "
              f"min {ratios[0]:.2f}x, max {ratios[-1]:.2f}x)")


if __name__ == "__main__":
    main()
