"""Roofline analysis from the dry-run's compiled artifacts (deliverable g).

Reads results/dryrun.json (written by repro.launch.dryrun) and derives, per
(arch x shape x mesh):

    compute term    = FLOPs_per_device / peak_FLOPs      [s]
    memory term     = bytes_per_device / HBM_bw          [s]
    collective term = coll_bytes_per_device / ICI_bw     [s]

FLOPs/bytes come from compiled.cost_analysis(); since XLA counts a lax.scan
body once, totals are reconstructed with the per-unit probe:
    total = full_program + (n_units - 1) * unit_probe  (+ encoder analog).

Collective bytes are summed per-device output-operand sizes of all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute ops in the
post-SPMD HLO, with ring-traffic multipliers {ar: 2x, others: 1x} — a
first-order ICI model (documented in EXPERIMENTS.md).

Peaks are the target chip's row of ``repro.launch.peaks`` (v5e: 197 bf16
TFLOP/s, 819 GB/s HBM, 1,600 Gbit/s ICI).
"""
from __future__ import annotations

import json
import os
import time

from repro.configs import ARCHS, SHAPES, get_arch
from repro.launch.peaks import PEAKS, TARGET_KIND

from .common import RESULTS, emit

AR_MULT = 2.0
DRYRUN_JSON = os.path.join(RESULTS, "dryrun.json")


def _coll_weighted(coll: dict) -> float:
    b = coll["bytes"]
    return (AR_MULT * b.get("all-reduce", 0)
            + b.get("all-gather", 0) + b.get("reduce-scatter", 0)
            + b.get("all-to-all", 0) + b.get("collective-permute", 0))


def reconstruct_totals(rec: dict) -> dict:
    """Scan-body correction via the unit probes."""
    n_units = rec.get("n_units", 1)
    enc_units = rec.get("enc_n_units", 0)
    flops = rec["cost"].get("flops", 0.0)
    byts = rec["cost"].get("bytes accessed", 0.0)
    coll = _coll_weighted(rec["collectives"])
    probe = rec.get("probe", {})
    if "pattern" in probe:
        p = probe["pattern"]
        flops += (n_units - 1) * p["cost"].get("flops", 0.0)
        byts += (n_units - 1) * p["cost"].get("bytes accessed", 0.0)
        coll += (n_units - 1) * _coll_weighted(p["collectives"])
    if "enc" in probe and enc_units > 1:
        p = probe["enc"]
        flops += (enc_units - 1) * p["cost"].get("flops", 0.0)
        byts += (enc_units - 1) * p["cost"].get("bytes accessed", 0.0)
        coll += (enc_units - 1) * _coll_weighted(p["collectives"])
    return {"flops": flops, "bytes": byts, "coll": coll}


def model_flops(arch: str, shape_name: str) -> float:
    """6*N*D (train) / 2*N*D (prefill) / 2*N_active*B (decode), MoE-active."""
    cfg = get_arch(arch, shape_name)
    shape = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch          # one token


def analyse(rec: dict) -> dict:
    tot = reconstruct_totals(rec)
    chips = rec["n_chips"]
    peaks = PEAKS[TARGET_KIND]
    t_c = tot["flops"] / peaks.flops_bf16
    t_m = tot["bytes"] / peaks.hbm_bytes_per_s
    t_n = tot["coll"] / peaks.ici_bytes_per_s
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_n),
              key=lambda kv: kv[1])
    mf = model_flops(rec["arch"], rec["shape"])
    useful = mf / max(tot["flops"] * chips, 1.0)
    hints = {
        "compute": "shard more work per chip is already ideal; cut waste "
                   "(remat/dense-MoE dispatch) or grow the mesh",
        "memory": "fuse/blockwise the dominant elementwise chains and keep "
                  "params/caches in bf16; raise arithmetic intensity via "
                  "larger per-chip tiles",
        "collective": "reshard to cut resharding (same-axis activations "
                      "through the stack), overlap collectives with compute, "
                      "or swap all-reduce for reduce-scatter+all-gather",
    }
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
        "dominant": dom[0], "dominant_s": dom[1],
        "model_flops": mf, "hlo_flops_global": tot["flops"] * chips,
        "useful_ratio": useful,
        "hint": hints[dom[0]],
        "mem_per_dev_bytes": (rec["memory"].get("argument_bytes", 0)
                              + rec["memory"].get("temp_bytes", 0)),
    }


def markdown_table(rows: list[dict]) -> str:
    out = ["| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | useful FLOP ratio | bytes/dev |",
           "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} "
            f"| {r['collective_s']:.3e} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.2f} "
            f"| {r['mem_per_dev_bytes']:.2e} |")
    return "\n".join(out)


def run(path: str = DRYRUN_JSON):
    t0 = time.time()
    if not os.path.exists(path):
        print(f"[roofline] {path} missing — run "
              f"`python -m repro.launch.dryrun --all --out {path}` first")
        return [emit("roofline/missing", 0.0, "dryrun.json not found")]
    with open(path) as f:
        data = json.load(f)
    rows = [analyse(r) for r in data["results"]]
    rows.sort(key=lambda r: (r["arch"], r["shape"], r["mesh"]))
    print(markdown_table(rows))
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "roofline.json"), "w") as f:
        json.dump(rows, f, indent=1)
    # summary rows
    out = []
    n_dom = {}
    for r in rows:
        n_dom[r["dominant"]] = n_dom.get(r["dominant"], 0) + 1
    out.append(emit("roofline/combos_analysed", time.time() - t0, len(rows)))
    out.append(emit("roofline/dominant_split", 0.0,
                    ";".join(f"{k}={v}" for k, v in sorted(n_dom.items()))))
    skips = data.get("skips", [])
    out.append(emit("roofline/skips_noted", 0.0, len(skips)))
    return out


if __name__ == "__main__":
    run()
