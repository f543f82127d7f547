"""SGLD posterior-update benchmark: fused kernel vs the XLA paths, with
roofline terms.

Times one jitted, chain-vmapped ``fgts.sgld_sample`` per (K, m, d, chains)
point and reports microseconds *per SGLD step* for three backends:

    :kernel    backend="fused"    — the Pallas kernel (compiled Mosaic on
               accelerators, its interpret lowering on CPU CI)
    :xla       backend="xla"      — the kernel's pure-XLA lowering, forced.
               On an accelerator this is the Mosaic-vs-XLA gap; on host
               (interpret mode) it is bit-identical to :kernel, so the
               bench times the shared program once and reports it for both
               rows (marked ``shared_with_kernel=1``) instead of measuring
               allocator noise between two copies of the same code.
    :autodiff  backend="autodiff" — the legacy path: jax.grad through
               likelihood_batch (the pre-kernel implementation, also the
               numerics oracle: the kernel row carries max_err against it)
    :auto      backend="auto"     — whatever the trace-time heuristic
               (``resolve_sgld_backend``) picks for this point's chain
               count; reported as the resolved backend's time (same
               compiled program — timing it twice would measure noise).
               The BENCH_6 regression this guards: on host, multi-chain
               sweeps vmap the XLA scan and its per-chain control flow
               dominates — auto now resolves chains>1 to "autodiff"
               (one traced graph, vmap-friendly) and only single-chain
               host points to "xla".

Derived fields per row: an analytic per-step cost model and where it lands
on the roofline. Per gradient evaluation the kernel runs 5 (m, K)x(K, d)-
class contractions (forward: score numerator + denominator; backward:
score recompute + the weighted feature sum), so

    flops ≈ 10·m·K·d·chains
    bytes ≈ 4·chains·(2·m·d + 4·K·d + 2·d)      (HBM model: x and the arm
            table stream once per pass; the (m, K) score/weight tiles live
            and die in VMEM — that is the point of the fusion)
    ai     = flops / bytes
    roofline_us = max(flops / peak_flops, bytes / peak_hbm) · 1e6

with the running chip's peaks from ``repro.launch.peaks``. CPU rows carry
no roofline term (the host has no entry in that table).

A full run also merges an ``"sgld"`` record into ``BENCH_7.json`` (rows +
kernel-vs-xla / kernel-vs-autodiff / auto-vs-autodiff medians); ``--smoke``
runs a two-point subset for the CI interpret lane and skips the JSON
artifact.

    PYTHONPATH=src python -m benchmarks.bench_sgld [--smoke] [--out F.json]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fgts
from repro.kernels.dueling_score import default_interpret
from repro.kernels.sgld_update import MAX_K_FUSED, resolve_sgld_backend
from repro.launch.peaks import peaks_for

from .common import emit, merge_bench_json

STEPS = 2                      # SGLD steps per timed sample call
BACKENDS = ("kernel", "xla", "autodiff")
_CFG_BACKEND = {"kernel": "fused", "xla": "xla", "autodiff": "autodiff"}

SWEEP = [(k, m, d, c)
         for k in (64, 256, 1024)
         for m in (128, 512, 1024)
         for d in (256, 768)
         for c in (1, 8)]
SMOKE = [(64, 128, 256, 1), (256, 128, 256, 8)]


def _cost_model(k, m, d, c, peaks):
    """flops, bytes, intensity and the roofline bound in us (None when
    ``peaks`` is None: the host CPU has no roofline)."""
    flops = 10.0 * m * k * d * c
    bytes_ = 4.0 * c * (2.0 * m * d + 4.0 * k * d + 2.0 * d)
    ai = flops / bytes_
    roofline_us = None if peaks is None else max(
        flops / peaks.flops_bf16, bytes_ / peaks.hbm_bytes_per_s) * 1e6
    return flops, bytes_, ai, roofline_us


def _point(k, m, d, c, seed=0):
    """Replay state + sampler per backend for one sweep point. The whole
    replay is the minibatch (sgld_minibatch=m): every step pays the full
    (m, K, d) contraction the cost model counts."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (m, d))
    a1 = jax.random.randint(ks[1], (m,), 0, k)
    a2 = (a1 + 1 + jax.random.randint(ks[2], (m,), 0, k - 1)) % k
    y = jnp.where(jax.random.bernoulli(ks[3], 0.5, (m,)), 1.0, -1.0)
    a_emb = jax.random.normal(ks[4], (k, d))
    theta = jax.random.normal(ks[5], (d,)) * 0.1
    st = fgts.FGTSState(x=x, a1=a1, a2=a2, y=y,
                        t=jnp.asarray(m, jnp.int32),
                        theta1=theta, theta2=theta)
    keys = jax.random.split(jax.random.fold_in(ks[5], 1), c)

    def sampler(backend):
        cfg = fgts.FGTSConfig(n_models=k, dim=d, horizon=m,
                              sgld_steps=STEPS, sgld_minibatch=m,
                              n_chains=c,
                              sgld_backend=_CFG_BACKEND[backend])
        return jax.jit(lambda kk, s, th: jax.vmap(
            lambda ki: fgts.sgld_sample(ki, th, s, a_emb, 1, cfg))(kk))

    return sampler, keys, st, theta


def _time_interleaved(fns, *args, n=5):
    """Min-of-n wall clock per labelled fn, reps interleaved round-robin so
    slow machine-level drift (shared CPU, allocator state) hits every
    backend equally instead of biasing whichever ran last."""
    for fn in fns.values():                    # warmup / compile
        jax.block_until_ready(fn(*args))
    best = {name: float("inf") for name in fns}
    for _ in range(n):
        for name, fn in fns.items():
            t0 = time.time()
            jax.block_until_ready(fn(*args))
            best[name] = min(best[name], time.time() - t0)
    return best


def run(smoke: bool = False, out: str | None = "BENCH_7.json"):
    rows, records = [], []
    peaks = peaks_for(jax.devices()[0])
    # label the auto heuristic's pick in this bench's vocabulary
    resolved_label = {"fused": "kernel", "xla": "xla",
                      "autodiff": "autodiff"}
    for k, m, d, c in (SMOKE if smoke else SWEEP):
        sampler, keys, st, theta = _point(k, m, d, c)
        flops, bytes_, ai, roof = _cost_model(k, m, d, c, peaks)
        # Where "fused" resolves to the interpret lowering (host backends,
        # or K above MAX_K_FUSED), :kernel and :xla are bit-identical
        # programs — time once and report the shared number rather than
        # measuring allocator noise between two copies of the same code.
        same_program = default_interpret() or k > MAX_K_FUSED
        fns = {backend: sampler(backend) for backend in BACKENDS
               if not (same_program and backend == "xla")}
        best = _time_interleaved(fns, keys, st, theta)
        if same_program:
            best["xla"] = best["kernel"]
        secs = {b: best[b] / STEPS for b in BACKENDS}
        auto_to = resolved_label[resolve_sgld_backend("auto", c)]
        secs["auto"] = secs[auto_to]        # same compiled program
        samples = {b: fn(keys, st, theta) for b, fn in fns.items()}
        err = float(jnp.max(jnp.abs(samples["kernel"]
                                    - samples["autodiff"])))
        base = f"sgld/K{k}_m{m}_d{d}_c{c}"
        model = f"flops={flops:.3e};bytes={bytes_:.3e};ai={ai:.1f}"
        if roof is not None:
            model += f";roofline_us={roof:.2f}"
        rows.append(emit(f"{base}:kernel", secs["kernel"],
                         f"{model};max_err={err:.2e}"))
        xla_model = model + (";shared_with_kernel=1" if same_program else "")
        rows.append(emit(f"{base}:xla", secs["xla"], xla_model))
        rows.append(emit(f"{base}:autodiff", secs["autodiff"], model))
        rows.append(emit(f"{base}:auto", secs["auto"],
                         f"{model};resolves_to={auto_to}"))
        records.append(dict(K=k, m=m, d=d, chains=c,
                            us_per_step={b: secs[b] * 1e6
                                         for b in (*BACKENDS, "auto")},
                            auto_resolves_to=auto_to,
                            xla_shared_with_kernel=same_program,
                            flops=flops, bytes=bytes_, ai=ai,
                            roofline_us=roof, max_err=err))
    if not smoke and out:
        vs_xla = [r["us_per_step"]["xla"] / r["us_per_step"]["kernel"]
                  for r in records]
        vs_ad = [r["us_per_step"]["autodiff"] / r["us_per_step"]["kernel"]
                 for r in records]
        # the BENCH_6 regression guard: auto must never lose to the legacy
        # autodiff path, in particular on the multi-chain host rows where
        # the old chains-blind heuristic picked the scan-heavy XLA lowering
        auto_vs_ad = [r["us_per_step"]["autodiff"] / r["us_per_step"]["auto"]
                      for r in records]
        auto_vs_ad_mc = [
            r["us_per_step"]["autodiff"] / r["us_per_step"]["auto"]
            for r in records if r["chains"] > 1]
        payload = dict(
            bench="sgld", backend=jax.default_backend(),
            steps=STEPS, rows=records,
            summary=dict(
                kernel_vs_xla_speedup_median=float(np.median(vs_xla)),
                kernel_vs_autodiff_speedup_median=float(np.median(vs_ad)),
                auto_vs_autodiff_speedup_median=float(np.median(auto_vs_ad)),
                auto_vs_autodiff_speedup_min_multichain=float(
                    min(auto_vs_ad_mc)) if auto_vs_ad_mc else None,
                max_err=max(r["max_err"] for r in records)))
        merge_bench_json(out, "sgld", payload, pr=7)
        print(f"# bench_sgld: wrote {out}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="two-point subset, no JSON artifact (CI lane)")
    ap.add_argument("--out", default="BENCH_7.json")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke, out=args.out)


if __name__ == "__main__":
    main()
