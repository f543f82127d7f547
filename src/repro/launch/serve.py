"""Serving driver: routed inference over a pool of candidate models.

Runs the full routed-serving loop on CPU with *reduced* candidate models:
queries stream in, the RouterService picks two candidates per query, both
generate (greedy decode), preference feedback is synthesized from the pool's
latent skill profile, and the posterior adapts online.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --rounds 40 --batch 8
    PYTHONPATH=src python -m repro.launch.serve --mesh 4,2 --batch 8
    PYTHONPATH=src python -m repro.launch.serve --autopilot --budget 0.5 \
        --pool-schedule "+arctic-480b@5"
    PYTHONPATH=src python -m repro.launch.serve --refresh-every 128

``--mesh data,model`` serves through the mesh-sharded RouterService: act is
shard_map-partitioned over the batch, the pending ring and replay update
are batch-sharded jitted programs. On a CPU-only host the requested device
count is forced automatically (--xla_force_host_platform_device_count).
"""
from __future__ import annotations

# --mesh on a CPU-only host needs the device count forced BEFORE jax
# initializes; peek at argv ahead of the imports (no-op when XLA_FLAGS
# already forces a count, and harmless on real accelerator platforms).
import os as _os
import sys as _sys

def _mesh_devices_from_argv() -> int:
    val = None
    for i, arg in enumerate(_sys.argv):
        if arg == "--mesh" and i + 1 < len(_sys.argv):
            val = _sys.argv[i + 1]
        elif arg.startswith("--mesh="):
            val = arg.split("=", 1)[1]
    if val is None:
        return 0
    parts = val.split(",")
    if len(parts) != 2:        # main() rejects it with a usage error later
        return 0
    try:
        return int(parts[0]) * int(parts[1])
    except ValueError:
        return 0


# Only as the CLI entry point: importers of this module (e.g. for the
# POLICIES registry) must not have their process's device topology mutated
# by whatever happens to be in their argv.
if __name__ == "__main__":
    _n = _mesh_devices_from_argv()
    if _n > 1 and "host_platform_device_count" \
            not in _os.environ.get("XLA_FLAGS", ""):
        _os.environ["XLA_FLAGS"] = (
            _os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_n}")

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS
from repro.core import baselines, fgts
from repro.core import model_pool as mp
from repro.core.btl import sample_preference
from repro.core.policy import fgts_policy
from repro.data.pool import PoolEntry, build_entries, synthetic_pool
from repro.data.synth import CorpusConfig, make_split
from repro.encoder.model import EncoderConfig, init_encoder
from repro.launch import mesh as mesh_lib
from repro.models import lm
from repro.serving import stream
from repro.serving.router_service import RouterService, RouterServiceConfig

# Any RoutingPolicy can serve — the service just drives act/update. Every
# scoring policy honours the config's serve-time cost tilt.
from repro.core.policy import cost_tilt_vector


POLICIES = {
    # cfg.use_kernel arrives resolved from the service (False under a mesh,
    # where the Pallas call cannot be partitioned over the batch axes).
    # ``arms`` is the (K, d) embedding table for a static service, or a
    # core.model_pool.ModelPool when the service is dynamic (k_max set) —
    # every policy constructor takes either.
    "fgts": lambda arms, costs, cfg: fgts_policy(
        arms, cfg.fgts, costs=costs, cost_tilt=cfg.cost_tilt,
        use_kernel=cfg.use_kernel if cfg.use_kernel is not None else True),
    # dynamic pools get cost_tilt= (live pool costs, hot adds included)
    # instead of a construction-time tilt vector
    "eps_greedy": lambda arms, costs, cfg: baselines.eps_greedy_policy(
        arms, baselines.EpsGreedyConfig(n_models=cfg.fgts.n_models,
                                        dim=cfg.fgts.dim),
        tilt=None if isinstance(arms, mp.ModelPool)
        else cost_tilt_vector(costs, cfg.cost_tilt),
        cost_tilt=cfg.cost_tilt,
        use_kernel=cfg.use_kernel if cfg.use_kernel is not None else True),
    "linucb": lambda arms, costs, cfg: baselines.linucb_duel_policy(
        arms, baselines.LinUCBConfig(n_models=cfg.fgts.n_models,
                                     dim=cfg.fgts.dim),
        tilt=None if isinstance(arms, mp.ModelPool)
        else cost_tilt_vector(costs, cfg.cost_tilt),
        cost_tilt=cfg.cost_tilt),
    "uniform": lambda arms, costs, cfg: baselines.uniform_policy(
        arms if isinstance(arms, mp.ModelPool) else cfg.fgts.n_models),
}

# Reduced pool members used for CPU serving runs (arch ids from the assigned
# set; each entry's latent skill vector drives synthetic preferences).
DEFAULT_POOL = ["granite-3-2b", "qwen2-7b", "mamba2-1.3b",
                "recurrentgemma-9b", "gemma2-9b"]

# Canonical pool construction lives in repro.data.pool; kept under the old
# name for callers of the serve driver's helper.
build_pool = synthetic_pool


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--gen-tokens", type=int, default=8)
    ap.add_argument("--with-generation", action="store_true",
                    help="actually decode from the two routed models")
    ap.add_argument("--policy", choices=sorted(POLICIES), default="fgts",
                    help="RoutingPolicy serving the pool")
    ap.add_argument("--feedback-delay", type=int, default=0,
                    help="rounds between a duel being issued and its vote "
                         "arriving (0 = synchronous act->update ticks)")
    ap.add_argument("--feedback-expiry", type=int, default=None,
                    help="drop votes older than this many rounds")
    ap.add_argument("--stale-half-life", type=float, default=None,
                    help="age-discount half-life (rounds) for stale votes")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="serve mesh-sharded over a (data, model) debug mesh"
                         " — e.g. 4,2; --batch must divide the data size")
    ap.add_argument("--pool-schedule", default=None, metavar="EVENTS",
                    help="dynamic-pool membership events, comma-separated: "
                         "'+ARCH@R' hot-adds a CCFT-warm-started ARCH at "
                         "round R, '-K@R' retires slot K — e.g. "
                         "'+arctic-480b@5,-0@12'. Enables k_max = "
                         "len(pool) + #adds")
    ap.add_argument("--autopilot", action="store_true",
                    help="closed-loop pool management: posterior-dominance "
                         "auto-retirement, arrivals enter as quota-capped "
                         "A/B candidates, cost governor (see --budget); "
                         "implies a dynamic pool")
    ap.add_argument("--budget", type=float, default=None, metavar="COST",
                    help="autopilot cost governor target: mean realized "
                         "duel cost ($/1k tok) to hold via the lambda tilt")
    ap.add_argument("--autopilot-every", type=int, default=4,
                    help="rounds between autopilot control ticks")
    ap.add_argument("--refresh-every", type=int, default=0, metavar="DUELS",
                    help="online representation refresh: once this many new "
                         "duels are in the log, re-run CCFT against the "
                         "logged outcomes (inverse-propensity-calibrated) "
                         "and hot-swap the embedding table — retrace-free "
                         "(0 = off; implies a dynamic pool)")
    ap.add_argument("--refresh-naive", action="store_true",
                    help="refresh ablation: score logged duels without the "
                         "IPW correction for the router's selection bias")
    ap.add_argument("--arrival", default=None, metavar="SPEC",
                    help="serve an event-time arrival stream instead of "
                         "fixed synchronous rounds: 'poisson:RATE', "
                         "'bursty:RATE[,BURST]' or "
                         "'diurnal:RATE[,DEPTH[,PERIOD]]' (requests/sec). "
                         "Requests are cut into dynamic batches (see "
                         "--max-wait), padded onto the --buckets ladder and "
                         "served through the AOT streaming path; total "
                         "requests = --rounds * --batch")
    ap.add_argument("--buckets", default="8,16,32,64", metavar="B1,B2,...",
                    help="pow2 padding-bucket ladder for --arrival "
                         "streaming (one AOT-compiled program per bucket)")
    ap.add_argument("--max-wait", type=float,
                    default=stream.DEFAULT_MAX_WAIT, metavar="SECONDS",
                    help="longest a request may wait for batchmates before "
                         "its batch is cut (the latency/padding knob)")
    ap.add_argument("--pref-dist", default=None, metavar="SPEC",
                    help="per-request preference tilts: 'grid:V1,V2,...' "
                         "cycles the listed cost weights over batch rows, "
                         "'uniform:LO,HI' samples one per request per round. "
                         "Row i routes under the extra utility tilt "
                         "pref_i*cost_k — one shared posterior serves every "
                         "trade-off (needs a preference-aware policy; all "
                         "built-ins qualify when the pool is dynamic)")
    args = ap.parse_args()
    enable_compile_cache()

    pref_sampler = None
    if args.pref_dist:
        kind, _, body = args.pref_dist.partition(":")
        try:
            vals = [float(v) for v in body.split(",")] if body else []
        except ValueError:
            vals = None
        if kind == "grid" and vals:
            grid = jnp.asarray(vals, jnp.float32)

            def pref_sampler(k, r, b):
                return grid[(r * b + jnp.arange(b)) % grid.shape[0]]
        elif kind == "uniform" and vals is not None and len(vals) == 2:
            lo, hi = vals

            def pref_sampler(k, r, b):
                return jax.random.uniform(k, (b,), minval=lo, maxval=hi)
        else:
            raise SystemExit(
                f"--pref-dist {args.pref_dist!r} must be 'grid:V1,V2,...' "
                f"or 'uniform:LO,HI'")

    buckets = spec = None
    if args.arrival:
        for flag, bad in (("--pool-schedule", args.pool_schedule),
                          ("--autopilot", args.autopilot),
                          ("--with-generation", args.with_generation),
                          ("--feedback-delay", args.feedback_delay)):
            if bad:
                raise SystemExit(
                    f"--arrival streams the core routing loop; {flag} is a "
                    f"synchronous-rounds feature")
        try:
            spec = stream.parse_arrival(args.arrival)
            buckets = stream.validate_buckets(
                int(v) for v in args.buckets.split(","))
        except ValueError as e:
            raise SystemExit(f"[serve] {e}") from None

    events = []
    if args.pool_schedule:
        for tok in args.pool_schedule.split(","):
            body, _, rnd = tok.strip().rpartition("@")
            if body.startswith("+"):
                events.append(("add", body[1:], int(rnd)))
            elif body.startswith("-"):
                events.append(("retire", int(body[1:]), int(rnd)))
            else:
                raise SystemExit(f"--pool-schedule event {tok!r} must be "
                                 f"'+ARCH@ROUND' or '-SLOT@ROUND'")

    mesh = None
    if args.mesh:
        parts = args.mesh.split(",")
        try:
            data, model = (int(v) for v in parts)
        except ValueError:
            raise SystemExit(
                f"--mesh expects two comma-separated sizes DATA,MODEL "
                f"(e.g. 4,2), got {args.mesh!r}") from None
        if args.batch % data:
            raise SystemExit(f"--batch {args.batch} must divide over the "
                             f"mesh's data axis ({data})")
        mesh = mesh_lib.make_debug_mesh(data, model)
        print(f"[serve] mesh {dict(mesh.shape)} over "
              f"{len(jax.devices())} devices")

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    n_cats, emb_dim = 5, 64
    pool_names = DEFAULT_POOL
    # arrivals share the same latent category space: build the full zoo
    # (initial pool + scheduled arrivals) in one shot, serve the prefix
    arrival_names = [a for kind, a, _ in events if kind == "add"]
    all_entries, skills, protos = build_pool(
        ks[0], pool_names + arrival_names, n_cats, emb_dim)
    pool = all_entries[:len(pool_names)]
    arrivals = dict(zip(arrival_names, all_entries[len(pool_names):]))
    k_max = len(pool_names) + len(arrival_names) \
        if (events or args.autopilot or args.refresh_every) else None
    ap_cfg = None
    if args.autopilot:
        from repro.autopilot import AutopilotConfig
        ap_cfg = AutopilotConfig(every=args.autopilot_every,
                                 budget=args.budget)
    rcfg = None
    if args.refresh_every:
        from repro.refresh import RefreshConfig
        rcfg = RefreshConfig(every=args.refresh_every,
                             n_categories=n_cats,
                             causal=not args.refresh_naive,
                             epochs=1, steps_per_epoch=10, batch=32)

    enc_cfg = EncoderConfig(d_model=emb_dim, n_layers=2, n_heads=4, d_ff=256,
                            max_len=32)
    enc_params = init_encoder(ks[1], enc_cfg)

    n_models = k_max if k_max is not None else len(pool)
    fcfg = fgts.FGTSConfig(n_models=n_models, dim=emb_dim,
                           horizon=args.rounds * args.batch, eta=2.0, mu=0.2,
                           sgld_steps=10, sgld_eps=2e-4, sgld_minibatch=32)
    svc = RouterService(pool, enc_params, enc_cfg,
                        RouterServiceConfig(fgts=fcfg, cost_tilt=0.0,
                                            policy_factory=POLICIES[
                                                args.policy],
                                            feedback_expiry=args.feedback_expiry,
                                            stale_half_life=args.stale_half_life,
                                            k_max=k_max,
                                            autopilot=ap_cfg,
                                            refresh=rcfg,
                                            buckets=buckets),
                        mesh=mesh)

    # reduced candidate models (actual generation path)
    gen_models = {}
    if args.with_generation:
        for name in pool_names:
            cfg = ARCHS[name].reduced()
            gen_models[name] = (cfg, lm.init_params(ks[2], cfg))

    cc = CorpusConfig(n_categories=n_cats, seq_len=32)
    refresh_tick = None
    if args.refresh_every:
        from repro.refresh import refresh_table
        # the offline corpus CCFT was originally fine-tuned on: the refresh
        # re-runs it with anchor sampling tilted to the live category mix
        offline = make_split(ks[6], 16, cc)

        def refresh_tick(step):
            if not svc.refresh_due():
                return
            table, info = refresh_table(
                jax.random.fold_in(ks[7], step), svc.export_log(),
                enc_params, enc_cfg, offline, rcfg, n_models,
                costs=svc.costs)
            svc.apply_table(table)
            print(f"[serve] step {step}: representation refresh on "
                  f"{info['n_duels']} logged duels "
                  f"(mix={np.round(np.asarray(info['mix']), 2)}, "
                  f"{'IPW' if rcfg.causal else 'naive'} scores) — "
                  f"table hot-swapped")
    if args.arrival:
        row_of_slot = np.arange(n_models) % skills.shape[0]
        _serve_stream(args, spec, buckets, svc, skills, row_of_slot, cc,
                      n_cats, ks, pref_sampler, refresh_tick)
        return
    regrets = []
    pref_log, duel_cost_log = [], []   # realized-cost readout per tilt
    in_flight = []            # (due_round, tickets, y) — votes on their way
    # slot -> latent-skills row (arrivals may land in any freed slot)
    row_of_slot = np.arange(n_models) % skills.shape[0]
    arrival_row = {n: len(pool_names) + i
                   for i, n in enumerate(arrival_names)}
    t0 = time.time()
    for r in range(args.rounds):
        from repro.data.synth import sample_queries
        for kind, what, rnd in events:
            if rnd != r:
                continue
            if kind == "add":
                slot = svc.add_model(arrivals[what])
                row_of_slot[slot] = arrival_row[what]
                # offline->online warm start: replay BTL duels of the new
                # arm vs active incumbents on a small offline query split
                ko, kc_off, kw = jax.random.split(
                    jax.random.fold_in(ks[4], r), 3)
                cats_off = jax.random.randint(kc_off, (16,), 0, n_cats)
                toks_off, mask_off = sample_queries(ko, cats_off, cc)
                x_off = svc.embed(toks_off, mask_off)
                utils_off = skills[row_of_slot][:, cats_off].T
                n_seed = svc.seed_replay(*mp.warm_start_duels(
                    kw, x_off, utils_off, slot,
                    jnp.asarray(svc.active_mask()),
                    feedback_scale=8.0))    # match the live-vote sharpness
                print(f"[serve] round {r}: +{what} -> slot {slot} "
                      f"(CCFT warm start, {n_seed} seeded duels)")
            else:
                svc.retire_model(what)
                print(f"[serve] round {r}: retired slot {what}")
        kq, kc, kf = jax.random.split(jax.random.fold_in(ks[3], r), 3)
        cats = jax.random.randint(kc, (args.batch,), 0, n_cats)
        toks, mask = sample_queries(kq, cats, cc)
        x = svc.embed(toks, mask)
        prefs = None if pref_sampler is None else pref_sampler(
            jax.random.fold_in(ks[5], r), r, args.batch)
        a1, a2, tickets = svc.route_batch(
            x, prefs=prefs, cats=cats if args.refresh_every else None)
        if prefs is not None:
            pref_log.append(np.asarray(prefs))
            duel_cost_log.append(np.asarray(
                0.5 * (svc.costs[a1] + svc.costs[a2])))
        if args.with_generation:
            for b in range(min(args.batch, 2)):   # decode a couple per round
                for arm in (int(a1[b]), int(a2[b])):
                    arch = (pool_names + arrival_names)[
                        int(row_of_slot[arm])]
                    if arch not in gen_models:
                        continue      # scheduled arrivals have no reduced LM
                    cfg, params = gen_models[arch]
                    t = toks[b: b + 1, : 8] % cfg.vocab_size
                    logits, _ = lm.forward(params, {"tokens": t}, cfg,
                                           remat=False)
        utils = skills[row_of_slot][:, cats].T     # (B, K slots)
        y = sample_preference(kf, 8.0 * utils[jnp.arange(args.batch), a1],
                              8.0 * utils[jnp.arange(args.batch), a2])
        if args.feedback_delay == 0:
            svc.feedback_batch(tickets, y)
        else:
            in_flight.append((r + args.feedback_delay, tickets, y))
        # votes issued --feedback-delay rounds ago land at the end of this
        # round (so a D-round lag resolves at service age exactly D, the
        # same bookkeeping as env.run's lag ring; the env loop folds the
        # due batch in just *before* its round's act instead — one round of
        # scheduling skew, identical ages)
        due = [f for f in in_flight if f[0] <= r]
        in_flight = [f for f in in_flight if f[0] > r]
        for _, due_tickets, due_y in due:
            svc.feedback_batch(due_tickets, due_y)
        svc.expire_pending()
        if refresh_tick is not None:
            refresh_tick(r)
        # regret vs the best *active* arm (retired arms are not a benchmark)
        if svc.dynamic:
            act = jnp.asarray(svc.active_mask())
            best = jnp.max(jnp.where(act[None, :], utils, -jnp.inf), axis=-1)
        else:
            best = jnp.max(utils, axis=-1)
        reg = jnp.mean(best - 0.5 * (utils[jnp.arange(args.batch), a1]
                                     + utils[jnp.arange(args.batch), a2]))
        regrets.append(float(reg))
        ap_note = ""
        if args.autopilot:
            st = svc.autopilot_status()
            ap_note = (f" lam={st['lambda']:.3f} "
                       f"cost_ema={st['cost_ema']:.3f} "
                       f"active={int(st['active'].sum())}"
                       f"/{len(st['active'])} "
                       f"cand={int(st['candidate'].sum())}")
        print(f"[serve] round {r}: batch-regret={regrets[-1]:.4f} "
              f"cost=${svc.spend(a1):.3f} pending={svc.pending_count()}"
              f"{ap_note} ({time.time()-t0:.1f}s)")
    early = np.mean(regrets[:max(args.rounds // 4, 1)])
    late = np.mean(regrets[-max(args.rounds // 4, 1):])
    stats = svc.service_stats()     # one sync for all traffic counters
    print(f"[serve] regret early={early:.4f} late={late:.4f} "
          f"(adaptive: {'yes' if late < early else 'no'}) "
          f"routed={stats['n_routed']} folded={stats['n_folded']} "
          f"duel-cost=${stats['duel_cost']:.2f} "
          f"unresolved={stats['pending']}")
    if pref_log:
        # realized duel cost bucketed by the pref each request carried:
        # higher tilts should buy cheaper duels — the cost-quality knob
        # working end to end from one posterior
        pv = np.concatenate(pref_log)
        cv = np.concatenate(duel_cost_log)
        edges = np.unique(np.round(pv, 6))
        if edges.size > 8:                     # continuous dist: quartiles
            edges = np.quantile(pv, [0.0, 0.25, 0.5, 0.75])
        parts = []
        for i, lo in enumerate(edges):
            hi = edges[i + 1] if i + 1 < edges.size else np.inf
            sel = (pv >= lo) & (pv < hi) if edges.size > 1 else pv >= lo
            if sel.any():
                parts.append(f"pref>={lo:g}: ${cv[sel].mean():.3f}")
        print(f"[serve] realized duel cost by pref  " + "  ".join(parts))
    if args.autopilot:
        st = svc.autopilot_status()
        names = [p.name if p is not None else "-" for p in svc.pool]
        alive = [n for n, a in zip(names, st["active"]) if a]
        cands = [n for n, c in zip(names, st["candidate"]) if c]
        print(f"[serve] autopilot: lam={st['lambda']:.3f} "
              f"cost_ema={st['cost_ema']:.3f} active={alive} "
              f"candidates={cands}")


def _serve_stream(args, spec, buckets, svc, skills, row_of_slot, cc,
                  n_cats, ks, pref_sampler, refresh_tick=None):
    """Event-time streaming serving: cut the simulated arrival stream into
    dynamic batches (``--max-wait`` deadline forming) and drive them through
    the AOT bucket programs, reporting sustained QPS and per-request latency
    tails — simulated queueing wait plus measured route service time."""
    from repro.data.synth import sample_queries
    n_total = args.rounds * args.batch
    times = stream.arrival_times(spec, n_total, seed=0)
    batches = stream.form_batches(times, buckets, args.max_wait)
    print(f"[serve] streaming {args.arrival}: {n_total} requests -> "
          f"{len(batches)} batches on buckets {buckets} "
          f"(max_wait {args.max_wait * 1e3:g}ms)")
    lat, regrets = [], []
    report = max(len(batches) // 8, 1)
    t0 = time.time()
    for i, fb in enumerate(batches):
        kq, kc, kf = jax.random.split(jax.random.fold_in(ks[3], i), 3)
        cats = jax.random.randint(kc, (fb.n,), 0, n_cats)
        toks, mask = sample_queries(kq, cats, cc)
        # embed at bucket width: one encoder shape per bucket, not per n
        x = svc.embed(stream.pad_rows(toks, fb.bucket),
                      stream.pad_rows(mask, fb.bucket))[:fb.n]
        prefs = None if pref_sampler is None else pref_sampler(
            jax.random.fold_in(ks[5], i), i, fb.n)
        t_r = time.time()
        a1, a2, tickets = svc.route_stream(
            x, prefs=prefs, cats=cats if refresh_tick is not None else None)
        jax.block_until_ready(tickets)
        service = time.time() - t_r
        lat.append(fb.t_form - times[fb.start:fb.start + fb.n] + service)
        utils = skills[row_of_slot][:, cats].T           # (n, K slots)
        rows = jnp.arange(fb.n)
        y = sample_preference(kf, 8.0 * utils[rows, a1],
                              8.0 * utils[rows, a2])
        svc.feedback_stream(tickets, y)
        if refresh_tick is not None:
            refresh_tick(i)
        reg = jnp.mean(jnp.max(utils, axis=-1)
                       - 0.5 * (utils[rows, a1] + utils[rows, a2]))
        regrets.append(float(reg))
        if i % report == 0:
            print(f"[serve] batch {i}: n={fb.n} bucket={fb.bucket} "
                  f"wait_ms={(fb.t_form - times[fb.start]) * 1e3:.1f} "
                  f"regret={regrets[-1]:.4f} ({time.time() - t0:.1f}s)")
    jax.block_until_ready(svc.state)
    wall = time.time() - t0
    lat = np.concatenate(lat)
    stats = svc.service_stats()
    early = np.mean(regrets[:max(len(regrets) // 4, 1)])
    late = np.mean(regrets[-max(len(regrets) // 4, 1):])
    pad_eff = n_total / sum(fb.bucket for fb in batches)
    print(f"[serve] streaming done: qps={n_total / wall:.0f} "
          f"p50={np.percentile(lat, 50) * 1e3:.2f}ms "
          f"p99={np.percentile(lat, 99) * 1e3:.2f}ms pad={pad_eff:.2f} "
          f"regret early={early:.4f} late={late:.4f} "
          f"(adaptive: {'yes' if late < early else 'no'}) "
          f"routed={stats['n_routed']} folded={stats['n_folded']} "
          f"unresolved={stats['pending']}")
    # host compiles per router.* span since construction: a count that
    # grows between runs of the same traffic is a recompile in serving
    print("[serve] compiles " + " ".join(
        f"{k}={n} ({stats['compile_s'][k]:.2f}s)"
        for k, n in sorted(stats["compiles"].items())))


if __name__ == "__main__":
    main()
