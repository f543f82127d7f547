#!/usr/bin/env python3
"""Chip smoke test: the router's served path, end to end, on a TPU.

    python chip_smoke.py [--seed N]              # one chip, the served path
    python chip_smoke.py --chips 4 [--seed N]    # four chips, the mesh path

One chip. Everything is built from ``--seed``: the production router encoder
(``EncoderConfig(vocab_size=32768, d_model=768, n_layers=6, n_heads=12,
d_ff=3072, max_len=128)``, random weights), the 11-arm RouterBench pool with
CCFT embeddings and standardized perf/cost metadata (d = 768 + 2 * 7 = 782),
and an FGTS.CDB ``RouterService`` whose SGLD gradient resolves to the fused
Pallas kernel. A streaming service on a pow2 bucket ladder up to 1024 takes
formed batches tokens -> ``embed`` -> ``route_stream`` -> ``feedback_stream``
twice over (a warm-up pass, then a timed pass, one batch of >= 256 rows),
then a tick-batch service takes a few ``route_batch`` / ``feedback_batch``
rounds. Checks, each a hard failure: every pair is an active arm; the
service's counters match what was sent; no program compiles after warm-up;
the compiled route programs hold the Pallas kernels (``tpu_custom_call``);
and at these widths ``dueling_select`` agrees with the XLA reference
selection (at "highest" matmul precision), and the fused SGLD potential
and gradient agree with the autodiff reference (the value as the chip
computes it at "highest", the gradient in float64 on the host CPU).

Four chips (``--chips 4``): only the mesh path. A streaming service on the
(4, 1) data mesh (batch-sharded act, shard-local pending ring, replicated
posterior fold) at the same widths is compared with a one-device service
running the same XLA program: identical pairs, the same folded count, the
posterior within the tolerance of the repo's sharded-serving tests, and a
resolve program without collectives.

The last line of stdout is the JSON result. A device that is not a TPU, an
environment override that would force interpret mode or a reference
backend, a pool beyond the fused-kernel ceiling, or any failed check exits
non-zero before that line is printed. JAX's persistent compilation cache is
used (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ENC_KW = dict(vocab_size=32_768, d_model=768, n_layers=6, n_heads=12,
              d_ff=3072, max_len=128, name="router-encoder-prod")
BUCKETS = (16, 64, 256, 1024)
FGTS_KW = dict(horizon=4096, eta=8.0, mu=0.2, sgld_steps=20,
               sgld_minibatch=256, sgld_eps=5e-4, n_chains=4,
               sgld_backend="auto")
# formed batches: bursty arrivals cut on the ladder with a 20 ms deadline
N_REQUESTS = 1536
ARRIVAL = dict(kind="bursty", rate=6_000.0, burst=64.0)
MAX_WAIT = 0.02
TICK_BATCH, TICK_ROUNDS = 256, 3
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


def check_environment():
    """Refuse overrides that would route the served path around the chip."""
    interp = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    check(interp in ("", "0", "false"),
          f"REPRO_PALLAS_INTERPRET={interp!r} forces Pallas interpret mode; "
          f"unset it")
    sgld = os.environ.get("REPRO_SGLD_BACKEND", "").strip().lower()
    check(sgld in ("", "fused"),
          f"REPRO_SGLD_BACKEND={sgld!r} forces a non-kernel SGLD backend; "
          f"unset it")


@dataclasses.dataclass
class Setup:
    enc_cfg: object
    enc_params: dict
    corpus: object
    entries: list
    utils: object           # (K, M) cost-adjusted utility per benchmark
    fgts_cfg: object
    cost_tilt: float
    seed: int


def build(seed, enc_kw=ENC_KW, fgts_kw=FGTS_KW):
    """Encoder, CCFT pool and FGTS config, all from ``seed``."""
    import jax
    import numpy as np
    from repro.core import fgts
    from repro.data import pipeline, routerbench as rb
    from repro.data.pool import build_entries
    from repro.data.synth import CorpusConfig
    from repro.encoder import EncoderConfig, init_encoder

    enc_cfg = EncoderConfig(**enc_kw)
    k_enc, k_split = jax.random.split(jax.random.PRNGKey(seed))
    enc_params = init_encoder(k_enc, enc_cfg)
    corpus = CorpusConfig(n_categories=rb.N_BENCHMARKS,
                          seq_len=enc_cfg.max_len)
    split = rb.make_split(k_split, corpus, n_offline_per_cat=5, t_online=8)
    a_emb = pipeline.routerbench_model_embeddings(
        enc_params, enc_cfg, split, "excel_perf_cost", tau=3)
    costs = np.asarray(split.cost).mean(axis=1)
    entries = build_entries(rb.LLMS, np.asarray(a_emb), costs)
    cfg = fgts.FGTSConfig(n_models=len(entries), dim=a_emb.shape[1],
                          **fgts_kw)
    return Setup(enc_cfg, enc_params, corpus, entries,
                 rb.scores(split.perf, split.cost), cfg,
                 cost_tilt=rb.LAMBDA_COST, seed=seed)


def make_service(setup, *, buckets=BUCKETS, mesh=None, **cfg_kw):
    from repro.serving import RouterService, RouterServiceConfig
    cfg = RouterServiceConfig(fgts=setup.fgts_cfg, cost_tilt=setup.cost_tilt,
                              seed=setup.seed, feedback_capacity=4096,
                              buckets=buckets, **cfg_kw)
    return RouterService(setup.entries, setup.enc_params, setup.enc_cfg, cfg,
                         mesh=mesh)


def formed_batches(seed, buckets=BUCKETS, n_requests=N_REQUESTS):
    from repro.serving import stream
    spec = stream.ArrivalSpec(ARRIVAL["kind"], ARRIVAL["rate"],
                              burst=ARRIVAL["burst"])
    times = stream.arrival_times(spec, n_requests, seed=seed)
    return stream.form_batches(times, buckets, MAX_WAIT)


class Traffic:
    """Seeded queries (tokens + category) and BTL votes on the RouterBench
    utilities, one formed batch at a time."""

    def __init__(self, setup):
        import jax
        from repro.data.synth import sample_queries
        self.setup = setup
        self.key = jax.random.PRNGKey(setup.seed + 1)
        self._sample = jax.jit(sample_queries, static_argnames="cfg")

    def _next(self):
        import jax
        self.key, k = jax.random.split(self.key)
        return k

    def queries(self, n):
        import jax
        from repro.data import routerbench as rb
        cats = jax.random.randint(self._next(), (n,), 0, rb.N_BENCHMARKS)
        tokens, mask = self._sample(self._next(), cats, cfg=self.setup.corpus)
        return tokens, mask, cats

    def votes(self, cats, a1, a2):
        import jax.numpy as jnp
        from repro.core.btl import sample_preference
        u = jnp.asarray(self.setup.utils)[:, cats]           # (K, n)
        cols = jnp.arange(cats.shape[0])
        return sample_preference(self._next(), 8.0 * u[a1, cols],
                                 8.0 * u[a2, cols])


def embed_queries(svc, tokens, mask):
    """Encode a formed batch as its bucket (padding rows have an all-zero
    mask and are sliced off) so it runs the bucket's AOT encoder."""
    from repro.core import ccft
    from repro.data import routerbench as rb
    from repro.serving import stream
    n = tokens.shape[0]
    b = stream.bucket_for(n, svc.buckets) if svc.streaming else n
    x = svc.embed(stream.pad_rows(tokens, b), stream.pad_rows(mask, b))[:n]
    return ccft.pad_queries(x, 2 * rb.N_BENCHMARKS)


def check_pairs(a1, a2, k, what):
    import numpy as np
    a1, a2 = np.asarray(a1), np.asarray(a2)
    check(((0 <= a1) & (a1 < k) & (0 <= a2) & (a2 < k)).all(),
          f"{what}: a routed arm is outside the {k} active arms")


# ---------------------------------------------------------------------------
# one chip: the served path
# ---------------------------------------------------------------------------

def serve_stream(setup, batches):
    """Two passes of formed batches through the streaming surface; votes
    trail their batch by one. Returns the service and per-batch timings."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    svc = make_service(setup)
    compile_s = time.perf_counter() - t0
    traffic = Traffic(setup)
    k = len(setup.entries)
    sent = {"routed": 0, "votes": 0}
    rng = np.random.default_rng(setup.seed)

    def one_pass():
        lagged, rows = None, []
        for i, fb in enumerate(batches):
            tokens, mask, cats = traffic.queries(fb.n)
            jax.block_until_ready(tokens)
            prefs = (None if i % 2 == 0 else
                     jax.numpy.asarray(rng.uniform(0.0, 2.0, fb.n),
                                       jax.numpy.float32))
            t_r = time.perf_counter()
            x = embed_queries(svc, tokens, mask)
            a1, a2, tickets = svc.route_stream(x, prefs=prefs)
            jax.block_until_ready((a1, a2, tickets))
            t_route = time.perf_counter() - t_r
            check_pairs(a1, a2, k, f"route_stream batch of {fb.n}")
            sent["routed"] += fb.n
            t_fb = 0.0
            if lagged is not None:
                t_f = time.perf_counter()
                n_ok = svc.feedback_stream(*lagged)
                jax.block_until_ready(n_ok)
                t_fb = time.perf_counter() - t_f
                sent["votes"] += lagged[0].shape[0]
            lagged = (tickets, traffic.votes(cats, a1, a2))
            rows.append((fb.n, fb.bucket, t_route, t_fb))
        sent["votes"] += lagged[0].shape[0]
        jax.block_until_ready(svc.feedback_stream(*lagged))
        return rows

    t0 = time.perf_counter()
    one_pass()
    first_s = time.perf_counter() - t0
    counts = svc.compiled_program_counts()
    warm = one_pass()
    check(svc.compiled_program_counts() == counts,
          f"streaming programs grew after warm-up: {counts} -> "
          f"{svc.compiled_program_counts()}")
    stats = svc.service_stats()
    check(stats["n_routed"] == sent["routed"]
          and stats["n_folded"] == sent["votes"] and stats["pending"] == 0,
          f"service_stats {stats} != sent {sent}")
    return svc, compile_s, first_s, warm, stats


def serve_ticks(setup, traffic):
    """Tick-batch surface: lazy-jit route_batch / feedback_batch rounds."""
    import jax
    svc = make_service(setup, buckets=None)
    k = len(setup.entries)
    times, counts = [], None
    for r in range(TICK_ROUNDS):
        tokens, mask, cats = traffic.queries(TICK_BATCH)
        t0 = time.perf_counter()
        x = embed_queries(svc, tokens, mask)
        a1, a2, tickets = svc.route_batch(x)
        n_ok = svc.feedback_batch(tickets, traffic.votes(cats, a1, a2))
        jax.block_until_ready((a1, a2, n_ok))
        times.append(time.perf_counter() - t0)
        check_pairs(a1, a2, k, f"route_batch round {r}")
        check(int(n_ok) == TICK_BATCH, f"feedback_batch folded {int(n_ok)} "
              f"of {TICK_BATCH} votes in round {r}")
        if r == 0:
            counts = svc.compiled_program_counts()
    check(svc.compiled_program_counts() == counts,
          f"tick-batch programs grew after round 0: {counts} -> "
          f"{svc.compiled_program_counts()}")
    stats = svc.service_stats()
    check(stats["n_routed"] == stats["n_folded"] == TICK_ROUNDS * TICK_BATCH
          and stats["pending"] == 0, f"tick service_stats {stats}")
    return times


def argmax_ok(s, idx, tol):
    """Each row's pick scores within ``tol`` of that row's best score."""
    import numpy as np
    s = np.asarray(s, np.float64)
    picked = np.take_along_axis(s, np.asarray(idx)[:, None], 1)[:, 0]
    best = s.max(axis=1)
    return ((picked >= best - tol) & np.isfinite(picked)) | ~np.isfinite(best)


def reference_scores(x, a_emb, theta, tilt, mask):
    import jax.numpy as jnp
    den = jnp.sqrt(jnp.maximum((x * x) @ (a_emb * a_emb).T, 1e-24))
    s = ((x * theta[None, :]) @ a_emb.T) / den - jnp.atleast_2d(tilt)
    return jnp.where(jnp.atleast_2d(mask), s, -jnp.inf)


def select_parity(x, a_emb, theta1, theta2, tilt, mask):
    """Kernel pairs vs the XLA reference selection (highest precision). A
    pair may differ only where the reference's top scores are within
    tolerance of each other. Returns (rows, exact matches, max |score|)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.policy import select_pair
    from repro.kernels.dueling_score import dueling_select

    kern = jax.jit(lambda x, t, m: dueling_select(
        x, a_emb, jnp.stack([theta1, theta2]), tilt=t, mask=m,
        distinct=True))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda x, t, m: select_pair(
            x, a_emb, theta1, theta2, tilt=t, mask=m, distinct=True,
            use_kernel=False))
        k1, k2 = kern(x, tilt, mask)
        r1, r2 = ref(x, tilt, mask)
        s1 = np.asarray(jax.jit(reference_scores)(x, a_emb, theta1, tilt,
                                                  mask))
        s2 = np.asarray(jax.jit(reference_scores)(x, a_emb, theta2, tilt,
                                                  mask))
    k1, k2, r1, r2 = (np.asarray(v) for v in (k1, k2, r1, r2))
    scale = float(np.max(np.abs(s1[np.isfinite(s1)])))
    tol = 1e-4 * max(1.0, scale)
    ok1 = argmax_ok(s1, k1, tol)
    # a2 is judged against the reference scores with the kernel's own a1
    # struck out (force-distinct), unless that leaves no arm at all
    s2d = s2.copy()
    s2d[np.arange(len(k1)), k1] = -np.inf
    s2d = np.where(np.isfinite(s2d).any(axis=1, keepdims=True), s2d, s2)
    ok2 = argmax_ok(s2d, k2, tol)
    check(ok1.all() and ok2.all(),
          f"dueling_select: {int((~ok1).sum())} a1 and {int((~ok2).sum())} "
          f"a2 picks are not argmaxes of the reference scores within {tol}")
    return len(k1), int(((k1 == r1) & (k2 == r2)).sum()), scale


def sgld_parity(svc, setup):
    """Fused SGLD potential and gradient vs the autodiff reference on
    replayed duels (2 row tiles), 4 vmapped chains.

    Each is held to the reference that computes its math as the kernel
    does. The value goes against the chip's own autodiff potential at
    "highest": both take the chip's f32 softplus, which on a v5e is up to
    1.05e-4 off exact, so against float64 the value would measure the
    chip's softplus and not the kernel. The gradient goes against the
    autodiff gradient in float64 on the host CPU, one chain at a time: the
    kernel's VJP is a sigmoid, while the chip's autodiff gradient goes
    through logaddexp's VJP and carries that softplus error (~1e-4), and
    on the installed jaxlib's CPU backend the jitted, chain-vmapped
    autodiff gradient of these operands came out up to 5e-2 off its
    per-chain value. Returns (chains, value error vs the chip, value error
    vs float64, gradient error vs float64)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import fgts
    from repro.kernels.sgld_update import sgld_potential

    st, cfg = svc.state, setup.fgts_cfg
    m = cfg.sgld_minibatch
    check(int(st.t) >= m, f"only {int(st.t)} duels replayed, need {m}")
    idx = jnp.arange(m)
    ops = (st.x[idx], st.a1[idx], st.a2[idx], st.y[idx],
           jnp.ones((m,), jnp.float32), svc.a_emb,
           jnp.ones((svc.a_emb.shape[0],), bool).at[3].set(False),
           jax.random.uniform(jax.random.PRNGKey(setup.seed + 2), (m,),
                              maxval=2.0), svc.costs)

    def fused(th, x, a1, a2, y, valid, a_emb, mask, pref, costs):
        return sgld_potential(th, x, a1, a2, y, valid, a_emb, mask,
                              pref=pref, costs=costs, j=1, eta=cfg.eta,
                              mu=cfg.mu, backend="fused")

    def reference(th, x, a1, a2, y, valid, a_emb, mask, pref, costs):
        return jnp.sum(fgts.likelihood_batch(
            th, x, a1, a2, y, a_emb, 1, cfg, arm_mask=mask, pref=pref,
            costs=costs) * valid)

    thetas = st.theta1                                   # (C, d)
    chain_axes = (0,) + (None,) * len(ops)
    v_k, g_k = (np.asarray(v, np.float64) for v in jax.jit(jax.vmap(
        jax.value_and_grad(fused), in_axes=chain_axes))(thetas, *ops))
    with jax.default_matmul_precision("highest"):
        v_chip = np.asarray(jax.jit(jax.vmap(reference, in_axes=chain_axes))(
            thetas, *ops), np.float64)
    with jax.enable_x64(True):
        thetas_c, *ops_c = (
            v.astype(jnp.float64) if jnp.issubdtype(v.dtype, jnp.floating)
            else v for v in jax.device_put((thetas,) + ops,
                                           jax.devices("cpu")[0]))
        ref = jax.jit(jax.value_and_grad(reference))
        vg_r = [ref(th, *ops_c) for th in thetas_c]
    v_r = np.array([float(v) for v, _ in vg_r])
    g_r = np.stack([np.asarray(g) for _, g in vg_r])

    def rel(v, r):
        return float(np.max(np.abs(v - r) / np.maximum(1.0, np.abs(r))))

    v_err, v_err64 = rel(v_k, v_chip), rel(v_k, v_r)
    g_err = float(np.max(np.abs(g_k - g_r))
                  / max(1.0, float(np.max(np.abs(g_r)))))
    check(v_err <= 1e-4 and g_err <= 1e-4,
          f"fused SGLD vs autodiff: value rel err {v_err:.3e} (chip), grad "
          f"rel err {g_err:.3e} (float64) (limit 1e-4)")
    return thetas.shape[0], v_err, v_err64, g_err


def custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def one_chip(setup, batches):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import MAX_K_FUSED
    from repro.kernels.sgld_update import resolve_sgld_backend

    k, d = len(setup.entries), setup.fgts_cfg.dim
    cfg = setup.fgts_cfg
    check(k <= MAX_K_FUSED, f"K={k} exceeds MAX_K_FUSED={MAX_K_FUSED}: both "
          f"kernels would fall back to XLA")
    backend = resolve_sgld_backend(cfg.sgld_backend, cfg.n_chains)
    check(backend == "fused",
          f"sgld_backend 'auto' resolved to {backend!r}, not 'fused'")
    n_params = sum(int(np.prod(v.shape))
                   for v in jax.tree.leaves(setup.enc_params))
    e = setup.enc_cfg
    log(f"widths: encoder d_model={e.d_model} n_layers={e.n_layers} "
        f"n_heads={e.n_heads} d_ff={e.d_ff} vocab={e.vocab_size} "
        f"max_len={e.max_len} params={n_params}; pool K={k}; d={d}")
    log(f"sgld: backend={backend} steps={cfg.sgld_steps} "
        f"minibatch={cfg.sgld_minibatch} chains={cfg.n_chains}")
    sizes = [int(fb.n) for fb in batches]
    check(max(sizes) >= 256, f"no formed batch reached 256 rows: {sizes}")
    log(f"stream: {len(batches)} formed batches, sizes {sizes}, buckets "
        f"{[fb.bucket for fb in batches]}")

    svc, compile_s, first_s, warm, stats = serve_stream(setup, batches)
    n_progs = sum(v for kk, v in svc.compiled_program_counts().items()
                  if kk.startswith("s_"))
    log(f"compile: streaming service built {n_progs} AOT bucket programs in "
        f"{compile_s:.3f} s; first pass (eager ops compile) "
        f"{first_s:.3f} s")
    for n, b, t_route, t_fb in warm:
        log(f"warm batch n={n} bucket={b}: embed+route {t_route:.6f} s, "
            f"feedback {t_fb:.6f} s")
    log(f"stream stats: {stats}; no program compiled after warm-up")
    route_cc = {b: custom_calls(p) for b, p in svc._s_route.items()}
    route_pref_cc = {b: custom_calls(p) for b, p in svc._s_route_pref.items()}
    fb_cc = {b: custom_calls(p) for b, p in svc._s_feedback.items()}
    check(all(v > 0 for v in route_cc.values())
          and all(v > 0 for v in route_pref_cc.values()),
          f"route programs without tpu_custom_call: {route_cc} "
          f"{route_pref_cc}")
    log(f"tpu_custom_call per program: route {route_cc}, route_pref "
        f"{route_pref_cc}, feedback {fb_cc} (the feedback fold is a replay "
        f"ring scatter: no kernel)")

    traffic = Traffic(dataclasses.replace(setup, seed=setup.seed + 7))
    tick_s = serve_ticks(setup, traffic)
    log(f"ticks: {TICK_ROUNDS} route_batch+feedback_batch rounds of "
        f"{TICK_BATCH}: seconds {[round(t, 6) for t in tick_s]}; programs "
        f"flat after round 0")

    # kernel parity at the served widths, on served queries and thetas
    theta1 = svc.state.theta1.mean(axis=0)
    theta2 = svc.state.theta2.mean(axis=0)
    tilt_k = setup.cost_tilt * svc.costs
    rng = np.random.default_rng(setup.seed)
    for b in (256, 1024):
        tokens, mask_t, _ = traffic.queries(b)
        x = embed_queries(svc, tokens, mask_t)
        arm_mask = jnp.ones((k,), bool).at[2].set(False)
        rows, exact, scale = select_parity(x, svc.a_emb, theta1, theta2,
                                           tilt_k, arm_mask)
        pref = jnp.asarray(rng.uniform(0, 2, (b, 1)), jnp.float32)
        row_mask = jnp.asarray(rng.uniform(size=(b, k)) > 0.3)
        rows2, exact2, _ = select_parity(x, svc.a_emb, theta1, theta2,
                                         tilt_k + pref * svc.costs, row_mask)
        log(f"parity dueling_select B={b}: (K,) mask {exact}/{rows} exact, "
            f"(B,K) mask+tilt {exact2}/{rows2} exact; every other pick "
            f"within 1e-4 of the top score (|s| <= {scale:.3f})")
    chains, v_err, v_err64, g_err = sgld_parity(svc, setup)
    log(f"parity sgld_potential m={cfg.sgld_minibatch} chains={chains}: "
        f"value rel err {v_err:.3e} vs the chip's autodiff at \"highest\" "
        f"({v_err64:.3e} vs float64), grad rel err {g_err:.3e} vs float64 "
        f"autodiff (host CPU)")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"memory: peak_bytes_in_use={mem.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# four chips: the mesh path only
# ---------------------------------------------------------------------------

def mesh_phase(setup, batches, n_chips):
    """Streaming service on an (n_chips, 1) data mesh vs the same XLA
    program on one device."""
    import jax
    import numpy as np
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_debug_mesh(n_chips, 1)
    ref_setup = dataclasses.replace(setup, fgts_cfg=dataclasses.replace(
        setup.fgts_cfg, sgld_backend="xla"))
    t0 = time.perf_counter()
    svc_m = make_service(setup, mesh=mesh)
    t_mesh = time.perf_counter() - t0
    t0 = time.perf_counter()
    svc_1 = make_service(ref_setup, use_kernel=False)
    t_one = time.perf_counter() - t0
    check(svc_m.cfg.fgts.sgld_backend == "xla" and not svc_m.cfg.use_kernel,
          "the mesh service did not resolve to the partitionable XLA path")
    log(f"mesh: {dict(mesh.shape)} over {n_chips} {jax.devices()[0].device_kind}"
        f" chips; AOT build {t_mesh:.3f} s (mesh), {t_one:.3f} s (one "
        f"device)")
    traffic = Traffic(setup)
    k = len(setup.entries)
    lagged = None
    mismatched = 0
    for fb in batches:
        tokens, mask, cats = traffic.queries(fb.n)
        x = embed_queries(svc_1, tokens, mask)
        t0 = time.perf_counter()
        a1m, a2m, tm = svc_m.route_stream(x)
        jax.block_until_ready((a1m, a2m, tm))
        t_m = time.perf_counter() - t0
        a1s, a2s, ts = svc_1.route_stream(x)
        check_pairs(a1m, a2m, k, f"mesh route_stream batch of {fb.n}")
        mismatched += int((np.asarray(a1m) != np.asarray(a1s)).sum()
                          + (np.asarray(a2m) != np.asarray(a2s)).sum())
        if lagged is not None:
            (tm0, ts0, y0) = lagged
            n_m = int(svc_m.feedback_stream(tm0, y0))
            n_s = int(svc_1.feedback_stream(ts0, y0))
            check(n_m == n_s == y0.shape[0],
                  f"folded {n_m} on the mesh, {n_s} on one device, of "
                  f"{y0.shape[0]}")
        lagged = (tm, ts, traffic.votes(cats, a1s, a2s))
        log(f"mesh batch n={fb.n} bucket={fb.bucket}: route {t_m:.6f} s")
    tm0, ts0, y0 = lagged
    check(int(svc_m.feedback_stream(tm0, y0))
          == int(svc_1.feedback_stream(ts0, y0)) == y0.shape[0],
          "final feedback batch folded unequal counts")
    check(mismatched == 0, f"{mismatched} routed arms differ between the "
          f"mesh and the one-device service")
    st_m, st_1 = svc_m.service_stats(), svc_1.service_stats()
    check(st_m["n_folded"] == st_1["n_folded"] and st_m["pending"] == 0
          and st_m["n_routed"] == st_1["n_routed"],
          f"stats differ: mesh {st_m}, one device {st_1}")
    worst = 0.0
    for a, b in zip(jax.tree.leaves(svc_m.state), jax.tree.leaves(svc_1.state)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
        if a.dtype.kind == "f":
            worst = max(worst, float(np.max(np.abs(a - b), initial=0.0)))
    log(f"mesh parity: pairs identical over {st_m['n_routed']} routed, "
        f"{st_m['n_folded']} folded on both; posterior max abs diff "
        f"{worst:.3e} (rtol=atol=1e-4)")
    for b, prog in svc_m._s_resolve.items():
        hlo = prog.as_text()
        found = [op for op in COLLECTIVES if op in hlo]
        check(not found, f"resolve[{b}] lowered collectives {found}")
    log(f"mesh resolve programs {sorted(svc_m._s_resolve)}: zero collectives")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the (4, 1) data-mesh serving path and "
                         "its one-device comparison")
    args = ap.parse_args(argv)
    try:
        check_environment()
        from repro.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        import jax
        devices = jax.devices()
        dev = devices[0]
        check(dev.platform == "tpu",
              f"no TPU: JAX's first device is {dev.platform} "
              f"({dev.device_kind}); this smoke test runs only on the chip")
        check(len(devices) >= args.chips,
              f"--chips {args.chips} but JAX sees {len(devices)} devices")
        log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
            f"compile cache {cache}")
        t0 = time.perf_counter()
        setup = build(args.seed)
        batches = formed_batches(args.seed)
        log(f"setup: encoder init + CCFT pool embeddings "
            f"{time.perf_counter() - t0:.3f} s")
        if args.chips == 1:
            one_chip(setup, batches)
        else:
            mesh_phase(setup, batches, args.chips)
        count = args.chips
    except Exception as e:   # noqa: BLE001 — every failure is a failed smoke
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
