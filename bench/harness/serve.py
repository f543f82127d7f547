"""The system under test, built and driven open loop.

Set-up builds the program's ``RouterService`` (streaming, AOT programs per
bucket) on weights the benchmark made from the seed, then warms every
bucket once: embed, the route program the cell's traffic takes, and the
feedback fold. The window then serves requests on their due times: when
the service is free it forms one batch from the due requests with the
program's ``serving/stream.form_batches`` (a request that has waited past
``max_wait`` counts as arriving ``max_wait`` ago, so that a busy service
takes every waiting request), cuts it to the largest size of the
ladder of batch sizes (``batch_sizes``) not above it, and the batch goes
tokens -> ``RouterService.embed`` -> ``route_stream`` (SGLD posterior
sample, dueling selection, ring enqueue) -> pair on the host; the votes
that are due then go through ``feedback_stream``.

Every call into the program is logged (request ids, the embeddings and
pairs it returned, the posterior samples after each route, the votes
delivered) for the comparison that decides ``correct``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from . import traffic as tr
from .cell import encoder as load_encoder
from .weights import make_weights, std_metadata

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def batch_sizes(buckets) -> list:
    """The sizes a formed batch is cut to: every size up to the first
    bucket, then steps of 1.25x up to the top bucket (35 sizes for
    16-1024). The program compiles small programs for every batch size it
    has not seen, so set-up warms each of these and the window forms no
    other; the requests cut off wait for the next batch."""
    first, top = int(buckets[0]), int(buckets[-1])
    sizes = list(range(1, first + 1))
    i = 1
    while round(first * 1.25 ** i) < top:
        sizes.append(round(first * 1.25 ** i))
        i += 1
    return sizes + [top]


class Spans:
    """Host spans around each call into a layer. Traced, each is a
    ``jax.profiler.TraceAnnotation`` named ``bench.<layer>`` that ends in a
    sync, so that it encloses its layer's device work. Whether traced or
    not, backend compiles are counted per span (``jax.monitoring``), and
    the host seconds spent in each span and in its compiles are summed
    (``seconds``, ``compile_s``), for the run's log line."""

    def __init__(self, traced: bool):
        import jax
        self.traced = traced
        self.current = "setup"
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def reset(self):
        self.compiles: dict = {}
        self.compile_s: dict = {}
        self.seconds: dict = {}

    def _event(self, name, secs, **_):
        if name == COMPILE_EVENT:
            c = self.current
            self.compiles[c] = self.compiles.get(c, 0) + 1
            self.compile_s[c] = self.compile_s.get(c, 0.0) + secs

    @contextlib.contextmanager
    def __call__(self, name: str, sync=None):
        import jax
        prev, self.current = self.current, name
        t0 = time.perf_counter()
        try:
            if self.traced:
                with jax.profiler.TraceAnnotation(f"bench.{name}"):
                    out = []
                    yield out
                    if out:
                        jax.block_until_ready(out)
            else:
                yield []
        finally:
            self.current = prev
            self.seconds[name] = self.seconds.get(name, 0.0) \
                + time.perf_counter() - t0


@dataclasses.dataclass
class Pool:
    names: list
    costs: np.ndarray        # (K,) per-arm cost
    utils: np.ndarray        # (K, M) utility per arm and category
    meta: np.ndarray | None  # (K, m) metadata columns of the arm table
    cost_tilt: float
    vote_scale: float


def make_pool(config: dict, seed: int) -> Pool:
    p = config["pool"]
    if p["benchmark"] == "routerbench":
        perf = np.asarray(p["perf"], np.float32)
        cost = np.asarray(p["cost"], np.float32)
        return Pool(list(p["models"]), cost.mean(axis=1),
                    perf - p["cost_tilt"] * cost,
                    std_metadata(perf, cost) if p["metadata_columns"]
                    else None, float(p["cost_tilt"]), float(p["vote_scale"]))
    if p["benchmark"] == "mixinstruct":
        pct = np.asarray(p["first_rank_pct"], np.float64)
        base = np.log(pct / pct.sum())
        base = 0.55 + 0.22 * (base - base.mean()) / base.std()
        rng = np.random.default_rng([seed, 3])
        dev = p["skill_dev"] * rng.standard_normal(
            (len(p["models"]), p["latent_categories"]))
        dev -= dev.mean(axis=1, keepdims=True)
        return Pool(list(p["models"]), np.zeros(len(p["models"]), np.float32),
                    (base[:, None] + dev).astype(np.float32), None,
                    float(p["cost_tilt"]), float(p["vote_scale"]))
    raise ValueError(f"unknown pool {p['benchmark']!r}")


def sub_seeds(seed: int) -> dict:
    """Independent 31-bit seeds for the weights and the service's key
    stream, from any whole-number run seed."""
    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return {"weights": int(s[0] & 0x7FFFFFFF),
            "service": int(s[1] & 0x7FFFFFFF)}


@dataclasses.dataclass
class RouteCall:
    call: int               # the service's route count, 1-based
    ids: np.ndarray         # request ids, in batch order
    bucket: int
    x: object               # (n, d) embeddings the program returned
    a1: np.ndarray
    a2: np.ndarray
    theta: object           # (2, C, d) posterior samples after the route
    in_window: bool


@dataclasses.dataclass
class Delivery:
    ids: np.ndarray
    y: np.ndarray


class Session:
    """One service under one cell's traffic."""

    def __init__(self, config: dict, mix: dict, seed: int, seconds: float,
                 spans: Spans):
        from repro.core import fgts
        from repro.data.pool import build_entries
        from repro.serving import RouterService, RouterServiceConfig

        self.config, self.spans = config, spans
        self.calls: list[RouteCall] = []
        self.log: list = []          # RouteCall and Delivery, in call order
        self.n_calls = 0
        self.seeds = sub_seeds(seed)
        self.encoder = load_encoder(config["encoder"])
        self.enc = self.encoder.widths(config["encoder"])
        self.seq_len = int(self.encoder.seq_len(self.enc))
        sv = config["serving"]
        self.buckets = tuple(sv["buckets"])
        self.max_wait = float(sv["max_wait_s"])
        cap = int(mix.get("max_batch", self.buckets[-1]))
        self.sizes = [b for b in batch_sizes(self.buckets) if b <= cap]
        self.pool = make_pool(config, seed)
        k = len(self.pool.names)
        self.params, self.a_emb = make_weights(
            self.seeds["weights"], self.encoder, self.enc, k, self.pool.meta)
        self.n_meta = 0 if self.pool.meta is None else self.pool.meta.shape[1]
        self.d = self.encoder.embed_dim(self.enc) + self.n_meta
        enc_cfg = self.encoder.service_encoder(self.enc, self.seq_len,
                                               config["name"])
        fg = config["fgts"]
        self.fgts_cfg = fgts.FGTSConfig(
            n_models=k, dim=self.d, horizon=fg["horizon"], eta=fg["eta"],
            mu=fg["mu"], prior_var=fg["prior_var"],
            sgld_steps=fg["sgld_steps"], sgld_eps=fg["sgld_eps"],
            sgld_minibatch=fg["sgld_minibatch"], sgld_temp=fg["sgld_temp"],
            n_chains=fg["n_chains"], sgld_backend=fg["sgld_backend"])
        entries = build_entries(self.pool.names, np.asarray(self.a_emb),
                                self.pool.costs)
        self.svc = RouterService(
            entries, self.params, enc_cfg,
            RouterServiceConfig(fgts=self.fgts_cfg,
                                cost_tilt=self.pool.cost_tilt,
                                seed=self.seeds["service"],
                                feedback_capacity=sv["feedback_capacity"],
                                buckets=self.buckets))
        self.offer(mix, seconds, seed)

    def offer(self, mix: dict, seconds: float, seed: int):
        """The requests of a window of ``seconds`` under ``mix`` (and the
        warm-up's, which come after them)."""
        self.mix = mix
        n_warm = sum(self.sizes)
        n_win = int(float(mix["rate"]) * seconds * 1.5) + 2 * self.sizes[-1]
        self.reqs = tr.make_requests(mix, self.config["corpus"],
                                     self.seq_len, n_warm + n_win, seed)
        self.reqs.arrival = self.reqs.arrival[:n_win]
        self.ones = np.ones((self.sizes[-1], self.seq_len), np.float32)
        self.vote_rng = np.random.default_rng([seed, 4])
        self.y = np.zeros(self.reqs.tokens.shape[0], np.float32)
        self.tickets = np.full(self.reqs.tokens.shape[0], -1, np.int32)
        self.votes_due: list = []    # (due batch, order key, request)

    # -- one formed batch ----------------------------------------------------

    def _serve(self, ids: np.ndarray, bucket: int, in_window: bool):
        import jax
        import jax.numpy as jnp
        from repro.core import ccft
        from repro.serving import stream

        svc, sp = self.svc, self.spans
        n = ids.shape[0]
        with sp("form"):
            tok = stream.pad_rows(self.reqs.tokens[ids], bucket)
            mask = stream.pad_rows(self.ones[:n], bucket)
            prefs = None if self.reqs.pref is None else self.reqs.pref[ids]
        with sp("embed") as out:
            x = svc.embed(tok, mask)[:n]
            if self.n_meta:
                x = ccft.pad_queries(x, self.n_meta)
            out.append(x)
        with sp("route") as out:
            a1, a2, tickets = svc.route_stream(x, prefs=prefs)
            st = svc.state
            theta = jnp.stack([st.theta1, st.theta2])
            a1, a2, tickets = jax.device_get((a1, a2, tickets))
        t_done = time.perf_counter()
        self.n_calls += 1
        call = RouteCall(self.n_calls, ids, bucket, x, np.asarray(a1),
                         np.asarray(a2), theta, in_window)
        self.calls.append(call)
        self.log.append(call)
        self.tickets[ids] = tickets
        c = self.reqs.cats[ids]
        self.y[ids] = tr.votes(self.pool.utils, c, call.a1, call.a2,
                               self.reqs.u_vote[ids], self.pool.vote_scale)
        return t_done

    def cut(self, n: int) -> int:
        """The largest batch size of the ladder not above ``n``."""
        return max(b for b in self.sizes if b <= n)

    def _fold(self, ids: np.ndarray):
        """Deliver the votes of ``ids``, in order, in chunks whose sizes
        are on the ladder (largest first)."""
        sp = self.spans
        with sp("fold") as out:
            i = 0
            while i < ids.shape[0]:
                chunk = ids[i:i + self.cut(ids.shape[0] - i)]
                i += chunk.shape[0]
                out.append(self.svc.feedback_stream(self.tickets[chunk],
                                                    self.y[chunk]))
                self.log.append(Delivery(chunk, self.y[chunk]))
        return out

    # -- set-up --------------------------------------------------------------

    def warm_up(self):
        """Every batch size the window can form: embed, the route program
        the traffic takes, and a fold of all its votes at once.
        The program compiles small programs for each new batch size, so
        this is what keeps compilation out of the window."""
        import jax
        from repro.serving import stream
        start = self.reqs.arrival.shape[0]
        for n in self.sizes:
            ids = np.arange(start, start + n)
            start += n
            self._serve(ids, stream.bucket_for(n, self.buckets),
                        in_window=False)
            jax.block_until_ready(self._fold(ids))

    # -- the measured window -------------------------------------------------

    def window(self, seconds: float) -> dict:
        """Serve on the schedule for ``seconds``; returns the host-clock
        record of the window."""
        import jax
        from repro.serving import stream

        arr, n_all = self.reqs.arrival, self.reqs.arrival.shape[0]
        top = self.sizes[-1]
        latency, late, batches = [], [], []
        sp = self.spans
        served, k = 0, 0
        log0 = len(self.log)
        t_open = time.perf_counter()
        end = t_open + seconds

        def wait_until(t_rel):
            t = t_open + t_rel
            with sp("wait"):
                while True:
                    left = min(t, end) - time.perf_counter()
                    if left <= 0:
                        break
                    if left > 1e-3:
                        time.sleep(left - 5e-4)
            if t <= end:
                late.append(time.perf_counter() - t)

        last = None
        while served < n_all:
            now = time.perf_counter()
            if now >= end:
                break
            rel = now - t_open
            hi = int(np.searchsorted(arr, rel, side="right"))
            if hi == served:
                wait_until(arr[served])
                continue
            with sp("form"):
                seen = np.maximum(arr[served:min(hi, served + top)],
                                  rel - self.max_wait)
                fb = stream.form_batches(seen, self.buckets,
                                         self.max_wait)[0]
            # form_batches cuts at the ladder's top bucket; a cell may cap
            # its batches lower
            fb = fb._replace(n=min(fb.n, top))
            if fb.n < top and fb.t_form > rel:
                nxt = fb.t_form
                if served + top - 1 < n_all:
                    nxt = min(nxt, arr[served + top - 1])
                wait_until(nxt)
                continue
            n = self.cut(fb.n)
            bucket = stream.bucket_for(n, self.buckets)
            ids = np.arange(served, served + n)
            t_done = self._serve(ids, bucket, in_window=True)
            latency.append(t_done - t_open - arr[ids])
            batches.append((n, bucket))
            served += n
            for r in ids[self.reqs.labelled[ids]]:
                self.votes_due.append((k + int(self.reqs.lag[r]),
                                       self.vote_rng.random(), int(r)))
            due = sorted((v for v in self.votes_due if v[0] <= k),
                         key=lambda v: v[1])      # out of order
            if due:
                self.votes_due = [v for v in self.votes_due if v[0] > k]
                last = self._fold(np.array([v[2] for v in due]))
            k += 1
        if last is not None:
            jax.block_until_ready(last)
        t_close = time.perf_counter()
        lat = np.concatenate(latency) if latency else np.zeros(0)
        folds = [d for d in self.log[log0:] if isinstance(d, Delivery)]
        return {"latency_s": lat, "late_s": np.asarray(late),
                "batches": batches, "routed": int(lat.shape[0]),
                "window_s": t_close - t_open, "fold_calls": len(folds),
                "votes_folded": int(sum(d.ids.shape[0] for d in folds)),
                "votes_pending": len(self.votes_due)}
