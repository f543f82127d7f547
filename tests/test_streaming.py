"""Event-time streaming serving core: arrival/forming host layer, the
shard-local ring, and the AOT bucket-program surface of ``RouterService``.

Contracts pinned here (ISSUE 9 acceptance):

  * padding buckets are masked end to end — routing n rows through a
    larger bucket is **bit-identical** (pairs, tickets, posterior) to
    routing them through an exactly-sized bucket, for every policy in the
    serve driver's registry, with and without per-request prefs;
  * the streaming surface compiles everything ahead of time — a mixed-size
    traffic sweep over arbitrary batch sizes compiles **zero** new
    programs after construction;
  * ``init_pending`` enforces the power-of-two capacity contract (and the
    shard-local layout's pow2/divisibility contracts) by raising;
  * the strided ticket encoding of ``enqueue_stream``/``resolve_stream``
    round-trips with masked padding, dedup and staleness intact;
  * the query encoder runs one AOT program per bucket: a bucket-shaped
    ``embed`` equals ``encode`` and compiles nothing, any other shape
    compiles once under the service's jit, and ``embed_calls`` counts
    both ways;
  * ``env.run(DelaySpec(per_item=True))`` with a constant lag is
    bit-identical to the per-tick lag, and raises for policies without a
    masked fold;
  * the host batch former respects the max-wait deadline and partitions
    the arrival stream.

The mesh half (8-device lowering audit: no cross-device scatter on the
feedback path) lives in ``test_streaming_mesh.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import env, fgts, policy
from repro.serving import feedback_queue as fq
from repro.serving import stream

KEY = jax.random.PRNGKey(11)
DIM = 16
K = 4


def _cfg(**kw):
    d = dict(n_models=K, dim=DIM, horizon=512, sgld_steps=2,
             sgld_minibatch=4)
    d.update(kw)
    return fgts.FGTSConfig(**d)


def _service(buckets=(8, 16), mesh=None, **cfg_kw):
    from repro.encoder import EncoderConfig, init_encoder
    from repro.serving import PoolEntry, RouterService, RouterServiceConfig
    enc_cfg = EncoderConfig(d_model=DIM, n_layers=1, n_heads=2, d_ff=32,
                            max_len=8)
    enc = init_encoder(KEY, enc_cfg)
    entries = [PoolEntry(name=f"m{i}", arch="granite-3-2b",
                         cost_per_1k_tokens=0.1 * (i + 1),
                         embedding=np.random.RandomState(i).randn(DIM)
                         .astype(np.float32)) for i in range(K)]
    cfg = RouterServiceConfig(fgts=_cfg(), feedback_capacity=128,
                              buckets=buckets, **cfg_kw)
    return RouterService(entries, enc, enc_cfg, cfg, mesh=mesh)


def _state_eq(sa, sb):
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# stream.py host layer: arrival specs, generators, forming, buckets
# ---------------------------------------------------------------------------

def test_parse_arrival_specs():
    s = stream.parse_arrival("poisson:800")
    assert s.kind == "poisson" and s.rate == 800.0
    s = stream.parse_arrival("bursty:400,8")
    assert s.kind == "bursty" and s.rate == 400.0 and s.burst == 8.0
    assert stream.parse_arrival("bursty:400").burst == 16.0
    s = stream.parse_arrival("diurnal:100,0.25,30")
    assert (s.kind, s.depth, s.period) == ("diurnal", 0.25, 30.0)
    assert stream.parse_arrival("diurnal:100").depth == 0.5
    for bad in ("poisson", "poisson:", "poisson:a", "weibull:3",
                "poisson:1,2", "diurnal:100,0.5,60,9", "poisson:-5",
                "diurnal:100,1.5"):
        with pytest.raises(ValueError):
            stream.parse_arrival(bad)


@pytest.mark.parametrize("spec", ["poisson:500", "bursty:500,8",
                                  "diurnal:500,0.5,10"])
def test_arrival_times_sorted_and_rate(spec):
    """Each generator emits n sorted nonnegative times whose long-run rate
    matches the spec (bursty/diurnal match poisson's mean by design)."""
    n = 4000
    t = stream.arrival_times(stream.parse_arrival(spec), n, seed=3)
    assert t.shape == (n,) and (np.diff(t) >= 0).all() and (t >= 0).all()
    rate = n / t[-1]
    assert 0.8 * 500 < rate < 1.25 * 500, (spec, rate)


def test_arrival_seeds_and_determinism():
    s = stream.parse_arrival("poisson:100")
    a = stream.arrival_times(s, 64, seed=0)
    np.testing.assert_array_equal(a, stream.arrival_times(s, 64, seed=0))
    assert not np.array_equal(a, stream.arrival_times(s, 64, seed=1))


def test_validate_buckets():
    assert stream.validate_buckets([16, 4, 4, 8]) == (4, 8, 16)
    for bad in ([], [12], [0], [8, 10]):
        with pytest.raises(ValueError):
            stream.validate_buckets(bad)
    assert stream.validate_buckets([8, 16], n_shards=4) == (8, 16)
    with pytest.raises(ValueError, match="shards"):
        stream.validate_buckets([2, 16], n_shards=4)


def test_bucket_for():
    assert stream.bucket_for(1, (4, 8)) == 4
    assert stream.bucket_for(4, (4, 8)) == 4
    assert stream.bucket_for(5, (4, 8)) == 8
    with pytest.raises(ValueError, match="largest"):
        stream.bucket_for(9, (4, 8))


def test_form_batches_partitions_and_respects_deadline():
    spec = stream.parse_arrival("bursty:800,8")
    times = stream.arrival_times(spec, 1000, seed=1)
    buckets, max_wait = (4, 16), 0.01
    fb = stream.form_batches(times, buckets, max_wait)
    # exact partition of the stream, in order
    assert fb[0].start == 0
    for a, b in zip(fb, fb[1:]):
        assert b.start == a.start + a.n
    assert fb[-1].start + fb[-1].n == 1000
    for f in fb:
        assert 1 <= f.n <= f.bucket <= buckets[-1]
        assert f.bucket == stream.bucket_for(f.n, buckets)
        # the oldest row never waits past its deadline, and the batch is
        # never cut before the bucket fills or the deadline hits
        assert f.t_form - times[f.start] <= max_wait + 1e-9
        if f.n < buckets[-1]:
            assert f.t_form == pytest.approx(times[f.start] + max_wait)
    # a bursty stream at 800 qps with a 10ms deadline must actually fill
    # the big bucket sometimes AND cut short batches sometimes
    sizes = {f.bucket for f in fb}
    assert buckets[-1] in sizes and buckets[0] in sizes


def test_form_batches_zero_wait_ships_singletons():
    times = np.array([0.0, 0.0, 1.0])
    fb = stream.form_batches(times, (4,), 0.0)
    # simultaneous arrivals still batch; the lone one ships alone
    assert [(f.start, f.n) for f in fb] == [(0, 2), (2, 1)]


def test_pad_rows():
    x = np.ones((3, 2), np.float32)
    p = stream.pad_rows(x, 8)
    assert p.shape == (8, 2) and (p[3:] == 0).all() and (p[:3] == 1).all()
    assert stream.pad_rows(x, 3) is x
    j = stream.pad_rows(jnp.ones((3,)), 4)
    assert j.shape == (4,) and float(j.sum()) == 3.0
    with pytest.raises(ValueError, match="fit"):
        stream.pad_rows(x, 2)


# ---------------------------------------------------------------------------
# ring contracts: pow2 validation, strided shard-local tickets
# ---------------------------------------------------------------------------

def test_init_pending_rejects_non_pow2_capacity():
    """Regression (ISSUE 9 satellite): slot = ticket % capacity is only
    collision-free across the int32 wrap when capacity divides 2^32."""
    for cap in (24, 3, 100, 127):
        with pytest.raises(ValueError, match="power of two"):
            fq.init_pending(cap, DIM)
    q = fq.init_pending(fq.next_pow2(100), DIM)
    assert q.x.shape == (128, DIM)
    assert [fq.next_pow2(n) for n in (0, 1, 2, 3, 8, 9)] == \
        [1, 1, 2, 4, 8, 16]
    with pytest.raises(ValueError, match="shards"):
        fq.init_pending(64, DIM, shards=3)
    with pytest.raises(ValueError, match="divide"):
        fq.init_pending(4, DIM, shards=8)


def test_enqueue_stream_masked_padding_and_tickets():
    q = fq.init_pending(16, 2, shards=1)
    x = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    a = jnp.arange(6, dtype=jnp.int32)
    mask = jnp.asarray([True, True, True, True, False, False])
    q, t = fq.enqueue_stream(q, x, a, a, jnp.int32(1),
                             jnp.zeros((6,)), mask, 0, 1)
    np.testing.assert_array_equal(np.asarray(t), [0, 1, 2, 3, -1, -1])
    assert int(fq.pending_count(q)) == 4          # padding never written
    # second masked batch continues the sequence
    q, t2 = fq.enqueue_stream(q, x, a, a, jnp.int32(2),
                              jnp.zeros((6,)), mask, 0, 1)
    np.testing.assert_array_equal(np.asarray(t2), [4, 5, 6, 7, -1, -1])


def test_resolve_stream_dedup_stale_and_padding():
    q = fq.init_pending(16, 2, shards=1)
    x = jnp.ones((8, 2))
    a = jnp.zeros((8,), jnp.int32)
    ones = jnp.ones((8,))
    mask = jnp.ones((8,), bool)
    q, t = fq.enqueue_stream(q, x, a, a, jnp.int32(1), jnp.zeros((8,)),
                             mask, 0, 1)
    # duplicates fold once; masked rows never validate (-1 padding tickets)
    dup = jnp.concatenate([t[:3], t[:3], jnp.full((2,), -1, jnp.int32)])
    m2 = jnp.asarray([True] * 6 + [False] * 2)
    q, res = fq.resolve_stream(q, dup, ones, m2, jnp.int32(2), 0, 1)
    np.testing.assert_array_equal(
        np.asarray(res.ok), [True] * 3 + [False] * 5)
    # the consumed slots are gone; the rest still resolve
    q, res = fq.resolve_stream(q, t, ones, mask, jnp.int32(2), 0, 1)
    np.testing.assert_array_equal(
        np.asarray(res.ok), [False] * 3 + [True] * 5)
    assert int(fq.pending_count(q)) == 0


def test_resolve_stream_shard_ownership():
    """A ticket delivered to a shard that did not issue it fails the
    ownership test instead of clearing a foreign slot."""
    q = fq.init_pending(16, 2, shards=2)      # local view of shard 1
    x = jnp.ones((4, 2))
    a = jnp.zeros((4,), jnp.int32)
    mask = jnp.ones((4,), bool)
    q, t = fq.enqueue_stream(q, x, a, a, jnp.int32(1), jnp.zeros((4,)),
                             mask, 1, 2)
    np.testing.assert_array_equal(np.asarray(t), [1, 3, 5, 7])  # strided
    ones = jnp.ones((4,))
    _, res = fq.resolve_stream(q, t, ones, mask, jnp.int32(1), 0, 2)
    assert not np.asarray(res.ok).any()       # shard 0 owns none of these
    q, res = fq.resolve_stream(q, t, ones, mask, jnp.int32(1), 1, 2)
    assert np.asarray(res.ok).all()
    assert int(fq.pending_count(q)) == 0


# ---------------------------------------------------------------------------
# RouterService streaming surface (single device)
# ---------------------------------------------------------------------------

def _policy_factories():
    from repro.launch.serve import POLICIES
    return sorted(POLICIES)


@pytest.mark.parametrize("name", _policy_factories())
def test_bucket_padding_identity_every_registered_policy(name):
    """The tentpole identity: n rows through a 2x-padded bucket reproduce
    the exactly-sized bucket bit for bit — pairs, tickets, and posterior —
    for every policy the serve driver can host (masked-fold policies take
    the fused feedback program, the rest the compaction fallback)."""
    from repro.launch.serve import POLICIES
    factory = POLICIES[name]
    svc_a = _service(buckets=(8,), policy_factory=factory)
    svc_b = _service(buckets=(16,), policy_factory=factory)
    x = jax.random.normal(KEY, (8, DIM))
    for r in range(3):
        a1a, a2a, ta = svc_a.route_stream(x)
        a1b, a2b, tb = svc_b.route_stream(x)      # 8 rows of padding
        np.testing.assert_array_equal(np.asarray(a1a), np.asarray(a1b))
        np.testing.assert_array_equal(np.asarray(a2a), np.asarray(a2b))
        np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
        y = jax.random.choice(jax.random.fold_in(KEY, r),
                              jnp.asarray([-1.0, 1.0]), (8,))
        na = int(svc_a.feedback_stream(ta, y))
        nb = int(svc_b.feedback_stream(tb, y))
        assert na == nb == 8
    _state_eq(svc_a.state, svc_b.state)
    assert svc_a.pending_count() == svc_b.pending_count() == 0


def test_bucket_padding_identity_with_prefs():
    svc_a, svc_b = _service(buckets=(8,)), _service(buckets=(16,))
    x = jax.random.normal(KEY, (8, DIM))
    prefs = jnp.linspace(0.0, 2.0, 8)
    for r in range(2):
        a1a, a2a, ta = svc_a.route_stream(x, prefs=prefs)
        a1b, a2b, tb = svc_b.route_stream(x, prefs=prefs)
        np.testing.assert_array_equal(np.asarray(a1a), np.asarray(a1b))
        np.testing.assert_array_equal(np.asarray(a2a), np.asarray(a2b))
        np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
        y = jax.random.choice(jax.random.fold_in(KEY, 20 + r),
                              jnp.asarray([-1.0, 1.0]), (8,))
        assert int(svc_a.feedback_stream(ta, y)) == 8
        assert int(svc_b.feedback_stream(tb, y)) == 8
    _state_eq(svc_a.state, svc_b.state)


def test_streaming_zero_recompiles_mixed_sizes(assert_flat):
    """Every serving program is AOT-compiled at construction: a mixed-size
    sweep (every n from 1 to the ladder top, prefs on and off, feedback
    after every route) compiles nothing — the zero-recompile acceptance."""
    svc = _service(buckets=(4, 16))
    counts = svc.compiled_program_counts()
    assert counts["s_route"] == counts["s_resolve"] == 2
    rng = np.random.default_rng(0)
    with assert_flat(svc, note="mixed-size streaming sweep") as flat:
        for i, n in enumerate([1, 3, 4, 5, 11, 16, 2, 7, 13]):
            x = jnp.asarray(rng.normal(size=(n, DIM)), jnp.float32)
            prefs = (None if i % 2 else
                     jnp.asarray(rng.uniform(size=(n,)), jnp.float32))
            a1, a2, t = svc.route_stream(x, prefs=prefs)
            assert a1.shape == a2.shape == t.shape == (n,)
            assert int(svc.feedback_stream(t, jnp.ones((n,)))) == n
            flat.check(f"n={n}")
    assert svc.n_routed == 62 and svc.pending_count() == 0


def test_route_batch_delegates_to_stream():
    """With buckets configured, the classic route/feedback_batch entry
    points serve through the AOT bucket programs (one service object, one
    code path for callers)."""
    svc = _service(buckets=(8,))
    x = jax.random.normal(KEY, (5, DIM))
    a1, a2, t = svc.route_batch(x)
    assert t.shape == (5,)
    assert int(svc.feedback_batch(t, jnp.ones((5,)))) == 5
    assert svc.pending_count() == 0


def test_streaming_host_device_tick_lockstep():
    svc = _service(buckets=(8,))
    x = jax.random.normal(KEY, (8, DIM))
    for _ in range(3):
        _, _, t = svc.route_stream(x)
        svc.feedback_stream(t, jnp.ones((8,)))
    assert svc.tick == int(svc._tick_dev) == 3


def test_streaming_validation_errors():
    svc = _service(buckets=(8,))
    x = jax.random.normal(KEY, (9, DIM))
    with pytest.raises(ValueError, match="largest"):
        svc.route_stream(x)                       # above the ladder
    with pytest.raises(ValueError, match="prefs shape"):
        svc.route_stream(x[:4], prefs=jnp.zeros((3,)))
    with pytest.raises(ValueError, match="tickets shape"):
        svc.feedback_stream(jnp.zeros((4,), jnp.int32), jnp.zeros((3,)))
    plain = _service(buckets=None)
    with pytest.raises(RuntimeError, match="buckets"):
        plain.route_stream(x[:4])
    with pytest.raises(RuntimeError, match="buckets"):
        plain.feedback_stream(jnp.zeros((4,), jnp.int32), jnp.zeros((4,)))
    from repro.serving import RouterServiceConfig
    with pytest.raises(ValueError, match="powers of two"):
        _service(buckets=(6,))


def test_streaming_checkpoint_roundtrip(tmp_path):
    """Mid-flight streaming checkpoint: the shard-local ring, per-shard
    ticket counters and the device tick restore and continue identically."""
    svc, svc2 = _service(buckets=(8,)), _service(buckets=(8,))
    x0 = jax.random.normal(KEY, (6, DIM))
    x1 = jax.random.normal(jax.random.fold_in(KEY, 1), (8, DIM))
    _, _, t0 = svc.route_stream(x0)
    svc.save(str(tmp_path))
    svc2.restore(str(tmp_path))
    assert svc2.pending_count() == 6
    assert svc2.tick == svc.tick == int(svc2._tick_dev)
    outs = []
    for s in (svc, svc2):
        assert int(s.feedback_stream(t0, jnp.ones((6,)))) == 6
        a1, a2, t = s.route_stream(x1)
        outs.append((np.asarray(a1), np.asarray(a2), np.asarray(t),
                     s.state))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    np.testing.assert_array_equal(outs[0][2], outs[1][2])
    _state_eq(outs[0][3], outs[1][3])


def test_feedback_direct_resolves_streaming_ring():
    """feedback_direct (vote + ground-truth embedding path) consumes
    streaming tickets through the AOT resolve, not the legacy global
    layout."""
    svc = _service(buckets=(8,))
    x = jax.random.normal(KEY, (4, DIM))
    a1, a2, t = svc.route_stream(x)
    assert svc.pending_count() == 4
    svc.feedback_direct(x, a1, a2, jnp.ones((4,)), tickets=t)
    assert svc.pending_count() == 0


# ---------------------------------------------------------------------------
# the query encoder's bucket programs (RouterService.embed)
# ---------------------------------------------------------------------------

SEQ = 8                                   # _service's encoder max_len


def _queries(b, seq=SEQ, live=None):
    """(b, seq) int32 tokens and a float32 pooling mask whose first
    ``live`` rows (all, by default) hold a query."""
    tok = (np.arange(b * seq).reshape(b, seq) * 7 % 50).astype(np.int32)
    mask = np.zeros((b, seq), np.float32)
    mask[:b if live is None else live] = 1.0
    return tok, mask


@pytest.mark.parametrize("bucket", [8, 16])
def test_embed_bucket_program_matches_encode(bucket):
    """A bucket-shaped batch runs the bucket's AOT encoder and gives what
    ``encode`` gives, padding rows included."""
    from repro.encoder.model import encode
    svc = _service(buckets=(8, 16))
    tok, mask = _queries(bucket, live=bucket - 3)
    got = svc.embed(tok, mask)
    want = encode(svc.enc_params, tok, mask, svc.enc_cfg)
    assert got.shape == (bucket, DIM)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert svc.service_stats()["embed_calls"] == {"aot": 1, "jit": 0}


def test_embed_at_every_bucket_compiles_nothing(assert_flat):
    """The encoder is compiled per bucket at construction: repeated embeds
    at every bucket, host or device rows, compile no program and the
    ``router.embed`` spans count no compile."""
    svc = _service(buckets=(4, 8, 16))
    assert svc.compiled_program_counts()["s_embed"] == 3
    with assert_flat(svc, note="bucket embeds") as flat:
        for _ in range(2):
            for b in (4, 8, 16):
                tok, mask = _queries(b)
                svc.embed(tok, mask)
                svc.embed(jnp.asarray(tok), jnp.asarray(mask))
                flat.check(f"bucket={b}")
    stats = svc.service_stats()
    assert stats["compiles"] == {"embed": 0}
    assert stats["compile_s"] == {"embed": 0.0}
    assert stats["embed_calls"] == {"aot": 12, "jit": 0}


@pytest.mark.parametrize("buckets,b,seq", [
    ((8, 16), 5, SEQ),       # a batch size off the ladder
    ((8, 16), 8, SEQ // 2),  # a bucket, at another sequence length
    (None, 8, SEQ),          # a service without buckets
])
def test_embed_off_ladder_compiles_once_per_shape(buckets, b, seq):
    """Any other shape runs ``encode`` under the service's one jit: it
    compiles on its first call and never after."""
    from repro.encoder.model import encode
    svc = _service(buckets=buckets)
    tok, mask = _queries(b, seq)
    jit0 = svc.compiled_program_counts()["embed_jit"]
    got = svc.embed(tok, mask)
    first = svc.service_stats()["compiles"]["embed"]
    assert first >= 1
    assert svc.compiled_program_counts()["embed_jit"] == jit0 + 1
    for _ in range(2):
        svc.embed(tok, mask)
    assert svc.service_stats()["compiles"]["embed"] == first
    assert svc.compiled_program_counts()["embed_jit"] == jit0 + 1
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(encode(svc.enc_params, tok, mask, svc.enc_cfg)),
        atol=1e-5)
    assert svc.service_stats()["embed_calls"] == {"aot": 0, "jit": 3}


def test_embed_calls_count_aot_and_jit():
    svc = _service(buckets=(8, 16))
    for b, seq in [(8, SEQ), (16, SEQ), (5, SEQ), (8, SEQ), (16, 4)]:
        svc.embed(*_queries(b, seq))
    assert svc.service_stats()["embed_calls"] == {"aot": 3, "jit": 2}


def test_embed_programs_survive_restore(tmp_path):
    """``restore`` brings back the posterior, ring and clock; the encoder
    is not in the checkpoint, so its bucket programs keep serving the
    restored service, with nothing to compile."""
    svc, svc2 = _service(buckets=(8,)), _service(buckets=(8,))
    tok, mask = _queries(8, live=6)
    _, _, t = svc.route_stream(svc.embed(tok, mask)[:6])
    svc.save(str(tmp_path))
    progs = dict(svc2._s_embed)
    svc2.restore(str(tmp_path))
    assert svc2._s_embed == progs
    x = svc2.embed(tok, mask)
    np.testing.assert_array_equal(np.asarray(x),
                                  np.asarray(svc.embed(tok, mask)))
    stats = svc2.service_stats()
    assert stats["embed_calls"] == {"aot": 1, "jit": 0}
    assert stats["compiles"]["embed"] == 0
    assert int(svc2.feedback_stream(t, jnp.ones((6,)))) == 6


# ---------------------------------------------------------------------------
# env.run per-item event-time lag
# ---------------------------------------------------------------------------

def _world(t=24, cfg=None, key=KEY):
    cfg = cfg or _cfg(horizon=32, dim=8)
    ks = jax.random.split(key, 3)
    a_emb = jax.random.normal(ks[0], (cfg.n_models, cfg.dim))
    e = env.EnvData(x=jax.random.normal(ks[1], (t, cfg.dim)),
                    utils=jax.random.uniform(ks[2], (t, cfg.n_models)))
    return e, a_emb, cfg


def test_env_per_item_constant_lag_bit_identical_to_per_tick():
    """DelaySpec(per_item=True) with a constant lag puts every row of a
    tick on the same due tick — the masked fold must reproduce the
    per-tick cond'd fold bit for bit (the ISSUE's pinned identity)."""
    e, a_emb, cfg = _world()
    pol = policy.fgts_policy(a_emb, cfg)
    for d in (1, 3):
        cum_t, st_t = env.run(KEY, e, pol, batch=2, delay=d)
        cum_i, st_i = env.run(KEY, e, pol, batch=2,
                              delay=env.DelaySpec(delay=d, per_item=True))
        np.testing.assert_array_equal(np.asarray(cum_t), np.asarray(cum_i))
        _state_eq(st_t, st_i)


def test_env_per_item_geometric_lag_differs_and_stays_sane():
    """Per-item geometric lags draw one lag per row: the trajectory is a
    genuinely different (but finite, monotone-regret) process from the
    per-tick draw at the same spec."""
    e, a_emb, cfg = _world()
    pol = policy.fgts_policy(a_emb, cfg)
    spec = dict(delay=1, geom_p=0.4, max_lag=6)
    cum_t, st_t = env.run(KEY, e, pol, batch=2,
                          delay=env.DelaySpec(**spec))
    cum_i, st_i = env.run(KEY, e, pol, batch=2,
                          delay=env.DelaySpec(per_item=True, **spec))
    c = np.asarray(cum_i)
    assert np.isfinite(c).all() and (np.diff(c) >= -1e-6).all()
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(st_t),
                               jax.tree.leaves(st_i)))


def test_env_per_item_with_prefs():
    """Event-time lags compose with per-request prefs: each row's duel
    folds through update_pref with the pref it was served under."""
    e, a_emb, cfg = _world()
    costs = jnp.linspace(0.1, 0.4, cfg.n_models)
    pol = policy.fgts_policy(a_emb, cfg, costs=costs)

    def pref_fn(step, x_b):
        return jnp.full((x_b.shape[0],), 0.5) * (step % 3)

    cum_t, st_t = env.run(KEY, e, pol, batch=2, delay=2, pref_fn=pref_fn)
    cum_i, st_i = env.run(KEY, e, pol, batch=2,
                          delay=env.DelaySpec(delay=2, per_item=True),
                          pref_fn=pref_fn)
    np.testing.assert_array_equal(np.asarray(cum_t), np.asarray(cum_i))
    _state_eq(st_t, st_i)


def test_env_per_item_requires_masked_fold():
    from repro.core import baselines
    e, a_emb, cfg = _world()
    uni = baselines.uniform_policy(cfg.n_models)
    assert uni.update_masked is None
    with pytest.raises(ValueError, match="masked"):
        env.run(KEY, e, uni, batch=2,
                delay=env.DelaySpec(delay=2, per_item=True))
