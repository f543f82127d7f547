"""Streaming serving on the 8-device mesh: padding identity under GSPMD,
the shard-local pending ring's collective-free lowering, and strided
ticket encoding.

Needs ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the sharded
CI lane); on fewer devices everything here skips.

The headline acceptance pinned here: the compiled streaming **resolve**
program contains *zero* cross-device collectives — a ticket encodes the
shard that issued it, so feedback lookups and slot clears are device-local
(the legacy global ring gathers across devices on every resolve). The
fused route/feedback programs keep only the reductions inherent to the
algorithm (cost-scalar sum, replicated-posterior fold): no all-to-all,
collective-permute or reduce-scatter anywhere on the serving path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fgts

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

KEY = jax.random.PRNGKey(13)
DIM = 16
K = 4

# GSPMD scatter/shuffle collectives that must never appear on the
# streaming serving path (the shard-local ring's whole point), and the
# reduction collectives additionally banned from the resolve program.
SHUFFLE = ("all-to-all", "collective-permute", "reduce-scatter")
REDUCE = ("all-reduce", "all-gather")


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


def _mesh():
    from repro.launch import mesh as mesh_lib
    return mesh_lib.make_debug_mesh(4, 2)


def _state_eq(sa, sb):
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resolve_lowering_is_collective_free():
    """Feedback-path acceptance: the AOT resolve executable touches only
    this device's ring rows — its HLO has no collectives at all. The
    fused route/feedback keep reductions (cost sum, posterior fold) but
    never a scatter/shuffle collective."""
    svc = _service(mesh=_mesh())
    for b, prog in svc._s_resolve.items():
        hlo = prog.as_text()
        for op in SHUFFLE + REDUCE:
            assert op not in hlo, f"resolve[{b}] lowered a {op}"
    progs = [("route", svc._s_route)]
    if svc._s_route_pref is not None:
        progs.append(("route_pref", svc._s_route_pref))
    if svc._s_feedback is not None:
        progs.append(("feedback", svc._s_feedback))
    for name, table in progs:
        for b, prog in table.items():
            hlo = prog.as_text()
            for op in SHUFFLE:
                assert op not in hlo, f"{name}[{b}] lowered a {op}"


def test_tickets_are_shard_strided():
    """ticket = seq * n_shards + shard: a routed batch's tickets are
    strided over the 4 batch shards, so every ticket names its issuer."""
    svc = _service(mesh=_mesh())
    x = jax.random.normal(KEY, (8, DIM))
    _, _, t = svc.route_stream(x)
    t = np.asarray(t)
    assert set(t.tolist()) == set(range(8))
    # rows 2i, 2i+1 live on batch shard i: their tickets are ≡ i (mod 4)
    np.testing.assert_array_equal(t % 4, np.repeat(np.arange(4), 2))
    assert int(svc.feedback_stream(jnp.asarray(t), jnp.ones((8,)))) == 8


def test_bucket_padding_identity_on_mesh_with_prefs():
    """The padding-identity acceptance on the 8-device lane: a (16,)
    ladder reproduces the (8,) ladder's duel pairs and posterior bit for
    bit through GSPMD-sharded AOT programs, prefs included. (Tickets are
    the one thing allowed to differ on a mesh: padding shifts which shard
    owns a row, and a ticket names its issuing shard — opaque handles;
    each service resolves its own.)"""
    mesh = _mesh()
    svc_a = _service(buckets=(8,), mesh=mesh)
    svc_b = _service(buckets=(16,), mesh=mesh)
    x = jax.random.normal(KEY, (8, DIM))
    prefs = jnp.linspace(0.0, 2.0, 8)
    for r in range(3):
        p = None if r == 0 else prefs
        a1a, a2a, ta = svc_a.route_stream(x, prefs=p)
        a1b, a2b, tb = svc_b.route_stream(x, prefs=p)
        np.testing.assert_array_equal(np.asarray(a1a), np.asarray(a1b))
        np.testing.assert_array_equal(np.asarray(a2a), np.asarray(a2b))
        y = jax.random.choice(jax.random.fold_in(KEY, r),
                              jnp.asarray([-1.0, 1.0]), (8,))
        assert int(svc_a.feedback_stream(ta, y)) == 8
        assert int(svc_b.feedback_stream(tb, y)) == 8
    _state_eq(svc_a.state, svc_b.state)
    assert svc_a.pending_count() == svc_b.pending_count() == 0


def test_factory_policy_padding_identity_on_mesh():
    """Partitionable per-row randomness: padding identity holds for the
    GSPMD act path of factory policies too (uniform has per-row draws and
    the compaction feedback fallback)."""
    from repro.core import baselines

    def factory(a_emb, costs, cfg):
        return baselines.uniform_policy(cfg.fgts.n_models)

    mesh = _mesh()
    svc_a = _service(buckets=(8,), mesh=mesh, policy_factory=factory)
    svc_b = _service(buckets=(16,), mesh=mesh, policy_factory=factory)
    x = jax.random.normal(KEY, (8, DIM))
    for r in range(2):
        a1a, a2a, ta = svc_a.route_stream(x)
        a1b, a2b, tb = svc_b.route_stream(x)
        np.testing.assert_array_equal(np.asarray(a1a), np.asarray(a1b))
        np.testing.assert_array_equal(np.asarray(a2a), np.asarray(a2b))
        assert int(svc_a.feedback_stream(ta, jnp.ones((8,)))) == 8
        assert int(svc_b.feedback_stream(tb, jnp.ones((8,)))) == 8
    assert svc_a.pending_count() == svc_b.pending_count() == 0


def test_mesh_zero_recompiles_mixed_sizes(assert_flat):
    """Mixed-size streaming traffic on the mesh compiles nothing after
    construction (batch sizes must divide over the 4 batch shards)."""
    svc = _service(buckets=(8, 16), mesh=_mesh())
    with assert_flat(svc, note="mesh mixed-size sweep") as flat:
        for i, n in enumerate([4, 8, 12, 16, 8, 4]):
            x = jax.random.normal(jax.random.fold_in(KEY, i), (n, DIM))
            prefs = None if i % 2 else jnp.linspace(0.0, 1.0, n)
            a1, a2, t = svc.route_stream(x, prefs=prefs)
            assert t.shape == (n,)
            assert int(svc.feedback_stream(t, jnp.ones((n,)))) == n
            flat.check(f"n={n}")
    assert svc.pending_count() == 0


def test_mesh_streaming_checkpoint_roundtrip(tmp_path):
    """Streaming checkpoint crosses the mesh boundary: saved on the mesh,
    restored onto the mesh, in-flight strided tickets still resolve."""
    mesh = _mesh()
    svc, svc2 = _service(mesh=mesh), _service(mesh=mesh)
    x = jax.random.normal(KEY, (8, DIM))
    _, _, t0 = svc.route_stream(x)
    svc.save(str(tmp_path))
    svc2.restore(str(tmp_path))
    assert svc2.pending_count() == 8 and svc2.tick == svc.tick
    outs = []
    for s in (svc, svc2):
        assert int(s.feedback_stream(t0, jnp.ones((8,)))) == 8
        a1, a2, _ = s.route_stream(x)
        outs.append((np.asarray(a1), np.asarray(a2), s.state))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    _state_eq(outs[0][2], outs[1][2])


def test_mesh_embed_bucket_program_matches_single_device():
    """Under the mesh each bucket's encoder runs with the weights
    replicated and the rows batch-sharded: it gives the single-device
    program's embeddings, already sharded as ``route_stream`` takes its
    queries, with no collective and nothing compiled per call."""
    svc1 = _service(buckets=(8, 16))
    svcm = _service(buckets=(8, 16), mesh=_mesh())
    for b in (8, 16):
        tok = (np.arange(b * 8).reshape(b, 8) * 7 % 50).astype(np.int32)
        mask = np.ones((b, 8), np.float32)
        mask[b - 2:] = 0.0
        xm = svcm.embed(tok, mask)
        np.testing.assert_allclose(np.asarray(xm),
                                   np.asarray(svc1.embed(tok, mask)),
                                   atol=1e-5)
        assert xm.sharding == svcm._x_sh
        hlo = svcm._s_embed[b].as_text()
        for op in SHUFFLE + REDUCE:
            assert op not in hlo, f"embed[{b}] lowered a {op}"
        _, _, t = svcm.route_stream(xm)
        assert int(svcm.feedback_stream(t, jnp.ones((b,)))) == b
    stats = svcm.service_stats()
    assert stats["embed_calls"] == {"aot": 2, "jit": 0}
    assert stats["compiles"]["embed"] == 0
