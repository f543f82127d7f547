"""Spans and compile counters at RouterService's layer boundaries
(``serving/telemetry.py``), on a tiny streaming service on the CPU.

Pinned here: one ``router.embed`` / ``router.route`` / ``router.feedback``
host event per call in a profiler trace, each carrying ``tick``, ``n`` and
``bucket`` and, set as it closes, the compile-path metadata; one formed
batch's embed and route share their tick; a bucket-shaped embed runs its
AOT program and compiles nothing, while the route's eager work compiles at
a new batch size and not on a repeat; with no profiler
recording the span stack empties after every call and ``service_stats()``
still counts compiles; the spans leave ``compiled_program_counts()`` as it
was.
"""
import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fgts
from repro.serving import telemetry

KEY = jax.random.PRNGKey(3)
DIM, K, L = 16, 4, 8
META = ("compiles", "trace_s", "lower_s", "compile_s")


def _service():
    from repro.encoder import EncoderConfig, init_encoder
    from repro.serving import PoolEntry, RouterService, RouterServiceConfig
    enc_cfg = EncoderConfig(d_model=DIM, n_layers=1, n_heads=2, d_ff=32,
                            max_len=L)
    entries = [PoolEntry(name=f"m{i}", arch="granite-3-2b",
                         cost_per_1k_tokens=0.1 * (i + 1),
                         embedding=np.random.RandomState(i).randn(DIM)
                         .astype(np.float32)) for i in range(K)]
    cfg = RouterServiceConfig(
        fgts=fgts.FGTSConfig(n_models=K, dim=DIM, horizon=512, sgld_steps=2,
                             sgld_minibatch=4),
        feedback_capacity=128, buckets=(8, 16))
    return RouterService(entries, init_encoder(KEY, enc_cfg), enc_cfg, cfg)


def _batch(svc, n, bucket=8):
    """One formed batch of ``n`` live rows padded to ``bucket``: embed,
    route, and its votes fed back. Returns the open spans after each."""
    tok = np.zeros((bucket, L), np.int32)
    tok[:n] = np.arange(n * L).reshape(n, L) % 50
    mask = np.zeros((bucket, L), np.float32)
    mask[:n] = 1.0
    after = []
    x = svc.embed(tok, mask)[:n]
    after.append(telemetry.open_spans())
    _, _, tickets = svc.route_stream(x)
    after.append(telemetry.open_spans())
    svc.feedback_stream(tickets, jnp.ones((n,), jnp.float32))
    after.append(telemetry.open_spans())
    return after


def _router_events(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("router."):
                    out.append((e.start_ns, e.name, dict(e.stats)))
    return [(name, stats) for _, name, stats in sorted(out)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three batches under the profiler: 5 rows (new), 5 again, 3 (new).
    JAX keeps compiled eager ops for the whole process, keyed by shape, so
    any earlier test of the same shapes would leave nothing to compile:
    start from empty caches."""
    jax.clear_caches()
    svc = _service()
    log_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        for n in (5, 5, 3):
            _batch(svc, n)
    return svc, _router_events(log_dir)


def test_one_event_per_call_with_its_attributes(traced):
    _, events = traced
    names = [name for name, _ in events]
    assert names == ["router.embed", "router.route", "router.feedback"] * 3
    for (name, st), n in zip(events, [5] * 6 + [3] * 3):
        assert st["n"] == n
        assert st["bucket"] == 8
        assert set(META) <= set(st)
        assert st["compiles"] >= 0 and st["compile_s"] >= 0.0


def test_embed_and_route_of_a_batch_share_the_tick(traced):
    _, events = traced
    ticks = [st["tick"] for _, st in events]
    # per batch: embed and route at the tick on entry, feedback one later
    assert ticks == [0, 0, 1, 1, 1, 2, 2, 2, 3]


def test_first_size_compiles_and_a_repeat_route_does_not(traced):
    _, events = traced
    st = [s for _, s in events]
    embed, route = st[0::3], st[1::3]
    # the encoder was compiled per bucket at construction
    assert all(e["aot"] == 1 for e in embed)
    assert all(e["compiles"] == 0 and e["compile_s"] == 0.0 for e in embed)
    assert route[0]["compiles"] > 0
    assert route[1]["compiles"] == 0 and route[1]["compile_s"] == 0.0
    assert route[2]["compiles"] > 0          # 3 live rows: a new size


def test_embed_of_a_device_mask_names_no_live_rows(tmp_path):
    """``n`` is the rows with a token, counted only from a host mask: a
    device mask is not read, and the span carries its bucket alone."""
    svc = _service()
    tok = np.zeros((8, L), np.int32)
    mask = np.zeros((8, L), np.float32)
    mask[:2] = 1.0
    with jax.profiler.trace(str(tmp_path)):
        svc.embed(tok, mask)
        svc.embed(tok, jnp.asarray(mask))
    (host, device) = [st for _, st in _router_events(tmp_path)]
    assert host["n"] == 2 and host["bucket"] == 8
    assert "n" not in device and device["bucket"] == 8


def test_unrecorded_spans_close_and_still_count_compiles():
    jax.clear_caches()          # a first call compiles, whatever ran before
    svc = _service()
    before = svc.compiled_program_counts()
    assert telemetry.open_spans() == ()
    for n in (4, 4):
        assert _batch(svc, n) == [(), (), ()]
    stats = svc.service_stats()
    assert set(stats["compiles"]) == {"embed", "route", "feedback"}
    assert all(stats["compiles"][k] > 0 for k in ("route", "feedback"))
    assert all(stats["compile_s"][k] > 0.0 for k in ("route", "feedback"))
    # bucket-shaped embeds run the AOT encoder: nothing to compile
    assert stats["compiles"]["embed"] == 0
    assert stats["compile_s"]["embed"] == 0.0
    assert stats["n_routed"] == 8 and stats["n_folded"] == 8
    assert svc.compiled_program_counts() == before


def test_compile_counts_repeat_adds_no_route_compile():
    svc = _service()
    _batch(svc, 6)
    _batch(svc, 6)
    first = dict(svc.service_stats()["compiles"])
    _batch(svc, 6)
    again = svc.service_stats()["compiles"]
    assert again["route"] == first["route"]
    assert again["feedback"] == first["feedback"]


def test_compiles_land_in_the_innermost_span():
    counts = telemetry.CompileCounts()
    v = jnp.ones(7)
    with telemetry.span("outer", counts):
        with telemetry.span("inner", counts, tick=1):
            assert telemetry.open_spans() == ("outer", "inner")
            jax.jit(lambda v: v * 3.0 + 1.0)(v).block_until_ready()
    assert counts.compiles == {"outer": 0, "inner": 1}
    assert counts.compile_s["inner"] > 0.0
    assert telemetry.open_spans() == ()


def test_a_raising_call_closes_its_span():
    counts = telemetry.CompileCounts()
    with pytest.raises(ValueError):
        with telemetry.span("route", counts, n=3):
            raise ValueError("bad batch")
    assert telemetry.open_spans() == ()
    assert counts.compiles == {"route": 0}


def test_spans_are_per_thread():
    counts = telemetry.CompileCounts()
    seen = []
    v = jnp.ones(5)

    def other():
        seen.append(telemetry.open_spans())
        jax.jit(lambda v: v - 2.0)(v).block_until_ready()

    with telemetry.span("route", counts):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen == [()]
    assert counts.compiles == {"route": 0}


def test_nested_phase_intervals_count_once():
    assert telemetry._covered([]) == 0.0
    assert telemetry._covered([(1.0, 2.0), (0.0, 3.0), (5.0, 6.5)]) == \
        pytest.approx(4.5)
    assert telemetry._covered([(0.0, 1.0), (0.5, 2.0)]) == pytest.approx(2.0)
