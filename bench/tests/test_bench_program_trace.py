"""The reader of the program's own spans and kernel names
(``harness/program_trace.py``) and the per-layer metrics behind it, on a
small recorded trace whose answers are worked out by hand.

``data/program_trace.xplane.pb`` was recorded by ``jax.profiler`` on the
host CPU (Python tracer off) around one formed batch of a tiny streaming
``RouterService`` whose route and feedback programs had run once before:
``bench.embed`` around ``svc.embed`` (3 live rows in bucket 8, whose eager
layer scan compiles: a JAX backend compile inside ``router.embed``),
``bench.route`` around ``route_stream``, a 3 ms sleep in ``bench.wait`` and
``bench.fold`` around ``feedback_stream``. Its metadata plane (the HLO of
every program) was dropped to keep it small.
"""
import os
import shutil
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

from harness import program_trace as pt  # noqa: E402
from harness.trace import Event, Reduction, read_xplane  # noqa: E402

DATA = os.path.join(HERE, "data", "program_trace.xplane.pb")
OLD = os.path.join(HERE, "data", "cpu_trace.xplane.pb")
DEV, OPS = "/device:TPU:0", "XLA Ops"
CPU_OPS = "tf_XLAPjRtCpuClient/3717319268940654581"

# the router spans of the recorded trace (ns), by hand from the file
EMBED = (165_526, 276_647_547)
ROUTE = (298_721_814, 300_982_489)
FEEDBACK = (305_925_009, 307_398_979)
WINDOW = (25_582, 307_428_391)          # bench.embed start .. bench.fold end

# the kernels as the chip's profile names them after the rename (the
# served backward goes through a VJP; shapes abridged)
SGLD_BWD = ("%transpose_jvp_sgld_potential_bwd__.18 = f32[4,2,1,782]{3,2,1,0}"
            " custom-call(f32[4,1,1]{2,1,0} %copy.71, f32[4,1,782]{2,1,0} %r,"
            " f32[4,256,782]{2,1,0} %bitcast.243), "
            "custom_call_target=\"tpu_custom_call\"")
SGLD_FWD = ("%vmap_jvp_sgld_potential_fwd__.1 = f32[4,2,1,128]{3,2,1,0} "
            "custom-call(f32[4,1,782]{2,1,0} %r, f32[4,256,782]{2,1,0} %x), "
            "custom_call_target=\"tpu_custom_call\"")
SELECT = ("%dueling_select.1 = (s32[1,1024]{1,0:T(1,128)}, s32[1,1024]"
          "{1,0:T(1,128)}) custom-call(f32[1024,782]{1,0} %x), "
          "custom_call_target=\"tpu_custom_call\"")


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """``TRACE_DIR`` pointed at a directory holding the recorded trace."""
    shutil.copy(DATA, tmp_path / "x.xplane.pb")
    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path)
    return tmp_path


def _run(events):
    from harness import peaks
    fg = NS(n_chains=4, sgld_minibatch=256, sgld_steps=20)
    return NS(trace=Reduction(events), peaks=peaks.peaks_for("TPU v5 lite"),
              sess=NS(fgts_cfg=fg, d=782, pool=NS(names=list(range(11)))))


def _reader(name):
    from harness.cell import reader
    return reader(name)


def test_trace_path_is_the_runs():
    import run
    assert pt.TRACE_DIR == run.TRACE_DIR


def test_spans_and_their_metadata_by_hand():
    sp = pt.read_spans(DATA)
    assert [(s.name, s.start_ns, s.end_ns) for s in sp] == [
        ("embed",) + EMBED, ("route",) + ROUTE, ("feedback",) + FEEDBACK]
    embed, route, feedback = (s.stats for s in sp)
    # one formed batch: embed and route at tick 1, its votes one later
    assert [st["tick"] for st in (embed, route, feedback)] == [1, 1, 2]
    assert all(st["n"] == 3 and st["bucket"] == 8
               for st in (embed, route, feedback))
    # the eager encoder compiled once; the warm route and fold did not
    assert (embed["compiles"], route["compiles"], feedback["compiles"]) \
        == (1, 0, 0)
    assert embed["trace_s"] == pytest.approx(0.0008871555)
    assert embed["lower_s"] == pytest.approx(0.0414812565)
    assert embed["compile_s"] == pytest.approx(0.2048909664)
    assert route["lower_s"] == route["compile_s"] == 0.0
    # JAX's own compile event lies inside router.embed
    (comp,) = [e for e in read_xplane(DATA)
               if e.name == "backend_compile_and_load"]
    assert EMBED[0] < comp.start_ns < comp.start_ns + comp.dur_ns < EMBED[1]


def test_parse_is_cached_per_file():
    pt.read_spans(DATA)
    hits = pt._read.cache_info().hits
    assert pt.read_spans(DATA) is pt.read_spans(DATA)
    assert pt._read.cache_info().hits == hits + 2


def test_spans_clipped_to_the_window(trace_dir):
    # a window from inside embed to inside route: feedback falls out,
    # embed and route are cut at the window's edges
    run = NS(trace=NS(t0=200_000_000, t1=300_000_000))
    sp = pt.spans(run)
    assert [(s.name, s.start_ns, s.end_ns) for s in sp] == [
        ("embed", 200_000_000, EMBED[1]),
        ("route", ROUTE[0], 300_000_000)]
    assert pt.spans(run, "feedback") == []
    assert [s.name for s in pt.spans(run, "route")] == ["route"]


def test_idle_overlap_by_hand(trace_dir):
    # busy [200, 299) ms and [306, 306.5) ms; idle inside the spans:
    # embed 276482021 - (276647547 - 200e6), route 2260675 - (299e6 -
    # 298721814), feedback 1473970 - 500000
    busy = np.array([[200_000_000, 299_000_000],
                     [306_000_000, 306_500_000]], np.int64)
    run = NS(trace=NS(t0=0, t1=310_000_000, busy=busy))
    want = (276_482_021 - 76_647_547) + (2_260_675 - 278_186) \
        + (1_473_970 - 500_000)
    assert want == 202_790_933
    assert pt.idle_in_spans_s(run) == pytest.approx(want * 1e-9)


def test_idle_overlap_on_the_recorded_trace(trace_dir):
    # the CPU client's ops line as the device: 22.6 ms busy, nearly all of
    # it in embed; an independent sweep over the span and op boundaries
    # gives 279261041 ns idle inside the router spans
    red = Reduction(read_xplane(DATA), device_plane="/host:CPU",
                    ops_line=CPU_OPS)
    assert (red.t0, red.t1) == WINDOW
    run = NS(trace=red)
    idle = pt.idle_in_spans_s(run)
    assert idle == pytest.approx(279_261_041e-9)
    assert idle <= red.window_s - red.busy_s
    share = _reader("idle_in_program_share.lat")(run)
    assert share == pytest.approx(100 * 279_261_041 / 307_402_809)
    assert share <= 100 * red.idle_share
    assert _reader("idle_in_program_share.sat")(run) == share


def test_readers_by_hand(trace_dir):
    # the recorded host spans, with the chip's kernels laid over them: one
    # SGLD backward of 1 ms and one forward of 0.5 ms, a selection of 20 us,
    # all inside route
    ev = read_xplane(DATA) + [
        Event(DEV, OPS, SGLD_BWD, 299_000_000, 1_000_000),
        Event(DEV, OPS, SGLD_FWD, 300_000_000, 500_000),
        Event(DEV, OPS, SELECT, 300_600_000, 20_000),
        Event(DEV, OPS, "%fusion.95 = f32[8] fusion(sgld_potential_x)",
              300_700_000, 7_000)]
    run = _run(ev)
    st = pt.read_spans(DATA)[0].stats
    assert _reader("embed_lower_ms.lat")(run) == pytest.approx(
        (st["trace_s"] + st["lower_s"] + st["compile_s"]) * 1e3)
    assert _reader("route_host_ms.lat")(run) == pytest.approx(2.260675)
    # one route call; the fusion's operand text does not make it a kernel
    assert _reader("sgld_ms.lat")(run) == pytest.approx(1.5)
    assert _reader("select_us.lat")(run) == pytest.approx(20.0)
    # busy 1.527 ms, all inside route: idle in the spans is the rest
    inside = sum(b - a for a, b in (EMBED, ROUTE, FEEDBACK))
    assert pt.idle_in_spans_s(run) == pytest.approx(
        (inside - 1_527_000) * 1e-9)


NEW = ["embed_lower_ms.lat", "route_host_ms.lat", "sgld_ms.lat",
       "select_us.lat", "idle_in_program_share.lat",
       "idle_in_program_share.sat"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_reads_nothing(name, tmp_path, monkeypatch):
    # the older recorded trace has bench.* spans and no router.* span, as a
    # program before the spans would give
    shutil.copy(OLD, tmp_path / "old.xplane.pb")
    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path)
    assert _reader(name)(_run(read_xplane(OLD))) is None


def test_no_trace_file_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path)
    run = _run([Event("/host:CPU", "python", "bench.route", 0, 10)])
    assert pt.spans(run) == [] and pt.idle_in_spans_s(run) is None


def test_sgld_roofline_still_finds_the_renamed_kernels():
    # sgld_roofline matches shapes and tpu_custom_call, not names: both
    # renamed kernels are found, each priced as its kind
    from harness import costs
    ev = [Event("/host:CPU", "python", "bench.route", 0, 100_000),
          Event(DEV, OPS, SGLD_BWD, 1_000, 26_000),
          Event(DEV, OPS, SGLD_FWD, 30_000, 10_000)]
    run = _run(ev)
    m, k, d, c = 256, 11, 782, 4
    least = sum(costs.roofline_seconds(
        *costs.sgld_kernel_cost(kind, m, k, d, c), run.peaks)[0]
        for kind in ("bwd", "fwd"))
    assert least > 0
    assert _reader("sgld_roofline")(run) == pytest.approx(
        100 * least / 36e-6)
    one = _run(ev[:2])
    assert _reader("sgld_roofline")(one) == pytest.approx(100 * costs.
        roofline_seconds(*costs.sgld_kernel_cost("bwd", m, k, d, c),
                         one.peaks)[0] / 26e-6)
