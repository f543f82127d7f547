"""The reduction from a profiler trace to busy time, idle share, per-span
device time and the breakdown, on small traces whose answers are worked
out by hand."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

from harness.trace import Event, Reduction, read_xplane  # noqa: E402

DEV, OPS, HOST = "/device:TPU:0", "XLA Ops", "/host:CPU"


def _events():
    # host spans (ns): embed [0, 100), route [100, 160), fold [160, 200)
    # device ops: e1 [10, 40), e2 [30, 70) overlap -> busy [10, 70);
    # r1 [110, 130); f1 [170, 175); one op on another device is ignored
    return [
        Event(HOST, "python", "bench.embed", 0, 100),
        Event(HOST, "python", "bench.route", 100, 60),
        Event(HOST, "python", "bench.fold", 160, 40),
        Event(DEV, OPS, "fusion.1", 10, 30),
        Event(DEV, OPS, "fusion.2", 30, 40),
        Event(DEV, OPS, "sgld_bwd", 110, 20),
        Event(DEV, OPS, "fusion.1", 170, 5),
        Event("/device:TPU:1", OPS, "fusion.9", 0, 200),
        Event(DEV, "XLA Modules", "jit_route", 100, 60),
    ]


def test_busy_idle_and_spans_by_hand():
    r = Reduction(_events())
    assert r.window_s == pytest.approx(200e-9)
    # busy: [10, 70) + [110, 130) + [170, 175) = 60 + 20 + 5 = 85 ns
    assert r.busy_s == pytest.approx(85e-9)
    assert r.idle_share == pytest.approx(1 - 85 / 200)
    assert r.span_device_s("embed") == pytest.approx(60e-9)
    assert r.span_device_s("route") == pytest.approx(20e-9)
    assert r.span_device_s("fold") == pytest.approx(5e-9)
    assert r.span_device_s("wait") == 0.0
    assert r.span_count("embed") == 1
    assert [e.name for e in r.op_events(lambda n: "sgld" in n)] == \
        ["sgld_bwd"]


def test_breakdown_by_hand():
    b = Reduction(_events()).breakdown()
    # per op name: fusion.2 40, fusion.1 30 + 5 = 35, sgld_bwd 20
    assert [n for n, _ in b["device_ops"]] == ["fusion.2", "fusion.1",
                                                "sgld_bwd"]
    assert b["device_ops"][1][1] == pytest.approx(35e-9)
    # gaps: [0,10) embed, [70,110) 30 embed + 10 route -> embed,
    # [130,170) 30 route + 10 fold -> route, [175,200) fold
    gaps = b["idle_gaps"]
    assert [round(s * 1e9) for _, s in gaps] == [40, 40, 25, 10]
    assert {tuple(g) for g in gaps[:2]} == {("bench.embed", 40e-9),
                                            ("bench.route", 40e-9)}
    assert gaps[2][0] == "bench.fold" and gaps[3][0] == "bench.embed"


def test_ops_clipped_to_the_window():
    ev = _events() + [Event(DEV, OPS, "early", -50, 60)]   # [-50, 10)
    r = Reduction(ev)
    # the early op adds [0, 10) to the busy time
    assert r.busy_s == pytest.approx(95e-9)


def test_short_names():
    from harness.trace import short_name
    assert short_name(SGLD_BWD) == "transpose_jvp___.18 custom-call"
    assert short_name("%fusion.95 = f32[8,128]{1,0:T(8,128)S(1)} fusion("
                      "f32[8]{0} %p), kind=kOutput") == "fusion.95 fusion"
    assert short_name("%x = (s32[1,1024]{1,0:T(1,128)}, s32[1]) "
                      "custom-call(f32[2] %a)") == "x custom-call"
    assert short_name("jit_scan(1234)") == "jit_scan(1234)"


DATA = os.path.join(HERE, "data", "cpu_trace.xplane.pb")


def test_recorded_trace_by_hand():
    # recorded by jax.profiler on the host CPU: a jitted matmul + sum inside
    # bench.embed, a sleep in bench.wait, the same call in bench.fold; the
    # CPU's XLA operations run on its client thread's line
    ev = read_xplane(DATA)
    assert [(e.name, e.start_ns, e.dur_ns) for e in ev
            if e.name.startswith("bench.")] == [
        ("bench.embed", 17889, 8333605), ("bench.wait", 8367934, 3108738),
        ("bench.fold", 11491291, 991844)]
    line = next(e.line for e in ev
                if e.line.startswith("tf_XLAPjRtCpuClient"))
    r = Reduction(ev, device_plane="/host:CPU", ops_line=line)
    # window: 17889 .. 11491291 + 991844 = 12483135 ns
    assert r.window_s == pytest.approx(12_465_246e-9)
    # first call: dot_general [141793, 4396430) holding its waits and end
    # markers, reduce-window [4398492, 4464806), reduce [4465458, 4466926),
    # [4468833, 4468874): 4254637 + 66314 + 1468 + 41 = 4322460 ns;
    # second call: 664176 + 51117 + 1426 + 39 = 716758 ns
    assert r.busy_s == pytest.approx(5_039_218e-9)
    assert r.span_device_s("embed") == pytest.approx(4_322_460e-9)
    assert r.span_device_s("fold") == pytest.approx(716_758e-9)
    assert r.span_device_s("wait") == 0.0
    # the gap [4468874, 11717876) is 3882620 ns embed, 3108738 wait,
    # 226585 fold: labelled embed
    assert r.idle_gaps(3) == [["bench.embed", pytest.approx(7_249_002e-9)],
                              ["bench.embed", pytest.approx(123_904e-9)],
                              ["bench.fold", pytest.approx(45_559e-9)]]


def test_no_span_is_an_error():
    with pytest.raises(ValueError, match="no bench"):
        Reduction([Event(DEV, OPS, "x", 0, 1)])


class _Run:
    """What a per-layer reader sees, for a tiny RouterBench-like cell."""

    def __init__(self, events):
        from types import SimpleNamespace as NS
        from harness import peaks
        self.trace = Reduction(events)
        self.peaks = peaks.peaks_for("TPU v5 lite")
        self.rec = {"routed": 30, "batches": [(10, 16), (20, 64)],
                    "late_s": np.array([0.001, 0.002, 0.003]),
                    "fold_calls": 1, "window_s": 1e-6}
        self.compiles = {"embed": 2, "route": 1}
        fg = NS(n_chains=4, sgld_minibatch=256, sgld_steps=20)
        from harness.cell import encoder
        enc = {"hidden_size": 768, "intermediate_size": 3072,
               "num_hidden_layers": 6}
        self.sess = NS(fgts_cfg=fg, d=782, pool=NS(names=list(range(11))),
                       encoder=encoder({"arch": "rope_bidir"}), enc=enc,
                       seq_len=128)


# the backward SGLD kernel as the chip's trace names it (shapes abridged)
SGLD_BWD = ("%transpose_jvp___.18 = f32[4,2,1,782]{3,2,1,0} custom-call("
            "f32[4,1,1]{2,1,0} %copy.71, f32[4,1,782]{2,1,0} %r, "
            "f32[4,256,782]{2,1,0} %bitcast.243), "
            "custom_call_target=\"tpu_custom_call\"")


def _reader(name):
    from harness.cell import reader
    return reader(name)


def test_readers_by_hand():
    ev = _events() + [Event(DEV, OPS, SGLD_BWD, 130, 26)]
    run = _Run(ev)
    # route span [100, 160) now holds sgld_bwd [110, 130) + [130, 156)
    assert _reader("route_prog_ms.lat")(run) == pytest.approx(46e-9 * 1e3)
    assert _reader("encoder_ms.lat")(run) == pytest.approx(60e-9 * 1e3)
    assert _reader("encoder_us_per_req.sat")(run) == \
        pytest.approx(60e-9 / 30 * 1e6)
    assert _reader("fold_ms.lat")(run) == pytest.approx(5e-9 * 1e3)
    assert _reader("pad_efficiency")(run) == pytest.approx(100 * 30 / 80)
    assert _reader("gen_late_p95_ms")(run) == pytest.approx(2.9)
    assert _reader("device_idle_share.lat")(run) == \
        pytest.approx(100 * (1 - 111 / 200))
    m, k, d, c = 256, 11, 782, 4
    # 30 queries x 14.80 GFLOP + 2 batches x (2 x 20 x 10 m K d c FLOPs of
    # SGLD + 6 n K d of selection), over 1 us and 197 TFLOP/s
    want = (30 * 19_267_584 * 6 * 128
            + 2 * 2 * 20 * 10 * m * k * d * c + 6 * 30 * k * d) / 1e-6
    assert _reader("serve_mfu")(run) == pytest.approx(100 * want / 197e12)
    assert _reader("serve_mfu.lat")(run) == _reader("serve_mfu")(run)


def test_sgld_roofline_by_hand():
    # one backward call of 26 us, as the chip ran it: 6 m K d c FLOPs and
    # 4 c (m d + 2 K d + d) bytes, so memory bounds it at 4.26 us (16.4 %)
    ev = [Event(HOST, "python", "bench.route", 0, 40_000),
          Event(DEV, OPS, SGLD_BWD, 1_000, 26_000)]
    m, k, d, c = 256, 11, 782, 4
    t = max(6 * m * k * d * c / 197e12,
            4 * c * (m * d + 2 * k * d + d) / 819e9)
    assert t == pytest.approx(4 * c * (m * d + 2 * k * d + d) / 819e9)
    got = _reader("sgld_roofline")(_Run(ev))
    assert got == pytest.approx(100 * t / 26e-6)
    assert 16.0 < got < 16.8


def test_reader_without_kernels_reads_nothing():
    assert _reader("sgld_roofline")(_Run(_events())) is None
