"""The yardstick kept with the benchmark: the seeded arrival generator, the
peaks table, the SGLD cost model and the encoder's FLOP count (its
module's ``flops_per_query``)."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

from harness import costs, peaks, traffic  # noqa: E402


@pytest.mark.parametrize("kind,burst", [("poisson", 16.0), ("bursty", 64.0)])
def test_arrivals_match_the_program_generator(kind, burst):
    from repro.serving import stream
    spec = stream.ArrivalSpec(kind, 2500.0, burst=burst)
    want = stream.arrival_times(spec, 4000, seed=1234)
    got = traffic.arrival_times(kind, 2500.0, 4000, 1234, burst=burst)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", ["poisson", "bursty"])
def test_schedule_reorders_the_same_arrivals(kind):
    mix = {"arrivals": kind, "rate": 900.0, "burst": 64.0,
           "schedule_seed": 7}
    a = traffic.schedule(mix, 5000, 2 ** 31 + 11)
    b = traffic.schedule(mix, 5000, 2 ** 31 + 12)
    assert np.all(np.diff(a) >= 0) and np.all(np.diff(b) >= 0)
    # same gaps (and so the same last arrival), in another order
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                               np.sort(np.diff(b, prepend=0.0)))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, traffic.schedule(mix, 5000,
                                                      2 ** 31 + 11))


def test_tokens_follow_the_corpus():
    from repro.data.synth import CorpusConfig, category_token_logits
    corpus = {"n_categories": 7, "vocab_size": 2048, "common_pool": 256,
              "common_frac": 0.35, "block_size": 192, "topic_temp": 1.2}
    logits = traffic.category_token_logits(corpus)
    np.testing.assert_array_equal(
        logits, category_token_logits(CorpusConfig(**corpus)))
    cats = np.repeat(np.arange(7), 400)
    tok = traffic.sample_tokens(np.random.default_rng(0), cats, corpus, 128)
    assert tok.dtype == np.int32 and tok.shape == (2800, 128)
    assert tok.min() >= 0 and tok.max() < 2048
    for c in range(7):
        p = np.exp(logits[c] - logits[c].max())
        p /= p.sum()
        t = tok[cats == c]
        lo, hi = 256 + 192 * c, 256 + 192 * (c + 1)
        assert abs(np.mean(t < 256) - p[:256].sum()) < 0.01
        assert abs(np.mean((t >= lo) & (t < hi)) - p[lo:hi].sum()) < 0.01


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.flops_bf16 == 197e12 and p.hbm_bytes_per_s == 819e9
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_sgld_cost_model_is_the_programs_count():
    # benchmarks/bench_sgld.py: flops = 10 m K d c per step,
    # bytes = 4 c (2 m d + 4 K d + 2 d)
    m, k, d, c = 256, 11, 782, 4
    f = [costs.sgld_kernel_cost(kind, m, k, d, c) for kind in ("fwd", "bwd")]
    assert sum(x[0] for x in f) == 10.0 * m * k * d * c
    assert sum(x[1] for x in f) == 4.0 * c * (2 * m * d + 4 * k * d + 2 * d)
    with pytest.raises(ValueError):
        costs.sgld_kernel_cost("other", m, k, d, c)


@pytest.mark.parametrize("layers,want_gflop", [(6, 14.80), (12, 29.60)])
def test_encoder_flops_closed_form(layers, want_gflop):
    from harness.cell import encoder
    enc = {"hidden_size": 768, "intermediate_size": 3072,
           "num_hidden_layers": layers}
    f = encoder({"arch": "rope_bidir"}).flops_per_query(enc, 128)
    # per token and layer: 2 (4 d^2 + 3 d d_ff + 2 L d) = 19,267,584 FLOPs
    assert f == 19_267_584 * layers * 128
    assert abs(f / 1e9 - want_gflop) < 0.01


def test_roofline_names_its_bound():
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = costs.roofline_seconds(197e12, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = costs.roofline_seconds(1.0, 819e9, p)
    assert bound == "memory" and t == pytest.approx(1.0)
