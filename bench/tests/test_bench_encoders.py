"""The query encoder behind one seam: a configuration names its encoder
module (``harness/encoders/<arch>.py``) by ``arch``, and every harness path
(weights, the served session, the references, the FLOP count) goes through
that module. The block the benchmark had before the seam is reproduced bit
for bit, a module that a test writes serves a cell end to end, and a
configuration that names no module is refused."""
import hashlib
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

from tiny import TINY_LIMITS, tiny_cell  # noqa: E402

W_SEED = 1234567


def _digest(tree) -> str:
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        a = np.asarray(leaf)
        h.update(f"{a.shape}{a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# sha256 (first 16 hex digits) of the encoder tree, the arm table and the
# reference embeddings at "highest" of 64 rows (70 at two layers) drawn by
# default_rng(7), made on the host CPU by the harness as it was before the
# seam (``weights.make_weights`` and ``reference.encode_rows`` with the
# block written into them), weights seed 1234567
TWO_LAYERS = {"hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 4, "intermediate_size": 128,
              "max_position_embeddings": 16}
GOLDEN = [
    ("routerbench-distilbert", None, 64,
     ("29c978c77584fadf", "d4276e8490e3261b", "e67234b5c0417136")),
    ("mixinstruct-mpnet", None, 64,
     ("29c978c77584fadf", "5ff305e75106be2e", "e67234b5c0417136")),
    ("mixinstruct-mpnet", TWO_LAYERS, 70,
     ("67719d408e4b246a", "201e198f84119246", "d2f8c969a952f676")),
]


@pytest.mark.parametrize("config,override,rows,want", GOLDEN,
                         ids=["distilbert", "mpnet", "mpnet-two-layers"])
def test_seam_reproduces_the_block_bit_for_bit(config, override, rows, want):
    from harness import cell, reference as ref, weights
    from harness.serve import make_pool
    cfg = tiny_cell(config=config).config
    if override:
        cfg["encoder"].update(override)
    mod = cell.encoder(cfg["encoder"])
    assert mod.__name__ == "bench_encoder_rope_bidir"
    w = mod.widths(cfg["encoder"])
    pool = make_pool(cfg, W_SEED)
    params, a_emb = weights.make_weights(W_SEED, mod, w, len(pool.names),
                                         pool.meta)
    seq = mod.seq_len(w)
    tok = np.random.default_rng(7).integers(0, 2048, (rows, seq)).astype(
        np.int32)
    x = ref.encode_rows(mod.reference, params, tok)
    assert (_digest(params), _digest(a_emb), _digest(x)) == want
    # the count the harness had: 2 (4 d^2 + 3 d d_ff + 2 L d) a token and
    # layer
    d, dff = mod.embed_dim(w), w["intermediate_size"]
    assert mod.flops_per_query(w, seq) == 2.0 * (
        4 * d * d + 3 * d * dff + 2 * seq * d) \
        * w["num_hidden_layers"] * seq


# A toy encoder: a token embedding, one linear map of the mean of the
# RMS-normed tokens added back to every token, a final RMSNorm, mean
# pooling and an L2 norm. The program serves its own block only, so the
# toy hands it the one-layer block that computes the same function: no
# query or key projection (uniform attention: the mean of the values),
# the value projection as the linear map, the identity as the output
# projection, and an MLP whose weights are zero. Its widths, its tree's
# maker, its reference and its FLOP count are its own.
TOY = '''
from harness.reference import einsum


def widths(published):
    return {k: int(published[k]) for k in ("width", "vocab", "query_len")}


def embed_dim(w):
    return w["width"]


def seq_len(w):
    return w["query_len"]


def make_params(keys, w):
    import jax
    import jax.numpy as jnp
    d = w["width"]
    z = jnp.zeros
    lin = jax.random.normal(keys[1], (1, d, 1, d)) * d ** -0.5
    return {"embed": jax.random.normal(keys[0], (w["vocab"], d)) * d ** -0.5,
            "units": {"0": {
                "norm1": z((1, d)),
                "mixer": {"wq": z((1, d, 1, d)), "wk": z((1, d, 1, d)),
                          "wv": lin, "wo": jnp.eye(d)[None, None]},
                "norm2": z((1, d)),
                "ffn": {"w1": z((1, d, 1)), "w3": z((1, d, 1)),
                        "w2": z((1, 1, d))}}},
            "final_norm": 0.1 * jax.random.normal(keys[2], (d,))}


def service_encoder(w, seq_len, name):
    from repro.encoder import EncoderConfig
    return EncoderConfig(vocab_size=w["vocab"], d_model=w["width"],
                         n_layers=1, n_heads=1, d_ff=1, max_len=seq_len,
                         name=name)


def _rms(x, scale):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + scale)


def reference(params, tokens, mode="highest"):
    import jax.numpy as jnp
    x = params["embed"][tokens]
    lin = params["units"]["0"]["mixer"]["wv"][0, :, 0, :]
    h = jnp.mean(_rms(x, 0.0), axis=1)
    x = x + einsum("bd,de->be", h, lin, mode)[:, None, :]
    pooled = jnp.mean(_rms(x, params["final_norm"]), axis=1)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def flops_per_query(w, seq_len):
    return 2.0 * w["width"] ** 2
'''

SEED = 2 ** 31 + 1517
SECONDS = 3.0


@pytest.fixture
def toy_dir(tmp_path, monkeypatch):
    from harness import cell
    (tmp_path / "toy_linear.py").write_text(TOY)
    monkeypatch.setattr(cell, "ENCODERS", tmp_path)
    return tmp_path


def test_an_encoder_module_written_as_a_file_serves_a_cell(toy_dir):
    from types import SimpleNamespace as NS
    from harness import check, mfu, peaks
    from harness.serve import Session, Spans
    cell = tiny_cell()
    cell.config["encoder"] = {"arch": "toy_linear", "width": 24,
                              "vocab": 2048, "query_len": 8}
    sess = Session(cell.config, cell.mix, SEED, SECONDS, Spans(False))
    assert sess.encoder.__name__ == "bench_encoder_toy_linear"
    assert sess.seq_len == 8 and sess.d == 24 + sess.n_meta
    assert sess.a_emb.shape == (len(sess.pool.names), sess.d)
    sess.warm_up()
    rec = sess.window(SECONDS)
    assert len(rec["batches"]) >= 4 and rec["votes_folded"] > 0
    run = NS(sess=sess, rec=rec, peaks=peaks.peaks_for("TPU v5 lite"))
    got = mfu.serve_mfu(run)
    correct, shown, bad = check.judge(sess, SEED, TINY_LIMITS)
    assert correct and bad == 0, shown
    # the toy's reference at int8, in the program's place, fails
    ctl = check.control_numbers(check.Evidence(sess, SEED), "int8",
                                "highest")
    assert ctl["enc_err"] > TINY_LIMITS["enc_err"], ctl
    # the toy's count, 2 d^2 a query, is what the whole step's share counts
    sess.encoder = NS(flops_per_query=lambda w, s: 0.0)
    assert got - mfu.serve_mfu(run) == pytest.approx(
        100.0 * 2 * 24 ** 2 * rec["routed"] / rec["window_s"] / 197e12)


@pytest.mark.parametrize("published", [
    {"dim": 32}, {"arch": "no_such_block", "dim": 32}, {"arch": None}],
    ids=["missing", "unknown", "null"])
def test_a_configuration_without_a_known_arch_is_refused(published):
    from harness import cell
    from harness.serve import Session, Spans
    with pytest.raises(ValueError, match="rope_bidir"):
        cell.encoder(published)
    cfg = tiny_cell().config
    cfg["encoder"] = published
    with pytest.raises(ValueError, match="rope_bidir"):
        Session(cfg, tiny_cell().mix, SEED, SECONDS, Spans(False))
