"""The repo's encoder block: pre-norm (RMSNorm) bidirectional attention
with rotary positions and a gated (SiLU) three-matrix MLP per layer, a
final RMSNorm, mean pooling over every position and an L2 norm. The
program serves it as ``repro.encoder.EncoderConfig`` (one stacked unit of
the block, scanned over the layers).

Configurations name it ``"arch": "rope_bidir"`` and give DistilBERT's or
BERT's published key names for its widths. The reference imports nothing
of the program; only ``service_encoder`` does.
"""
from __future__ import annotations

import numpy as np

from harness.reference import einsum

KEYS = {   # published key -> the benchmark's name for it
    "dim": "hidden_size", "hidden_size": "hidden_size",
    "hidden_dim": "intermediate_size",
    "intermediate_size": "intermediate_size",
    "n_layers": "num_hidden_layers", "num_hidden_layers": "num_hidden_layers",
    "n_heads": "num_attention_heads",
    "num_attention_heads": "num_attention_heads",
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_position_embeddings",
}


def widths(published: dict) -> dict:
    """The encoder's widths under one set of names, from a config that
    uses either DistilBERT's or BERT's key names."""
    out = {}
    for k, v in published.items():
        if k in KEYS and isinstance(v, int):
            out[KEYS[k]] = v
    missing = {"hidden_size", "intermediate_size", "num_hidden_layers",
               "num_attention_heads", "vocab_size",
               "max_position_embeddings"} - set(out)
    if missing:
        raise KeyError(f"encoder config lacks {sorted(missing)}")
    return out


def embed_dim(w: dict) -> int:
    return w["hidden_size"]


def seq_len(w: dict) -> int:
    """Queries are this many tokens, fixed length."""
    return w["max_position_embeddings"]


def make_params(keys, w: dict) -> dict:
    """The encoder's tree in the layout the program reads, from eleven
    keys; traced inside the benchmark's one jitted maker."""
    import jax
    d, dff, layers = w["hidden_size"], w["intermediate_size"], \
        w["num_hidden_layers"]
    heads = w["num_attention_heads"]
    hd = d // heads
    n = jax.random.normal
    units = {"0": {
        "norm1": 0.1 * n(keys[0], (layers, d)),
        "mixer": {
            "wq": n(keys[1], (layers, d, heads, hd)) * d ** -0.5,
            "wk": n(keys[2], (layers, d, heads, hd)) * d ** -0.5,
            "wv": n(keys[3], (layers, d, heads, hd)) * d ** -0.5,
            "wo": n(keys[4], (layers, heads, hd, d)) * d ** -0.5,
        },
        "norm2": 0.1 * n(keys[5], (layers, d)),
        "ffn": {
            "w1": n(keys[6], (layers, d, dff)) * d ** -0.5,
            "w3": n(keys[7], (layers, d, dff)) * d ** -0.5,
            "w2": n(keys[8], (layers, dff, d)) * dff ** -0.5,
        },
    }}
    return {"embed": n(keys[9], (w["vocab_size"], d)) * d ** -0.5,
            "units": units,
            "final_norm": 0.1 * n(keys[10], (d,))}


def service_encoder(w: dict, seq_len: int, name: str):
    """The program's ``EncoderConfig`` at these widths."""
    from repro.encoder import EncoderConfig
    return EncoderConfig(
        vocab_size=w["vocab_size"], d_model=w["hidden_size"],
        n_layers=w["num_hidden_layers"], n_heads=w["num_attention_heads"],
        d_ff=w["intermediate_size"], max_len=seq_len, name=name)


def flops_per_query(w: dict, seq_len: int) -> float:
    """Forward FLOPs of one query of ``seq_len`` tokens: per token and
    layer, the q/k/v/o projections (4 d^2 MACs), the gated MLP (3 d d_ff
    MACs) and attention's scores and values (2 L d MACs); 2 FLOPs a MAC.
    Norms, softmax, the embedding gather and pooling are not counted."""
    d, dff, layers = w["hidden_size"], w["intermediate_size"], \
        w["num_hidden_layers"]
    macs = 4 * d * d + 3 * d * dff + 2 * seq_len * d
    return 2.0 * macs * layers * seq_len


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------

def _rms(x, scale, eps=1e-6):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta=10000.0):
    """Rotary positions 0..L-1 on the two halves of each head."""
    import jax.numpy as jnp
    hd, seq = x.shape[-1], x.shape[1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(seq, dtype=np.float32)[:, None] * freqs     # (L, hd/2)
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def reference(params, tokens, mode: str = "highest"):
    """L2-normalised sentence embeddings (n, d) of fixed-length token rows.
    The layers run one after another over the stacked weights."""
    import jax
    import jax.numpy as jnp
    x = params["embed"][tokens]
    u = params["units"]["0"]
    layers = u["norm1"].shape[0]
    hd = u["mixer"]["wq"].shape[-1]
    for i in range(layers):
        w = jax.tree.map(lambda a: a[i], u)
        h = _rms(x, w["norm1"])
        q = _rope(einsum("bsd,dhk->bshk", h, w["mixer"]["wq"], mode))
        k = _rope(einsum("bsd,dhk->bshk", h, w["mixer"]["wk"], mode))
        v = einsum("bsd,dhk->bshk", h, w["mixer"]["wv"], mode)
        s = einsum("bshd,bthd->bhst", q, k, mode) * hd ** -0.5
        p = jax.nn.softmax(s, axis=-1)
        o = einsum("bhst,bthd->bshd", p, v, mode)
        x = x + einsum("bshk,hkd->bsd", o, w["mixer"]["wo"], mode)
        h = _rms(x, w["norm2"])
        g = jax.nn.silu(einsum("bsd,df->bsf", h, w["ffn"]["w1"], mode)) \
            * einsum("bsd,df->bsf", h, w["ffn"]["w3"], mode)
        x = x + einsum("bsf,fd->bsd", g, w["ffn"]["w2"], mode)
    x = _rms(x, params["final_norm"])
    pooled = jnp.mean(x, axis=1)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
