"""Weights and the arm table, made on the device from the seed in one
jitted call, in the type they are served in (float32).

The encoder's tree has the layout the program's encoder reads (one stacked
unit of a pre-norm bidirectional-attention block with a gated MLP); the
benchmark makes it so that the reference can use the same numbers without
taking anything the program made.
"""
from __future__ import annotations

import functools

import numpy as np

ENC_KEYS = {   # published key -> the benchmark's name for it
    "dim": "hidden_size", "hidden_size": "hidden_size",
    "hidden_dim": "intermediate_size",
    "intermediate_size": "intermediate_size",
    "n_layers": "num_hidden_layers", "num_hidden_layers": "num_hidden_layers",
    "n_heads": "num_attention_heads",
    "num_attention_heads": "num_attention_heads",
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_position_embeddings",
}


def encoder_widths(published: dict) -> dict:
    """The encoder's widths under one set of names, from a config that
    uses either DistilBERT's or BERT's key names."""
    out = {}
    for k, v in published.items():
        if k in ENC_KEYS and isinstance(v, int):
            out[ENC_KEYS[k]] = v
    missing = {"hidden_size", "intermediate_size", "num_hidden_layers",
               "num_attention_heads", "vocab_size"} - set(out)
    if missing:
        raise KeyError(f"encoder config lacks {sorted(missing)}")
    return out


def std_metadata(perf: np.ndarray, cost: np.ndarray) -> np.ndarray:
    """(K, 2M) per-column standardized perf/cost metadata, scaled by 0.3
    (the CCFT arm table's metadata columns)."""
    meta = np.concatenate([perf, cost], axis=-1).astype(np.float64)
    mu = meta.mean(axis=0, keepdims=True)
    sd = np.maximum(meta.std(axis=0, keepdims=True), 1e-6)
    return (0.3 * (meta - mu) / sd).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _maker(d, dff, layers, heads, vocab, k, d_arm):
    import jax
    import jax.numpy as jnp

    hd = d // heads

    def make(key, meta):
        ks = jax.random.split(key, 12)
        n = jax.random.normal
        units = {"0": {
            "norm1": 0.1 * n(ks[0], (layers, d)),
            "mixer": {
                "wq": n(ks[1], (layers, d, heads, hd)) * d ** -0.5,
                "wk": n(ks[2], (layers, d, heads, hd)) * d ** -0.5,
                "wv": n(ks[3], (layers, d, heads, hd)) * d ** -0.5,
                "wo": n(ks[4], (layers, heads, hd, d)) * d ** -0.5,
            },
            "norm2": 0.1 * n(ks[5], (layers, d)),
            "ffn": {
                "w1": n(ks[6], (layers, d, dff)) * d ** -0.5,
                "w3": n(ks[7], (layers, d, dff)) * d ** -0.5,
                "w2": n(ks[8], (layers, dff, d)) * dff ** -0.5,
            },
        }}
        params = {"embed": n(ks[9], (vocab, d)) * d ** -0.5,
                  "units": units,
                  "final_norm": 0.1 * n(ks[10], (d,))}
        sem = n(ks[11], (k, d))
        sem = sem / jnp.linalg.norm(sem, axis=1, keepdims=True)
        a_emb = jnp.concatenate([sem, meta], axis=1) if d_arm > d else sem
        return params, a_emb

    return jax.jit(make)


def make_weights(seed: int, enc: dict, k: int, meta: np.ndarray | None):
    """(encoder params, (K, d_arm) arm table) on the default device.
    ``meta`` (K, m) columns are appended to the arm table when given."""
    import jax
    import jax.numpy as jnp
    d = enc["hidden_size"]
    m = np.zeros((k, 0), np.float32) if meta is None else meta
    fn = _maker(d, enc["intermediate_size"], enc["num_hidden_layers"],
                enc["num_attention_heads"], enc["vocab_size"], k,
                d + m.shape[1])
    return fn(jax.random.PRNGKey(seed), jnp.asarray(m))
