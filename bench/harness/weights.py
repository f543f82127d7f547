"""Weights and the arm table, made on the device from the seed in one
jitted call, in the type they are served in (float32).

The encoder's tree is made by the configuration's encoder module
(``harness/encoders/<arch>.py``, ``make_params``) in the layout the
program's encoder reads; the benchmark makes it so that the reference can
use the same numbers without taking anything the program made.
"""
from __future__ import annotations

import functools
import json

import numpy as np

N_ENCODER_KEYS = 11    # the seed's key splits into these and the arm table's


def std_metadata(perf: np.ndarray, cost: np.ndarray) -> np.ndarray:
    """(K, 2M) per-column standardized perf/cost metadata, scaled by 0.3
    (the CCFT arm table's metadata columns)."""
    meta = np.concatenate([perf, cost], axis=-1).astype(np.float64)
    mu = meta.mean(axis=0, keepdims=True)
    sd = np.maximum(meta.std(axis=0, keepdims=True), 1e-6)
    return (0.3 * (meta - mu) / sd).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _maker(encoder, widths: str, k, d_arm):
    import jax
    import jax.numpy as jnp

    w = json.loads(widths)
    d = encoder.embed_dim(w)

    def make(key, meta):
        ks = jax.random.split(key, N_ENCODER_KEYS + 1)
        params = encoder.make_params(ks[:N_ENCODER_KEYS], w)
        sem = jax.random.normal(ks[N_ENCODER_KEYS], (k, d))
        sem = sem / jnp.linalg.norm(sem, axis=1, keepdims=True)
        a_emb = jnp.concatenate([sem, meta], axis=1) if d_arm > d else sem
        return params, a_emb

    return jax.jit(make)


def make_weights(seed: int, encoder, w: dict, k: int,
                 meta: np.ndarray | None):
    """(encoder params, (K, d_arm) arm table) on the default device, for
    the encoder module ``encoder`` at widths ``w``. ``meta`` (K, m)
    columns are appended to the arm table when given."""
    import jax
    import jax.numpy as jnp
    m = np.zeros((k, 0), np.float32) if meta is None else meta
    fn = _maker(encoder, json.dumps(w, sort_keys=True), k,
                encoder.embed_dim(w) + m.shape[1])
    return fn(jax.random.PRNGKey(seed), jnp.asarray(m))
