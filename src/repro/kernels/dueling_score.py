"""Dueling-score Pallas TPU kernel — the router's serving hot path.

Computes, for a batch of query embeddings x and all K model embeddings a_k,
the FGTS.CDB scores for both posterior samples theta^1, theta^2:

    phi(x, a_k) = (x * a_k) / ||x * a_k||          (paper's Hadamard feature)
    s_jk        = <theta^j, phi(x, a_k)>

Key identity that makes this MXU work instead of a (B,K,d) elementwise blow-up:

    <theta, (x*a)/||x*a||> = ((x*theta) . a) / sqrt((x*x) . (a*a))

so each (B,K) tile is two matmuls: (x*theta_j) @ A^T and x^2 @ (A^2)^T.
Tiling: grid (B/BB, K/BK); d is kept whole in VMEM (router dims are <= 1k).

Interpret-mode selection: ``interpret=None`` (the default everywhere) picks
the compiled Mosaic path automatically when an accelerator backend is
present and falls back to interpret mode on host-only platforms. Override
with the ``REPRO_PALLAS_INTERPRET`` env var ("1"/"0").

``dueling_select`` is the batched argmax epilogue: same score math, but the
kernel reduces each arms-by-queries (K, BB) tile directly to the routed
pair (a1, a2) per query — K stays whole in VMEM, so no (J,B,K) score tensor
ever reaches HBM, and the pairs leave as lane-dense (1, BB) int32 rows.
It also applies the serve-time cost tilt — a global (K,) penalty or a
per-request (B,K) preference tilt, row-broadcast exactly like the activity
mask — and the paper's force-distinct selection inside the kernel.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BB = 128
DEFAULT_BK = 128
# The fused-epilogue K ceiling lives on the package (single source of
# truth, asserted by repro-lint's kernel-budget pass).
from repro.kernels import KERNEL_PRECISION, MAX_K_FUSED  # noqa: E402

_ACCEL_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


def default_interpret() -> bool:
    """interpret=True only when no compiled Pallas backend is available.

    ``REPRO_PALLAS_INTERPRET=1`` forces interpret mode (debugging);
    ``REPRO_PALLAS_INTERPRET=0`` forces the compiled path. Set it before
    the first kernel call: jitted wrappers read it at trace time, so a
    mid-process change does not invalidate already-compiled programs.
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env:                         # empty/unset falls through to the default
        return env not in ("0", "false")
    return jax.default_backend() not in _ACCEL_BACKENDS


def _resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else interpret


def mask_fallback_pair(s2: jax.Array, a1: jax.Array,
                       a2: jax.Array) -> jax.Array:
    """Single-survivor degeneration, shared by every masked-selection site:
    when all of a2's candidates are masked to -inf (one active arm and
    ``distinct``), duel (a1, a1) instead of an inactive arm. ``s2`` is the
    post-masking score row(s); reduces over the last (arm) axis."""
    return jnp.where(jnp.max(s2, axis=-1) == -jnp.inf, a1, a2)


def _dueling_kernel(x_ref, a_ref, th_ref, s_ref, *, n_theta: int):
    x = x_ref[...].astype(jnp.float32)              # (BB, d)
    a = a_ref[...].astype(jnp.float32)              # (BK, d)
    th = th_ref[...].astype(jnp.float32)            # (J, d)
    den = jax.lax.dot_general(x * x, a * a, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32,
                              precision=KERNEL_PRECISION)
    den = jnp.sqrt(jnp.maximum(den, 1e-24))         # (BB, BK)
    for j in range(n_theta):
        num = jax.lax.dot_general(x * th[j][None, :], a,
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32,
                                  precision=KERNEL_PRECISION)
        s_ref[j] = num / den


def dueling_score(x: jax.Array, a: jax.Array, thetas: jax.Array, *,
                  bb: int = DEFAULT_BB, bk: int = DEFAULT_BK,
                  interpret: bool | None = None) -> jax.Array:
    """x: (B,d) queries; a: (K,d) model embeddings; thetas: (J,d).

    Returns scores (J,B,K) float32.
    """
    interpret = _resolve_interpret(interpret)
    b, d = x.shape
    k = a.shape[0]
    j = thetas.shape[0]
    bb = min(bb, max(8, b))
    bk = min(bk, max(8, k))
    b_pad = -(-b // bb) * bb
    k_pad = -(-k // bk) * bk
    if b_pad != b:
        x = jnp.pad(x, ((0, b_pad - b), (0, 0)))
    if k_pad != k:
        a = jnp.pad(a, ((0, k_pad - k), (0, 0)))

    grid = (b_pad // bb, k_pad // bk)
    out = pl.pallas_call(
        functools.partial(_dueling_kernel, n_theta=j),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, d), lambda bi, ki: (bi, 0)),
            pl.BlockSpec((bk, d), lambda bi, ki: (ki, 0)),
            pl.BlockSpec((j, d), lambda bi, ki: (0, 0)),
        ],
        out_specs=pl.BlockSpec((j, bb, bk), lambda bi, ki: (0, bi, ki)),
        out_shape=jax.ShapeDtypeStruct((j, b_pad, k_pad), jnp.float32),
        interpret=interpret,
        name="dueling_score",
    )(x, a, thetas)
    return out[:, :b, :k]


def posterior_scores(a: jax.Array, thetas: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """Context-free arm scores s_ck = <theta_c, a_k / ||a_k||> for every
    posterior sample — the same Pallas score kernel driven with the all-ones
    query (phi(1, a) = a/||a||, so the Hadamard identity collapses to a
    normalized dot). a: (K, d); thetas: (C, d). Returns (C, K) float32.

    The autopilot's posterior-dominance matrix is built on these: the
    fraction of SGLD chains scoring arm i above arm j estimates
    P[theta · (e_i - e_j) > 0] (``autopilot.dominance``, which also carries
    the pure-XLA reference path this kernel is parity-tested against).
    """
    ones = jnp.ones((1, a.shape[1]), jnp.float32)
    return dueling_score(ones, a, thetas, interpret=interpret)[:, 0, :]


def _first_max(s: jax.Array, rows: jax.Array):
    """(max, argmax) over axis 0 with ``jnp.argmax``'s first-index tie rule,
    as a max plus a min over the row ids where the max is attained — two
    sublane reductions that lower on every backend. Returns (1, BB) rows."""
    top = jnp.max(s, axis=0, keepdims=True)
    idx = jnp.min(jnp.where(s == top, rows, s.shape[0]), axis=0,
                  keepdims=True)
    return top, idx


def _select_kernel(x_ref, a_ref, th_ref, tilt_ref, mask_ref, a1_ref, a2_ref,
                   *, k_valid: int, distinct: bool):
    """Score + argmax epilogue for one (BB,) block of queries.

    The tile is laid out arms-by-queries, (K_pad, BB): the arm argmax is a
    reduction over sublanes and each routed pair leaves the kernel as a
    lane-dense (1, BB) int32 row. K lives whole in VMEM; padded arms AND
    masked-out (inactive) arms are set to -inf so they can never win the
    argmax. ``tilt`` is the pre-multiplied score penalty, one column per
    query — a global cost tilt (cost_tilt * cost_k broadcast over queries)
    or a per-request preference tilt (pref_b * cost_k), subtracted from
    both samples' scores; ``mask`` is the int32 arm-activity mask, one
    column per query (dynamic model pools flip whole arm rows at hot
    add/remove; the autopilot's candidate-quota gate flips per-query
    slices — both without retracing).
    """
    x = x_ref[...].astype(jnp.float32)              # (BB, d)
    a = a_ref[...].astype(jnp.float32)              # (K_pad, d)
    th = th_ref[...].astype(jnp.float32)            # (2, d)
    tilt = tilt_ref[...].astype(jnp.float32)        # (K_pad, BB)
    mask = mask_ref[...]                            # (K_pad, BB) int32
    den = jax.lax.dot_general(a * a, x * x, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32,
                              precision=KERNEL_PRECISION)
    den = jnp.sqrt(jnp.maximum(den, 1e-24))         # (K_pad, BB)
    rows = jax.lax.broadcasted_iota(jnp.int32, den.shape, 0)
    valid = (rows < k_valid) & (mask > 0)

    def scores(j):
        num = jax.lax.dot_general(a, x * th[j:j + 1, :],
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32,
                                  precision=KERNEL_PRECISION)
        return jnp.where(valid, num / den - tilt, -jnp.inf)

    _, a1 = _first_max(scores(0), rows)                         # (1, BB)
    s2 = scores(1)
    if distinct:
        s2 = jnp.where(rows == a1, -jnp.inf, s2)
    top2, a2 = _first_max(s2, rows)
    a1_ref[...] = a1
    # single-survivor pool: with one active arm a distinct pair is
    # impossible (s2 all -inf) — duel (a1, a1) instead of a masked arm
    a2_ref[...] = jnp.where(top2 == -jnp.inf, a1, a2)


def dueling_select(x: jax.Array, a: jax.Array, thetas: jax.Array, *,
                   tilt: jax.Array | None = None,
                   mask: jax.Array | None = None, distinct: bool = False,
                   bb: int = DEFAULT_BB,
                   interpret: bool | None = None):
    """Route a batch: argmax_k of both samples' (cost-tilted) scores.

    x: (B,d); a: (K,d); thetas: (2,d); tilt: score penalty or None;
    mask: arm-activity mask or None (None == all arms active). Like the
    mask, the tilt operand is row-broadcast: a (K,) tilt (the global
    serve-time cost penalty cost_tilt * cost_k) applies to every query,
    while a (B,K) tilt carries *per-request* penalties (preference-
    conditioned routing: pref_b * cost_k bends each row's trade-off
    independently). A (K,) bool mask applies to every query (dynamic model
    pools pass their ``active`` mask so retired / not-yet-arrived arms can
    never win the argmax); a (B,K) bool mask restricts arms *per query*
    (the autopilot's candidate traffic quota gates candidate columns row
    by row). With a single surviving arm a ``distinct`` pair degenerates
    to (k, k). Returns (a1, a2) int32 arrays of shape (B,).
    """
    interpret = _resolve_interpret(interpret)
    b, d = x.shape
    k = a.shape[0]
    assert thetas.shape[0] == 2, "dueling_select pairs exactly two thetas"
    tilt_i = jnp.zeros((1, k), jnp.float32) if tilt is None \
        else jnp.atleast_2d(tilt.astype(jnp.float32))
    tilt_i = jnp.broadcast_to(tilt_i, (b, k))
    mask_i = jnp.ones((1, k), jnp.int32) if mask is None \
        else jnp.atleast_2d(mask.astype(jnp.int32))
    mask_i = jnp.broadcast_to(mask_i, (b, k))
    if k > MAX_K_FUSED:
        s = dueling_score(x, a, thetas, interpret=interpret)
        s = s - tilt_i[None, :, :]
        s = jnp.where(mask_i[None, :, :] > 0, s, -jnp.inf)
        a1 = jnp.argmax(s[0], axis=-1).astype(jnp.int32)
        s2 = s[1]
        if distinct:
            s2 = jnp.where(jnp.arange(k)[None, :] == a1[:, None],
                           -jnp.inf, s2)
        a2 = jnp.argmax(s2, axis=-1).astype(jnp.int32)
        return a1, mask_fallback_pair(s2, a1, a2)

    # queries ride the lanes: a block is either the whole (padded) batch
    # or a multiple of 128 rows; arms ride the sublanes, padded to 8
    bb = min(bb, max(8, b))
    b_pad = -(-b // bb) * bb
    k_pad = -(-max(8, k) // 8) * 8
    if b_pad != b:
        x = jnp.pad(x, ((0, b_pad - b), (0, 0)))
        tilt_i = jnp.pad(tilt_i, ((0, b_pad - b), (0, 0)))
        mask_i = jnp.pad(mask_i, ((0, b_pad - b), (0, 0)))
    if k_pad != k:
        a = jnp.pad(a, ((0, k_pad - k), (0, 0)))
        tilt_i = jnp.pad(tilt_i, ((0, 0), (0, k_pad - k)))
        mask_i = jnp.pad(mask_i, ((0, 0), (0, k_pad - k)))

    a1, a2 = pl.pallas_call(
        functools.partial(_select_kernel, k_valid=k, distinct=distinct),
        grid=(b_pad // bb,),
        in_specs=[
            pl.BlockSpec((bb, d), lambda bi: (bi, 0)),
            pl.BlockSpec((k_pad, d), lambda bi: (0, 0)),
            pl.BlockSpec((2, d), lambda bi: (0, 0)),
            pl.BlockSpec((k_pad, bb), lambda bi: (0, bi)),
            pl.BlockSpec((k_pad, bb), lambda bi: (0, bi)),
        ],
        out_specs=[pl.BlockSpec((1, bb), lambda bi: (0, bi)),
                   pl.BlockSpec((1, bb), lambda bi: (0, bi))],
        out_shape=[jax.ShapeDtypeStruct((1, b_pad), jnp.int32),
                   jax.ShapeDtypeStruct((1, b_pad), jnp.int32)],
        # at K = MAX_K_FUSED the f32 contractions' operand splits take ~18
        # MiB of scoped VMEM, over the compiler's 16 MiB default (a v5e core
        # has 128 MiB)
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="dueling_select",
    )(x, a, thetas, tilt_i.T, mask_i.T)
    return a1[0, :b], a2[0, :b]
