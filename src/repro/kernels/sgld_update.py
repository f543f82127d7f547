"""Fused SGLD posterior-update kernel — the FGTS.CDB training hot path.

Every routing round samples theta from the pseudo-posterior by SGLD, and
each SGLD step evaluates (the gradient of) the minibatch potential

    U_data(theta) = sum_i valid_i * L^j(theta, x_i, a1_i, a2_i, y_i)
    L^j = eta * softplus(-y <theta, phi1 - phi2>)
        - mu_i * (max_{k active} (s_k - t_ik) - (s_opp - t_opp))  (feel-good)

with phi(x,a) = (x*a)/||x*a||, s_k = <theta, phi(x, a_k)>, the optional
per-row preference tilt t_ik = pref_i * cost_k (zero when preference
conditioning is off — then the term is the plain feel-good max), and the
pref-stratified feel-good weight mu_i = mu / (1 + max(pref_i, 0)) (exactly
mu on untilted rows). The naive
evaluation materializes an (m, K, d) feature tensor per gradient step. This
kernel fuses the whole minibatch term into two MXU matmuls per tile via the
same Hadamard identity the serving kernel uses:

    <theta, (x*a)/||x*a||> = ((x*theta) . a) / sqrt((x*x) . (a*a))

so each (bm, K) score tile is ``(x*theta) @ A^T`` over ``sqrt(x^2 @ (A^2)^T)``
— K stays whole in VMEM, the grid walks the minibatch rows, and per-tile
partial sums land in their own output slots (reduced outside the kernel, so
``vmap`` over SGLD chains lifts cleanly to a leading grid axis instead of
racing on an accumulator).

The backward pass is a hand-derived ``jax.custom_vjp``: dU/dtheta is a
*weighted* sum of phi features,

    dU/dtheta = sum_i x_i * ((W_i / den_i) @ A)        (one more matmul)

where W (m, K) collects the logistic slope on the duelled columns, the
(tie-split) argmax one-hot of the feel-good max, and the opponent one-hot —
so neither pass ever builds (m, K, d). Only the theta cotangent is exact;
all other operands get symbolic zeros (SGLD differentiates w.r.t. theta
alone).

Backend selection (``resolve_sgld_backend``):

    fused     the Pallas kernel: compiled Mosaic on accelerators, interpret
              elsewhere (the same ``default_interpret()`` rule as every
              kernel in this package)
    xla       the kernel's interpret lowering, forced: pure XLA ops (the
              grid emulated with slices/loops), so it runs anywhere, is
              partitionable under GSPMD meshes, and is *bit-identical by
              construction* to the fused path under interpret mode — it is
              the same program
    autodiff  the legacy reference: jax.grad through ``likelihood_batch``'s
              batched-identity XLA path (independent implementation, used
              as the fp32-tolerance parity oracle)
    auto      fused on accelerator backends, xla otherwise; overridable via
              the ``REPRO_SGLD_BACKEND`` env var (read at trace time, so a
              mid-process flip never invalidates compiled programs)

K above ``MAX_K_FUSED`` no longer fits one VMEM tile: the fused path then
silently degrades to the interpret (pure-XLA) lowering.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import KERNEL_PRECISION, MAX_K_FUSED

from .dueling_score import _resolve_interpret, default_interpret

DEFAULT_BM = 128

SGLD_BACKENDS = ("auto", "fused", "xla", "autodiff")


def resolve_sgld_backend(backend: str = "auto", chains: int = 1) -> str:
    """Resolve an SGLD backend name to one of fused / xla / autodiff.

    "auto" picks the fused Pallas kernel when a compiled Pallas backend is
    available (``default_interpret()`` False). On host the interpret
    lowering's grid emulation serializes poorly under ``vmap`` over chains
    (BENCH_6: ~1.8x slower than the autodiff reference at chains=8), so
    multi-chain host configs resolve to "autodiff" and single-chain ones to
    "xla". ``REPRO_SGLD_BACKEND`` overrides the auto choice; explicit names
    pass through untouched (tests pin them). ``chains`` is a static config
    field and the env var is read at trace time, so the choice is fixed per
    trace — flipping either mid-process never retraces compiled programs.
    """
    if backend not in SGLD_BACKENDS:
        raise ValueError(f"sgld_backend {backend!r} not in {SGLD_BACKENDS}")
    if backend != "auto":
        return backend
    env = os.environ.get("REPRO_SGLD_BACKEND", "").strip().lower()
    if env:
        if env not in ("fused", "xla", "autodiff"):
            raise ValueError(f"REPRO_SGLD_BACKEND={env!r} not in "
                             f"('fused', 'xla', 'autodiff')")
        return env
    if not default_interpret():
        return "fused"
    return "autodiff" if chains > 1 else "xla"


class _SgldSpec(NamedTuple):
    """Static (hashable) parameters of one potential evaluation — the
    nondiff argument of the custom_vjp."""
    mode: str           # "fgts" | "mixed"
    j: int              # which posterior sample (opponent = a^{3-j})
    eta: float
    mu: float
    bm: int             # minibatch tile rows
    interpret: bool     # True = the pure-XLA lowering ("xla" backend)
    k_valid: int        # real arm count (columns beyond it are padding)


# ---------------------------------------------------------------------------
# Tile math (the kernel bodies); grid walks minibatch tiles, K whole in VMEM
# ---------------------------------------------------------------------------

def _tile_scores(theta, x, a):
    """(bm, Kp) score tile via the two-matmul identity; also returns den.
    ``theta`` is a (1, d) row."""
    num = jax.lax.dot_general(x * theta, a, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32,
                              precision=KERNEL_PRECISION)
    den = jax.lax.dot_general(x * x, a * a, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32,
                              precision=KERNEL_PRECISION)
    den = jnp.sqrt(jnp.maximum(den, 1e-24))
    return num / den, den


def _tile_terms(mode, theta, x, a1, a2, y, duel, valid, pref, a, mask,
                costs, *, j, eta, mu, k_valid):
    """Summed potential contribution of one row tile. Per-row operands are
    (bm, 1) columns and per-arm operands (1, Kp) rows, so each broadcasts
    against the (bm, Kp) score tile as it stands. ``pref`` and ``costs``
    carry the per-row feel-good tilt t_ik = pref_i * cost_k (all-zero when
    preference conditioning is off — a bitwise no-op since the tilt only
    ever *subtracts*)."""
    s, _ = _tile_scores(theta, x, a)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    oh1 = cols == a1
    oh2 = cols == a2
    s1 = jnp.sum(jnp.where(oh1, s, 0.0), axis=1, keepdims=True)  # one-hot
    s2 = jnp.sum(jnp.where(oh2, s, 0.0), axis=1, keepdims=True)  # gathers
    z = y * (s1 - s2)
    pref_ll = eta * jax.nn.softplus(-z)
    if mode == "fgts":
        t = pref * costs                             # (bm, Kp) tilt
        live = (cols < k_valid) & (mask > 0)
        smax = jnp.max(jnp.where(live, s - t, -jnp.inf), axis=1,
                       keepdims=True)
        t_opp = jnp.sum(jnp.where(oh2 if j == 1 else oh1, t, 0.0), axis=1,
                        keepdims=True)
        opp = (s2 if j == 1 else s1) - t_opp
        # pref-stratified feel-good weight mu / (1 + pref): tilted rows get
        # proportionally less optimism so their cheap-end feel-good doesn't
        # bleed into untilted rows. pref = 0 divides by exactly 1.0 — the
        # untilted term stays bitwise identical (padding rows included).
        mu_row = mu / (1.0 + jnp.maximum(pref, 0.0))
        terms = pref_ll - mu_row * (smax - opp)
    else:                                            # mixed duel + click rows
        click = eta * jnp.where(y > 0.5, jax.nn.softplus(-s1),
                                jax.nn.softplus(s1))
        terms = jnp.where(duel > 0, pref_ll, click)
    return jnp.sum(terms * valid)


def _tile_grad(mode, theta, x, a1, a2, y, duel, valid, pref, a, mask,
               costs, g, *, j, eta, mu, k_valid):
    """d(tile potential)/dtheta as a (1, d) row: weights W on the score
    matrix, then dtheta = g * sum_i x_i * ((W_i / den_i) @ A). The tilt
    t_ik is theta-independent, so it only moves *which* column wins the
    feel-good max (the argmax one-hot is taken on the tilted scores); the
    weight values are unchanged. ``g`` is the (1, 1) cotangent."""
    s, den = _tile_scores(theta, x, a)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    oh1b = cols == a1
    oh2b = cols == a2
    s1 = jnp.sum(jnp.where(oh1b, s, 0.0), axis=1, keepdims=True)
    s2 = jnp.sum(jnp.where(oh2b, s, 0.0), axis=1, keepdims=True)
    z = y * (s1 - s2)
    dz = eta * (-jax.nn.sigmoid(-z)) * y             # d pref / d(s1 - s2)
    oh1 = oh1b.astype(jnp.float32)
    oh2 = oh2b.astype(jnp.float32)
    if mode == "fgts":
        w = dz * (oh1 - oh2)
        t = pref * costs
        live = (cols < k_valid) & (mask > 0)
        sm = jnp.where(live, s - t, -jnp.inf)
        smax = jnp.max(sm, axis=1, keepdims=True)
        # tie-split argmax one-hot: jnp.max's VJP spreads the cotangent
        # evenly over tied maxima, so the hand gradient must too
        eq = ((sm == smax) & live).astype(jnp.float32)
        cnt = jnp.maximum(jnp.sum(eq, axis=1, keepdims=True), 1.0)
        # per-row feel-good weight — must mirror _tile_terms exactly
        mu_row = mu / (1.0 + jnp.maximum(pref, 0.0))
        w = w - mu_row * (eq / cnt)
        w = w + mu_row * (oh2 if j == 1 else oh1)
    else:
        dclick = eta * jnp.where(y > 0.5, -jax.nn.sigmoid(-s1),
                                 jax.nn.sigmoid(s1))
        w = jnp.where(duel > 0, dz * (oh1 - oh2), dclick * oh1)
    w = w * valid
    r = jax.lax.dot_general(w / den, a, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=KERNEL_PRECISION)  # (bm, d)
    return g * jnp.sum(x * r, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# Pallas kernels + drivers (forward and backward)
# ---------------------------------------------------------------------------
# Every operand and output is 2-D with its last two block dims either whole
# or (8, 128)-aligned, as the TPU compiler requires: theta a (1, d) row, the
# per-row minibatch fields (bm, 1) columns, the arm mask and costs (1, Kp)
# rows. Each tile writes its partial into its own output slot — the forward
# a lane-broadcast (1, 128) row, the backward a (1, d) row — and the slots
# are summed outside the kernel, so ``vmap`` over SGLD chains lifts to a
# leading grid axis instead of racing on an accumulator.

_LANES = 128


def _fwd_kernel(th_ref, x_ref, a1_ref, a2_ref, y_ref, du_ref, v_ref, p_ref,
                a_ref, m_ref, c_ref, o_ref, *, mode, j, eta, mu, k_valid):
    total = _tile_terms(
        mode, th_ref[...], x_ref[...], a1_ref[...], a2_ref[...], y_ref[...],
        du_ref[...], v_ref[...], p_ref[...], a_ref[...], m_ref[...],
        c_ref[...], j=j, eta=eta, mu=mu, k_valid=k_valid)
    o_ref[...] = jnp.broadcast_to(total, o_ref.shape)


def _bwd_kernel(g_ref, th_ref, x_ref, a1_ref, a2_ref, y_ref, du_ref, v_ref,
                p_ref, a_ref, m_ref, c_ref, o_ref, *, mode, j, eta, mu,
                k_valid):
    o_ref[...] = _tile_grad(
        mode, th_ref[...], x_ref[...], a1_ref[...], a2_ref[...], y_ref[...],
        du_ref[...], v_ref[...], p_ref[...], a_ref[...], m_ref[...],
        c_ref[...], g_ref[...],
        j=j, eta=eta, mu=mu, k_valid=k_valid)


def _row_specs(spec, d, kp):
    bm = spec.bm
    col = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    return [
        pl.BlockSpec((1, d), lambda i: (0, 0)),      # theta
        pl.BlockSpec((bm, d), lambda i: (i, 0)),     # x
        col, col, col,                               # a1, a2, y
        col, col, col,                               # is_duel, valid, pref
        pl.BlockSpec((kp, d), lambda i: (0, 0)),     # a_emb
        pl.BlockSpec((1, kp), lambda i: (0, 0)),     # arm mask
        pl.BlockSpec((1, kp), lambda i: (0, 0)),     # arm costs
    ]


def _statics(spec):
    return dict(mode=spec.mode, j=spec.j, eta=spec.eta, mu=spec.mu,
                k_valid=spec.k_valid)


def _kernel_operands(theta, x, a1, a2, y, du, valid, pref, a_emb, mask,
                     costs):
    """The custom-VJP operands in the kernels' 2-D layouts."""
    col = lambda v: v.reshape(-1, 1)
    row = lambda v: v.reshape(1, -1)
    return (row(theta), x, col(a1), col(a2), col(y), col(du), col(valid),
            col(pref), a_emb, row(mask), row(costs))


def _forward(spec, theta, x, a1, a2, y, du, valid, pref, a_emb, mask, costs):
    d = x.shape[1]
    kp = a_emb.shape[0]
    n = x.shape[0] // spec.bm
    partials = pl.pallas_call(
        functools.partial(_fwd_kernel, **_statics(spec)),
        grid=(n,),
        in_specs=_row_specs(spec, d, kp),
        out_specs=pl.BlockSpec((None, 1, _LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, _LANES), jnp.float32),
        interpret=spec.interpret,
        name="sgld_potential_fwd",
    )(*_kernel_operands(theta, x, a1, a2, y, du, valid, pref, a_emb, mask,
                        costs))
    return jnp.sum(partials[:, 0, 0])


def _backward(spec, g, theta, x, a1, a2, y, du, valid, pref, a_emb, mask,
              costs):
    d = x.shape[1]
    kp = a_emb.shape[0]
    n = x.shape[0] // spec.bm
    g2 = jnp.reshape(g, (1, 1)).astype(jnp.float32)
    partials = pl.pallas_call(
        functools.partial(_bwd_kernel, **_statics(spec)),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0))]
        + _row_specs(spec, d, kp),
        out_specs=pl.BlockSpec((None, 1, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, d), jnp.float32),
        interpret=spec.interpret,
        name="sgld_potential_bwd",
    )(g2, *_kernel_operands(theta, x, a1, a2, y, du, valid, pref, a_emb,
                            mask, costs))
    return jnp.sum(partials[:, 0, :], axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _potential_sum(spec, theta, x, a1, a2, y, du, valid, pref, a_emb, mask,
                   costs):
    return _forward(spec, theta, x, a1, a2, y, du, valid, pref, a_emb, mask,
                    costs)


def _potential_sum_fwd(spec, theta, x, a1, a2, y, du, valid, pref, a_emb,
                       mask, costs):
    out = _forward(spec, theta, x, a1, a2, y, du, valid, pref, a_emb, mask,
                   costs)
    return out, (theta, x, a1, a2, y, du, valid, pref, a_emb, mask, costs)


def _potential_sum_bwd(spec, res, g):
    theta, x, a1, a2, y, du, valid, pref, a_emb, mask, costs = res
    dtheta = _backward(spec, g, theta, x, a1, a2, y, du, valid, pref, a_emb,
                       mask, costs)
    f0 = lambda v: np.zeros(jnp.shape(v), dtype=jax.dtypes.float0)
    # only theta's cotangent is exact — SGLD differentiates w.r.t. theta
    # alone; x / y / a_emb get symbolic zeros, int operands float0
    return (dtheta, jnp.zeros_like(x), f0(a1), f0(a2), jnp.zeros_like(y),
            jnp.zeros_like(du), jnp.zeros_like(valid),
            jnp.zeros_like(pref), jnp.zeros_like(a_emb), f0(mask),
            jnp.zeros_like(costs))


_potential_sum.defvjp(_potential_sum_fwd, _potential_sum_bwd)


# ---------------------------------------------------------------------------
# Padding + public entry points
# ---------------------------------------------------------------------------

def _prep_rows(bm, x, *rows):
    """Tile-align the minibatch: pad rows to a bm multiple with zeros (the
    valid mask is one of the rows, so padding can never contribute)."""
    m = x.shape[0]
    bm = min(bm, max(8, m))
    m_pad = -(-m // bm) * bm
    if m_pad != m:
        p = m_pad - m
        x = jnp.pad(x, ((0, p), (0, 0)))
        rows = tuple(jnp.pad(r, (0, p)) for r in rows)
    return (bm, x) + rows


def _prep_arms(a_emb, arm_mask, costs=None):
    """Pad the arm table to >= 8 columns; the kernel masks padding via
    k_valid, so padded columns can never win the feel-good max. ``costs``
    (the feel-good tilt's arm operand) defaults to zeros — a bitwise no-op
    tilt — and is zero-padded like the table."""
    k = a_emb.shape[0]
    kp = max(8, k)
    mask = jnp.ones((k,), jnp.int32) if arm_mask is None \
        else arm_mask.astype(jnp.int32)
    costs = jnp.zeros((k,), jnp.float32) if costs is None \
        else costs.astype(jnp.float32)
    if kp != k:
        a_emb = jnp.pad(a_emb, ((0, kp - k), (0, 0)))
        mask = jnp.pad(mask, (0, kp - k))
        costs = jnp.pad(costs, (0, kp - k))
    return a_emb, mask, costs, k


def _resolve_kernel_mode(backend: str, k: int,
                         interpret: bool | None) -> bool:
    """interpret flag for one potential call. "xla" forces the pure-XLA
    interpret lowering; so does K > MAX_K_FUSED (the score tile no longer
    fits VMEM whole)."""
    if backend not in ("fused", "xla"):
        raise ValueError(f"sgld kernel backend {backend!r} (use "
                         f"resolve_sgld_backend for 'auto'/'autodiff')")
    if backend == "xla" or k > MAX_K_FUSED:
        return True
    return _resolve_interpret(interpret)


def sgld_potential(theta: jax.Array, x: jax.Array, a1: jax.Array,
                   a2: jax.Array, y: jax.Array, valid: jax.Array,
                   a_emb: jax.Array, arm_mask: jax.Array | None = None, *,
                   pref: jax.Array | None = None,
                   costs: jax.Array | None = None,
                   j: int = 1, eta: float = 1.0, mu: float = 0.2,
                   backend: str = "fused", bm: int = DEFAULT_BM,
                   interpret: bool | None = None) -> jax.Array:
    """Fused FGTS data potential: sum_i valid_i * L^j_i over a minibatch.

    theta: (d,); x: (m, d); a1/a2: (m,) int32; y/valid: (m,); a_emb: (K, d);
    arm_mask: (K,) bool restricting the feel-good max to active arms (None =
    all arms). ``pref`` (m,) + ``costs`` (K,) condition the feel-good term
    on each row's own preference tilt t_ik = pref_i * cost_k (either None =
    zeros, bit-identical to the untilted term). Returns a float32 scalar;
    ``jax.grad`` w.r.t. theta runs the hand-derived custom-VJP backward.
    ``backend`` is "fused" (compiled Mosaic where available) or "xla" (the
    bit-identical interpret lowering); K > MAX_K_FUSED degrades fused to
    the lowering. ``vmap`` over theta gives per-chain potentials.
    """
    interpret = _resolve_kernel_mode(backend, a_emb.shape[0], interpret)
    ap, mask, cp, k = _prep_arms(a_emb, arm_mask, costs)
    if pref is None:
        pref = jnp.zeros(x.shape[:1], jnp.float32)
    bm, xp, a1p, a2p, yp, vp, pp = _prep_rows(
        bm, x, a1.astype(jnp.int32), a2.astype(jnp.int32),
        y.astype(jnp.float32), valid.astype(jnp.float32),
        pref.astype(jnp.float32))
    du = jnp.zeros_like(yp)                         # unused in fgts mode
    spec = _SgldSpec("fgts", j, float(eta), float(mu), bm, interpret, k)
    return _potential_sum(spec, theta, xp, a1p, a2p, yp, du, vp, pp, ap,
                          mask, cp)


def sgld_mixed_potential(theta: jax.Array, x: jax.Array, a1: jax.Array,
                         a2: jax.Array, y: jax.Array, is_duel: jax.Array,
                         valid: jax.Array, a_emb: jax.Array, *,
                         eta: float = 1.0, backend: str = "fused",
                         bm: int = DEFAULT_BM,
                         interpret: bool | None = None) -> jax.Array:
    """Fused mixed-stream data potential (duels + clicks, no feel-good).

    Duel rows (is_duel) use the BTL preference term on (a1, a2); click rows
    use the Bernoulli term on a1 with y in {0, 1}. Same identity, same
    custom-VJP structure as ``sgld_potential``.
    """
    interpret = _resolve_kernel_mode(backend, a_emb.shape[0], interpret)
    ap, mask, cp, k = _prep_arms(a_emb, None)
    bm, xp, a1p, a2p, yp, dup, vp = _prep_rows(
        bm, x, a1.astype(jnp.int32), a2.astype(jnp.int32),
        y.astype(jnp.float32), is_duel.astype(jnp.float32),
        valid.astype(jnp.float32))
    pp = jnp.zeros_like(yp)                         # no feel-good, no tilt
    spec = _SgldSpec("mixed", 0, float(eta), 0.0, bm, interpret, k)
    return _potential_sum(spec, theta, xp, a1p, a2p, yp, dup, vp, pp, ap,
                          mask, cp)
