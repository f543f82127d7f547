"""Plain references for what the timed path produces, and the controls.

Written from the router's published semantics in straightforward
``jax.numpy``; nothing here imports the program. Each matmul takes a
``mode``:

* ``"highest"``: float32 contraction (``Precision.HIGHEST``), the
  reference;
* ``"high"``: three bf16 passes (hi*hi + hi*lo + lo*hi, the ``high``
  precision), the control for a layer the configuration states at
  "highest";
* ``"bf16"``: one bf16 pass, XLA's default on a TPU;
* ``"int8"``: both operands rounded to 127 symmetric levels per tensor, the
  control for a layer the configuration states at one bf16 pass.

The lower modes are emulated with exact products of rounded operands, so
they read the same on the host CPU as on the chip. The SGLD reference runs
on the host (``matmul_host``, the same modes, gradients in float64), with
the random draws the sampler makes on its device.
"""
from __future__ import annotations

import functools

import numpy as np


def _split_bf16(a):
    import jax.numpy as jnp
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _int8(a):
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
    return jnp.round(a / s) * s


def einsum(spec: str, a, b, mode: str = "highest"):
    """``jnp.einsum`` at the given precision mode (see the module doc)."""
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST
    e = functools.partial(jnp.einsum, spec, precision=hp,
                          preferred_element_type=jnp.float32)
    if mode == "highest":
        return e(a, b)
    if mode == "high":
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)
        return e(ah, bh) + (e(ah, bl) + e(al, bh))
    if mode == "bf16":
        return e(_split_bf16(a)[0], _split_bf16(b)[0])
    if mode == "int8":
        return e(_int8(a), _int8(b))
    raise ValueError(f"unknown precision mode {mode!r}")


# ---------------------------------------------------------------------------
# Query encoder (the block itself is the encoder module's ``reference``)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jitted(encode):
    import jax
    return jax.jit(encode, static_argnames=("mode",))


def encode_rows(encode, params, tokens: np.ndarray, mode: str = "highest",
                block: int = 64) -> np.ndarray:
    """An encoder module's ``reference`` (``encode``) over (n, L) host
    tokens in blocks of rows, so that the reference fits beside the
    program's buffers."""
    fn = _jitted(encode)
    out = []
    for i in range(0, tokens.shape[0], block):
        t = tokens[i:i + block]
        pad = block - t.shape[0]
        if pad:
            t = np.concatenate([t, np.zeros((pad, t.shape[1]), t.dtype)])
        out.append(np.asarray(fn(params, t, mode=mode))[:block - pad])
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# Dueling selection
# ---------------------------------------------------------------------------

def scores(x, a_emb, theta, mode: str = "highest"):
    """(n, K) <theta, phi(x, a_k)> with phi(x, a) = x*a / ||x*a||."""
    import jax.numpy as jnp
    num = einsum("nd,kd->nk", x * theta[None, :], a_emb, mode)
    den = einsum("nd,kd->nk", x * x, a_emb * a_emb, mode)
    return num / jnp.sqrt(jnp.maximum(den, 1e-24))


@functools.partial(__import__("jax").jit, static_argnames=("mode",))
def select_scores(x, a_emb, theta1, theta2, tilt, mode: str = "highest"):
    """Both thetas' tilted scores; ``tilt`` is (n, K)."""
    return (scores(x, a_emb, theta1, mode) - tilt,
            scores(x, a_emb, theta2, mode) - tilt)


def pick_gap(s: np.ndarray, picks: np.ndarray) -> np.ndarray:
    """Per row, how far the picked arm's score lies below the row's best
    (inf for a pick outside the arms or a non-finite score)."""
    s = np.asarray(s, np.float64)
    k = s.shape[1]
    ok = (picks >= 0) & (picks < k)
    got = np.take_along_axis(s, np.clip(picks, 0, k - 1)[:, None], 1)[:, 0]
    gap = s.max(axis=1) - got
    return np.where(ok & np.isfinite(gap), gap, np.inf)


# ---------------------------------------------------------------------------
# SGLD posterior sample (FGTS.CDB, warm-started chains)
# ---------------------------------------------------------------------------

def _split_bf16_host(a: np.ndarray):
    import ml_dtypes
    bf = ml_dtypes.bfloat16
    a = np.asarray(a, np.float32)
    hi = a.astype(bf).astype(np.float32)
    lo = (a - hi).astype(bf).astype(np.float32)
    return hi.astype(np.float64), lo.astype(np.float64)


def matmul_host(a: np.ndarray, b: np.ndarray, mode: str = "highest"):
    """``a @ b`` on the host in float64 at the given precision mode: the
    operands of ``"high"`` and ``"bf16"`` are rounded as the passes round
    them, and their products summed in float64."""
    if mode == "highest":
        return np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    ah, al = _split_bf16_host(a)
    bh, bl = _split_bf16_host(b)
    if mode == "high":
        return ah @ bh + (ah @ bl + al @ bh)
    if mode == "bf16":
        return ah @ bh
    raise ValueError(f"unknown precision mode {mode!r}")


def _data_grad(theta, x, a1, a2, y, pref, valid, a_emb, costs, j, eta, mu,
               mode):
    """d/dtheta of sum_i valid_i * L^j_i(theta), in float64 on the host:
    the BTL term eta * softplus(-y (s_a1 - s_a2)) minus the feel-good term
    mu / (1 + pref_i) * (max_k (s_k - pref_i c_k) - (s_opp - pref_i c_opp)),
    with a tied maximum's gradient split evenly over the tied arms."""
    num = matmul_host(x * theta[None, :], a_emb.T, mode)
    den = np.sqrt(np.maximum(matmul_host(x * x, (a_emb * a_emb).T, mode),
                             1e-24))
    s = num / den
    n, k = s.shape
    rows = np.arange(n)
    oh1 = np.zeros((n, k))
    oh1[rows, a1] = 1.0
    oh2 = np.zeros((n, k))
    oh2[rows, a2] = 1.0
    z = y * (s[rows, a1] - s[rows, a2])
    dz = -eta * y / (1.0 + np.exp(z))             # -eta y sigmoid(-z)
    w = dz[:, None] * (oh1 - oh2)
    sm = s - pref[:, None] * costs[None, :]
    eq = (sm == sm.max(axis=1, keepdims=True)).astype(np.float64)
    mu_row = (mu / (1.0 + np.maximum(pref, 0.0)))[:, None]
    w = w - mu_row * eq / eq.sum(axis=1, keepdims=True)
    w = w + mu_row * (oh2 if j == 1 else oh1)
    w = w * valid[:, None]
    r = matmul_host(w / den, a_emb, mode)
    return np.sum(x * r, axis=0)


@functools.partial(__import__("jax").jit,
                   static_argnames=("n_chains", "steps", "m", "d",
                                    "eps_temp"))
def sgld_draws(key, t, cap: int, *, n_chains: int, steps: int, m: int,
               d: int, eps_temp: float):
    """The sampler's random draws for every chain of one posterior sample:
    (n_chains, steps, m) minibatch slots and (n_chains, steps, d) Langevin
    noise. ``key`` splits into one key per chain, each chain's key into
    one per step, and each step's into the minibatch draw (uniform slots
    below min(t, cap)) and the noise, as the sampler draws them under
    partitionable threefry, on the device the sampler runs on."""
    import jax
    import jax.numpy as jnp
    hi = jnp.maximum(jnp.minimum(t, cap), 1)

    def chain(kc):
        def step(_, k):
            k_idx, k_noise = jax.random.split(k)
            idx = jax.random.randint(k_idx, (m,), 0, hi)
            noise = jnp.sqrt(eps_temp) * jax.random.normal(k_noise, (d,))
            return None, (idx, noise)
        return jax.lax.scan(step, None, jax.random.split(kc, steps))[1]

    with jax.threefry_partitionable(True):
        return jax.vmap(chain)(jax.random.split(key, n_chains))


def sgld_chains(key, theta0, rx, ra1, ra2, ry, rpref, t, a_emb, costs, *,
                j: int, fg: tuple, mode: str = "highest",
                state: str = "float64"):
    """Run every chain of posterior sample ``j`` from ``theta0`` (C, d) on
    the replay ring (rx, ra1, ra2, ry, rpref; ``t`` duels folded so far),
    on the host with the sampler's own draws (``sgld_draws``). The
    gradient is computed in float64 with its matmuls at ``mode``; the
    chain's state is kept in ``state``: ``"float64"``, or ``"float32"``,
    rounded after each step as the sampler's float32 update
    ``theta - (eps / 2) * g + noise`` rounds it. Returns the chains'
    samples (C, d) and how far the data term moved each chain (C,): the
    norm of the sum of its gradient steps, the prior's left out.
    ``fg`` holds (steps, minibatch, eps, temp, prior_var, eta, mu)."""
    import jax
    import jax.numpy as jnp
    steps, m, eps, temp, prior_var, eta, mu = fg
    n_chains, d = np.shape(theta0)
    idx, noise = jax.device_get(sgld_draws(
        key, jnp.int32(t), int(rx.shape[0]), n_chains=n_chains,
        steps=steps, m=m, d=d, eps_temp=float(eps * temp)))
    rx, ry, rpref = (np.asarray(a, np.float64) for a in (rx, ry, rpref))
    a_emb = np.asarray(a_emb, np.float64)
    costs = np.asarray(costs, np.float64)
    half_eps = np.float32(0.5 * eps)
    out = np.empty((n_chains, d))
    moved = np.empty(n_chains)
    for c in range(n_chains):
        th = np.asarray(theta0[c], np.float32)
        if state == "float64":
            th = th.astype(np.float64)
        data = np.zeros(d)
        for s in range(steps):
            i = idx[c, s]
            valid = (i < t).astype(np.float64)
            scale = float(t) / max(valid.sum(), 1.0)
            dg = scale * _data_grad(np.asarray(th, np.float64), rx[i],
                                    ra1[i], ra2[i], ry[i], rpref[i], valid,
                                    a_emb, costs, j, eta, mu, mode)
            data += dg
            g = dg + np.asarray(th, np.float64) / prior_var
            if state == "float32":
                th = (th - half_eps * g.astype(np.float32)) + noise[c, s]
            elif state == "float64":
                th = th - 0.5 * eps * g + noise[c, s].astype(np.float64)
            else:
                raise ValueError(f"unknown state type {state!r}")
        out[c] = th
        moved[c] = 0.5 * eps * np.linalg.norm(data)
    return out, moved


def route_key(seed: int, call: int):
    """The key the service hands its ``call``-th route (1-based): the
    service's key stream starts at PRNGKey(seed), gives its first split to
    the policy's init and one split to each route."""
    import jax
    key = jax.random.PRNGKey(seed)
    for _ in range(call + 1):
        key, k = jax.random.split(key)
    return k


def sample_keys(key):
    """The keys of posterior samples 1 and 2 within one route."""
    import jax
    with jax.threefry_partitionable(True):
        k1, k2 = jax.random.split(key)
    return k1, k2


# ---------------------------------------------------------------------------
# Pending ring and replay fold
# ---------------------------------------------------------------------------

class FoldReference:
    """Sequential replay of what was sent: each routed request takes the
    next ticket; a delivered vote folds when its ticket is still pending
    and was issued fewer than ``capacity`` tickets ago; folded duels append
    to a ring of ``horizon`` slots in delivery order."""

    def __init__(self, horizon: int, d: int, capacity: int):
        self.h, self.cap = horizon, capacity
        self.x = np.zeros((horizon, d), np.float32)
        self.a1 = np.zeros(horizon, np.int32)
        self.a2 = np.zeros(horizon, np.int32)
        self.y = np.zeros(horizon, np.float32)
        self.pref = np.zeros(horizon, np.float32)
        self.t = 0
        self.seq = 0
        self.pending = {}          # request -> issue sequence number
        self.folded = 0

    def issue(self, reqs):
        for r in reqs:
            self.pending[int(r)] = self.seq
            self.seq += 1

    def deliver(self, reqs, y, rows):
        """``rows(r)`` gives (x, a1, a2, pref) of request ``r``."""
        for r, v in zip(reqs, y):
            s = self.pending.pop(int(r), None)
            if s is None or self.seq - s > self.cap:
                continue
            x, a1, a2, pref = rows(int(r))
            i = self.t % self.h
            self.x[i], self.a1[i], self.a2[i] = x, a1, a2
            self.y[i], self.pref[i] = v, pref
            self.t += 1
            self.folded += 1

    def n_pending(self) -> int:
        """Tickets still in the ring: unresolved and not overwritten."""
        return sum(1 for s in self.pending.values()
                   if self.seq - s <= self.cap)

    def state(self):
        return dict(x=self.x.copy(), a1=self.a1.copy(), a2=self.a2.copy(),
                    y=self.y.copy(), pref=self.pref.copy(), t=self.t)
