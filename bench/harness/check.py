"""The comparison that decides ``correct``, on what the timed path produced.

Four numbers, each against a limit kept in the configuration file
(``check``):

* ``enc_err``: the widest L2 distance between a served query embedding and
  the reference encoder's (float32 at "highest") for the same tokens, over
  a sample of the window's requests drawn from the seed. Embeddings are
  unit vectors, so this is relative.
* ``sgld_err``: for a sample of the window's routes, each chain's posterior
  sample against reference SGLD chains run from the same warm start, on
  the replay ring rebuilt from what was sent, with the sampler's own random
  draws: the chain's distance from its float32 twin (exact gradient, state
  rounded to float32 after each step as the sampler's is), in units of
  the distance between the twin and the exact chain (float64 state and
  gradient), as a median over the chains that the data moved. The widest
  chain is not compared: a feel-good maximum that two arms tie for to
  rounding flips one row's gradient, and the chains multiply small
  differences by up to a thousand, so the widest gap swings by itself.
* ``pair_gap``: over every request routed in the window, how far the
  served arm's reference score (given the served posterior sample and the
  request's tilt) lies below the best arm's.
* ``fold_mismatch``: replay-ring slots that differ from a sequential fold
  of the votes that were delivered, plus every routed, folded and pending
  count that differs from what was sent. Exact: its limit is 0.

The control puts the reference in the program's place at the next
precision below the configuration's (``control_numbers``).
"""
from __future__ import annotations

import numpy as np

from . import reference as ref
from .serve import Delivery, RouteCall

NAMES = ("enc_err", "sgld_err", "pair_gap", "fold_mismatch")
N_ENC_ROWS = 1024
N_SGLD_CALLS = 12


def _fg(cfg) -> tuple:
    return (cfg.sgld_steps, cfg.sgld_minibatch, cfg.sgld_eps, cfg.sgld_temp,
            cfg.prior_var, cfg.eta, cfg.mu)


class Evidence:
    """What the window produced, on the host, and the inputs the
    references need; gathered once the window has closed."""

    def __init__(self, sess, seed: int):
        import jax
        self.sess = sess
        self.rng = np.random.default_rng([seed, 5])
        self.calls = [c for c in sess.calls]
        for c in self.calls:
            c.x = np.asarray(jax.device_get(c.x))
            c.theta = np.asarray(jax.device_get(c.theta))
        self.window = [i for i, c in enumerate(self.calls) if c.in_window]
        self.row_of = {}
        for i, c in enumerate(self.calls):
            for j, r in enumerate(c.ids):
                self.row_of[int(r)] = (i, j)

    def tilt(self, ids: np.ndarray) -> np.ndarray:
        s = self.sess
        costs = s.pool.costs.astype(np.float32)
        t = np.broadcast_to(s.pool.cost_tilt * costs,
                            (ids.shape[0], costs.shape[0])).copy()
        if s.reqs.pref is not None:
            t += s.reqs.pref[ids][:, None] * costs[None, :]
        return t

    def row(self, r: int):
        i, j = self.row_of[r]
        c = self.calls[i]
        pref = 0.0 if self.sess.reqs.pref is None else \
            float(self.sess.reqs.pref[r])
        return c.x[j], c.a1[j], c.a2[j], pref

    def replays(self, at: set) -> dict:
        """The reference replay ring just before each route call in ``at``
        (call numbers), and after the last call (key None)."""
        s = self.sess
        cfg = s.fgts_cfg
        fold = ref.FoldReference(cfg.horizon, s.d,
                                 _ring_capacity(s.config))
        out = {}
        for ev in s.log:
            if isinstance(ev, RouteCall):
                if ev.call in at:
                    out[ev.call] = fold.state()
                fold.issue(ev.ids)
            elif isinstance(ev, Delivery):
                fold.deliver(ev.ids, ev.y, self.row)
        out[None] = fold.state()
        out["pending"] = fold.n_pending()
        out["folded"] = fold.folded
        return out


def _ring_capacity(config: dict) -> int:
    c = int(config["serving"]["feedback_capacity"])
    return 1 << (c - 1).bit_length()


def _sampled_calls(ev: Evidence) -> list:
    w = ev.window
    if len(w) <= N_SGLD_CALLS:
        return list(w)
    return sorted(ev.rng.choice(w, N_SGLD_CALLS, replace=False).tolist())


def _sampled_rows(ev: Evidence) -> np.ndarray:
    ids = np.concatenate([ev.calls[i].ids for i in ev.window])
    if ids.shape[0] <= N_ENC_ROWS:
        return ids
    return np.sort(ev.rng.choice(ids, N_ENC_ROWS, replace=False))


def _sgld(ev: Evidence, i: int, rep: dict, mode: str, state: str):
    """Reference chains for route call index ``i``, on the host:
    (theta (2, C, d), how far the data term moved each chain (2, C))."""
    s = ev.sess
    c = ev.calls[i]
    theta0 = ev.calls[i - 1].theta
    keys = ref.sample_keys(ref.route_key(s.seeds["service"], c.call))
    th, moved = [], []
    for j in (1, 2):
        t, m = ref.sgld_chains(
            keys[j - 1], theta0[j - 1], rep["x"], rep["a1"], rep["a2"],
            rep["y"], rep["pref"], int(rep["t"]), s.a_emb, s.pool.costs,
            j=j, fg=_fg(s.fgts_cfg), mode=mode, state=state)
        th.append(t)
        moved.append(m)
    return np.stack(th), np.stack(moved)


def _dist(a, b) -> np.ndarray:
    d = a.shape[-1]
    return np.linalg.norm((np.asarray(a, np.float64) - b).reshape(-1, d),
                          axis=1)


def _sgld_ratios(ev: Evidence, picked: list, reps: dict, chains) -> dict:
    """For the sampled routes' chains that the data moved, each chain's
    distance from its float32 twin (the exact gradient, the state kept in
    float32 as the sampler keeps it) and from the exact chain (float64
    state and gradient), both in units of the distance between the twin
    and the exact chain: what keeping the state in float32 alone makes.
    Returns the median of each (``twin``, ``exact``). ``chains(i, rep)``
    gives the chains under test for route index ``i``. A chain counts where
    the reference's data term moved it by at least a thousandth of the
    most any sampled chain was moved; the others move by the prior and
    rounding alone."""
    to_twin, to_exact, moved = [], [], []
    for i in picked:
        rep = reps[ev.calls[i].call]
        exact, m = _sgld(ev, i, rep, "highest", "float64")
        twin, _ = _sgld(ev, i, rep, "highest", "float32")
        unit = np.maximum(_dist(twin, exact), 1e-30)
        th = chains(i, rep)
        to_twin.append(_dist(th, twin) / unit)
        to_exact.append(_dist(th, exact) / unit)
        moved.append(m.reshape(-1))
    if not moved:
        return {"twin": float("nan"), "exact": float("nan")}
    moved = np.concatenate(moved)
    counted = moved >= 1e-3 * moved.max()
    return {k: float(np.median(np.concatenate(v)[counted]))
            for k, v in (("twin", to_twin), ("exact", to_exact))}


def _gaps(ev: Evidence, i: int, a1, a2) -> float:
    import jax.numpy as jnp
    c = ev.calls[i]
    th = c.theta.mean(axis=1)
    s1, s2 = ref.select_scores(jnp.asarray(c.x), ev.sess.a_emb,
                               jnp.asarray(th[0]), jnp.asarray(th[1]),
                               jnp.asarray(ev.tilt(c.ids)))
    return float(max(ref.pick_gap(np.asarray(s1), a1).max(),
                     ref.pick_gap(np.asarray(s2), a2).max()))


def numbers(ev: Evidence, final: dict) -> dict:
    """The four numbers for the program's run. ``final`` holds the
    program's replay ring (x, a1, a2, y, pref, t) and its
    ``service_stats()`` after the window."""
    s = ev.sess
    enc_d = s.encoder.embed_dim(s.enc)
    rows = _sampled_rows(ev)
    x_ref = ref.encode_rows(s.encoder.reference, s.params,
                            s.reqs.tokens[rows])
    x_prog = np.stack([ev.row(int(r))[0][:enc_d] for r in rows])
    enc_err = float(np.max(np.linalg.norm(x_prog - x_ref, axis=1)))

    picked = _sampled_calls(ev)
    reps = ev.replays({ev.calls[i].call for i in picked})
    sgld_err = _sgld_ratios(ev, picked, reps,
                            lambda i, rep: ev.calls[i].theta)["twin"]

    pair_gap = max((_gaps(ev, i, ev.calls[i].a1, ev.calls[i].a2)
                    for i in ev.window), default=0.0)

    last = reps[None]
    diff = ~np.all(final["x"] == last["x"], axis=1)
    for f in ("a1", "a2", "y", "pref"):
        diff |= final[f] != last[f]
    stats = final["stats"]
    routed = sum(c.ids.shape[0] for c in ev.calls)
    fold_mismatch = int(diff.sum()) + int(final["t"] != last["t"]) \
        + int(stats["n_routed"] != routed) \
        + int(stats["n_folded"] != reps["folded"]) \
        + int(stats["pending"] != reps["pending"])
    return {"enc_err": enc_err, "sgld_err": sgld_err, "pair_gap": pair_gap,
            "fold_mismatch": float(fold_mismatch)}


def control_numbers(ev: Evidence, enc_mode: str, kernel_mode: str) -> dict:
    """The same numbers with the reference, computed at ``enc_mode`` for
    the encoder and ``kernel_mode`` for the SGLD and selection matmuls, in
    the program's place (fed the program's own inputs)."""
    import jax.numpy as jnp
    s = ev.sess
    rows = _sampled_rows(ev)
    tok = s.reqs.tokens[rows]
    x_ref = ref.encode_rows(s.encoder.reference, s.params, tok)
    x_ctl = ref.encode_rows(s.encoder.reference, s.params, tok,
                            mode=enc_mode)
    enc_err = float(np.max(np.linalg.norm(x_ctl - x_ref, axis=1)))

    picked = _sampled_calls(ev)
    reps = ev.replays({ev.calls[i].call for i in picked})
    sgld_err = _sgld_ratios(ev, picked, reps, lambda i, rep: _sgld(
        ev, i, rep, kernel_mode, "float32")[0])["twin"]

    pair_gap = 0.0
    for i in ev.window:
        c = ev.calls[i]
        th = c.theta.mean(axis=1)
        args = (jnp.asarray(c.x), s.a_emb, jnp.asarray(th[0]),
                jnp.asarray(th[1]), jnp.asarray(ev.tilt(c.ids)))
        c1, c2 = ref.select_scores(*args, mode=kernel_mode)
        pair_gap = max(pair_gap, _gaps(
            ev, i, np.argmax(np.asarray(c1), axis=1),
            np.argmax(np.asarray(c2), axis=1)))
    return {"enc_err": enc_err, "sgld_err": sgld_err, "pair_gap": pair_gap,
            "fold_mismatch": 0.0}


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit; a number without a limit fails."""
    out, ok = {}, True
    for k in NAMES:
        lim = limits.get(k)
        v = nums[k]
        out[k] = {"value": v, "limit": lim}
        ok &= lim is not None and np.isfinite(v) and v <= lim
    return bool(ok), out


def program_final(sess) -> dict:
    """The program's replay ring and counters after the window, on the
    host; then the service is dropped, so that the references run with the
    program's state freed."""
    import jax
    st = sess.svc.state
    final = {k: jax.device_get(getattr(st, k))
             for k in ("x", "a1", "a2", "y", "pref", "t")}
    final["stats"] = sess.svc.service_stats()
    sess.svc = None
    return final


def judge(sess, seed: int, limits: dict) -> tuple[bool, dict, int]:
    """(correct, {name: value and limit}, window requests routed to an arm
    outside the pool) of a session whose window has closed. Such a pair is
    never correct."""
    final = program_final(sess)
    ev = Evidence(sess, seed)
    nums = numbers(ev, final)
    correct, shown = verdict(nums, limits)
    k = len(sess.pool.names)
    bad = sum(int(((c.a1 < 0) | (c.a1 >= k) | (c.a2 < 0) | (c.a2 >= k))
                  .sum()) for c in ev.calls if c.in_window)
    return correct and bad == 0, shown, bad
