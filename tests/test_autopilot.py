"""Pool autopilot: posterior-dominance retirement, A/B candidate slots,
cost governor.

Contracts pinned here:

  * ``dominance_matrix`` agrees between the Pallas score kernel and the
    XLA reference path (parity, like ``dueling_select``), and is a valid
    pairwise win-probability matrix (diagonal 0.5, P + P^T == 1);
  * the controller retires an arm only when a cheaper-or-equal active
    full member dominates it for ``window`` consecutive control ticks,
    never shrinks the pool below ``min_active``, and a retired arm is
    never emitted by ``act`` afterwards;
  * candidate traffic honours the quota gate: with quota 0 a candidate is
    never selected, and a candidate's traffic share stays at the gate
    rate in expectation; promotion and rollback fire on the duel record;
  * the cost governor raises lambda above budget and holds the realized
    duel cost at the configured budget;
  * a mid-flight service checkpoint round-trips the controller state
    (lambda, candidacy, tallies) next to the posterior;
  * control ticks and autopilot membership flips compile zero new
    programs — single device and the 8-device mesh lane.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import autopilot as ap
from repro.core import baselines, env as env_lib, fgts
from repro.core import model_pool as mp
from repro.core import policy as policy_lib
from repro.kernels.dueling_score import posterior_scores

KEY = jax.random.PRNGKey(11)
K, DIM, T = 5, 16, 96
BATCH = 4


def _cfg(**kw):
    d = dict(n_models=K, dim=DIM, horizon=256, sgld_steps=2,
             sgld_minibatch=4, n_chains=2)
    d.update(kw)
    return fgts.FGTSConfig(**d)


def _pool(costs=None, key=KEY):
    a_emb = jax.random.normal(jax.random.fold_in(key, 1), (K, DIM))
    return mp.init_pool(a_emb, costs)


# ---------------------------------------------------------------------------
# dominance matrix: kernel/XLA parity + probability structure
# ---------------------------------------------------------------------------

def test_posterior_scores_kernel_matches_ref():
    for kk, (k_arms, c) in enumerate([(3, 1), (8, 4), (13, 7)]):
        a = jax.random.normal(jax.random.fold_in(KEY, kk), (k_arms, DIM))
        th = jax.random.normal(jax.random.fold_in(KEY, 50 + kk), (c, DIM))
        np.testing.assert_allclose(
            np.asarray(posterior_scores(a, th)),
            np.asarray(ap.posterior_scores_ref(a, th)),
            rtol=1e-5, atol=1e-6)


def test_dominance_matrix_parity_and_structure():
    pool = _pool()
    chains = jax.random.normal(jax.random.fold_in(KEY, 2), (6, DIM))
    d_k = np.asarray(ap.dominance_matrix(chains, pool, use_kernel=True))
    d_x = np.asarray(ap.dominance_matrix(chains, pool, use_kernel=False))
    np.testing.assert_allclose(d_k, d_x, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.diag(d_x), 0.5)
    np.testing.assert_allclose(d_x + d_x.T, np.ones((K, K)), atol=1e-6)
    assert (d_x >= 0).all() and (d_x <= 1).all()


def test_dominance_matrix_scale_invariant():
    """posterior_scores normalizes each arm row, so rescaling an embedding
    cannot manufacture (or hide) dominance."""
    pool = _pool()
    chains = jax.random.normal(jax.random.fold_in(KEY, 3), (4, DIM))
    scaled = pool._replace(a_emb=pool.a_emb * 7.5)
    np.testing.assert_allclose(
        np.asarray(ap.dominance_matrix(chains, pool, use_kernel=False)),
        np.asarray(ap.dominance_matrix(chains, scaled, use_kernel=False)),
        atol=1e-6)


# ---------------------------------------------------------------------------
# controller.step unit behaviour
# ---------------------------------------------------------------------------

def _aligned_posterior(pool, best, worst, n=6):
    """Posterior samples that unanimously score ``best`` above ``worst``:
    theta = the normalized difference of their embeddings (plus copies)."""
    e = pool.a_emb / jnp.linalg.norm(pool.a_emb, axis=-1, keepdims=True)
    theta = e[best] - e[worst]
    return jnp.tile(theta[None, :], (n, 1))


def test_step_retires_after_window_consecutive_ticks():
    costs = jnp.asarray([0.1, 0.2, 0.3, 0.4, 0.5])
    pool = _pool(costs)
    cfg = ap.AutopilotConfig(tau=0.9, window=3)
    post = _aligned_posterior(pool, best=0, worst=4)
    ctrl = ap.init_controller(pool.active)
    for tick in range(3):
        ctrl, dec = ap.step(ctrl, post, pool, cfg, use_kernel=False)
        assert bool(dec.dominated[4])
        assert bool(dec.retire[4]) == (tick == 2)   # fires on the 3rd tick
        pool = ap.apply_decisions(pool, dec)
    assert not bool(pool.active[4])
    # a dominated streak that breaks resets the window
    ctrl2 = ap.init_controller(_pool(costs).active)
    p2 = _pool(costs)
    ctrl2, _ = ap.step(ctrl2, post, p2, cfg, use_kernel=False)
    ctrl2, dec = ap.step(ctrl2, None, p2, cfg, use_kernel=False)  # no post
    assert int(ctrl2.dominated_ticks[4]) == 0
    ctrl2, dec = ap.step(ctrl2, post, p2, cfg, use_kernel=False)
    assert not bool(dec.retire[4])


def test_step_cost_aware_never_retires_for_a_pricier_winner():
    """Arm 4 beats arm 0 on quality with probability 1, but costs more —
    the cheaper arm 0 must survive (the paper's cost axis is a first-class
    control knob, not a tiebreak)."""
    costs = jnp.asarray([0.1, 0.2, 0.3, 0.4, 5.0])
    pool = _pool(costs)
    cfg = ap.AutopilotConfig(tau=0.9, window=1)
    post = _aligned_posterior(pool, best=4, worst=0)
    ctrl = ap.init_controller(pool.active)
    ctrl, dec = ap.step(ctrl, post, pool, cfg, use_kernel=False)
    assert not bool(dec.dominated[0])
    assert not bool(dec.retire[0])
    # ...and the pricier winner itself is not dominated by its victim
    assert not bool(dec.dominated[4])


def test_step_min_active_floor_cancels_kills():
    costs = jnp.asarray([0.1, 0.2, 0.3, 0.4, 0.5])
    pool = _pool(costs)
    for k in range(2, K):
        pool = mp.retire_arm(pool, k)          # two survivors: 0, 1
    cfg = ap.AutopilotConfig(tau=0.9, window=1, min_active=2)
    post = _aligned_posterior(pool, best=0, worst=1)
    ctrl = ap.init_controller(pool.active)
    ctrl, dec = ap.step(ctrl, post, pool, cfg, use_kernel=False)
    assert bool(dec.dominated[1]) and not bool(dec.retire[1])
    assert mp.n_active_mask(ap.apply_decisions(pool, dec).active) == 2


def test_step_promote_and_rollback_paths():
    pool = _pool()
    cfg = ap.AutopilotConfig(promote_wins=4.0, max_cand_duels=10.0)
    ctrl = ap.init_controller(pool.active)
    cand = jnp.zeros((K,), bool).at[2].set(True).at[3].set(True)
    ctrl = ctrl._replace(
        candidate=cand,
        cand_wins=jnp.asarray([0.0, 0.0, 5.0, 1.0, 0.0]),
        cand_duels=jnp.asarray([0.0, 0.0, 8.0, 12.0, 0.0]))
    ctrl, dec = ap.step(ctrl, None, pool, cfg, use_kernel=False)
    assert bool(dec.promote[2]) and not bool(dec.rollback[2])
    assert bool(dec.rollback[3]) and not bool(dec.promote[3])
    pool = ap.apply_decisions(pool, dec)
    assert bool(pool.active[2])                # promoted: stays, full member
    assert not bool(pool.active[3])            # rolled back: retired
    assert not ctrl.candidate.any()            # both left candidacy
    assert float(ctrl.cand_wins[2]) == 0.0     # counters reset


def test_step_budget_lambda_integrates_and_clamps():
    pool = _pool(jnp.ones((K,)))
    cfg = ap.AutopilotConfig(budget=0.5, budget_lr=0.5, lam_max=1.0)
    ctrl = ap.init_controller(pool.active)._replace(
        cost_ema=jnp.asarray(1.5))
    lam = []
    for _ in range(5):
        ctrl, dec = ap.step(ctrl, None, pool, cfg, use_kernel=False)
        lam.append(float(dec.lam))
    assert lam[0] == 0.5 and lam[1] == 1.0      # integrates the error
    assert max(lam) <= 1.0                      # clamped at lam_max
    ctrl = ctrl._replace(cost_ema=jnp.asarray(0.0))
    for _ in range(10):
        ctrl, dec = ap.step(ctrl, None, pool, cfg, use_kernel=False)
    assert float(dec.lam) == 0.0                # never goes negative


# ---------------------------------------------------------------------------
# wrapped-policy behaviour (env loop end to end)
# ---------------------------------------------------------------------------

def _dominated_world(key=KEY):
    """Linear world where slot K-1 is strictly worse than the cheap slot 0
    (same construction as bench_autopilot, miniaturized)."""
    from repro.core import ccft
    k_a, k_th, k_x, k_n = jax.random.split(jax.random.fold_in(key, 7), 4)
    a_emb = jax.random.normal(k_a, (K, DIM))
    theta_star = jax.random.normal(k_th, (DIM,))
    x = jax.random.normal(k_x, (T, DIM))
    u0 = jax.vmap(lambda xi: ccft.scores_all(xi, a_emb, theta_star))(x)
    a_emb = a_emb[jnp.argsort(-u0.mean(axis=0))]
    bad = a_emb[0] - 0.6 * theta_star * jnp.sign(
        jnp.sum(a_emb[0] * theta_star)) \
        + 0.2 * jax.random.normal(k_n, (DIM,))
    a_emb = a_emb.at[K - 1].set(bad)
    utils = jax.vmap(lambda xi: ccft.scores_all(xi, a_emb, theta_star))(x)
    utils = (utils - utils.min()) / (utils.max() - utils.min())
    costs = jnp.asarray([0.1, 0.2, 0.3, 0.2, 2.0])
    return env_lib.EnvData(x=x, utils=utils), a_emb, costs


def test_wrapped_fgts_retires_dominated_arm_and_never_selects_it_after():
    e, a_emb, costs = _dominated_world()
    pol = ap.wrap(policy_lib.fgts_policy(mp.init_pool(a_emb, costs),
                                         _cfg(eta=8.0, sgld_steps=8,
                                              sgld_minibatch=16)),
                  ap.AutopilotConfig(every=3, tau=0.75, window=2))
    cum, state = env_lib.run(KEY, e, pol, batch=BATCH)
    pool = mp.get_pool(state)
    assert not bool(pool.active[K - 1]), "dominated arm not retired"
    # the replay ring records every routed duel in tick order: once the
    # arm left the pool it must never appear again
    inner = state.inner.inner
    t = int(inner.t)
    a_rows = np.stack([np.asarray(inner.a1)[:t], np.asarray(inner.a2)[:t]])
    hits = np.flatnonzero((a_rows == K - 1).any(axis=0))
    last_active_row = hits.max() if hits.size else -1
    # after its last appearance, >= one full batch of ticks passed with
    # the arm retired and absent
    assert last_active_row < t - BATCH
    assert float(cum[-1]) == float(cum[-1])     # finite curve


def test_wrapper_act_emits_only_active_arms_every_tick():
    """Act-by-act: whatever the controller decides mid-stream, an emitted
    arm is active in the post-act pool (the decision applies to the very
    act that makes it)."""
    e, a_emb, costs = _dominated_world()
    pol = ap.wrap(policy_lib.fgts_policy(mp.init_pool(a_emb, costs),
                                         _cfg(eta=8.0)),
                  ap.AutopilotConfig(every=2, tau=0.75, window=2))
    state = pol.init(KEY)
    act = jax.jit(pol.act)
    update = jax.jit(pol.update)
    from repro.core.btl import sample_preference
    rows = jnp.arange(BATCH)
    for r in range(16):
        k = jax.random.fold_in(KEY, 100 + r)
        x_b = e.x[r * BATCH:(r + 1) * BATCH]
        u_b = e.utils[r * BATCH:(r + 1) * BATCH]
        state, a1, a2 = act(k, state, x_b)
        active = np.asarray(mp.get_pool(state).active)
        assert active[np.asarray(a1)].all() and active[np.asarray(a2)].all()
        y = sample_preference(jax.random.fold_in(k, 1),
                              5.0 * u_b[rows, a1], 5.0 * u_b[rows, a2])
        state = update(state, x_b, a1, a2, y)


def test_candidate_quota_zero_blocks_all_candidate_traffic():
    """quota=0: a candidate can never be duelled, however strong."""
    a_emb = jax.random.normal(jax.random.fold_in(KEY, 21), (K, DIM))
    pol = ap.wrap(baselines.uniform_policy(mp.init_pool(a_emb)),
                  ap.AutopilotConfig(every=1000, quota=0.0))
    state = pol.init(KEY)
    state = state._replace(ctrl=state.ctrl._replace(
        candidate=jnp.zeros((K,), bool).at[2].set(True)))
    x = jax.random.normal(KEY, (64, DIM))
    for r in range(5):
        state, a1, a2 = pol.act(jax.random.fold_in(KEY, r), state, x)
        arms = np.concatenate([np.asarray(a1), np.asarray(a2)])
        assert (arms != 2).all()


def test_candidate_quota_share_matches_gate_in_expectation():
    """Uniform routing, one candidate among K=5 arms: rows that can see
    the candidate are gated at ``quota``, so the candidate's share of a1
    slots is quota * (1/K) +- sampling noise — far below its 1/K
    full-member share, and scaling with quota."""
    a_emb = jax.random.normal(jax.random.fold_in(KEY, 22), (K, DIM))
    shares = {}
    for quota in (0.1, 0.5):
        pol = ap.wrap(baselines.uniform_policy(mp.init_pool(a_emb)),
                      ap.AutopilotConfig(every=1000, quota=quota))
        state = pol.init(KEY)
        state = state._replace(ctrl=state.ctrl._replace(
            candidate=jnp.zeros((K,), bool).at[2].set(True)))
        x = jax.random.normal(KEY, (512, DIM))
        hits = total = 0
        for r in range(6):
            state, a1, a2 = pol.act(jax.random.fold_in(KEY, 40 + r),
                                    state, x)
            arms = np.concatenate([np.asarray(a1), np.asarray(a2)])
            hits += int((arms == 2).sum())
            total += arms.size
        shares[quota] = hits / total
    for quota, share in shares.items():
        expected = quota / K
        assert share <= 3.0 * expected + 0.01, (quota, share)
    assert shares[0.1] < shares[0.5]


def test_candidate_promotion_lifts_the_quota():
    """A winning candidate is promoted at a control tick and its traffic
    is no longer gated (it becomes eligible on every row)."""
    a_emb = jax.random.normal(jax.random.fold_in(KEY, 23), (K, DIM))
    pol = ap.wrap(baselines.uniform_policy(mp.init_pool(a_emb)),
                  ap.AutopilotConfig(every=1, quota=0.0, promote_wins=2.0))
    state = pol.init(KEY)
    state = state._replace(ctrl=state.ctrl._replace(
        candidate=jnp.zeros((K,), bool).at[2].set(True),
        cand_wins=jnp.zeros((K,)).at[2].set(5.0),
        cand_duels=jnp.zeros((K,)).at[2].set(6.0)))
    x = jax.random.normal(KEY, (256, DIM))
    # first act runs the control tick -> promotion; quota 0 then irrelevant
    state, a1, a2 = pol.act(KEY, state, x)
    assert not bool(state.ctrl.candidate[2])
    state, a1, a2 = pol.act(jax.random.fold_in(KEY, 1), state, x)
    arms = np.concatenate([np.asarray(a1), np.asarray(a2)])
    assert (arms == 2).any()                   # back to full-member traffic
    assert bool(mp.get_pool(state).active[2])


def test_candidate_rollback_retires_the_arm():
    a_emb = jax.random.normal(jax.random.fold_in(KEY, 24), (K, DIM))
    pol = ap.wrap(baselines.uniform_policy(mp.init_pool(a_emb)),
                  ap.AutopilotConfig(every=1, promote_wins=50.0,
                                     max_cand_duels=4.0))
    state = pol.init(KEY)
    state = state._replace(ctrl=state.ctrl._replace(
        candidate=jnp.zeros((K,), bool).at[2].set(True),
        cand_wins=jnp.zeros((K,)).at[2].set(1.0),
        cand_duels=jnp.zeros((K,)).at[2].set(9.0)))
    x = jax.random.normal(KEY, (16, DIM))
    state, a1, a2 = pol.act(KEY, state, x)
    assert not bool(mp.get_pool(state).active[2])
    assert not bool(state.ctrl.candidate[2])


def test_all_candidate_pool_serves_candidates_on_every_row():
    """Regression: when every surviving arm is a candidate (all full
    members retired mid-A/B), the quota gate degrades to full eligibility
    — ungated rows must route to a live candidate, never to an inactive
    slot via an all--inf argmax."""
    a_emb = jax.random.normal(jax.random.fold_in(KEY, 26), (K, DIM))
    pool = mp.init_pool(a_emb)
    for k in range(K):
        if k != 2:
            pool = mp.retire_arm(pool, k)        # only arm 2 survives...
    pol = ap.wrap(baselines.uniform_policy(pool),
                  ap.AutopilotConfig(every=1000, quota=0.0))
    state = pol.init(KEY)
    state = state._replace(ctrl=state.ctrl._replace(   # ...as a candidate
        candidate=jnp.zeros((K,), bool).at[2].set(True)))
    x = jax.random.normal(KEY, (32, DIM))
    for r in range(3):
        state, a1, a2 = pol.act(jax.random.fold_in(KEY, 60 + r), state, x)
        assert (np.asarray(a1) == 2).all() and (np.asarray(a2) == 2).all()


def test_permissive_tau_cannot_self_retire():
    """Regression: the dominance diagonal (P[j,j] = 0.5) is excluded
    structurally, so tau <= 0.5 never lets an arm retire itself — a
    single-survivor pool stays alive under any threshold."""
    pool = _pool(jnp.ones((K,)))
    for k in range(1, K):
        pool = mp.retire_arm(pool, k)
    post = jax.random.normal(jax.random.fold_in(KEY, 27), (4, DIM))
    ctrl = ap.init_controller(pool.active)
    cfg = ap.AutopilotConfig(tau=0.3, window=1)
    for _ in range(3):
        ctrl, dec = ap.step(ctrl, post, pool, cfg, use_kernel=False)
        assert not dec.dominated.any() and not dec.retire.any()
        pool = ap.apply_decisions(pool, dec)
    assert bool(pool.active[0])


def test_seed_replay_does_not_count_toward_candidate_tallies():
    """Regression: offline warm-start replay (synthetic BTL duels, which
    may pair against an incumbent mid-A/B) shapes the posterior only —
    candidate win/duel tallies must not move."""
    embs = np.random.RandomState(9).randn(K, DIM).astype(np.float32)
    svc = _ap_service(_entries(embs, [0.1] * K), K + 1)
    x = jax.random.normal(KEY, (BATCH, DIM))
    _, _, t = svc.route_batch(x)
    svc.feedback_batch(t, jnp.ones((BATCH,)))
    svc.add_model(_entries(np.random.RandomState(10).randn(1, DIM),
                           names=["late"])[0])
    _, _, t = svc.route_batch(x)           # candidacy registers
    svc.feedback_batch(t, jnp.ones((BATCH,)))
    st0 = svc.autopilot_status()
    cand_slot = int(np.flatnonzero(st0["candidate"])[0])
    t_before = int(svc.state.inner.inner.t)
    # replay duels deliberately involving the live candidate on both sides
    n = 8
    svc.seed_replay(np.random.RandomState(11).randn(n, DIM),
                    np.full((n,), cand_slot, np.int32),
                    np.zeros((n,), np.int32), np.ones((n,), np.float32))
    st1 = svc.autopilot_status()
    np.testing.assert_array_equal(st1["cand_wins"], st0["cand_wins"])
    np.testing.assert_array_equal(st1["cand_duels"], st0["cand_duels"])
    np.testing.assert_array_equal(st1["candidate"], st0["candidate"])
    assert int(svc.state.inner.inner.t) == t_before + n   # posterior moved


def test_wrapper_counts_candidate_duels_from_feedback():
    a_emb = jax.random.normal(jax.random.fold_in(KEY, 25), (K, DIM))
    pol = ap.wrap(baselines.uniform_policy(mp.init_pool(a_emb)),
                  ap.AutopilotConfig(every=1000))
    state = pol.init(KEY)
    state = state._replace(ctrl=state.ctrl._replace(
        candidate=jnp.zeros((K,), bool).at[1].set(True)))
    x = jax.random.normal(KEY, (4, DIM))
    a1 = jnp.asarray([1, 0, 1, 2], jnp.int32)
    a2 = jnp.asarray([0, 1, 3, 3], jnp.int32)
    y = jnp.asarray([1.0, 1.0, -1.0, 1.0])
    state = pol.update(state, x, a1, a2, y)
    # arm 1 duelled rows 0,1,2: wins row 0 (a1, y>0), loses row 1 (a2,
    # y>0) and row 2 (a1, y<0); row 3 does not involve it
    assert float(state.ctrl.cand_duels[1]) == 3.0
    assert float(state.ctrl.cand_wins[1]) == 1.0
    assert float(state.ctrl.cand_duels[3]) == 0.0   # non-candidates untracked


def test_cost_governor_holds_budget_in_env_loop():
    """Make the expensive arm the *best* arm, so an unconstrained router
    gravitates to it; under a budget the governor's lambda must tilt
    routing until the realized duel cost sits at (or under) budget.

    The best arm's edge in these random environments is small, so one
    environment's late-window cost is a noisy draw either side of the
    budget: each property is asserted on the mean over 16 seeded
    environments (one vmapped program per arm of the comparison), over a
    horizon long enough for the posterior to find the best arm."""
    from repro.core import ccft
    horizon, seeds = 256, 16
    budget = 0.4

    def late_cost(key, cfg):
        k_a, k_th, k_x = jax.random.split(jax.random.fold_in(key, 31), 3)
        a_emb = jax.random.normal(k_a, (K, DIM))
        theta_star = jax.random.normal(k_th, (DIM,))
        x = jax.random.normal(k_x, (horizon, DIM))
        utils = jax.vmap(
            lambda xi: ccft.scores_all(xi, a_emb, theta_star))(x)
        utils = (utils - utils.min()) / (utils.max() - utils.min())
        best = jnp.argmax(utils.mean(axis=0))
        costs = jnp.where(jnp.arange(K) == best, 2.0, 0.1)
        e = env_lib.EnvData(x=x, utils=utils)
        pol = ap.wrap(policy_lib.fgts_policy(
            mp.init_pool(a_emb, costs),
            _cfg(eta=8.0, sgld_steps=6, sgld_minibatch=16)), cfg)
        _, state, aux = env_lib.run(
            key, e, pol, batch=BATCH,
            aux_fn=lambda s, i, j: jnp.mean(
                0.5 * (mp.get_pool(s).costs[i] + mp.get_pool(s).costs[j])))
        n = aux.shape[0]
        return jnp.mean(aux[3 * n // 4:]), state.ctrl.lam

    def sweep(cfg):
        keys = jax.random.split(KEY, seeds)
        cost, lam = jax.jit(jax.vmap(lambda k: late_cost(k, cfg)))(keys)
        return np.asarray(cost), np.asarray(lam)

    late_free, lam_free = sweep(ap.AutopilotConfig(every=2, tau=2.0))
    late_gov, lam_gov = sweep(ap.AutopilotConfig(every=2, tau=2.0,
                                                 budget=budget,
                                                 budget_lr=1.0))
    assert late_free.mean() > budget            # unconstrained: over budget
    assert late_gov.mean() <= budget * 1.1      # governed: held at budget
    assert lam_gov.mean() > 0.0
    assert (lam_free == 0.0).all()


# ---------------------------------------------------------------------------
# live service: checkpointing + zero-recompilation contracts
# ---------------------------------------------------------------------------

def _entries(embs, costs=None, names=None):
    from repro.serving import PoolEntry
    return [PoolEntry(name=names[i] if names else f"m{i}",
                      arch="granite-3-2b",
                      cost_per_1k_tokens=0.1 if costs is None else costs[i],
                      embedding=np.asarray(embs[i], np.float32))
            for i in range(len(embs))]


def _ap_service(entries, k_max, mesh=None, ap_cfg=None, seed=0):
    from repro.encoder import EncoderConfig, init_encoder
    from repro.serving import RouterService, RouterServiceConfig
    enc_cfg = EncoderConfig(d_model=DIM, n_layers=1, n_heads=2, d_ff=32,
                            max_len=8)
    return RouterService(
        entries, init_encoder(KEY, enc_cfg), enc_cfg,
        RouterServiceConfig(
            fgts=fgts.FGTSConfig(n_models=k_max, dim=DIM, horizon=512,
                                 sgld_steps=2, sgld_minibatch=4,
                                 n_chains=2),
            seed=seed, k_max=k_max, feedback_capacity=64,
            autopilot=ap_cfg if ap_cfg is not None
            else ap.AutopilotConfig(every=2, budget=0.2)), mesh=mesh)


def test_autopilot_requires_dynamic_pool():
    from repro.encoder import EncoderConfig, init_encoder
    from repro.serving import RouterService, RouterServiceConfig
    embs = np.random.RandomState(0).randn(K, DIM).astype(np.float32)
    enc_cfg = EncoderConfig(d_model=DIM, n_layers=1, n_heads=2, d_ff=32,
                            max_len=8)
    with pytest.raises(ValueError, match="k_max"):
        RouterService(
            _entries(embs), init_encoder(KEY, enc_cfg), enc_cfg,
            RouterServiceConfig(
                fgts=fgts.FGTSConfig(n_models=K, dim=DIM, horizon=64),
                autopilot=ap.AutopilotConfig()))


def test_wrap_requires_act_masked():
    a_emb = jax.random.normal(KEY, (K, DIM))
    static = policy_lib.fgts_policy(a_emb, _cfg())      # no pool
    with pytest.raises(ValueError, match="act_masked"):
        ap.wrap(static, ap.AutopilotConfig())


def test_service_checkpoint_roundtrips_controller_state(tmp_path):
    embs = np.random.RandomState(3).randn(K, DIM).astype(np.float32)
    costs = [0.1, 0.2, 0.3, 0.4, 0.5]
    svc = _ap_service(_entries(embs, costs), K + 1)
    x = jax.random.normal(KEY, (BATCH, DIM))
    for r in range(5):
        _, _, t = svc.route_batch(x)
        svc.feedback_batch(t, jnp.where(
            jax.random.uniform(jax.random.fold_in(KEY, r), (BATCH,)) < 0.5,
            1.0, -1.0))
    svc.add_model(_entries(np.random.RandomState(4).randn(1, DIM),
                           names=["late"])[0])
    _, _, t = svc.route_batch(x)       # arrival registers as a candidate
    svc.feedback_batch(t, jnp.ones((BATCH,)))
    st = svc.autopilot_status()
    assert st["candidate"].any()
    svc.save(str(tmp_path))

    svc2 = _ap_service(_entries(embs, costs), K + 1)
    svc2.restore(str(tmp_path))
    st2 = svc2.autopilot_status()
    assert st2["lambda"] == st["lambda"]
    assert st2["cost_ema"] == st["cost_ema"]
    np.testing.assert_array_equal(st2["candidate"], st["candidate"])
    np.testing.assert_array_equal(st2["cand_wins"], st["cand_wins"])
    np.testing.assert_array_equal(st2["dominated_ticks"],
                                  st["dominated_ticks"])
    # and the restored service routes identically
    a1a, a2a, _ = svc.route_batch(x)
    a1b, a2b, _ = svc2.route_batch(x)
    np.testing.assert_array_equal(np.asarray(a1a), np.asarray(a1b))
    np.testing.assert_array_equal(np.asarray(a2a), np.asarray(a2b))


def test_control_ticks_and_autopilot_flips_compile_nothing_new():
    embs = np.random.RandomState(5).randn(K, DIM).astype(np.float32)
    svc = _ap_service(_entries(embs, [0.1, 0.2, 0.3, 0.4, 2.0]), K + 2)
    x = jax.random.normal(KEY, (BATCH, DIM))
    extra = _entries(np.random.RandomState(6).randn(2, DIM),
                     names=["n0", "n1"])
    # warm-up: act/update across >= 2 control ticks + one add/retire cycle
    _, _, t = svc.route_batch(x)
    svc.feedback_batch(t, jnp.ones((BATCH,)))
    svc.add_model(extra[0])
    svc.retire_model(0)
    for _ in range(4):
        _, _, t = svc.route_batch(x)
        svc.feedback_batch(t, jnp.ones((BATCH,)))
    counts = svc.compiled_program_counts()
    # more control ticks, a fresh candidate arrival, dominance churn
    svc.add_model(extra[1])
    for r in range(8):
        _, _, t = svc.route_batch(x)
        svc.feedback_batch(t, jnp.where(
            jax.random.uniform(jax.random.fold_in(KEY, r), (BATCH,)) < 0.5,
            1.0, -1.0))
    assert svc.compiled_program_counts() == counts


@pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
def test_autopilot_zero_new_compilations_mesh():
    """Same contract on an 8-device (4, 2) mesh: the controller state is
    replicated policy state, the quota gate rides the GSPMD act, so
    control ticks stay one compiled program there too."""
    from repro.launch import mesh as mesh_lib
    mesh = mesh_lib.make_debug_mesh(4, 2)
    embs = np.random.RandomState(7).randn(K, DIM).astype(np.float32)
    svc = _ap_service(_entries(embs, [0.1, 0.2, 0.3, 0.4, 2.0]), K + 1,
                      mesh=mesh)
    x = jax.random.normal(KEY, (32, DIM))
    _, _, t = svc.route_batch(x)
    svc.feedback_batch(t, jnp.ones((32,)))
    svc.add_model(_entries(np.random.RandomState(8).randn(1, DIM),
                           names=["n0"])[0])
    for _ in range(4):
        _, _, t = svc.route_batch(x)
        svc.feedback_batch(t, jnp.ones((32,)))
    counts = svc.compiled_program_counts()
    for r in range(6):
        a1, a2, t = svc.route_batch(x)
        svc.feedback_batch(t, jnp.where(
            jax.random.uniform(jax.random.fold_in(KEY, r), (32,)) < 0.5,
            1.0, -1.0))
    assert svc.compiled_program_counts() == counts
    act = svc.active_mask()
    assert act[np.asarray(a1)].all() and act[np.asarray(a2)].all()
