"""Batched routing service — the production wrapper around a RoutingPolicy.

A deployment keeps one ``RouterService`` per model pool. Requests arrive in
batches; the service embeds them (encoder), then drives a batched
``RoutingPolicy``: one jitted ``act`` per batch (for FGTS.CDB that is one
amortized multi-chain SGLD refresh + the dueling_score kernel's argmax
epilogue) and one jitted ``update`` per feedback batch (a single scatter
into the replay ring — no Python per-item loop).

Act and update run at independent cadences: ``route_batch`` issues every
duel into a fixed-capacity ``PendingDuels`` ring (one scatter) and returns
one int32 ticket per query; feedback arrives whenever users vote —
``feedback_batch(tickets, y)`` resolves the tickets (one gather + one
scatter to clear), drops stale/expired ones, and folds the rest into the
policy. The pending buffer checkpoints alongside the posterior, so a
restart never strands in-flight duels.

The pool registry carries per-model cost metadata so selection can apply a
cost-aware utility tilt at serve time (the paper's perf-cost trade-off
knob). Any policy that speaks the protocol can serve: pass a
``policy_factory`` in the config, or leave it None for the paper's
FGTS.CDB default.

Passing ``mesh=`` makes the live path mesh-parallel end to end: ``act``
runs under ``shard_map`` with the query batch partitioned over the
("pod","data") axes and the policy state replicated (selection takes the
XLA scoring path — a Pallas call cannot be partitioned here); the pending
ring and the replay update run as batch-sharded jitted programs with
explicit ``NamedSharding``s (``sharding/routing_rules.py``), so tickets and
votes never gather to one device.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import autopilot as ap
from repro.core import fgts
from repro.core import model_pool as mp
from repro.core.policy import (RoutingPolicy, fgts_policy, staleness_weight,
                               with_staleness)
from repro.data.pool import PoolEntry
from repro.encoder.model import EncoderConfig, encode
from repro.refresh import duel_log as dl
from repro.refresh.trainer import RefreshConfig
from repro.sharding import routing_rules as rr
from . import feedback_queue as fq
from . import stream
from . import telemetry

# Donated argnums of the streaming AOT bucket programs (``buckets=...``):
# buffer donation on the pending ring, the policy/posterior state, the tick
# scalar and the traffic accumulators is what removes the per-dispatch
# copies of the (C, d) ring and the replay buffers. repro-lint's
# trace-hazard pass mirrors this table (``DONATED_ARGNUMS``) and flags both
# reads-after-donation and drift between the wiring here and the lint's
# copy — changing a signature below means updating the lint table in the
# same PR.
STREAM_DONATION = {
    "_s_route": (1, 2, 6, 8),       # state, ring, tick, duel-cost acc
    "_s_route_pref": (1, 2, 6, 8),  # state, ring, tick, duel-cost acc
    "_s_feedback": (0, 1, 5, 6),    # state, ring, tick, folded-count acc
    # refresh-enabled feedback twin: same donations plus the duel-log ring
    "_s_feedback_log": (0, 1, 5, 6, 7),
    "_s_resolve": (0, 4),           # ring, tick
}


def _tick32(tick: int) -> jax.Array:
    """The service clock as a wrapping int32 device scalar.

    The host-side ``tick`` is an unbounded Python int; a plain
    ``jnp.asarray(tick, jnp.int32)`` raises OverflowError at 2^31 instead
    of wrapping like the on-device ticket/age arithmetic does. Reduce
    modulo 2^32 into the signed range first — all downstream comparisons
    (``feedback_queue.resolve``/``expire``) are wraparound-safe.
    """
    return jnp.asarray(((tick + 2 ** 31) % 2 ** 32) - 2 ** 31, jnp.int32)


@dataclasses.dataclass
class RouterServiceConfig:
    fgts: fgts.FGTSConfig
    cost_tilt: float = 0.0         # lambda applied at serve time
    seed: int = 0
    # Dynamic-pool capacity K_max: set to enable hot add_model /
    # retire_model / swap_model (the policy then carries a ModelPool in its
    # state and cfg.fgts.n_models must equal k_max — buffers are sized for
    # capacity). None = static pool, frozen at construction.
    k_max: Optional[int] = None
    # (a_emb, costs, cfg) -> RoutingPolicy; None = FGTS.CDB with cost tilt.
    policy_factory: Optional[Callable] = None
    # Pallas selection kernel vs XLA reference scoring. None = auto: kernel
    # on a single device, XLA path under a mesh (a Pallas call cannot be
    # partitioned over the batch axes). Factories receive the resolved bool.
    use_kernel: Optional[bool] = None
    # Mesh mode act mechanism. shard_map hands each device its local batch
    # shard with the key replicated — zero collectives, but a policy whose
    # act draws *per-row* randomness (uniform, eps-greedy exploration)
    # would sample identically on every shard. None = auto: shard_map for
    # the built-in FGTS default (its act randomness is batch-independent —
    # the posterior refresh — so every shard recomputes it identically),
    # GSPMD in_shardings traced under partitionable threefry for
    # factory-built policies (per-row draws decorrelated across shards and
    # invariant to the mesh size, though on a different stream than the
    # single-device default threefry).
    act_shard_map: Optional[bool] = None
    # -- async feedback -----------------------------------------------------
    feedback_capacity: int = 1024  # max in-flight duels (ring: oldest expire)
    feedback_expiry: Optional[int] = None   # max age in ticks; None = never
    stale_half_life: Optional[float] = None  # age-discount stale votes
    # -- streaming serving --------------------------------------------------
    # Padding-bucket ladder (sorted powers of two). Setting this flips the
    # service into event-time streaming mode: route/feedback run through
    # fused AOT programs compiled per bucket at construction (buffer
    # donation on the ring/state/tick — see STREAM_DONATION), arbitrary
    # formed-batch sizes pad to the next bucket with masked rows, and the
    # pending ring switches to shard-local ticket addressing under a mesh.
    # None = the legacy tick-batch surface (lazy jit, one batch shape).
    buckets: Optional[tuple] = None
    # -- online representation refresh --------------------------------------
    # Standing CCFT refresh loop (requires k_max: the refreshed table swaps
    # through the policy's ModelPool). Setting this makes the service (1)
    # record act-time selection propensities and query categories with every
    # issued duel — computed inside the jitted route programs, riding the
    # pending ring, no new syncs — and (2) fold resolved feedback into an
    # exportable ``refresh.DuelLog`` ring inside the jitted feedback
    # programs. ``export_log()`` hands the logged duels to the offline
    # trainer (``refresh.refresh_table``) and ``apply_table`` swaps the
    # refreshed (K_max, d) table in retrace-free. None = no logging, every
    # program byte-identical to a refresh-less service.
    refresh: Optional[RefreshConfig] = None
    # -- pool autopilot -----------------------------------------------------
    # Closed-loop population management (requires k_max): the policy is
    # wrapped with repro.autopilot — posterior-dominance auto-retirement,
    # arrivals enter as quota-capped A/B candidates, and a cost-governor
    # lambda holds the realized duel cost at the configured budget. The
    # controller runs inside the jitted act (control ticks compile nothing
    # new); its state replicates with the policy state under a mesh.
    autopilot: Optional[ap.AutopilotConfig] = None

    def __post_init__(self):
        hl = self.stale_half_life
        if hl is not None and hl != hl:      # NaN
            raise ValueError(
                "stale_half_life=NaN would silently poison every delayed "
                "update — use None (no staleness wrap), a positive "
                "half-life, or <= 0 / inf for an explicit no-discount")
        if self.feedback_capacity < 1:
            raise ValueError(
                f"feedback_capacity={self.feedback_capacity} — the pending "
                f"ring needs at least one slot")
        if self.feedback_expiry is not None and self.feedback_expiry < 0:
            raise ValueError(
                f"feedback_expiry={self.feedback_expiry} must be >= 0 "
                f"ticks (None disables age expiry)")
        if self.refresh is not None and self.k_max is None:
            raise ValueError(
                "RouterServiceConfig(refresh=...) needs a dynamic pool "
                "(k_max=...): the refreshed table swaps through the "
                "policy's ModelPool (apply_table / model_pool.set_table)")


class RouterService:
    """Online routing service state (host-side orchestration, jitted math)."""

    def __init__(self, pool: list[PoolEntry], enc_params, enc_cfg: EncoderConfig,
                 cfg: RouterServiceConfig, *, mesh=None):
        self.dynamic = cfg.k_max is not None
        if self.dynamic:
            if len(pool) > cfg.k_max:
                raise ValueError(f"{len(pool)} pool entries exceed "
                                 f"k_max={cfg.k_max}")
            if cfg.fgts.n_models != cfg.k_max:
                raise ValueError(
                    f"dynamic pools size every arm buffer for capacity: "
                    f"cfg.fgts.n_models={cfg.fgts.n_models} must equal "
                    f"k_max={cfg.k_max}")
        else:
            assert len(pool) == cfg.fgts.n_models
        self.pool = list(pool) + [None] * (
            (cfg.k_max - len(pool)) if self.dynamic else 0)
        # slots that have ever hosted an arm: add_model prefers virgin
        # slots so an unrelated model never inherits a retired arm's
        # replay-ring history / per-slot stats
        self._ever_used = [p is not None for p in self.pool]
        self.enc_params = enc_params
        self.enc_cfg = enc_cfg
        self.mesh = mesh
        use_kernel = cfg.use_kernel if cfg.use_kernel is not None \
            else mesh is None
        cfg = dataclasses.replace(cfg, use_kernel=use_kernel)
        if mesh is not None and cfg.fgts.sgld_backend == "auto":
            # like use_kernel: a compiled Pallas call cannot be partitioned
            # over the mesh, so auto resolves the SGLD gradient to the fused
            # kernel's pure-XLA lowering (bit-identical under interpret
            # mode) for the GSPMD programs
            cfg = dataclasses.replace(
                cfg, fgts=dataclasses.replace(cfg.fgts, sgld_backend="xla"))
        self.cfg = cfg
        self.a_emb = jnp.asarray(np.stack([p.embedding for p in pool]))
        entry_costs = [p.cost_per_1k_tokens for p in pool]
        if self.dynamic:
            pool0 = mp.init_pool(self.a_emb, jnp.asarray(entry_costs),
                                 k_max=cfg.k_max)
            # (K_max,) padded mirror — copied: the pool's own buffer lives
            # inside the (donated) policy state, and the mirror must survive
            # the streaming programs consuming their state operand
            self.costs = jnp.array(pool0.costs)
            arms = pool0
        else:
            self.costs = jnp.asarray(entry_costs)
            arms = self.a_emb
        if cfg.policy_factory is not None:
            self.policy: RoutingPolicy = cfg.policy_factory(
                arms, self.costs, cfg)
        else:
            self.policy = fgts_policy(arms, cfg.fgts, costs=self.costs,
                                      cost_tilt=cfg.cost_tilt,
                                      use_kernel=use_kernel)
        if cfg.autopilot is not None:
            if not self.dynamic:
                raise ValueError(
                    "autopilot manages pool membership: construct the "
                    "service with RouterServiceConfig(k_max=...) so the "
                    "policy carries a ModelPool it can retire into")
            self.policy = ap.wrap(self.policy, cfg.autopilot,
                                  use_kernel=use_kernel)
        self._staleness_wrapped = (cfg.stale_half_life is not None
                                   and self.policy.update_delayed is None)
        if self._staleness_wrapped:
            self.policy = with_staleness(self.policy, cfg.stale_half_life)
        self._key = jax.random.PRNGKey(cfg.seed)
        self.state = self.policy.init(self._next_key())
        if self.dynamic and not mp.is_pooled(self.state):
            raise ValueError(
                f"policy '{self.policy.name}' ignored the ModelPool: a "
                f"dynamic service needs a pool-backed policy (state must "
                f"be a PooledState) — build it from the ModelPool first "
                f"argument the factory receives")
        # the ring's wrapping slot arithmetic needs a power-of-two capacity
        # (feedback_queue.init_pending raises on anything else): round the
        # requested capacity up here so configs stay free-form
        capacity = fq.next_pow2(cfg.feedback_capacity) if mesh is None \
            else rr.round_capacity(cfg.feedback_capacity, mesh)
        self.streaming = cfg.buckets is not None
        if self.streaming:
            shards = 1 if mesh is None else rr.n_batch_shards(mesh)
            self.buckets = stream.validate_buckets(cfg.buckets, shards)
            self.pending = fq.init_pending(capacity, self.a_emb.shape[1],
                                           shards=shards)
        else:
            self.pending = fq.init_pending(capacity, self.a_emb.shape[1])
        self.tick = 0                  # route_batch calls (the service clock)
        self.n_routed = 0
        # host totals of the router.* spans (telemetry): compiles per layer
        self._compile_counts = telemetry.CompileCounts()
        # the query encoder off the bucket ladder (any (B, L), and every
        # call of a service without buckets): compiled once per shape
        self._embed_jit = jax.jit(functools.partial(encode, cfg=enc_cfg))
        self._embed_calls = {"aot": 0, "jit": 0}
        # online representation refresh: the exportable duel-log ring rides
        # next to the policy state (replicated under a mesh) and is folded
        # inside the jitted feedback programs; None when refresh is off —
        # every program then stays byte-identical to a refresh-less build
        self.refresh_on = cfg.refresh is not None
        if self.refresh_on:
            self.duel_log = dl.init_log(fq.next_pow2(cfg.refresh.capacity),
                                        self.a_emb.shape[1])
            self._count_at_swap = 0    # log.count at the last apply_table
            self._table_swaps = 0
        else:
            self.duel_log = None
        # on-device stats accumulators: the hot path only *adds* to these
        # (lazy, no host sync); service_stats() materializes them in one
        # deliberate device_get. Process-local by design — not part of the
        # checkpoint payload, so they reset to zero on restore().
        self._n_folded = jnp.zeros((), jnp.int32)
        self._duel_cost = jnp.zeros((), jnp.float32)
        self._build_programs()
        if self.streaming:
            self._build_stream_programs()

    def _build_programs(self):
        """Jit (and, under a mesh, shard) the service's four programs: act,
        enqueue, resolve, update. Single-device mode is the plain jit path;
        mesh mode partitions the batch and the pending ring per
        ``sharding/routing_rules`` and replicates the policy state."""
        cfg, mesh = self.cfg, self.mesh
        resolve = functools.partial(fq.resolve, max_age=cfg.feedback_expiry)

        # dynamic-pool membership programs: a hot add/retire/swap is a pure
        # shape-stable state update (one row scatter + mask flip) — slot is
        # a *traced* operand, so one compiled program serves every slot and
        # membership changes never retrace act/update
        def pool_set(state, emb, cost, slot):
            return mp.set_pool(state, mp.set_arm(mp.get_pool(state), slot,
                                                 emb, cost))

        def pool_retire(state, slot):
            return mp.set_pool(state, mp.retire_arm(mp.get_pool(state),
                                                    slot))

        # refresh-loop table swap: the whole (K_max, d) embedding table is a
        # *traced* operand (the swap_model idiom, one table-sized scatter +
        # generation bump), so one compiled program serves every refreshed
        # table — a refresh tick never retraces act/update
        def table_swap(state, table):
            return mp.set_pool(state, mp.set_table(mp.get_pool(state),
                                                   table))

        half_life = cfg.stale_half_life if self._staleness_wrapped else None
        masked = self.policy.update_masked
        # The masked path subsumes update_delayed only when the staleness
        # semantics are the generic label shrink (with_staleness); a policy
        # with its own delayed path keeps the compaction route.
        if masked is not None and (self.policy.update_delayed is None
                                   or self._staleness_wrapped):
            def masked_update(state, x, a1, a2, y, age, ok):
                if half_life is not None:
                    y = y * staleness_weight(age, half_life)
                return masked(state, x, a1, a2, y, ok)
        else:
            masked_update = None

        # preference-conditioned twins: selection with a (B,) per-request
        # pref (the policy broadcasts it against live arm costs), feedback
        # with the pref each duel was served under (same staleness shrink)
        pol_act_pref = self.policy.act_pref
        if pol_act_pref is not None:
            def act_pref(key, state, x, pref, _ap=pol_act_pref):
                return _ap(key, state, x, None, pref)
        else:
            act_pref = None
        pol_upd_pref = self.policy.update_pref
        if pol_upd_pref is not None and (self.policy.update_delayed is None
                                         or self._staleness_wrapped):
            def masked_update_pref(state, x, a1, a2, y, age, ok, pref):
                if half_life is not None:
                    y = y * staleness_weight(age, half_life)
                return pol_upd_pref(state, x, a1, a2, y, pref, ok)
        else:
            masked_update_pref = None

        # refresh instrumentation: when the log is on, the act programs
        # additionally return the act-time pair propensity (the policy's
        # ``propensity`` readout; constant 1.0 when it exposes none, so IPW
        # degrades to the naive estimator) and the feedback programs fold
        # resolved duels into the exportable duel-log ring — all inside the
        # same jitted dispatches, zero extra syncs on the hot path
        record = self.refresh_on
        prop_fn = self.policy.propensity
        if prop_fn is None:
            def prop_fn(state, x, a1, a2):
                return jnp.ones(a1.shape, jnp.float32)
        act_core, act_pref_core, fold_log = self.policy.act, act_pref, None
        if record:
            def act_core(key, state, x, _act=self.policy.act):
                state, a1, a2 = _act(key, state, x)
                return state, a1, a2, prop_fn(state, x, a1, a2)
            if act_pref is not None:
                def act_pref_core(key, state, x, pref, _ap=act_pref):
                    state, a1, a2 = _ap(key, state, x, pref)
                    return state, a1, a2, prop_fn(state, x, a1, a2)

            def fold_log(log, res, now):
                return dl.fold(log, res.x, res.a1, res.a2, res.y, res.pref,
                               res.prop, res.cat, now - res.age, res.ok)

        # raw (un-jitted) traceables, reused by the streaming AOT builder so
        # both surfaces fold feedback through literally the same closures
        self._traceables = {"masked_update": masked_update,
                            "masked_update_pref": masked_update_pref,
                            "act_core": act_core,
                            "act_pref_core": act_pref_core,
                            "fold_log": fold_log, "act_mesh": None,
                            "act_pref_mesh": None}

        def seed_fn(fn):
            """Seeding program for offline->online replay. Under an
            autopilot the candidate flags are blanked around the fold:
            synthetic offline duels (e.g. ``warm_start_duels`` pairing a
            newcomer against incumbents mid-A/B) must shape the posterior
            only — never a live candidate's win/duel tallies."""
            if cfg.autopilot is None:
                return fn

            def seeded(state, *args):
                ctrl = state.ctrl
                blank = state._replace(ctrl=ctrl._replace(
                    candidate=jnp.zeros_like(ctrl.candidate)))
                out = fn(blank, *args)
                return out._replace(ctrl=out.ctrl._replace(
                    candidate=ctrl.candidate))
            return seeded

        if mesh is None:
            self._n_shards = 1
            self._act = jax.jit(act_core)
            self._act_pref = (jax.jit(act_pref_core)
                              if act_pref_core is not None else None)
            self._fold_log = jax.jit(fold_log) if record else None
            self._update = jax.jit(self.policy.update)
            self._update_delayed = (jax.jit(self.policy.update_delayed)
                                    if self.policy.update_delayed is not None
                                    else None)
            self._update_masked = (jax.jit(masked_update)
                                   if masked_update is not None else None)
            self._update_pref = (jax.jit(masked_update_pref)
                                 if masked_update_pref is not None else None)
            self._update_compact = self._update
            self._update_delayed_compact = self._update_delayed
            self._enqueue = jax.jit(fq.enqueue)
            self._resolve = jax.jit(resolve)
            if self.dynamic:
                self._pool_set = jax.jit(pool_set)
                self._pool_retire = jax.jit(pool_retire)
                self._table_swap = jax.jit(table_swap)
                # offline->online seeding folds replay duels through the
                # policy's shape-stable masked update when it has one
                if cfg.autopilot is not None:
                    self._update_seed = jax.jit(seed_fn(
                        masked_update if masked_update is not None
                        else self.policy.update))
                else:
                    self._update_seed = (
                        self._update_masked
                        if self._update_masked is not None
                        else self._update)
            return

        self._n_shards = rr.n_batch_shards(mesh)
        bx = rr.batch_axes(mesh)
        sh = functools.partial(NamedSharding, mesh)
        rep, row, qry = sh(P()), sh(rr.per_query_spec(mesh)), \
            sh(rr.query_batch_spec(mesh))
        # streaming mode reshapes the ring's ticket counter to (S,) per
        # shard; its spec tree (and the live buffer's placement) follow
        pend = rr.to_shardings(
            mesh, rr.stream_pending_specs(mesh) if self.streaming
            else rr.pending_specs(mesh))
        res_sh = rr.to_shardings(mesh, rr.resolved_specs(mesh))
        self._x_sh, self._row_sh, self._rep_sh = qry, row, rep

        # act: batch partitioned, state + key replicated. shard_map hands
        # each device its local shard — every device recomputes the
        # identical posterior refresh (same key, same replicated state) and
        # scores only its rows; check_vma is off because the varying-axes
        # check cannot prove the refresh is replicated through random ops.
        # Factory-built policies default to GSPMD in_shardings traced under
        # partitionable threefry instead: per-row randomness then comes out
        # decorrelated across shards and invariant to the mesh size (the
        # default threefry lowering is NOT sharding-invariant).
        # the autopilot's quota gate is a *per-row* uniform draw, so its act
        # takes the GSPMD path by default like factory policies (shard_map
        # with a replicated key would repeat the same gate on every shard)
        use_sm = cfg.act_shard_map if cfg.act_shard_map is not None \
            else (cfg.policy_factory is None and cfg.autopilot is None)
        # the propensity row (refresh logging) shards like every other
        # per-query vector — computed per shard inside the same program
        act_extra = (P(bx),) if record else ()
        out_extra = (row,) if record else ()
        if use_sm:
            act = jax.shard_map(act_core, mesh=mesh,
                                in_specs=(P(), P(),
                                          rr.query_batch_spec(mesh)),
                                out_specs=(P(), P(bx), P(bx)) + act_extra,
                                check_vma=False)
        else:
            def act(key, state, x, _act=act_core):
                with jax.threefry_partitionable(True):
                    return _act(key, state, x)
        self._traceables["act_mesh"] = act
        self._act = jax.jit(act, in_shardings=(rep, rep, qry),
                            out_shardings=(rep, row, row) + out_extra)
        # the pref operand shards like every per-query vector: each device
        # tilts only the rows it scores (rr.pref_spec)
        self._act_pref = None
        if act_pref_core is not None:
            if use_sm:
                act_p = jax.shard_map(
                    act_pref_core, mesh=mesh,
                    in_specs=(P(), P(), rr.query_batch_spec(mesh),
                              rr.pref_spec(mesh)),
                    out_specs=(P(), P(bx), P(bx)) + act_extra,
                    check_vma=False)
            else:
                def act_p(key, state, x, pref, _ap=act_pref_core):
                    with jax.threefry_partitionable(True):
                        return _ap(key, state, x, pref)
            self._traceables["act_pref_mesh"] = act_p
            self._act_pref = jax.jit(act_p,
                                     in_shardings=(rep, rep, qry, row),
                                     out_shardings=(rep, row, row)
                                     + out_extra)
        self._update = jax.jit(
            self.policy.update,
            in_shardings=(rep, qry, row, row, row),
            out_shardings=rep)
        self._update_delayed = (jax.jit(
            self.policy.update_delayed,
            in_shardings=(rep, qry, row, row, row, row),
            out_shardings=rep)
            if self.policy.update_delayed is not None else None)
        self._update_masked = (jax.jit(
            masked_update,
            in_shardings=(rep, qry, row, row, row, row, row),
            out_shardings=rep)
            if masked_update is not None else None)
        self._update_pref = (jax.jit(
            masked_update_pref,
            in_shardings=(rep, qry, row, row, row, row, row, row),
            out_shardings=rep)
            if masked_update_pref is not None else None)
        # compaction fallback (policies without update_masked): the
        # survivor count is arbitrary, so the compacted batch is replicated
        # — no divisibility constraint — and only the state stays meshed
        self._update_compact = jax.jit(
            self.policy.update, in_shardings=(rep, rep, rep, rep, rep),
            out_shardings=rep)
        self._update_delayed_compact = (jax.jit(
            self.policy.update_delayed,
            in_shardings=(rep, rep, rep, rep, rep, rep),
            out_shardings=rep)
            if self.policy.update_delayed is not None else None)
        enq_sh = (pend, qry, row, row, rep, row)
        if record:
            enq_sh = enq_sh + (row, row)    # prop, cat operands
        self._enqueue = jax.jit(
            fq.enqueue, in_shardings=enq_sh, out_shardings=(pend, row))
        self._resolve = jax.jit(
            resolve, in_shardings=(pend, row, row, rep),
            out_shardings=(pend, res_sh))
        self._fold_log = None
        if record:
            log_sh = rr.to_shardings(mesh, rr.duel_log_specs(mesh))
            self._fold_log = jax.jit(fold_log,
                                     in_shardings=(log_sh, res_sh, rep),
                                     out_shardings=log_sh)
        if self.dynamic:
            self._pool_set = jax.jit(pool_set,
                                     in_shardings=(rep, rep, rep, rep),
                                     out_shardings=rep)
            self._pool_retire = jax.jit(pool_retire,
                                        in_shardings=(rep, rep),
                                        out_shardings=rep)
            self._table_swap = jax.jit(table_swap,
                                       in_shardings=(rep, rep),
                                       out_shardings=rep)
            # replay batches have arbitrary lengths: fold them replicated
            # (the state stays meshed), masked path first
            if masked_update is not None:
                self._update_seed = jax.jit(
                    seed_fn(masked_update),
                    in_shardings=(rep,) * 7, out_shardings=rep)
            elif cfg.autopilot is not None:
                self._update_seed = jax.jit(
                    seed_fn(self.policy.update),
                    in_shardings=(rep,) * 5, out_shardings=rep)
            else:
                self._update_seed = self._update_compact
        # replicate / shard the live buffers onto the mesh
        self.state = jax.device_put(self.state, rep)
        self.pending = jax.device_put(self.pending, pend)
        self._n_folded = jax.device_put(self._n_folded, rep)
        self._duel_cost = jax.device_put(self._duel_cost, rep)
        if record:
            self.duel_log = jax.device_put(
                self.duel_log, rr.to_shardings(mesh,
                                               rr.duel_log_specs(mesh)))

    # -- streaming serving (cfg.buckets) -------------------------------------

    @staticmethod
    def _avals(tree):
        """Array pytree -> ShapeDtypeStruct pytree (AOT lowering operands)."""
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
            tree)

    def _aot(self, fn, *, avals, donate_argnums=(), shardings=None):
        """Ahead-of-time compile one bucket program. The trace happens here,
        at construction, against abstract operands — first-request latency
        pays zero compile time — and the compiled executable can never
        retrace: an off-ladder operand shape is a loud arity error, not a
        silent recompile. ``donate_argnums`` hands the hot buffers (ring,
        posterior state, tick, accumulators) to XLA for in-place reuse."""
        if shardings is None:
            jitted = jax.jit(fn, donate_argnums=donate_argnums)
        else:
            jitted = jax.jit(fn, in_shardings=shardings[0],
                             out_shardings=shardings[1],
                             donate_argnums=donate_argnums)
        return jitted.lower(*avals).compile()

    def _stream_avals(self, b: int) -> dict:
        f32, i32 = jnp.float32, jnp.int32
        d = self.a_emb.shape[1]
        s = jax.ShapeDtypeStruct
        av = {"key": self._avals(self._key),
              "state": self._avals(self.state),
              "q": self._avals(self.pending),
              "x": s((b, d), f32), "mask": s((b,), jnp.bool_),
              "pref": s((b,), f32), "now": s((), i32),
              "costs": self._avals(self.costs),
              "acc_f": s((), f32), "acc_i": s((), i32),
              "tickets": s((b,), i32), "y": s((b,), f32)}
        if self.refresh_on:
            av["cat"] = s((b,), i32)
            av["log"] = self._avals(self.duel_log)
        return av

    def _build_stream_programs(self):
        """AOT-compile the streaming surface: per padding bucket, one fused
        route program (selection + masked shard-local ring enqueue + cost
        accounting) and one fused feedback program (shard-local resolve +
        masked posterior fold), with the ring, the policy state, the device
        tick and the traffic accumulators donated (``STREAM_DONATION``) so
        every step updates them in place instead of copying the (C, d) ring
        and replay buffers.

        Masking contract: padded rows never enter the ring (ticket -1,
        nothing scattered) and never reach the posterior (``ok=False`` rows
        scatter out of bounds in the masked update), and selection runs
        under partitionable threefry, whose per-row draws depend only on
        (key, row) — so a batch padded to the next bucket is bit-identical
        to the unpadded batch (pinned in tests/test_streaming.py).

        Policies without a masked update cannot fold feedback shape-stably:
        they get a donated AOT resolve per bucket and fall back to the
        legacy host-compaction fold.
        """
        cfg, mesh, policy = self.cfg, self.mesh, self.policy
        n_shards = self._n_shards
        record = self.refresh_on
        tr = self._traceables
        masked_update = tr["masked_update"]
        masked_update_pref = tr["masked_update_pref"]

        # selection cores. Mesh mode reuses the exact closures the legacy
        # surface jits (shard_map for the FGTS default, partitionable GSPMD
        # otherwise); single-device act is re-wrapped under partitionable
        # threefry — the default threefry lowering folds the batch shape
        # into the stream and is NOT padding-stable. With refresh logging
        # the cores return a fourth output, the act-time pair propensity.
        if mesh is None:
            def s_act(key, state, x, _act=tr["act_core"]):
                with jax.threefry_partitionable(True):
                    return _act(key, state, x)
            s_act_pref = None
            if tr["act_pref_core"] is not None:
                def s_act_pref(key, state, x, pref,
                               _ap=tr["act_pref_core"]):
                    with jax.threefry_partitionable(True):
                        return _ap(key, state, x, pref)
        else:
            s_act, s_act_pref = tr["act_mesh"], tr["act_pref_mesh"]

        # ring cores: shard-local ticket addressing. Under a mesh each
        # device owns a (C/S,)-row ring slice plus its own (1,) sequence
        # counter, and tickets are strided by shard (ticket = seq*S +
        # shard) — enqueue and resolve never leave the device that routed
        # the row, so the feedback path lowers with zero collectives
        # (asserted against the compiled HLO in tests).
        if mesh is None:
            def enq(q, x, a1, a2, now, pref, mask, prop=None, cat=None):
                return fq.enqueue_stream(q, x, a1, a2, now, pref, mask,
                                         0, n_shards, prop=prop, cat=cat)

            def rsv(q, tickets, y, mask, now):
                return fq.resolve_stream(q, tickets, y, mask, now, 0,
                                         n_shards,
                                         max_age=cfg.feedback_expiry)
        else:
            sidx = rr.shard_index(mesh)
            pspec = rr.stream_pending_specs(mesh)
            rowp = rr.per_query_spec(mesh)
            qryp = rr.query_batch_spec(mesh)

            if record:
                def enq_local(q, x, a1, a2, now, pref, mask, prop, cat):
                    return fq.enqueue_stream(q, x, a1, a2, now, pref,
                                             mask, sidx(), n_shards,
                                             prop=prop, cat=cat)

                enq = jax.shard_map(enq_local, mesh=mesh,
                                    in_specs=(pspec, qryp, rowp, rowp, P(),
                                              rowp, rowp, rowp, rowp),
                                    out_specs=(pspec, rowp),
                                    check_vma=False)
            else:
                def enq_local(q, x, a1, a2, now, pref, mask):
                    return fq.enqueue_stream(q, x, a1, a2, now, pref,
                                             mask, sidx(), n_shards)

                enq = jax.shard_map(enq_local, mesh=mesh,
                                    in_specs=(pspec, qryp, rowp, rowp, P(),
                                              rowp, rowp),
                                    out_specs=(pspec, rowp),
                                    check_vma=False)

            def rsv_local(q, tickets, y, mask, now):
                return fq.resolve_stream(q, tickets, y, mask, now, sidx(),
                                         n_shards,
                                         max_age=cfg.feedback_expiry)

            rsv = jax.shard_map(rsv_local, mesh=mesh,
                                in_specs=(pspec, rowp, rowp, rowp, P()),
                                out_specs=(pspec, rr.resolved_specs(mesh)),
                                check_vma=False)

        # fused per-bucket programs. The tick advances ON DEVICE (now + 1)
        # and is threaded through every program as a donated passthrough,
        # so the hot path never ships the clock from the host; the host
        # ``self.tick`` mirror advances in lockstep for checkpoints/expiry
        # (both wrap int32-identically).
        # With refresh logging the route programs take one extra trailing
        # operand (the per-row category, -1 = unknown) and thread the
        # act-time propensity into the ring — donated argnums unchanged
        # (state/ring/tick/acc keep their positions).
        if record:
            def route_fused(key, state, q, x, mask, pref, now, costs, acc,
                            cat):
                state, a1, a2, prop = s_act(key, state, x)
                now = now + 1
                q, tickets = enq(q, x, a1, a2, now, pref, mask, prop, cat)
                live = jnp.where(mask, costs[a1] + costs[a2], 0.0)
                return state, q, now, a1, a2, tickets, acc + jnp.sum(live)

            route_pref_fused = None
            if s_act_pref is not None:
                def route_pref_fused(key, state, q, x, mask, pref, now,
                                     costs, acc, cat):
                    state, a1, a2, prop = s_act_pref(key, state, x, pref)
                    now = now + 1
                    q, tickets = enq(q, x, a1, a2, now, pref, mask, prop,
                                     cat)
                    live = jnp.where(mask, costs[a1] + costs[a2], 0.0)
                    return state, q, now, a1, a2, tickets, \
                        acc + jnp.sum(live)
        else:
            def route_fused(key, state, q, x, mask, pref, now, costs, acc):
                state, a1, a2 = s_act(key, state, x)
                now = now + 1
                q, tickets = enq(q, x, a1, a2, now, pref, mask)
                live = jnp.where(mask, costs[a1] + costs[a2], 0.0)
                return state, q, now, a1, a2, tickets, acc + jnp.sum(live)

            route_pref_fused = None
            if s_act_pref is not None:
                def route_pref_fused(key, state, q, x, mask, pref, now,
                                     costs, acc):
                    state, a1, a2 = s_act_pref(key, state, x, pref)
                    now = now + 1
                    q, tickets = enq(q, x, a1, a2, now, pref, mask)
                    live = jnp.where(mask, costs[a1] + costs[a2], 0.0)
                    return state, q, now, a1, a2, tickets, \
                        acc + jnp.sum(live)

        # Canonicalize the fold layout on the mesh: gather the resolved
        # batch to every device *before* the posterior update. The fold
        # pays an all-gather/all-reduce either way (row-sharded duels into
        # a replicated posterior); constraining it here pins the reduction
        # grouping to the canonical row order, so the folded posterior is
        # bitwise invariant to how much padding the bucket added (free
        # per-shard partial sums would regroup as padding shifts live rows
        # across devices). The resolve program itself stays collective-free
        # — the constraint lives in the feedback program only, after the
        # shard-local ring lookup.
        if mesh is None:
            def canon(res):
                return res
        else:
            rep_sh = self._rep_sh

            def canon(res):
                return jax.tree.map(
                    lambda a: jax.lax.with_sharding_constraint(a, rep_sh),
                    res)

        feedback_fused = feedback_log_fused = None
        if masked_update_pref is not None:
            # preference-conditioned fold (same precedence as
            # feedback_batch: the ring records the pref each duel was
            # served under, zeros when the caller passed none)
            def fb_fold(state, res):
                return masked_update_pref(state, res.x, res.a1, res.a2,
                                          res.y, res.age, res.ok, res.pref)
        elif masked_update is not None:
            def fb_fold(state, res):
                return masked_update(state, res.x, res.a1, res.a2, res.y,
                                     res.age, res.ok)
        else:
            fb_fold = None
        if fb_fold is not None and record:
            fold_log = tr["fold_log"]

            # refresh twin of the feedback program: the duel-log ring rides
            # as one extra donated operand (STREAM_DONATION appends it, so
            # the shared argnums keep their positions) and every surviving
            # row is folded into it after canonicalization — the log, like
            # the posterior, is bitwise invariant to bucket padding
            def feedback_log_fused(state, q, tickets, y, mask, now, acc,
                                   log):
                q, res = rsv(q, tickets, y, mask, now)
                res = canon(res)
                n_ok = jnp.sum(res.ok).astype(jnp.int32)
                log = fold_log(log, res, now)
                state = fb_fold(state, res)
                return state, q, now, acc + n_ok, log, n_ok
        elif fb_fold is not None:
            def feedback_fused(state, q, tickets, y, mask, now, acc):
                q, res = rsv(q, tickets, y, mask, now)
                res = canon(res)
                n_ok = jnp.sum(res.ok).astype(jnp.int32)
                state = fb_fold(state, res)
                return state, q, now, acc + n_ok, n_ok

        def resolve_fused(q, tickets, y, mask, now):
            q, res = rsv(q, tickets, y, mask, now)
            return q, now, res

        if mesh is None:
            r_sh = f_sh = fl_sh = v_sh = None
        else:
            rep, row, qry = self._rep_sh, self._row_sh, self._x_sh
            pend = rr.to_shardings(mesh, rr.stream_pending_specs(mesh))
            res_sh = rr.to_shardings(mesh, rr.resolved_specs(mesh))
            cat_in = (row,) if record else ()
            r_sh = ((rep, rep, pend, qry, row, row, rep, rep, rep)
                    + cat_in,
                    (rep, pend, rep, row, row, row, rep))
            f_sh = ((rep, pend, row, row, row, rep, rep),
                    (rep, pend, rep, rep, rep))
            fl_sh = None
            if record:
                log_sh = rr.to_shardings(mesh, rr.duel_log_specs(mesh))
                fl_sh = ((rep, pend, row, row, row, rep, rep, log_sh),
                         (rep, pend, rep, rep, log_sh, rep))
            v_sh = ((pend, row, row, row, rep), (pend, rep, res_sh))

        av = {b: self._stream_avals(b) for b in self.buckets}

        def r_avals(b):
            a = av[b]
            base = (a["key"], a["state"], a["q"], a["x"], a["mask"],
                    a["pref"], a["now"], a["costs"], a["acc_f"])
            return base + ((a["cat"],) if record else ())

        def f_avals(b):
            a = av[b]
            return (a["state"], a["q"], a["tickets"], a["y"], a["mask"],
                    a["now"], a["acc_i"])

        def fl_avals(b):
            return f_avals(b) + (av[b]["log"],)

        def v_avals(b):
            a = av[b]
            return (a["q"], a["tickets"], a["y"], a["mask"], a["now"])

        self._s_route = {
            b: self._aot(route_fused,
                         donate_argnums=STREAM_DONATION["_s_route"],
                         avals=r_avals(b), shardings=r_sh)
            for b in self.buckets}
        self._s_route_pref = None if route_pref_fused is None else {
            b: self._aot(route_pref_fused,
                         donate_argnums=STREAM_DONATION["_s_route_pref"],
                         avals=r_avals(b), shardings=r_sh)
            for b in self.buckets}
        self._s_feedback = None if feedback_fused is None else {
            b: self._aot(feedback_fused,
                         donate_argnums=STREAM_DONATION["_s_feedback"],
                         avals=f_avals(b), shardings=f_sh)
            for b in self.buckets}
        self._s_feedback_log = None if feedback_log_fused is None else {
            b: self._aot(feedback_log_fused,
                         donate_argnums=STREAM_DONATION["_s_feedback_log"],
                         avals=fl_avals(b), shardings=fl_sh)
            for b in self.buckets}
        self._s_resolve = {
            b: self._aot(resolve_fused,
                         donate_argnums=STREAM_DONATION["_s_resolve"],
                         avals=v_avals(b), shardings=v_sh)
            for b in self.buckets}
        # the query encoder per bucket at the configured length: embed
        # dispatches a compiled executable and makes no program per call.
        # The weights are operands (replicated under a mesh), never
        # constants baked into the executable; the rows come out
        # batch-sharded as route_fused takes its x.
        if mesh is not None:
            self.enc_params = jax.device_put(self.enc_params, self._rep_sh)
        e_sh = None if mesh is None else (
            (self._rep_sh, self._x_sh, self._x_sh), self._x_sh)
        seq = self.enc_cfg.max_len
        self._s_embed = {
            b: self._aot(functools.partial(encode, cfg=self.enc_cfg),
                         avals=(self._avals(self.enc_params),
                                jax.ShapeDtypeStruct((b, seq), jnp.int32),
                                jax.ShapeDtypeStruct((b, seq), jnp.float32)),
                         shardings=e_sh)
            for b in self.buckets}
        # per-(bucket, live-count) mask / zero-pref / unknown-category
        # caches: placed once, reused every call (never donated)
        self._masks, self._zero_prefs, self._neg_cats = {}, {}, {}
        self._tick_dev = _tick32(self.tick)
        if mesh is not None:
            self._tick_dev = jax.device_put(self._tick_dev, self._rep_sh)
        self._sync_stream_costs()

    def _sync_stream_costs(self):
        """Refresh the replicated cost-vector operand of the AOT route
        programs (the AOT call path validates placement, so the mirror must
        live on the mesh). Always a fresh copy: under a dynamic pool
        ``self.costs`` aliases ``pool.costs`` *inside* the donated policy
        state, and passing the same buffer as both a donated and a
        non-donated operand is an XLA execute error."""
        if not self.streaming:
            return
        self._costs_dev = (jnp.array(self.costs) if self.mesh is None
                           else jax.device_put(
                               jnp.array(self.costs), self._rep_sh))

    def _stream_mask(self, b: int, n: int) -> jax.Array:
        m = self._masks.get((b, n))
        if m is None:
            m = self._shard_batch(jnp.arange(b, dtype=jnp.int32)
                                  < jnp.int32(n), "route_stream")
            self._masks[(b, n)] = m
        return m

    def _pad_batch(self, arr: jax.Array, b: int, what: str) -> jax.Array:
        """End-pad a formed batch to its bucket and place it on the mesh.

        Padding sits at the *end* deliberately: live row i keeps global
        position i for every bucket, so per-row randomness under
        partitionable threefry (prefix-stable in the batch axis) draws the
        same bits whatever the padding — the bucket-identity contract for
        pairs and posterior. The flip side is that under a mesh the
        padding changes which device owns a live row, so *tickets* are
        bucket-dependent there (opaque handles either way; the posterior
        fold is made layout-canonical inside the feedback program
        instead)."""
        return self._shard_batch(stream.pad_rows(arr, b), what)

    def _zero_pref(self, b: int) -> jax.Array:
        z = self._zero_prefs.get(b)
        if z is None:
            z = self._shard_batch(jnp.zeros((b,), jnp.float32),
                                  "route_stream")
            self._zero_prefs[b] = z
        return z

    def _unknown_cat(self, b: int) -> jax.Array:
        c = self._neg_cats.get(b)
        if c is None:
            c = self._shard_batch(jnp.full((b,), -1, jnp.int32),
                                  "route_stream")
            self._neg_cats[b] = c
        return c

    def route_stream(self, x: jax.Array, prefs: jax.Array | None = None,
                     cats: jax.Array | None = None):
        """Route a formed batch of *arbitrary* size through the AOT bucket
        programs: pad to the smallest bucket >= n, run the fused
        route program (selection + masked ring enqueue + cost accounting,
        hot buffers donated), slice the padding back off. Returns
        (a1 (n,), a2 (n,), tickets (n,)) exactly like ``route_batch`` —
        padded rows never enter the ring or the posterior, and the live
        rows are bit-identical to routing the unpadded batch. Zero
        recompiles for any n <= max(buckets); n above the ladder raises
        (form smaller batches — see ``serving.stream.form_batches``)."""
        if not self.streaming:
            raise RuntimeError(
                "route_stream needs RouterServiceConfig(buckets=...): the "
                "tick-batch service compiles no AOT bucket programs")
        n = np.shape(x)[0]
        b = stream.bucket_for(n, self.buckets)
        with telemetry.span("route", self._compile_counts, tick=self.tick,
                            n=n, bucket=b):
            return self._route_bucket(x, n, b, prefs, cats)

    def _route_bucket(self, x, n: int, b: int, prefs, cats):
        """``route_stream``'s work inside its span: n live rows, bucket b."""
        x = jnp.asarray(x, jnp.float32)
        xb = self._pad_batch(x, b, "route_stream")
        mask = self._stream_mask(b, n)
        if prefs is None:
            prog, pref_row = self._s_route[b], self._zero_pref(b)
        else:
            if self._s_route_pref is None:
                raise ValueError(
                    f"policy '{self.policy.name}' has no act_pref path — "
                    f"per-request prefs need a preference-aware policy "
                    f"(the pooled FGTS/eps-greedy/LinUCB families)")
            pref_row = jnp.asarray(prefs, jnp.float32)
            if pref_row.shape != (n,):
                raise ValueError(
                    f"prefs shape {pref_row.shape} != ({n},) — one scalar "
                    f"cost weight per query row")
            prog = self._s_route_pref[b]
            pref_row = self._pad_batch(pref_row, b, "route_stream")
        key = self._next_key()
        if self.mesh is not None:
            key = jax.device_put(key, self._rep_sh)
        self.tick += 1                 # host mirror of the device clock
        if self.refresh_on:
            # extra trailing operand: the query categories the duel log
            # records (-1 = unknown; the refresh trainer infers offline)
            if cats is None:
                catb = self._unknown_cat(b)
            else:
                catb = self._pad_batch(jnp.asarray(cats, jnp.int32), b,
                                       "route_stream")
            self.state, self.pending, self._tick_dev, a1, a2, tickets, \
                self._duel_cost = prog(key, self.state, self.pending, xb,
                                       mask, pref_row, self._tick_dev,
                                       self._costs_dev, self._duel_cost,
                                       catb)
        else:
            self.state, self.pending, self._tick_dev, a1, a2, tickets, \
                self._duel_cost = prog(key, self.state, self.pending, xb,
                                       mask, pref_row, self._tick_dev,
                                       self._costs_dev, self._duel_cost)
        self.n_routed += n
        return a1[:n], a2[:n], tickets[:n]

    def feedback_stream(self, tickets: jax.Array, y: jax.Array):
        """Streaming twin of ``feedback_batch``: pad the delivered batch to
        the next bucket (padding masked out of the resolve), run the fused
        AOT feedback program — shard-local resolve + masked posterior fold,
        ring/state/tick donated. Same delivery semantics as feedback_batch
        (out-of-order, partial, duplicate, stale all fine) with one
        streaming addition: under a mesh, tickets must come back through
        the shard that issued them (the service keeps batch positions
        stable, so delivering votes at the positions their queries were
        routed in satisfies this for free). Returns the folded count (lazy
        device scalar on the masked path, host int on the compaction
        fallback)."""
        if not self.streaming:
            raise RuntimeError(
                "feedback_stream needs RouterServiceConfig(buckets=...): "
                "the tick-batch service compiles no AOT bucket programs")
        if np.shape(tickets) != np.shape(y):
            raise ValueError(
                f"feedback_stream: tickets shape {np.shape(tickets)} != "
                f"votes shape {np.shape(y)} — one vote per delivered ticket")
        n = np.shape(tickets)[0]
        b = stream.bucket_for(n, self.buckets)
        with telemetry.span("feedback", self._compile_counts,
                            tick=self.tick, n=n, bucket=b):
            return self._feedback_bucket(tickets, y, n, b)

    def _feedback_bucket(self, tickets, y, n: int, b: int):
        """``feedback_stream``'s work inside its span: n votes, bucket b."""
        tickets = jnp.asarray(tickets, jnp.int32)
        y = jnp.asarray(y, jnp.float32)
        tk = self._pad_batch(tickets, b, "feedback_stream")
        yb = self._pad_batch(y, b, "feedback_stream")
        mask = self._stream_mask(b, n)
        if self._s_feedback_log is not None:
            # refresh-enabled twin: the duel-log ring is donated through
            # and rebound with the rest of the hot buffers
            self.state, self.pending, self._tick_dev, self._n_folded, \
                self.duel_log, n_ok = self._s_feedback_log[b](
                    self.state, self.pending, tk, yb, mask,
                    self._tick_dev, self._n_folded, self.duel_log)
            return n_ok
        if self._s_feedback is not None:
            self.state, self.pending, self._tick_dev, self._n_folded, \
                n_ok = self._s_feedback[b](self.state, self.pending, tk,
                                           yb, mask, self._tick_dev,
                                           self._n_folded)
            return n_ok
        # no masked update: donated AOT resolve, legacy host-shaped fold
        self.pending, self._tick_dev, res = self._s_resolve[b](
            self.pending, tk, yb, mask, self._tick_dev)
        if self.refresh_on:
            self.duel_log = self._fold_log(self.duel_log, res,
                                           self._tick_dev)
        return self._fold_compact(res)

    def _shard_batch(self, x: jax.Array, what: str = "batch") -> jax.Array:
        """Mesh mode: place a (B, ...) array batch-sharded (no-op on a
        single device); B must divide over the batch-shard count."""
        if self.mesh is None:
            return jnp.asarray(x)
        if x.shape[0] % self._n_shards:
            raise ValueError(
                f"{what} size {x.shape[0]} does not divide over the mesh's "
                f"{self._n_shards} batch shards "
                f"({dict(self.mesh.shape)}) — pad or rebatch")
        sh = self._x_sh if x.ndim > 1 else self._row_sh
        return jax.device_put(jnp.asarray(x), sh)

    def _next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    def embed(self, tokens: jax.Array, mask: jax.Array) -> jax.Array:
        """(B, L) tokens and pooling mask -> (B, d) query embeddings, in a
        ``router.embed`` span with ``bucket`` B, ``aot`` (1 when a bucket
        program served the call) and, where the mask is on the host,
        ``n``, the rows with a token (a device mask is not read, and the
        span then has no ``n``).

        A (bucket, ``enc_cfg.max_len``) batch runs the bucket's AOT
        encoder; any other shape, and every call of a service without
        buckets, runs ``encode`` under one jit that compiles once per
        shape."""
        b = np.shape(tokens)[0]
        prog = (self._s_embed.get(b) if self.streaming
                and np.shape(tokens) == (b, self.enc_cfg.max_len) else None)
        live = ({"n": np.count_nonzero(mask.any(axis=1))}
                if isinstance(mask, np.ndarray) else {})
        with telemetry.span("embed", self._compile_counts, tick=self.tick,
                            bucket=b, aot=int(prog is not None), **live):
            if prog is None:
                self._embed_calls["jit"] += 1
                return self._embed_jit(self.enc_params, tokens, mask)
            self._embed_calls["aot"] += 1
            rows = [a if a.dtype == dt else a.astype(dt)
                    for a, dt in ((tokens, np.int32), (mask, np.float32))]
            if self.mesh is not None:
                rows = jax.device_put(rows, self._x_sh)
            return prog(self.enc_params, *rows)

    def route_batch(self, x: jax.Array, prefs: jax.Array | None = None,
                    cats: jax.Array | None = None):
        """x: (B, d) query features. Returns (a1 (B,), a2 (B,), tickets (B,)).

        One policy.act per batch: for FGTS.CDB that amortizes the SGLD
        posterior refresh over the whole batch and selects every pair in the
        dueling_score kernel (cost tilt included). Every issued duel enters
        the ``PendingDuels`` ring (one scatter); hand each query's ticket
        back with its responses and redeem it in ``feedback_batch`` whenever
        the vote lands.

        ``prefs`` (B,) float are per-request cost weights: row i is scored
        under the extra tilt ``prefs[i] * cost_k`` (added to the service's
        global cost_tilt and, under an autopilot, the governor's lambda),
        so one service serves every point of the cost-quality front from
        the same posterior. Prefs are traced operands of one compiled
        program — distinct values never retrace — and are recorded with
        each issued duel so the feedback fold conditions on them.

        ``cats`` (B,) int32 are optional query-category labels (-1 =
        unknown) recorded with each duel for the representation-refresh
        log; with refresh off they are ignored.

        In streaming mode (``cfg.buckets``) this delegates to
        ``route_stream``: the batch pads to the next bucket and runs the
        fused AOT program — any batch size up the ladder, zero recompiles.
        """
        if self.streaming:
            return self.route_stream(x, prefs=prefs, cats=cats)
        x = self._shard_batch(x, "route_batch")
        prop = None
        if prefs is None:
            if self.refresh_on:
                self.state, a1, a2, prop = self._act(self._next_key(),
                                                     self.state, x)
            else:
                self.state, a1, a2 = self._act(self._next_key(),
                                               self.state, x)
            pref_row = jnp.zeros((x.shape[0],), jnp.float32)
        else:
            if self._act_pref is None:
                raise ValueError(
                    f"policy '{self.policy.name}' has no act_pref path — "
                    f"per-request prefs need a preference-aware policy "
                    f"(the pooled FGTS/eps-greedy/LinUCB families)")
            pref_row = jnp.asarray(prefs, jnp.float32)
            if pref_row.shape != (x.shape[0],):
                raise ValueError(
                    f"prefs shape {pref_row.shape} != ({x.shape[0]},) — one "
                    f"scalar cost weight per query row")
            pref_sh = self._shard_batch(pref_row, "route_batch")
            if self.refresh_on:
                self.state, a1, a2, prop = self._act_pref(
                    self._next_key(), self.state, x, pref_sh)
            else:
                self.state, a1, a2 = self._act_pref(
                    self._next_key(), self.state, x, pref_sh)
        # clock first, then issue at the new tick: feedback redeemed before
        # the next routing round reports age 0 (so feedback_expiry=N means
        # "survives N further rounds", matching env.run's lag-D => age-D)
        self.tick += 1
        if self.refresh_on:
            # the act-time propensity and the query category ride the ring
            # with the duel (resolved into the exportable log later)
            cat_row = (jnp.full((x.shape[0],), -1, jnp.int32)
                       if cats is None else jnp.asarray(cats, jnp.int32))
            self.pending, tickets = self._enqueue(
                self.pending, x, a1, a2, _tick32(self.tick),
                self._shard_batch(pref_row, "route_batch"), prop,
                self._shard_batch(cat_row, "route_batch"))
        else:
            self.pending, tickets = self._enqueue(
                self.pending, x, a1, a2, _tick32(self.tick),
                self._shard_batch(pref_row, "route_batch"))
        self.n_routed += int(x.shape[0])     # static shape: no device sync
        # realized duel cost rides on-device; spend() is lazy
        self._duel_cost = self._duel_cost + self.spend(a1) + self.spend(a2)
        return a1, a2, tickets

    def feedback_batch(self, tickets: jax.Array, y: jax.Array):
        """Resolve a batch of votes by ticket id and fold them in.

        Out-of-order, partial, and duplicate deliveries are all fine:
        resolution is one gather + one clearing scatter against the pending
        ring (duplicate tickets within the batch dedupe *inside* the jitted
        resolve — first delivery wins), stale tickets (already resolved,
        expired, or overwritten under capacity pressure) are dropped, and
        the surviving duels enter the policy with one jitted batched update
        (the staleness-aware ``update_delayed`` path when the policy has
        one). Returns the number of duels actually folded in — a *lazy*
        device scalar on the masked/pref paths (compare or ``int()`` it at
        the edge; the hot loop never blocks on it), a host int only on the
        compaction fallback.

        Policies with an ``update_masked`` fold rejects through the
        shape-stable masked update on the full resolved batch — rejected
        rows scatter out of bounds (``mode="drop"``) and contribute
        nothing, so the fold is bit-identical to compacting first, every
        survivor count reuses ONE compiled program, and the whole path
        runs without a single host sync. Policies without one keep the
        host-side compaction path (which must concretize the survivor
        count to shape the batch — each new count retraces once).

        In streaming mode (``cfg.buckets``) this delegates to
        ``feedback_stream`` (padded AOT resolve + fold, buffers donated).
        """
        if self.streaming:
            return self.feedback_stream(tickets, y)
        tickets = jnp.asarray(tickets, jnp.int32)
        y = jnp.asarray(y, jnp.float32)
        if tickets.shape != y.shape:
            # the old gather path silently sliced an oversized y; fail loud
            raise ValueError(
                f"feedback_batch: tickets shape {tickets.shape} != votes "
                f"shape {y.shape} — one vote per delivered ticket")
        tickets = self._shard_batch(tickets, "feedback_batch")
        y = self._shard_batch(y, "feedback_batch")
        self.pending, res = self._resolve(
            self.pending, tickets, y, _tick32(self.tick))
        if self.refresh_on:
            # fold the resolved batch into the exportable duel log (one
            # more lazy jitted dispatch — still zero host syncs)
            self.duel_log = self._fold_log(self.duel_log, res,
                                           _tick32(self.tick))
        n_ok = jnp.sum(res.ok).astype(jnp.int32)    # lazy device count
        if self._update_pref is not None and res.pref is not None:
            # preference-conditioned fold: each duel updates under the pref
            # it was served with, so the feel-good term targets the same
            # tilted objective the selection optimized
            self.state = self._update_pref(
                self.state, res.x, res.a1, res.a2, res.y, res.age,
                res.ok, res.pref)
            self._n_folded = self._n_folded + n_ok
            return n_ok
        if self._update_masked is not None:
            self.state = self._update_masked(
                self.state, res.x, res.a1, res.a2, res.y, res.age, res.ok)
            self._n_folded = self._n_folded + n_ok
            return n_ok
        return self._fold_compact(res)

    def _fold_compact(self, res: fq.ResolvedDuels) -> int:
        """Host-side compaction fallback for policies without a masked
        update: each new surviving count retraces the jitted update once
        (the update is the ring scatter; the SGLD refresh lives in act).
        Shaping the compacted batch forces the one host sync this path is
        named for (baselined in analysis/baseline.json)."""
        ok = np.asarray(res.ok)
        n_host = int(ok.sum())
        self._n_folded = self._n_folded + n_host
        if n_host == 0:
            return 0
        if n_host == ok.size:
            x, a1, a2, yv, age = res.x, res.a1, res.a2, res.y, res.age
        else:
            keep = np.flatnonzero(ok)
            x, a1, a2, yv, age = (res.x[keep], res.a1[keep], res.a2[keep],
                                  res.y[keep], res.age[keep])
        if self.mesh is not None:
            # compacted batches have arbitrary lengths: replicate them
            x, a1, a2, yv, age = (jax.device_put(v, self._rep_sh)
                                  for v in (x, a1, a2, yv, age))
        if self._update_delayed_compact is not None:
            self.state = self._update_delayed_compact(self.state, x, a1, a2,
                                                      yv, age)
        else:
            self.state = self._update_compact(self.state, x, a1, a2, yv)
        return n_host

    def feedback_direct(self, x: jax.Array, a1: jax.Array, a2: jax.Array,
                        y: jax.Array, tickets: jax.Array | None = None):
        """Synchronous escape hatch: fold a feedback batch in directly,
        bypassing the pending ring (callers that kept the duel data and
        never let feedback lag — e.g. offline replay). Pass the batch's
        ``tickets`` to also clear its ring slots; otherwise the issued
        entries linger until overwritten, inflating ``pending_count`` and
        the checkpointed buffer."""
        y = self._shard_batch(jnp.asarray(y, jnp.float32), "feedback_direct")
        if tickets is not None:
            t = jnp.asarray(tickets, jnp.int32)
            if self.streaming:
                # the streaming ring resolves through the AOT bucket
                # programs (shard-local addressing; legacy resolve assumes
                # the global ring layout)
                b = stream.bucket_for(t.shape[0], self.buckets)
                self.pending, self._tick_dev, _ = self._s_resolve[b](
                    self.pending,
                    self._pad_batch(t, b, "feedback_direct"),
                    self._pad_batch(y, b, "feedback_direct"),
                    self._stream_mask(b, t.shape[0]), self._tick_dev)
            else:
                self.pending, _ = self._resolve(
                    self.pending, self._shard_batch(t, "feedback_direct"),
                    y, _tick32(self.tick))
        self.state = self._update(
            self.state, self._shard_batch(x, "feedback_direct"),
            self._shard_batch(jnp.asarray(a1), "feedback_direct"),
            self._shard_batch(jnp.asarray(a2), "feedback_direct"), y)

    def pending_count(self) -> int:
        """In-flight duels (issued, unresolved, unexpired)."""
        return int(fq.pending_count(self.pending))

    def expire_pending(self) -> int:
        """Age out pending duels past ``cfg.feedback_expiry`` (no-op when
        unset). Returns the number dropped."""
        if self.cfg.feedback_expiry is None:
            return 0
        self.pending, dropped = fq.expire(
            self.pending, _tick32(self.tick), self.cfg.feedback_expiry)
        return int(dropped)

    def spend(self, arms: jax.Array, tokens_out: int = 1000) -> jax.Array:
        """Cost accounting for a batch of dispatches — a lazy device
        scalar, so route_batch can accumulate it without blocking; callers
        that need a host number ``float()`` it at the edge (a print, a
        summary), not per batch."""
        return jnp.sum(self.costs[arms]) * (tokens_out / 1000.0)

    def service_stats(self) -> dict:
        """Materialize the on-device traffic accumulators in ONE deliberate
        host sync: routed/folded duel counts, realized duel cost (both
        sides of every issued pair at the pool's per-1k rates), in-flight
        pending count. This is the summary call the hot path defers to —
        route_batch/feedback_batch only ever add lazily."""
        if self.refresh_on:
            n_folded, duel_cost, pending, logged = jax.device_get(
                (self._n_folded, self._duel_cost,
                 fq.pending_count(self.pending), self.duel_log.count))
        else:
            n_folded, duel_cost, pending = jax.device_get(
                (self._n_folded, self._duel_cost,
                 fq.pending_count(self.pending)))
        out = {"tick": self.tick, "n_routed": self.n_routed,
               "n_folded": int(n_folded), "duel_cost": float(duel_cost),
               "pending": int(pending),
               "compiles": dict(self._compile_counts.compiles),
               "compile_s": dict(self._compile_counts.compile_s),
               "embed_calls": dict(self._embed_calls)}
        if self.refresh_on:
            out["duels_logged"] = int(logged)
            out["table_swaps"] = self._table_swaps
        return out

    # -- online representation refresh (cfg.refresh) -------------------------

    def _require_refresh(self, what: str):
        if not self.refresh_on:
            raise RuntimeError(
                f"{what} needs the refresh loop: construct the service "
                f"with RouterServiceConfig(refresh=RefreshConfig(...))")

    def export_log(self) -> dict:
        """Host export of the logged duels — the input of the offline
        refresh job (``refresh.refresh_table``). One deliberate device
        transfer of the whole ring; refresh cadence is hundreds of rounds,
        so this read is off the hot path by construction."""
        self._require_refresh("export_log")
        return dl.export(self.duel_log)

    def refresh_due(self) -> bool:
        """True once ``cfg.refresh.every`` new duels have been folded into
        the log since the last ``apply_table`` (always False when every=0:
        manual refreshes only). One scalar device read — call it at the
        refresh-check cadence, not per batch."""
        if not self.refresh_on or self.cfg.refresh.every <= 0:
            return False
        count = jax.device_get(self.duel_log.count)
        return int(count) - self._count_at_swap >= self.cfg.refresh.every

    def apply_table(self, table, replay=None) -> None:
        """Hot-swap the whole (K_max, d) embedding table (e.g. a refreshed
        CCFT table from ``refresh.refresh_table``): one jitted table-sized
        scatter through ``model_pool.set_table``. The table is a *traced*
        operand, so every refresh reuses ONE compiled swap program and the
        act/update programs never retrace — the pool generation bumps,
        costs and the active mask ride through untouched. The posterior is
        kept as-is (duels learned under the old geometry still shape it)
        unless ``replay=(x, a1, a2, y)`` re-warm-starts it through
        ``seed_replay`` (e.g. ``model_pool.warm_start_duels`` against the
        refreshed table)."""
        self._require_dynamic("apply_table")
        table = jnp.asarray(table, jnp.float32)
        if self.mesh is not None:
            table = jax.device_put(table, self._rep_sh)
        self.state = self._table_swap(self.state, table)
        if self.refresh_on:
            count = jax.device_get(self.duel_log.count)
            self._count_at_swap = int(count)
            self._table_swaps += 1
        if replay is not None:
            self.seed_replay(*replay)

    # -- dynamic pool membership (requires cfg.k_max) ------------------------

    def _require_dynamic(self, what: str):
        if not self.dynamic:
            raise RuntimeError(
                f"{what} needs a dynamic pool: construct the service with "
                f"RouterServiceConfig(k_max=...) (and fgts.n_models == "
                f"k_max) to reserve hot-swap capacity")

    def model_pool(self) -> mp.ModelPool:
        """The live arm registry (embeddings, costs, active mask)."""
        self._require_dynamic("model_pool")
        return mp.get_pool(self.state)

    def active_mask(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.model_pool().active))

    # -- pool autopilot readouts (requires cfg.autopilot) --------------------

    def controller_state(self) -> "ap.ControllerState":
        """The live autopilot controller state (device pytree)."""
        if self.cfg.autopilot is None:
            raise RuntimeError(
                "no autopilot: construct the service with "
                "RouterServiceConfig(autopilot=AutopilotConfig(...))")
        return self.state.ctrl

    def autopilot_status(self) -> dict:
        """Host snapshot of the control loops: governor lambda, realized
        cost EMA, candidate slots and their duel tallies, dominance
        streaks. Pure observability — reading it never touches the jitted
        programs."""
        ctrl = jax.device_get(self.controller_state())
        return {
            "lambda": float(ctrl.lam),
            "cost_ema": float(ctrl.cost_ema),
            "tick": int(ctrl.tick),
            "active": self.active_mask(),
            "candidate": np.asarray(ctrl.candidate),
            "cand_wins": np.asarray(ctrl.cand_wins),
            "cand_duels": np.asarray(ctrl.cand_duels),
            "dominated_ticks": np.asarray(ctrl.dominated_ticks),
        }

    def add_model(self, entry: PoolEntry, replay=None) -> int:
        """Hot-add a model into the first free slot; returns the slot.

        The arm goes live warm, not cold: ``entry.embedding`` should come
        from ``ccft.model_embeddings`` on the model's offline skill scores,
        and ``replay=(x, a1, a2, y)`` (e.g. from
        ``model_pool.warm_start_duels``) replays historical duels through
        the policy's shape-stable masked update to pre-shape the posterior
        before the arm takes live traffic. The add itself is one jitted
        row-scatter + mask flip — zero new act/update compilations.

        Under an autopilot (``cfg.autopilot``) the arm enters as an A/B
        *candidate*: the next act registers the arrival, caps its traffic
        at the configured quota, and promotes or rolls it back on its duel
        record — seeded replay duels fold into the posterior but do not
        count toward promotion (the arm is not yet a candidate while they
        replay).

        Never-used slots are preferred: reusing a retired arm's slot would
        hand the newcomer that arm's replay-ring history and per-slot
        stats. When only retired slots remain the first one is reused with
        a warning — size ``k_max`` with headroom, or use ``swap_model``
        when the inheritance is intended (a retrained successor).
        """
        self._require_dynamic("add_model")
        mask = self.active_mask()
        if mask.all():
            raise RuntimeError(
                f"pool at capacity k_max={self.cfg.k_max}: retire an arm "
                f"first (or rebuild with more headroom)")
        virgin = [i for i in range(self.cfg.k_max)
                  if not mask[i] and not self._ever_used[i]]
        if virgin:
            slot = virgin[0]
        else:
            slot = int(np.argmin(mask))      # first retired slot
            warnings.warn(
                f"add_model: no never-used slot left — '{entry.name}' "
                f"reuses retired slot {slot} and inherits its replay "
                f"history / per-slot stats; grow k_max (or use swap_model "
                f"if this is a successor model)", stacklevel=2)
        self._set_slot(slot, entry)
        if replay is not None:
            self.seed_replay(*replay)
        return slot

    def retire_model(self, k: int) -> None:
        """Take arm ``k`` out of rotation: a jitted mask flip. The slot's
        embedding row and its replay-ring history are retained — the shared
        posterior keeps learning from the retired arm's duels, it just can
        never be selected again. In-flight duels that referenced it still
        resolve normally."""
        self._require_dynamic("retire_model")
        mask = self.active_mask()
        if not mask[k]:
            raise ValueError(f"arm {k} is not active")
        if mask.sum() <= 1:
            raise RuntimeError("cannot retire the last active arm")
        self.state = self._pool_retire(self.state,
                                       jnp.asarray(k, jnp.int32))
        self.costs = jnp.array(mp.get_pool(self.state).costs)
        self._sync_stream_costs()

    def swap_model(self, k: int, entry: PoolEntry, replay=None) -> None:
        """Replace slot ``k``'s model in place (retrained successor, new
        cost point): row scatter + activate, replay history inherited — use
        ``retire_model`` + ``add_model`` for an unrelated model instead."""
        self._require_dynamic("swap_model")
        if not 0 <= k < self.cfg.k_max:
            raise ValueError(f"slot {k} outside capacity {self.cfg.k_max}")
        self._set_slot(k, entry)
        if replay is not None:
            self.seed_replay(*replay)

    def _set_slot(self, slot: int, entry: PoolEntry) -> None:
        self.state = self._pool_set(
            self.state, jnp.asarray(entry.embedding, jnp.float32),
            jnp.asarray(entry.cost_per_1k_tokens, jnp.float32),
            jnp.asarray(slot, jnp.int32))
        self.pool[slot] = entry
        self._ever_used[slot] = True
        self.costs = jnp.array(mp.get_pool(self.state).costs)
        self._sync_stream_costs()

    def seed_replay(self, x, a1, a2, y) -> int:
        """Offline→online seeding: fold a batch of historical duels into
        the posterior (no pending ring, no tickets — the duels already
        happened offline). Uses the policy's shape-stable ``update_masked``
        when it has one. Returns the number of duels folded."""
        self._require_dynamic("seed_replay")
        x = jnp.asarray(x, jnp.float32)
        a1 = jnp.asarray(a1, jnp.int32)
        a2 = jnp.asarray(a2, jnp.int32)
        y = jnp.asarray(y, jnp.float32)
        if self.mesh is not None:
            x, a1, a2, y = (jax.device_put(v, self._rep_sh)
                            for v in (x, a1, a2, y))
        if self._update_masked is not None:
            b = x.shape[0]
            age = jnp.zeros((b,), jnp.int32)
            ok = jnp.ones((b,), bool)
            if self.mesh is not None:
                age, ok = (jax.device_put(v, self._rep_sh)
                           for v in (age, ok))
            self.state = self._update_seed(self.state, x, a1, a2, y, age,
                                           ok)
        else:
            self.state = self._update_seed(self.state, x, a1, a2, y)
        return int(x.shape[0])

    def compiled_program_counts(self) -> dict:
        """Executable-cache sizes of the service's jitted programs — the
        zero-retrace contract for dynamic pools is asserted against this
        (an add/retire/swap must not grow any act/update entry)."""
        fns = {"act": self._act, "act_pref": self._act_pref,
               "update": self._update,
               "update_delayed": self._update_delayed,
               "update_masked": self._update_masked,
               "update_pref": self._update_pref,
               "enqueue": self._enqueue, "resolve": self._resolve,
               "embed_jit": self._embed_jit}
        if self.dynamic:
            fns.update(pool_set=self._pool_set,
                       pool_retire=self._pool_retire,
                       update_seed=self._update_seed,
                       table_swap=self._table_swap)
        if self.refresh_on:
            fns["fold_log"] = self._fold_log
        counts = {name: fn._cache_size() for name, fn in fns.items()
                  if fn is not None}
        if self.streaming:
            # AOT executables cannot retrace — their count is the bucket
            # ladder size, fixed at construction. Reporting them keeps
            # assert_flat honest about the whole surface (a stray lazy-path
            # compile still shows up in the entries above).
            counts["s_route"] = len(self._s_route)
            if self._s_route_pref is not None:
                counts["s_route_pref"] = len(self._s_route_pref)
            if self._s_feedback is not None:
                counts["s_feedback"] = len(self._s_feedback)
            if self._s_feedback_log is not None:
                counts["s_feedback_log"] = len(self._s_feedback_log)
            counts["s_resolve"] = len(self._s_resolve)
            counts["s_embed"] = len(self._s_embed)
        return counts

    # -- persistence (posterior + replay + in-flight duels survive restarts) -

    def save(self, path: str, step: int | None = None) -> str:
        from repro.checkpoint import save_checkpoint
        payload = {"state": self.state,
                   "key": self._key,
                   "pending": self.pending,
                   "tick": jnp.asarray(self.tick),
                   "n_routed": jnp.asarray(self.n_routed)}
        if self.dynamic:
            # slot-usage history survives restarts, so add_model's
            # virgin-slot preference (and its inheritance warning) keeps
            # working after a checkpoint round-trip
            payload["ever_used"] = jnp.asarray(self._ever_used)
        if self.refresh_on:
            # the duel log (propensities included) restarts with the
            # posterior: a crash never loses the refresh loop's evidence
            payload["duel_log"] = self.duel_log
        return save_checkpoint(path, step if step is not None
                               else self.n_routed, payload)

    def restore(self, path: str, step: int | None = None) -> int:
        from repro.checkpoint import latest_step, restore_checkpoint
        step = latest_step(path) if step is None else step
        like = {"state": self.state, "key": self._key,
                "pending": self.pending, "tick": jnp.asarray(self.tick),
                "n_routed": jnp.asarray(self.n_routed)}
        if self.dynamic:
            like["ever_used"] = jnp.asarray(self._ever_used)
        if self.refresh_on:
            like["duel_log"] = self.duel_log
        try:
            payload = restore_checkpoint(path, step, like)
        except AssertionError as e:
            raise RuntimeError(
                f"incompatible router checkpoint at {path} step {step}: "
                f"structure/shape mismatch with policy "
                f"'{self.policy.name}' (pre-async checkpoints lack the "
                f"pending-duels buffer; pre-RoutingPolicy ones carry (dim,) "
                f"thetas instead of (n_chains, dim)) — {e}") from e
        self.state = payload["state"]
        self._key = payload["key"]
        self.pending = payload["pending"]
        self.tick = int(payload["tick"])
        self.n_routed = int(payload["n_routed"])
        if self.mesh is not None:     # re-place restored buffers on the mesh
            self.state = jax.device_put(self.state, self._rep_sh)
            self.pending = jax.device_put(
                self.pending, rr.to_shardings(
                    self.mesh,
                    rr.stream_pending_specs(self.mesh) if self.streaming
                    else rr.pending_specs(self.mesh)))
        if self.streaming:
            # re-seat the device clock and cost mirror behind the restored
            # host tick/state
            self._tick_dev = _tick32(self.tick)
            if self.mesh is not None:
                self._tick_dev = jax.device_put(self._tick_dev,
                                                self._rep_sh)
        if self.dynamic:
            # the pool travels with the state: re-sync the cost mirror
            # (entry names/registry are host bookkeeping and not part of
            # the checkpoint — re-register entries if you need them)
            self.costs = mp.get_pool(self.state).costs
            self._ever_used = [bool(v) for v in
                               np.asarray(payload["ever_used"])]
            self._sync_stream_costs()
        if self.refresh_on:
            self.duel_log = payload["duel_log"]
            if self.mesh is not None:
                self.duel_log = jax.device_put(
                    self.duel_log,
                    rr.to_shardings(self.mesh,
                                    rr.duel_log_specs(self.mesh)))
            # the refresh cadence marker is process-local (like the stats
            # accumulators): re-anchor it at the restored log head so a
            # restart never fires a spurious immediate refresh
            count = jax.device_get(self.duel_log.count)
            self._count_at_swap = int(count)
        return step
