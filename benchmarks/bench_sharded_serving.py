"""Mesh-sharded live-serving throughput: queries/sec vs devices vs batch.

Drives the full RouterService hot loop — shard_map-partitioned ``act``
(SGLD refresh + pair selection), sharded pending-ring enqueue, ticket
resolution and the replay update — and compares the single-device service
against the mesh-sharded one on the same host. On a CPU-only run the
"devices" are forced host devices (threads), so the table is a scaling
*shape* check plus a partitioning-overhead measurement; on a real
TPU/GPU mesh the same harness measures true scaling.

    PYTHONPATH=src python -m benchmarks.bench_sharded_serving
    (forces --xla_force_host_platform_device_count=8 when run standalone)
"""
from __future__ import annotations

import os

if __name__ == "__main__" and "host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fgts
from repro.encoder.model import EncoderConfig, init_encoder
from repro.launch import mesh as mesh_lib
from repro.serving.router_service import (PoolEntry, RouterService,
                                          RouterServiceConfig)

from .common import emit

DIM = 64
K_MODELS = 8
BATCHES = (256, 1024)
ROUNDS = 6
WARMUP = 2
SEED = 0


def _make_service(batch: int, mesh) -> RouterService:
    key = jax.random.PRNGKey(SEED)
    enc_cfg = EncoderConfig(d_model=DIM, n_layers=1, n_heads=2, d_ff=128,
                            max_len=8)
    enc = init_encoder(key, enc_cfg)
    rng = np.random.RandomState(SEED)
    pool = [PoolEntry(name=f"m{i}", arch="granite-3-2b",
                      cost_per_1k_tokens=0.1 * (i + 1),
                      embedding=rng.randn(DIM).astype(np.float32))
            for i in range(K_MODELS)]
    fcfg = fgts.FGTSConfig(n_models=K_MODELS, dim=DIM,
                           horizon=max(4 * batch, 4096), sgld_steps=5,
                           sgld_minibatch=64)
    return RouterService(pool, enc, enc_cfg,
                         RouterServiceConfig(fgts=fcfg,
                                             feedback_capacity=4 * batch),
                         mesh=mesh)


def _throughput(svc: RouterService, batch: int, key) -> float:
    """Steady-state queries/sec over the act -> enqueue -> resolve -> update
    loop (feedback redeemed one round late, the async serving shape),
    synced only at the measurement boundaries."""
    xs = [jax.random.normal(jax.random.fold_in(key, i), (batch, DIM))
          for i in range(ROUNDS + WARMUP)]
    pending = None
    t0 = None
    for i, x in enumerate(xs):
        if i == WARMUP:
            jax.block_until_ready(svc.state)
            t0 = time.time()
        _, _, tickets = svc.route_batch(x)
        if pending is not None:
            svc.feedback_batch(pending, jnp.ones((batch,), jnp.float32))
        pending = tickets
    jax.block_until_ready(svc.state)
    t1 = time.time()
    return ROUNDS * batch / (t1 - t0)


def run(seed: int = SEED):
    key = jax.random.PRNGKey(seed + 11)
    n_dev = len(jax.devices())
    # (label, mesh): single device vs the full host mesh (4,2)-style
    grids = [("1", None)]
    if n_dev > 1:
        shape = (n_dev // 2, 2) if n_dev % 2 == 0 else (n_dev, 1)
        grids.append((str(n_dev), mesh_lib.make_debug_mesh(*shape)))
    else:
        # jax is already initialized when the orchestrator imports us, so
        # the device count cannot be forced here — say what's missing
        # rather than silently printing a one-column table
        print("[sharded] only 1 host device visible — mesh column SKIPPED; "
              "run `PYTHONPATH=src python -m benchmarks.bench_sharded_"
              "serving` standalone (it forces 8 host devices) for the "
              "1-vs-N comparison")

    rows, table = [], {}
    for batch in BATCHES:
        for label, mesh in grids:
            svc = _make_service(batch, mesh)
            qps = _throughput(svc, batch, key)
            table[(batch, label)] = qps
            rows.append(emit(f"sharded/serve_b{batch}_dev{label}",
                             1.0 / qps, f"qps={qps:.0f}"))

    dev_cols = [g[0] for g in grids]
    print(f"\nsharded serving throughput (queries/sec, {ROUNDS} timed "
          f"rounds, feedback lag 1 round, syncs at measurement boundaries "
          f"only)")
    print(f"{'batch':<8}" + "".join(f"{'dev=' + c:>12}" for c in dev_cols)
          + (f"{'speedup':>10}" if len(dev_cols) > 1 else ""))
    for batch in BATCHES:
        line = f"{batch:<8}" + "".join(
            f"{table[(batch, c)]:>12.0f}" for c in dev_cols)
        if len(dev_cols) > 1:
            speedup = table[(batch, dev_cols[-1])] / table[(batch, "1")]
            line += f"{speedup:>10.2f}"
        print(line)
    return rows


if __name__ == "__main__":
    run()
