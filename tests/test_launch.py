"""Launch-layer tests: train driver, input_specs coverage, serve plumbing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_arch, OPTIMIZED_OVERRIDES
from repro.launch import steps as steps_lib
from repro.launch.train import synthetic_batch, train


def test_train_driver_reduced_runs_and_descends(tmp_path):
    losses = train("granite-3-2b", steps=6, batch=4, seq=32, reduced=True,
                   lr=1e-3, ckpt_dir=str(tmp_path), log_every=100)
    assert len(losses) == 6
    assert np.isfinite(losses).all()
    # checkpoint written and restorable
    from repro.checkpoint import latest_step
    assert latest_step(str(tmp_path)) == 6


def test_train_driver_resumes(tmp_path):
    train("mamba2-1.3b", steps=3, batch=2, seq=32, reduced=True,
          ckpt_dir=str(tmp_path), log_every=100)
    losses = train("mamba2-1.3b", steps=5, batch=2, seq=32, reduced=True,
                   ckpt_dir=str(tmp_path), log_every=100)
    assert len(losses) == 5 - 3          # resumed from step 3


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_input_specs_build_for_all_combos(arch, shape_name):
    """Spec construction (no compile) must work for every combo that the
    dry-run would attempt, on both mesh shapes."""
    from repro.launch.dryrun import applicable
    if not applicable(arch, shape_name):
        pytest.skip("long_500k on full-attention arch (noted skip)")
    cfg = get_arch(arch, shape_name)
    for mesh in (jax.sharding.AbstractMesh((16, 16), ("data", "model")),
                 jax.sharding.AbstractMesh(
                     (2, 16, 16), ("pod", "data", "model"))):
        args, in_sh, out_sh, step = steps_lib.input_specs(
            cfg, SHAPES[shape_name], mesh)
        assert callable(step)
        assert len(jax.tree.leaves(args)) > 0


def test_optimized_overrides_are_valid_config_fields():
    for arch, ov in OPTIMIZED_OVERRIDES.items():
        cfg = get_arch(arch, optimized=True)
        for k, v in ov.items():
            assert getattr(cfg, k) == v, (arch, k)


def test_synthetic_batch_shapes():
    cfg = ARCHS["llava-next-34b"].reduced()
    b = synthetic_batch(jax.random.PRNGKey(0), cfg, 2, 64)
    assert b["patches"].shape == (2, cfg.n_frontend_tokens, cfg.d_model)
    assert b["tokens"].shape[1] == 64 - cfg.n_frontend_tokens
    cfg = ARCHS["seamless-m4t-medium"].reduced()
    b = synthetic_batch(jax.random.PRNGKey(0), cfg, 2, 64)
    assert b["frames"].shape == (2, cfg.enc_frames, cfg.d_model)


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
      %ar = f32[8,128]{1,0} all-reduce(%x), replica_groups={}
      %ag.1 = bf16[1024]{0} all-gather(%y), dimensions={0}
      %cp = u8[4]{0} collective-permute(%z)
      %notacoll = f32[2]{0} add(%a, %b)
    """
    rec = collective_bytes(hlo)
    assert rec["bytes"]["all-reduce"] == 8 * 128 * 4
    assert rec["bytes"]["all-gather"] == 1024 * 2
    assert rec["bytes"]["collective-permute"] == 4
    assert rec["counts"]["all-reduce"] == 1
    assert rec["total_bytes"] == 8 * 128 * 4 + 2048 + 4
