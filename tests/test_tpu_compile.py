"""The main-path Pallas kernels compile for a TPU v5e, without a chip.

libtpu's compiler compiles for a topology that is described and not
attached, so these tests run the chip's own kernel compiler (Mosaic) on the
serving widths: K=11 RouterBench arms, d=782 (a 768-wide query encoder plus
2 x 7 metadata columns). They catch what interpret mode cannot — block
shapes the TPU tiling refuses, operand layouts XLA and Mosaic disagree on,
scalar stores to vector memory. Each test asserts that the kernel is in the
compiled program (``tpu_custom_call``) under its ``pallas_call`` name, which
a profile of the chip shows as the instruction's name.

Only one process may hold libtpu, and it keeps it until it exits, so the
topology is described inside a module-scoped fixture (never at import):
under several pytest workers only the worker given this file loads it.
Keep these compiles in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import MAX_K_FUSED
from repro.kernels.dueling_score import dueling_select, posterior_scores
from repro.kernels.sgld_update import sgld_potential

K, D = 11, 782


@pytest.fixture(scope="module")
def one_chip():
    """A SingleDeviceSharding on chip 0 of a described v5e:2x2 topology.
    The persistent compilation cache is off meanwhile: a compile for a
    described chip is written there but cannot be read back without one."""
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _compiled_hlo(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels_named(hlo, name):
    """Instruction names of the compiled kernels whose name holds ``name``
    (a VJP prefixes the transforms: ``transpose_jvp_<name>__.N``)."""
    insts = [line.split(" = ", 1)[0].strip().lstrip("%")
             for line in hlo.splitlines() if "tpu_custom_call" in line]
    return [i for i in insts if name in i]


@pytest.mark.parametrize("per_row", [False, True], ids=["mask_K", "mask_BK"])
@pytest.mark.parametrize("b", [8, 256, 4096])
def test_dueling_select_compiles(one_chip, b, per_row):
    """Route-path selection at every bucket scale, with a global (K,) or a
    per-request (B, K) mask and tilt, force-distinct on."""
    f32 = jnp.float32
    mshape = (b, K) if per_row else (K,)

    def route(x, a, th, m, t):
        return dueling_select(x, a, th, tilt=t, mask=m, distinct=True,
                              interpret=False)

    hlo = _compiled_hlo(route, [((b, D), f32), ((K, D), f32), ((2, D), f32),
                                (mshape, jnp.bool_), (mshape, f32)],
                        one_chip)
    assert "tpu_custom_call" in hlo
    assert _kernels_named(hlo, "dueling_select")


def test_dueling_select_compiles_at_max_k(one_chip):
    """The fused epilogue's K ceiling: MAX_K_FUSED arms whole in VMEM."""
    f32 = jnp.float32

    def route(x, a, th, m):
        return dueling_select(x, a, th, mask=m, distinct=True,
                              interpret=False)

    hlo = _compiled_hlo(route, [((256, D), f32), ((MAX_K_FUSED, D), f32),
                                ((2, D), f32), ((MAX_K_FUSED,), jnp.bool_)],
                        one_chip)
    assert "tpu_custom_call" in hlo
    assert _kernels_named(hlo, "dueling_select")


def test_posterior_scores_compiles(one_chip):
    """Autopilot dominance scores: 16 SGLD chains against the pool."""
    hlo = _compiled_hlo(
        lambda a, th: posterior_scores(a, th, interpret=False),
        [((K, D), jnp.float32), ((16, D), jnp.float32)], one_chip)
    assert "tpu_custom_call" in hlo
    assert _kernels_named(hlo, "dueling_score")


@pytest.mark.parametrize("chains", [None, 8], ids=["one_theta", "vmap8"])
@pytest.mark.parametrize("m", [128, 256])
def test_sgld_potential_value_and_grad_compiles(one_chip, m, chains):
    """The fused SGLD potential and its hand-written VJP at the serving
    minibatch sizes (one and two row tiles), alone and vmapped over
    chains, with the arm mask and the preference tilt on."""
    f32, i32 = jnp.float32, jnp.int32

    def value_and_grad(theta, x, a1, a2, y, valid, a_emb, mask, pref,
                       costs):
        def pot(th):
            return sgld_potential(th, x, a1, a2, y, valid, a_emb, mask,
                                  pref=pref, costs=costs, backend="fused",
                                  interpret=False)
        vg = jax.value_and_grad(pot)
        return vg(theta) if chains is None else jax.vmap(vg)(theta)

    theta = (D,) if chains is None else (chains, D)
    hlo = _compiled_hlo(
        value_and_grad,
        [(theta, f32), ((m, D), f32), ((m,), i32), ((m,), i32), ((m,), f32),
         ((m,), f32), ((K, D), f32), ((K,), jnp.bool_), ((m,), f32),
         ((K,), f32)], one_chip)
    assert hlo.count("tpu_custom_call") >= 2      # forward and backward
    assert _kernels_named(hlo, "sgld_potential_fwd")
    assert _kernels_named(hlo, "sgld_potential_bwd")
