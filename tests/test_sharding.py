"""Sharding-rule tests on a tiny host mesh (structure-level, no 512 devices:
the production-mesh pass is `python -m repro.launch.dryrun`, exercised by the
benchmark harness; here we verify spec trees match param/cache trees and that
a reduced arch lowers+compiles under a real (1,1) mesh)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models import lm
from repro.sharding import rules


def fake_mesh(shape=(16, 16), axes=("data", "model")):
    """Abstract mesh for spec construction (no devices needed)."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_specs_match_param_tree(arch):
    cfg = ARCHS[arch]
    mesh = fake_mesh()
    sp = rules.param_specs(cfg, mesh)
    sds = steps_lib.params_specs(cfg)
    # every param leaf has a spec leaf with matching rank constraints
    flat_p = {jax.tree_util.keystr(k): v
              for k, v in jax.tree_util.tree_flatten_with_path(sds)[0]}
    flat_s = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_flatten_with_path(
                  sp, is_leaf=lambda x: isinstance(x, P))[0]}
    assert set(flat_p) == set(flat_s), (
        set(flat_p) ^ set(flat_s))
    for k, sds_leaf in flat_p.items():
        spec = flat_s[k]
        assert len(spec) <= len(sds_leaf.shape), (k, spec, sds_leaf.shape)
        # sharded dims must divide
        sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes_ = (ax,) if isinstance(ax, str) else tuple(ax)
            prod = int(np.prod([sizes[a] for a in axes_]))
            assert sds_leaf.shape[dim] % prod == 0, (k, spec, sds_leaf.shape)


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("shape_name", ["train_4k", "decode_32k"])
def test_cache_and_batch_specs_divide(arch, shape_name):
    cfg = ARCHS[arch]
    mesh = fake_mesh()
    shape = SHAPES[shape_name]
    args, in_sh, out_sh, step = steps_lib.input_specs(cfg, shape, mesh)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))

    def check(sds_tree, sp_tree):
        flat_p = jax.tree_util.tree_flatten_with_path(sds_tree)[0]
        flat_s = dict()
        for k, v in jax.tree_util.tree_flatten_with_path(
                sp_tree, is_leaf=lambda x: isinstance(x, P))[0]:
            flat_s[jax.tree_util.keystr(k)] = v
        for k, leaf in flat_p:
            spec = flat_s[jax.tree_util.keystr(k)]
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axes_ = (ax,) if isinstance(ax, str) else tuple(ax)
                prod = int(np.prod([sizes[a] for a in axes_]))
                assert leaf.shape[dim] % prod == 0, (
                    arch, shape_name, k, spec, leaf.shape)

    for a, s in zip(args, in_sh):
        check(a, s)


def test_production_mesh_shapes_monkeypatched(monkeypatch):
    """make_production_mesh wires the (2,16,16)/(16,16) shapes (verified via
    jax.make_mesh arguments; actually building 512 devices needs the dry-run
    entrypoint)."""
    calls = {}

    def fake_make_mesh(shape, axes, axis_types=None):
        calls["shape"], calls["axes"] = shape, axes
        calls["types"] = axis_types
        return "mesh"

    auto = jax.sharding.AxisType.Auto
    monkeypatch.setattr(jax, "make_mesh", fake_make_mesh)
    mesh_lib.make_production_mesh()
    assert calls == {"shape": (16, 16), "axes": ("data", "model"),
                     "types": (auto, auto)}
    mesh_lib.make_production_mesh(multi_pod=True)
    assert calls == {"shape": (2, 16, 16), "axes": ("pod", "data", "model"),
                     "types": (auto, auto, auto)}


def test_reduced_arch_lowers_on_real_mesh():
    """Full jit lower+compile path on the single real device."""
    cfg = dataclasses.replace(
        ARCHS["gemma2-9b"].reduced(), dtype="float32")
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64,
                                global_batch=2)
    args, in_sh, out_sh, step = steps_lib.input_specs(cfg, shape, mesh)
    with mesh:
        compiled = jax.jit(
            step,
            in_shardings=steps_lib.tree_shardings(mesh, in_sh),
            out_shardings=steps_lib.tree_shardings(mesh, out_sh),
        ).lower(*args).compile()
    assert compiled.cost_analysis() is not None


def test_long500k_decode_cache_is_window_bounded():
    """gemma2 long-context serving mode must not allocate 500k KV."""
    from repro.configs import get_arch
    cfg = get_arch("gemma2-9b", "long_500k")
    assert cfg.sub_quadratic
    sds = steps_lib.cache_sds(cfg, 1, SHAPES["long_500k"].seq_len)
    biggest = max(int(np.prod(l.shape)) * l.dtype.itemsize
                  for l in jax.tree.leaves(sds))
    # 4096-window cache: 1 x 4096 x 8 x 256 x 2B = 16.8 MB per layer slot
    assert biggest < 1e9, biggest
