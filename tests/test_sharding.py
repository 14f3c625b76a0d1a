"""Partition-rule correctness for every architecture.

These run WITHOUT building the production mesh (pure spec construction):
rank alignment, divisibility of every sharded dim by the mesh axis, and
worker-axis placement — the cheap invariants whose violations are exactly
what makes a 512-device lower() fail.  Specs come from the per-algorithm
``state_specs`` / ``batch_specs`` hooks, the only sharding seam.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import registry
from repro.core.api import MeshAxes
from repro.core.types import DCS3GDConfig, INPUT_SHAPES
from repro.launch import specs as S
from repro.models.transformer import Model
from repro.parallel.sharding import cache_specs, param_specs

from helpers import ALL_ARCHS

MESH_SHAPE = {"data": 16, "model": 16, "pod": 2}

AXES_POD = MeshAxes(worker=("data",), model="model", model_size=16)
AXES_MULTIPOD = MeshAxes(worker=("pod", "data"), model="model",
                         model_size=16)


def _axis_size(ax):
    if ax is None:
        return 1
    if isinstance(ax, tuple):
        out = 1
        for a in ax:
            out *= MESH_SHAPE[a]
        return out
    return MESH_SHAPE[ax]


def _check_divisible(tree, specs, where):
    for (path, leaf), spec in zip(
            jax.tree_util.tree_flatten_with_path(tree)[0],
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        assert len(spec) <= leaf.ndim, (where, path, spec, leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(spec)):
            n = _axis_size(ax)
            assert dim % n == 0, (where, jax.tree_util.keystr(path),
                                  leaf.shape, spec)


@pytest.mark.parametrize("arch", ALL_ARCHS)
@pytest.mark.parametrize("multipod", [False, True])
def test_train_state_specs_divisible(arch, multipod):
    cfg = S.dryrun_model_config(get_config(arch))
    model = Model(cfg, remat=True)
    W = 32 if multipod else 16
    axes = AXES_MULTIPOD if multipod else AXES_POD
    dc_cfg = DCS3GDConfig()
    alg = registry.make("dc_s3gd", dc_cfg, n_workers=W)
    state = S.abstract_train_state(model, W, dc_cfg, alg)
    spec = alg.state_specs(cfg, state, axes)
    _check_divisible(state.params, spec.params, f"{arch}.params")
    _check_divisible(state.comm["delta_prev"], spec.comm["delta_prev"],
                     f"{arch}.delta")
    # worker axis present on every param leaf
    for sp in jax.tree.leaves(spec.params,
                              is_leaf=lambda x: isinstance(x, P)):
        assert tuple(sp)[0] == axes.worker_spec, sp


@pytest.mark.parametrize("arch", ALL_ARCHS)
@pytest.mark.parametrize("shape_name", ["prefill_32k", "decode_32k",
                                        "long_500k"])
def test_serve_specs_divisible(arch, shape_name):
    shape = INPUT_SHAPES[shape_name]
    cfg0 = get_config(arch)
    ok, why = S.supports_shape(cfg0, shape)
    if not ok:
        pytest.skip(why)
    cfg = S.variant_for_shape(S.dryrun_model_config(cfg0), shape)
    model = Model(cfg, remat=False)
    params = S.abstract_params(model)
    pspec = param_specs(cfg, params, model_size=16, worker_axes=None)
    _check_divisible(params, pspec, f"{arch}.serve_params")
    if shape.kind == "decode":
        cache = S.abstract_cache(model, shape)
        da = "data" if shape.global_batch % 16 == 0 else None
        cspec = cache_specs(cfg, cache, model_size=16,
                            data_axes=da)
        _check_divisible(cache, cspec, f"{arch}.cache")


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_batch_specs_divisible(arch):
    cfg = S.dryrun_model_config(get_config(arch))
    shape = INPUT_SHAPES["train_4k"]
    alg = registry.make("dc_s3gd", DCS3GDConfig(), n_workers=16)
    batch = S.train_batch_specs(cfg, shape, 16)
    spec = alg.batch_specs(cfg, batch, AXES_POD)
    _check_divisible(batch, spec, f"{arch}.batch")


def test_head_padding_only_when_needed():
    for arch in ALL_ARCHS:
        cfg = S.dryrun_model_config(get_config(arch))
        if cfg.n_heads:
            assert cfg.eff_n_heads % 16 == 0, arch
            assert cfg.eff_n_heads - cfg.n_heads < 16, arch


def test_small_mesh_end_to_end_jit():
    """Actually run one sharded DC-S3GD step on a 1x1 mesh (the only real
    device) through the Engine — validates that the hook-derived sharding
    trees agree with the jit API end to end."""
    from repro.configs import reduced
    from repro.launch.engine import Engine
    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg, remat=False, scan_chunk=8, loss_chunk=8)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    dc_cfg = DCS3GDConfig(learning_rate=0.01)
    alg = registry.make("dc_s3gd", dc_cfg, n_workers=2)
    engine = Engine(model, alg, mesh=mesh)
    state = alg.init(model.init(jax.random.PRNGKey(0)))
    batch = {
        "tokens": jnp.zeros((2, 2, 16), jnp.int32),
        "labels": jnp.zeros((2, 2, 16), jnp.int32),
    }
    step = engine.jit_train_step(state, batch, donate=False)
    state2, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
