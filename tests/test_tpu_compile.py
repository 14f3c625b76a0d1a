"""Compile the main path for a described TPU v5e chip — no chip attached.

The TPU compiler ships with jax; it compiles for a topology that is only
described (``jax.experimental.topologies``) and refuses what the chip
would refuse: a Pallas block that breaks the (8, 128) tiling, a scalar
store to VMEM, a program larger than HBM.  Interpret mode (every other
kernel test) sees none of that.  Each test compiles one kernel at the
widths the launchers use, or the full-width qwen3-0.6b train step, and
checks that the kernel lowered to ``tpu_custom_call``; nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import registry
from repro.core.types import DCS3GDConfig
from repro.kernels import compress as KC
from repro.kernels import dc_update as K
from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention
from repro.launch.engine import Engine
from repro.models.transformer import Model

ARCH = "qwen3-0.6b"
# HBM a v5e chip reports as ``memory_stats()["bytes_limit"]`` (15.75 GiB)
V5E_HBM_BYTES = 16_909_336_064


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_dc_norms_compiles(one_chip):
    x = _shape((4 * K.ROWS, K.LANES), jnp.float32, one_chip)
    _compile(K.dc_norms, x, x)


@pytest.mark.parametrize("w_dtype", [jnp.float32, jnp.bfloat16])
def test_dc_fused_update_compiles(one_chip, w_dtype):
    x = _shape((4 * K.ROWS, K.LANES), jnp.float32, one_chip)
    w = _shape(x.shape, w_dtype, one_chip)
    s = _shape((), jnp.float32, one_chip)

    def update(g, d, m, w, lam):
        return K.dc_fused_update(g, d, m, w, lam=lam, mu=0.9, eta=0.05,
                                 wd=1e-4)
    _compile(update, x, x, x, w, s)


@pytest.mark.parametrize("comm_dtype", ["float32", "int8"])
def test_select_ef_mean_compiles(one_chip, comm_dtype):
    W = 4
    a = _shape((W, 4 * K.BLOCK), jnp.float32, one_chip)
    t = _shape((W, 1), jnp.float32, one_chip)
    _compile(lambda a, t: KC.select_ef_mean(
        a, t, comm_dtype=comm_dtype, union=False, interpret=False), a, t)


@pytest.mark.parametrize("kv_dtype,page_size", [(jnp.bfloat16, 16),
                                                (jnp.int8, 32)])
def test_paged_attention_compiles(one_chip, kv_dtype, page_size):
    """One decode step of the paged kernel at qwen3-0.6b's head layout
    (8 KV heads, 2 query heads each, head_dim 128) over 8 rows of 256
    cached tokens."""
    cfg = get_config(ARCH)
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    G = cfg.n_heads // KV
    B, max_pages = 8, 256 // page_size
    n_pages = B * max_pages + 1
    q = _shape((B, KV, G, hd), jnp.bfloat16, one_chip)
    pool = _shape((n_pages, page_size, KV, hd), kv_dtype, one_chip)
    bt = _shape((B, max_pages), jnp.int32, one_chip)
    lengths = _shape((B,), jnp.int32, one_chip)
    args = [q, pool, pool, bt, lengths]
    if kv_dtype == jnp.int8:
        scale = _shape((n_pages, page_size), jnp.float32, one_chip)
        args += [scale, scale]

    def attend(q, k, v, bt, lengths, k_scale=None, v_scale=None):
        return paged_attention(q, k, v, bt, lengths, k_scale=k_scale,
                               v_scale=v_scale, interpret=False)
    _compile(attend, *args)


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["xla_tail", "fused_tail"])
def test_train_step_fits_one_chip(one_chip, monkeypatch, use_kernels):
    """The W=1 DC-S3GD step of ``python -m repro.launch.train --arch
    qwen3-0.6b --workers 1 --batch-per-worker 4 --seq 512`` (and with
    ``--use-kernels --buckets 8``) at the published widths: the fused
    tail lowers to Pallas kernels, and the compiler's peak for the whole
    step fits one chip's HBM."""
    # the CPU process would pick interpret mode; this program is for the TPU
    monkeypatch.setattr(ops, "_is_cpu", lambda: False)
    cfg = get_config(ARCH)
    # the trainer's model (launch/train.py `run`)
    model = Model(cfg, remat=True, q_chunk=64, kv_chunk=64, scan_chunk=64,
                  loss_chunk=256)
    dc = DCS3GDConfig()
    alg = registry.make("dc_s3gd", dc, n_workers=1,
                        reducer=registry.make_reducer("mean_allreduce", dc),
                        use_kernels=use_kernels,
                        buckets=8 if use_kernels else 0)
    engine = Engine(model, alg)

    def place(tree):
        return jax.tree.map(lambda a: _shape(a.shape, a.dtype, one_chip),
                            tree)
    state = place(jax.eval_shape(engine.init_state, jax.random.PRNGKey(0)))
    tokens = _shape((1, 4, 512), jnp.int32, one_chip)
    batch = {"tokens": tokens, "labels": tokens}
    compiled = engine.lower_train_step(state, batch).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernels
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak <= V5E_HBM_BYTES, f"{peak / 2**30:.2f} GiB"
