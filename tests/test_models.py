"""Per-architecture smoke tests (reduced variants: 2 layers, d<=512,
<=4 experts) + decode-vs-forward consistency + component oracles."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

from repro.configs import ARCHS, get_config, reduced
from repro.core import registry
from repro.core.types import DCS3GDConfig, MoEConfig, RGLRUConfig, SSMConfig
from repro.models import attention, moe as moe_mod, rglru, ssm
from repro.models.transformer import Model, chunked_xent

from helpers import ALL_ARCHS, make_lm_batch


def _model(cfg, **kw):
    kw.setdefault("remat", False)
    kw.setdefault("scan_chunk", 8)
    kw.setdefault("loss_chunk", 8)
    return Model(cfg, **kw)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_train_step(arch):
    """Instantiate the reduced family variant, run one forward and one
    DC-S3GD train step: shapes correct, loss finite, params move."""
    cfg = reduced(get_config(arch))
    m = _model(cfg, moe_dense=True)
    params = m.init(random.PRNGKey(0))
    batch = make_lm_batch(cfg, B=2, S=16)

    logits = m.logits(params, {k: v for k, v in batch.items()
                               if k != "labels"})
    S_total = 16 + (cfg.vlm.n_patches if cfg.vlm else 0)
    assert logits.shape == (2, S_total, m.vocab_padded)
    assert bool(jnp.isfinite(logits).all())

    dc_cfg = DCS3GDConfig(learning_rate=0.01, momentum=0.9,
                          weight_decay=1e-4)
    W = 2
    alg = registry.make("dc_s3gd", dc_cfg, n_workers=W)
    state = alg.init(params)
    wbatch = {k: jnp.stack([v, v]) for k, v in batch.items()}
    state2, metrics = alg.step(state, wbatch, loss_fn=m.loss)
    assert bool(jnp.isfinite(metrics["loss"]))
    moved = any(not jnp.allclose(a, b) for a, b in
                zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(state2.params)))
    assert moved


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_decode_continuation_matches_forward(arch):
    """prefill(S) + decode_step == forward(S+1) last logits, per arch."""
    cfg = reduced(get_config(arch))
    m = _model(cfg, moe_dense=True)
    params = m.init(random.PRNGKey(1))
    B, S = 2, 8
    batch = make_lm_batch(cfg, B=B, S=S + 1, with_labels=False)
    full = m.logits(params, batch)
    offset = cfg.vlm.n_patches if cfg.vlm is not None else 0

    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :S]
    if "mrope_positions" in pre:
        pre["mrope_positions"] = batch["mrope_positions"][:, :S + offset]
    last, cache = m.prefill(params, pre, cache_len=S + 4 + offset)
    np.testing.assert_allclose(last, full[:, S + offset - 1], atol=1e-4)

    step = {"tokens": batch["tokens"][:, S:S + 1],
            "pos": jnp.int32(S + offset)}
    if cfg.vlm is not None:
        step["mrope_positions"] = jnp.full((3, 1), S + offset)
    lg, _ = m.decode_step(params, cache, step)
    np.testing.assert_allclose(lg, full[:, -1], atol=1e-4)


def test_sliding_window_ring_cache_decode():
    """Dense arch with sliding window: ring cache decode matches the full
    forward with the same window mask, beyond one wrap of the ring."""
    cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b")),
                              sliding_window=4)
    m = _model(cfg)
    params = m.init(random.PRNGKey(2))
    B, S = 1, 12
    toks = random.randint(random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
    full = m.logits(params, {"tokens": toks})
    cache = m.init_cache(B, cache_len=S)  # ring buffers sized min(window, S)
    for t in range(S):
        lg, cache = m.decode_step(params, cache,
                                  {"tokens": toks[:, t:t + 1],
                                   "pos": jnp.int32(t)})
    np.testing.assert_allclose(lg, full[:, -1], atol=1e-4)


def test_moe_ep_matches_dense_oracle_with_capacity():
    mo = MoEConfig(n_experts=4, top_k=2, d_ff_expert=32)
    p = moe_mod.init_moe(random.PRNGKey(0), 64, mo, True, jnp.float32)
    x = random.normal(random.PRNGKey(1), (2, 9, 64))
    o1, a1 = moe_mod.moe_ffn(p, x, mo, "silu", capacity_factor=4.0)
    o2, a2 = moe_mod.moe_ffn_dense(p, x, mo, "silu")
    np.testing.assert_allclose(o1, o2, atol=1e-4)
    np.testing.assert_allclose(a1, a2, rtol=1e-5)


def test_moe_dropless_mode():
    mo = MoEConfig(n_experts=4, top_k=2, d_ff_expert=16)
    p = moe_mod.init_moe(random.PRNGKey(0), 32, mo, True, jnp.float32)
    x = random.normal(random.PRNGKey(1), (1, 3, 32))
    o1, _ = moe_mod.moe_ffn(p, x, mo, "silu", capacity_factor=-1.0)
    o2, _ = moe_mod.moe_ffn_dense(p, x, mo, "silu")
    np.testing.assert_allclose(o1, o2, atol=1e-4)


def test_moe_capacity_drops_tokens():
    """With capacity factor << 1 some (token, expert) pairs must drop —
    outputs differ from dropless but stay finite."""
    mo = MoEConfig(n_experts=4, top_k=2, d_ff_expert=16)
    p = moe_mod.init_moe(random.PRNGKey(0), 32, mo, True, jnp.float32)
    x = random.normal(random.PRNGKey(1), (2, 16, 32))
    lo, _ = moe_mod.moe_ffn(p, x, mo, "silu", capacity_factor=0.25)
    hi, _ = moe_mod.moe_ffn(p, x, mo, "silu", capacity_factor=-1.0)
    assert bool(jnp.isfinite(lo).all())
    assert not bool(jnp.allclose(lo, hi))


def test_mamba_chunked_scan_vs_naive():
    sc = SSMConfig()
    p = ssm.init_mamba(random.PRNGKey(0), 32, sc, jnp.float32)
    x = random.normal(random.PRNGKey(1), (2, 13, 32))
    y8 = ssm.mamba_forward(p, x, sc, chunk=8)
    y4 = ssm.mamba_forward(p, x, sc, chunk=4)
    y13 = ssm.mamba_forward(p, x, sc, chunk=13)
    np.testing.assert_allclose(y8, y4, atol=1e-5)
    np.testing.assert_allclose(y8, y13, atol=1e-5)


def test_rglru_stability_long_sequence():
    """RG-LRU gates keep the state bounded over a long sequence."""
    rc = RGLRUConfig(lru_width=16)
    p = rglru.init_rglru_block(random.PRNGKey(0), 16, rc, jnp.float32)
    x = random.normal(random.PRNGKey(1), (1, 512, 16))
    y = rglru.rglru_forward(p, x, rc, chunk=64)
    assert bool(jnp.isfinite(y).all())
    assert float(jnp.max(jnp.abs(y))) < 1e3


def test_chunked_xent_matches_direct():
    V, d, B, S = 50, 16, 2, 12
    ks = random.split(random.PRNGKey(0), 3)
    x = random.normal(ks[0], (B, S, d))
    un = random.normal(ks[1], (d, V))
    labels = random.randint(ks[2], (B, S), 0, V)
    labels = labels.at[0, :3].set(-1)  # masked positions
    got = chunked_xent(x, un, labels, chunk=5)
    logits = (x @ un).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    gold = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    mask = labels >= 0
    expected = -(gold * mask).sum() / mask.sum()
    np.testing.assert_allclose(got, expected, rtol=1e-5)


def test_vocab_padding_masks_pad_logits():
    cfg = dataclasses.replace(reduced(get_config("qwen3-0.6b")),
                              vocab_size=500)  # pads to 512
    m = _model(cfg)
    assert m.vocab_padded == 512
    params = m.init(random.PRNGKey(0))
    toks = random.randint(random.PRNGKey(1), (1, 4), 0, 500)
    lg = m.logits(params, {"tokens": toks})
    assert bool((lg[..., 500:] < -1e29).all())


@pytest.mark.parametrize("entry", ["loss", "prefill"])
def test_published_config_traces_in_mixed_precision(entry):
    """The published qwen3-0.6b keeps f32 parameters and computes in
    bf16.  Each entry point casts the parameters to the compute dtype
    once, so the per-layer scan carry keeps one dtype and the full-width
    program traces (shapes only: nothing is allocated)."""
    cfg = get_config("qwen3-0.6b")
    m = _model(cfg, remat=True, scan_chunk=64, loss_chunk=256)
    params = jax.eval_shape(m.init, random.PRNGKey(0))
    assert {x.dtype for x in jax.tree.leaves(params)} == {jnp.dtype("float32")}
    assert m.compute_dtype == jnp.bfloat16
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    if entry == "loss":
        out = jax.eval_shape(m.loss, params,
                             {"tokens": tokens, "labels": tokens})
        assert out.shape == ()
    else:
        out, _ = jax.eval_shape(lambda p, b: m.prefill(p, b, cache_len=256),
                                params, {"tokens": tokens})
        assert out.shape == (2, m.vocab_padded)
