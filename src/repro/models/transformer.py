"""Unified model assembly for every assigned architecture.

A model is a sequence of *stages*; each stage is a repeated homogeneous
*unit* of one or more blocks, scanned with ``jax.lax.scan`` over stacked
parameters (keeps the HLO size O(1) in depth — essential for 62-layer
configs at 512-device GSPMD compile).  Hybrid architectures (recurrentgemma)
use a multi-block unit ``(recurrent, recurrent, attention)``; the
non-divisible remainder becomes a trailing stage.

Three execution paths share the same parameters:
  * ``loss(params, batch)``      — training objective (chunked xent + MoE aux)
  * ``prefill(params, batch)``   — full-sequence forward that also emits the
    KV/recurrent cache and last-position logits
  * ``decode_step(params, cache, batch)`` — one token, cache update

Block kinds: ``attention`` (GQA / qk-norm / M-RoPE / sliding window,
dense-or-MoE FFN), ``mla`` (MiniCPM3), ``mamba`` (falcon-mamba),
``recurrent`` (RG-LRU + MLP), ``cross`` (whisper decoder: self+cross+MLP).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import random

from repro.core.types import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (causal_conv1d, dense_init, embed_init,
                                 init_layernorm, init_mlp, init_rmsnorm,
                                 layernorm, mlp, rmsnorm,
                                 sinusoidal_positions)

PyTree = Any


def _seq_constrain(x):
    """Megatron-style sequence parallelism for the residual stream: the
    scan-carried (and remat-saved) activations are sharded over 'model' on
    the sequence dim; GSPMD inserts the all-gather at the first
    seq-global consumer (attention/matmul) and a reduce-scatter after.
    Cuts the remat-saved (L, B, S, d) stack by the model-axis size (the
    dominant XLA temp for the big dense configs — see EXPERIMENTS.md §Perf
    H3).  No-op without an ambient mesh (CPU tests) or when S doesn't
    divide."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return x
    if x.shape[-2] % mesh.shape["model"]:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(None, "model", None))


# ---------------------------------------------------------------------------
# stage plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Stage:
    kinds: Tuple[str, ...]  # block kinds within one unit
    repeats: int


def stage_plan(cfg: ModelConfig) -> List[Stage]:
    if cfg.family == "ssm":
        return [Stage(("mamba",), cfg.n_layers)]
    if cfg.family == "hybrid":
        pattern = cfg.rglru.block_pattern
        unit = tuple("recurrent" if p == "recurrent" else "attention_local"
                     for p in pattern)
        n_units, rem = divmod(cfg.n_layers, len(pattern))
        stages = [Stage(unit, n_units)]
        if rem:
            stages.append(Stage(unit[:rem], 1))
        return stages
    if cfg.family == "encdec":
        return [Stage(("cross",), cfg.n_layers)]
    kind = "mla" if cfg.mla is not None else "attention"
    return [Stage((kind,), cfg.n_layers)]


# ---------------------------------------------------------------------------
# norm dispatch
# ---------------------------------------------------------------------------


def _init_norm(cfg, d, dtype):
    return init_layernorm(d, dtype) if cfg.norm == "layernorm" else init_rmsnorm(d, dtype)


def _norm(cfg, p, x):
    if cfg.norm == "layernorm":
        return layernorm(p, x, cfg.norm_eps)
    return rmsnorm(p, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# block init
# ---------------------------------------------------------------------------


def _init_block(key, kind: str, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = random.split(key, 4)
    p: dict = {}
    if kind in ("attention", "attention_local", "cross"):
        p["ln1"] = _init_norm(cfg, d, dtype)
        p["attn"] = attn.init_attention(ks[0], d, cfg.eff_n_heads,
                                        cfg.eff_n_kv_heads,
                                        hd, cfg.qk_norm, dtype)
        if kind == "cross":
            p["ln_x"] = _init_norm(cfg, d, dtype)
            p["xattn"] = attn.init_cross_attention(ks[2], d, cfg.eff_n_heads, hd,
                                                   dtype)
        p["ln2"] = _init_norm(cfg, d, dtype)
        if cfg.moe is not None and kind != "cross":
            p["moe"] = moe_mod.init_moe(ks[1], d, cfg.moe, cfg.mlp_gated, dtype)
        else:
            p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_gated, dtype)
    elif kind == "mla":
        p["ln1"] = _init_norm(cfg, d, dtype)
        p["attn"] = attn.init_mla(ks[0], d, cfg.eff_n_heads, cfg.mla, dtype)
        p["ln2"] = _init_norm(cfg, d, dtype)
        p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_gated, dtype)
    elif kind == "mamba":
        p["ln"] = _init_norm(cfg, d, dtype)
        p["mamba"] = ssm_mod.init_mamba(ks[0], d, cfg.ssm, dtype)
    elif kind == "recurrent":
        p["ln1"] = _init_norm(cfg, d, dtype)
        p["rglru"] = rglru_mod.init_rglru_block(ks[0], d, cfg.rglru, dtype)
        p["ln2"] = _init_norm(cfg, d, dtype)
        p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_gated, dtype)
    else:
        raise ValueError(kind)
    return p


# ---------------------------------------------------------------------------
# block apply — full sequence (train / prefill)
# ---------------------------------------------------------------------------


def _apply_block(kind: str, cfg: ModelConfig, p: dict, x, ctx: dict,
                 collect_cache: bool):
    """Returns (x, aux_loss, cache_entry_or_None)."""
    aux = jnp.zeros((), jnp.float32)
    cache_entry = None
    positions = ctx["positions"]
    window = cfg.sliding_window
    if kind == "attention_local":
        window = cfg.rglru.attention_window

    # the `attn` and `mlp` scopes name the ops of each half of a block
    # (bench/scopes.py charges device time to them); the residual adds
    # stay outside, with the layer scan's own ops under `layers`
    if kind in ("attention", "attention_local", "cross", "mla"):
        with jax.named_scope("attn"):
            h = _norm(cfg, p["ln1"], x)
            if kind == "mla":
                h = attn.mla_train(p["attn"], h, positions, mla_cfg=cfg.mla,
                                   rope_theta=cfg.rope_theta,
                                   norm_eps=cfg.norm_eps,
                                   q_chunk=ctx["q_chunk"],
                                   kv_chunk=ctx["kv_chunk"])
                if collect_cache:
                    cache_entry = _mla_cache_from_seq(p, cfg, x, positions,
                                                      ctx)
            else:
                h = attn.attention_train(
                    p["attn"], h, positions, rope_theta=cfg.rope_theta,
                    window=window, qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps,
                    mrope_positions=ctx.get("mrope_positions"),
                    mrope_sections=cfg.vlm.mrope_sections if cfg.vlm
                    else None,
                    q_chunk=ctx["q_chunk"], kv_chunk=ctx["kv_chunk"])
                if collect_cache:
                    cache_entry = _kv_cache_from_seq(
                        p, cfg, _norm(cfg, p["ln1"], x), positions, window,
                        ctx)
        x = x + h
        if kind == "cross":
            with jax.named_scope("attn"):
                h = _norm(cfg, p["ln_x"], x)
                enc = ctx["encoder_out"]
                xk = jnp.einsum("bsd,dhk->bshk", enc, p["xattn"]["wk"])
                xv = jnp.einsum("bsd,dhk->bshk", enc, p["xattn"]["wv"])
                h = attn.attention_train(p["xattn"], h, positions,
                                         rope_theta=0.0, causal=False,
                                         kv_override=(xk, xv),
                                         q_chunk=ctx["q_chunk"],
                                         kv_chunk=ctx["kv_chunk"])
            x = x + h
            if collect_cache:
                cache_entry = dict(cache_entry or {}, xk=xk, xv=xv)
        with jax.named_scope("mlp"):
            h = _norm(cfg, p["ln2"], x)
            if "moe" in p:
                h, aux = moe_mod.moe_ffn(p["moe"], h, cfg.moe,
                                         cfg.activation) \
                    if not ctx.get("moe_dense") else \
                    moe_mod.moe_ffn_dense(p["moe"], h, cfg.moe,
                                          cfg.activation)
            else:
                h = mlp(p["mlp"], h, cfg.activation)
        x = x + h
    elif kind == "mamba":
        h = _norm(cfg, p["ln"], x)
        if collect_cache:
            h, cache_entry = _mamba_with_state(p["mamba"], h, cfg.ssm, ctx)
        else:
            h = ssm_mod.mamba_forward(p["mamba"], h, cfg.ssm, chunk=ctx["scan_chunk"])
        x = x + h
    elif kind == "recurrent":
        h = _norm(cfg, p["ln1"], x)
        if collect_cache:
            h, cache_entry = _rglru_with_state(p["rglru"], h, cfg.rglru, ctx)
        else:
            h = rglru_mod.rglru_forward(p["rglru"], h, cfg.rglru,
                                        chunk=ctx["scan_chunk"])
        x = x + h
        with jax.named_scope("mlp"):
            h = _norm(cfg, p["ln2"], x)
            h = mlp(p["mlp"], h, cfg.activation)
        x = x + h
    else:
        raise ValueError(kind)
    return x, aux, cache_entry


# ---- prefill cache builders ----


def _kv_cache_from_seq(p, cfg, h, positions, window, ctx):
    """Recompute (roped, normed) k/v for the whole sequence and lay them out
    exactly as the decode ring/linear cache expects."""
    k = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"])
    if cfg.qk_norm:
        k = rmsnorm(p["attn"]["k_norm"], k, cfg.norm_eps)
    if cfg.rope_theta > 0:
        if ctx.get("mrope_positions") is not None:
            k = attn.apply_mrope(k, ctx["mrope_positions"], cfg.rope_theta,
                                 cfg.vlm.mrope_sections)
        else:
            k = attn.apply_rope(k, positions, cfg.rope_theta)
    S = k.shape[1]
    cache_len = ctx["cache_len"]
    if window > 0:
        w = min(window, cache_len)
        # keep last w positions, placed at slot pos % w
        ks_, vs_ = k[:, -w:], v[:, -w:]
        pos_tail = positions[-w:]
        slots = pos_tail % w
        kc = jnp.zeros((k.shape[0], w) + k.shape[2:], k.dtype).at[:, slots].set(ks_)
        vc = jnp.zeros((v.shape[0], w) + v.shape[2:], v.dtype).at[:, slots].set(vs_)
        return {"k": kc, "v": vc}
    pad = cache_len - S
    kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": kc, "v": vc}


def _mla_cache_from_seq(p, cfg, x, positions, ctx):
    h = _norm(cfg, p["ln1"], x)
    m = cfg.mla
    ckv = rmsnorm(p["attn"]["kv_norm"], h @ p["attn"]["w_dkv"], cfg.norm_eps)
    k_rope = attn.apply_rope((h @ p["attn"]["w_kr"])[:, :, None, :], positions,
                             cfg.rope_theta)[:, :, 0]
    pad = ctx["cache_len"] - ckv.shape[1]
    return {
        "ckv": jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
        "k_rope": jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))),
    }


def _mamba_with_state(p, h, ssm_cfg, ctx):
    y, state = ssm_mod.mamba_forward(p, h, ssm_cfg, chunk=ctx["scan_chunk"],
                                     return_state=True)
    # conv state stores the raw (pre-conv) inputs of the last K-1 positions
    xz = h @ p["w_in"]
    xi = jnp.split(xz, 2, axis=-1)[0]
    conv = xi[:, -(ssm_cfg.conv_kernel - 1):, :].astype(h.dtype)
    return y, {"conv": conv, "ssm": state}


def _rglru_with_state(p, h, rcfg, ctx):
    y = rglru_mod.rglru_forward(p, h, rcfg, chunk=ctx["scan_chunk"])
    xi = h @ p["w_x"]
    conv = xi[:, -(rcfg.conv_kernel - 1):, :].astype(h.dtype)
    xi_c = causal_conv1d(xi, p["conv_w"], p["conv_b"])
    a, bx = rglru_mod._gates(p, xi_c)
    S_len = h.shape[1]
    chunk = ctx["scan_chunk"]
    pad = (-S_len) % chunk
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        bx = jnp.pad(bx, ((0, 0), (0, pad), (0, 0)))
    _, h_last = ssm_mod._ssm_scan_chunked(
        a[..., None], bx[..., None],
        jnp.zeros((h.shape[0], a.shape[-1], 1), jnp.float32), chunk)
    return y, {"conv": conv, "h": h_last[..., 0]}


# ---------------------------------------------------------------------------
# block apply — decode (one token, cache)
# ---------------------------------------------------------------------------


def _decode_block(kind: str, cfg: ModelConfig, p: dict, cache: dict, x, ctx):
    pos = ctx["pos"]
    cache_ops = ctx.get("cache_ops")
    window = cfg.sliding_window
    if kind == "attention_local":
        window = cfg.rglru.attention_window
    if kind in ("attention", "attention_local", "cross"):
        h = _norm(cfg, p["ln1"], x)
        # the self-attention k/v pools plus their per-token scale pools
        # when the paged layout quantizes pages (cross xk/xv stay out)
        self_c = {kk: cache[kk] for kk in ("k", "v", "k_scale", "v_scale")
                  if kk in cache}
        h, new_self = attn.attention_decode(
            p["attn"], self_c, h, pos,
            rope_theta=cfg.rope_theta, window=window, qk_norm=cfg.qk_norm,
            norm_eps=cfg.norm_eps,
            mrope_positions=ctx.get("mrope_positions"),
            mrope_sections=cfg.vlm.mrope_sections if cfg.vlm else None,
            cache_ops=cache_ops)
        x = x + h
        new_cache = dict(cache, **new_self)
        if kind == "cross":
            h = _norm(cfg, p["ln_x"], x)
            h, _ = attn.attention_decode(
                p["xattn"], {"k": cache["xk"], "v": cache["xv"]}, h, pos,
                rope_theta=0.0, cross=True)
            x = x + h
        h = _norm(cfg, p["ln2"], x)
        if "moe" in p:
            if ctx.get("moe_dense"):
                h, _ = moe_mod.moe_ffn_dense(p["moe"], h, cfg.moe, cfg.activation)
            else:  # dropless EP dispatch at decode (drops corrupt generation)
                h, _ = moe_mod.moe_ffn(p["moe"], h, cfg.moe, cfg.activation,
                                       capacity_factor=-1.0)
        else:
            h = mlp(p["mlp"], h, cfg.activation)
        x = x + h
        return x, new_cache
    if kind == "mla":
        h = _norm(cfg, p["ln1"], x)
        h, new_cache = attn.mla_decode(p["attn"], cache, h, pos, mla_cfg=cfg.mla,
                                       rope_theta=cfg.rope_theta,
                                       norm_eps=cfg.norm_eps,
                                       cache_ops=cache_ops)
        x = x + h
        h = _norm(cfg, p["ln2"], x)
        x = x + mlp(p["mlp"], h, cfg.activation)
        return x, new_cache
    if kind == "mamba":
        h = _norm(cfg, p["ln"], x)
        h, new_cache = ssm_mod.mamba_decode(p["mamba"], cache, h, cfg.ssm)
        return x + h, new_cache
    if kind == "recurrent":
        h = _norm(cfg, p["ln1"], x)
        h, new_cache = rglru_mod.rglru_decode(p["rglru"], cache, h, cfg.rglru)
        x = x + h
        h = _norm(cfg, p["ln2"], x)
        x = x + mlp(p["mlp"], h, cfg.activation)
        return x, new_cache
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# cache init (shapes only — decode starts from a prefilled or zero cache)
# ---------------------------------------------------------------------------


def _init_block_cache(kind: str, cfg: ModelConfig, batch: int, cache_len: int,
                      dtype):
    window = cfg.sliding_window
    if kind == "attention_local":
        window = cfg.rglru.attention_window
    if kind in ("attention", "attention_local", "cross"):
        eff = min(window, cache_len) if window > 0 else cache_len
        c = attn.init_kv_cache(batch, eff, cfg.eff_n_kv_heads,
                               cfg.resolved_head_dim, dtype)
        if kind == "cross":
            nf = cfg.encoder.n_frames
            c["xk"] = jnp.zeros((batch, nf, cfg.eff_n_heads,
                                 cfg.resolved_head_dim), dtype)
            c["xv"] = jnp.zeros((batch, nf, cfg.eff_n_heads,
                                 cfg.resolved_head_dim), dtype)
        return c
    if kind == "mla":
        return attn.init_mla_cache(batch, cache_len, cfg.mla, dtype)
    if kind == "mamba":
        return ssm_mod.init_mamba_state(batch, cfg.d_model, cfg.ssm, dtype)
    if kind == "recurrent":
        return rglru_mod.init_rglru_state(batch, cfg.d_model, cfg.rglru, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class Model:
    """Functional model wrapper: all methods are pure and jit/vmap friendly."""

    def __init__(self, cfg: ModelConfig, *, remat: bool = True,
                 moe_dense: bool = False, q_chunk: int = 512,
                 kv_chunk: int = 1024, scan_chunk: int = 256,
                 loss_chunk: int = 2048, seq_parallel: bool = False):
        self.cfg = cfg
        self.remat = remat
        self.moe_dense = moe_dense
        self.seq_parallel = seq_parallel
        self.q_chunk = q_chunk
        self.kv_chunk = kv_chunk
        self.scan_chunk = scan_chunk
        self.loss_chunk = loss_chunk
        self.stages = stage_plan(cfg)
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)
        # pad vocab to a multiple of 256 so the embedding/unembedding shard
        # evenly over any reasonable 'model' axis (MaxText-style padding;
        # logits for pad ids are masked at decode time)
        self.vocab_padded = -(-cfg.vocab_size // 256) * 256

    # -------------------------------------------------- init

    def init(self, key) -> PyTree:
        cfg = self.cfg
        dtype = self.param_dtype
        keys = random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": {"tok": embed_init(keys[0],
                                        (self.vocab_padded, cfg.d_model),
                                        dtype)},
            "final_norm": _init_norm(cfg, cfg.d_model, dtype),
            "unembed": dense_init(keys[1], (cfg.d_model, self.vocab_padded),
                                  dtype),
        }
        if cfg.vlm is not None:
            params["vision_proj"] = dense_init(keys[5], (cfg.d_model, cfg.d_model),
                                               dtype)
        for si, stage in enumerate(self.stages):
            def init_unit(k):
                uks = random.split(k, len(stage.kinds))
                return {f"b{j}": _init_block(uks[j], kind, cfg, dtype)
                        for j, kind in enumerate(stage.kinds)}
            stage_keys = random.split(random.fold_in(keys[2], si), stage.repeats)
            params[f"stage{si}"] = jax.vmap(init_unit)(stage_keys)
        if cfg.encoder is not None:
            enc_keys = random.split(keys[3], cfg.encoder.n_layers)

            def init_enc(k):
                return _init_block(k, "attention", dataclasses.replace(
                    cfg, moe=None, qk_norm=False), dtype)
            params["encoder"] = {
                "blocks": jax.vmap(init_enc)(enc_keys),
                "final_norm": _init_norm(cfg, cfg.d_model, dtype),
            }
        return params

    # -------------------------------------------------- shared pieces

    def _cast(self, params) -> PyTree:
        """The floating parameters at ``compute_dtype``: each entry point
        runs on this one cast copy, so the f32 master weights (and the
        optimizer state built on them) keep their dtype while every
        matmul and scan carry stays at the compute dtype.  A no-op when
        the two dtypes agree."""
        dt = self.compute_dtype

        def cast(p):
            if jnp.issubdtype(p.dtype, jnp.floating) and p.dtype != dt:
                return p.astype(dt)
            return p
        return jax.tree.map(cast, params)

    def _ctx(self, S, extra=None):
        ctx = {
            "q_chunk": min(self.q_chunk, S),
            "kv_chunk": min(self.kv_chunk, S),
            "scan_chunk": min(self.scan_chunk, S),
            "moe_dense": self.moe_dense,
        }
        if extra:
            ctx.update(extra)
        return ctx

    def _embed(self, params, batch):
        cfg = self.cfg
        x = jnp.take(params["embed"]["tok"], batch["tokens"], axis=0)
        x = x.astype(self.compute_dtype)
        if cfg.vlm is not None and "patches" in batch:
            pe = (batch["patches"].astype(self.compute_dtype)
                  @ params["vision_proj"].astype(self.compute_dtype))
            x = jnp.concatenate([pe, x], axis=1)
        if cfg.rope_theta == 0.0:  # absolute positions (whisper decoder)
            x = x + sinusoidal_positions(x.shape[1], cfg.d_model
                                         ).astype(x.dtype)[None]
        return x

    def _encoder_out(self, params, frames):
        """Whisper encoder over precomputed (stub) frame embeddings."""
        cfg = self.cfg
        x = frames.astype(self.compute_dtype)
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model
                                     ).astype(self.compute_dtype)[None]
        positions = jnp.arange(x.shape[1])
        ctx = self._ctx(x.shape[1])
        ctx["positions"] = positions
        # encoder attention is bidirectional; inline the unit here
        def bidir_body(carry, p):
            h = _norm(cfg, p["ln1"], carry)
            h = attn.attention_train(p["attn"], h, positions,
                                     rope_theta=0.0, causal=False,
                                     q_chunk=ctx["q_chunk"],
                                     kv_chunk=ctx["kv_chunk"])
            carry = carry + h
            h = _norm(cfg, p["ln2"], carry)
            carry = carry + mlp(p["mlp"], h, cfg.activation)
            return carry, None

        fn = jax.checkpoint(bidir_body) if self.remat else bidir_body
        x, _ = jax.lax.scan(fn, x, params["encoder"]["blocks"])
        return _norm(cfg, params["encoder"]["final_norm"], x)

    def _backbone(self, params, x, ctx, collect_cache: bool):
        """Run all stages; returns (x, aux_sum, caches or None)."""
        aux_total = jnp.zeros((), jnp.float32)
        caches = [] if collect_cache else None
        for si, stage in enumerate(self.stages):
            def unit_body(carry, p, _stage=stage):
                h, aux_c = carry
                if self.seq_parallel:
                    h = _seq_constrain(h)
                entries = {}
                for j, kind in enumerate(_stage.kinds):
                    h, aux, ce = _apply_block(kind, self.cfg, p[f"b{j}"], h,
                                              ctx, collect_cache)
                    aux_c = aux_c + aux
                    if collect_cache:
                        entries[f"b{j}"] = ce
                return (h, aux_c), (entries if collect_cache else None)

            fn = jax.checkpoint(unit_body) if self.remat else unit_body
            # `layers`: what the scan itself moves (the stacked weights'
            # slices, the gradients' stacking, the carry, residual adds)
            with jax.named_scope("layers"):
                (x, aux_total), ys = jax.lax.scan(fn, (x, aux_total),
                                                  params[f"stage{si}"])
            if collect_cache:
                caches.append(ys)
        return x, aux_total, caches

    # -------------------------------------------------- train loss

    def loss(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        params = self._cast(params)
        with jax.named_scope("embed"):
            x = self._embed(params, batch)
        S = x.shape[1]
        extra = {"positions": jnp.arange(S)}
        if cfg.vlm is not None and "mrope_positions" in batch:
            extra["mrope_positions"] = batch["mrope_positions"]
        if cfg.encoder is not None:
            extra["encoder_out"] = self._encoder_out(params, batch["frames"])
        ctx = self._ctx(S, extra)
        x, aux, _ = self._backbone(params, x, ctx, False)
        with jax.named_scope("head"):
            x = _norm(cfg, params["final_norm"], x)
            labels = batch["labels"]
            if cfg.vlm is not None and "patches" in batch:
                # patches carry no next-token loss
                pads = jnp.full(batch["patches"].shape[:2], -1, labels.dtype)
                labels = jnp.concatenate([pads, labels], axis=1)
            ce = chunked_xent(x, params["unembed"], labels, self.loss_chunk)
        return ce + aux.astype(ce.dtype)

    def logits(self, params, batch) -> jnp.ndarray:
        """Full-sequence logits (small-scale use: smoke tests, examples)."""
        cfg = self.cfg
        params = self._cast(params)
        x = self._embed(params, batch)
        S = x.shape[1]
        extra = {"positions": jnp.arange(S)}
        if cfg.vlm is not None and "mrope_positions" in batch:
            extra["mrope_positions"] = batch["mrope_positions"]
        if cfg.encoder is not None:
            extra["encoder_out"] = self._encoder_out(params, batch["frames"])
        x, _, _ = self._backbone(params, x, self._ctx(S, extra), False)
        x = _norm(cfg, params["final_norm"], x)
        logits = (x @ params["unembed"].astype(x.dtype)).astype(jnp.float32)
        return self._mask_pad_logits(logits)

    def _mask_pad_logits(self, logits):
        if self.vocab_padded == self.cfg.vocab_size:
            return logits
        pad_mask = jnp.arange(self.vocab_padded) >= self.cfg.vocab_size
        return jnp.where(pad_mask, -1e30, logits)

    # -------------------------------------------------- prefill / decode

    def init_cache(self, batch: int, cache_len: int, dtype=None) -> PyTree:
        dtype = dtype or self.compute_dtype
        caches = []
        for stage in self.stages:
            def one(kind):
                return _init_block_cache(kind, self.cfg, batch, cache_len, dtype)
            unit = {f"b{j}": one(kind) for j, kind in enumerate(stage.kinds)}
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (stage.repeats,) + a.shape), unit)
            caches.append(stacked)
        return caches

    def prefill(self, params, batch, cache_len: int) -> Tuple[jnp.ndarray, PyTree]:
        """Forward over the prompt; returns (last-token logits, cache)."""
        cfg = self.cfg
        params = self._cast(params)
        x = self._embed(params, batch)
        S = x.shape[1]
        extra = {"positions": jnp.arange(S), "cache_len": cache_len}
        if cfg.vlm is not None and "mrope_positions" in batch:
            extra["mrope_positions"] = batch["mrope_positions"]
        if cfg.encoder is not None:
            extra["encoder_out"] = self._encoder_out(params, batch["frames"])
        ctx = self._ctx(S, extra)
        x, _, caches = self._backbone(params, x, ctx, True)
        x = _norm(cfg, params["final_norm"], x[:, -1:])
        logits = (x @ params["unembed"].astype(x.dtype)).astype(jnp.float32)
        return self._mask_pad_logits(logits[:, 0]), caches

    def prefill_chunk(self, params, caches, batch, *, positions,
                      cache_ops) -> Tuple[jnp.ndarray, PyTree]:
        """Forward ONE chunk of a prompt against a paged cache
        (`repro.models.cache.PagedLayout.prefill_resume`): ``tokens``
        (B, L) at absolute ``positions`` (L,), earlier positions already
        in the pages ``cache_ops`` addresses.  Returns ((B, vocab)
        logits at ``batch['last']`` — the chunk's final real position —
        and the updated caches.  Only attention / MLA kinds: the layout
        gates chunkability before dispatch."""
        cfg = self.cfg
        params = self._cast(params)
        x = jnp.take(params["embed"]["tok"], batch["tokens"], axis=0)
        x = x.astype(self.compute_dtype)
        if cfg.rope_theta == 0.0:  # absolute positions (mid-prompt offset)
            import math as _math
            d = cfg.d_model
            dim = jnp.arange(d // 2, dtype=jnp.float32)
            inv = jnp.exp(-_math.log(10000.0) * dim / max(d // 2 - 1, 1))
            ang = positions.astype(jnp.float32)[:, None] * inv[None]
            pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
            x = x + pe.astype(x.dtype)[None]
        new_caches = []
        for si, stage in enumerate(self.stages):
            def unit_body(carry, pc, _stage=stage):
                h = carry
                p, c = pc
                new_c = {}
                for j, kind in enumerate(_stage.kinds):
                    h, nc = self._prefill_chunk_block(
                        kind, p[f"b{j}"], c[f"b{j}"], h, positions, cache_ops)
                    new_c[f"b{j}"] = nc
                return h, new_c
            x, nc = jax.lax.scan(unit_body, x,
                                 (params[f"stage{si}"], caches[si]))
            new_caches.append(nc)
        # logits at the chunk's last real position only (the tail of the
        # final chunk is padding)
        x = jnp.take_along_axis(x, batch["last"][:, None, None], axis=1)
        x = _norm(cfg, params["final_norm"], x)
        logits = (x[:, 0] @ params["unembed"].astype(x.dtype)
                  ).astype(jnp.float32)
        return self._mask_pad_logits(logits), new_caches

    def _prefill_chunk_block(self, kind, p, cache, x, positions, cache_ops):
        cfg = self.cfg
        if kind == "attention":
            h = _norm(cfg, p["ln1"], x)
            h, new_cache = attn.attention_prefill_chunk(
                p["attn"], cache, h, positions, rope_theta=cfg.rope_theta,
                qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps,
                cache_ops=cache_ops)
            x = x + h
            h = _norm(cfg, p["ln2"], x)
            if "moe" in p:  # chunkable gate ensures moe_dense
                h, _ = moe_mod.moe_ffn_dense(p["moe"], h, cfg.moe,
                                             cfg.activation)
            else:
                h = mlp(p["mlp"], h, cfg.activation)
            return x + h, new_cache
        if kind == "mla":
            h = _norm(cfg, p["ln1"], x)
            h, new_cache = attn.mla_prefill_chunk(
                p["attn"], cache, h, positions, mla_cfg=cfg.mla,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                cache_ops=cache_ops)
            x = x + h
            h = _norm(cfg, p["ln2"], x)
            return x + mlp(p["mlp"], h, cfg.activation), new_cache
        raise ValueError(f"chunked prefill over {kind!r} blocks — the "
                         "layout's chunkable gate should have refused")

    def decode_step(self, params, caches, batch, *,
                    cache_ops=None) -> Tuple[jnp.ndarray, PyTree]:
        """batch: {'tokens': (B,1), 'pos': scalar int32, [mrope/frames aux]}.
        Returns ((B, vocab) logits, new caches).

        ``cache_ops`` (a `repro.models.cache` layout object) reroutes the
        attention/MLA cache update + attend — the paged-KV seam.  With a
        layout, ``batch['pos']`` may be a per-row (B,) vector (continuous
        batching: every slot at its own position)."""
        cfg = self.cfg
        params = self._cast(params)
        x = jnp.take(params["embed"]["tok"], batch["tokens"], axis=0)
        x = x.astype(self.compute_dtype)
        if cfg.rope_theta == 0.0:  # absolute positions (whisper decoder)
            import math as _math
            d = cfg.d_model
            dim = jnp.arange(d // 2, dtype=jnp.float32)
            inv = jnp.exp(-_math.log(10000.0) * dim / max(d // 2 - 1, 1))
            if batch["pos"].ndim:  # per-row positions (paged layout)
                ang = batch["pos"].astype(jnp.float32)[:, None] * inv[None]
                pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
                x = x + pe.astype(x.dtype)[:, None]
            else:
                ang = batch["pos"].astype(jnp.float32) * inv
                pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])
                x = x + pe.astype(x.dtype)[None, None]
        ctx = {"pos": batch["pos"], "moe_dense": self.moe_dense,
               "cache_ops": cache_ops}
        if cfg.vlm is not None and "mrope_positions" in batch:
            ctx["mrope_positions"] = batch["mrope_positions"]
        new_caches = []
        for si, stage in enumerate(self.stages):
            def unit_body(carry, pc, _stage=stage):
                h = carry
                p, c = pc
                new_c = {}
                for j, kind in enumerate(_stage.kinds):
                    h, nc = _decode_block(kind, self.cfg, p[f"b{j}"],
                                          c[f"b{j}"], h, ctx)
                    new_c[f"b{j}"] = nc
                return h, new_c
            x, nc = jax.lax.scan(unit_body, x,
                                 (params[f"stage{si}"], caches[si]))
            new_caches.append(nc)
        x = _norm(cfg, params["final_norm"], x)
        logits = (x[:, 0] @ params["unembed"].astype(x.dtype)).astype(jnp.float32)
        return self._mask_pad_logits(logits), new_caches


# ---------------------------------------------------------------------------
# chunked cross-entropy (memory-safe for 256k vocab)
# ---------------------------------------------------------------------------


def chunked_xent(x, unembed, labels, chunk: int) -> jnp.ndarray:
    """x: (B, S, d) post-final-norm; unembed: (d, V); labels: (B, S) int32,
    -1 = masked.  Scans over sequence chunks so the (B, chunk, V) logits are
    the only vocab-sized live tensor (with V sharded over `model`)."""
    B, S, d = x.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = (S + pad) // chunk
    xs = x.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(carry, xl):
        tot, cnt = carry
        xc, lc = xl
        logits = (xc @ unembed.astype(xc.dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None],
                                   axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((logz - gold) * mask)
        cnt = cnt + jnp.sum(mask)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (xs, ls))
    return tot / jnp.maximum(cnt, 1.0)
