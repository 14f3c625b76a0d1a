"""Pallas TPU paged-attention decode kernel.

One new token attends over a **paged** KV cache: each sequence's keys and
values live in fixed-size pages of a shared pool, addressed through a
per-sequence block table (`repro.models.cache.PagedLayout`).  The XLA
fallback materializes the whole ``(B, max_pages · page_size, KV, hd)``
gather in HBM every step; this kernel never builds it — the block table
rides the grid as a **scalar-prefetch** operand, so each grid step DMAs
exactly one physical page of k and v into VMEM and folds it into the
online-softmax state.  HBM traffic per (row, head) is the row's *live*
pages once, plus q and the (G, hd) output tile.

Layout: grid (B, max_pages) — TPU executes the grid sequentially per
core, innermost dim last, so VMEM scratch carries the per-KV-head
(m, l, acc) online-softmax state across the page dimension; it is
(re)initialized at page 0 and the output tile is written at the final
page.  The k/v block specs index the *pool's* page dim through the
prefetched block table — that indirection is the whole kernel.  A block
is one whole physical page ``(page_size, KV, hd)``: its last two dims
are the pool's full ``(KV, hd)``, which is what the TPU's (8, 128)
tiling rule accepts (a one-head ``(1, hd)`` block is refused), and the
page arrives in ONE contiguous DMA; the kernel then walks the KV heads
with a static loop.

The pure-jnp oracle is `repro.kernels.ref.paged_attention_ref` (gather +
masked softmax on the linearized view); tests sweep shapes / page sizes /
ragged lengths against it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _fold_page(b, j, q, k, v, len_ref, m_ref, l_ref, acc_ref,
               *, page_size: int):
    """Fold one f32 (page_size, hd) k/v page of one KV head into that
    head's online-softmax scratch state."""
    hd = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (hd ** -0.5)                           # (G, page_size)
    kpos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < len_ref[b], s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  page_size: int, n_pages: int, n_kv: int, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(n_kv):
        q = q_ref[h].astype(jnp.float32)               # (G, hd)
        k = k_ref[:, h, :].astype(jnp.float32)         # (page_size, hd)
        v = v_ref[:, h, :].astype(jnp.float32)
        if quant:
            k = k * ks_ref[0][:, None]
            v = v * vs_ref[0][:, None]
        _fold_page(b, j, q, k, v, len_ref, m_ref.at[h], l_ref.at[h],
                   acc_ref.at[h], page_size=page_size)

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                    block_tables: jnp.ndarray, lengths: jnp.ndarray, *,
                    k_scale: Optional[jnp.ndarray] = None,
                    v_scale: Optional[jnp.ndarray] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, KV, G, hd); k_pool/v_pool: (num_pages, page_size, KV, hd);
    block_tables: (B, max_pages) int32; lengths: (B,) int32 valid
    positions per row.  Returns (B, KV, G, hd) f32.

    Semantics = `repro.kernels.ref.paged_attention_ref`: attend over the
    logical linearization of each row's block table, masking positions
    ``>= lengths[b]`` (rows must have ``lengths >= 1``).

    With ``k_scale``/``v_scale`` (``(num_pages, page_size)`` f32 — the
    per-token scales of int8/fp8 quantized pools,
    `repro.models.cache.PagedLayout` with ``kv_dtype``), each grid step
    additionally DMAs the page's scale row and dequantizes inside the
    kernel — the online-softmax state never sees the storage dtype.
    The scales ride as ``(num_pages, 1, page_size)`` so that their block
    too spans the array's last two dims.  Note TPU int8 tiling wants
    ``page_size >= 32``; smaller pages fall back to relayouts (correct,
    slower).
    """
    from jax.experimental.pallas import tpu as pltpu

    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, KV, G, hd = q.shape
    page_size = k_pool.shape[1]
    mp = block_tables.shape[1]

    pool_spec = pl.BlockSpec((None, page_size, KV, hd),
                             lambda b, j, bt, ln: (bt[b, j], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((None, KV, G, hd), lambda b, j, bt, ln: (b, 0, 0, 0)),
        pool_spec,
        pool_spec,
    ]
    operands = [q, k_pool, v_pool]
    quant = k_scale is not None
    if quant:
        scale_spec = pl.BlockSpec((None, 1, page_size),
                                  lambda b, j, bt, ln: (bt[b, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale.astype(jnp.float32)[:, None],
                     v_scale.astype(jnp.float32)[:, None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, KV, G, hd),
                               lambda b, j, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               n_pages=mp, n_kv=KV, quant=quant)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
