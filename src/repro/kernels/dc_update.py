"""Pallas TPU kernels for the DC-S3GD update tail.

The paper's contribution is optimizer/communication-level, so the
perf-critical *compute* of the technique is the per-step elementwise tail
that touches four model-sized tensors (g, D, m, w) and produces three
(w', m', Δw):

  unfused (XLA default, worst case): ~6 separate HBM passes
  fused here:                        read 4N, write 3N — one pass

plus the two norm reductions of Eq. 17 fused into a single read of (g, D).

TPU adaptation: blocks are (ROWS, 128) f32 tiles in VMEM (lane dim 128,
sublane multiple of 8); tensors are flattened and padded to tile boundaries
by the ops.py wrapper.  Grid iterations on TPU execute sequentially per
core, so the norm kernel accumulates its two partial sums into (1, 1)
outputs held in SMEM for the whole grid (init on step 0) — the standard
Pallas reduction idiom; Mosaic stores scalars to SMEM only, never VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 256          # sublane rows per block (multiple of 8)
LANES = 128         # TPU lane width
BLOCK = ROWS * LANES


# ---------------------------------------------------------------------------
# kernel 1: fused Eq.17 norms — one pass over (g, D)
# ---------------------------------------------------------------------------


def _dc_norms_kernel(g_ref, d_ref, gsq_ref, csq_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        gsq_ref[0, 0] = jnp.float32(0.0)
        csq_ref[0, 0] = jnp.float32(0.0)

    g = g_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    c = g * g * d
    gsq_ref[0, 0] += jnp.sum(g * g)
    csq_ref[0, 0] += jnp.sum(c * c)


def dc_norms(g2d: jnp.ndarray, d2d: jnp.ndarray, *, interpret: bool = False):
    """g2d/d2d: (M, 128) f32, M % ROWS == 0 (pre-padded with zeros — zero
    padding contributes nothing to either sum).  Returns (gsq, csq) scalars."""
    m = g2d.shape[0]
    grid = (m // ROWS,)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    gsq, csq = pl.pallas_call(
        _dc_norms_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=[smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(g2d, d2d)
    return gsq[0, 0], csq[0, 0]


# ---------------------------------------------------------------------------
# kernel 2: fused correction + momentum + Eq.12 weight move
# ---------------------------------------------------------------------------


def _dc_update_kernel(scalars_ref, g_ref, d_ref, m_ref, w_ref,
                      w_out_ref, m_out_ref, delta_ref):
    lam = scalars_ref[0, 0]
    mu = scalars_ref[0, 1]
    eta = scalars_ref[0, 2]
    wd = scalars_ref[0, 3]

    g = g_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)

    g_t = g + lam * (g * g * d)          # Eq. 10
    g_t = g_t + wd * w                   # decoupled weight decay
    m_new = mu * m + g_t                 # U(., eta, mu) slot update
    delta = -eta * m_new                 # Eq. 11
    w_new = w + d + delta                # Eq. 12

    w_out_ref[...] = w_new.astype(w_out_ref.dtype)
    m_out_ref[...] = m_new
    delta_ref[...] = delta


def pack_scalars(lam, mu, eta, wd) -> jnp.ndarray:
    """The (1, 4) scalar operand of the fused update.  Callers looping
    over many buffers (ops.py trees/buckets) build the decayed and
    undecayed rows ONCE instead of re-stacking four scalars per leaf."""
    return jnp.stack([
        jnp.asarray(lam, jnp.float32), jnp.asarray(mu, jnp.float32),
        jnp.asarray(eta, jnp.float32), jnp.asarray(wd, jnp.float32)
    ]).reshape(1, 4)


def dc_fused_update(g2d, d2d, m2d, w2d, *, lam=None, mu=None, eta=None,
                    wd=None, scalars=None, interpret: bool = False):
    """All inputs (M, 128), M % ROWS == 0.  lam/eta/wd may be traced scalars,
    or pre-packed via ``scalars=pack_scalars(...)``.
    Returns (w', m', Δw) with w' in w2d.dtype, m'/Δw f32."""
    m_rows = g2d.shape[0]
    grid = (m_rows // ROWS,)
    if scalars is None:
        scalars = pack_scalars(lam, mu, eta, wd)
    block = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _dc_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 4), lambda i: (0, 0)),  # broadcast scalars
            block, block, block, block,
        ],
        out_specs=[block, block, block],
        out_shape=[
            jax.ShapeDtypeStruct(w2d.shape, w2d.dtype),
            jax.ShapeDtypeStruct(m2d.shape, jnp.float32),
            jax.ShapeDtypeStruct(g2d.shape, jnp.float32),
        ],
        interpret=interpret,
    )(scalars, g2d, d2d, m2d, w2d)
