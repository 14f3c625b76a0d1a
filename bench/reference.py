"""The plain reference of what does not depend on the architecture:
DC-S3GD's update from the paper's equations, over the per-sequence loss
of the configuration's family (`families/`), and the fp8 control's
matrix product.  Imports nothing of the program.

Matrix products run at ``Precision.HIGHEST``; ``lowp=True`` rounds every
matrix-product operand, and in training every product's output
gradient, to fp8 with a per-tensor scale (e4m3 forward, e5m2
backward): the control that has to come out as not correct.

A departure that the program makes and the reference follows, because
it is part of the configuration as it is run: weight decay reaches
every per-worker leaf of rank above one, the layer-stacked norm scales
included.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# fp8 rounding for the control
# ---------------------------------------------------------------------------


def _round8(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _q_fwd(x):
    return _round8(x, jnp.float8_e4m3fn)


_q_fwd.defvjp(lambda x: (_round8(x, jnp.float8_e4m3fn), None),
              lambda _, g: (g,))


@jax.custom_vjp
def _q_bwd(x):
    return x


_q_bwd.defvjp(lambda x: (x, None),
              lambda _, g: (_round8(g, jnp.float8_e5m2),))


def matmul(lowp: bool):
    """``mm(spec, a, b)``: an einsum at full float32, or on fp8-rounded
    operands (and fp8-rounded output gradient) for the control."""
    def mm(spec, a, b):
        if lowp:
            return _q_bwd(jnp.einsum(spec, _q_fwd(a), _q_fwd(b),
                                     precision=HI))
        return jnp.einsum(spec, a, b, precision=HI)
    return mm


def batch_loss(params, tokens, labels, rc, mm, seq_loss):
    """Mean next-token loss over a worker's batch (B, S), one sequence
    at a time; ``seq_loss`` is the family's."""
    def one(carry, tl):
        t, c = seq_loss(params, tl[0], tl[1], rc, mm)
        return (carry[0] + t, carry[1] + c), None
    (tot, cnt), _ = jax.lax.scan(one, (jnp.float32(0), jnp.float32(0)),
                                 (tokens, labels))
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# DC-S3GD (paper Algorithm 1, Eqs. 9-12 and 17) with momentum SGD
# ---------------------------------------------------------------------------


def schedule(t, peak, warmup, total):
    """Linear warm-up to ``peak`` over ``warmup`` steps, then linear decay
    to zero at ``total``."""
    t = jnp.asarray(t, jnp.float32)
    warm = peak * t / max(warmup, 1)
    decay = peak * jnp.maximum(total - t, 0.0) / max(total - warmup, 1)
    return jnp.where(t < warmup, warm, decay)


def _sq(tree):
    return sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))


def dc_step(w, m, lr_prev, batch, t, *, hp, rc, seq_loss, lowp=False,
            exchange=True):
    """One DC-S3GD step of every worker (leading axis W on ``w``, ``m``
    and the batch).  The previous update is Δw_i = -lr_prev · m_i, so the
    state is the weights and the momentum alone.

        D_i  = mean_j Δw_j − Δw_i                      (Eq. 9)
        g̃_i = g_i + λ_i g_i ⊙ g_i ⊙ D_i,
        λ_i  = λ0 ‖g_i‖ / ‖g_i ⊙ g_i ⊙ D_i‖            (Eqs. 10, 17)
        m_i  = μ m_i + g̃_i + wd · w_i   (rank > 1)    (Eq. 11)
        w_i  = w_i + D_i − lr m_i                     (Eq. 12)

    ``seq_loss`` is the family's; ``exchange=False`` leaves the mean
    out (D = 0): a planted fault.
    Returns (w, m, per-worker losses)."""
    mm = matmul(lowp)
    lr = schedule(t, hp["lr"], hp["warmup_steps"], hp["total_steps"])
    wd = schedule(t, hp["weight_decay"] * hp["weight_decay_k"],
                  hp["warmup_steps"], hp["total_steps"])
    dw_prev = jax.tree.map(lambda x: -lr_prev * x, m)
    if exchange:
        D = jax.tree.map(lambda d: jnp.mean(d, 0, keepdims=True) - d,
                         dw_prev)
    else:
        D = jax.tree.map(jnp.zeros_like, dw_prev)

    def worker(wi, mi, Di, toks, labs):
        loss, g = jax.value_and_grad(batch_loss)(wi, toks, labs, rc, mm,
                                                 seq_loss)
        c = jax.tree.map(lambda gg, d: gg * gg * d, g, Di)
        gn, cn = jnp.sqrt(_sq(g)), jnp.sqrt(_sq(c))
        lam = jnp.where(cn > 1e-30, hp["lambda0"] * gn / (cn + 1e-30), 0.0)
        gt = jax.tree.map(lambda gg, cc: gg + lam * cc, g, c)
        mi = jax.tree.map(
            lambda mm_, gg, ww: hp["momentum"] * mm_ + gg
            + (wd * ww if ww.ndim > 1 else 0.0), mi, gt, wi)
        wi = jax.tree.map(lambda ww, d, mm_: ww + d - lr * mm_, wi, Di, mi)
        return wi, mi, loss

    w, m, loss = jax.vmap(worker)(w, m, D, batch["tokens"], batch["labels"])
    return w, m, loss, lr


def leaf_norms(tree):
    """Per-worker norms of every leaf: list of (W,) arrays, in
    `jax.tree.leaves` order."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                             axis=tuple(range(1, x.ndim))))
            for x in jax.tree.leaves(tree)]
