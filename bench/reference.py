"""The plain reference: a float32 decoder in `jax.numpy` and DC-S3GD's
update from the paper's equations.  Imports nothing of the program.

It reads weights in the layout the benchmark makes them (`model.py`):
``embed.tok`` (V, d), ``unembed`` (d, V), ``final_norm``, and the layer
stack under ``stage0.b0`` with a leading layer axis.  Matrix products
run at ``Precision.HIGHEST``; ``lowp=True`` rounds every matrix-product
operand, and in training every product's output gradient, to fp8 with a
per-tensor scale (e4m3 forward, e5m2 backward): the control that has to
come out as not correct.

Departures that the program makes and the reference follows, because
they are part of the configuration as it is run: the vocabulary padded
to a multiple of 256 joins the training softmax (decode ignores the
padded columns), and weight decay reaches every per-worker leaf of rank
above one, the layer-stacked norm scales included.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


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


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------


def _norm(p, x, rc):
    x = x.astype(jnp.float32)
    if rc["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + rc["norm_eps"]) * p["scale"] \
            + p["bias"]
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(var + rc["norm_eps"]) * p["scale"]


def _rotary(x, pos, theta, dims):
    """Rotate the first ``dims`` features of each head, pairing feature
    i with i + dims/2 (x: (S, H, hd))."""
    half = dims // 2
    inv = 1.0 / theta ** (jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def _layer(x, p, rc, mm):
    """One decoder layer over one sequence (x: (S, d))."""
    S = x.shape[0]
    pos = jnp.arange(S)
    a = p["attn"]
    h = _norm(p["ln1"], x, rc)
    q = mm("sd,dhk->shk", h, a["wq"])
    k = mm("sd,dhk->shk", h, a["wk"])
    v = mm("sd,dhk->shk", h, a["wv"])
    if rc["qk_norm"]:
        q = _norm(a["q_norm"], q, {**rc, "norm": "rmsnorm"})
        k = _norm(a["k_norm"], k, {**rc, "norm": "rmsnorm"})
    q = _rotary(q, pos, rc["rope_theta"], rc["rotary_dims"])
    k = _rotary(k, pos, rc["rope_theta"], rc["rotary_dims"])
    H, KV, hd = q.shape[1], k.shape[1], q.shape[2]
    # query head j reads key/value head j // (H / KV)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = mm("qhk,chk->hqc", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, NEG)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("hqc,chk->qhk", w, v)
    x = x + mm("shk,hkd->sd", o, a["wo"])
    h = _norm(p["ln2"], x, rc)
    m = p["mlp"]
    up = mm("sd,df->sf", h, m["w_up"])
    if "w_gate" in m:
        up = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"])) * up
    else:
        up = jax.nn.silu(up)
    return x + mm("sf,fd->sd", up, m["w_down"])


def hidden(params, tokens, rc, mm, *, remat: bool):
    """Final-normed hidden states of one sequence: (S,) -> (S, d)."""
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    body = partial(_layer, rc=rc, mm=mm)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x,
                        params["stage0"]["b0"])
    return _norm(params["final_norm"], x, rc)


def seq_nll(params, tokens, labels, rc, mm, chunk: int = 256):
    """(sum of next-token losses, count) of one sequence; label -1 is
    masked.  Logits are made ``chunk`` positions at a time."""
    x = hidden(params, tokens, rc, mm, remat=True)
    S = x.shape[0]
    chunk = min(chunk, S)
    n = S // chunk
    xs = x.reshape(n, chunk, -1)
    ls = labels.reshape(n, chunk)

    @jax.checkpoint
    def body(carry, xl):
        xc, lc = xl
        logits = mm("sd,dv->sv", xc, params["unembed"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[:, None],
                                   axis=-1)[:, 0]
        valid = (lc >= 0).astype(jnp.float32)
        return (carry[0] + jnp.sum((lse - gold) * valid),
                carry[1] + jnp.sum(valid)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                 (xs, ls))
    return tot, cnt


def batch_loss(params, tokens, labels, rc, mm):
    """Mean next-token loss over a worker's batch (B, S), one sequence
    at a time."""
    def one(carry, tl):
        t, c = seq_nll(params, tl[0], tl[1], rc, mm)
        return (carry[0] + t, carry[1] + c), None
    (tot, cnt), _ = jax.lax.scan(one, (jnp.float32(0), jnp.float32(0)),
                                 (tokens, labels))
    return tot / jnp.maximum(cnt, 1.0)


def logits(params, tokens, rc, mm):
    """(S, V) logits of one sequence."""
    x = hidden(params, tokens, rc, mm, remat=False)
    return mm("sd,dv->sv", x, params["unembed"])


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


def dc_step(w, m, lr_prev, batch, t, *, hp, rc, lowp=False,
            exchange=True):
    """One DC-S3GD step of every worker (leading axis W on ``w``, ``m``
    and the batch).  The previous update is Δw_i = -lr_prev · m_i, so the
    state is the weights and the momentum alone.

        D_i  = mean_j Δw_j − Δw_i                      (Eq. 9)
        g̃_i = g_i + λ_i g_i ⊙ g_i ⊙ D_i,
        λ_i  = λ0 ‖g_i‖ / ‖g_i ⊙ g_i ⊙ D_i‖            (Eqs. 10, 17)
        m_i  = μ m_i + g̃_i + wd · w_i   (rank > 1)    (Eq. 11)
        w_i  = w_i + D_i − lr m_i                     (Eq. 12)

    ``exchange=False`` leaves the mean out (D = 0): a planted fault.
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
        loss, g = jax.value_and_grad(batch_loss)(wi, toks, labs, rc, mm)
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
