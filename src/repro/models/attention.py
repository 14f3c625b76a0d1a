"""Attention variants: GQA (+qk-norm, sliding/local window), MLA, cross-attn.

Execution paths per variant:
  * ``*_train`` — full sequence, training and `Model.prefill`: the custom
    VJP `flash_attention` (FlashAttention-2 in XLA).  Its forward is an
    online softmax over (query tile, key tile) pairs and saves only q, k,
    v, the output and the f32 row log-sum-exp; its backward recomputes
    each tile's probabilities from them.  Memory is O(S·H) plus one
    tile, not O(S²·H), so 32k prefill fits; tiles the causal or window
    mask empties are skipped in both directions;
  * ``*_prefill_chunk`` — a prompt chunk against the paged cache, through
    `_blocked_attention` at a page-aligned block (bitwise independent of
    where chunks fall; forward only);
  * ``*_decode`` — one new token against a KV cache (linear in cache length,
    ring-buffer variant for sliding-window archs).

`_blocked_attention` (nested scans, differentiable by autodiff) is also
the oracle the VJP is tested against.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import random

from repro.models.layers import apply_mrope, apply_rope, dense_init, init_rmsnorm, rmsnorm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def init_attention(key, d: int, n_heads: int, n_kv: int, head_dim: int,
                   qk_norm: bool, dtype) -> dict:
    ks = random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, n_heads, head_dim), dtype, in_axis_size=d),
        "wk": dense_init(ks[1], (d, n_kv, head_dim), dtype, in_axis_size=d),
        "wv": dense_init(ks[2], (d, n_kv, head_dim), dtype, in_axis_size=d),
        "wo": dense_init(ks[3], (n_heads, head_dim, d), dtype,
                         in_axis_size=n_heads * head_dim),
    }
    if qk_norm:
        p["q_norm"] = init_rmsnorm(head_dim, dtype)
        p["k_norm"] = init_rmsnorm(head_dim, dtype)
    return p


def init_cross_attention(key, d: int, n_heads: int, head_dim: int, dtype) -> dict:
    return init_attention(key, d, n_heads, n_heads, head_dim, False, dtype)


# ---------------------------------------------------------------------------
# flash-style blocked attention core (pure jnp): the serving chunk path, and
# the oracle of `flash_attention` and of the Pallas kernel
# ---------------------------------------------------------------------------


def _blocked_attention(q, k, v, q_pos, k_pos, *, causal: bool, window: int,
                       q_chunk: int, kv_chunk: int) -> jnp.ndarray:
    """q: (B, Sq, KV, G, hd); k/v: (B, Sk, KV, hd).  Online-softmax over KV
    blocks, scanned over Q chunks.  Returns (B, Sq, KV, G, hd)."""
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    vd = v.shape[-1]  # value head dim may differ (MLA)
    scale = hd ** -0.5

    # pad sequence dims to chunk multiples
    pq = (-Sq) % q_chunk
    pk = (-Sk) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq), constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pk), constant_values=2**30)
    nq, nk = (Sq + pq) // q_chunk, (Sk + pk) // kv_chunk

    qs = q.reshape(B, nq, q_chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks_ = k.reshape(B, nk, kv_chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kv_chunk, KV, vd).transpose(1, 0, 2, 3, 4)
    qp = q_pos.reshape(nq, q_chunk)
    kp = k_pos.reshape(nk, kv_chunk)

    def per_q_chunk(_, xq):
        qc, qpos = xq  # (B, qc, KV, G, hd), (qc,)

        def per_kv_block(carry, xkv):
            m, l, acc = carry
            kc, vc, kpos = xkv
            s = jnp.einsum("bqkgh,bckh->bkgqc", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((q_chunk, kv_chunk), dtype=bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window > 0:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            mask &= (kpos < 2**30)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqc,bckh->bkgqh", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, vd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(per_kv_block, (m0, l0, a0), (ks_, vs, kp))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 3, 1, 2, 4)  # (B, qc, KV, G, hd)

    _, out = jax.lax.scan(per_q_chunk, None, (qs, qp))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq + pq, KV, G, vd)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# training attention: tiled forward + recomputing backward (custom VJP)
# ---------------------------------------------------------------------------

TILE = 512  # most rows of a tile (tuned on a v5e at S = 2048)


def tile_rows(S: int) -> int:
    """Rows (and key columns) of one tile for a sequence of ``S``: all of
    S when it fits one tile, else S split evenly into tiles of at most
    ``TILE`` rows, each rounded up to a multiple of 128."""
    n = -(-S // TILE)
    return S if n == 1 else -(-S // (n * 128)) * 128


def live_tiles(n_q: int, n_k: int, t: int, *, causal: bool,
               window: int) -> np.ndarray:
    """(n_q, n_k) bool: the (query tile, key tile) pairs the mask does not
    empty.  Under ``causal`` none above the diagonal; under ``window > 0``
    none whose nearest pair is ``window`` or more apart.  Read by index,
    so valid for self-attention over positions that increase along the
    sequence (n indices apart is at least n positions apart)."""
    i, j = np.arange(n_q)[:, None], np.arange(n_k)[None, :]
    live = np.ones((n_q, n_k), bool)
    if causal:
        live &= j <= i
    if window > 0:
        live &= (i - j - 1) * t + 1 < window
    return live


def _span(row: np.ndarray):
    """[first, last + 1) of a row or column of `live_tiles`: one run,
    never empty (the diagonal tile, or every tile without a mask)."""
    idx = np.flatnonzero(row)
    return int(idx[0]), int(idx[-1]) + 1


def _tile_of(x, i, t, axis=1):
    return jax.lax.dynamic_slice_in_dim(x, i * t, t, axis)


class _Tiles:
    """The operands padded to whole tiles of ``t`` rows, which tiles are
    live, and each tile's scaled, masked scores (f32)."""

    def __init__(self, q, k, v, positions, causal, window, t):
        Sq, Sk = q.shape[1], k.shape[1]
        if (causal or window > 0) and Sq != Sk:
            raise ValueError("a causal or windowed mask needs self-attention")
        self.n_q, self.n_k = -(-Sq // t), -(-Sk // t)
        self.Sq, self.Sk, self.t = Sq, Sk, t
        self.causal, self.window = causal, window
        self.scale = q.shape[-1] ** -0.5
        self.q = self.pad(q)
        self.k, self.v = self.pad(k), self.pad(v)
        self.pos = jnp.pad(positions, (0, max(self.n_q, self.n_k) * t
                                       - positions.shape[0]))
        self.live = live_tiles(self.n_q, self.n_k, t, causal=causal,
                               window=window)

    def pad(self, x, axis=1):
        n = -(-x.shape[axis] // self.t) * self.t - x.shape[axis]
        return jnp.pad(x, [(0, n if a == axis else 0) for a in range(x.ndim)])

    def scores(self, qi, kj, i, j):
        """qi (B, t, KV, G, hd) of query tile i against kj of key tile j:
        (B, KV, G, t, t), masked entries at NEG_INF."""
        t = self.t
        s = jnp.einsum("bqkgh,bckh->bkgqc", qi, kj,
                       preferred_element_type=jnp.float32) * self.scale
        qp = _tile_of(self.pos, i, t, 0)[:, None]
        kp = _tile_of(self.pos, j, t, 0)[None, :]
        ok = (j * t + jnp.arange(t) < self.Sk)[None, :]
        if self.causal:
            ok = ok & (qp >= kp)
        if self.window > 0:
            ok = ok & (qp - kp < self.window)
        return jnp.where(ok[None, None, None], s, NEG_INF)


def _flash_forward(q, k, v, positions, causal, window, t):
    """Online softmax, row by row over each query tile's live key tiles.
    Returns the output (B, Sq, KV, G, vd) in q's dtype and the row
    log-sum-exp (B, KV, G, Sq) in f32."""
    B, _, KV, G, _ = q.shape
    vd = v.shape[-1]
    T = _Tiles(q, k, v, positions, causal, window, t)
    outs, lses = [], []
    for i in range(T.n_q):
        qi = T.q[:, i * t:(i + 1) * t]

        def step(j, carry, i=i, qi=qi):
            m, l, acc = carry
            s = T.scores(qi, _tile_of(T.k, j, t), i, j)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            pv = jnp.einsum("bkgqc,bckh->bkgqh", p.astype(v.dtype),
                            _tile_of(T.v, j, t),
                            preferred_element_type=jnp.float32)
            return (m_new, l * corr + jnp.sum(p, axis=-1),
                    acc * corr[..., None] + pv)

        init = (jnp.full((B, KV, G, t), NEG_INF, jnp.float32),
                jnp.zeros((B, KV, G, t), jnp.float32),
                jnp.zeros((B, KV, G, t, vd), jnp.float32))
        m, l, acc = jax.lax.fori_loop(*_span(T.live[i]), step, init)
        l = jnp.maximum(l, 1e-30)
        outs.append((acc / l[..., None]).astype(q.dtype))
        lses.append(m + jnp.log(l))
    out = jnp.concatenate(outs, axis=3).transpose(0, 3, 1, 2, 4)
    return out[:, :T.Sq], jnp.concatenate(lses, axis=3)[..., :T.Sq]


def _flash_backward(q, k, v, positions, out, lse, d_out, causal, window, t):
    """FlashAttention-2's backward, column by column over each key tile's
    live query tiles: a tile's probabilities are recomputed from the
    saved log-sum-exp; dK and dV gather in the column's carry, dQ in a
    buffer over the whole sequence."""
    T = _Tiles(q, k, v, positions, causal, window, t)
    delta = T.pad(jnp.einsum("bqkgh,bqkgh->bkgq", d_out.astype(jnp.float32),
                             out.astype(jnp.float32)), 3)
    lse = T.pad(lse, 3)
    d_out = T.pad(d_out)
    dq = jnp.zeros(T.q.shape, jnp.float32)
    dks, dvs = [], []
    for j in range(T.n_k):
        kj, vj = T.k[:, j * t:(j + 1) * t], T.v[:, j * t:(j + 1) * t]

        def step(i, carry, j=j, kj=kj, vj=vj):
            dk, dv, dq = carry
            qi, doi = _tile_of(T.q, i, t), _tile_of(d_out, i, t)
            p = jnp.exp(T.scores(qi, kj, i, j)
                        - _tile_of(lse, i, t, 3)[..., None])
            dp = jnp.einsum("bqkgh,bckh->bkgqc", doi, vj,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - _tile_of(delta, i, t, 3)[..., None])
            ds = ds.astype(q.dtype)
            dv = dv + jnp.einsum("bkgqc,bqkgh->bckh", p.astype(q.dtype), doi,
                                 preferred_element_type=jnp.float32)
            dk = dk + jnp.einsum("bkgqc,bqkgh->bckh", ds, qi,
                                 preferred_element_type=jnp.float32) * T.scale
            dq_i = jnp.einsum("bkgqc,bckh->bqkgh", ds, kj,
                              preferred_element_type=jnp.float32) * T.scale
            dq = jax.lax.dynamic_update_slice_in_dim(
                dq, _tile_of(dq, i, t) + dq_i, i * t, 1)
            return dk, dv, dq

        init = (jnp.zeros(kj.shape, jnp.float32),
                jnp.zeros(vj.shape, jnp.float32), dq)
        dk, dv, dq = jax.lax.fori_loop(*_span(T.live[:, j]), step, init)
        dks.append(dk)
        dvs.append(dv)
    dk = jnp.concatenate(dks, axis=1)
    dv = jnp.concatenate(dvs, axis=1)
    return dq[:, :T.Sq], dk[:, :T.Sk], dv[:, :T.Sk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, positions, causal, window, t):
    return _flash_forward(q, k, v, positions, causal, window, t)[0]


def _flash_fwd(q, k, v, positions, causal, window, t):
    out, lse = _flash_forward(q, k, v, positions, causal, window, t)
    return out, (q, k, v, positions, out, lse)


def _flash_bwd(causal, window, t, res, d_out):
    q, k, v, positions, out, lse = res
    dq, dk, dv = _flash_backward(q, k, v, positions, out, lse, d_out,
                                 causal, window, t)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, positions, *, causal: bool,
                    window: int) -> jnp.ndarray:
    """Training attention.  q: (B, Sq, KV, G, hd); k: (B, Sk, KV, hd); v:
    (B, Sk, KV, vd); ``positions`` (Sq,) of the queries, and of the keys
    where the mask reads them (causal or windowed self-attention, Sq ==
    Sk).  Returns (B, Sq, KV, G, vd) in q's dtype.

    FlashAttention-2 in XLA: the forward keeps only q, k, v, the output
    and the row log-sum-exp for the backward, which recomputes each
    tile's probabilities from them, so memory is O(S·H) and one tile,
    not O(S²·H); neither pass visits a tile the mask empties
    (`live_tiles`); tiles are `tile_rows` of the longer sequence.
    `_blocked_attention` is its oracle."""
    t = tile_rows(max(q.shape[1], k.shape[1]))
    return _flash(q, k, v, positions, causal, window, t)


# ---------------------------------------------------------------------------
# GQA train / prefill forward
# ---------------------------------------------------------------------------


def attention_train(params: dict, x: jnp.ndarray, positions: jnp.ndarray, *,
                    rope_theta: float, causal: bool = True, window: int = 0,
                    qk_norm: bool = False, norm_eps: float = 1e-6,
                    mrope_positions: Optional[jnp.ndarray] = None,
                    mrope_sections: Optional[Tuple[int, int, int]] = None,
                    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
                    ) -> jnp.ndarray:
    """x: (B, S, d); positions: (S,) absolute positions.  Returns (B, S, d).

    ``kv_override`` supplies external (k, v) for cross-attention (already
    projected).  ``mrope_positions`` (3, S) switches to multimodal RoPE.
    """
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    else:
        k, v = kv_override
    if qk_norm:
        q = rmsnorm(params["q_norm"], q, norm_eps)
        k = rmsnorm(params["k_norm"], k, norm_eps) if kv_override is None else k
    if rope_theta > 0 and kv_override is None:
        if mrope_positions is not None:
            q = apply_mrope(q, mrope_positions, rope_theta, mrope_sections)
            k = apply_mrope(k, mrope_positions, rope_theta, mrope_sections)
        else:
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)

    H, KV = q.shape[2], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, q.shape[-1])
    out = flash_attention(qg, k, v, positions, causal=causal, window=window)
    out = out.reshape(B, S, H, -1)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# GQA decode with KV cache (full or ring-buffer/sliding-window)
# ---------------------------------------------------------------------------


def init_kv_cache(batch: int, cache_len: int, n_kv: int, head_dim: int, dtype):
    z = jnp.zeros((batch, cache_len, n_kv, head_dim), dtype=dtype)
    return {"k": z, "v": z}


def decode_positions(pos: jnp.ndarray) -> jnp.ndarray:
    """Positions for RoPE at decode: scalar pos (the dense layout — every
    row at the same position) broadcasts as (1,); a per-row (B,) vector
    (the paged/continuous-batching layout) becomes (B, 1)."""
    return pos[None] if pos.ndim == 0 else pos[:, None]


def attend_one(qg: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
               valid: jnp.ndarray) -> jnp.ndarray:
    """One-token GQA attention core.  qg: (B, KV, G, hd); k/v caches:
    (B, C, KV, hd); valid: (C,) shared or (B, C) per-row mask.  Returns
    (B, KV, G, hd) f32.  Shared by the dense and paged cache layouts so
    the two stay bitwise-identical on matched inputs."""
    hd = qg.shape[-1]
    s = jnp.einsum("bkgh,bckh->bkgc", qg, k_cache,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    mask = valid[None] if valid.ndim == 1 else valid
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgc,bckh->bkgh", p.astype(v_cache.dtype), v_cache,
                      preferred_element_type=jnp.float32)


def attention_decode(params: dict, cache: dict, x: jnp.ndarray, pos: jnp.ndarray, *,
                     rope_theta: float, window: int = 0, qk_norm: bool = False,
                     norm_eps: float = 1e-6,
                     mrope_positions: Optional[jnp.ndarray] = None,
                     mrope_sections: Optional[Tuple[int, int, int]] = None,
                     cross: bool = False, cache_ops=None
                     ) -> Tuple[jnp.ndarray, dict]:
    """One-token decode.  x: (B, 1, d); pos: scalar int32 (current position)
    for the dense layout, or a per-row (B,) vector under a paged layout.

    Cache keys are stored post-RoPE.  For ``window > 0`` the cache is a ring
    buffer of size ``window`` (slot = pos % window) — memory O(window), not
    O(sequence).  ``cross=True`` treats the cache as static (whisper
    cross-attention: k/v precomputed from the encoder).  ``cache_ops``
    (a `repro.models.cache` layout object) takes over the cache
    update + attend for the self-attention path — the seam the paged KV
    layout plugs into; ``None`` is the dense in-place path."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if qk_norm:
        q = rmsnorm(params["q_norm"], q, norm_eps)
    if rope_theta > 0 and not cross:
        if mrope_positions is not None:
            q = apply_mrope(q, mrope_positions, rope_theta, mrope_sections)
        else:
            q = apply_rope(q, decode_positions(pos), rope_theta)

    H, hd = q.shape[2], q.shape[3]
    if not cross:
        k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        if qk_norm:
            k_new = rmsnorm(params["k_norm"], k_new, norm_eps)
        if rope_theta > 0:
            if mrope_positions is not None:
                k_new = apply_mrope(k_new, mrope_positions, rope_theta, mrope_sections)
            else:
                k_new = apply_rope(k_new, decode_positions(pos), rope_theta)
        KV = k_new.shape[2]
        qg = q.reshape(B, KV, H // KV, hd)
        if cache_ops is not None:
            out, cache = cache_ops.kv_attend(cache, qg, k_new, v_new,
                                             window=window)
            out = out.reshape(B, 1, H, hd).astype(x.dtype)
            return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), cache
        cache_len = cache["k"].shape[1]
        slot = jnp.where(window > 0, pos % cache_len, pos)
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
        cache = {"k": k_cache, "v": v_cache}
        valid = jnp.arange(cache_len) <= pos  # ring: all valid once wrapped
    else:
        k_cache, v_cache = cache["k"], cache["v"]
        valid = jnp.ones((k_cache.shape[1],), dtype=bool)
        KV = k_cache.shape[2]
        qg = q.reshape(B, KV, H // KV, hd)

    out = attend_one(qg, k_cache, v_cache, valid)
    out = out.reshape(B, 1, H, hd).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), cache


# ---------------------------------------------------------------------------
# chunked prefill (paged cache resume — repro.models.cache._ChunkOps)
# ---------------------------------------------------------------------------


def attention_prefill_chunk(params: dict, cache: dict, x: jnp.ndarray,
                            positions: jnp.ndarray, *, rope_theta: float,
                            qk_norm: bool = False, norm_eps: float = 1e-6,
                            cache_ops=None) -> Tuple[jnp.ndarray, dict]:
    """Prefill a CHUNK of a prompt against the paged KV cache: x is
    (B, L, d) at absolute ``positions`` (L,) — the prompt's earlier
    positions already live in the pages ``cache_ops`` addresses.  The
    chunk's k/v are scattered into the pages first, then the chunk's
    queries attend over the whole linearized paged view with the
    causal mask doing the future-masking.

    The KV reduction is blocked at a FIXED page-aligned block size
    (``cache_ops.kv_prefill_attend`` → `_blocked_attention` with
    ``kv_chunk = page_size``), so a position's output is bitwise
    independent of the total prompt length and of where chunk
    boundaries fall — fully-masked KV blocks are exact no-ops in the
    online softmax.  That invariance is what lets a prefix-cache hit
    resume mid-prompt and still be bitwise the cold prefill."""
    B, L, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if qk_norm:
        q = rmsnorm(params["q_norm"], q, norm_eps)
        k = rmsnorm(params["k_norm"], k, norm_eps)
    if rope_theta > 0:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    H, KV = q.shape[2], k.shape[2]
    qg = q.reshape(B, L, KV, H // KV, q.shape[-1])
    out, cache = cache_ops.kv_prefill_attend(cache, qg, k, v, positions)
    out = out.reshape(B, L, H, -1).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), cache


def mla_prefill_chunk(params: dict, cache: dict, x: jnp.ndarray,
                      positions: jnp.ndarray, *, mla_cfg, rope_theta: float,
                      norm_eps: float = 1e-6, cache_ops=None
                      ) -> Tuple[jnp.ndarray, dict]:
    """MLA analogue of `attention_prefill_chunk`: the chunk's latents are
    scattered into the latent pages, then the chunk queries attend over
    the linearized latent view expanded through W_uk / W_uv (the
    multi-query form — the absorbed decode form is single-token)."""
    m = mla_cfg
    B, L, _ = x.shape
    q_lat = rmsnorm(params["q_norm"], x @ params["w_dq"], norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, params["w_uq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, rope_theta)

    ckv = rmsnorm(params["kv_norm"], x @ params["w_dkv"], norm_eps)
    k_rope = apply_rope((x @ params["w_kr"])[:, :, None, :], positions,
                        rope_theta)[:, :, 0]
    ckv_lin, kr_lin, cache = cache_ops.mla_prefill(cache, ckv, k_rope)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv_lin, params["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv_lin, params["w_uv"])
    H = q.shape[2]
    Sk = ckv_lin.shape[1]
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_lin[:, :, None, :],
                                  (B, Sk, H, m.qk_rope_head_dim))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = _blocked_attention(q_full[:, :, :, None, :], k_full, v,
                             positions, jnp.arange(Sk), causal=True,
                             window=0, q_chunk=L,
                             kv_chunk=cache_ops.layout.page_size)
    out = out.reshape(B, L, H, m.v_head_dim).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------


def init_mla(key, d: int, n_heads: int, mla_cfg, dtype) -> dict:
    m = mla_cfg
    ks = random.split(key, 8)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": dense_init(ks[0], (d, m.q_lora_rank), dtype),
        "q_norm": init_rmsnorm(m.q_lora_rank, dtype),
        "w_uq": dense_init(ks[1], (m.q_lora_rank, n_heads, qk), dtype,
                           in_axis_size=m.q_lora_rank),
        "w_dkv": dense_init(ks[2], (d, m.kv_lora_rank), dtype),
        "kv_norm": init_rmsnorm(m.kv_lora_rank, dtype),
        "w_kr": dense_init(ks[3], (d, m.qk_rope_head_dim), dtype),
        "w_uk": dense_init(ks[4], (m.kv_lora_rank, n_heads, m.qk_nope_head_dim),
                           dtype, in_axis_size=m.kv_lora_rank),
        "w_uv": dense_init(ks[5], (m.kv_lora_rank, n_heads, m.v_head_dim),
                           dtype, in_axis_size=m.kv_lora_rank),
        "wo": dense_init(ks[6], (n_heads, m.v_head_dim, d), dtype,
                         in_axis_size=n_heads * m.v_head_dim),
    }


def mla_train(params: dict, x: jnp.ndarray, positions: jnp.ndarray, *,
              mla_cfg, rope_theta: float, norm_eps: float = 1e-6) -> jnp.ndarray:
    m = mla_cfg
    B, S, _ = x.shape
    q_lat = rmsnorm(params["q_norm"], x @ params["w_dq"], norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, params["w_uq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, rope_theta)

    ckv = rmsnorm(params["kv_norm"], x @ params["w_dkv"], norm_eps)
    k_rope = apply_rope((x @ params["w_kr"])[:, :, None, :], positions, rope_theta)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, params["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, params["w_uv"])

    H = q.shape[2]
    k_rope_b = jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope_head_dim))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    # MHA (KV = H groups of 1)
    qg = q_full[:, :, :, None, :]
    out = flash_attention(qg, k_full, v, positions, causal=True, window=0)
    out = out.reshape(B, S, H, m.v_head_dim)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def init_mla_cache(batch: int, cache_len: int, mla_cfg, dtype):
    return {
        "ckv": jnp.zeros((batch, cache_len, mla_cfg.kv_lora_rank), dtype=dtype),
        "k_rope": jnp.zeros((batch, cache_len, mla_cfg.qk_rope_head_dim), dtype=dtype),
    }


def mla_attend_one(params: dict, q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                   ckv: jnp.ndarray, k_rope: jnp.ndarray,
                   valid: jnp.ndarray, *, mla_cfg, out_dtype) -> jnp.ndarray:
    """Absorbed-weight MLA attention core for one token.  ckv: (B, C, rank);
    k_rope: (B, C, rr); valid: (C,) shared or (B, C) per-row.  Returns
    (B, H, v_head_dim) in ``out_dtype``.  Shared by the dense and paged
    latent-cache layouts (bitwise on matched inputs)."""
    m = mla_cfg
    # absorb W_uk into the query:  q_lat_h = q_nope @ W_uk^T  (per head)
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope, params["w_uk"])  # (B,H,ckv_rank)
    s = jnp.einsum("bhr,bsr->bhs", q_abs, ckv, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhk,bsk->bhs", q_rope, k_rope,
                       preferred_element_type=jnp.float32)
    s = s * ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5)
    mask = valid[None] if valid.ndim == 1 else valid
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # values in latent space, then expand through W_uv
    lat = jnp.einsum("bhs,bsr->bhr", p.astype(ckv.dtype), ckv,
                     preferred_element_type=jnp.float32).astype(out_dtype)
    return jnp.einsum("bhr,rhk->bhk", lat, params["w_uv"])


def mla_decode(params: dict, cache: dict, x: jnp.ndarray, pos: jnp.ndarray, *,
               mla_cfg, rope_theta: float, norm_eps: float = 1e-6,
               cache_ops=None) -> Tuple[jnp.ndarray, dict]:
    """Absorbed-weight MLA decode: scores and values are computed directly in
    the compressed latent space, so per-step cost is O(S · kv_lora_rank · H)
    instead of re-expanding the whole cache.  This is the TPU-friendly form —
    two extra small matmuls per step instead of an S-sized expansion.

    ``pos`` is scalar for the dense layout, per-row (B,) under a paged
    layout; ``cache_ops`` takes over the latent-cache update + view."""
    m = mla_cfg
    B = x.shape[0]
    q_lat = rmsnorm(params["q_norm"], x @ params["w_dq"], norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, params["w_uq"])[:, 0]  # (B,H,qk)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope[:, None], decode_positions(pos),
                        rope_theta)[:, 0]

    ckv_t = rmsnorm(params["kv_norm"], x @ params["w_dkv"], norm_eps)[:, 0]
    k_rope_t = apply_rope((x @ params["w_kr"])[:, :, None, :],
                          decode_positions(pos), rope_theta)[:, 0, 0]

    if cache_ops is not None:
        ckv, k_rope, valid, cache = cache_ops.mla_update(cache, ckv_t,
                                                         k_rope_t)
    else:
        ckv = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv_t[:, None].astype(cache["ckv"].dtype),
            (0, pos, 0))
        k_rope = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope_t[:, None].astype(cache["k_rope"].dtype),
            (0, pos, 0))
        cache = {"ckv": ckv, "k_rope": k_rope}
        valid = jnp.arange(ckv.shape[1]) <= pos

    out = mla_attend_one(params, q_nope, q_rope, ckv, k_rope, valid,
                         mla_cfg=m, out_dtype=x.dtype)
    out = jnp.einsum("bhk,hkd->bd", out, params["wo"])[:, None]
    return out, cache
