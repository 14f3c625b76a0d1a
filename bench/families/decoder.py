"""The dense decoder family: pre-norm layers of rotary attention (GQA or
MHA, q/k-normed where the configuration says) and a SiLU MLP, gated
where the weights have a gate, under RMSNorm or LayerNorm.  Qwen3 and
StableLM as the program runs them.

A family module, ``bench/families/<family>.py``, is named by a
configuration file's ``"family"`` (`harness.family`) and holds all that
the benchmark knows of an architecture.  It imports nothing of the
program, and exports:

- ``widths(conf) -> dict``: the program's `ModelConfig` attribute ->
  the value the file states; `model.program_config` checks each.
- ``reference_config(conf) -> dict``: what the reference needs of the
  file (``rc`` below).
- ``fan_in(name, shape) -> int``: the fan-in of the matrix leaf
  ``name`` of that shape, for the benchmark's weights
  (`model.make_weights`).
- ``seq_loss(params, tokens, labels, rc, mm) -> (sum, count)``: the
  per-sequence objective the program's ``Model.loss`` computes, in
  float32 ``jax.numpy``; a family with an auxiliary term adds it here.
- ``hidden(params, tokens, rc, mm, *, remat)`` and ``unembed(params)``:
  one sequence's final hidden states (S, d) and the head's (d, V)
  matrix, for serving's check.
- ``matmul_params(conf)`` and ``pair_flops(conf)``: the weights in
  matrix products per token, and the forward FLOPs per (query, key)
  pair summed over the layers, for `flops.py`.

``mm(spec, a, b)`` is `reference.matmul`'s einsum, at full float32 or
fp8-rounded for the control.

Weights are read in the layout the benchmark makes them: ``embed.tok``
(V, d), ``unembed`` (d, V), ``final_norm``, and the layer stack under
``stage0.b0`` with a leading layer axis.  The vocabulary padded to a
multiple of 256 joins the training softmax, as in the program (decode
ignores the padded columns).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG = -1e30


# ---------------------------------------------------------------------------
# the configuration file
# ---------------------------------------------------------------------------


def _dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return {"d": d, "H": H, "KV": conf["num_key_value_heads"],
            "hd": conf.get("head_dim", d // H),
            "f": conf["intermediate_size"], "L": conf["num_hidden_layers"],
            "V": conf["vocab_size"]}


def widths(conf: dict) -> dict:
    ref = conf["reference"]
    k = _dims(conf)
    return {
        "n_layers": k["L"],
        "d_model": k["d"],
        "n_heads": k["H"],
        "n_kv_heads": k["KV"],
        "d_ff": k["f"],
        "vocab_size": k["V"],
        "resolved_head_dim": k["hd"],
        "rope_theta": float(conf["rope_theta"]),
        "norm": ref["norm"],
        "qk_norm": ref["qk_norm"],
        "norm_eps": ref["norm_eps"],
        "compute_dtype": ref["compute_dtype"],
        "tie_embeddings": conf["tie_word_embeddings"],
    }


def reference_config(conf: dict) -> dict:
    ref = conf["reference"]
    return {"norm": ref["norm"], "qk_norm": ref["qk_norm"],
            "norm_eps": float(ref["norm_eps"]),
            "rope_theta": float(conf["rope_theta"]),
            "rotary_dims": int(ref["rotary_dims"])}


def fan_in(name: str, shape) -> int:
    if name in ("wq", "wk", "wv"):
        return shape[-3]
    if name == "wo":
        return shape[-3] * shape[-2]
    return shape[-2]


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------


def matmul_params(conf: dict) -> int:
    """Attention projections and the gated MLP of every layer, and the
    head; the embedding gather does not count."""
    k = _dims(conf)
    d, H, KV, hd, f = k["d"], k["H"], k["KV"], k["hd"], k["f"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 3 * d * f
    return k["L"] * (attn + mlp) + d * k["V"]


def pair_flops(conf: dict) -> int:
    """The scores and the weighted sum: ``4 * H * hd`` a layer."""
    k = _dims(conf)
    return 4 * k["L"] * k["H"] * k["hd"]


# ---------------------------------------------------------------------------
# the reference
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


def unembed(params):
    return params["unembed"]


def seq_loss(params, tokens, labels, rc, mm, chunk: int = 256):
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
