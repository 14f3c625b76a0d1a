"""Model FLOPs from shapes: what a step needs, not what the program
computes.  The embedding gather, recomputed (rematerialised) forwards,
padded vocabulary columns and idle decode slots do not count.

A multiply-add is two FLOPs.  Per token, a dense decoder's matrix
products take ``2 * N`` FLOPs forward, where ``N`` counts the matmul
weights (attention projections, MLP, head); causal attention adds
``4 * H * hd`` per layer for every earlier position attended to (the
scores and the weighted sum).  Training takes three times the forward.
"""
from __future__ import annotations


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return {"d": d, "H": H, "KV": conf["num_key_value_heads"],
            "hd": conf.get("head_dim", d // H),
            "f": conf["intermediate_size"], "L": conf["num_hidden_layers"],
            "V": conf["vocab_size"]}


def matmul_params(conf: dict) -> int:
    """Weights that take part in matrix products, per token."""
    k = dims(conf)
    d, H, KV, hd, f = k["d"], k["H"], k["KV"], k["hd"], k["f"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 3 * d * f
    return k["L"] * (attn + mlp) + d * k["V"]


def attention_pairs(seq: int) -> int:
    """(query, key) pairs of causal attention over ``seq`` positions."""
    return seq * (seq + 1) // 2


def forward_flops(conf: dict, tokens: int, pairs: int) -> float:
    """Forward FLOPs of ``tokens`` positions that attend ``pairs``
    (query, key) pairs in total."""
    k = dims(conf)
    return 2.0 * matmul_params(conf) * tokens \
        + 4.0 * k["L"] * k["H"] * k["hd"] * pairs


def train_step_flops(conf: dict, workers: int, seqs: int, seq: int) -> float:
    """One training step of every worker: forward and backward."""
    n = workers * seqs
    return 3.0 * forward_flops(conf, n * seq, n * attention_pairs(seq))


def prefill_flops(conf: dict, prompt: int) -> float:
    return forward_flops(conf, prompt, attention_pairs(prompt))


def decode_flops(conf: dict, context: int) -> float:
    """One decoded token that attends ``context`` positions (itself
    included)."""
    return forward_flops(conf, 1, context)
