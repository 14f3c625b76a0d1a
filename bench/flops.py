"""Model FLOPs from shapes: what a step needs, not what the program
computes.  The embedding gather, recomputed (rematerialised) forwards,
padded vocabulary columns and idle decode slots do not count.

A multiply-add is two FLOPs.  Per token, the matrix products take
``2 * N`` FLOPs forward, where ``N`` counts the weights in them (the
family's ``matmul_params``); causal attention adds the family's
``pair_flops`` for every earlier position attended to.  Training takes
three times the forward.
"""
from __future__ import annotations

import harness


def attention_pairs(seq: int) -> int:
    """(query, key) pairs of causal attention over ``seq`` positions."""
    return seq * (seq + 1) // 2


def forward_flops(conf: dict, tokens: int, pairs: int) -> float:
    """Forward FLOPs of ``tokens`` positions that attend ``pairs``
    (query, key) pairs in total."""
    family = harness.family(conf)
    return 2.0 * family.matmul_params(conf) * tokens \
        + family.pair_flops(conf) * pairs


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
