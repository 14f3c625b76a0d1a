"""The one generator of serving traffic: open-loop requests from a
traffic file's parameters.

The shapes of a run's requests (arrival times, prompt and output
lengths, in their order) come from the file's fixed ``shape_seed``, so
every seed offers the same work on the same schedule; the run's seed
draws the token ids.  Arrivals: ``poisson`` (exponential gaps at
``rate_per_s``) or ``gamma`` (gaps of shape ``burst_shape``, the same
mean rate; a shape under 1 makes bursts).  Lengths: lognormal with a
median and sigma, rounded and clipped to [min, max].  A file may also
name a ``backlog``: that many requests of the same lengths, queued
before the window opens (a cell past the knee).
"""
from __future__ import annotations

import numpy as np


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, arr: dict, n: int) -> np.ndarray:
    rate = float(arr["rate_per_s"])
    if arr["process"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    if arr["process"] == "gamma":
        k = float(arr["burst_shape"])
        return rng.gamma(k, 1.0 / (rate * k), n)
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def requests(traffic: dict, seed: int, seconds: float, vocab: int,
             rate: float | None = None) -> list:
    """Requests due in ``[0, seconds)``: dicts of ``due`` (seconds after
    the window opens), ``prompt`` (token ids) and ``max_new``."""
    arr = dict(traffic["arrivals"])
    if rate is not None:
        arr["rate_per_s"] = rate
    # one stream each, so the first requests do not depend on the window
    gap_rng, p_rng, o_rng = (np.random.default_rng(
        [int(traffic["shape_seed"]), k]) for k in range(3))
    cap = int(arr["rate_per_s"] * seconds * 2 + 64)
    due = np.cumsum(_gaps(gap_rng, arr, cap))
    while due[-1] < seconds:  # a slow draw: extend, same stream
        due = np.concatenate([due, due[-1] + np.cumsum(
            _gaps(gap_rng, arr, cap))])
    due = due[due < seconds]
    n = len(due)
    p_len = _lengths(p_rng, traffic["prompt_len"], n)
    o_len = _lengths(o_rng, traffic["output_len"], n)
    rng = np.random.default_rng([int(seed), 1])
    return [{"due": float(due[i]),
             "prompt": rng.integers(0, vocab, int(p_len[i])).tolist(),
             "max_new": int(o_len[i])} for i in range(n)]


def backlog(traffic: dict, seed: int, vocab: int) -> list:
    """The ``backlog`` requests queued before the window opens, in their
    order: dicts of ``prompt`` and ``max_new``, with streams of their
    own so that the window's requests stay as they are."""
    n = int(traffic.get("backlog", 0))
    p_rng, o_rng = (np.random.default_rng([int(traffic["shape_seed"]), k])
                    for k in (3, 4))
    p_len = _lengths(p_rng, traffic["prompt_len"], n)
    o_len = _lengths(o_rng, traffic["output_len"], n)
    rng = np.random.default_rng([int(seed), 3])
    return [{"prompt": rng.integers(0, vocab, int(p_len[i])).tolist(),
             "max_new": int(o_len[i])} for i in range(n)]
