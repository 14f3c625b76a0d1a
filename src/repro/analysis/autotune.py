"""Roofline-driven autotuner for the two hot paths.

The hand-picked constants this repo has accreted — bucket count on the
train wire, the fused kernels' bucket padding block, the paged cache's
``page_size``, the scheduler's ``decode_burst`` — are exactly the knobs
a roofline cost model can rank (docs/analysis.md).  This module closes
the loop in two stages:

1. **Predict**: every candidate config gets an analytic step/decode-time
   estimate from the v5e roofline constants (`repro.analysis.roofline`)
   plus the reducer's own ``wire_bytes`` byte model at the *padded*
   `BucketPlan` layout — so bucket count trades per-collective launch
   latency against padding waste, wire dtype prices the payload at
   int8/fp8 bytes (the `analyze(wire_dtype=...)` seam), ``page_size``
   trades internal fragmentation against block-table gather width, and
   ``decode_burst`` amortizes the per-dispatch host overhead.

2. **Probe**: the top-ranked candidates PLUS THE DEFAULT CONFIG are
   measured for real (a few steps / a small serve workload).  The tuned
   config is the probe's argmin, so ``tuned <= default`` holds by
   construction on whatever backend ran the probe — the roofline only
   prunes the search space, the measurement decides.  (On CPU CI the
   v5e constants are obviously not the machine model; the probe is what
   keeps the result honest there.)

The result is a JSON **config blob**::

    {"version": 1,
     "train": {"default": {...}, "tuned": {"buckets": 8, "plan_block": null},
               "default_ms": ..., "tuned_ms": ..., "candidates": [...]},
     "serve": {"default": {...}, "tuned": {"page_size": 32, "decode_burst": 8},
               "default_tps": ..., "tuned_tps": ..., "candidates": [...]}}

consumed by the launch drivers (``--tuned-config blob.json`` /
``--autotune`` on `repro.launch.train` and `repro.launch.serve`) and by
both benchmarks (the ``autotune`` entry of ``BENCH_step_time.json`` /
``BENCH_serve.json``; CI gates tuned >= default).

  PYTHONPATH=src python -m repro.analysis.autotune --out tuned.json --smoke
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from repro.analysis.roofline import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

# per-collective launch latency (s) — the fixed cost each bucket's
# reduce pays regardless of payload; the reason 1000 buckets is slow
# even though padding waste shrinks
COLL_LATENCY_S = 8e-6
# per-dispatch host overhead (s) of one scheduler decode burst: python
# bookkeeping + device dispatch — amortized over decode_burst steps
DISPATCH_OVERHEAD_S = 1.5e-3

TRAIN_DEFAULT = {"buckets": 4, "plan_block": None}
SERVE_DEFAULT = {"page_size": 16, "decode_burst": 4}


def train_space(smoke: bool = False) -> List[dict]:
    from repro.kernels.dc_update import BLOCK
    buckets = (2, 4, 8) if smoke else (1, 2, 4, 8)
    blocks = (None,) if smoke else (None, 2 * BLOCK)
    return [{"buckets": b, "plan_block": blk}
            for b in buckets for blk in blocks]


def serve_space(smoke: bool = False) -> List[dict]:
    sizes = (8, 16, 32)
    bursts = (4, 8) if smoke else (1, 4, 8, 16)
    return [{"page_size": p, "decode_burst": d}
            for p in sizes for d in bursts]


# ---------------------------------------------------------------------------
# analytic predictors (stage 1)
# ---------------------------------------------------------------------------

def predict_train(cand: dict, *, leaf_sizes: Sequence[int], n_workers: int,
                  reducer, flops: float = 0.0, hbm_bytes: float = 0.0
                  ) -> float:
    """Predicted step seconds for one train candidate.

    Compute/memory terms are config-independent (same model, same
    batch) and may be 0 when ranking only; the candidate-dependent part
    is the wire: the reducer's ``wire_bytes`` at the candidate's padded
    bucket layout over ICI, plus one launch latency per bucket."""
    from repro.kernels.dc_update import BLOCK
    block = cand["plan_block"] or BLOCK
    # mirror plan_buckets' greedy fill: no bucket over ceil(total / n)
    cap = -(-sum(leaf_sizes) // max(cand["buckets"], 1))
    parts: List[List[int]] = [[]]
    for n in leaf_sizes:
        if parts[-1] and sum(parts[-1]) + n > cap:
            parts.append([])
        parts[-1].append(n)
    padded = [-(-sum(p) // block) * block for p in parts if p]
    wire = float(reducer.wire_bytes(padded))
    comm_s = wire / ICI_BW + len(padded) * COLL_LATENCY_S
    return flops / PEAK_FLOPS_BF16 + hbm_bytes / HBM_BW + comm_s


def predict_serve(cand: dict, *, kv_bytes_per_token: int, param_bytes: int,
                  slots: int, mean_len: float, decode_flops: float = 0.0
                  ) -> float:
    """Predicted seconds per generated token (lower = better).

    A decode step streams the params plus every live row's KV — the KV
    read includes the allocated-but-empty tail of each row's last page
    (mean ``(page_size - 1) / 2`` slots), which is how ``page_size``
    enters; ``decode_burst`` divides the per-dispatch host overhead
    across the burst's steps."""
    frag_tokens = (cand["page_size"] - 1) / 2.0
    kv_bytes = slots * (mean_len + frag_tokens) * kv_bytes_per_token
    step_s = max((param_bytes + kv_bytes) / HBM_BW,
                 decode_flops / PEAK_FLOPS_BF16)
    step_s += DISPATCH_OVERHEAD_S / cand["decode_burst"]
    return step_s / max(slots, 1)


# ---------------------------------------------------------------------------
# measured probes (stage 2) — ALWAYS include the default config
# ---------------------------------------------------------------------------

def _with_default(cands: List[dict], default: dict) -> List[dict]:
    return ([default] if default not in cands else []) + list(cands)


def probe_train(candidates: List[dict], *, model=None, algo: str = "dc_s3gd",
                reducer: str = "mean_allreduce", comm_dtype: str = None,
                n_workers: int = 2, batch_per_worker: int = 2, seq: int = 32,
                steps: int = 3, warmup: int = 1) -> List[dict]:
    """Measure ms/step for each candidate (default first)."""
    import jax

    from repro.configs import get_config, reduced
    from repro.core import registry
    from repro.core.types import DCS3GDConfig
    from repro.data import SyntheticLMDataset, worker_batches
    from repro.launch.engine import Engine
    from repro.models.transformer import Model

    if model is None:
        cfg = reduced(get_config("qwen3-0.6b"))
        model = Model(cfg, remat=False, scan_chunk=16, loss_chunk=64)
    data = SyntheticLMDataset(model.cfg.vocab_size, seq, seed=0)
    dc_cfg = DCS3GDConfig(learning_rate=0.05, momentum=0.9, lambda0=0.2,
                          warmup_steps=1, total_steps=max(steps, 2))

    out = []
    for cand in _with_default(candidates, dict(TRAIN_DEFAULT)):
        red = registry.make_reducer(reducer, dc_cfg, **(
            {"comm_dtype": comm_dtype} if comm_dtype else {}))
        alg = registry.make(algo, dc_cfg, n_workers=n_workers, reducer=red,
                            buckets=cand["buckets"],
                            plan_block=cand["plan_block"])
        engine = Engine(model, alg)
        state = engine.init_state(jax.random.PRNGKey(0))
        step_fn = engine.jit_train_step()
        for it in range(warmup):
            state, m = step_fn(state, worker_batches(data, it, n_workers,
                                                     batch_per_worker))
        jax.block_until_ready(m)
        t0 = time.perf_counter()
        for it in range(warmup, warmup + steps):
            state, m = step_fn(state, worker_batches(data, it, n_workers,
                                                     batch_per_worker))
        jax.block_until_ready((state, m))
        ms = (time.perf_counter() - t0) / steps * 1e3
        sizes = [x.size for x in jax.tree.leaves(state.params)]
        pred = predict_train(cand, leaf_sizes=sizes, n_workers=n_workers,
                             reducer=red)
        out.append({"config": dict(cand), "ms_per_step": round(ms, 3),
                    "predicted_comm_s": pred})
    return out


def probe_serve(candidates: List[dict], *, model=None, params=None,
                slots: int = 8, n_requests: int = 16, prompt_len: int = 16,
                gen: int = 8, kv_dtype: Optional[str] = None,
                seed: int = 0) -> List[dict]:
    """Measure serve tokens/s for each candidate (default first)."""
    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models.transformer import Model
    from repro.serve import Request, Scheduler

    if model is None:
        cfg = reduced(get_config("qwen3-0.6b"))
        model = Model(cfg, remat=False, scan_chunk=16)
    if params is None:
        params = model.init(jax.random.PRNGKey(0))
    vocab = model.cfg.vocab_size
    max_len = prompt_len + gen + 1

    def workload():
        rng = np.random.default_rng(seed)
        return [Request(rid=i,
                        prompt=rng.integers(0, vocab, prompt_len).tolist(),
                        max_new=1 + (i * gen) // n_requests)
                for i in range(n_requests)]

    out = []
    for cand in _with_default(candidates, dict(SERVE_DEFAULT)):
        ps = cand["page_size"]
        max_pages = -(-max_len // ps)
        pages = slots * max_pages + 1 + max_pages
        sch = Scheduler(model, params, slots=slots, pages=pages,
                        page_size=ps, max_len=max_len,
                        decode_burst=cand["decode_burst"],
                        kv_dtype=kv_dtype)
        reqs = workload()
        sch.run(reqs)                       # warm (compile)
        sch.finished.clear()
        sch.stats.update(decode_steps=0, prefills=0, preemptions=0,
                         tokens=0, step_walls=[], occupancy=[])
        reqs = workload()
        t0 = time.perf_counter()
        sch.run(reqs)
        wall = time.perf_counter() - t0
        toks = sum(r.max_new for r in reqs)
        pred = predict_serve(
            cand, kv_bytes_per_token=sch.layout.kv_bytes_per_token(),
            param_bytes=sum(x.size * x.dtype.itemsize
                            for x in jax.tree.leaves(params)),
            slots=slots, mean_len=prompt_len + gen / 2)
        out.append({"config": dict(cand),
                    "tokens_per_s": round(toks / wall, 1),
                    "predicted_s_per_token": pred})
    return out


# ---------------------------------------------------------------------------
# the blob
# ---------------------------------------------------------------------------

def autotune(*, smoke: bool = False, skip_train: bool = False,
             skip_serve: bool = False, top_k: int = 6,
             kv_dtype: Optional[str] = None) -> dict:
    """Run the full predict-then-probe loop; returns the config blob."""
    blob: Dict = {"version": 1, "smoke": bool(smoke),
                  "hardware": {"peak_flops_bf16": PEAK_FLOPS_BF16,
                               "hbm_bw": HBM_BW, "ici_bw": ICI_BW}}
    if not skip_train:
        cands = train_space(smoke)[:top_k]
        probed = probe_train(cands)
        best = min(probed, key=lambda r: r["ms_per_step"])
        default = next(r for r in probed
                       if r["config"] == TRAIN_DEFAULT)
        blob["train"] = {"default": dict(TRAIN_DEFAULT),
                         "tuned": best["config"],
                         "default_ms": default["ms_per_step"],
                         "tuned_ms": best["ms_per_step"],
                         "candidates": probed}
    if not skip_serve:
        cands = serve_space(smoke)[:top_k]
        probed = probe_serve(cands, kv_dtype=kv_dtype)
        best = max(probed, key=lambda r: r["tokens_per_s"])
        default = next(r for r in probed
                       if r["config"] == SERVE_DEFAULT)
        blob["serve"] = {"default": dict(SERVE_DEFAULT),
                         "tuned": best["config"],
                         "default_tps": default["tokens_per_s"],
                         "tuned_tps": best["tokens_per_s"],
                         "candidates": probed}
    return blob


def load_tuned(path) -> dict:
    """Read a blob written by `autotune` (or the CLI); validates shape."""
    blob = json.loads(Path(path).read_text())
    if not isinstance(blob, dict) or blob.get("version") != 1:
        raise ValueError(f"{path}: not an autotune config blob (version 1)")
    return blob


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, default=Path("tuned.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="small candidate grids (CI)")
    ap.add_argument("--skip-train", action="store_true")
    ap.add_argument("--skip-serve", action="store_true")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("bfloat16", "float32", "int8", "fp8"))
    args = ap.parse_args(argv)
    blob = autotune(smoke=args.smoke, skip_train=args.skip_train,
                    skip_serve=args.skip_serve, kv_dtype=args.kv_dtype)
    args.out.write_text(json.dumps(blob, indent=2))
    for side in ("train", "serve"):
        if side in blob:
            b = blob[side]
            print(f"[autotune] {side}: default {b['default']} -> "
                  f"tuned {b['tuned']}")
    print(f"[autotune] wrote {args.out}")


if __name__ == "__main__":
    main()
