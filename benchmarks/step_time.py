"""Step-time benchmark — the first entry in the perf trajectory.

Times the jitted train step for the full hot-path grid

    {dc_s3gd, ssgd} x {mean_allreduce, gossip, hierarchical}
                    x {use_kernels on/off} x {buckets 0/BUCKETS}

plus the error-feedback compressed reducers ``{topk, powersgd}`` at the
bucketed setting (compression is per bucket; ``buckets=0`` has no flat
wire to compress), on the reduced transformer (the CI smoke model; on
real hardware pass a bigger ``--arch`` through ``repro.launch.train``
instead) and, with ``--json``, writes ``BENCH_step_time.json``: one row
per config with measured ms/step, the per-step HLO ``reduce``/
``convert`` op counts of the lowered step — the static evidence that
bucketing collapses per-leaf wire ops — and the **wire-bytes column**:
``wire_bytes_per_step`` is the per-worker bytes each reducer puts on the
wire at the lowered bucket layout (padded `BucketPlan` sizes for
bucketed rows, exact leaf sizes per-leaf), ``wire_compression`` the
dense/compressed ratio, so the file shows the compression win, not just
ms/step (Dynamic-SSP's lesson: measure per-step cost, don't assume it).

Two more columns per row: ``kernel_mode`` reports whether the Pallas
bodies actually compiled ("compiled": a Mosaic custom-call appears in
the lowering) or run interpreted ("interpret" — CPU CI; ``null`` when
``use_kernels`` is off), so perf gates compare like-for-like; and every
stale-family bucketed row is re-timed with the
`repro.parallel.pipeline` double-buffered schedule
(``overlap_ms_per_step`` / ``overlap_ms_saved``; ``null`` for ssgd and
per-leaf rows, which have no bucket pipeline to stage).

Step times are measured with buffer donation in effect (the Engine's
jitted step donates the TrainState), so the numbers include the
zero-copy state reuse the bucketed path is designed around.

The JSON also carries a top-level ``resize`` entry — the cost of one
elastic membership transition (W=8 -> W=7 through
``repro.cluster``'s collapse-to-consensus reshard): ``resize_ms`` for
the reshard itself and ``rejit_first_step_ms`` for the first
(re-compiled) step at the new worker count.

Quantized wire: every row records ``wire_dtype`` and the grid adds
``dc_s3gd`` x ``{mean_allreduce, topk}`` rows at ``comm_dtype="int8"``
— the dense yardstick of ``wire_compression`` is always priced at f32
so those rows read ~4x / ~80x against the same baseline (CI gates
int8 >= 3x).  A top-level ``autotune`` entry holds the
`repro.analysis.autotune` train probe (buckets x plan_block, the
default config always measured alongside the candidates), which CI
gates at tuned <= default ms/step.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax

from benchmarks.common import emit, requested_algos

BUCKETS = 4
REDUCERS = ("mean_allreduce", "gossip", "hierarchical")
# compressed reducers ride the bucketed wire only (per-bucket sparsify /
# low-rank — repro.core.compress); grid them at buckets=BUCKETS.
# topk_exact is the all-gather union-support variant: its wire_bytes row
# shows what exactness costs next to gather-free topk (k indices + up to
# W·k union values vs k of each)
COMPRESSED = ("topk", "topk_exact", "powersgd")
FULL_ALGOS = ("dc_s3gd", "ssgd")
# the committed perf-trajectory baseline is only ever written by a full
# (non-smoke, full-grid) run; smoke/partial runs go to a sibling name so
# a CI-reproduction from the repo root can't clobber the baseline
JSON_NAME = "BENCH_step_time.json"
SMOKE_JSON_NAME = "BENCH_step_time.smoke.json"


def _build(algo: str, reducer: str, use_kernels: bool, buckets: int,
           model, n_workers: int, steps: int, overlap: bool = False,
           comm_dtype: str = None):
    from repro.core import registry
    from repro.core.types import DCS3GDConfig
    cfg = DCS3GDConfig(learning_rate=0.05, momentum=0.9, lambda0=0.2,
                       warmup_steps=1, total_steps=max(steps, 2))
    red = registry.make_reducer(reducer, cfg, **(
        {"comm_dtype": comm_dtype} if comm_dtype else {}))
    return registry.make(algo, cfg, n_workers=n_workers, reducer=red,
                         use_kernels=use_kernels, buckets=buckets,
                         overlap=overlap)


def _hlo_counts(step_fn, state, batch, *, use_kernels: bool) -> dict:
    from repro.analysis.hlo import count_ops
    txt = step_fn.lower(state, batch).as_text()
    # kernel_mode comes from the ACTUAL lowering, not the flag: a Mosaic
    # custom-call in the stablehlo means the Pallas bodies compiled for
    # the accelerator; their absence under use_kernels means the
    # interpreter path (CPU CI) — gates must compare like-for-like
    mode = None
    if use_kernels:
        mode = ("compiled" if ("tpu_custom_call" in txt or "mosaic" in txt)
                else "interpret")
    # op counts via the shared pass-framework parser (same prefix
    # semantics as the historical substring counts — pinned in
    # tests/test_hlo_analysis.py)
    return {"hlo_reduce_ops": count_ops(txt, "reduce"),
            "hlo_convert_ops": count_ops(txt, "convert"),
            "kernel_mode": mode}


def _wire_columns(alg, algo: str, state) -> dict:
    """Per-worker wire payload of one step at the lowered layout.

    Bucketed rows use the padded `BucketPlan` sizes (what the lowered
    step actually moves); per-leaf rows the exact canonical leaf sizes.
    ``wire_compression`` is the one-shot dense payload (mean_allreduce
    at the same layout/``comm_dtype``) over the reducer's own payload:
    1.0 for the dense mean, BELOW 1 for multi-hop topologies (gossip /
    hierarchical move the payload once per hop), the headline 10–100x
    for the compressed reducers, and ~4x for an int8 wire."""
    red = getattr(alg, "reducer", None)
    if red is None or not hasattr(red, "wire_bytes"):
        return {}
    if getattr(alg, "buckets", 0):
        sizes = list(alg._plan(state.params).bucket_sizes)
    else:
        import jax
        stacked = algo != "ssgd"   # dc_s3gd/stale params are (W, ...)
        sizes = [x.size // (x.shape[0] if stacked else 1)
                 for x in jax.tree.leaves(state.params)]
    wire = int(red.wire_bytes(sizes))
    # the compression reference is the one-shot DENSE F32 payload at the
    # same layout — a fixed yardstick, so an int8 mean_allreduce row
    # shows ~4x, not 1x against itself (bitwise unchanged for the
    # pre-quantization rows: their comm_dtype was float32)
    dense = sum(sizes) * 4
    return {"wire_bytes_per_step": wire,
            "wire_compression": round(dense / max(wire, 1), 2)}


def time_config(algo: str, reducer: str, use_kernels: bool, buckets: int,
                model, data, *, n_workers: int, batch_per_worker: int,
                steps: int, warmup: int, comm_dtype: str = None) -> dict:
    from repro.data import worker_batches
    from repro.launch.engine import Engine

    def run(overlap: bool):
        alg = _build(algo, reducer, use_kernels, buckets, model,
                     n_workers, steps, overlap, comm_dtype=comm_dtype)
        engine = Engine(model, alg)
        state = engine.init_state(jax.random.PRNGKey(0))
        step_fn = engine.jit_train_step()
        counts = _hlo_counts(step_fn, state,
                             worker_batches(data, 0, n_workers,
                                            batch_per_worker),
                             use_kernels=use_kernels)
        counts.update(_wire_columns(alg, algo, state))
        for it in range(warmup):
            state, metrics = step_fn(state,
                                     worker_batches(data, it, n_workers,
                                                    batch_per_worker))
        jax.block_until_ready(metrics)
        t0 = time.perf_counter()
        for it in range(warmup, warmup + steps):
            state, metrics = step_fn(state,
                                     worker_batches(data, it, n_workers,
                                                    batch_per_worker))
        jax.block_until_ready((state, metrics))
        return (time.perf_counter() - t0) / steps * 1e3, counts

    ms, counts = run(overlap=False)
    # the pipelined (double-buffered) schedule only exists over the
    # bucketed wire of the stale-family algorithms — ssgd's blocking
    # all-reduce has nothing to overlap (see repro.parallel.pipeline)
    overlap_ms = None
    if algo != "ssgd" and buckets:
        overlap_ms, _ = run(overlap=True)
    return {"algo": algo, "reducer": reducer, "use_kernels": use_kernels,
            "buckets": buckets,
            "wire_dtype": comm_dtype or "float32",
            "ms_per_step": round(ms, 3),
            "overlap_ms_per_step":
                None if overlap_ms is None else round(overlap_ms, 3),
            "overlap_ms_saved":
                None if overlap_ms is None else round(ms - overlap_ms, 3),
            "steps": steps, **counts}


def resize_timing(model, data, *, batch_per_worker: int) -> dict:
    """Cost of one elastic membership transition (W=8 -> W=7).

    Two numbers, because they amortize differently: ``resize_ms`` is the
    collapse-to-consensus reshard itself (`resize_state` +
    `rebuild_algorithm` — pure array work, paid at every transition) and
    ``rejit_first_step_ms`` is the first step at the new W (dominated by
    the re-compile; paid once per distinct worker count)."""
    from repro.cluster import rebuild_algorithm
    from repro.data import worker_batches
    from repro.launch.engine import Engine

    w_old, w_new = 8, 7
    alg = _build("dc_s3gd", "mean_allreduce", False, BUCKETS, model,
                 w_old, 2)
    engine = Engine(model, alg)
    state = engine.init_state(jax.random.PRNGKey(0))
    step_fn = engine.jit_train_step()
    state, m = step_fn(state, worker_batches(data, 0, w_old,
                                             batch_per_worker))
    jax.block_until_ready((state, m))

    t0 = time.perf_counter()
    state = alg.resize_state(state, w_new)
    jax.block_until_ready(state)
    alg = rebuild_algorithm(alg, w_new)
    resize_ms = (time.perf_counter() - t0) * 1e3

    engine.alg = alg
    batch = worker_batches(data, 1, w_new, batch_per_worker)
    t0 = time.perf_counter()
    state, m = engine.jit_train_step()(state, batch)
    jax.block_until_ready((state, m))
    rejit_ms = (time.perf_counter() - t0) * 1e3
    return {"transition": f"W{w_old}->W{w_new}",
            "algo": "dc_s3gd", "reducer": "mean_allreduce",
            "buckets": BUCKETS,
            "resize_ms": round(resize_ms, 3),
            "rejit_first_step_ms": round(rejit_ms, 3)}


def autotune_entry(model, *, smoke: bool, steps: int, warmup: int,
                   n_workers: int, batch_per_worker: int, seq: int) -> dict:
    """The ``autotune`` entry of the artifact: every candidate bucket
    layout probed on THIS bench's model and step budget, tuned = the
    measured argmin (the default config is always probed, so
    ``tuned_ms <= default_ms`` cannot fail on a fair machine)."""
    from repro.analysis.autotune import (TRAIN_DEFAULT, probe_train,
                                         train_space)
    probed = probe_train(train_space(smoke), model=model,
                         n_workers=n_workers,
                         batch_per_worker=batch_per_worker, seq=seq,
                         steps=steps, warmup=warmup)
    best = min(probed, key=lambda r: r["ms_per_step"])
    default = next(r for r in probed if r["config"] == TRAIN_DEFAULT)
    return {"default": dict(TRAIN_DEFAULT), "tuned": best["config"],
            "default_ms": default["ms_per_step"],
            "tuned_ms": best["ms_per_step"],
            "candidates": probed}


def main(args=None):
    from repro.configs import get_config, reduced
    from repro.data import SyntheticLMDataset
    from repro.models.transformer import Model

    smoke = bool(getattr(args, "smoke", False))
    steps = 2 if smoke else 5
    warmup = 1
    W, bpw, seq = 2, 2, 32

    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg, remat=False, scan_chunk=16, loss_chunk=64)
    data = SyntheticLMDataset(cfg.vocab_size, seq, seed=0)

    algos = [a for a in requested_algos(args, default=FULL_ALGOS)
             if a in FULL_ALGOS]
    rows = []
    for algo in algos:
        # dense topologies over {0, BUCKETS}; compressed reducers only at
        # the bucketed setting (they consume the flat-buffer wire)
        grid = [(r, b) for r in REDUCERS for b in (0, BUCKETS)] \
            + [(r, BUCKETS) for r in COMPRESSED]
        for reducer, buckets in grid:
            # the Pallas tail only exists on dc_s3gd (ssgd has no
            # update tail to fuse) — skip the redundant axis there
            for uk in ((False, True) if algo == "dc_s3gd"
                       else (False,)):
                row = time_config(algo, reducer, uk, buckets, model,
                                  data, n_workers=W,
                                  batch_per_worker=bpw, steps=steps,
                                  warmup=warmup)
                rows.append(row)
                emit(f"step_time_{algo}_{reducer}"
                     f"{'_kernels' if uk else ''}_b{buckets}",
                     row["ms_per_step"] * 1e3,
                     f"reduce_ops={row['hlo_reduce_ops']};"
                     f"convert_ops={row['hlo_convert_ops']};"
                     f"wire_bytes={row.get('wire_bytes_per_step', '-')}")
        # quantized wire: the error-feedback residual absorbs the int8
        # rounding (repro.core.quant), so the same bucketed step runs
        # with a ~4x (dense) / ~400x (topk) smaller payload — one dense
        # and one compressed int8 row per algo
        if algo == "dc_s3gd":
            for reducer in ("mean_allreduce", "topk"):
                row = time_config(algo, reducer, False, BUCKETS, model,
                                  data, n_workers=W,
                                  batch_per_worker=bpw, steps=steps,
                                  warmup=warmup, comm_dtype="int8")
                rows.append(row)
                emit(f"step_time_{algo}_{reducer}_int8_b{BUCKETS}",
                     row["ms_per_step"] * 1e3,
                     f"wire_bytes={row.get('wire_bytes_per_step', '-')};"
                     f"compression={row.get('wire_compression', '-')}")

    # the elastic-transition cost rides along with the step-time grid:
    # one row, not a grid — the reshard is reducer-independent
    resize = resize_timing(model, data, batch_per_worker=bpw)
    emit("step_time_resize_w8_w7", resize["resize_ms"] * 1e3,
         f"rejit_first_step_ms={resize['rejit_first_step_ms']}")

    # roofline-driven autotune (repro.analysis.autotune): probe the
    # candidate bucket layouts INCLUDING the default, adopt the argmin —
    # tuned <= default by construction, and CI gates exactly that
    autotuned = autotune_entry(model, smoke=smoke, steps=steps,
                               warmup=warmup, n_workers=W,
                               batch_per_worker=bpw, seq=seq)
    emit("step_time_autotune_tuned", autotuned["tuned_ms"] * 1e3,
         f"default_ms={autotuned['default_ms']};"
         f"tuned={autotuned['tuned']}")

    if getattr(args, "json", False):
        out = {
            "bench": "step_time",
            "model": cfg.name,
            "n_workers": W, "batch_per_worker": bpw, "seq": seq,
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "smoke": smoke,
            "resize": resize,
            "autotune": autotuned,
            "rows": rows,
        }
        full_grid = tuple(algos) == FULL_ALGOS
        name = JSON_NAME if (not smoke and full_grid) else SMOKE_JSON_NAME
        Path(name).write_text(json.dumps(out, indent=2))
        print(f"# wrote {name} ({len(rows)} rows)")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    main(ap.parse_args())
