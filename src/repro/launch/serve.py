"""Serving driver: argument parsing + the `repro.serve` subsystem.

Three modes:

* **one-shot** (default) — prefill a fixed batch of equal-length
  prompts, decode with the single-trace `jax.lax.scan` loop
  (`repro.serve.oneshot` via `Engine.generate`); the sampler is
  pluggable (`SAMPLERS`: greedy / categorical);
* **offline request file** (``--requests file.jsonl``) — continuous
  batching over the paged KV cache (`repro.serve.scheduler`): each line
  is a request (``{"prompt": [ids...], "gen": N}`` or synthetic
  ``{"prompt_len": P, "gen": N}``), admitted into free decode slots as
  capacity allows, evicted on completion;
* **synthetic Poisson load** (``--poisson RATE --num-requests N``) —
  the same scheduler under open-loop arrivals (exponential gaps at
  RATE req/s), staggered prompt/gen lengths.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --reduced --requests r.jsonl
  PYTHONPATH=src python -m repro.launch.serve --reduced --poisson 4 \
      --num-requests 12 --slots 4

To serve weights produced by the training driver, point ``--train-ckpt``
at a `repro.launch.train` checkpoint: the checkpoint's own
{algo, reducer, local_optimizer, n_workers, staleness} metadata rebuilds
the matching `DistributedOptimizer` (the flags are only fallbacks for
pre-metadata checkpoints), and its ``eval_params`` (e.g. the DC-S3GD
worker average, paper Eq. 8) become the served weights.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_pytree
from repro.configs import ARCHS, get_config, reduced
from repro.core import registry
from repro.launch.compile_cache import use_compile_cache
from repro.launch.engine import SAMPLERS, Engine, algorithm_for_checkpoint
from repro.models.transformer import Model
from repro.serve import Request, Scheduler


def generate(model: Model, params, prompts: jnp.ndarray, *, gen: int,
             temperature: float = 0.0, key=None, extra_batch=None,
             sampler=None):
    """prompts: (B, P) int32.  Returns (B, gen) generated ids.
    Thin wrapper over `Engine.generate` (the scan-based decode loop)."""
    return Engine(model).generate(params, prompts, gen=gen,
                                  temperature=temperature, key=key,
                                  extra_batch=extra_batch, sampler=sampler)


def params_from_train_ckpt(model: Model, path, *, algo: str, n_workers: int,
                           local_optimizer: str = "momentum",
                           reducer: str = "mean_allreduce"):
    """Restore a training checkpoint and extract the served weights through
    the algorithm recorded in its metadata (arguments are fallbacks for
    pre-metadata checkpoints)."""
    alg, resolved = algorithm_for_checkpoint(
        path, algo=algo, n_workers=n_workers,
        local_optimizer=local_optimizer, reducer=reducer)
    template = alg.init(model.init(jax.random.PRNGKey(0)))
    state = restore_pytree(path, template)
    return alg.eval_params(state), resolved


def load_requests(path: Path, vocab: int, default_gen: int,
                  seed: int = 0) -> list:
    """Parse a JSONL request file.  Lines carry either explicit token ids
    (``{"prompt": [...]}``)  or a synthetic length (``{"prompt_len": P}``,
    tokens drawn from a seeded PRNG); ``gen`` defaults per file."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i, line in enumerate(Path(path).read_text().splitlines()):
        line = line.strip()
        if not line:
            continue
        spec = json.loads(line)
        if "prompt" in spec:
            prompt = [int(t) for t in spec["prompt"]]
        else:
            prompt = rng.integers(0, vocab,
                                  int(spec["prompt_len"])).tolist()
        reqs.append(Request(rid=spec.get("id", i), prompt=prompt,
                            max_new=int(spec.get("gen", default_gen))))
    return reqs


def synthetic_requests(n: int, vocab: int, gen: int, seed: int = 0,
                       rng=None) -> list:
    """Staggered synthetic workload: prompt lengths cycle over a few
    buckets (bounding prefill compilations), gen lengths spread 1..gen.
    Pass ``rng`` to draw contents from a caller-owned stream (the Poisson
    mode keeps contents and arrivals independently seeded so neither
    perturbs the other)."""
    rng = np.random.default_rng(seed) if rng is None else rng
    p_lens = [8, 16, 24, 32]
    reqs = []
    for i in range(n):
        P = p_lens[i % len(p_lens)]
        g = 1 + int(rng.integers(0, gen))
        reqs.append(Request(rid=i, prompt=rng.integers(0, vocab, P).tolist(),
                            max_new=g))
    return reqs


def record_arrival_schedule(args, reqs, arrivals,
                            path=Path("BENCH_serve.json")) -> None:
    """Record the Poisson workload (stream seeds, per-request shape, the
    drawn arrival offsets) under the ``poisson`` key of
    ``BENCH_serve.json`` so a load run is exactly reproducible."""
    data = {}
    if path.exists():
        try:
            data = json.loads(path.read_text())
        except (json.JSONDecodeError, OSError):
            data = {}
    data["poisson"] = {
        "rate_req_s": args.poisson,
        "num_requests": len(reqs),
        "content_stream_seed": [args.seed, 0],
        "arrival_stream_seed": [args.seed, 1],
        "requests": [{"rid": r.rid, "prompt_len": len(r.prompt),
                      "gen": r.max_new} for r in reqs],
        "arrivals_s": [round(float(a), 6) for a in arrivals],
    }
    path.write_text(json.dumps(data, indent=2))
    print(f"[serve] arrival schedule recorded in {path}")


def run_scheduler(model, params, reqs, args, arrivals=None) -> None:
    sch = Scheduler(model, params, slots=args.slots, pages=args.pages,
                    page_size=args.page_size,
                    sampler=args.sampler, temperature=args.temperature,
                    seed=args.seed, use_kernel=args.paged_kernel,
                    decode_burst=args.decode_burst,
                    prefill_chunk=args.prefill_chunk,
                    prefix_cache=args.prefix_cache,
                    kv_dtype=args.kv_dtype)
    t0 = time.time()
    done = sch.run(reqs, arrivals=arrivals)
    wall = time.time() - t0
    summary = sch.latency_summary()
    toks = summary["tokens"]
    print(f"[serve] continuous batching: {len(done)} requests, "
          f"{toks} tokens in {wall:.1f}s ({toks / wall:.1f} tok/s), "
          f"slots={args.slots} pages={args.pages}x{args.page_size}")
    for k in ("p50_token_latency_s", "p95_token_latency_s",
              "p50_ttft_s", "p95_ttft_s",
              "mean_pool_utilization", "mean_internal_fragmentation",
              "preemptions", "prefill_chunks", "cow_copies",
              "prefix_hits", "prefix_hit_tokens", "prefix_evictions"):
        if k in summary:
            print(f"[serve]   {k} = {summary[k]:.4g}")
    for req in sorted(done, key=lambda r: r.rid)[:4]:
        print(f"[serve]   req {req.rid}: prompt={len(req.prompt)} "
              f"-> {len(req.out)} tokens {req.out[:8]}...")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--sampler", choices=sorted(SAMPLERS), default=None,
                    help="token sampler (default: greedy at temperature 0, "
                         "categorical above)")
    ap.add_argument("--seed", type=int, default=0)
    # continuous-batching modes (repro.serve.scheduler)
    ap.add_argument("--requests", type=Path, default=None,
                    help="JSONL request file -> offline continuous "
                         "batching over the paged KV cache")
    ap.add_argument("--poisson", type=float, default=None, metavar="RATE",
                    help="synthetic open-loop load: Poisson arrivals at "
                         "RATE req/s (with --num-requests)")
    ap.add_argument("--num-requests", type=int, default=12,
                    help="request count for --poisson")
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent decode slots (continuous batching)")
    ap.add_argument("--pages", type=int, default=96,
                    help="KV page pool size")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("bfloat16", "float32", "int8", "fp8"),
                    help="storage dtype of the paged KV pools (default: "
                         "compute dtype); int8/fp8 quantize per token "
                         "slot with f32 scales stored alongside the "
                         "pages — roughly 4x users per pool vs f32")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="Pallas paged-attention decode kernel (interpret "
                         "mode on CPU) instead of the XLA gather")
    ap.add_argument("--decode-burst", type=int, default=4,
                    help="decode steps scanned per dispatch (multi-step "
                         "scheduling; admissions/evictions land on burst "
                         "boundaries)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: forward prompts this many "
                         "tokens per step, interleaved with decode (0 = "
                         "whole-prompt prefill on join)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share committed prompt-prefix pages between "
                         "requests (copy-on-write on divergence; implies "
                         "chunked prefill, default chunk 4*page_size)")
    ap.add_argument("--tuned-config", type=Path, default=None,
                    help="autotuner config blob (repro.analysis.autotune): "
                         "its serve.tuned {page_size, decode_burst} "
                         "override the flag defaults")
    ap.add_argument("--autotune", action="store_true",
                    help="run the serve-side autotuner probe first and "
                         "adopt its tuned config")
    ap.add_argument("--train-ckpt", type=Path, default=None,
                    help="serve eval_params of a training checkpoint "
                         "(metadata selects the algorithm)")
    ap.add_argument("--algo", choices=registry.names(), default="dc_s3gd",
                    help="fallback for pre-metadata checkpoints")
    ap.add_argument("--workers", type=int, default=4,
                    help="fallback for pre-metadata checkpoints")
    ap.add_argument("--local-optimizer", default="momentum",
                    choices=registry.names(registry.LOCAL_OPTIMIZER),
                    help="fallback for pre-metadata checkpoints")
    ap.add_argument("--reducer", default="mean_allreduce",
                    choices=registry.names(registry.REDUCER),
                    help="fallback for pre-metadata checkpoints")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = Model(cfg, remat=False, scan_chunk=64)
    engine = Engine(model)
    key = jax.random.PRNGKey(args.seed)
    if args.train_ckpt is not None:
        params, resolved = params_from_train_ckpt(
            model, args.train_ckpt, algo=args.algo, n_workers=args.workers,
            local_optimizer=args.local_optimizer, reducer=args.reducer)
        print(f"[serve] weights from {args.train_ckpt} "
              f"(algo={resolved['algo']}, eval_params)")
    else:
        params = model.init(key)

    # tuned config (repro.analysis.autotune) — applies to the paged
    # scheduler modes; the pool size in pages stays the flag's, so a
    # bigger tuned page_size means a bigger pool in tokens
    tuned = None
    if args.autotune:
        from repro.analysis.autotune import autotune
        tuned = autotune(smoke=True, skip_train=True,
                         kv_dtype=args.kv_dtype)["serve"]["tuned"]
    elif args.tuned_config is not None:
        from repro.analysis.autotune import load_tuned
        tuned = load_tuned(args.tuned_config).get("serve", {}).get("tuned")
    if tuned:
        args.page_size = int(tuned["page_size"])
        args.decode_burst = int(tuned["decode_burst"])
        print(f"[serve] autotuned: page_size={args.page_size} "
              f"decode_burst={args.decode_burst}")

    if args.requests is not None:
        reqs = load_requests(args.requests, cfg.vocab_size, args.gen,
                             seed=args.seed)
        run_scheduler(model, params, reqs, args)
        return
    if args.poisson is not None:
        # independently seeded streams: prompt contents and arrival gaps
        # never read the same bits, so changing --num-requests (or the
        # rate) leaves every request's content identical
        content_rng = np.random.default_rng([args.seed, 0])
        arrival_rng = np.random.default_rng([args.seed, 1])
        reqs = synthetic_requests(args.num_requests, cfg.vocab_size,
                                  args.gen, rng=content_rng)
        gaps = arrival_rng.exponential(1.0 / max(args.poisson, 1e-6),
                                       len(reqs))
        arrivals = np.cumsum(gaps).tolist()
        record_arrival_schedule(args, reqs, arrivals)
        run_scheduler(model, params, reqs, args, arrivals=arrivals)
        return

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    extra = {}
    if cfg.vlm is not None:
        extra["patches"] = jax.random.normal(
            key, (args.batch, cfg.vlm.n_patches, cfg.d_model))
        total = args.prompt_len + cfg.vlm.n_patches
        extra["mrope_positions"] = jnp.tile(jnp.arange(total)[None], (3, 1))
    if cfg.encoder is not None:
        extra["frames"] = jax.random.normal(
            key, (args.batch, cfg.encoder.n_frames, cfg.d_model))

    t0 = time.time()
    ids = engine.generate(params, prompts, gen=args.gen,
                          sampler=args.sampler,
                          temperature=args.temperature, key=key,
                          extra_batch=extra)
    dt = time.time() - t0
    print(f"[serve] {cfg.name}: batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen} -> {ids.shape} in {dt:.1f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s)")
    print("[serve] first sequence:", ids[0].tolist())


if __name__ == "__main__":
    main()
