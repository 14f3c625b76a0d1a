"""Batched serving across architecture families: prefill a prompt batch,
decode greedily with the family-appropriate cache (KV / MLA latent /
SSM state / RG-LRU state / ring buffer).

  PYTHONPATH=src python examples/serve_batched.py
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.launch.serve import generate
from repro.models.transformer import Model


def demo(arch: str, gen: int = 8):
    cfg = reduced(get_config(arch))
    model = Model(cfg, remat=False, scan_chunk=32)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    prompts = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
    extra = {}
    if cfg.vlm is not None:
        extra["patches"] = jax.random.normal(
            key, (4, cfg.vlm.n_patches, cfg.d_model))
        total = 16 + cfg.vlm.n_patches
        extra["mrope_positions"] = jnp.tile(jnp.arange(total)[None], (3, 1))
    if cfg.encoder is not None:
        extra["frames"] = jax.random.normal(
            key, (4, cfg.encoder.n_frames, cfg.d_model))
    t0 = time.time()
    ids = generate(model, params, prompts, gen=gen, temperature=0.0,
                   extra_batch=extra)
    print(f"  {arch:25s} family={cfg.family:7s} -> {ids.shape} "
          f"in {time.time()-t0:4.1f}s  first: {ids[0, :6].tolist()}")


def main():
    print("[serve_batched] greedy decode, 4 sequences x 8 tokens each:")
    for arch in ("qwen3-0.6b",          # dense GQA + qk-norm
                 "minicpm3-4b",         # MLA latent cache
                 "falcon-mamba-7b",     # SSM O(1) state
                 "recurrentgemma-9b",   # RG-LRU + local-attention ring
                 "whisper-large-v3",    # enc-dec with cross-attention cache
                 "olmoe-1b-7b"):        # MoE (dropless EP dispatch at decode)
        demo(arch)
    demo_continuous()


def demo_continuous(arch: str = "qwen3-0.6b"):
    """Continuous batching (PR 5): a staggered request stream through the
    paged-KV scheduler — short requests evict early, waiting ones join
    mid-flight, pages recycle through the pool."""
    from repro.serve import Request, Scheduler
    cfg = reduced(get_config(arch))
    model = Model(cfg, remat=False, scan_chunk=32)
    params = model.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    reqs = [Request(rid=i,
                    prompt=jax.random.randint(
                        jax.random.fold_in(rng, i), (8 + 4 * (i % 3),),
                        0, cfg.vocab_size).tolist(),
                    max_new=3 + 3 * i) for i in range(6)]
    sch = Scheduler(model, params, slots=2, pages=48, page_size=8,
                    decode_burst=2)
    t0 = time.time()
    done = sch.run(reqs)
    s = sch.latency_summary()
    print(f"\n[serve_batched] continuous batching: {len(done)} staggered "
          f"requests over 2 slots in {time.time()-t0:.1f}s "
          f"({s['tokens']} tokens, {s['prefills']} prefill groups, "
          f"pool util {s.get('mean_pool_utilization', 0):.0%})")
    for r in sorted(done, key=lambda r: r.rid):
        print(f"  req {r.rid}: prompt={len(r.prompt):2d} -> "
              f"{len(r.out):2d} tokens")


if __name__ == "__main__":
    main()
