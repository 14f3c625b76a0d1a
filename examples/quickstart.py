"""Quickstart: DC-S3GD on a small LM in ~30 seconds on CPU.

  PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax

from repro.configs import get_config, reduced
from repro.core import registry
from repro.core.types import DCS3GDConfig
from repro.data import SyntheticLMDataset, worker_batches
from repro.models.transformer import Model


def main():
    # 1. pick an architecture (any of the 10 assigned ones) at smoke scale
    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg, remat=False, scan_chunk=32, loss_chunk=64)
    params = model.init(jax.random.PRNGKey(0))
    print(f"model: {cfg.name}, "
          f"{sum(x.size for x in jax.tree.leaves(params))/1e6:.1f}M params")

    # 2. wrap it in the paper's optimizer: 4 decentralized workers,
    #    stale-synchronous with delay compensation (Algorithm 1).  The
    #    registry builds the algorithm from config — swap "dc_s3gd" for
    #    "ssgd" / "stale" / "dc_asgd", or pass reducer="gossip", and
    #    nothing else changes.
    dc_cfg = DCS3GDConfig(learning_rate=0.1, momentum=0.9, lambda0=0.2,
                          warmup_steps=10, total_steps=60)
    alg = registry.make("dc_s3gd", dc_cfg, n_workers=4)
    state = alg.init(params)
    step = jax.jit(lambda s, b: alg.step(s, b, loss_fn=model.loss))

    # 3. train — each worker sees a disjoint shard of the stream
    data = SyntheticLMDataset(cfg.vocab_size, seq_len=64, seed=0)
    for t in range(60):
        batch = worker_batches(data, t, alg.n_workers, per_worker=4)
        state, m = step(state, batch)
        if t % 10 == 0 or t == 59:
            print(f"step {t:3d}  loss={float(m['loss']):.4f}  "
                  f"lr={float(m['lr']):.3f}  lambda={float(m['lambda']):.3f}  "
                  f"|D_i|={float(m['distance_norm']):.2e}")

    # 4. evaluate with the averaged weights (paper Eq. 8)
    avg = alg.eval_params(state)
    eval_batch = {k: v[0] for k, v in
                  worker_batches(data, 999, 1, 8).items()}
    print("averaged-weight eval loss:", float(model.loss(avg, eval_batch)))


if __name__ == "__main__":
    main()
