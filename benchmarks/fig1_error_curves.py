"""Paper Figure 1 analogue — top-1 training error curves per (N, batch).

Writes experiments/fig1_curves.csv with columns
(algo, n_workers, global_batch, step, train_loss, train_err) for the LM
task; the shapes of the curves (warm-up plateau, stale divergence, DC
recovery) are the CPU-scale analogue of the paper's figure.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.common import emit, requested_algos
from repro.configs import get_config, reduced
from repro.core import registry
from repro.core.types import DCS3GDConfig
from repro.data import SyntheticLMDataset, worker_batches
from repro.models.transformer import Model

OUT = Path("experiments/fig1_curves.csv")


def run_curve(algo: str, n_workers: int, steps: int = 60, bpw: int = 4,
              seq: int = 64, lr: float = 0.3,
              reducer: str = "mean_allreduce"):
    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg, remat=False, scan_chunk=32, loss_chunk=64)
    params = model.init(jax.random.PRNGKey(0))
    ds = SyntheticLMDataset(cfg.vocab_size, seq, seed=0)
    dc_cfg = DCS3GDConfig(learning_rate=lr, momentum=0.9, lambda0=0.2,
                          weight_decay=0.0,
                          warmup_steps=steps // 6, total_steps=steps)
    alg = registry.make(algo, dc_cfg, n_workers=n_workers, reducer=reducer)
    state = alg.init(params)
    step = jax.jit(lambda s, b: alg.step(s, b, loss_fn=model.loss))
    curve = []
    for t in range(steps):
        state, m = step(state, worker_batches(ds, t, n_workers, bpw))
        curve.append((t, float(m["loss"])))
    return curve


def main(args=None):
    OUT.parent.mkdir(parents=True, exist_ok=True)
    reducer = getattr(args, "reducer", "mean_allreduce")
    lines = ["algo,n_workers,global_batch,step,train_loss"]
    final = {}
    for algo in requested_algos(args):
        for W in (2, 8):
            curve = run_curve(algo, W, reducer=reducer)
            for t, loss in curve:
                lines.append(f"{algo},{W},{W*4},{t},{loss:.5f}")
            final[(algo, W)] = curve[-1][1]
            emit(f"fig1_{algo}_w{W}", 0.0, f"final_loss={curve[-1][1]:.4f}")
    OUT.write_text("\n".join(lines))
    emit("fig1_csv", 0.0, str(OUT))
    return final


if __name__ == "__main__":
    main()
