"""Paper Eq. 13/14 — the step-time model t_DC = max(tC, tAR) vs
t_SSGD = tC + tAR.

Two views:
  (a) analytic, from the dry-run roofline terms (when the JSONs exist):
      tC = max(compute, memory) per step; tAR = the DC delta all-reduce's
      share of the collective term.  Reported per hillclimb arch.
  (b) measured on CPU: wall-clock per step of the jitted DC-S3GD step vs
      the SSGD step at equal work.  On one CPU device collectives are
      memcpy-scale, so (b) mainly verifies both steps run at comparable
      cost (the overlap claim itself is structural — see EXPERIMENTS.md
      §Overlap for the HLO dependency-graph evidence).
"""
from __future__ import annotations

import glob
import json

import jax

from benchmarks.common import emit, timeit
from repro.configs import get_config, reduced
from repro.core import registry
from repro.core.types import DCS3GDConfig
from repro.data import SyntheticLMDataset, worker_batches
from repro.models.transformer import Model


def analytic_from_dryrun():
    rows = []
    for f in sorted(glob.glob("experiments/dryrun/*train_4k__pod__dc_s3gd.json")):
        r = json.load(open(f))
        if r.get("status") != "ok":
            continue
        ro = r["roofline"]
        tC = max(ro["compute_s"], ro["memory_s"])
        # the DC delta all-reduce: 2 x params_bytes/device / link_bw — the
        # only collective OUTSIDE the layer scan; approximate from breakdown
        tAR = ro["collective_s"]
        t_ssgd = tC + tAR
        t_dc = max(tC, tAR)
        rows.append((r["arch"], t_ssgd, t_dc))
        emit(f"eq13_14_{r['arch']}", 0.0,
             f"t_ssgd={t_ssgd*1e3:.0f}ms;t_dc_s3gd={t_dc*1e3:.0f}ms;"
             f"speedup={t_ssgd/t_dc:.2f}x")
    return rows


def measured_cpu(algos=("dc_s3gd", "ssgd"), reducer: str = "mean_allreduce"):
    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg, remat=False, scan_chunk=32, loss_chunk=64)
    params = model.init(jax.random.PRNGKey(0))
    ds = SyntheticLMDataset(cfg.vocab_size, 64, seed=0)
    dc_cfg = DCS3GDConfig(learning_rate=0.05)
    W = 4
    batch = worker_batches(ds, 0, W, 4)

    out = []
    for algo in algos:
        alg = registry.make(algo, dc_cfg, n_workers=W, reducer=reducer)
        state = alg.init(params)
        f = jax.jit(lambda s, b, alg=alg: alg.step(s, b,
                                                   loss_fn=model.loss))
        us = timeit(f, state, batch, iters=3)
        emit(f"eq13_14_measured_{algo}_step", us, "cpu 4-worker step")
        out.append(us)
    return tuple(out)


def main(args=None):
    from benchmarks.common import requested_algos
    analytic_from_dryrun()
    measured_cpu(algos=requested_algos(args, default=("dc_s3gd", "ssgd")),
                 reducer=getattr(args, "reducer", "mean_allreduce"))


if __name__ == "__main__":
    main()
