"""End-to-end training driver: argument parsing + an `Engine` call.

Runs any registered `DistributedOptimizer` (DC-S3GD, the SSGD / stale
baselines, the DC-ASGD simulator) for real steps on whatever devices
exist — a ~100M-param config on CPU for the example run, or the
production mesh on a pod (same code path; the mesh just grows).  The
algorithm, its local optimizer, reducer, compensator, and staleness
policy are all selected from config via `repro.core.registry`; the mesh,
sharding trees, jit, checkpointing, and step loop all live in
`repro.launch.engine.Engine` — this module knows no algorithm internals.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
      --reduced --steps 200 --workers 4 --batch-per-worker 8 --seq 128 \
      --algo dc_s3gd --reducer mean_allreduce --staleness fixed

``--resume`` reads the checkpoint's {algo, reducer, local_optimizer,
n_workers, staleness} metadata back instead of trusting the re-passed
flags (pre-metadata checkpoints fall back to the flags).  Passing an
explicit ``--workers`` that differs from the checkpoint's count performs
an **elastic resume**: the state is restored at the checkpoint's W and
resharded through `repro.cluster`'s collapse-to-consensus resize.
``--fault-schedule`` / ``--eject-skew`` make the run itself elastic
(scripted churn, straggler ejection — see docs/cluster.md).
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax

from repro.checkpoint import checkpoint_exists, checkpoint_meta
from repro.cluster.spec import ClusterSpec
from repro.configs import ARCHS, get_config, reduced
from repro.core import registry
from repro.core.types import DCS3GDConfig
from repro.data import SyntheticLMDataset, worker_batches
from repro.launch.compile_cache import use_compile_cache
from repro.launch.engine import CKPT_ALGO_KEYS, Engine
from repro.launch.mesh import mesh_for_spec
from repro.models.transformer import Model


def build_argparser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--algo", choices=registry.names(), default="dc_s3gd",
                    help="'stale' = DC-S3GD with lambda0=0 (no compensation)")
    ap.add_argument("--reducer", choices=registry.names(registry.REDUCER),
                    default="mean_allreduce",
                    help="cross-worker reduce topology (topk/randk/"
                         "powersgd = error-feedback compressed; need "
                         "--buckets > 0)")
    ap.add_argument("--gossip-neighbors", type=int, default=1,
                    help="ring neighbors per side for --reducer gossip")
    ap.add_argument("--compress-density", type=float, default=0.01,
                    help="kept fraction per bucket for --reducer "
                         "topk/randk")
    ap.add_argument("--compress-rank", type=int, default=4,
                    help="low-rank factor width for --reducer powersgd")
    ap.add_argument("--comm-dtype", default="float32",
                    choices=["float32", "bfloat16", "float16", "int8",
                             "fp8"],
                    help="wire dtype for the reducer payload (int8/fp8 "
                         "= quantized with one f32 scale per bucket "
                         "row; error feedback absorbs the error)")
    ap.add_argument("--local-optimizer", default=None,
                    choices=registry.names(registry.LOCAL_OPTIMIZER),
                    help="override cfg.local_optimizer")
    ap.add_argument("--staleness", default="fixed",
                    choices=registry.names(registry.STALENESS_POLICY),
                    help="stale-window policy (dynamic_ssp = skew threshold)")
    ap.add_argument("--ssp-threshold", type=int, default=4,
                    help="max per-worker step skew for --staleness "
                         "dynamic_ssp")
    ap.add_argument("--measure-skew", action="store_true",
                    help="drive the staleness policy from measured "
                         "wall-clock step times (syncs every step; see "
                         "Engine.fit) instead of only injected progress")
    ap.add_argument("--skew-warmup", type=int, default=1,
                    help="leading steps excluded from the measured-skew "
                         "virtual clock (the JIT compile spike is not a "
                         "skew signal); re-arms after every resize")
    ap.add_argument("--fault-schedule", type=Path, default=None,
                    help="JSON fault schedule (repro.cluster.faults): "
                         "scripted join/leave/eject/slowdown events make "
                         "the run elastic")
    ap.add_argument("--eject-skew", type=float, default=None,
                    help="eject a worker whose measured virtual-clock lag "
                         "exceeds this many steps persistently (needs "
                         "--measure-skew); None disables ejection")
    ap.add_argument("--eject-patience", type=int, default=3,
                    help="consecutive over-threshold observations before "
                         "an ejection fires")
    ap.add_argument("--min-workers", type=int, default=2,
                    help="the ejection policy never shrinks below this")
    ap.add_argument("--transition-log", type=Path, default=None,
                    help="write the membership transition log (JSON) here")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--workers", type=int, default=None,
                    help="worker count W (default 4; on --resume the "
                         "checkpoint's count — passing a DIFFERENT count "
                         "reshards the state through the elastic resize "
                         "path, e.g. a W=8 checkpoint resumed at 6)")
    ap.add_argument("--batch-per-worker", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--lambda0", type=float, default=0.2)
    ap.add_argument("--warmup-frac", type=float, default=0.15)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt", type=Path, default=None)
    ap.add_argument("--resume", type=Path, default=None)
    ap.add_argument("--metrics-out", type=Path, default=None)
    ap.add_argument("--use-kernels", action="store_true",
                    help="use the fused Pallas update path")
    ap.add_argument("--buckets", type=int, default=0,
                    help="pack comm state into this many contiguous "
                         "flat buckets (repro.parallel.buckets); 0 = "
                         "legacy per-leaf reduce/update")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffered bucket pipeline "
                         "(repro.parallel.pipeline): issue each step's "
                         "reduce at the tail, consume it at the next "
                         "step's head; needs --buckets > 0")
    ap.add_argument("--tuned-config", type=Path, default=None,
                    help="autotuner config blob (repro.analysis.autotune): "
                         "its train.tuned {buckets, plan_block} override "
                         "the flag defaults")
    ap.add_argument("--autotune", action="store_true",
                    help="run the train-side autotuner probe first and "
                         "adopt its tuned config (a few extra minutes)")
    ap.add_argument("--dense-after-join", type=int, default=0,
                    help="run this many steps on the dense wire after an "
                         "elastic join before re-enabling a compressed "
                         "(error-feedback) reducer — drains the joiner's "
                         "inherited residual in one step")
    ap.add_argument("--profile-dir", type=Path, default=None,
                    help="write a profiler trace of --profile-steps steps "
                         "after the first logged step here (host spans "
                         "train/repro.*, device ops named by the model's "
                         "scopes, which bench/scopes.py reads)")
    ap.add_argument("--profile-steps", type=int, default=3)
    return ap


def _adopt_resume_meta(args) -> None:
    """Checkpoint metadata wins over re-passed algorithm flags."""
    meta = checkpoint_meta(args.resume)
    adopted = {k: meta[k] for k in CKPT_ALGO_KEYS if meta.get(k) is not None}
    if not adopted:
        return
    args.algo = adopted.get("algo", args.algo)
    args.reducer = adopted.get("reducer", args.reducer)
    # reducer hyper-params (neighbors/groups/comm_dtype/density/rank)
    # recorded at save time rebuild the exact topology, not the defaults
    args.reducer_opts = adopted.get("reducer_opts", None)
    args.local_optimizer = adopted.get("local_optimizer",
                                       args.local_optimizer)
    args.staleness = adopted.get("staleness", args.staleness)
    args.ssp_threshold = int(adopted.get("ssp_threshold",
                                         args.ssp_threshold))
    args.workers = int(adopted.get("n_workers", args.workers))
    args.buckets = int(adopted.get("buckets", args.buckets) or 0)
    args.overlap = bool(adopted.get("overlap", args.overlap) or False)
    print(f"[train] resume metadata: {adopted}")


def _worker_mesh(n_workers: int, elastic: bool):
    """One DC-S3GD worker per device: a ``(data, model=1)`` mesh with
    ``data = gcd(W, devices)`` when more than one device is visible.  One
    device, or an elastic run (whose worker count changes under a fixed
    mesh), keeps the unsharded path."""
    if jax.device_count() == 1 or elastic:
        return None
    return mesh_for_spec(ClusterSpec.uniform(n_workers))


def run(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = Model(cfg, remat=True, moe_dense=args.reduced,
                  scan_chunk=64, loss_chunk=256)

    # an explicit --workers on resume is an elastic-resume request: the
    # state is restored at the CHECKPOINT's count, then resharded
    requested_workers = args.workers
    resuming = args.resume is not None and checkpoint_exists(args.resume)
    if resuming:
        _adopt_resume_meta(args)
    if args.workers is None:
        args.workers = 4
    resize_to = requested_workers if (
        resuming and requested_workers is not None
        and requested_workers != args.workers) else None

    dc_cfg = DCS3GDConfig(
        learning_rate=args.lr, momentum=args.momentum, lambda0=args.lambda0,
        warmup_steps=max(int(args.warmup_frac * args.steps), 1),
        total_steps=args.steps,
        local_optimizer=args.local_optimizer or "momentum",
        ssp_threshold=args.ssp_threshold,
        gossip_neighbors=args.gossip_neighbors,
        compress_density=args.compress_density,
        compress_rank=args.compress_rank,
        comm_dtype=args.comm_dtype,
    )

    # tuned config (repro.analysis.autotune): --tuned-config reads a
    # blob, --autotune probes inline; either way train.tuned overrides
    # the bucket layout flags
    plan_block = None
    tuned = None
    if getattr(args, "autotune", False):
        from repro.analysis.autotune import autotune
        tuned = autotune(smoke=True, skip_serve=True)["train"]["tuned"]
    elif getattr(args, "tuned_config", None) is not None:
        from repro.analysis.autotune import load_tuned
        tuned = load_tuned(args.tuned_config).get("train", {}).get("tuned")
    if tuned:
        args.buckets = int(tuned["buckets"])
        plan_block = tuned.get("plan_block")
        print(f"[train] autotuned: buckets={args.buckets} "
              f"plan_block={plan_block}")

    key = jax.random.PRNGKey(args.seed)
    n_params = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, key)))
    reducer = registry.make_reducer(args.reducer, dc_cfg,
                                    **(getattr(args, "reducer_opts", None)
                                       or {}))
    alg = registry.make(args.algo, dc_cfg, n_workers=args.workers,
                        reducer=reducer, staleness=args.staleness,
                        use_kernels=args.use_kernels, buckets=args.buckets,
                        overlap=args.overlap, plan_block=plan_block)
    elastic = (resize_to is not None or args.fault_schedule is not None
               or args.eject_skew is not None)
    engine = Engine(model, alg, mesh=_worker_mesh(args.workers, elastic))
    state = engine.init_state(key)

    data = SyntheticLMDataset(cfg.vocab_size, args.seq, seed=args.seed)

    start = 0
    if resuming:
        state = engine.restore(args.resume, state)
        start = int(state.step)
        print(f"[train] resumed from {args.resume} at step {start}")
        if resize_to is not None:
            # elastic resume: the SAME collapse-to-consensus code path as
            # a live resize — the resharded consensus is bitwise the
            # checkpoint's (tests/test_cluster.py pins this)
            from repro.cluster import rebuild_algorithm
            state = alg.resize_state(state, resize_to)
            alg = rebuild_algorithm(alg, resize_to)
            engine.alg = alg
            print(f"[train] elastic resume: resharded "
                  f"W={args.workers} -> W={resize_to}")
            args.workers = resize_to

    print(f"[train] {cfg.name} ({n_params/1e6:.1f}M params) algo={alg.name} "
          f"reducer={alg.reducer.name if hasattr(alg, 'reducer') else '-'} "
          f"staleness="
          f"{alg.staleness.name if hasattr(alg, 'staleness') else '-'} "
          f"W={args.workers} b={args.batch_per_worker} seq={args.seq}")

    membership = None
    if args.fault_schedule is not None or args.eject_skew is not None:
        from repro.cluster import FaultSchedule, Membership
        faults = FaultSchedule.from_json(args.fault_schedule) \
            if args.fault_schedule is not None else None
        membership = Membership(alg, faults=faults,
                                eject_threshold=args.eject_skew,
                                eject_patience=args.eject_patience,
                                min_workers=args.min_workers,
                                dense_after_join=args.dense_after_join)

    def batch_fn(it, n_workers=args.workers):
        return worker_batches(data, it, n_workers, args.batch_per_worker)

    state, history, wall = engine.fit(
        state, batch_fn, steps=args.steps, start=start,
        log_every=args.log_every, measure_skew=args.measure_skew,
        skew_warmup=args.skew_warmup, membership=membership,
        profile_dir=args.profile_dir, profile_steps=args.profile_steps)

    final_workers = membership.n_workers if membership is not None \
        else args.workers

    if args.ckpt:
        # engine.alg tracks membership transitions: the metadata records
        # the worker count the state actually has, not the t=0 flag
        engine.save(args.ckpt, state, step=args.steps)
        print(f"[train] checkpoint -> {args.ckpt}")

    result = {
        "arch": cfg.name, "algo": args.algo, "steps": args.steps,
        "workers": final_workers, "final_loss": history[-1]["loss"],
        # placement: the fewest devices any TrainState leaf spans (W on a
        # one-worker-per-device mesh, 1 on the unsharded path)
        "state_devices": min(len(x.sharding.device_set)
                             for x in jax.tree.leaves(state)),
        "wall_s": round(wall, 1),
        "tokens_per_s": round(args.steps * args.workers
                              * args.batch_per_worker * args.seq / wall, 1),
        "history": history,
    }
    if membership is not None:
        result["transitions"] = membership.log
        if args.transition_log is not None:
            args.transition_log.parent.mkdir(parents=True, exist_ok=True)
            args.transition_log.write_text(
                json.dumps(membership.log, indent=2))
            print(f"[train] transition log -> {args.transition_log}")
    if args.metrics_out:
        args.metrics_out.parent.mkdir(parents=True, exist_ok=True)
        args.metrics_out.write_text(json.dumps(result, indent=2))
    return result


def main(argv=None):
    use_compile_cache()
    run(build_argparser().parse_args(argv))


if __name__ == "__main__":
    main()
