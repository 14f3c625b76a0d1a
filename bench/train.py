"""Training cells: DC-S3GD through the program's `Engine.jit_train_step`.

Set-up builds the state and the compiled step once, drives the first
``checked_steps`` steps through the window's own call and batch feed,
and keeps what the check compares: each step's loss, the per-leaf norm
of the momentum after step one (the first gradient as the optimizer got
it) and the per-leaf norm of the weights' change after the checked
steps.  The window then runs the same object for ``--seconds``; the
reference (`reference.py`) follows the checked steps after the window,
once the program's state is freed, over the loss of the
configuration's family (`families/`).
"""
from __future__ import annotations

import gc
import sys
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

import harness
import model as bm
import reference as R


@dataclass
class Job:
    conf: dict
    family: object
    traffic: dict
    cfg: object
    model: object
    alg: object
    engine: object
    devices: list
    template: object
    W: int
    B: int
    S: int
    hp: dict
    st_sh: object = None
    b_sh: object = None
    step_fn: object = None


@dataclass
class Readings:
    losses: list
    grad_norms: np.ndarray      # (leaves, W)
    change_norms: np.ndarray    # (leaves, W)


# steps dispatched ahead of the oldest unfinished one: enough queued work
# that a host pause leaves the chip busy (pauses of 1.25-1.9 s were seen
# on a v5e host; 12 steps hold 2.4 s of work at a 0.2-s step)
IN_FLIGHT = 12


@dataclass
class Window:
    steps: int = 0
    seconds: float = 0.0
    tokens: int = 0
    traced_steps: int = 0
    fetched: list = field(default_factory=list)   # perf_counter stamps


def trainer_args(traffic: dict):
    harness.add_program_path()
    from repro.launch import train as T
    return T.build_argparser().parse_args(traffic["trainer_flags"])


def build(conf: dict, traffic: dict, devices) -> Job:
    """The program's model, algorithm and engine, built as the trainer
    builds them from ``traffic['trainer_flags']`` (its defaults for every
    flag not given; the model's settings read from the trainer itself),
    with the benchmark's weights in place of the model's own init."""
    harness.add_program_path()
    from repro.cluster.spec import ClusterSpec
    from repro.core import registry
    from repro.core.types import DCS3GDConfig
    from repro.launch import train as T
    from repro.launch.engine import Engine
    from repro.launch.mesh import mesh_for_spec
    from repro.models.transformer import Model

    family = harness.family(conf)
    cfg = bm.program_config(conf)
    args = trainer_args(traffic)
    W, B, S = args.workers, args.batch_per_worker, args.seq
    model_kwargs = harness.launcher_model_kwargs(T, T.run, args)

    class BenchModel(Model):
        """The program's model; only its init is the benchmark's."""

        def init(self, key):
            return bm.make_weights(family, template, key)

    model = BenchModel(cfg, **model_kwargs)
    template = jax.eval_shape(lambda k: Model.init(model, k),
                              jax.random.PRNGKey(0))
    dc_cfg = DCS3GDConfig(
        learning_rate=args.lr, momentum=args.momentum, lambda0=args.lambda0,
        warmup_steps=max(int(args.warmup_frac * args.steps), 1),
        total_steps=args.steps,
        local_optimizer=args.local_optimizer or "momentum",
        ssp_threshold=args.ssp_threshold,
        gossip_neighbors=args.gossip_neighbors,
        compress_density=args.compress_density,
        compress_rank=args.compress_rank, comm_dtype=args.comm_dtype)
    hp = dict(traffic["optimizer"])
    ran = {"lr": dc_cfg.learning_rate, "momentum": dc_cfg.momentum,
           "lambda0": dc_cfg.lambda0, "weight_decay": dc_cfg.weight_decay,
           "weight_decay_k": dc_cfg.weight_decay_k,
           "warmup_steps": dc_cfg.warmup_steps,
           "total_steps": dc_cfg.total_steps,
           "local_optimizer": dc_cfg.local_optimizer,
           "schedule_weight_decay": dc_cfg.schedule_weight_decay,
           "lambda_norm": dc_cfg.lambda_norm, "nesterov": dc_cfg.nesterov,
           "state_dtype": dc_cfg.state_dtype,
           "microbatches": dc_cfg.microbatches}
    if ran != hp:
        raise ValueError(f"the trainer runs {ran}, the traffic file "
                         f"states {hp}")
    reducer = registry.make_reducer(args.reducer, dc_cfg)
    alg = registry.make(args.algo, dc_cfg, n_workers=W, reducer=reducer,
                        staleness=args.staleness,
                        use_kernels=args.use_kernels, buckets=args.buckets,
                        overlap=args.overlap)
    mesh = None if len(devices) == 1 else mesh_for_spec(
        ClusterSpec.uniform(W), devices=devices)
    engine = Engine(model, alg, mesh=mesh)
    return Job(conf, family, traffic, cfg, model, alg, engine,
               list(devices), template, W, B, S, hp)


def make_batch(seed: int, step: int, job: Job, half: bool = False) -> dict:
    """Step ``step``'s batch for every worker, on the host: uniform token
    ids, every row distinct.  ``half`` masks the second half of every
    row's labels (a planted fault: half the batch left out)."""
    rng = np.random.default_rng([int(seed), int(step)])
    t = rng.integers(0, job.cfg.vocab_size, (job.W, job.B, job.S + 1),
                     dtype=np.int32)
    labels = t[..., 1:].copy()
    if half:
        labels[..., job.S // 2:] = -1
    return {"tokens": t[..., :-1], "labels": labels}


def put(batch: dict, job: Job):
    if job.b_sh is not None:
        return jax.device_put(batch, job.b_sh)
    return jax.device_put(batch, job.devices[0])


def init_state(job: Job, key):
    """State from the seed in one jitted call, placed as the engine
    shards it."""
    def init(k):
        return job.alg.init(job.model.init(k))
    abstract = jax.eval_shape(init, key)
    abatch = jax.eval_shape(lambda: jax.tree.map(
        jnp.asarray, make_batch(0, 0, job)))
    job.st_sh, job.b_sh = job.engine.train_shardings(abstract, abatch)
    return jax.jit(init, out_shardings=job.st_sh)(key)


def _norms_fn():
    return jax.jit(lambda t: jnp.stack(R.leaf_norms(t)))


def _change_fn(job: Job):
    def change(params, key):
        w0 = bm.make_weights(job.family, job.template, key)
        return jnp.stack(R.leaf_norms(jax.tree.map(
            lambda p, w: p.astype(jnp.float32) - w[None].astype(jnp.float32),
            params, w0)))
    return jax.jit(change)


def checked_steps(job: Job, seed: int, key, *, half: bool = False):
    """Set-up: state and compiled step, then the first checked steps
    through the window's call.  Returns (state, readings)."""
    state = init_state(job, key)
    job.step_fn = job.engine.jit_train_step(state, make_batch(0, 0, job))
    n = job.traffic["checked_steps"]
    losses, grads = [], None
    for t in range(n):
        state, met = job.step_fn(state, put(make_batch(seed, t, job, half),
                                            job))
        losses.append(met["loss"])
        if t == 0:
            grads = _norms_fn()(state.opt["m"])
    change = _change_fn(job)(state.params, key)
    return state, Readings([float(x) for x in losses],
                           np.asarray(grads), np.asarray(change))


def run_window(job: Job, state, seed: int, seconds: float, *,
               trace_dir=None):
    """Dispatch steps on fresh batches for ``seconds``, at most
    ``IN_FLIGHT`` ahead of the oldest unfinished one, and end on the
    state being ready.  With ``trace_dir`` the
    profiler records ``trace_steps`` steps from 30 % of the window on."""
    win = Window()
    start_at = job.traffic["checked_steps"]
    pending = deque()
    n_trace = job.traffic["trace_steps"]
    tracing = False
    gc_pauses = harness.GcPauses()
    harness.quiet_gc(gc_pauses)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if trace_dir is not None and not tracing and win.traced_steps == 0 \
                and time.perf_counter() - t0 >= 0.3 * seconds:
            jax.block_until_ready(state)
            jax.profiler.start_trace(str(trace_dir))
            tracing = True
        with jax.profiler.TraceAnnotation("bench.batch"):
            batch = put(make_batch(seed, start_at + win.steps, job), job)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, met = job.step_fn(state, batch)
        pending.append(met["loss"])
        win.steps += 1
        if tracing:
            win.traced_steps += 1
        if len(pending) > IN_FLIGHT:
            with jax.profiler.TraceAnnotation("bench.fetch"):
                pending.popleft().block_until_ready()
            win.fetched.append(time.perf_counter())
        if tracing and win.traced_steps >= n_trace:
            with jax.profiler.TraceAnnotation("bench.fetch"):
                jax.block_until_ready(state)
            jax.profiler.stop_trace()
            tracing = False
    jax.block_until_ready(state)
    win.seconds = time.perf_counter() - t0
    harness.loud_gc(gc_pauses)
    if tracing:
        jax.profiler.stop_trace()
    win.tokens = win.steps * job.W * job.B * job.S
    gaps_s = np.diff(win.fetched) if len(win.fetched) > 1 else [0.0]
    print(f"bench: window {win.steps} steps in {win.seconds!r} s; between "
          f"fetches median {float(np.median(gaps_s))!r} s, longest "
          f"{float(np.max(gaps_s))!r} s; {gc_pauses}", file=sys.stderr)
    return state, win


def reference_readings(job: Job, seed: int, key, *, lowp: bool = False,
                       exchange: bool = True) -> Readings:
    """The reference's readings of the checked steps, on the same chips,
    one worker per chip (W > 1) or one chip."""
    rc = job.family.reference_config(job.conf)
    sh = None
    if job.W > 1 and len(job.devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(job.devices[:job.W]), ("w",))
        sh = NamedSharding(mesh, P("w"))

    def init(k):
        w = bm.make_weights(job.family, job.template, k)
        w = jax.tree.map(lambda x: jnp.broadcast_to(
            x.astype(jnp.float32)[None], (job.W,) + x.shape), w)
        return w, jax.tree.map(jnp.zeros_like, w)

    w, m = jax.jit(init, out_shardings=sh)(key)
    step = jax.jit(lambda w, m, lp, b, t: R.dc_step(
        w, m, lp, b, t, hp=job.hp, rc=rc, seq_loss=job.family.seq_loss,
        lowp=lowp, exchange=exchange),
        donate_argnums=(0, 1), out_shardings=(sh, sh, None, None))
    lr_prev = jnp.float32(0.0)
    losses, grads = [], None
    for t in range(job.traffic["checked_steps"]):
        b = make_batch(seed, t, job)
        b = jax.device_put(b, sh) if sh is not None else b
        w, m, loss, lr_prev = step(w, m, lr_prev, b, jnp.int32(t))
        losses.append(float(jnp.mean(loss)))
        if t == 0:
            grads = np.asarray(jnp.stack(R.leaf_norms(m)))
    del m
    change = np.asarray(_change_fn(job)(w, key))
    return Readings(losses, grads, change)


def gaps(prog: Readings, ref: Readings) -> dict:
    """The numbers compared: the worst relative loss gap over the checked
    steps, and by the worst (leaf, worker) the gap between the program's
    and the reference's norms of the first gradient and of the change,
    against the larger of that leaf's reference norm and the median
    leaf's.  Leaves whose reference gradient is under a thousandth of the
    median leaf's (moved by round-off alone) are left out of the
    change."""
    lp, lr = np.asarray(prog.losses), np.asarray(ref.losses)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))

    def worst(p, r, keep):
        med = np.median(r)
        rel = np.abs(p - r) / np.maximum(r, med)
        return float(np.max(rel[keep])) if keep.any() else 0.0

    g_r = ref.grad_norms
    keep = g_r >= 1e-3 * np.median(g_r)
    out = {"loss_gap": loss_gap,
           "grad_gap": worst(prog.grad_norms, g_r, np.ones_like(keep)),
           "change_gap": worst(prog.change_norms, ref.change_norms, keep)}
    if not all(np.isfinite(v) for v in out.values()) \
            or not np.all(np.isfinite(lp)):
        out = {k: float("inf") for k in out}
    return out


def run(conf, traffic, devices, *, seed, seconds, trace_dir, limits,
        t_start):
    """One run of a training cell: the record the metric readers read,
    with the checks (name, number, limit)."""
    key = harness.seed_key(seed)
    job = build(conf, traffic, devices)
    state, prog = checked_steps(job, seed, key)
    setup_s = time.perf_counter() - t_start
    state, win = run_window(job, state, seed, seconds, trace_dir=trace_dir)
    device = harness.device_info(devices)
    del state
    gc.collect()
    ref = reference_readings(job, seed, key)
    g = gaps(prog, ref)
    checks = [(k, g[k], limits[k]) for k in ("loss_gap", "grad_gap",
                                             "change_gap")]
    return {"kind": "train", "job": job, "window": win, "setup_s": setup_s,
            "device": device, "readings": prog, "reference": ref,
            "checks": checks, "attempted": win.steps, "failed": 0}


def calibrate(conf, traffic, devices, seed: int) -> dict:
    """The readings that set a training cell's limits, for one seed:
    the numbers compared for sound runs of the program, for the control
    (the reference computed in fp8 in the program's place) and for the
    planted faults (half the batch left out, in the program; the
    exchange between workers left out, in the reference).  No window."""
    key = harness.seed_key(seed)
    job = build(conf, traffic, devices)
    out = {}
    progs = {}
    for name, half in (("sound", False), ("half_batch", True)):
        state, progs[name] = checked_steps(job, seed, key, half=half)
        del state
        gc.collect()
    ref = reference_readings(job, seed, key)
    for name, p in progs.items():
        out[name] = gaps(p, ref)
    out["control"] = gaps(reference_readings(job, seed, key, lowp=True), ref)
    if job.W > 1:
        out["no_exchange"] = gaps(
            reference_readings(job, seed, key, exchange=False), ref)
    out["losses"] = {"program": progs["sound"].losses, "reference":
                     ref.losses}
    return out


def end_to_end(rec) -> dict:
    win = rec["window"]
    return {"train_tokens_per_s": win.tokens / win.seconds,
            "setup_s": rec["setup_s"]}
