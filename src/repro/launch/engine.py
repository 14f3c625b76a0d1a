"""The Engine — one object behind train, serve, and the dry-run.

`Engine` owns everything the launch layer used to hand-roll per driver:

* **mesh + sharding trees** — derived from the per-algorithm
  ``state_specs`` / ``batch_specs`` hooks (`repro.core.api.MeshAxes`);
  serving param/cache shardings come from the same partition rules
  (`repro.parallel.sharding`), so training and serving shard from one
  seam.  With ``mesh=None`` (CPU smoke scale) everything degrades to
  plain jit — the trajectories are unchanged;
* **jit** — train-step / prefill / decode compilation, with donation and
  in/out shardings attached when a mesh is present (inputs may be
  ``jax.ShapeDtypeStruct`` trees: the dry-run lowers without allocating);
* **checkpointing with metadata** — ``save`` records
  ``{algo, reducer, local_optimizer, n_workers, staleness,
  ssp_threshold}`` next to the state so ``restore`` sites can rebuild
  the matching algorithm instead of trusting re-passed flags
  (`algorithm_for_checkpoint`);
* **the step loop** — ``fit`` runs the jitted step over a batch function
  with logging and history collection; ``measure_skew=True`` times every
  step and feeds the implied per-worker progress to the staleness policy
  (`alg.observe_progress`) so ``dynamic_ssp`` trips on real skew;
* **generation** — delegated to the `repro.serve` subsystem:
  ``generate`` is the one-shot scan-loop case
  (`repro.serve.oneshot.OneShotGenerator`, compiled pairs cached on the
  Engine), and request streams run through the continuous-batching
  `repro.serve.scheduler.Scheduler` over the paged KV cache
  (``docs/serve.md``).

`train.py`, `serve.py`, and `dryrun.py` are argument parsing plus Engine
calls.
"""
from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpoint_meta, restore_pytree, save_pytree
from repro.core import registry
from repro.core.types import DCS3GDConfig
from repro.launch.mesh import make_axes
from repro.parallel import sharding as shd
# the samplers (and the scan-loop generate they feed) live in the serve
# subsystem now; re-exported here for the existing import sites
from repro.serve.oneshot import SAMPLERS, OneShotGenerator

PyTree = Any

# checkpoint metadata keys describing the algorithm that produced a state
CKPT_ALGO_KEYS = ("algo", "reducer", "reducer_opts", "local_optimizer",
                  "n_workers", "staleness", "ssp_threshold", "buckets",
                  "overlap")


def mesh_context(mesh):
    """Context manager activating a mesh (`jax.sharding.set_mesh`); a
    no-op context when ``mesh`` is None."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.sharding.set_mesh(mesh)


class Engine:
    """Mesh, shardings, jit, checkpoints, and loops for one (model, alg).

    ``alg`` may be None for pure serving engines; ``mesh`` may be None for
    single-host smoke runs (no shardings attached to jit).
    """

    def __init__(self, model, alg=None, *, mesh=None):
        self.model = model
        self.alg = alg
        self.mesh = mesh
        # fail fast on a worker count the mesh cannot carry — the same
        # mistake surfaced inside jit as an opaque XLA sharding error
        if alg is not None:
            shd.validate_worker_count(getattr(alg, "n_workers", None), mesh)
        # compiled (prefill, decode-loop) pairs for `generate`, keyed by
        # (shape, cache_len, sampler, ...) — rebuilt jits used to leak a
        # recompilation into EVERY repeated serve call
        self._oneshot: Optional[OneShotGenerator] = None
        # retrace bookkeeping (`retrace_stats`): the last `fit` loop's
        # jitted step and how often the loop had to re-jit it.  A
        # steady-state loop must keep both at 1/0 — the invariant
        # repro.analysis.lint's recompile pass gates on (the Engine.generate
        # per-call-retrace bug class, detectable for every entry point)
        self._fit_step_fn = None
        self._fit_rejits = 0

    # -- mesh / sharding seam ----------------------------------------------

    def mesh_axes(self):
        return None if self.mesh is None else make_axes(self.mesh)

    def mesh_context(self):
        return mesh_context(self.mesh)

    def _shard(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    def train_shardings(self, state: PyTree, batch: PyTree):
        """(state shardings, batch shardings) from the algorithm's own
        ``state_specs`` / ``batch_specs`` hooks; (None, None) without a
        mesh.  ``state``/``batch`` may be abstract."""
        axes = self.mesh_axes()
        if axes is None:
            return None, None
        cfg = self.model.cfg
        return (self._shard(self.alg.state_specs(cfg, state, axes)),
                self._shard(self.alg.batch_specs(cfg, batch, axes)))

    def _data_axes(self, global_batch: int):
        """Serving batch axis: worker mesh axes when they divide the batch
        (long_500k has global_batch=1: must stay replicated)."""
        axes = self.mesh_axes()
        total = 1
        for a in axes.worker:
            total *= self.mesh.shape[a]
        return axes.worker_spec if global_batch % total == 0 else None

    def serve_shardings(self, params: PyTree, *, global_batch: int,
                        batch: Optional[PyTree] = None,
                        cache: Optional[PyTree] = None):
        """Param (+ batch / cache) shardings for serving — the same
        partition rules as training, minus the worker axis."""
        axes = self.mesh_axes()
        if axes is None:
            return None, None, None
        cfg = self.model.cfg
        da = self._data_axes(global_batch)
        p_sh = self._shard(shd.param_specs(cfg, params,
                                           model_size=axes.model_size,
                                           worker_axes=None))
        b_sh = None if batch is None else self._shard(
            shd.batch_specs(cfg, batch, data_axes=da))
        c_sh = None if cache is None else self._shard(
            shd.cache_specs(cfg, cache, model_size=axes.model_size,
                            data_axes=da))
        return p_sh, b_sh, c_sh

    # -- training -----------------------------------------------------------

    def init_state(self, key) -> PyTree:
        """The initial `TrainState`.  With a mesh, ``init`` is jitted with
        the state shardings as its outputs, so every worker's replica is
        created on its own devices (none passes through device 0)."""
        def init(k):
            return self.alg.init(self.model.init(k))
        if self.mesh is None:
            return init(key)
        abstract = jax.eval_shape(init, key)
        st_sh = self._shard(self.alg.state_specs(self.model.cfg, abstract,
                                                 self.mesh_axes()))
        return jax.jit(init, out_shardings=st_sh)(key)

    def jit_train_step(self, state: Optional[PyTree] = None,
                       batch: Optional[PyTree] = None, *,
                       donate: bool = True):
        """The jitted training step.  With a mesh, ``state``/``batch``
        (possibly abstract) are required to derive the sharding trees."""
        step = partial(self.alg.step, loss_fn=self.model.loss)
        donate_argnums = (0,) if donate else ()
        if self.mesh is None:
            return jax.jit(step, donate_argnums=donate_argnums)
        st_sh, b_sh = self.train_shardings(state, batch)
        return jax.jit(step, in_shardings=(st_sh, b_sh),
                       out_shardings=(st_sh, None),
                       donate_argnums=donate_argnums)

    def lower_train_step(self, state: PyTree, batch: PyTree, *,
                         donate: bool = True):
        """Lower (without compiling) the jitted train step for the given
        inputs — ``state``/``batch`` may be ``jax.ShapeDtypeStruct``
        trees, so no buffers are allocated.  This is the substrate the
        compiled-program passes in `repro.analysis.lint` read: the
        returned ``Lowered`` exposes the StableHLO text (donation
        aliasing, host callbacks, converts, fences, collectives)."""
        return self.jit_train_step(state, batch,
                                   donate=donate).lower(state, batch)

    def retrace_stats(self) -> dict:
        """Jit cache-miss counters for the steady-state entry points:
        ``fit_cache_size`` (traces taken by the last ``fit`` loop's step
        — 1 in steady state), ``fit_rejits`` (loop-level re-jits; > 0
        only across elastic transitions), ``generate_cache_size``
        (compiled pairs cached by ``generate``).  `repro.analysis.lint`'s
        recompile pass fails a loop whose counters grow with the
        iteration count."""
        fn = self._fit_step_fn
        return {
            "fit_cache_size":
                None if fn is None else int(fn._cache_size()),
            "fit_rejits": self._fit_rejits,
            "generate_cache_size":
                0 if self._oneshot is None else self._oneshot.cache_size,
        }

    @property
    def fit_cache_size(self) -> Optional[int]:
        return self.retrace_stats()["fit_cache_size"]

    @property
    def generate_cache_size(self) -> int:
        return self.retrace_stats()["generate_cache_size"]

    def fit(self, state: PyTree, batch_fn: Callable[..., PyTree], *,
            steps: int, start: int = 0, log_every: int = 10,
            verbose: bool = True, measure_skew: bool = False,
            skew_probe: Optional[Callable[[int, float], Any]] = None,
            skew_warmup: int = 1, membership=None,
            profile_dir=None, profile_steps: int = 0
            ) -> Tuple[PyTree, list, float]:
        """Run the step loop; returns (state, metric history, wall s).

        The loop stays on jax's async dispatch queue: non-logging
        iterations never touch the device-resident ``metrics`` (no
        ``float``/``block_until_ready`` — a per-step host sync would
        serialize dispatch against compute and hide nothing).  On
        ``log_every`` boundaries the whole metrics dict is fetched with
        ONE ``jax.device_get`` (which blocks on just that step).

        ``measure_skew=True`` (train ``--measure-skew``) drives the
        staleness policy from **measured wall-clock step times** instead
        of only host-injected observations: each step is synced and
        timed, and every worker's progress counter advances by the steps
        it would have completed free-running within the measured wall
        step (``max(durs) / durs[w]``) before being fed to
        ``alg.observe_progress`` — so ``dynamic_ssp`` trips on real
        skew.  On a revoked step (``ssp_admit == 0``) the measured
        counters collapse to the leader, mirroring the policy's own
        sync semantics (`repro.core.staleness`): a transient slowdown
        costs ONE sync step, not a permanent offset.  In the lockstep
        single-host simulation every worker shares the measured step
        time (skew 0 — correct: lockstep HAS no skew);
        ``skew_probe(it, dt) -> per-worker durations`` is the seam a
        heterogeneous deployment (or a test) plugs real per-worker
        timings into (a non-positive duration means a stalled worker:
        its counter simply stops advancing).  The per-step sync this
        needs serializes dispatch — only paid behind the flag.

        ``skew_warmup`` excludes that many leading steps from the
        virtual-clock advance: the first step's measured duration is
        dominated by JIT compilation, not worker speed, and feeding the
        spike into the skew signal made ``dynamic_ssp`` (and the
        ejection policy) trigger on compilation.  The exclusion window
        re-arms after every membership transition — a resize re-jits,
        so the next step carries a fresh compile spike.

        ``membership`` (a `repro.cluster.Membership`) makes the run
        elastic: scripted fault events and queued straggler ejections
        are polled at every step boundary and applied as a
        collapse-to-consensus resize (``alg.resize_state`` +
        `repro.cluster.membership.rebuild_algorithm`), after which the
        step re-jits at the new worker count.  Elastic runs call
        ``batch_fn(it, n_workers)`` — the batch must follow the live
        worker count — and feed measured per-worker progress to the
        controller's ejection policy (under ``measure_skew``, which
        works here even for the stateless ``fixed`` staleness policy).

        Every step runs under ``StepTraceAnnotation("train")``, its batch
        making under ``repro.batch`` and the logging fetch under
        ``repro.fetch``: host spans on the profiler's clock.  With
        ``profile_dir`` the profiler records ``profile_steps`` steps
        after the first logged one (train ``--profile-dir``)."""
        elastic = membership is not None
        if elastic:
            self.alg = membership.alg
        cur_w = getattr(self.alg, "n_workers", 1)

        def make_batch(it):
            return batch_fn(it, cur_w) if elastic else batch_fn(it)

        def stateful_policy():
            return (self.alg is not None
                    and hasattr(self.alg, "observe_progress")
                    and not getattr(getattr(self.alg, "staleness", None),
                                    "stateless", True))

        with jax.profiler.TraceAnnotation("repro.batch"):
            batch = make_batch(start) if steps > start else None
        step_fn = self.jit_train_step(state, batch)
        self._fit_step_fn, self._fit_rejits = step_fn, 0
        stateful = stateful_policy()
        measuring = measure_skew and (stateful or elastic)
        n_workers = cur_w if measuring else 0
        vprogress = [0.0] * n_workers  # measured free-running step counts
        warmup = max(int(skew_warmup), 0)
        warm_until = start + warmup    # steps below this: compile spike
        history = []
        trace_stop = None   # the step after which the profiler stops
        t0 = time.time()
        for it in range(start, steps):
            if profile_dir is not None and trace_stop is None and history:
                # the first logged step has synced: trace from here
                jax.profiler.start_trace(str(profile_dir))
                trace_stop = it + max(profile_steps, 1) - 1
            with jax.profiler.StepTraceAnnotation("train", step_num=it):
                rejit = False
                if elastic:
                    events = membership.poll(it)
                    if events:
                        state, rejit = membership.apply(events, state, step=it)
                        if rejit:
                            self.alg = membership.alg
                            cur_w = membership.n_workers
                            stateful = stateful_policy()
                            n_workers = cur_w if measuring else 0
                            # the transition is a barrier: everyone leaves it
                            # in lockstep at the leader's virtual clock
                            vprogress = [max(vprogress, default=0.0)] \
                                * n_workers
                            # re-jit => a fresh compile spike on the next
                            # step: exclude it like the step-0 one
                            warm_until = it + warmup
                if it != start or rejit:
                    with jax.profiler.TraceAnnotation("repro.batch"):
                        batch = make_batch(it)
                if rejit:
                    step_fn = self.jit_train_step(state, batch)
                    self._fit_step_fn = step_fn
                    self._fit_rejits += 1
                ts = time.perf_counter()
                state, metrics = step_fn(state, batch)
                if measuring:
                    # ONE host round-trip per measured step: when the policy
                    # is stateful the admit flag must come to the host anyway,
                    # so that device_get IS the timing sync — a separate
                    # block_until_ready before it would pay a second
                    # dispatch-queue drain for nothing (the fit metric fetch
                    # the lint host-sync audit flagged)
                    if stateful:
                        admit = float(jax.device_get(
                            metrics.get("ssp_admit", 1.0)))
                    else:
                        jax.block_until_ready(metrics)
                        admit = 1.0
                    dt = time.perf_counter() - ts
                    if it >= warm_until:
                        durs = list(skew_probe(it, dt)) \
                            if skew_probe is not None else [dt] * n_workers
                        assert len(durs) == n_workers, (len(durs), n_workers)
                        slow = membership.slowdown_factors(it) if elastic \
                            else None
                        if slow is not None:
                            durs = [d * f for d, f in zip(durs, slow)]
                        if stateful and admit == 0.0:
                            # the policy revoked the window and did its
                            # blocking pull: the sync resolved the skew, so
                            # the measured counters collapse to the leader
                            vprogress = [max(vprogress)] * n_workers
                        wall = max(durs)
                        vprogress = [p + (wall / d if d > 0 else 0.0)
                                     for p, d in zip(vprogress, durs)]
                    progress = [int(p) for p in vprogress]
                    if stateful:
                        state = self.alg.observe_progress(state, progress)
                    if elastic:
                        membership.observe_progress(it, vprogress)
                if it % log_every == 0 or it == steps - 1:
                    with jax.profiler.TraceAnnotation("repro.fetch"):
                        m = {k: float(v)
                             for k, v in jax.device_get(metrics).items()}
                    m["step"] = it
                    m["wall_s"] = time.time() - t0
                    if measuring:
                        m["measured_skew"] = max(progress) - min(progress)
                    if elastic:
                        m["n_workers"] = cur_w
                    history.append(m)
                    if verbose:
                        extra = ""
                        if "distance_norm" in m:
                            extra = (f" |D|={m['distance_norm']:.2e} "
                                     f"lam={m.get('lambda', 0):.3f}")
                        print(f"[train] step {it:5d} loss={m['loss']:.4f} "
                              f"lr={m['lr']:.4f}{extra}")
            if it == trace_stop:
                jax.block_until_ready(state)
                jax.profiler.stop_trace()
        if trace_stop is not None and trace_stop >= steps:
            jax.block_until_ready(state)
            jax.profiler.stop_trace()
        return state, history, time.time() - t0

    # -- checkpointing with metadata -----------------------------------------

    def ckpt_meta(self) -> dict:
        alg = self.alg
        return {
            "algo": alg.name,
            "n_workers": getattr(alg, "n_workers", None),
            "reducer": getattr(getattr(alg, "reducer", None), "name", None),
            # reducer hyper-parameters travel with the reducer name — a
            # `hierarchical groups=4` or `gossip neighbors=2` (or a
            # compressed `topk density=0.05`) run restored with only the
            # name silently rebuilt with the DEFAULT topology: a
            # wrong-mixing-matrix resume no shape check catches
            "reducer_opts": getattr(
                getattr(alg, "reducer", None), "hparams", None),
            "local_optimizer": getattr(
                getattr(alg, "local_optimizer", None), "name", None),
            "staleness": getattr(
                getattr(alg, "staleness", None), "name", None),
            # policy hyper-params travel with the policy name — a resumed
            # dynamic_ssp run must get the trained threshold back, not
            # whatever the flag defaults to
            "ssp_threshold": getattr(
                getattr(alg, "staleness", None), "threshold", None),
            # bucketing changes the comm-state STRUCTURE (flat buffers vs
            # the per-leaf tree): restore sites must rebuild with the same
            # plan or the template won't match the checkpoint
            "buckets": getattr(alg, "buckets", None),
            # the pipelined schedule carries in-flight buckets in
            # comm["pipeline"] — restore sites must rebuild with overlap
            # on or the state template won't match the checkpoint
            "overlap": getattr(alg, "overlap", None),
        }

    def save(self, path, state: PyTree, *, step: Optional[int] = None):
        """Save the state with the algorithm metadata restore sites need."""
        return save_pytree(path, state, step=step,
                           extra_meta=self.ckpt_meta())

    def restore(self, path, state: PyTree) -> PyTree:
        return restore_pytree(path, state)

    # -- generation (serve) ---------------------------------------------------

    def generate(self, params: PyTree, prompts: jnp.ndarray, *, gen: int,
                 sampler: Optional[str] = None, temperature: float = 0.0,
                 key=None, extra_batch: Optional[dict] = None,
                 cache_len: Optional[int] = None) -> jnp.ndarray:
        """prompts: (B, P) int32 -> (B, gen) generated ids.

        The trivial one-shot case of the serve subsystem
        (`repro.serve.oneshot.OneShotGenerator`): one prefill trace plus
        ONE `jax.lax.scan` trace for the whole decode loop, with the
        compiled pair cached on the Engine keyed by (shape, cache_len,
        sampler) — repeated calls with the same signature reuse the
        executables instead of re-tracing.  ``sampler`` is a `SAMPLERS`
        name; by default greedy at ``temperature <= 0`` and categorical
        above.  For request *streams* (continuous batching, paged KV) use
        `repro.serve.scheduler.Scheduler`.
        """
        if self._oneshot is None:
            self._oneshot = OneShotGenerator(self.model)
        return self._oneshot(params, prompts, gen=gen, sampler=sampler,
                             temperature=temperature, key=key,
                             extra_batch=extra_batch, cache_len=cache_len)


# ---------------------------------------------------------------------------
# rebuilding the algorithm a checkpoint was trained with
# ---------------------------------------------------------------------------


def algorithm_for_checkpoint(path, *, algo: str = "dc_s3gd",
                             n_workers: int = 1,
                             local_optimizer: str = "momentum",
                             reducer: str = "mean_allreduce",
                             reducer_opts: Optional[dict] = None,
                             staleness: str = "fixed",
                             ssp_threshold: int = 4,
                             buckets: int = 0,
                             overlap: bool = False,
                             dc_cfg: Optional[DCS3GDConfig] = None
                             ) -> Tuple[Any, dict]:
    """Build the `DistributedOptimizer` matching a training checkpoint.

    Metadata recorded by `Engine.save` wins; the keyword arguments are
    fallbacks for pre-metadata checkpoints.  Returns (algorithm, the
    resolved {algo, reducer, local_optimizer, n_workers, staleness}).
    Before metadata, a mismatched ``--local-optimizer`` silently restored
    into wrong-shaped opt slots cast by the template — now the template is
    built from what actually trained.  ``reducer_opts`` (the reducer's
    recorded ``hparams`` — neighbors, groups, comm_dtype, density, rank)
    rebuild the exact topology/compressor, not the flag defaults.
    """
    meta = checkpoint_meta(path)
    resolved = {"algo": algo, "n_workers": n_workers,
                "local_optimizer": local_optimizer, "reducer": reducer,
                "reducer_opts": reducer_opts,
                "staleness": staleness, "ssp_threshold": ssp_threshold,
                "buckets": buckets, "overlap": overlap}
    for k in CKPT_ALGO_KEYS:
        if meta.get(k) is not None:
            resolved[k] = meta[k]
    cfg = dc_cfg if dc_cfg is not None else \
        DCS3GDConfig(local_optimizer=resolved["local_optimizer"],
                     ssp_threshold=int(resolved["ssp_threshold"]))
    red = registry.make_reducer(resolved["reducer"], cfg,
                                **(resolved["reducer_opts"] or {}))
    alg = registry.make(resolved["algo"], cfg,
                        n_workers=int(resolved["n_workers"]),
                        local_optimizer=resolved["local_optimizer"],
                        reducer=red,
                        staleness=resolved["staleness"],
                        buckets=int(resolved["buckets"] or 0),
                        overlap=bool(resolved["overlap"] or False))
    return alg, resolved
