"""DC-S3GD — the paper's contribution (Algorithm 1), JAX/TPU-native.

Decentralized stale-synchronous SGD with delay compensation:

* every worker keeps its own weights ``w_i`` — expressed as a leading
  worker axis ``W`` on every parameter/optimizer leaf, sharded over the
  (``pod``, ``data``) mesh axes;
* the all-reduce of the *previous* update ``Δw^{t-1}`` (``MPI_Iallreduce``
  in the paper) is the pluggable `Reducer` applied to the carried
  ``delta_prev`` — it has **no data dependency** on this step's gradients,
  so XLA's latency-hiding scheduler overlaps it with the forward/backward
  pass.  The paper's ``MPI_Wait`` is the dependency of ``D_i`` on that
  reduction;
* the staleness error is compensated by the pluggable `Compensator`
  (pseudo-Hessian correction, `repro.core.correction`), and weights move
  to the average while applying the corrected local update in one fused
  operation (Eq. 12).

The algorithm is the `DCS3GD` class — a thin composition of a
`LocalOptimizer`, a `Reducer`, a `Compensator`, and a `StalenessPolicy`
over the generic `TrainState` (params / opt / comm / step), registered as
``"dc_s3gd"`` (and, with compensation disabled, ``"stale"``) in
`repro.core.registry`.  It declares its own sharding through the
``state_specs`` / ``batch_specs`` hooks: every state leaf carries the
leading worker axes of the `MeshAxes` it is handed.

Algorithm 1 line-by-line mapping (comments in :meth:`DCS3GD.step`).

The first iteration of Algorithm 1 (plain step before the loop) is
reproduced by initializing ``delta_prev = 0``: then ``Δ̄w = 0``, ``D_i = 0``,
the correction vanishes and the step degenerates to plain momentum SGD —
identical on all workers, exactly the algorithm's prologue.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import registry
from repro.core.api import LossFn, MeshAxes, Metrics, TrainState
from repro.core.types import DCS3GDConfig
from repro.optim import local as local_opt
from repro.optim.schedules import linear_warmup_linear_decay
from repro.parallel import sharding as shd

PyTree = Any


def replicate_for_workers(params: PyTree, n_workers: int) -> PyTree:
    """w_i = w̄ for every worker (Algorithm 1 'Initialize')."""
    return jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_workers,) + p.shape), params)


def schedules(step, cfg: DCS3GDConfig):
    lr = linear_warmup_linear_decay(step, peak=cfg.learning_rate,
                                    warmup_steps=cfg.warmup_steps,
                                    total_steps=cfg.total_steps) \
        if cfg.total_steps > 1 else jnp.float32(cfg.learning_rate)
    wd_peak = cfg.weight_decay_k * cfg.weight_decay
    if cfg.schedule_weight_decay and cfg.total_steps > 1:
        wd = linear_warmup_linear_decay(step, peak=wd_peak,
                                        warmup_steps=cfg.warmup_steps,
                                        total_steps=cfg.total_steps)
    else:
        wd = jnp.float32(wd_peak)
    return lr, wd


@registry.register(registry.ALGORITHM, "dc_s3gd")
class DCS3GD:
    """Algorithm 1 as a composition of protocol pieces.

    ``local_optimizer`` / ``reducer`` / ``compensator`` / ``staleness``
    accept a registered name or an object; defaults come from ``cfg``
    (``cfg.local_optimizer``, mean all-reduce, Eq. 10+17 compensation,
    fixed one-step window).  ``use_kernels`` routes the
    correction+momentum+Eq.12 tail through the fused Pallas kernels
    (`repro.kernels`) — momentum + global-lambda mode only.

    ``buckets > 0`` routes the hot path through a
    `repro.parallel.buckets.BucketPlan`: the carried ``delta_prev`` (or
    the mixed weights, for ``reduces_weights`` topologies) lives in a few
    contiguous flat buffers, reducers run once per bucket, and the fused
    tail launches one kernel per bucket.  ``buckets=0`` (default) is the
    legacy per-leaf path; trajectories are pinned against it (see
    ``docs/perf.md``).
    """

    name = "dc_s3gd"

    def __init__(self, cfg: DCS3GDConfig, *, n_workers: int = 1,
                 local_optimizer=None, reducer=None, compensator=None,
                 staleness=None, use_kernels: bool = False,
                 buckets: Optional[int] = None,
                 overlap: Optional[bool] = None,
                 plan_block: Optional[int] = None):
        self.cfg = cfg
        self.n_workers = n_workers
        self.local_optimizer = (
            local_opt.from_config(cfg) if local_optimizer is None
            else registry.make_local_optimizer(local_optimizer, cfg))
        self.reducer = registry.make_reducer(
            "mean_allreduce" if reducer is None else reducer, cfg)
        self.compensator = registry.make_compensator(
            "dc" if compensator is None else compensator, cfg)
        self.staleness = registry.make_staleness_policy(
            "fixed" if staleness is None else staleness, cfg)
        self.use_kernels = use_kernels
        # compressed reducers with a fused Pallas body share the knob:
        # one flag routes both the tail and the compression through kernels
        if use_kernels and hasattr(self.reducer, "use_kernels"):
            self.reducer.use_kernels = True
        # flat-buffer comm bucketing (repro.parallel.buckets): >0 packs the
        # wire state + fused tail into that many contiguous buckets; 0 is
        # the legacy per-leaf path
        self.buckets = int(cfg.buckets if buckets is None else buckets)
        # bucket padding granularity (multiple of the fused Pallas
        # BLOCK); None = the kernel default — the autotuner's train-side
        # block knob (repro.analysis.autotune)
        self.plan_block = None if plan_block is None else int(plan_block)
        # double-buffered bucket pipeline (repro.parallel.pipeline): issue
        # the next reduce at the end of each step, consume the landed one
        # at the top — bitwise the inline schedule, structurally overlapped
        self.overlap = bool(overlap or False)
        if self.overlap:
            from repro.parallel import pipeline as PL
            PL.validate(buckets=self.buckets, reducer=self.reducer,
                        staleness=self.staleness)
        self._plan_cache: dict = {}

    # -- protocol -----------------------------------------------------------

    @property
    def _reduces_weights(self) -> bool:
        return bool(getattr(self.reducer, "reduces_weights", False))

    @property
    def _reducer_stateless(self) -> bool:
        return bool(getattr(self.reducer, "stateless", True))

    def _plan(self, worker_params: PyTree):
        """The (cached) static `BucketPlan` for this model, built from the
        canonical per-worker shapes of a (W, ...) state tree.  Abstract
        leaves work — the dry-run never allocates."""
        from repro.parallel import buckets as B
        return B.cached_plan(self._plan_cache, worker_params, self.buckets,
                             block=self.plan_block, strip_leading_axis=True,
                             wire_dtype=getattr(self.reducer, "comm_dtype",
                                                None))

    def init(self, params: PyTree) -> TrainState:
        cfg = self.cfg
        wp = replicate_for_workers(params, self.n_workers)
        sdt = jnp.dtype(cfg.state_dtype)
        opt = self.local_optimizer.init(wp)
        opt = jax.tree.map(lambda x: x.astype(sdt) if x.ndim else x, opt)
        # weight-mixing reducers never read the carried deltas — don't
        # spend a params-sized (W, ...) tree on dead comm state
        if self._reduces_weights:
            comm = {}
        elif self.buckets:
            # carried flat-buffer wire state: a few contiguous buckets
            # instead of one leaf per parameter tensor
            comm = {"delta_prev": self._plan(wp).zeros(
                sdt, lead=(self.n_workers,))}
        else:
            comm = {"delta_prev": jax.tree.map(
                lambda p: jnp.zeros_like(p, dtype=sdt), wp)}
        if not self.staleness.stateless:
            comm["staleness"] = self.staleness.init(self.n_workers)
        # stateful (error-feedback compressed) reducers carry residuals /
        # warm-started factors across steps, exactly like the staleness
        # policy state — keyed under comm["reducer"]
        if not self._reducer_stateless:
            comm["reducer"] = self.reducer.init(
                self.n_workers, self._plan(wp) if self.buckets else None)
        if self.overlap:
            # prime the pipeline: issue the reduce of the zero payload
            # (resp. the packed initial weights) — exactly the call the
            # inline schedule makes on step 0, so step 0 consumes the
            # same landed value either way (Algorithm 1's prologue)
            from repro.parallel import pipeline as PL
            wire0 = self._plan(wp).pack(wp) if self._reduces_weights \
                else comm["delta_prev"]
            pl_state, rs = PL.issue(self.reducer, wire0,
                                    comm.get("reducer"),
                                    fence=self._reduces_weights)
            comm["pipeline"] = pl_state
            if rs is not None:
                comm["reducer"] = rs
        return TrainState(params=wp, opt=opt, comm=comm,
                          step=jnp.zeros((), jnp.int32))

    def step(self, state: TrainState, batch: PyTree, *, loss_fn: LossFn
             ) -> Tuple[TrainState, Metrics]:
        """One DC-S3GD iteration for all workers at once.

        ``batch`` leaves are (W, per_worker_batch, ...).  ``loss_fn(
        params_i, batch_i)`` is the per-worker loss; gradients are vmapped
        over workers.
        """
        cfg = self.cfg
        lr, wd = schedules(state.step, cfg)
        sched = {"lr": lr, "weight_decay": wd}
        plan = self._plan(state.params) if self.buckets else None

        # --- MPI_Iallreduce: pluggable reduction over workers.  Depends
        # only on carried state, NOT on this step's gradients ->
        # overlappable by the scheduler.  Mean-style reducers consume the
        # deltas (the paper's wire format — valid because the global mean
        # keeps the Eq. 12 base common); neighborhood reducers
        # (reduces_weights) mix the weights themselves, D-PSGD-style.
        # With bucketing the reducer sees a handful of contiguous flat
        # buffers instead of the param tree: one wire cast + one mean (or
        # 2k rolls) per BUCKET, not per leaf.  Stateful (compressed)
        # reducers additionally consume and return their carried
        # comm["reducer"] state (error-feedback residuals).
        rstate = None
        if self.overlap:
            # pipelined schedule: the reduction was issued at the END of
            # the previous step's program (repro.parallel.pipeline) — this
            # step only CONSUMES the landed buffers; the next issue happens
            # in `_comm` at the tail.  Same reducer calls on the same
            # inputs as the inline branch below, just staged one program
            # region earlier -> bitwise-equal trajectory.
            from repro.parallel import pipeline as PL
            landed = PL.landed(state.comm)
            if self._reduces_weights:
                wire = plan.pack(state.params)
                r_in = wire
                w_red = landed
            else:
                delta_prev = state.comm["delta_prev"]
                r_in = delta_prev
                delta_bar = landed
        elif self._reduces_weights:
            wire = plan.pack(state.params) if plan is not None \
                else state.params
            r_in = wire
            # fence the reduce input exactly like the pipelined issue does
            # (repro.parallel.pipeline.issue): with both ends fenced the
            # reduce is an isolated subgraph, compiled identically whether
            # it sits at the top of this step or the tail of the previous
            # one — the bitwise-equal-schedules guarantee rests on this
            fenced = jax.lax.optimization_barrier(wire)
            # the `wire` scope tags the reducer body's HLO locations so
            # repro.analysis.lint can attribute comm_dtype casts to the
            # simulated wire (dtype-drift / wire-accounting passes)
            with jax.named_scope("wire"):
                if self._reducer_stateless:
                    w_red = self.reducer(fenced)
                else:
                    w_red, rstate = self.reducer(fenced,
                                                 state.comm["reducer"])
        else:
            delta_prev = state.comm["delta_prev"]   # bucketed when buckets>0
            r_in = delta_prev
            fenced = jax.lax.optimization_barrier(delta_prev)
            with jax.named_scope("wire"):
                if self._reducer_stateless:
                    delta_bar = self.reducer(fenced)
                else:
                    delta_bar, rstate = self.reducer(fenced,
                                                     state.comm["reducer"])

        # --- MPI_Wait materializes a landed buffer: fence the reduction
        # so XLA cannot fuse its final ops into consumer arithmetic (FMA
        # across the seam) — otherwise the inline and pipelined schedules
        # differ at the last ulp for reducers ending in multiplies
        # (gossip's weighted neighbor sums).  No-op for the pipelined
        # branch, whose landed value is already a program input.
        if self._reduces_weights:
            w_red = jax.lax.optimization_barrier(w_red)
        else:
            delta_bar = jax.lax.optimization_barrier(delta_bar)

        # --- g_i = ∇l(w_i): per-worker gradients (the compute overlapped)
        grads, loss = _vgrads(loss_fn, state.params, batch, cfg.microbatches)

        # --- MPI_Wait() / D_i = (1/N)·Δ̄w − Δw_i  (Eq. 9); for weight
        # reducers D_i = R(w)_i − w_i directly (same quantity: distance
        # from my weights to my reduction target).  With buckets, D stays
        # in the flat-buffer representation until a consumer needs leaves.
        if self._reduces_weights:
            D = jax.tree.map(lambda rw, w: rw - w.astype(jnp.float32),
                             w_red, wire)
        else:
            D = jax.tree.map(lambda db, d: db - d.astype(jnp.float32),
                             delta_bar, delta_prev)
        # fence D as well: downstream reductions (the compensator's Eq. 17
        # norms) must see a materialized buffer so their codegen cannot
        # depend on which program region produced the reduction
        D = jax.lax.optimization_barrier(D)

        # --- staleness policy: may this step use the stale overlapped
        # window?  'fixed' is stateless and skips the branch (bitwise the
        # paper behaviour); 'dynamic_ssp' revokes the window when the
        # observed per-worker step skew exceeds its threshold, falling
        # back to a blocking pull toward the current weight average.
        pstate = None
        pol_metrics = {}
        if not self.staleness.stateless:
            admit, pstate = self.staleness.admit(state.comm["staleness"])

            def _sync_pull():
                wbar = jax.tree.map(
                    lambda p: jnp.mean(p.astype(jnp.float32), axis=0,
                                       keepdims=True), state.params)
                Dt = jax.tree.map(
                    lambda wb, w: wb - w.astype(jnp.float32),
                    wbar, state.params)
                # match the admitted branch's representation
                return plan.pack(Dt) if plan is not None else Dt

            # lax.cond (not where): the revoked-window branch costs a full
            # params-tree mean — only pay it on the steps that take it
            D = jax.lax.cond(admit, lambda: D, _sync_pull)
            if rstate is not None and hasattr(self.reducer, "revoke"):
                # a revoked window discards the reducer output: the
                # compressed payload never reached the trajectory, so it
                # must return to the error-feedback residual, not vanish
                rstate = jax.lax.cond(
                    admit, lambda: rstate,
                    lambda: self.reducer.revoke(
                        r_in, state.comm["reducer"], rstate))
            pol_metrics = {"ssp_admit": admit.astype(jnp.float32)}

        if self.use_kernels:
            return self._fused_tail(state, grads, D, loss, lr, wd,
                                    plan=plan, pstate=pstate,
                                    pol_metrics=pol_metrics, rstate=rstate)

        if plan is not None:
            # per-leaf reference tail: leave the flat-buffer world here.
            # The unpack is a static reshape/slice, so the bucketed wire is
            # bitwise the per-leaf wire for mean-style reducers.
            D = plan.unpack(D)

        # --- g̃_i = g_i + λ_i g_i⊙g_i⊙D_i  (Eq. 10 + 17)
        g_t, lam = self.compensator(grads, D, axis0_is_worker=True)

        # --- Δw_i = U(g̃_i, η, μ)  (Eq. 11).  axis0_is_worker: the decay
        # mask must judge canonical rank, not (W, ...)-stacked rank —
        # otherwise norm/bias vectors get decayed (and the fused tail,
        # which sees canonical leaves under vmap, would disagree).
        delta, opt = self.local_optimizer(g_t, state.opt, state.params,
                                          sched, axis0_is_worker=True)

        # --- w_i = w_i + D_i + Δw_i  (Eq. 12: move toward the average +
        # corrected update in one pass)
        new_params = jax.tree.map(
            lambda w, d_i, dw: (w.astype(jnp.float32) + d_i
                                + dw.astype(jnp.float32)).astype(w.dtype),
            state.params, D, delta)

        sdt = jnp.dtype(cfg.state_dtype)
        opt = jax.tree.map(lambda x: x.astype(sdt) if x.ndim else x, opt)
        metrics = {
            "loss": jnp.mean(loss),
            "lr": lr,
            "wd": wd,
            "lambda": jnp.mean(lam) if not isinstance(lam, dict) else
            jnp.mean(jnp.stack([jnp.mean(v) for v in jax.tree.leaves(lam)])),
            "distance_norm": _mean_worker_norm(D),
            "delta_norm": _mean_worker_norm(delta),
            **pol_metrics,
        }
        next_wire = None
        if self.overlap and self._reduces_weights:
            # fence BEFORE packing: the issue must not add a fusion
            # consumer to the weight-update expression, or the stored
            # params themselves shift by an ulp vs the inline program
            new_params = jax.lax.optimization_barrier(new_params)
            next_wire = plan.pack(new_params)
        return TrainState(new_params, opt,
                          self._comm(delta, sdt, pstate, plan=plan,
                                     rstate=rstate, prev_comm=state.comm,
                                     next_wire=next_wire),
                          state.step + 1), metrics

    def _comm(self, delta: PyTree, sdt, pstate: Optional[PyTree] = None, *,
              plan=None, packed: bool = False,
              rstate: Optional[PyTree] = None,
              prev_comm: Optional[dict] = None,
              next_wire: Optional[PyTree] = None) -> PyTree:
        """Next step's wire state; with a plan the carried deltas are the
        flat buckets themselves (``packed=True`` when ``delta`` already
        is the bucket list, e.g. from the fused bucketed tail).

        Under ``overlap`` this is also where the next reduction goes on
        the wire: the just-produced payload (the carried delta buckets,
        or ``next_wire`` — the packed NEW weights — for
        ``reduces_weights`` topologies) is issued NOW, at the very end of
        the step's program, and the landed result rides to the next step
        in ``comm["pipeline"]``.  The payload is exactly what the inline
        schedule would reduce at the top of the next step, so the
        trajectory is bitwise-unchanged."""
        if self._reduces_weights:
            comm = {}
        elif plan is not None:
            db = delta if packed else plan.pack(delta)
            comm = {"delta_prev": [b.astype(sdt) for b in db]}
        else:
            comm = {"delta_prev": jax.tree.map(lambda d: d.astype(sdt),
                                               delta)}
        if pstate is not None:
            comm["staleness"] = pstate
        if rstate is not None:
            comm["reducer"] = rstate
        if self.overlap:
            from repro.parallel import pipeline as PL
            wire = next_wire if self._reduces_weights \
                else comm["delta_prev"]
            rs_in = None if self._reducer_stateless \
                else prev_comm["reducer"]
            pl_state, rs_out = PL.issue(self.reducer, wire, rs_in)
            comm["pipeline"] = pl_state
            if rs_out is not None:
                comm["reducer"] = rs_out
        return comm

    def eval_params(self, state: TrainState) -> PyTree:
        """w̄ for evaluation (paper Eq. 8 / averaging-in-parameter-space).

        Anchor form (`repro.core.reduce.consensus_mean`): exact when the
        workers agree, for ANY W — which makes the elastic resize's
        collapse-and-restack a bitwise fixed point of this function."""
        from repro.core.reduce import consensus_mean
        return consensus_mean(state.params)

    def resize_state(self, state: TrainState, n_new: int) -> TrainState:
        """Reshard the carried state to ``n_new`` workers (elastic resize).

        A membership transition is a synchronization barrier — every
        worker-stacked piece collapses to its consensus mean over ALL old
        workers and is restacked at the new count:

        * **params / opt slots** — collapse to the anchor-form consensus
          (leavers' weights and momentum fold into the surviving mean,
          they are NOT dropped); joiners bootstrap from that same
          consensus, so ``eval_params`` after the resize is bitwise the
          pre-resize value;
        * **delta_prev** — the in-flight wire payload collapses the same
          way: the next step's ``Δ̄w − Δw_i`` is exactly zero (every
          worker already sits at the consensus), reproducing Algorithm
          1's prologue semantics after the barrier;
        * **comm["staleness"] / comm["reducer"]** — delegated to the
          piece's own ``resize`` hook (counters collapse to the leader;
          error-feedback residual mass is conserved, see
          `repro.core.compress`);
        * **comm["pipeline"]** — in-flight buckets drain or collapse
          (stateless reducers re-issue on the resized wire, stateful
          keep the worker-count-independent landed payload — see
          `repro.parallel.pipeline.resize`).

        Pure state transform: ``self`` still targets the old worker
        count afterwards — rebuild the algorithm for ``n_new`` via
        `repro.cluster.membership.rebuild_algorithm` (bucket plans are
        worker-count independent, so the plan is simply re-cached).
        """
        n_new = int(n_new)

        def restack(x):
            if getattr(x, "ndim", 0) == 0:
                return x  # scalar slot (e.g. adam's step count)
            a = x.astype(jnp.float32)
            avg = a[0] + jnp.mean(a - a[:1], axis=0)
            return jnp.broadcast_to(avg.astype(x.dtype)[None],
                                    (n_new,) + avg.shape)

        params = jax.tree.map(restack, state.params)
        opt = jax.tree.map(restack, state.opt)
        comm = {}
        if "delta_prev" in state.comm:
            # bucketed (list of (W, n) buffers) and per-leaf trees alike
            comm["delta_prev"] = jax.tree.map(restack,
                                              state.comm["delta_prev"])
        if "staleness" in state.comm:
            comm["staleness"] = self.staleness.resize(
                state.comm["staleness"], n_new)
        if "reducer" in state.comm:
            comm["reducer"] = self.reducer.resize(state.comm["reducer"],
                                                  n_new)
        if "pipeline" in state.comm:
            # drain/collapse the in-flight buckets against the RESIZED
            # wire (see repro.parallel.pipeline.resize)
            from repro.parallel import pipeline as PL
            wire = self._plan(params).pack(params) \
                if self._reduces_weights else comm["delta_prev"]
            comm["pipeline"] = PL.resize(self.reducer,
                                         state.comm["pipeline"], wire)
        return TrainState(params, opt, comm, state.step)

    # -- sharding hooks -----------------------------------------------------

    def state_specs(self, model_cfg, state: TrainState,
                    axes: MeshAxes) -> TrainState:
        """Every state leaf carries the leading worker axes (one weight
        replica per (pod, data) shard); policy state shards per the
        policy's own declaration."""
        overrides = {}
        if "staleness" in state.comm:
            overrides["staleness"] = self.staleness.state_specs(axes)
        if "reducer" in state.comm:
            overrides["reducer"] = self.reducer.state_specs(
                axes, self._plan(state.params) if self.buckets else None)
        if self.buckets and "delta_prev" in state.comm:
            # bucketed comm state: (W, bucket) buffers — worker axes on the
            # leading dim, the contiguous flat dim never split mid-leaf
            overrides["delta_prev"] = self._plan(state.params).specs(
                axes.worker_spec)
        if "pipeline" in state.comm:
            from repro.parallel import pipeline as PL
            overrides["pipeline"] = PL.specs(
                self.reducer, self._plan(state.params), axes.worker_spec)
        return shd.train_state_specs(
            model_cfg, state, model_size=axes.model_size,
            worker_axes=axes.worker_spec, comm_overrides=overrides)

    def batch_specs(self, model_cfg, batch: PyTree,
                    axes: MeshAxes) -> PyTree:
        return shd.batch_specs(model_cfg, batch,
                               worker_axes=axes.worker_spec)

    def observe_progress(self, state: TrainState, worker_steps
                         ) -> TrainState:
        """Feed measured per-worker progress to the staleness policy
        (host-side, between jitted scans).  No-op for stateless policies;
        the policy's own ``observe`` owns its state layout."""
        if self.staleness.stateless:
            return state
        comm = dict(state.comm)
        comm["staleness"] = self.staleness.observe(comm["staleness"],
                                                   worker_steps)
        return state._replace(comm=comm)

    def spread(self, state: TrainState) -> jnp.ndarray:
        """Mean Euclidean distance of workers from the average — the
        quantity the paper argues grows slowly with N (§III-D.2)."""
        avg = self.eval_params(state)
        sq = sum(jax.tree.leaves(jax.tree.map(
            lambda p, a: jnp.sum(jnp.square(p.astype(jnp.float32) - a[None]),
                                 axis=tuple(range(1, p.ndim))),
            state.params, avg)))
        return jnp.mean(jnp.sqrt(sq))

    # -- fused Pallas tail --------------------------------------------------

    def _fused_tail(self, state: TrainState, grads, D, loss, lr, wd, *,
                    plan=None, pstate: Optional[PyTree] = None,
                    pol_metrics: Optional[Metrics] = None,
                    rstate: Optional[PyTree] = None
                    ) -> Tuple[TrainState, Metrics]:
        cfg = self.cfg
        assert self.local_optimizer.name == "momentum" \
            and not getattr(self.local_optimizer, "nesterov", False) \
            and getattr(self.compensator, "mode", "global") == "global", \
            "fused kernel path: momentum + global-lambda only"
        from repro.kernels import ops as kops
        lambda0 = self.compensator.lambda0
        mu = self.local_optimizer.momentum
        sdt = jnp.dtype(cfg.state_dtype)

        if plan is not None:
            # single-launch tail: ONE row-grid kernel per bucket (vs one
            # per leaf), no per-leaf pad/unpad; D is already bucketed and
            # the produced delta stays bucketed for the wire.
            g_b = plan.pack(grads)
            m_b = plan.pack(state.opt["m"])
            w_b = plan.pack(state.params)

            def per_worker_b(g_i, d_i, m_i, w_i):
                gsq, csq = kops.dc_norms_buckets(g_i, d_i)
                lam_i = kops.dc_lambda(gsq, csq, lambda0)
                w_n, m_n, dw = kops.dc_fused_update_buckets(
                    g_i, d_i, m_i, w_i, lam=lam_i, mu=mu, eta=lr, wd=wd,
                    decay=plan.bucket_decay)
                return w_n, m_n, dw, lam_i

            w_nb, m_nb, delta_b, lam = jax.vmap(per_worker_b)(
                g_b, D, m_b, w_b)
            if self.overlap and self._reduces_weights:
                # fence before the issue reads w_nb (see reference tail)
                w_nb = jax.lax.optimization_barrier(w_nb)
            new_params = plan.unpack(w_nb)
            opt = jax.tree.map(lambda x: x.astype(sdt),
                               {"m": plan.unpack(m_nb)})
            metrics = {
                "loss": jnp.mean(loss), "lr": lr, "wd": wd,
                "lambda": jnp.mean(lam),
                "distance_norm": _mean_worker_norm(D),
                "delta_norm": _mean_worker_norm(delta_b),
                **(pol_metrics or {}),
            }
            return TrainState(new_params, opt,
                              self._comm(delta_b, sdt, pstate, plan=plan,
                                         packed=True, rstate=rstate,
                                         prev_comm=state.comm,
                                         next_wire=w_nb
                                         if (self.overlap
                                             and self._reduces_weights)
                                         else None),
                              state.step + 1), metrics

        def per_worker(g_i, d_i, m_i, w_i):
            gsq, csq = kops.dc_norms_tree(g_i, d_i)
            lam_i = kops.dc_lambda(gsq, csq, lambda0)
            w_n, m_n, dw = kops.dc_fused_update_tree(
                g_i, d_i, m_i, w_i, lam=lam_i, mu=mu, eta=lr, wd=wd)
            return w_n, m_n, dw, lam_i

        new_params, m_new, delta_f32, lam = jax.vmap(per_worker)(
            grads, D, state.opt["m"], state.params)
        metrics = {
            "loss": jnp.mean(loss), "lr": lr, "wd": wd,
            "lambda": jnp.mean(lam),
            "distance_norm": _mean_worker_norm(D),
            "delta_norm": _mean_worker_norm(delta_f32),
            **(pol_metrics or {}),
        }
        opt = jax.tree.map(lambda x: x.astype(sdt), {"m": m_new})
        return TrainState(new_params, opt,
                          self._comm(delta_f32, sdt, pstate, rstate=rstate),
                          state.step + 1), metrics


@registry.register(registry.ALGORITHM, "stale")
def _make_stale(cfg: DCS3GDConfig, **kw) -> DCS3GD:
    """Uncompensated stale-synchronous SGD: DC-S3GD with λ0 = 0."""
    kw.setdefault("compensator", "none")
    alg = DCS3GD(dataclasses.replace(cfg, lambda0=0.0), **kw)
    alg.name = "stale"
    return alg


# ---------------------------------------------------------------------------
# shared step internals (used by the class and by SSGD)
# ---------------------------------------------------------------------------


def _vgrads(loss_fn, params, batch, microbatches: int = 1):
    vg = jax.vmap(jax.value_and_grad(loss_fn), in_axes=(0, 0))
    if microbatches <= 1:
        loss, grads = vg(params, batch)
        return grads, loss

    # gradient accumulation: scan over microbatches of the per-worker batch
    # (leaves (W, b, ...) -> (k, W, b/k, ...)); per-worker-shared leaves
    # (mrope position ids) are broadcast instead of split.
    def split(path, x):
        name = getattr(path[-1], "key", "")
        if name == "mrope_positions":
            return jnp.broadcast_to(x[None], (microbatches,) + x.shape)
        W, b = x.shape[:2]
        assert b % microbatches == 0, (x.shape, microbatches)
        return x.reshape(W, microbatches, b // microbatches,
                         *x.shape[2:]).swapaxes(0, 1)

    mb = jax.tree_util.tree_map_with_path(split, batch)

    def body(carry, mbatch):
        g_acc, l_acc = carry
        loss, grads = vg(params, mbatch)
        g_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                             g_acc, grads)
        return (g_acc, l_acc + loss), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    l0 = jnp.zeros((jax.tree.leaves(params)[0].shape[0],), jnp.float32)
    (g_acc, l_acc), _ = jax.lax.scan(body, (g0, l0), mb)
    k = float(microbatches)
    return (jax.tree.map(lambda g: g / k, g_acc), l_acc / k)


def _mean_worker_norm(tree: PyTree) -> jnp.ndarray:
    sq = sum(jax.tree.leaves(jax.tree.map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32)),
                          axis=tuple(range(1, x.ndim))), tree)))
    return jnp.mean(jnp.sqrt(sq))
