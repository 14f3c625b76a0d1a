"""Synchronous SGD baseline (paper §II-A "decentralized synchronous").

Identical weights on every worker; the gradient all-reduce is on the
critical path (the update depends on *this* step's gradients), so the step
time is t_C + t_ARed (paper Eq. 13) — the thing DC-S3GD removes.

`SSGD` composes the same `LocalOptimizer` / `Reducer` pieces as DC-S3GD
over the generic `TrainState` (no worker axis on state leaves, ``comm`` is
empty) and registers as ``"ssgd"``.  Its ``state_specs`` hook therefore
returns canonical (replicated-over-workers) specs while ``batch_specs``
still shards the leading batch axis over the worker mesh axes.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import registry
from repro.core.api import LossFn, MeshAxes, Metrics, TrainState
from repro.core.dc_s3gd import schedules
from repro.core.reduce import collapse_worker_axis
from repro.core.types import DCS3GDConfig
from repro.optim import local as local_opt
from repro.parallel import sharding as shd

PyTree = Any


@registry.register(registry.ALGORITHM, "ssgd")
class SSGD:
    """Synchronous data-parallel SGD through the protocol.

    ``batch`` leaves are (W, per_worker_batch, ...) like DC-S3GD, but
    params are shared: grads go through the `Reducer` *before* the update
    (the blocking all-reduce).  ``n_workers`` is accepted for interface
    uniformity; the worker count is carried by the batch.
    """

    name = "ssgd"

    def __init__(self, cfg: DCS3GDConfig, *, n_workers: int = 1,
                 local_optimizer=None, reducer=None,
                 buckets: Optional[int] = None, use_kernels: bool = False,
                 overlap: bool = False,
                 plan_block: Optional[int] = None, **_ignored):
        if overlap:
            raise ValueError(
                "overlap=True is not available for ssgd: the gradient "
                "all-reduce is blocking by definition (the update depends "
                "on THIS step's gradients — paper Eq. 13).  Overlap is "
                "what dc_s3gd/stale buy with the one-step-stale wire")
        self.cfg = cfg
        self.n_workers = n_workers
        self.local_optimizer = (
            local_opt.from_config(cfg) if local_optimizer is None
            else registry.make_local_optimizer(local_optimizer, cfg))
        self.reducer = registry.make_reducer(
            "mean_allreduce" if reducer is None else reducer, cfg)
        self.use_kernels = bool(use_kernels)
        # route compressed reducers with a fused Pallas body through it
        if use_kernels and hasattr(self.reducer, "use_kernels"):
            self.reducer.use_kernels = True
        # flat-buffer bucketing for the gradient all-reduce (the blocking
        # wire): >0 packs grads into contiguous buckets so the reducer
        # casts/means once per bucket, not per leaf; 0 = legacy per-leaf
        self.buckets = int(cfg.buckets if buckets is None else buckets)
        # bucket padding granularity (autotuner knob; None = kernel BLOCK)
        self.plan_block = None if plan_block is None else int(plan_block)
        self._plan_cache: dict = {}

    def _plan(self, params: PyTree):
        from repro.parallel import buckets as B
        return B.cached_plan(self._plan_cache, params, self.buckets,
                             block=self.plan_block,
                             wire_dtype=getattr(self.reducer, "comm_dtype",
                                                None))

    @property
    def _reducer_stateless(self) -> bool:
        return bool(getattr(self.reducer, "stateless", True))

    def init(self, params: PyTree) -> TrainState:
        comm = {}
        # error-feedback compressed reducers carry per-worker residuals
        # across steps in comm["reducer"], same seam as DC-S3GD
        if not self._reducer_stateless:
            comm["reducer"] = self.reducer.init(
                self.n_workers, self._plan(params) if self.buckets
                else None)
        return TrainState(params=params,
                          opt=self.local_optimizer.init(params),
                          comm=comm, step=jnp.zeros((), jnp.int32))

    def step(self, state: TrainState, batch: PyTree, *, loss_fn: LossFn
             ) -> Tuple[TrainState, Metrics]:
        cfg = self.cfg
        lr, wd = schedules(state.step, cfg)
        vg = jax.vmap(jax.value_and_grad(loss_fn), in_axes=(None, 0))
        loss, grads = vg(state.params, batch)
        # blocking all-reduce: reduce over workers — on the critical path.
        # collapse_worker_axis folds the reducer's broadcastable output
        # ((1, ...) for the mean, (W, ...) for gossip) back to canonical
        # shapes; for the mean reducer this is bitwise the seed behaviour.
        # With bucketing the wire sees a few contiguous (W, bucket)
        # buffers — one cast+reduce per bucket — and the pack/unpack is a
        # bitwise reshape, so the trajectory is unchanged.
        g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        comm = {}
        if self.buckets:
            plan = self._plan(state.params)
            wire = plan.pack(g32)
            # `wire` scope: lets repro.analysis.lint attribute comm_dtype
            # casts inside the reducer body to the simulated wire
            with jax.named_scope("wire"):
                if self._reducer_stateless:
                    red = self.reducer(wire)
                else:
                    red, comm["reducer"] = self.reducer(
                        wire, state.comm["reducer"])
            grads = plan.unpack(collapse_worker_axis(red))
        else:
            if not self._reducer_stateless:
                raise ValueError(
                    f"reducer {self.reducer.name!r} needs the bucketed "
                    f"wire: construct with buckets > 0")
            with jax.named_scope("wire"):
                red = self.reducer(g32)
            grads = collapse_worker_axis(red)
        # `tail`: the update, named as DC-S3GD's (bench/scopes.py)
        with jax.named_scope("tail"):
            delta, opt = self.local_optimizer(grads, state.opt, state.params,
                                              {"lr": lr, "weight_decay": wd})
            new_params = jax.tree.map(
                lambda w, dw: (w.astype(jnp.float32)
                               + dw.astype(jnp.float32)).astype(w.dtype),
                state.params, delta)
        return (TrainState(new_params, opt, comm, state.step + 1),
                {"loss": jnp.mean(loss), "lr": lr, "wd": wd})

    def eval_params(self, state: TrainState) -> PyTree:
        return state.params

    def resize_state(self, state: TrainState, n_new: int) -> TrainState:
        """Elastic resize: SSGD params/opt are canonical (replicated —
        trivially the consensus already, so ``eval_params`` is bitwise
        unchanged); the only worker-stacked state is a stateful
        reducer's per-worker error-feedback residuals, which delegate
        to the reducer's own ``resize`` (mass-conserving fold)."""
        comm = dict(state.comm)
        if "reducer" in comm:
            comm["reducer"] = self.reducer.resize(comm["reducer"],
                                                  int(n_new))
        return state._replace(comm=comm)

    # -- sharding hooks -----------------------------------------------------

    def state_specs(self, model_cfg, state: TrainState,
                    axes: MeshAxes) -> TrainState:
        """Replicated over workers: canonical param layout, no worker axis
        on any state leaf — except a stateful reducer's per-worker
        residuals, which lead with the worker axes."""
        overrides = {}
        if "reducer" in state.comm:
            overrides["reducer"] = self.reducer.state_specs(
                axes, self._plan(state.params) if self.buckets else None)
        return shd.train_state_specs(model_cfg, state,
                                     model_size=axes.model_size,
                                     worker_axes=None,
                                     comm_overrides=overrides)

    def batch_specs(self, model_cfg, batch: PyTree,
                    axes: MeshAxes) -> PyTree:
        return shd.batch_specs(model_cfg, batch,
                               worker_axes=axes.worker_spec)
