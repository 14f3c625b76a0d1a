"""Double-buffered bucket pipeline — the paper's overlap, made explicit.

DC-S3GD's premise is that the delta all-reduce (``MPI_Iallreduce``) runs
*under* the forward/backward pass.  The inline step already expresses
that as a data dependency (the reduce of the carried ``delta_prev``
doesn't touch this step's gradients), but the reduce, the tail, and the
wire all live in one program region, so on real hardware the collective
issue order is whatever the scheduler picks.  This module pins the DDP
bucket-pipeline structure instead:

* every step **consumes** the reduction that is already in flight
  (``TrainState.comm["pipeline"]["reduced"]`` — one landed buffer per
  `repro.parallel.buckets.BucketPlan` bucket), and
* **issues** the next reduction at the very end of the step, bucket by
  bucket, as soon as the fused tail produces each payload — while the
  tail is still updating bucket i−1, the reduce of bucket i is on the
  wire.

Because the in-flight payloads ride in the TrainState, the jitted step
stays a pure function: donation, checkpointing, ``eval_shape`` dry-runs,
and elastic resizes all keep working.  And because the *sequence of
reducer invocations and their inputs* is identical to the inline
schedule (the issue of step t's payload simply moves from the top of
step t+1 to the bottom of step t), the pipelined trajectory is
**bitwise-equal** to the inline bucketed path at the same effective
staleness window — pinned in ``tests/test_pipeline.py``.

State contract (``comm["pipeline"]``):

* ``{"reduced": [r_0, ..., r_{B-1}]}`` — the landed reducer output per
  bucket: ``(1, n_b)`` f32 for mean-style reducers (including the
  error-feedback compressed family), ``(W, n_b)`` for
  ``reduces_weights`` topologies (gossip / hierarchical mix the packed
  weights themselves).
* For a **stateful** reducer, ``comm["reducer"]`` holds the state
  *after* the in-flight issue (one call ahead of the inline layout);
  the chain of states a resumed run replays is unchanged.
* ``init()`` primes the pipeline by issuing the reduce of the zero
  payload (resp. the packed initial weights) — exactly the call the
  inline schedule makes on step 0, so the prologue stays Algorithm 1's.

Interaction with the staleness window: the pipeline adds no staleness —
the consumed reduction is the reduce of ``delta_prev``, the same
one-step-old payload the inline schedule reduces.  ``dynamic_ssp``
composes with a *stateless* reducer (a revoked window discards the
landed value through the same ``lax.cond``); with a *stateful*
(error-feedback) reducer it is rejected at construction — the revoke
needs the pre-issue residual, which the pipeline has already advanced
past (see :func:`validate`).

Elastic resize (``resize_state``): in-flight buckets are drained or
collapsed, never duplicated — a stateless reducer's landed value is
recomputed from the resized wire (the drained buffer bitwise-equals a
fresh jitted reduce of the post-collapse payload — pinned in
``tests/test_pipeline.py``); a stateful reducer's landed ``(1, n)``
payload is worker-count independent and is kept as-is (its mass is
already accounted for by the resized error-feedback residual).  The
acceptance bar for resize is *survival* — the run continues finite with
the drained buffers, shapes tracking the new W — not bitwise equality
with the inline schedule: immediately after the collapse barrier the
correction ``D = Δ̄w − Δw_i`` is consensus-ulp noise, and the
compensator's ``λ = λ0·‖g‖/‖c‖`` normalizes that noise to gradient
magnitude, so *any* last-ulp codegen difference between two programs
(inline's in-step reduce vs. the drained buffer) is amplified to a
macroscopically different — statistically equivalent — trajectory.
The steady-state schedule (no resize) IS bitwise-inline; see above.
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

PyTree = Any


def validate(*, buckets: int, reducer, staleness=None) -> None:
    """Reject overlap configurations whose semantics cannot be honored.

    * ``buckets == 0`` — the pipeline double-buffers the *bucketed*
      wire; there is no per-leaf schedule to stage.
    * stateful staleness policy (``dynamic_ssp``) + stateful reducer —
      a revoked window must return the un-delivered payload to the
      error-feedback residual via ``reducer.revoke(wire, prev_rstate,
      rstate)``, but the pipelined issue already consumed
      ``prev_rstate`` inside the previous step's program.  Either the
      window policy or the compressor must be stateless.
    """
    if not buckets:
        raise ValueError(
            "overlap=True needs the bucketed wire: construct the "
            "algorithm with buckets > 0 (registry.make(..., buckets=N, "
            "overlap=True) / --buckets N --overlap)")
    if (staleness is not None
            and not getattr(staleness, "stateless", True)
            and not getattr(reducer, "stateless", True)):
        raise ValueError(
            "overlap=True cannot compose a stateful staleness policy "
            "(dynamic_ssp) with a stateful (error-feedback) reducer: a "
            "revoked window needs the pre-issue residual, which the "
            "pipelined issue has already advanced past.  Use a "
            "stateless reducer with dynamic_ssp, or the fixed window "
            "with the compressed reducer")


def issue(reducer, wire: List, rstate: Optional[PyTree] = None, *,
          fence: bool = True) -> Tuple[dict, Optional[PyTree]]:
    """Put the next payload on the wire: apply the reducer to the bucket
    list NOW (at the tail of the current step's program) and carry the
    result as the in-flight pipeline state.

    Returns ``(pipeline_state, new_reducer_state)`` — the latter is
    ``None`` for stateless reducers.  Also used by ``init()`` to prime
    the pipeline (the reduce of the zero payload / initial weights).

    The payload is fenced with ``optimization_barrier`` before the
    reducer sees it: in the inline schedule the reduce consumes program
    *inputs* (the carried state), and without the fence XLA may fuse the
    issue into the tail arithmetic that produced the payload (FMA /
    reassociation across the seam), breaking the bitwise-equal-to-inline
    guarantee for reducers whose last ops are multiplies (gossip's
    weighted neighbor sums).  ``fence=False`` is for a constant payload
    (``init()``'s zeros), which no arithmetic produced: fenced, it would
    be materialised, and on a worker mesh the partitioner lays such a
    constant out whole on every device (W× the bucket bytes of
    temporaries)."""
    if fence:
        wire = jax.lax.optimization_barrier(wire)
    # the `wire` scope tags the reducer body's HLO locations so
    # repro.analysis.lint can attribute comm_dtype casts to the simulated
    # wire (dtype-drift / wire-accounting passes) — same scope the inline
    # schedule uses around its reducer call
    with jax.named_scope("wire"):
        if rstate is None:
            reduced = reducer(wire)
        else:
            reduced, rstate = reducer(wire, rstate)
    # fence the landed side too: the stored result must be the same
    # values the inline program would hand to its consumers as a plain
    # array, not an expression XLA can re-fuse into the epilogue
    return ({"reduced": list(jax.lax.optimization_barrier(list(reduced)))},
            rstate)


def landed(comm: dict) -> List:
    """The reduction consumed by the current step — issued at the end of
    the previous one (or by ``init()``'s priming issue)."""
    return comm["pipeline"]["reduced"]


def resize(reducer, pstate: dict, wire: List) -> dict:
    """Drain/collapse the in-flight buckets for an elastic resize.

    ``wire`` is the already-resized payload (the restacked
    ``delta_prev`` buckets, or the packed restacked weights for
    ``reduces_weights`` reducers).  Stateless reducers re-issue on it —
    every post-collapse row is the consensus, so this is the same
    payload the inline schedule reduces on its first post-resize step
    (equality of the drained buffer with a fresh jitted reduce is
    pinned; trajectory-level bitwise-vs-inline is NOT promised across a
    resize — see the module docstring's λ-amplification note).
    Stateful reducers keep the landed ``(1, n)`` payload:
    it is worker-count independent, and the resized error-feedback
    residual already accounts for the mass it carries."""
    if getattr(reducer, "stateless", True):
        # under jit, like every other issue: the post-resize step consumes
        # this value in place of an in-program reduce, and eager op-by-op
        # evaluation can differ from the compiled reduce at the last ulp —
        # which the compensator's lambda = ||g||/||c|| direction amplifies
        # to macroscopic divergence when D is consensus-tiny after the
        # collapse barrier
        reduced = jax.jit(lambda w: list(reducer(w)))(wire)
        return {"reduced": list(reduced)}
    return dict(pstate)


def specs(reducer, plan, worker_spec) -> dict:
    """Partition specs for ``comm["pipeline"]``: mean-style landed
    buffers are (1, n) and replicated; ``reduces_weights`` buffers are
    (W, n) and lead with the worker axes, like the packed weights they
    mix.  The contiguous flat dim is never split mid-bucket."""
    lead = worker_spec if getattr(reducer, "reduces_weights", False) \
        else None
    return {"reduced": [P(lead, None) for _ in plan.bucket_sizes]}
