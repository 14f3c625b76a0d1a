"""Flat-buffer bucketing: plan/pack/unpack invariants, per-bucket reducer
parity, the fused bucketed Pallas tail, the hierarchical reducer, and
buffer donation in the Engine's jitted step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

from repro.core import registry
from repro.core.api import MeshAxes, TrainState
from repro.core.reduce import GossipReduce, HierarchicalReduce, MeanAllReduce
from repro.core.types import DCS3GDConfig
from repro.kernels import dc_update as K
from repro.parallel import buckets as B

from helpers import quadratic_problem, stack_batches

CFG = DCS3GDConfig(learning_rate=0.1, momentum=0.9, lambda0=0.2,
                   weight_decay=1e-3, total_steps=1)
W = 4


def _mixed_tree(key=0):
    """Ragged sizes (nothing BLOCK-aligned), mixed dtypes, mixed ranks."""
    ks = random.split(random.PRNGKey(key), 5)
    return {
        "mat": random.normal(ks[0], (33, 7)),
        "scale": random.normal(ks[1], (19,)),
        "emb": random.normal(ks[2], (130, 96)).astype(jnp.bfloat16),
        "big": random.normal(ks[3], (70_001,)),
        "w3": random.normal(ks[4], (3, 5, 8)),
    }


def _bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and bool(jnp.array_equal(x, y))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# plan / pack / unpack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_buckets", [1, 2, 4])
def test_pack_unpack_bitwise_round_trip(n_buckets):
    tree = _mixed_tree()
    plan = B.plan_buckets(tree, n_buckets)
    assert _bitwise(tree, plan.unpack(plan.pack(tree)))


def test_pack_unpack_round_trip_with_worker_axis():
    tree = _mixed_tree()
    plan = B.plan_buckets(tree, 3)
    wt = jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) for i in range(W)]), tree)
    packed = plan.pack(wt)
    assert all(p.shape == (W, n)
               for p, n in zip(packed, plan.bucket_sizes))
    assert _bitwise(wt, plan.unpack(packed))


def test_pack_is_jit_safe():
    tree = _mixed_tree()
    plan = B.plan_buckets(tree, 3)
    eager = plan.pack(tree)
    jitted = jax.jit(lambda t: plan.pack(t))(tree)
    assert _bitwise(eager, jitted)
    assert _bitwise(tree, jax.jit(lambda bs: plan.unpack(bs))(eager))


def test_buckets_are_block_aligned_and_homogeneous():
    tree = _mixed_tree()
    plan = B.plan_buckets(tree, 3)
    assert all(n % K.BLOCK == 0 for n in plan.bucket_sizes)
    # dtype- and decay-homogeneous: every slot agrees with its bucket
    for slot in plan.slots:
        assert slot.dtype == plan.bucket_dtypes[slot.bucket]
        assert (len(slot.shape) > 1) == plan.bucket_decay[slot.bucket]
    # ragged last leaf of a bucket: padding never overlaps a slot
    for b in range(plan.n_buckets):
        used = sum(s.size for s in plan.slots if s.bucket == b)
        assert used <= plan.bucket_sizes[b]


def test_plan_from_abstract_leaves_matches_concrete():
    tree = _mixed_tree()
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    pa = B.plan_buckets(abstract, 3)
    pc = B.plan_buckets(tree, 3)
    assert pa.bucket_sizes == pc.bucket_sizes
    assert pa.slots == pc.slots


def test_cached_plan_keys_and_threads_block():
    """Two plans differing only in ``block`` must not collide in the
    cache (their padded bucket sizes differ)."""
    tree = _mixed_tree()
    cache = {}
    p8 = B.cached_plan(cache, tree, 2, block=8)
    p256 = B.cached_plan(cache, tree, 2, block=256)
    assert p8.block == 8 and p256.block == 256
    assert p8.bucket_sizes != p256.bucket_sizes
    assert len(cache) == 2
    # and hits are real hits
    assert B.cached_plan(cache, tree, 2, block=8) is p8


def test_plan_buckets_empty_tree_raises_clearly():
    with pytest.raises(ValueError, match="empty pytree"):
        B.plan_buckets({}, 2)
    with pytest.raises(ValueError, match="empty pytree"):
        B.cached_plan({}, [], 1)


def test_plan_buckets_all_scalar_leaves():
    tree = {"a": jnp.float32(1.5), "b": jnp.float32(-2.0)}
    plan = B.plan_buckets(tree, 1, block=4)
    assert _bitwise(tree, plan.unpack(plan.pack(tree)))
    wt = jax.tree.map(lambda x: jnp.broadcast_to(x, (W,)), tree)
    assert _bitwise(wt, plan.unpack(plan.pack(wt)))


def test_bucket_specs_lead_with_worker_axes():
    from jax.sharding import PartitionSpec as P
    plan = B.plan_buckets(_mixed_tree(), 2)
    for sp in plan.specs(("pod", "data")):
        assert sp == P(("pod", "data"), None)
    for sp in plan.specs(None):
        assert sp == P(None)


# ---------------------------------------------------------------------------
# per-bucket reducers == per-leaf reducers, bitwise in f32
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("reducer", [
    MeanAllReduce(), GossipReduce(neighbors=1),
    HierarchicalReduce(groups=2)])
def test_bucketed_reducer_bitwise_matches_per_leaf(reducer):
    """A reducer is elementwise over the worker axis, so applying it to
    the packed flat buffers and unpacking must be bitwise the per-leaf
    result (f32 wire)."""
    tree = {k: v for k, v in _mixed_tree().items() if v.dtype ==
            jnp.float32}
    wt = jax.tree.map(
        lambda x: jnp.stack([x * (i - 1.5) for i in range(W)]), tree)
    plan = B.plan_buckets(tree, 3)
    per_leaf = reducer(wt)
    per_bucket = plan.unpack(reducer(plan.pack(wt)))
    assert _bitwise(per_leaf, per_bucket)


# ---------------------------------------------------------------------------
# algorithm trajectories: bucketed vs legacy
# ---------------------------------------------------------------------------


def _loss_and_init():
    loss_fn, _, _, batch_fn = quadratic_problem(n=8, seed=3)
    init = {"w": jnp.zeros((8,)), "mat": jnp.zeros((8, 8))}

    def loss2(p, b):
        pred = b["A"] @ (p["w"] + p["mat"].sum(0) * 0.01)
        return 0.5 * jnp.mean((pred - b["y"]) ** 2)

    return loss2, init, batch_fn


def _run(algo="dc_s3gd", steps=5, **kw):
    loss2, init, batch_fn = _loss_and_init()
    alg = registry.make(algo, CFG, n_workers=W, **kw)
    state = alg.init(init)
    metrics = None
    for t in range(steps):
        state, metrics = alg.step(state, stack_batches(batch_fn, t, W),
                                  loss_fn=loss2)
    return alg, state, metrics


@pytest.mark.parametrize("reducer", ["mean_allreduce", "gossip",
                                     "hierarchical"])
def test_dc_s3gd_bucketed_bitwise_matches_per_leaf(reducer):
    _, s0, m0 = _run(reducer=reducer)
    _, s1, m1 = _run(reducer=reducer, buckets=2)
    assert _bitwise(s0.params, s1.params)
    assert bool(jnp.array_equal(m0["loss"], m1["loss"]))


def test_dc_s3gd_bucketed_comm_is_flat_buffers():
    alg, s1, _ = _run(buckets=2)
    dp = s1.comm["delta_prev"]
    assert isinstance(dp, list)
    plan = alg._plan(s1.params)
    assert [x.shape for x in dp] == [(W, n) for n in plan.bucket_sizes]
    # a many-leaf tree really does collapse to few buckets
    big = {f"w{i}": jnp.zeros((16, 16)) for i in range(12)}
    assert B.plan_buckets(big, 3).n_buckets == 3


def test_ssgd_bucketed_bitwise_matches_per_leaf():
    _, s0, _ = _run("ssgd", steps=3)
    _, s1, _ = _run("ssgd", steps=3, buckets=2)
    assert _bitwise(s0.params, s1.params)


@pytest.mark.parametrize("buckets", [0, 2])
def test_fused_step_matches_reference_tail_5_steps(buckets):
    """use_kernels=True (legacy per-leaf AND bucketed single-launch)
    within 1e-6 of the reference tail over 5 steps."""
    _, s_ref, _ = _run()
    _, s_k, _ = _run(use_kernels=True, buckets=buckets)
    for a, b in zip(jax.tree.leaves(s_ref.params),
                    jax.tree.leaves(s_k.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_bucketed_padding_stays_zero_across_steps():
    """Carried bucketed delta_prev must never leak values into the pad
    region (the fused tail maps pad zeros to pad zeros)."""
    alg, state, _ = _run(use_kernels=True, buckets=2, steps=3)
    plan = alg._plan(state.params)
    for b, buf in enumerate(state.comm["delta_prev"]):
        used = sum(s.size for s in plan.slots if s.bucket == b)
        pad = np.asarray(buf[:, used:])
        assert pad.size == 0 or not pad.any()


def test_dynamic_ssp_works_with_buckets():
    """The revoked-window sync pull repacks into the bucketed rep."""
    loss2, init, batch_fn = _loss_and_init()
    alg = registry.make("dc_s3gd", CFG, n_workers=W, buckets=2,
                        staleness="dynamic_ssp")
    state = alg.init(init)
    for t in range(2):
        state, m = alg.step(state, stack_batches(batch_fn, t, W),
                            loss_fn=loss2)
    state = alg.observe_progress(state, [9] + [0] * (W - 1))
    state, m = alg.step(state, stack_batches(batch_fn, 2, W),
                        loss_fn=loss2)
    assert float(m["ssp_admit"]) == 0.0
    assert bool(jnp.isfinite(m["loss"]))


# ---------------------------------------------------------------------------
# hierarchical reducer semantics
# ---------------------------------------------------------------------------


def test_hierarchical_is_registered():
    assert "hierarchical" in registry.names(registry.REDUCER)
    red = registry.make_reducer("hierarchical", CFG)
    assert red.reduces_weights
    assert red.groups == CFG.hier_groups


def test_hierarchical_composes_intra_mean_inter_gossip():
    """G=2 groups of 4: output = (my group's mean + other group's mean)/2
    on every worker — intra-pod exact mean, inter-pod 1-hop gossip."""
    x = random.normal(random.PRNGKey(0), (8, 6))
    red = HierarchicalReduce(groups=2)
    out = red({"x": x})["x"]
    g0, g1 = x[:4].mean(0), x[4:].mean(0)
    both = (g0 + g1) / 2
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(both),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out[7]), np.asarray(both),
                               rtol=1e-6)
    # with >2k+1 groups the result is a strict neighborhood, not global
    red4 = HierarchicalReduce(groups=4, neighbors=1)
    out4 = red4({"x": x})["x"]
    assert not np.allclose(np.asarray(out4[0]), np.asarray(x.mean(0)))


def test_hierarchical_contracts_toward_consensus():
    """Repeated application shrinks worker spread (gossip consensus)."""
    x = random.normal(random.PRNGKey(1), (8, 16))
    red = HierarchicalReduce(groups=4)
    spread0 = float(jnp.std(x, axis=0).mean())
    y = x
    for _ in range(3):
        y = red({"x": y})["x"]
    assert float(jnp.std(y, axis=0).mean()) < 0.1 * spread0


def test_hierarchical_dryrunnable_on_multipod_mesh_shapes():
    """eval_shape the full dc_s3gd step at the multipod worker count
    (W=32, pods=2) with hierarchical reduce + buckets — the dry-run path
    never allocates."""
    cfg = DCS3GDConfig(total_steps=10, warmup_steps=2)
    alg = registry.make("dc_s3gd", cfg, n_workers=32,
                        reducer="hierarchical", buckets=2)
    loss2, init, batch_fn = _loss_and_init()
    state = jax.eval_shape(alg.init, init)
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((32,) + x.shape[1:], x.dtype),
        stack_batches(batch_fn, 0, W))
    out_state, metrics = jax.eval_shape(
        lambda s, b: alg.step(s, b, loss_fn=loss2), state, batch)
    assert jax.tree_util.tree_structure(out_state) == \
        jax.tree_util.tree_structure(state)
    assert "loss" in metrics


def test_bucketed_comm_state_specs_on_multipod_mesh():
    """The `state_specs` hook covers the bucketed flat-buffer comm state
    on the real model: worker axes lead, the contiguous dim stays
    whole."""
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config, reduced
    from repro.launch import specs as S
    from repro.models.transformer import Model

    mcfg = reduced(get_config("qwen3-0.6b"))
    model = Model(mcfg, remat=False, scan_chunk=8, loss_chunk=8)
    alg = registry.make("dc_s3gd", CFG, n_workers=32, buckets=4)
    state = jax.eval_shape(alg.init, S.abstract_params(model))
    axes = MeshAxes(worker=("pod", "data"), model="model", model_size=1)
    spec = alg.state_specs(mcfg, state, axes)
    dp = state.comm["delta_prev"]
    assert isinstance(dp, list) and len(dp) >= 4
    assert spec.comm["delta_prev"] == [P(("pod", "data"), None)] * len(dp)


# ---------------------------------------------------------------------------
# buffer donation
# ---------------------------------------------------------------------------


def test_engine_train_step_donates_state():
    """The jitted step donates the TrainState: the old buffers are
    deleted after the call (no params-sized copy per iteration)."""
    from repro.launch.engine import Engine

    loss2, init, batch_fn = _loss_and_init()

    class _M:
        cfg = None

        def loss(self, params, batch):
            return loss2(params, batch)

    alg = registry.make("dc_s3gd", CFG, n_workers=W, buckets=2)
    engine = Engine(_M(), alg)
    state = alg.init(init)
    step_fn = engine.jit_train_step()
    batch = stack_batches(batch_fn, 0, W)
    old_leaves = jax.tree.leaves(state)
    new_state, _ = step_fn(state, batch)
    assert all(x.is_deleted() for x in old_leaves if hasattr(x,
                                                             "is_deleted"))
    # and the returned state is usable (buffers really were reused)
    newer, m = step_fn(new_state, stack_batches(batch_fn, 1, W))
    assert bool(jnp.isfinite(m["loss"]))


def test_engine_fit_with_buckets_and_donation():
    from repro.launch.engine import Engine

    loss2, init, batch_fn = _loss_and_init()

    class _M:
        cfg = None

        def loss(self, params, batch):
            return loss2(params, batch)

    alg = registry.make("dc_s3gd", CFG, n_workers=W, buckets=2)
    engine = Engine(_M(), alg)
    state, history, _ = engine.fit(
        alg.init(init), lambda t: stack_batches(batch_fn, t, W),
        steps=5, log_every=2, verbose=False)
    assert int(state.step) == 5
    assert [h["step"] for h in history] == [0, 2, 4]


def test_checkpoint_metadata_records_buckets(tmp_path):
    from repro.checkpoint import checkpoint_meta
    from repro.launch.engine import Engine, algorithm_for_checkpoint

    loss2, init, batch_fn = _loss_and_init()
    alg = registry.make("dc_s3gd", CFG, n_workers=W, buckets=3)
    state = alg.init(init)
    path = tmp_path / "b.npz"
    Engine(None, alg).save(path, state, step=0)
    assert checkpoint_meta(path)["buckets"] == 3
    restored_alg, resolved = algorithm_for_checkpoint(path, buckets=0)
    assert resolved["buckets"] == 3
    # the rebuilt algorithm's template matches the bucketed structure
    template = restored_alg.init(init)
    assert jax.tree_util.tree_structure(template) == \
        jax.tree_util.tree_structure(state)
