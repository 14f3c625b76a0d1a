"""Error-feedback compressed reducers (`repro.core.compress`) and the
small-ring gossip regression: wire semantics, residual bookkeeping,
per-bucket selection, checkpoint round-trips, and trajectory tracking."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random
from jax.sharding import PartitionSpec as P

from repro.core import registry
from repro.core.api import MeshAxes
from repro.core.compress import PowerSGDReduce, RandKReduce, TopKReduce
from repro.core.reduce import GossipReduce, MeanAllReduce
from repro.core.types import DCS3GDConfig
from repro.parallel import buckets as B

from helpers import stack_batches

CFG = DCS3GDConfig(learning_rate=0.1, momentum=0.9, lambda0=0.2,
                   weight_decay=1e-3, total_steps=1)
W = 4


def _bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and bool(jnp.array_equal(x, y))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# gossip small-ring regression (the headline bugfix)
# ---------------------------------------------------------------------------


def test_gossip_w2_matches_exact_two_worker_mean():
    """W=2, k=1: the single neighbor used to be rolled in from BOTH sides
    and divided by 3 — worker 0 got (2·w0? no: w0 + 2·w1)/3.  Dedup'd
    offsets give the exact 2-worker mean."""
    x = jnp.array([[1.0, 4.0, -2.0], [3.0, 0.0, 6.0]])
    out = GossipReduce(neighbors=1)({"x": x})["x"]
    want = jnp.broadcast_to(x.mean(axis=0, keepdims=True), x.shape)
    assert bool(jnp.array_equal(out, want))


@pytest.mark.parametrize("w,k,row", [
    # hand-computed mixing rows (worker 0's weights over workers)
    (3, 1, [1 / 3, 1 / 3, 1 / 3]),      # full ring at W=3
    (2, 1, [1 / 2, 1 / 2]),             # the double-count case
    (3, 2, [1 / 3, 1 / 3, 1 / 3]),      # 2k+1=5 > W=3: still exact mean
    (5, 1, [1 / 3, 1 / 3, 0, 0, 1 / 3]),  # large ring: strict neighborhood
])
def test_gossip_mixing_matrix_rows(w, k, row):
    x = jnp.eye(w)
    out = GossipReduce(neighbors=k)({"x": x})["x"]
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(row),
                               rtol=1e-6)


def test_gossip_mixing_rows_are_stochastic_on_small_rings():
    """Every row of the mixing matrix sums to 1 for all (W, k) — the
    double-count bug made W=2 rows sum to 1 but with weight 2/3 on the
    neighbor (a biased, non-symmetric consensus)."""
    for w in (2, 3, 4, 5):
        for k in (1, 2, 3):
            mix = GossipReduce(neighbors=k)({"x": jnp.eye(w)})["x"]
            np.testing.assert_allclose(np.asarray(mix.sum(1)),
                                       np.ones(w), rtol=1e-6)
            # symmetric: worker i weighs j like j weighs i
            np.testing.assert_allclose(np.asarray(mix),
                                       np.asarray(mix.T), rtol=1e-6)


def test_gossip_neighbors_reachable_from_config():
    from repro.core.reduce import HierarchicalReduce
    cfg = DCS3GDConfig(gossip_neighbors=2)
    assert registry.make_reducer("gossip", cfg).neighbors == 2
    assert GossipReduce(cfg).neighbors == 2
    # the same knob drives hierarchical's inter-pod ring width
    assert HierarchicalReduce(cfg).neighbors == 2
    assert HierarchicalReduce(cfg, neighbors=1).neighbors == 1


def test_multi_hop_wire_bytes_scale_with_neighbors():
    """The wire column must reflect topology width: a 2k-neighbor ring
    moves the payload 2k times (hierarchical adds the intra-group hop)."""
    from repro.core.reduce import HierarchicalReduce
    sizes = [1024]
    assert GossipReduce(neighbors=2).wire_bytes(sizes) == \
        2 * GossipReduce(neighbors=1).wire_bytes(sizes)
    assert GossipReduce(neighbors=1).wire_bytes(sizes) == 2 * 1024 * 4
    assert HierarchicalReduce(neighbors=1).wire_bytes(sizes) == \
        3 * 1024 * 4


# ---------------------------------------------------------------------------
# compressed reducers: wire semantics
# ---------------------------------------------------------------------------


def _tiny_plan(n_buckets=2, block=8):
    """A 2-bucket plan with small, un-padded-ish buckets (block=8) so the
    sparsifiers actually drop elements in tests."""
    tree = {"v": jnp.zeros((60,)), "m": jnp.zeros((8, 8))}
    plan = B.plan_buckets(tree, n_buckets, block=block)
    assert plan.n_buckets == 2
    return plan


def _rand_buckets(plan, key=0, lead=(W,)):
    ks = random.split(random.PRNGKey(key), plan.n_buckets)
    return [random.normal(k, lead + (n,))
            for k, n in zip(ks, plan.bucket_sizes)]


@pytest.mark.parametrize("make", [
    lambda: TopKReduce(density=0.25),
    lambda: RandKReduce(density=0.25),
    lambda: PowerSGDReduce(rank=2),
])
def test_compressed_reducers_registered_and_stateful(make):
    red = make()
    assert red.name in registry.names(registry.REDUCER)
    assert red.stateless is False
    assert red.reduces_weights is False
    assert isinstance(red.hparams, dict) and "comm_dtype" in red.hparams


@pytest.mark.parametrize("make", [
    lambda: TopKReduce(density=0.25),
    lambda: RandKReduce(density=0.25),
    lambda: PowerSGDReduce(rank=2),
])
def test_error_feedback_conservation(make):
    """The defining EF invariant: what the wire carried plus what the
    residual kept equals the full payload — mean(out) == mean over
    workers of (d + e_old − e_new), exactly in f32."""
    red = make()
    plan = _tiny_plan()
    rstate = red.init(W, plan)
    d = _rand_buckets(plan)
    out, rs1 = red(d, rstate)
    for b in range(plan.n_buckets):
        carried = (d[b] + rstate["residual"][b]
                   - rs1["residual"][b]).mean(0, keepdims=True)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(carried),
                                   atol=1e-6)
        assert out[b].shape == (1, plan.bucket_sizes[b])


def test_topk_full_density_bitwise_matches_mean_allreduce():
    red = TopKReduce(density=1.0)
    plan = _tiny_plan()
    d = _rand_buckets(plan)
    out, rs = red(d, red.init(W, plan))
    assert _bitwise(out, MeanAllReduce()(d))
    # nothing dropped -> residual identically zero
    assert all(not np.asarray(r).any() for r in rs["residual"])


def test_topk_residual_carries_the_dropped_mass():
    red = TopKReduce(density=0.25)
    plan = _tiny_plan()
    d = _rand_buckets(plan)
    out, rs = red(d, red.init(W, plan))
    for b in range(plan.n_buckets):
        n = plan.bucket_sizes[b]
        k = max(1, int(round(0.25 * n)))
        resid = np.asarray(rs["residual"][b])
        # per worker: exactly n-k coordinates survive in the residual
        # (ties aside), and every kept coordinate dominates every dropped
        for w_i in range(W):
            dropped = np.flatnonzero(resid[w_i])
            assert len(dropped) <= n - k
            kept_min = np.abs(np.asarray(d[b][w_i]))[
                np.setdiff1d(np.arange(n), dropped)].min()
            assert np.abs(resid[w_i]).max() <= kept_min + 1e-6


def test_randk_support_is_shared_across_workers_and_steps_differ():
    red = RandKReduce(density=0.25)
    plan = _tiny_plan()
    rs = red.init(W, plan)
    d = _rand_buckets(plan)
    out1, rs = red(d, rs)
    # the mean is exact on the sampled support: nonzero coordinates of
    # the output are a subset of the support; residual == payload off it
    nz1 = np.flatnonzero(np.asarray(out1[0][0]))
    out2, rs = red(d, rs)
    nz2 = np.flatnonzero(np.asarray(out2[0][0]))
    assert not np.array_equal(nz1, nz2)  # fresh support each step
    assert int(rs["step"]) == 2


def test_powersgd_output_is_rank_r_and_common():
    red = PowerSGDReduce(rank=2)
    plan = _tiny_plan()
    d = _rand_buckets(plan)
    out, rs = red(d, red.init(W, plan))
    for b, o in enumerate(out):
        n = plan.bucket_sizes[b]
        rows, cols, r = red._dims(n)
        m = np.asarray(o[0]).reshape(rows, cols)
        assert np.linalg.matrix_rank(m, tol=1e-5) <= r
        assert rs["q"][b].shape == (cols, r)


def test_per_bucket_sparsify_never_crosses_bucket_boundaries():
    """All the globally-largest magnitudes live in bucket 0; a per-bucket
    top-k must STILL select k coordinates inside bucket 1 (a global
    selection would starve it to zero)."""
    red = TopKReduce(density=0.25)
    plan = _tiny_plan()
    d = _rand_buckets(plan)
    d[0] = d[0] * 1e6      # bucket 0 dominates any global ranking
    out, _ = red(d, red.init(W, plan))
    n1 = plan.bucket_sizes[1]
    k1 = max(1, int(round(0.25 * n1)))
    nz = int((np.asarray(out[1][0]) != 0).sum())
    assert nz >= 1 and abs(nz - k1) <= W * k1  # selected within bucket 1
    # and bucket 0's selection budget was not inflated by bucket 1
    k0 = max(1, int(round(0.25 * plan.bucket_sizes[0])))
    assert int((np.asarray(out[0][0]) != 0).sum()) <= W * k0


def test_magnitude_threshold_exact_below_cliff():
    """n <= EXACT_TOPK_MAX: the threshold IS the lax.top_k k-th value,
    and >= selects exactly k elements (random floats — no ties)."""
    from repro.core.compress import magnitude_threshold
    mag = jnp.abs(random.normal(random.PRNGKey(0), (3, 512)))
    k = 37
    t = magnitude_threshold(mag, k)
    expect = jax.lax.top_k(mag, k)[0][..., -1:]
    assert _bitwise(t, expect)
    assert (np.asarray(mag >= t).sum(-1) == k).all()


def test_magnitude_threshold_full_density_is_zero():
    from repro.core.compress import EXACT_TOPK_MAX, magnitude_threshold
    for n in (64, EXACT_TOPK_MAX * 2):
        mag = jnp.abs(random.normal(random.PRNGKey(1), (2, n)))
        assert not np.asarray(magnitude_threshold(mag, n)).any()
        assert not np.asarray(magnitude_threshold(mag, n + 5)).any()


def _hi_floor(x):
    """The smallest f32 whose top-16 bits equal x's (the coarse
    threshold's documented value)."""
    return ((np.float32(x).view(np.int32) >> 16) << 16).view(np.float32)


def test_magnitude_threshold_coarse_is_kth_hi_floor():
    """Above the cliff the threshold is the bit-space floor of the TRUE
    k-th magnitude — at least k selected, magnitude dominance, and the
    overshoot confined to low-mantissa ties of the k-th value."""
    from repro.core.compress import EXACT_TOPK_MAX, magnitude_threshold
    n = EXACT_TOPK_MAX * 2
    mag = jnp.abs(random.normal(random.PRNGKey(2), (2, n)))
    k = 131
    t = np.asarray(magnitude_threshold(mag, k))
    srt = np.sort(np.asarray(mag), axis=-1)[:, ::-1]
    for r in range(mag.shape[0]):
        assert t[r, 0] == _hi_floor(srt[r, k - 1]), (t[r, 0], srt[r, k - 1])
        kept = np.asarray(mag)[r] >= t[r, 0]
        assert kept.sum() >= k
        # dominance: every kept magnitude >= every dropped one up to the
        # hi-floor tie window
        assert np.asarray(mag)[r][~kept].max() < t[r, 0]


def test_magnitude_threshold_coarse_fallback_on_unlucky_subsample():
    """All large values at odd indices: the 1/16-strided subsample sees
    none of them, its estimate is invalid, and the lax.cond full-row
    fallback must still return the exact k-th hi-value."""
    from repro.core.compress import EXACT_TOPK_MAX, magnitude_threshold
    n = EXACT_TOPK_MAX * 2
    k = 97
    base = np.abs(np.asarray(
        random.normal(random.PRNGKey(3), (1, n)))) * 1e-3
    base[0, 1:2 * k * 16:16] += 100.0     # odd stride-16 offsets only
    mag = jnp.asarray(base, jnp.float32)
    t = np.asarray(magnitude_threshold(mag, k))[0, 0]
    srt = np.sort(base[0])[::-1]
    assert t == _hi_floor(srt[k - 1])
    assert (base[0] >= t).sum() >= k


def test_reducer_use_kernels_matches_xla_path():
    """The fused Pallas compression body (select + wire cast + worker
    mean + residual update in one launch) is a pure lowering swap:
    bitwise against the unfused XLA path, for the own-support and
    union-support variants, at a kernel-aligned bucket size."""
    from repro.core.compress import TopKExactReduce
    from repro.kernels import compress as KC
    tree = {"big": jnp.zeros((2 * KC.BLOCK,))}
    plan = B.plan_buckets(tree, 1)
    d = [random.normal(random.PRNGKey(4), (W, n))
         for n in plan.bucket_sizes]
    for make in (lambda: TopKReduce(density=0.01),
                 lambda: TopKExactReduce(density=0.01)):
        ref_red, k_red = make(), make()
        k_red.use_kernels = True
        out0, rs0 = ref_red(d, ref_red.init(W, plan))
        out1, rs1 = k_red(d, k_red.init(W, plan))
        assert _bitwise(out0, out1)
        assert _bitwise(rs0, rs1)


def test_topk_full_density_use_kernels_still_matches_mean():
    """density=1.0 through the FUSED body: zero threshold keeps all, so
    the kernelized topk still bitwise-equals the dense mean."""
    from repro.kernels import compress as KC
    tree = {"big": jnp.zeros((KC.BLOCK,))}
    plan = B.plan_buckets(tree, 1)
    d = [random.normal(random.PRNGKey(5), (W, n))
         for n in plan.bucket_sizes]
    red = TopKReduce(density=1.0)
    red.use_kernels = True
    out, rs = red(d, red.init(W, plan))
    assert _bitwise(out, MeanAllReduce()(d))
    assert all(not np.asarray(r).any() for r in rs["residual"])


def test_compressed_reducers_require_buckets():
    for red in (TopKReduce(), RandKReduce(), PowerSGDReduce()):
        with pytest.raises(ValueError, match="buckets"):
            red.init(W, None)
    with pytest.raises(TypeError, match="bucketed"):
        TopKReduce(density=1.0)({"w": jnp.zeros((W, 3))},
                                {"residual": []})


def test_wire_bytes_accounting():
    sizes = [32768, 65536]
    dense = MeanAllReduce().wire_bytes(sizes)
    assert dense == sum(sizes) * 4
    topk = TopKReduce(density=0.01).wire_bytes(sizes)
    assert dense / topk >= 8         # the acceptance ratio (it's ~50x)
    randk = RandKReduce(density=0.01).wire_bytes(sizes)
    assert randk < topk              # shared seed: no index payload
    psgd = PowerSGDReduce(rank=4)
    assert psgd.wire_bytes(sizes) == sum(
        (r + c) * 4 * 4 for r, c in
        [(psgd._dims(n)[0], psgd._dims(n)[1]) for n in sizes])


# ---------------------------------------------------------------------------
# through the algorithms
# ---------------------------------------------------------------------------


def _bigger_problem(n=12, m=64, seed=3):
    """A quadratic whose parameters are big enough that 1%-per-bucket
    sparsification actually drops coordinates (the M matrix matters)."""
    key = random.PRNGKey(seed)
    k1, k2, k3 = random.split(key, 3)
    w_star = random.normal(k1, (n,))
    proj = random.normal(k3, (m,)) / jnp.sqrt(m)

    def batch_fn(step, worker, bs=8):
        k = random.fold_in(random.fold_in(k2, step), worker)
        A = random.normal(k, (bs, n)) / jnp.sqrt(n)
        return {"A": A, "y": A @ w_star}

    def loss_fn(p, b):
        eff = p["w"] + p["M"] @ proj
        pred = b["A"] @ eff
        return 0.5 * jnp.mean((pred - b["y"]) ** 2)

    init = {"w": jnp.zeros((n,)), "M": jnp.zeros((n, m))}
    return loss_fn, init, batch_fn


def _run(reducer, steps, workers, buckets=2, use_kernels=False):
    loss_fn, init, batch_fn = _bigger_problem()
    alg = registry.make("dc_s3gd", CFG, n_workers=workers, reducer=reducer,
                        buckets=buckets, use_kernels=use_kernels)
    state = alg.init(init)
    step = jax.jit(lambda s, b: alg.step(s, b, loss_fn=loss_fn))
    m = None
    for t in range(steps):
        state, m = step(state, stack_batches(batch_fn, t, workers))
    return alg, state, m


@pytest.mark.parametrize("reducer", [
    TopKReduce(density=0.01), RandKReduce(density=0.1),
    PowerSGDReduce(rank=2)])
def test_compressed_dc_s3gd_tracks_uncompressed_20_steps_w8(reducer):
    """Error feedback keeps the compressed trajectory on the uncompressed
    one: after 20 steps at W=8 the loss is within tolerance (and both
    converge well below the init loss).  randk needs a higher density
    for the same delivery rate — its support is blind to magnitude."""
    _, s_ref, m_ref = _run("mean_allreduce", 20, 8)
    _, s_c, m_c = _run(reducer, 20, 8)
    ref, comp = float(m_ref["loss"]), float(m_c["loss"])
    assert np.isfinite(comp)
    assert comp < 0.25              # converged (init loss ~0.5)
    assert abs(comp - ref) < 0.1    # tracking the uncompressed run


def test_compressed_state_rides_comm_and_is_donation_stable():
    alg, state, _ = _run(TopKReduce(density=0.02), 3, W)
    rs = state.comm["reducer"]
    plan = alg._plan(state.params)
    assert [r.shape for r in rs["residual"]] == \
        [(W, n) for n in plan.bucket_sizes]
    # shape/dtype-stable across steps: a further step round-trips the
    # structure (the donation precondition)
    loss_fn, _, batch_fn = _bigger_problem()
    state2, _ = alg.step(state, stack_batches(batch_fn, 9, W),
                         loss_fn=loss_fn)
    assert jax.tree_util.tree_structure(state2) == \
        jax.tree_util.tree_structure(state)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(state2), jax.tree.leaves(state)))


def test_compressed_with_fused_kernel_tail():
    """use_kernels composes with compressed reducers (D arrives bucketed
    either way); the trajectory stays finite and the residual advances."""
    _, state, m = _run(TopKReduce(density=0.02), 3, W, use_kernels=True)
    assert np.isfinite(float(m["loss"]))
    assert any(np.asarray(r).any()
               for r in state.comm["reducer"]["residual"])


def test_revoked_window_returns_payload_to_residual():
    """dynamic_ssp revoking the stale window discards the reducer output
    — the compressed payload must return to the error-feedback residual
    (not vanish), so no mass is ever lost: on a revoked step
    residual' == delta_prev + residual (the full accumulated payload)."""
    loss_fn, init, batch_fn = _bigger_problem()
    alg = registry.make("dc_s3gd", CFG, n_workers=W,
                        reducer=TopKReduce(density=0.02), buckets=2,
                        staleness="dynamic_ssp")
    state = alg.init(init)
    for t in range(3):
        state, m = alg.step(state, stack_batches(batch_fn, t, W),
                            loss_fn=loss_fn)
    assert float(m["ssp_admit"]) == 1.0
    # build a skew above the threshold -> next step revokes the window
    state = alg.observe_progress(state, [99] + [0] * (W - 1))
    before = state.comm
    state2, m = alg.step(state, stack_batches(batch_fn, 3, W),
                         loss_fn=loss_fn)
    assert float(m["ssp_admit"]) == 0.0
    for dp, e_old, e_new in zip(before["delta_prev"],
                                before["reducer"]["residual"],
                                state2.comm["reducer"]["residual"]):
        np.testing.assert_allclose(
            np.asarray(e_new),
            np.asarray(dp.astype(jnp.float32) + e_old), atol=1e-7)
    # and the admitted steps keep the normal EF update (not the revoke)
    state3, m = alg.step(state2, stack_batches(batch_fn, 4, W),
                         loss_fn=loss_fn)
    assert float(m["ssp_admit"]) == 1.0


def test_ssgd_with_compressed_reducer():
    loss_fn, init, batch_fn = _bigger_problem()
    alg = registry.make("ssgd", CFG, n_workers=W,
                        reducer=TopKReduce(density=0.02), buckets=2)
    state = alg.init(init)
    assert "reducer" in state.comm
    for t in range(3):
        state, m = alg.step(state, stack_batches(batch_fn, t, W),
                            loss_fn=loss_fn)
    assert np.isfinite(float(m["loss"]))
    # buckets=0 has no flat wire: a clear error, not a silent fallback
    alg0 = registry.make("ssgd", CFG, n_workers=W,
                         reducer=TopKReduce(density=0.02), buckets=0)
    with pytest.raises(ValueError, match="buckets"):
        alg0.init(init)


def test_compressed_state_specs_on_multipod_mesh():
    """The sharding hook covers comm["reducer"] on the real model: worker
    axes lead the residuals, the warm-started q is replicated."""
    from repro.configs import get_config, reduced
    from repro.launch import specs as S
    from repro.models.transformer import Model

    mcfg = reduced(get_config("qwen3-0.6b"))
    model = Model(mcfg, remat=False, scan_chunk=8, loss_chunk=8)
    alg = registry.make("dc_s3gd", CFG, n_workers=32,
                        reducer=PowerSGDReduce(rank=2), buckets=4)
    state = jax.eval_shape(alg.init, S.abstract_params(model))
    axes = MeshAxes(worker=("pod", "data"), model="model", model_size=1)
    spec = alg.state_specs(mcfg, state, axes)
    n_b = len(state.comm["reducer"]["residual"])
    assert spec.comm["reducer"]["residual"] == \
        [P(("pod", "data"), None)] * n_b
    assert spec.comm["reducer"]["q"] == [P(None, None)] * n_b


def test_compressed_step_dryruns_under_eval_shape():
    """The whole compressed step eval_shapes — the dry-run never
    allocates (lax.top_k / QR / PRNG all trace abstractly)."""
    loss_fn, init, batch_fn = _bigger_problem()
    alg = registry.make("dc_s3gd", CFG, n_workers=8,
                        reducer=PowerSGDReduce(rank=2), buckets=2)
    state = jax.eval_shape(alg.init, init)
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((8,) + x.shape[1:], x.dtype),
        stack_batches(batch_fn, 0, W))
    out_state, metrics = jax.eval_shape(
        lambda s, b: alg.step(s, b, loss_fn=loss_fn), state, batch)
    assert jax.tree_util.tree_structure(out_state) == \
        jax.tree_util.tree_structure(state)
    assert "loss" in metrics


# ---------------------------------------------------------------------------
# checkpoint metadata + residual round-trip
# ---------------------------------------------------------------------------


def test_reducer_hparams_round_trip_through_checkpoint(tmp_path):
    """The satellite regression: `hierarchical groups=4` / `gossip
    neighbors=2` resumed from metadata must NOT silently rebuild with
    groups=2 / neighbors=1."""
    from repro.checkpoint import checkpoint_meta
    from repro.launch.engine import Engine, algorithm_for_checkpoint

    loss_fn, init, batch_fn = _bigger_problem()
    for name, opts, attr in [
            ("gossip", {"neighbors": 2}, "neighbors"),
            ("hierarchical", {"groups": 4}, "groups")]:
        red = registry.make_reducer(name, CFG, **opts)
        alg = registry.make("dc_s3gd", CFG, n_workers=8, reducer=red)
        state = alg.init(init)
        path = tmp_path / f"{name}.npz"
        Engine(None, alg).save(path, state, step=0)
        meta = checkpoint_meta(path)
        assert meta["reducer_opts"][attr] == opts[attr]
        assert meta["reducer_opts"]["comm_dtype"] == "float32"
        restored, resolved = algorithm_for_checkpoint(path)
        assert getattr(restored.reducer, attr) == opts[attr]


def test_compressed_residual_round_trips_through_checkpoint(tmp_path):
    from repro.launch.engine import Engine, algorithm_for_checkpoint

    loss_fn, init, batch_fn = _bigger_problem()
    alg, state, _ = _run(TopKReduce(density=0.02), 3, W)
    assert any(np.asarray(r).any()
               for r in state.comm["reducer"]["residual"])
    path = tmp_path / "ef.npz"
    Engine(None, alg).save(path, state, step=3)

    restored_alg, resolved = algorithm_for_checkpoint(path, buckets=0)
    assert resolved["buckets"] == 2
    assert restored_alg.reducer.name == "topk"
    assert restored_alg.reducer.density == pytest.approx(0.02)
    template = restored_alg.init(init)
    assert jax.tree_util.tree_structure(template) == \
        jax.tree_util.tree_structure(state)
    engine = Engine(None, restored_alg)
    got = engine.restore(path, template)
    assert _bitwise(got.comm["reducer"], state.comm["reducer"])
    # and the restored run steps with the carried residual
    state2, m = restored_alg.step(got, stack_batches(batch_fn, 3, W),
                                  loss_fn=loss_fn)
    assert np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# topk_exact — the all-gather union-support variant (PR 5)
# ---------------------------------------------------------------------------


def test_topk_exact_registered_and_stateful():
    from repro.core.compress import TopKExactReduce
    red = TopKExactReduce(density=0.25)
    assert "topk_exact" in registry.names(registry.REDUCER)
    assert red.stateless is False and red.reduces_weights is False
    assert red.hparams == {"comm_dtype": "float32", "density": 0.25}


def test_topk_exact_is_exact_dense_mean_on_union_support():
    """The point of the variant: on every coordinate ANY worker selected,
    the output equals the exact dense mean BITWISE (plain topk biases a
    coordinate selected by w of W workers low by w/W)."""
    from repro.core.compress import TopKExactReduce, _k_of
    red = TopKExactReduce(density=0.25)
    plan = _tiny_plan()
    d = _rand_buckets(plan)
    out, rs = red(d, red.init(W, plan))
    dense = MeanAllReduce()(d)
    for b in range(plan.n_buckets):
        a = np.asarray(d[b])
        k = _k_of(a.shape[-1], 0.25)
        thresh = np.sort(np.abs(a), axis=-1)[:, -k][:, None]
        union = (np.abs(a) >= thresh).any(0)
        got = np.asarray(out[b])[0]
        want = np.asarray(dense[b])[0]
        assert union.any() and not union.all()
        np.testing.assert_array_equal(got[union], want[union])
        np.testing.assert_array_equal(got[~union], 0.0)
        # residual carries exactly the off-union mass, per worker
        np.testing.assert_array_equal(
            np.asarray(rs["residual"][b])[:, union], 0.0)
        np.testing.assert_array_equal(
            np.asarray(rs["residual"][b])[:, ~union], a[:, ~union])


def test_topk_exact_unbiases_the_partial_support_mean():
    """Coordinate selected by exactly one worker: topk reports v/W with
    the rest riding residuals; topk_exact reports the true mean."""
    from repro.core.compress import TopKExactReduce
    tree = {"v": jnp.zeros((16,))}
    plan = B.plan_buckets(tree, 1, block=8)
    # worker 0's top-1 is coordinate 0; everyone else's is coordinate 1
    # (values distinct — ties would smear the top-k supports)
    d = [jnp.full((W, plan.bucket_sizes[0]), 0.1)]
    d[0] = d[0].at[0, 0].set(10.0)
    d[0] = d[0].at[1:, 1].set(1.0)
    exact = TopKExactReduce(density=1 / 16)
    plain = TopKReduce(density=1 / 16)
    oe, _ = exact(d, exact.init(W, plan))
    op, _ = plain(d, plain.init(W, plan))
    want = float((10.0 + 0.1 * (W - 1)) / W)
    assert abs(float(oe[0][0, 0]) - want) < 1e-6
    assert abs(float(op[0][0, 0]) - 10.0 / W) < 1e-6  # the bias


def test_topk_exact_full_density_bitwise_matches_mean_allreduce():
    from repro.core.compress import TopKExactReduce
    red = TopKExactReduce(density=1.0)
    plan = _tiny_plan()
    d = _rand_buckets(plan)
    out, rs = red(d, red.init(W, plan))
    assert _bitwise(out, MeanAllReduce()(d))
    assert all(not np.asarray(r).any() for r in rs["residual"])


def test_topk_exact_wire_bytes_accounting():
    """Per worker: k int32 support coordinates (the all-gather round) +
    up to min(W·k, n) union values — costlier than gather-free topk,
    bought for exactness."""
    from repro.core.compress import TopKExactReduce
    red = TopKExactReduce(density=0.25)
    plan = _tiny_plan()
    red.init(W, plan)
    sizes = [int(n) for n in plan.bucket_sizes]
    want = sum((n // 4) * 4 + min(W * (n // 4), n) * 4 for n in sizes)
    assert red.wire_bytes(sizes) == want
    plain = TopKReduce(density=0.25)
    assert red.wire_bytes(sizes) > plain.wire_bytes(sizes)


def test_topk_exact_in_step_time_grid():
    from benchmarks.step_time import COMPRESSED
    assert "topk_exact" in COMPRESSED
