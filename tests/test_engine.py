"""The new launch seams: per-algorithm sharding hooks, staleness policies,
the Engine's checkpoint metadata, and dry-run sharding parity with the
pre-refactor launch layer."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.core import registry
from repro.core.api import MeshAxes, TrainState
from repro.core.types import DCS3GDConfig
from repro.launch import specs as S
from repro.launch.engine import Engine, algorithm_for_checkpoint
from repro.models.transformer import Model
from repro.parallel.sharding import opt_specs, param_specs

from helpers import quadratic_problem, stack_batches

CFG = DCS3GDConfig(learning_rate=0.1, momentum=0.9, lambda0=0.2,
                   weight_decay=1e-3, total_steps=1)
# a fake 2-axis mesh: 4 workers on 'data', model axis of 1
AXES = MeshAxes(worker=("data",), model="model", model_size=1)
ALGOS = ["dc_s3gd", "stale", "ssgd", "dc_asgd"]


def _is_p(x):
    return isinstance(x, P)


def _reduced_model():
    cfg = reduced(get_config("qwen3-0.6b"))
    return cfg, Model(cfg, remat=False, scan_chunk=8, loss_chunk=8)


# ---------------------------------------------------------------------------
# sharding hooks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("algo", ALGOS)
def test_state_specs_hook_matches_eval_shape_tree(algo):
    """For every algorithm, the `state_specs` hook mirrors the
    `jax.eval_shape` state tree exactly: same structure, and every spec
    has rank <= its leaf (P() on scalars)."""
    cfg, model = _reduced_model()
    alg = registry.make(algo, CFG, n_workers=4)
    params = S.abstract_params(model)
    state = jax.eval_shape(alg.init, params)
    spec = alg.state_specs(cfg, state, AXES)
    assert isinstance(spec, TrainState)
    leaves = jax.tree.leaves(state)
    spec_leaves = jax.tree.leaves(spec, is_leaf=_is_p)
    assert len(leaves) == len(spec_leaves)
    for leaf, sp in zip(leaves, spec_leaves):
        assert isinstance(sp, P), sp
        assert len(sp) <= leaf.ndim, (algo, leaf.shape, sp)


@pytest.mark.parametrize("algo", ALGOS)
def test_batch_specs_hook_shards_worker_axis(algo):
    cfg, model = _reduced_model()
    alg = registry.make(algo, CFG, n_workers=4)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 2, 16), jnp.int32),
             "labels": jax.ShapeDtypeStruct((4, 2, 16), jnp.int32)}
    spec = alg.batch_specs(cfg, batch, AXES)
    for sp in jax.tree.leaves(spec, is_leaf=_is_p):
        assert tuple(sp)[0] == "data", (algo, sp)


def test_worker_axis_placement_differs_by_algorithm():
    """DC-S3GD leads every state leaf with the worker axes; SSGD (shared
    weights) and the DC-ASGD PS simulator stay canonical."""
    cfg, model = _reduced_model()
    params = S.abstract_params(model)

    dc = registry.make("dc_s3gd", CFG, n_workers=4)
    spec = dc.state_specs(cfg, jax.eval_shape(dc.init, params), AXES)
    for sp in jax.tree.leaves(spec.params, is_leaf=_is_p):
        assert tuple(sp)[0] == "data", sp

    for name in ("ssgd", "dc_asgd"):
        alg = registry.make(name, CFG, n_workers=4)
        spec = alg.state_specs(cfg, jax.eval_shape(alg.init, params), AXES)
        for sp in jax.tree.leaves(spec.params, is_leaf=_is_p):
            assert "data" not in tuple(sp), (name, sp)


def test_dryrun_specs_match_pre_refactor_tree():
    """The hook-derived dry-run shardings are IDENTICAL to what the
    pre-refactor launch layer computed (frozen transcript of the old
    `launch/dryrun.py` + `parallel/sharding.state_specs` logic) for
    qwen3-0.6b x train_4k on the pod mesh."""
    from repro.core.types import INPUT_SHAPES

    arch, shape = "qwen3-0.6b", INPUT_SHAPES["train_4k"]
    cfg = S.dryrun_model_config(get_config(arch))
    model = Model(cfg, remat=True)
    W, ms, wa = 16, 16, "data"          # pod mesh: ('data','model')=(16,16)
    dc_cfg = DCS3GDConfig(total_steps=10_000, warmup_steps=1_500)
    alg = registry.make("dc_s3gd", dc_cfg, n_workers=W)
    state = S.abstract_train_state(model, W, dc_cfg, alg)
    batch = S.train_batch_specs(cfg, shape, W)

    # --- frozen pre-refactor derivation (PR 1 dryrun.build_train) ---------
    ps = param_specs(cfg, state.params, model_size=ms, worker_axes=wa)
    opt = opt_specs(cfg, state.opt, model_size=ms, worker_axes=wa)
    comm = {k: param_specs(cfg, v, model_size=ms, worker_axes=wa)
            for k, v in state.comm.items()}
    old_state_spec = TrainState(ps, opt, comm, P())

    def old_batch_spec(leaf):
        return P(wa, *(None,) * (leaf.ndim - 1))

    # --- the one seam everything now derives from -------------------------
    axes = MeshAxes(worker=("data",), model="model", model_size=ms)
    new_state_spec = alg.state_specs(cfg, state, axes)
    new_batch_spec = alg.batch_specs(cfg, batch, axes)

    old_l = jax.tree.leaves(old_state_spec, is_leaf=_is_p)
    new_l = jax.tree.leaves(new_state_spec, is_leaf=_is_p)
    assert len(old_l) == len(new_l)
    assert all(a == b for a, b in zip(old_l, new_l))
    for leaf, sp in zip(jax.tree.leaves(batch),
                        jax.tree.leaves(new_batch_spec, is_leaf=_is_p)):
        assert sp == old_batch_spec(leaf), (leaf.shape, sp)


# ---------------------------------------------------------------------------
# staleness policies
# ---------------------------------------------------------------------------


def _bitwise(a, b):
    return all(bool(jnp.array_equal(x, y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_staleness_registry_names():
    assert set(registry.names(registry.STALENESS_POLICY)) == {
        "fixed", "dynamic_ssp"}


def test_dynamic_ssp_below_threshold_is_bitwise_fixed():
    """Skew at or below the threshold admits the stale window — the
    dynamic_ssp trajectory must reproduce `fixed` (= PR 1 step math)
    bitwise, params and carried deltas both."""
    loss_fn, init, _, batch_fn = quadratic_problem(n=8, seed=3)
    W = 4
    a_fixed = registry.make("dc_s3gd", CFG, n_workers=W)
    a_ssp = registry.make("dc_s3gd", CFG, n_workers=W,
                          staleness="dynamic_ssp")
    assert a_fixed.staleness.name == "fixed"
    s_f, s_d = a_fixed.init(init), a_ssp.init(init)
    # observed skew 3 <= cfg.ssp_threshold (4)
    s_d = a_ssp.observe_progress(s_d, [3, 1, 0, 2])
    for t in range(5):
        batch = stack_batches(batch_fn, t, W)
        s_f, m_f = a_fixed.step(s_f, batch, loss_fn=loss_fn)
        s_d, m_d = a_ssp.step(s_d, batch, loss_fn=loss_fn)
        assert _bitwise(s_f.params, s_d.params), t
        assert _bitwise(s_f.comm["delta_prev"], s_d.comm["delta_prev"]), t
        assert bool(jnp.array_equal(m_f["loss"], m_d["loss"])), t
        assert float(m_d["ssp_admit"]) == 1.0


def test_dynamic_ssp_above_threshold_revokes_then_recovers():
    """Skew beyond the threshold forces the blocking pull toward the
    global average for ONE step, then the window re-opens (the sync
    resolves the staleness — SSP barrier semantics, not a permanent
    downgrade).  Run on the gossip reducer, where the global pull
    genuinely differs from the admitted neighborhood mixing; workers
    diverge for two steps first so the pull has something to do."""
    loss_fn, init, _, batch_fn = quadratic_problem(n=8, seed=3)
    W = 8
    a_fixed = registry.make("dc_s3gd", CFG, n_workers=W, reducer="gossip")
    a_ssp = registry.make("dc_s3gd", CFG, n_workers=W, reducer="gossip",
                          staleness="dynamic_ssp")
    s_f, s_d = a_fixed.init(init), a_ssp.init(init)
    for t in range(2):
        batch = stack_batches(batch_fn, t, W)
        s_f, _ = a_fixed.step(s_f, batch, loss_fn=loss_fn)
        s_d, m_d = a_ssp.step(s_d, batch, loss_fn=loss_fn)
        assert float(m_d["ssp_admit"]) == 1.0
    assert _bitwise(s_f.params, s_d.params)            # admitted so far
    s_d = a_ssp.observe_progress(s_d, [9] + [0] * (W - 1))  # skew 9 > 4
    admits = []
    for t in range(2, 5):
        batch = stack_batches(batch_fn, t, W)
        s_f, _ = a_fixed.step(s_f, batch, loss_fn=loss_fn)
        s_d, m_d = a_ssp.step(s_d, batch, loss_fn=loss_fn)
        admits.append(float(m_d["ssp_admit"]))
        assert bool(jnp.isfinite(m_d["loss"]))
    assert admits == [0.0, 1.0, 1.0]                   # one sync, re-opened
    assert not _bitwise(s_f.params, s_d.params)        # the pull happened


def test_dynamic_ssp_threshold_is_runtime_tunable():
    """The threshold comes from cfg (ssp_threshold), not a constant."""
    cfg_tight = DCS3GDConfig(ssp_threshold=0)
    pol = registry.make_staleness_policy("dynamic_ssp", cfg_tight)
    assert pol.threshold == 0
    admit, _ = pol.admit({"worker_steps": jnp.array([1, 0], jnp.int32)})
    assert not bool(admit)
    admit, _ = pol.admit({"worker_steps": jnp.array([2, 2], jnp.int32)})
    assert bool(admit)


def test_dynamic_ssp_state_is_carried_and_sharded():
    """Policy state rides in TrainState.comm['staleness'] and the hook
    shards its (W,) counters over the worker axes."""
    init = {"w": jnp.zeros((4,))}
    alg = registry.make("dc_s3gd", CFG, n_workers=4,
                        staleness="dynamic_ssp")
    state = alg.init(init)
    assert "staleness" in state.comm
    assert state.comm["staleness"]["worker_steps"].shape == (4,)
    spec = alg.staleness.state_specs(AXES)
    assert spec["worker_steps"] == P("data")


# ---------------------------------------------------------------------------
# Engine checkpoint metadata
# ---------------------------------------------------------------------------


def test_engine_save_records_algorithm_metadata(tmp_path):
    from repro.checkpoint import checkpoint_meta
    loss_fn, init, _, batch_fn = quadratic_problem(n=8)
    cfg = DCS3GDConfig(local_optimizer="adam")
    alg = registry.make("dc_s3gd", cfg, n_workers=2)
    engine = Engine(None, alg)
    state = alg.init(init)
    state, _ = alg.step(state, stack_batches(batch_fn, 0, 2),
                        loss_fn=loss_fn)
    path = tmp_path / "state.npz"
    engine.save(path, state, step=1)
    meta = checkpoint_meta(path)
    assert meta["algo"] == "dc_s3gd"
    assert meta["n_workers"] == 2
    assert meta["local_optimizer"] == "adam"
    assert meta["reducer"] == "mean_allreduce"
    assert meta["staleness"] == "fixed"
    assert meta["step"] == 1


def test_checkpoint_metadata_wins_over_mismatched_flags(tmp_path):
    """The regression the metadata exists for: a checkpoint trained with
    adam restored while the caller passes --local-optimizer momentum.
    Pre-metadata this silently cast adam's {m, v, t} slots into a
    momentum-shaped template; now the recorded metadata rebuilds the
    right algorithm."""
    from repro.checkpoint import restore_pytree
    loss_fn, init, _, batch_fn = quadratic_problem(n=8)
    cfg = DCS3GDConfig(local_optimizer="adam")
    alg = registry.make("dc_s3gd", cfg, n_workers=2)
    state = alg.init(init)
    state, _ = alg.step(state, stack_batches(batch_fn, 0, 2),
                        loss_fn=loss_fn)
    path = tmp_path / "state.npz"
    Engine(None, alg).save(path, state, step=1)

    restored_alg, resolved = algorithm_for_checkpoint(
        path, algo="ssgd", n_workers=7, local_optimizer="momentum",
        reducer="gossip")
    assert resolved["algo"] == "dc_s3gd"
    assert resolved["local_optimizer"] == "adam"
    assert resolved["n_workers"] == 2
    assert restored_alg.local_optimizer.name == "adam"
    template = restored_alg.init(init)
    restored = restore_pytree(path, template)
    assert _bitwise(state, restored)
    # and the restored state still steps
    _, m = restored_alg.step(restored, stack_batches(batch_fn, 1, 2),
                             loss_fn=loss_fn)
    assert bool(jnp.isfinite(m["loss"]))


def test_pre_metadata_checkpoint_falls_back_to_flags(tmp_path):
    from repro.checkpoint import save_pytree
    _, init, _, _ = quadratic_problem(n=8)
    alg = registry.make("dc_s3gd", CFG, n_workers=2)
    state = alg.init(init)
    path = tmp_path / "old.npz"
    save_pytree(path, state, step=0)        # no extra metadata (PR 1 style)
    _, resolved = algorithm_for_checkpoint(
        path, algo="dc_s3gd", n_workers=2, local_optimizer="momentum",
        reducer="mean_allreduce")
    assert resolved["n_workers"] == 2
    assert resolved["local_optimizer"] == "momentum"


# ---------------------------------------------------------------------------
# Engine fit loop
# ---------------------------------------------------------------------------


def test_engine_fit_runs_and_logs():
    loss_fn, init, _, batch_fn = quadratic_problem(n=8)

    class _QuadraticModel:
        cfg = None

        def loss(self, params, batch):
            return loss_fn(params, batch)

    alg = registry.make("dc_s3gd", CFG, n_workers=2)
    engine = Engine(_QuadraticModel(), alg)
    state = alg.init(init)
    state, history, wall = engine.fit(
        state, lambda t: stack_batches(batch_fn, t, 2), steps=5,
        log_every=2, verbose=False)
    assert int(state.step) == 5
    assert [h["step"] for h in history] == [0, 2, 4]
    assert all(jnp.isfinite(h["loss"]) for h in history)


# ---------------------------------------------------------------------------
# measured-skew staleness feed (Engine.fit --measure-skew, PR 5)
# ---------------------------------------------------------------------------


def test_fit_measure_skew_uniform_times_never_trip():
    """Lockstep simulation: every worker shares the measured step time,
    so the implied progress counters stay equal and dynamic_ssp keeps
    admitting (measured skew 0 — lockstep HAS no skew)."""
    loss_fn, init, _, batch_fn = quadratic_problem(n=8)

    class _M:
        cfg = None

        def loss(self, params, batch):
            return loss_fn(params, batch)

    alg = registry.make("dc_s3gd", CFG, n_workers=4,
                        staleness="dynamic_ssp")
    engine = Engine(_M(), alg)
    state, history, _ = engine.fit(
        alg.init(init), lambda t: stack_batches(batch_fn, t, 4), steps=6,
        log_every=1, verbose=False, measure_skew=True)
    assert all(h["measured_skew"] == 0 for h in history)
    assert all(h["ssp_admit"] == 1.0 for h in history)


def test_fit_measure_skew_probe_trips_dynamic_ssp():
    """A heterogeneous deployment (here: a probe making worker 0 four
    times slower) builds real measured skew; once it crosses the
    threshold the policy must revoke the stale window — the ROADMAP
    'drive dynamic_ssp from measured step times' item."""
    loss_fn, init, _, batch_fn = quadratic_problem(n=8)

    class _M:
        cfg = None

        def loss(self, params, batch):
            return loss_fn(params, batch)

    W = 4
    cfg = DCS3GDConfig(learning_rate=0.1, momentum=0.9, lambda0=0.2,
                       total_steps=1, ssp_threshold=2)
    alg = registry.make("dc_s3gd", cfg, n_workers=W,
                        staleness="dynamic_ssp")
    engine = Engine(_M(), alg)

    def probe(it, dt):
        if it < 4:
            return [4 * dt] + [dt] * (W - 1)   # worker 0 measured 4x slower
        return [dt] * W                        # transient resolved

    state, history, _ = engine.fit(
        alg.init(init), lambda t: stack_batches(batch_fn, t, W), steps=10,
        log_every=1, verbose=False, measure_skew=True, skew_probe=probe)
    skews = [h["measured_skew"] for h in history]
    admits = [h["ssp_admit"] for h in history]
    assert max(skews) > 2, skews
    assert 0.0 in admits, \
        "measured skew above threshold never revoked the window"
    # the sync collapses the MEASURED counters too (one spike = one sync,
    # not a permanent offset): once the probe equalizes, the window must
    # re-open and stay open
    assert admits[-2:] == [1.0, 1.0], admits
    assert skews[-1] == 0, skews
    assert all(jnp.isfinite(h["loss"]) for h in history)


def test_fit_measure_skew_survives_stalled_worker():
    """A probe reporting a non-positive duration (stalled/dead worker)
    must not crash the loop — the worker's counter just stops."""
    loss_fn, init, _, batch_fn = quadratic_problem(n=8)

    class _M:
        cfg = None

        def loss(self, params, batch):
            return loss_fn(params, batch)

    alg = registry.make("dc_s3gd", CFG, n_workers=2,
                        staleness="dynamic_ssp")
    engine = Engine(_M(), alg)
    state, history, _ = engine.fit(
        alg.init(init), lambda t: stack_batches(batch_fn, t, 2), steps=4,
        log_every=1, verbose=False, measure_skew=True,
        skew_probe=lambda it, dt: [0.0, dt])
    assert history[-1]["measured_skew"] > 0
    assert all(jnp.isfinite(h["loss"]) for h in history)


def test_fit_measure_skew_noop_for_stateless_policy():
    """fixed-window algorithms carry no staleness state: the flag must
    not sync or annotate anything."""
    loss_fn, init, _, batch_fn = quadratic_problem(n=8)

    class _M:
        cfg = None

        def loss(self, params, batch):
            return loss_fn(params, batch)

    alg = registry.make("dc_s3gd", CFG, n_workers=2)
    engine = Engine(_M(), alg)
    state, history, _ = engine.fit(
        alg.init(init), lambda t: stack_batches(batch_fn, t, 2), steps=3,
        log_every=1, verbose=False, measure_skew=True)
    assert all("measured_skew" not in h for h in history)
