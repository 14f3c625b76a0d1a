"""End-to-end behaviour tests: the train driver, the serve driver, and the
DC-ASGD baseline simulator."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.core import registry
from repro.core.types import DCS3GDConfig
from repro.launch.train import build_argparser, run
from repro.launch.serve import generate
from repro.models.transformer import Model

from helpers import quadratic_problem, stack_batches


def _run_train(algo, steps=6, arch="qwen3-0.6b", **kw):
    argv = ["--arch", arch, "--reduced", "--algo", algo,
            "--steps", str(steps), "--workers", "2",
            "--batch-per-worker", "2", "--seq", "32", "--log-every", "2"]
    for k, v in kw.items():
        argv += [f"--{k}", str(v)]
    return run(build_argparser().parse_args(argv))


def test_train_driver_dc_s3gd_loss_decreases():
    res = _run_train("dc_s3gd", steps=30)
    first = res["history"][0]["loss"]
    assert res["final_loss"] < first
    assert res["tokens_per_s"] > 0


def test_train_driver_ssgd_runs():
    res = _run_train("ssgd", steps=6)
    assert jnp.isfinite(res["final_loss"])


def test_train_driver_stale_runs():
    res = _run_train("stale", steps=6)
    assert jnp.isfinite(res["final_loss"])


def test_train_checkpoint_resume(tmp_path):
    ck = tmp_path / "state.npz"
    _run_train("dc_s3gd", steps=5, ckpt=ck)
    assert ck.with_suffix(".npz").exists() or ck.exists()


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_compile_cache_dir_rule(monkeypatch, tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX (nothing is set
    in code); unset, the cache lives at a fixed, git-ignored directory
    of the checkout."""
    from pathlib import Path

    from repro.launch import compile_cache as CC

    before = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
            assert CC.use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv(CC.ENV_VAR, raising=False)
            root = Path(__file__).resolve().parents[1]
            assert CC.use_compile_cache() == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == str(
                root / ".jax_cache")
            assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_generate_greedy_deterministic():
    cfg = reduced(get_config("qwen3-0.6b"))
    m = Model(cfg, remat=False, scan_chunk=16)
    params = m.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    a = generate(m, params, prompts, gen=5, temperature=0.0)
    b = generate(m, params, prompts, gen=5, temperature=0.0)
    assert a.shape == (2, 5)
    assert jnp.array_equal(a, b)
    assert int(a.max()) < cfg.vocab_size  # pad logits masked


def test_serve_generate_scan_matches_per_token_loop():
    """The single-trace `lax.scan` decode loop must reproduce the
    dispatch-per-token reference exactly, for both samplers."""
    cfg = reduced(get_config("qwen3-0.6b"))
    m = Model(cfg, remat=False, scan_chunk=16)
    params = m.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)

    def reference(gen, temperature, key):
        # frozen transcript of the pre-scan per-token loop
        B, P = prompts.shape
        logits, cache = m.prefill(params, {"tokens": prompts},
                                  cache_len=P + gen + 1)

        def sample(lg, k, t):
            if t <= 0.0:
                return jnp.argmax(lg, axis=-1)
            return jax.random.categorical(k, lg / t, axis=-1)

        out, tok = [], sample(logits, key, temperature)
        for t in range(gen):
            out.append(tok)
            key, sub = jax.random.split(key)
            step = {"tokens": tok[:, None], "pos": jnp.int32(P + t)}
            logits, cache = m.decode_step(params, cache, step)
            tok = sample(logits, sub, temperature)
        return jnp.stack(out, axis=1)

    k = jax.random.PRNGKey(7)
    greedy = generate(m, params, prompts, gen=5, temperature=0.0, key=k)
    assert jnp.array_equal(greedy, reference(5, 0.0, k))
    hot = generate(m, params, prompts, gen=5, temperature=0.8, key=k)
    assert jnp.array_equal(hot, reference(5, 0.8, k))
    assert not jnp.array_equal(greedy, hot)  # sampler actually pluggable


def test_serve_generate_ssm():
    cfg = reduced(get_config("falcon-mamba-7b"))
    m = Model(cfg, remat=False, scan_chunk=16)
    params = m.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                                 cfg.vocab_size)
    out = generate(m, params, prompts, gen=4, temperature=0.0)
    assert out.shape == (1, 4)


def test_dc_asgd_simulator_and_compensation():
    """DC-ASGD PS baseline: runs round-robin, and compensation reduces the
    final distance to the optimum under staleness."""
    loss_fn, init, w_star, batch_fn = quadratic_problem(n=16, seed=5)
    cfg = DCS3GDConfig(learning_rate=0.5, momentum=0.9, lambda0=0.2,
                       weight_decay=0.0)
    W = 8

    def run_sim(compensate):
        alg = registry.make("dc_asgd", cfg, n_workers=W,
                            compensator="dc" if compensate else "none")
        state = alg.init(init)
        for t in range(160):
            # protocol batch layout: the round-robin worker t % W consumes
            # its own shard of the stacked (W, b, ...) batch
            state, m = alg.step(state, stack_batches(batch_fn, t, W),
                                loss_fn=loss_fn)
        return float(jnp.linalg.norm(alg.eval_params(state)["w"] - w_star))

    err_dc = run_sim(True)
    err_async = run_sim(False)
    assert err_dc <= err_async * 1.05, (err_dc, err_async)
