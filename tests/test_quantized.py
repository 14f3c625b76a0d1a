"""The quantization seam (PR 10): round-trip error bounds of
`repro.core.quant`, the quantized paged-attention kernel vs its oracle,
int8 paged greedy decode token-matching the dense fp32 path, the int8
wire riding error feedback at W=8, the plan-cache wire-dtype key, and
the autotuner's analytic predictors."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

from repro.core import quant as Q
from repro.core import registry
from repro.core.compress import TopKReduce
from repro.core.reduce import MeanAllReduce
from repro.core.types import DCS3GDConfig
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import paged_attention_ref
from repro.models.cache import PagedLayout
from repro.parallel import buckets as B

from helpers import stack_batches

CFG = DCS3GDConfig(learning_rate=0.1, momentum=0.9, lambda0=0.2,
                   weight_decay=1e-3, total_steps=1)


# ---------------------------------------------------------------------------
# quantize/dequantize round-trip bounds
# ---------------------------------------------------------------------------


def _rows(seed=0, n=16, m=257):
    """Rows spanning ~6 orders of magnitude — per-row scaling must keep
    the small rows accurate despite the large ones."""
    rng = np.random.default_rng(seed)
    mags = 10.0 ** rng.uniform(-3, 3, size=(n, 1))
    return jnp.asarray(rng.standard_normal((n, m)) * mags, jnp.float32)


def test_int8_roundtrip_error_bound():
    """Symmetric int8 with round-to-even: per-element error is at most
    half a quantization step, amax(row) / (2 * 127)."""
    x = _rows()
    q, scale = Q.quantize(x, "int8")
    assert q.dtype == jnp.int8 and scale.shape == (x.shape[0], 1)
    err = jnp.abs(x - Q.dequantize(q, scale))
    bound = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 254.0
    assert bool(jnp.all(err <= bound * (1 + 1e-6)))


def test_fp8_roundtrip_relative_bound():
    """e4m3fn has 3 mantissa bits: relative error <= 2^-4 on the normal
    range; the subnormal floor is 2^-9 of the scale."""
    x = _rows(seed=1)
    q, scale = Q.quantize(x, "fp8")
    assert q.dtype == jnp.float8_e4m3fn
    err = jnp.abs(x - Q.dequantize(q, scale))
    bound = jnp.maximum(jnp.abs(x) * 2.0 ** -4, scale * 2.0 ** -9)
    assert bool(jnp.all(err <= bound * (1 + 1e-6)))


def test_quantize_zero_row_stays_zero():
    """The epsilon-floored scale keeps all-zero rows exact (no 0/0)."""
    x = jnp.zeros((3, 64), jnp.float32)
    for name in ("int8", "fp8"):
        dq = Q.dequantize(*Q.quantize(x, name))
        assert bool(jnp.all(dq == 0.0)) and bool(jnp.all(jnp.isfinite(dq)))


def test_quantize_axes_and_aliases():
    x = _rows(seed=2, n=4, m=32).reshape(4, 8, 4)
    q, s = Q.quantize(x, "i8", axes=(2,))
    assert s.shape == (4, 8, 1)
    np.testing.assert_allclose(np.asarray(Q.dequantize(q, s)),
                               np.asarray(x), atol=float(jnp.max(s)) / 2)
    assert Q.canonical("fp8") == "float8_e4m3fn"
    assert Q.wire_itemsize("fp8") == 1 and Q.wire_itemsize("bfloat16") == 2
    assert not Q.is_quantized("float32")


# ---------------------------------------------------------------------------
# quantized paged-attention kernel vs oracle
# ---------------------------------------------------------------------------


def _paged_case(seed, num_pages=6, page_size=16, KV=2, G=2, hd=8, batch=3):
    key = random.PRNGKey(seed)
    kq, kk, kv, kl = random.split(key, 4)
    q = random.normal(kq, (batch, KV, G, hd), jnp.float32)
    k = random.normal(kk, (num_pages, page_size, KV, hd), jnp.float32)
    v = random.normal(kv, (num_pages, page_size, KV, hd), jnp.float32)
    mp = 2
    # each row owns distinct pages (page 0 is the scratch page)
    bt = jnp.asarray([[1 + 2 * b, 2 + 2 * b] for b in range(batch)],
                     jnp.int32)
    lengths = random.randint(kl, (batch,), 1, mp * page_size + 1)
    return q, k, v, bt, lengths


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_paged_attention_quant_kernel_matches_ref(kv_dtype):
    """Kernel and oracle consume the SAME quantized pools + scales, so
    the in-DMA dequant must agree with the linearized dequant to float
    tolerance."""
    q, k, v, bt, lengths = _paged_case(seed=5)
    P, ps = k.shape[:2]

    def qpool(pool):
        flat, scale = Q.quantize(pool.reshape(P * ps, -1), kv_dtype)
        return flat.reshape(pool.shape), scale.reshape(P, ps)

    k8, ks = qpool(k)
    v8, vs = qpool(v)
    out = paged_attention(q, k8, v8, bt, lengths, k_scale=ks, v_scale=vs,
                          interpret=True)
    ref = paged_attention_ref(q, k8, v8, bt, lengths, k_scale=ks,
                              v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # and the quantized path stays close to the fp32 pools (the error
    # the serving stack actually pays)
    dense = paged_attention_ref(q, k, v, bt, lengths)
    assert float(jnp.max(jnp.abs(ref - dense))) < 0.1


# ---------------------------------------------------------------------------
# int8 KV pages: greedy decode token-match vs the dense fp32 path
# ---------------------------------------------------------------------------


def _serve_model():
    from repro.configs import get_config, reduced
    from repro.models.transformer import Model
    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg, remat=False, scan_chunk=16, loss_chunk=16)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _quant_prompts(cfg, n=8, prompt_len=16):
    # the serve benchmark's pinned workload (benchmarks/serve_bench.py
    # QUANT_SEED): prompts whose greedy argmax margins dominate int8 KV
    # noise on the random-init reduced model
    rng = np.random.default_rng(29)
    return [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
            for _ in range(n)]


def test_int8_paged_decode_token_matches_dense_fp32_18_steps():
    """≥16 greedy steps through int8 KV pages reproduce the dense fp32
    token stream EXACTLY — quantization noise stays below every argmax
    margin on this pinned workload (prefill + 17 decode steps each)."""
    from repro.launch.engine import Engine
    from repro.serve import Request, Scheduler
    cfg, model, params = _serve_model()
    prompts = _quant_prompts(cfg)
    gen = 18
    reqs = [Request(rid=i, prompt=prompts[p], max_new=gen)
            for i, p in enumerate((6, 7))]

    engine = Engine(model)
    refs = {}
    for r in reqs:
        out = engine.generate(
            params, jnp.asarray(np.asarray(r.prompt, np.int32))[None],
            gen=gen)
        refs[r.rid] = np.asarray(out)[0][:gen].tolist()

    page_size = 16
    max_len = 16 + gen + 1
    mp = -(-max_len // page_size)
    sch = Scheduler(model, params, slots=2, pages=3 * mp + 1,
                    page_size=page_size, max_len=max_len, decode_burst=4,
                    kv_dtype="int8")
    assert sch.layout.kv_dtype_name == "int8"
    lay32 = PagedLayout(model, n_slots=2, num_pages=3 * mp + 1,
                        page_size=page_size, max_pages=mp)
    assert sch.layout.kv_bytes_per_token() * 3 <= \
        lay32.kv_bytes_per_token()
    sch.run(reqs)
    for r in reqs:
        assert len(r.out) == gen
        assert r.out == refs[r.rid], \
            f"rid {r.rid} diverged from the dense fp32 greedy stream"


def test_fp8_paged_decode_runs_and_completes():
    """fp8 KV has ~6% relative error — token-match is not promised on a
    random-init model, but the path must run and fill every request."""
    from repro.serve import Request, Scheduler
    cfg, model, params = _serve_model()
    prompts = _quant_prompts(cfg, n=2)
    sch = Scheduler(model, params, slots=2, pages=7, page_size=16,
                    max_len=24, decode_burst=2, kv_dtype="fp8")
    reqs = [Request(rid=i, prompt=p, max_new=6)
            for i, p in enumerate(prompts)]
    sch.run(reqs)
    assert all(len(r.out) == 6 for r in reqs)
    assert all(0 <= t < cfg.vocab_size for r in reqs for t in r.out)


# ---------------------------------------------------------------------------
# int8 wire through error feedback
# ---------------------------------------------------------------------------


def _bigger_problem(n=12, m=64, seed=3):
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


def _run(reducer, steps, workers):
    loss_fn, init, batch_fn = _bigger_problem()
    alg = registry.make("dc_s3gd", CFG, n_workers=workers, reducer=reducer,
                        buckets=2)
    state = alg.init(init)
    step = jax.jit(lambda s, b: alg.step(s, b, loss_fn=loss_fn))
    m = None
    for t in range(steps):
        state, m = step(state, stack_batches(batch_fn, t, workers))
    return alg, state, m


@pytest.mark.parametrize("reducer", [
    MeanAllReduce(comm_dtype="int8"),
    TopKReduce(density=0.05, comm_dtype="int8")])
def test_int8_wire_tracks_fp32_trajectory_20_steps_w8(reducer):
    """Error feedback absorbs the quantization residual exactly like it
    absorbs sparsification: 20 steps at W=8 over a 1-byte wire land
    within tolerance of the fp32-wire run (both converged)."""
    _, _, m_ref = _run("mean_allreduce", 20, 8)
    _, _, m_q = _run(reducer, 20, 8)
    ref, got = float(m_ref["loss"]), float(m_q["loss"])
    assert np.isfinite(got)
    assert got < 0.25               # converged (init loss ~0.5)
    assert abs(got - ref) < 0.1     # tracking the fp32-wire run


def test_quantized_wire_bytes_accounting():
    """int8 wire: 1 payload byte per element + one f32 scale per bucket;
    the ≥3x compression the acceptance gate demands is structural."""
    sizes = [32768, 65536]
    dense = MeanAllReduce().wire_bytes(sizes)
    i8 = MeanAllReduce(comm_dtype="int8").wire_bytes(sizes)
    assert i8 == sum(sizes) + Q.SCALE_BYTES * len(sizes)
    assert dense / i8 > 3.99
    # topk at int8 stacks multiplicatively with sparsification
    tk = TopKReduce(density=0.01, comm_dtype="int8").wire_bytes(sizes)
    tk32 = TopKReduce(density=0.01).wire_bytes(sizes)
    assert tk < tk32


def test_cached_plan_keys_on_wire_dtype():
    """A quantized and a dense wire must never alias a bucket plan, even
    while their layouts happen to match (see cached_plan docstring)."""
    tree = {"a": jnp.zeros((4, 100)), "b": jnp.zeros((4, 300))}
    cache = {}
    p32 = B.cached_plan(cache, tree, 2, strip_leading_axis=True)
    p8 = B.cached_plan(cache, tree, 2, strip_leading_axis=True,
                       wire_dtype="int8")
    assert len(cache) == 2
    assert p8 is not p32
    assert B.cached_plan(cache, tree, 2, strip_leading_axis=True,
                         wire_dtype="int8") is p8
    assert len(cache) == 2


# ---------------------------------------------------------------------------
# autotuner: analytic predictors + blob plumbing (no probes)
# ---------------------------------------------------------------------------


def test_autotune_spaces_contain_defaults():
    from repro.analysis.autotune import (SERVE_DEFAULT, TRAIN_DEFAULT,
                                         _with_default, serve_space,
                                         train_space)
    for smoke in (True, False):
        assert TRAIN_DEFAULT in _with_default(train_space(smoke),
                                              TRAIN_DEFAULT)
        assert SERVE_DEFAULT in _with_default(serve_space(smoke),
                                              SERVE_DEFAULT)
    # default is injected exactly once
    cands = _with_default([{"x": 1}], {"x": 0})
    assert cands[0] == {"x": 0} and len(cands) == 2
    assert _with_default([{"x": 0}], {"x": 0}) == [{"x": 0}]


def test_predict_train_charges_latency_per_bucket():
    """With a tiny payload the wire term is latency-bound, so more
    buckets must predict strictly slower — the roofline knee the search
    is built to find."""
    from repro.analysis.autotune import predict_train
    kw = dict(leaf_sizes=[256] * 4, n_workers=4,
              reducer=MeanAllReduce())
    t2 = predict_train({"buckets": 2, "plan_block": None}, **kw)
    t8 = predict_train({"buckets": 8, "plan_block": None}, **kw)
    assert t8 > t2
    # a huge payload flips it: bandwidth dominates and extra launch
    # latency is noise, while padding cost stays bounded
    big = dict(leaf_sizes=[10 ** 8], n_workers=4, reducer=MeanAllReduce())
    b2 = predict_train({"buckets": 2, "plan_block": None}, **big)
    b8 = predict_train({"buckets": 8, "plan_block": None}, **big)
    assert abs(b8 - b2) / b2 < 0.01


def test_predict_serve_burst_amortizes_dispatch():
    from repro.analysis.autotune import predict_serve
    kw = dict(kv_bytes_per_token=2048, param_bytes=10 ** 6, slots=8,
              mean_len=64.0)
    t1 = predict_serve({"page_size": 16, "decode_burst": 1}, **kw)
    t8 = predict_serve({"page_size": 16, "decode_burst": 8}, **kw)
    assert t8 < t1
    # bigger pages read a longer dead tail per row
    p8 = predict_serve({"page_size": 8, "decode_burst": 4}, **kw)
    p32 = predict_serve({"page_size": 32, "decode_burst": 4}, **kw)
    assert p32 > p8


def test_load_tuned_validates_blob(tmp_path):
    from repro.analysis.autotune import load_tuned
    good = tmp_path / "good.json"
    good.write_text(json.dumps({"version": 1, "train": {
        "tuned": {"buckets": 8, "plan_block": None}}}))
    assert load_tuned(good)["train"]["tuned"]["buckets"] == 8
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 2}))
    with pytest.raises(ValueError):
        load_tuned(bad)
