"""The serve subsystem (PR 5): page allocator invariants, the Pallas
paged-attention kernel vs its oracle, paged-vs-dense decode parity
(bitwise under greedy across attention / MLA / SSM / RGLRU cache kinds),
and scheduler join/evict/preempt correctness under staggered lengths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random

from repro.configs import get_config, reduced
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import paged_attention_ref
from repro.launch.engine import Engine
from repro.models.cache import SCRATCH_PAGE, DenseLayout, PagedLayout
from repro.models.transformer import Model
from repro.serve import PagePool, Request, Scheduler

PARITY_ARCHS = [
    "qwen3-0.6b",        # dense GQA + qk-norm (paged linear KV)
    "minicpm3-4b",       # MLA latent cache (paged latent pools)
    "falcon-mamba-7b",   # SSM O(1) state (slot-indexed)
    "recurrentgemma-9b",  # RG-LRU + local-attention ring (slot-indexed)
]


def _model(arch):
    cfg = reduced(get_config(arch))
    if cfg.rglru is not None:
        # shrink the local-attention window below the test cache length so
        # the dense ring (min(window, cache_len)) and the slot ring
        # (window) are the same size — a precondition for bitwise parity
        cfg = dataclasses.replace(
            cfg, rglru=dataclasses.replace(cfg.rglru, attention_window=8))
    return Model(cfg, remat=False, q_chunk=16, kv_chunk=16, scan_chunk=16,
                 loss_chunk=16)


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------


def test_pool_alloc_unique_and_reserved():
    pool = PagePool(10, 16)
    got = pool.alloc(6)
    assert len(set(got)) == 6
    assert all(p >= 1 for p in got), "scratch page 0 must never be granted"
    assert pool.free_pages == 3 and pool.used_pages == 6


def test_pool_exhaustion_returns_none_not_partial():
    pool = PagePool(5, 8)
    assert pool.alloc(4) is not None
    before = pool.free_pages
    assert pool.alloc(1) is None
    assert pool.free_pages == before, "failed alloc must not leak pages"


def test_pool_free_recycles_and_double_free_raises():
    pool = PagePool(6, 8)
    a = pool.alloc(5)
    pool.free(a[:2])
    assert pool.free_pages == 2
    b = pool.alloc(2)
    assert set(b) == set(a[:2])  # LIFO reuse
    pool.free(b)
    with pytest.raises(ValueError):
        pool.free(b)  # double free
    with pytest.raises(ValueError):
        pool.free([0])  # reserved scratch page was never granted


def test_pool_free_is_atomic_on_bad_batch():
    """A batch containing any invalid page must raise BEFORE any state
    changes — no half-applied frees corrupting the free list."""
    pool = PagePool(8, 8)
    a = pool.alloc(4)
    before_free, before_used = pool.free_pages, pool.used_pages
    with pytest.raises(ValueError):
        pool.free([a[0], a[1], 0])          # reserved page in the batch
    with pytest.raises(ValueError):
        pool.free([a[0], a[1], 99])         # foreign page in the batch
    with pytest.raises(ValueError):
        pool.free([a[0], a[0]])             # intra-call double free
    assert pool.free_pages == before_free and pool.used_pages == before_used
    pool.free(a)                            # the good batch still works
    assert pool.used_pages == 0


def test_pool_refcounts_share_and_release():
    pool = PagePool(8, 8)
    [pg] = pool.alloc(1)
    pool.ref([pg])                          # second holder
    assert pool.refcount(pg) == 2
    assert pool.shared_pages == 1
    assert pool.used_pages == 1, "a shared page counts ONCE"
    pool.free([pg])                         # first holder drops
    assert pool.refcount(pg) == 1 and pool.free_pages == 6
    pool.free([pg])                         # last holder drops -> recycled
    assert pool.refcount(pg) == 0 and pool.free_pages == 7
    with pytest.raises(ValueError):
        pool.free([pg])                     # now a double free
    with pytest.raises(ValueError):
        pool.ref([pg])                      # can't share a freed page
    # intra-call duplicates beyond the refcount raise atomically
    [pg2] = pool.alloc(1)
    pool.ref([pg2])
    with pytest.raises(ValueError):
        pool.free([pg2, pg2, pg2])          # 3 frees, 2 refs
    assert pool.refcount(pg2) == 2
    pool.free([pg2, pg2])                   # exactly the refcount is fine
    assert pool.used_pages == 0


def test_pool_fragmentation_stats():
    pool = PagePool(9, 16)
    pool.alloc(4)
    s = pool.stats(used_tokens=40)  # 4 pages * 16 = 64 slots, 40 live
    assert s["used_pages"] == 4 and s["free_pages"] == 4
    assert s["utilization"] == pytest.approx(4 / 8)
    assert s["internal_fragmentation"] == pytest.approx(1 - 40 / 64)
    assert pool.capacity_tokens == 8 * 16


def test_pool_rejects_degenerate_config():
    with pytest.raises(ValueError):
        PagePool(1, 16)  # nothing usable after the scratch reservation


# ---------------------------------------------------------------------------
# Pallas paged-attention kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,KV,G,hd,ps,mp", [
    (1, 1, 1, 16, 8, 2),
    (3, 2, 4, 32, 8, 4),
    (2, 4, 1, 64, 16, 3),
])
def test_paged_attention_kernel_matches_ref(B, KV, G, hd, ps, mp):
    ks = random.split(random.PRNGKey(0), 4)
    np_pool = mp * B + 1
    q = random.normal(ks[0], (B, KV, G, hd))
    kp = random.normal(ks[1], (np_pool, ps, KV, hd))
    vp = random.normal(ks[2], (np_pool, ps, KV, hd))
    bt = random.permutation(ks[3], np_pool - 1)[:B * mp] \
        .reshape(B, mp).astype(jnp.int32) + 1
    lengths = jnp.array([1 + (i * 7) % (mp * ps) for i in range(B)],
                        jnp.int32)
    ref = paged_attention_ref(q, kp, vp, bt, lengths)
    out = paged_attention(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_paged_attention_ref_is_dense_decode_on_linearized_view():
    """The oracle's semantics ARE the dense decode attention on the
    gather — masked softmax over logical positions."""
    from repro.kernels.ref import decode_attention_ref
    ks = random.split(random.PRNGKey(1), 3)
    B, KV, G, hd, ps, mp = 2, 2, 2, 16, 8, 3
    q = random.normal(ks[0], (B, KV, G, hd))
    kp = random.normal(ks[1], (7, ps, KV, hd))
    vp = random.normal(ks[2], (7, ps, KV, hd))
    bt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
    lengths = jnp.array([20, 9], jnp.int32)
    ref = paged_attention_ref(q, kp, vp, bt, lengths)
    k_lin = kp[bt].reshape(B, mp * ps, KV, hd)
    v_lin = vp[bt].reshape(B, mp * ps, KV, hd)
    for b in range(B):
        want = decode_attention_ref(q[b:b + 1], k_lin[b:b + 1],
                                    v_lin[b:b + 1], lengths[b])
        np.testing.assert_array_equal(np.asarray(want),
                                      np.asarray(ref[b:b + 1]))


# ---------------------------------------------------------------------------
# paged-vs-dense decode parity (bitwise, greedy, >= 16 steps)
# ---------------------------------------------------------------------------


def _dense_trace(m, params, prompts, gen, cache_len):
    """Fixed-batch dense decode transcript: (logits per step, tokens)."""
    prefill = jax.jit(lambda p, b: m.prefill(p, b, cache_len=cache_len))
    dstep = jax.jit(lambda p, c, b: m.decode_step(p, c, b))
    logits, cache = prefill(params, {"tokens": prompts})
    P = prompts.shape[1]
    trace = [logits]
    tok = jnp.argmax(logits, -1)
    for t in range(gen):
        logits, cache = dstep(params, cache,
                              {"tokens": tok[:, None],
                               "pos": jnp.int32(P + t)})
        trace.append(logits)
        tok = jnp.argmax(logits, -1)
    return trace


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_decode_bitwise_matches_dense(arch):
    """>= 16 greedy decode steps: the paged layout's logits are BITWISE
    the dense layout's at matched batch width and linearized cache
    length, for every cache kind (paged pools, slot rings, slot
    states)."""
    m = _model(arch)
    cfg = m.cfg
    params = m.init(random.PRNGKey(0))
    B, P, gen, ps = 2, 8, 16, 8
    mp = -(-(P + gen + 1) // ps)
    cache_len = mp * ps
    prompts = random.randint(random.PRNGKey(1), (B, P), 0, cfg.vocab_size)
    trace = _dense_trace(m, params, prompts, gen, cache_len)

    lay = PagedLayout(m, n_slots=B, num_pages=B * mp + 1, page_size=ps,
                      max_pages=mp)
    cache = lay.init_cache()
    bt = np.full((B, mp), SCRATCH_PAGE, np.int32)
    n_pg = lay.pages_for(P)
    pages = np.arange(1, B * mp + 1, dtype=np.int32).reshape(B, mp)
    if lay.uses_pages:
        bt[:] = pages
    prefill = jax.jit(lambda p, c, t, pg, s: lay.prefill_into(
        p, c, {"tokens": t}, pg, s))
    logits, cache = prefill(params, cache, prompts,
                            jnp.asarray(pages[:, :n_pg]),
                            jnp.arange(B, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(trace[0]))
    dstep = jax.jit(lay.decode_step)
    tok = jnp.argmax(logits, -1)
    pos = np.full((B,), P, np.int32)
    for t in range(gen):
        logits, cache = dstep(params, cache, tok[:, None],
                              jnp.asarray(pos), jnp.asarray(bt))
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(trace[t + 1]),
                                      err_msg=f"{arch} step {t}")
        tok = jnp.argmax(logits, -1)
        pos += 1


def test_paged_kernel_path_matches_reference_path():
    """use_kernel=True routes full-attention gathers through the Pallas
    kernel; logits must track the XLA-gather reference path."""
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    B, P, gen, ps = 2, 8, 6, 8
    mp = -(-(P + gen + 1) // ps)
    prompts = random.randint(random.PRNGKey(1), (B, P), 0,
                             m.cfg.vocab_size)

    def run(use_kernel):
        lay = PagedLayout(m, n_slots=B, num_pages=B * mp + 1, page_size=ps,
                          max_pages=mp, use_kernel=use_kernel)
        cache = lay.init_cache()
        pages = np.arange(1, B * mp + 1, dtype=np.int32).reshape(B, mp)
        logits, cache = lay.prefill_into(
            params, cache, {"tokens": prompts},
            jnp.asarray(pages[:, :lay.pages_for(P)]),
            jnp.arange(B, dtype=jnp.int32))
        tok = jnp.argmax(logits, -1)
        outs = []
        pos = np.full((B,), P, np.int32)
        step = jax.jit(lay.decode_step)
        for t in range(gen):
            logits, cache = step(params, cache, tok[:, None],
                                 jnp.asarray(pos), jnp.asarray(pages))
            outs.append(np.asarray(logits))
            tok = jnp.argmax(logits, -1)
            pos += 1
        return outs

    ref, kern = run(False), run(True)
    for t, (a, b) in enumerate(zip(ref, kern)):
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=f"step {t}")


def test_dense_layout_is_the_model_paths():
    m = _model("qwen3-0.6b")
    lay = DenseLayout(m)
    c = lay.init_cache(2, 16)
    ref = m.init_cache(2, 16)
    assert jax.tree.structure(c) == jax.tree.structure(ref)


# ---------------------------------------------------------------------------
# scheduler: join / evict / staggered lengths / preemption
# ---------------------------------------------------------------------------


def test_scheduler_matches_oneshot_generate_bitwise():
    """Equal-length requests joining together ARE the one-shot dense
    batch: greedy tokens must agree exactly (group prefill and the
    decode rows run at the same batch width as the dense loop)."""
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    B, P, gen, ps = 2, 8, 12, 8
    mp = -(-(P + gen + 1) // ps)
    prompts = random.randint(random.PRNGKey(1), (B, P), 0,
                             m.cfg.vocab_size)
    dense = Engine(m).generate(params, prompts, gen=gen, cache_len=mp * ps)
    sch = Scheduler(m, params, slots=B, pages=B * mp + 2, page_size=ps,
                    max_len=mp * ps)
    done = sch.run([Request(rid=i, prompt=[int(t) for t in prompts[i]],
                            max_new=gen) for i in range(B)])
    assert len(done) == B
    for r in done:
        assert r.out == [int(t) for t in dense[r.rid]], r.rid
    assert sch.pool.used_pages == 0, "eviction must free every page"
    assert sch.stats["prefills"] == 1, "equal-length joins must group"


def test_scheduler_staggered_evictions_stay_bitwise():
    """Four requests, four slots, staggered max_new: short lanes evict
    early while the batch row width never changes — every request's
    tokens must equal its row of the fixed-batch dense run (trimmed)."""
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    B, P, ps = 4, 8, 8
    gens = [3, 6, 10, 16]
    mp = -(-(P + max(gens) + 1) // ps)
    prompts = random.randint(random.PRNGKey(2), (B, P), 0,
                             m.cfg.vocab_size)
    dense = Engine(m).generate(params, prompts, gen=max(gens),
                               cache_len=mp * ps)
    sch = Scheduler(m, params, slots=B, pages=B * mp + 2, page_size=ps,
                    max_len=mp * ps, decode_burst=4)
    done = sch.run([Request(rid=i, prompt=[int(t) for t in prompts[i]],
                            max_new=gens[i]) for i in range(B)])
    assert sorted(r.rid for r in done) == list(range(B))
    for r in done:
        assert len(r.out) == gens[r.rid]
        assert r.out == [int(t) for t in dense[r.rid][:gens[r.rid]]], r.rid
    assert sch.pool.used_pages == 0


def test_scheduler_join_reuses_freed_slots_and_pages():
    """More requests than slots: evictions must hand slots/pages to the
    waiting queue (FIFO) and every request must run to completion."""
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    ps = 8
    max_len = 40
    sch = Scheduler(m, params, slots=2, pages=12, page_size=ps,
                    max_len=max_len)
    reqs = [Request(rid=i, prompt=list(range(4 + 2 * i)), max_new=3 + i)
            for i in range(6)]
    done = sch.run(list(reqs))
    assert sorted(r.rid for r in done) == list(range(6))
    for r in done:
        assert len(r.out) == r.max_new
        assert all(0 <= t < m.vocab_padded for t in r.out)
    assert sch.pool.used_pages == 0
    assert sch.stats["prefills"] >= 3  # slots turned over
    # FIFO: a request never finishes before one submitted 2 slots earlier
    order = [r.rid for r in sorted(done, key=lambda r: r.t_join)]
    assert order == sorted(order)


def test_scheduler_eos_evicts_early():
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    prompt = list(range(8))
    sch = Scheduler(m, params, slots=1, pages=12, page_size=8, max_len=48)
    [probe] = sch.run([Request(rid=0, prompt=prompt, max_new=12)])
    assert len(probe.out) == 12
    # EOS = the first decoded token not seen earlier in the output, so its
    # first occurrence is where the probe's output must be cut
    cut = next(i for i in range(1, len(probe.out) - 1)
               if probe.out[i] not in probe.out[:i])
    eos = probe.out[cut]
    sch2 = Scheduler(m, params, slots=1, pages=12, page_size=8, max_len=48,
                     eos_id=eos)
    [early] = sch2.run([Request(rid=0, prompt=prompt, max_new=12)])
    assert early.out == probe.out[:cut + 1], "evict ON the eos token"
    assert sch2.pool.used_pages == 0


def test_scheduler_preempts_and_recovers_when_pool_is_starved():
    """A pool too small for all lanes at full length: the youngest lane
    is preempted (pages freed, recompute-resumed) and every request
    still completes at its full length."""
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    ps = 4
    # 2 slots x up to 33 positions = 18 pages at full length; give 11
    sch = Scheduler(m, params, slots=2, pages=12, page_size=ps,
                    max_len=36)
    reqs = [Request(rid=i, prompt=list(range(8)), max_new=24)
            for i in range(2)]
    done = sch.run(list(reqs))
    assert sorted(r.rid for r in done) == [0, 1]
    assert all(len(r.out) == 24 for r in done)
    assert sch.stats["preemptions"] >= 1
    assert sch.pool.used_pages == 0


def test_scheduler_rejects_oversized_request():
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    sch = Scheduler(m, params, slots=1, pages=6, page_size=8, max_len=32)
    with pytest.raises(ValueError):
        sch.submit(Request(rid=0, prompt=list(range(20)), max_new=20))


def test_scheduler_decode_burst_is_token_invariant():
    """Multi-step scheduling must not change any request's tokens."""
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    prompts = random.randint(random.PRNGKey(3), (3, 8), 0,
                             m.cfg.vocab_size)
    gens = [4, 9, 14]

    def run(burst):
        sch = Scheduler(m, params, slots=2, pages=20, page_size=8,
                        max_len=40, decode_burst=burst)
        done = sch.run([Request(rid=i, prompt=[int(t) for t in prompts[i]],
                                max_new=gens[i]) for i in range(3)])
        return {r.rid: r.out for r in done}

    assert run(1) == run(4)


def test_scheduler_ssm_arch_runs_without_pages():
    """Slot-state-only families (no paged kind) serve through the same
    scheduler; the pool stays untouched."""
    m = _model("falcon-mamba-7b")
    params = m.init(random.PRNGKey(0))
    sch = Scheduler(m, params, slots=2, pages=8, page_size=8, max_len=32)
    assert not sch.layout.uses_pages
    done = sch.run([Request(rid=i, prompt=list(range(4 + i)), max_new=5)
                    for i in range(3)])
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(len(r.out) == 5 for r in done)
    assert sch.pool.used_pages == 0


# ---------------------------------------------------------------------------
# Engine.generate: compile cache (the re-tracing fix)
# ---------------------------------------------------------------------------


def test_engine_generate_reuses_compiled_functions():
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    prompts = random.randint(random.PRNGKey(1), (2, 8), 0,
                             m.cfg.vocab_size)
    eng = Engine(m)
    a = eng.generate(params, prompts, gen=4)
    assert eng._oneshot.cache_size == 1
    b = eng.generate(params, prompts, gen=4)
    assert eng._oneshot.cache_size == 1, "same signature must not re-jit"
    assert bool(jnp.array_equal(a, b))
    eng.generate(params, prompts, gen=5)           # new shape -> new entry
    assert eng._oneshot.cache_size == 2
    eng.generate(params, prompts, gen=4, sampler="categorical",
                 temperature=0.7, key=random.PRNGKey(3))
    assert eng._oneshot.cache_size == 3


def test_engine_generate_cached_fns_take_fresh_params():
    """The cached decode loop must consume the params passed per call —
    NOT the weights it was first traced with (the old closure baked them
    in as constants, which only worked because it re-traced every
    call)."""
    m = _model("qwen3-0.6b")
    p1 = m.init(random.PRNGKey(0))
    p2 = m.init(random.PRNGKey(42))
    prompts = random.randint(random.PRNGKey(1), (1, 8), 0,
                             m.cfg.vocab_size)
    eng = Engine(m)
    out1 = eng.generate(p1, prompts, gen=6)
    out2 = eng.generate(p2, prompts, gen=6)
    assert eng._oneshot.cache_size == 1
    assert not bool(jnp.array_equal(out1, out2)), \
        "different weights produced identical generations — params baked in"


def test_scheduler_rejects_encoder_decoder_archs_clearly():
    """Requests carry token ids only — whisper/VLM prefill needs encoder
    inputs the scheduler has no seam for yet; fail loudly at
    construction, not with a KeyError mid-prefill."""
    m = Model(reduced(get_config("whisper-large-v3")), remat=False,
              q_chunk=16, kv_chunk=16, scan_chunk=16)
    params = None  # never reached
    with pytest.raises(NotImplementedError):
        Scheduler(m, params, slots=1, pages=8, page_size=8, max_len=32)
