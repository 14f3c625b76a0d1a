"""Prefix cache + copy-on-write pages + chunked prefill (PR 8).

The load-bearing contract: a prefix-cache hit resumes prefill mid-prompt
on SHARED physical pages, and its decode is bitwise identical to the
cold chunked prefill under greedy — because every chunk (cold or hit)
runs the same fixed-shape executable over the same page-aligned KV
blocking, where fully-masked KV blocks are exact no-ops in the online
softmax.  Plus the refcount/COW invariants: a shared page is never
recycled or written while another holder can still read it.
"""
import dataclasses

import numpy as np
import pytest
from jax import random

from repro.configs import get_config, reduced
from repro.models.transformer import Model
from repro.serve import PagePool, PrefixCache, Request, Scheduler

PS = 8  # page size used throughout


def _model(arch):
    cfg = reduced(get_config(arch))
    if cfg.rglru is not None:
        cfg = dataclasses.replace(
            cfg, rglru=dataclasses.replace(cfg.rglru, attention_window=8))
    return Model(cfg, remat=False, scan_chunk=16, loss_chunk=16)


def _prompts(vocab, seed=0):
    """A 3-request family: shared 20-token system prefix, distinct
    tails that diverge INSIDE page 2 (so sharing needs COW)."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(1, vocab, 20).tolist()
    return [sys_prompt + rng.integers(1, vocab, 7).tolist()
            for _ in range(3)]


# ---------------------------------------------------------------------------
# PrefixCache host-side index (no model)
# ---------------------------------------------------------------------------


def test_prefix_cache_match_walks_full_page_chain():
    pool = PagePool(16, 4)
    cache = PrefixCache(pool, 4)
    toks = list(range(100, 110))                 # 2 full pages + 2 tokens
    pages = pool.alloc(3)
    assert cache.commit(toks, pages) == 2        # partial page 2 not indexed
    assert pool.refcount(pages[0]) == 2          # us + the cache
    assert pool.refcount(pages[2]) == 1          # partial page stays private
    got, n = cache.match(toks)
    assert got == pages[:2] and n == 8
    assert pool.refcount(pages[0]) == 3          # match hands out a ref
    # a different chain shares nothing even when one PAGE's tokens agree
    other = [0, 0, 0, 0] + toks[4:8]
    got2, n2 = cache.match(other)
    assert got2 == [] and n2 == 0
    assert cache.stats()["hits"] == 1 and cache.stats()["misses"] == 1


def test_prefix_cache_partial_tail_match_prefers_longest():
    pool = PagePool(16, 4)
    cache = PrefixCache(pool, 4)
    a = pool.alloc(2)
    b = pool.alloc(2)
    cache.commit([1, 2, 3, 4, 5, 6, 7, 8], a)
    cache.commit([1, 2, 3, 4, 5, 6, 9, 9], b)   # same page 0 -> a[0] reused
    assert cache.match([1, 2, 3, 4])[0] == [a[0]]
    got, n = cache.match([1, 2, 3, 4, 5, 6, 9])
    assert n == 7, "partial overlap with b's page 1 (3 of 4 tokens)"
    assert got == [a[0], b[1]]


def test_prefix_cache_eviction_respects_refcounts_and_children():
    pool = PagePool(16, 4)
    cache = PrefixCache(pool, 4)
    pages = pool.alloc(2)
    cache.commit([1, 2, 3, 4, 5, 6, 7, 8], pages)
    pool.free(pages)                             # cache is now sole holder
    held, n = cache.match([1, 2, 3, 4])          # we re-take page 0
    assert (held, n) == ([pages[0]], 4)
    # page 0 has a committed child AND an external ref: only the
    # childless page 1 is evictable
    assert cache.evict(2) == 1
    assert len(cache) == 1 and pool.refcount(pages[1]) == 0
    assert cache.evict(1) == 0, "page 0 still externally referenced"
    pool.free(held)
    assert cache.evict(1) == 1, "sole-holder parent evicts once child is gone"
    assert pool.used_pages == 0


def test_prefix_cache_commit_first_writer_wins():
    pool = PagePool(16, 4)
    cache = PrefixCache(pool, 4)
    a = pool.alloc(1)
    b = pool.alloc(1)
    assert cache.commit([1, 2, 3, 4], a) == 1
    assert cache.commit([1, 2, 3, 4], b) == 0    # duplicate chain: kept as a
    assert cache.match([1, 2, 3, 4])[0] == [a[0]]
    assert pool.refcount(b[0]) == 1, "loser keeps only its own ref"


# ---------------------------------------------------------------------------
# bitwise parity: prefix-hit decode == cold chunked-prefill decode
# ---------------------------------------------------------------------------


def _serve(model, params, prompts, *, prefix_cache, gens=None, slots=2,
           pages=40, chunk=2 * PS, max_len=5 * PS, together=False, **kw):
    sch = Scheduler(model, params, slots=slots, pages=pages, page_size=PS,
                    max_len=max_len, prefill_chunk=chunk,
                    prefix_cache=prefix_cache, **kw)
    gens = gens or [6] * len(prompts)
    reqs = [Request(rid=i, prompt=list(p), max_new=g)
            for i, (p, g) in enumerate(zip(prompts, gens))]
    if together:
        sch.run(reqs)
    else:
        for r in reqs:                           # sequential: later ones hit
            sch.run([r])
    return {r.rid: list(r.out) for r in sch.finished}, sch


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "minicpm3-4b"])
def test_prefix_hit_decode_is_bitwise_cold(arch):
    """Requests 2 and 3 share request 1's committed prompt pages (and
    COW the partially shared page) — their greedy tokens must be
    bit-for-bit the no-cache chunked run's."""
    m = _model(arch)
    params = m.init(random.PRNGKey(0))
    prompts = _prompts(m.cfg.vocab_size)
    cold, cold_sch = _serve(m, params, prompts, prefix_cache=False)
    hot, sch = _serve(m, params, prompts, prefix_cache=True)
    assert hot == cold
    s = sch.latency_summary()
    assert s["prefix_hits"] >= 2 and s["prefix_hit_tokens"] >= 2 * 20
    assert s["cow_copies"] >= 1, "divergence inside page 2 must COW"
    assert s["cache_tokens_allocated"] < \
        cold_sch.latency_summary()["cache_tokens_allocated"]


def test_prefix_hit_skips_prefill_chunks():
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    prompts = _prompts(m.cfg.vocab_size)
    _, cold_sch = _serve(m, params, prompts, prefix_cache=False)
    _, hot_sch = _serve(m, params, prompts, prefix_cache=True)
    assert hot_sch.stats["chunks"] < cold_sch.stats["chunks"], \
        "hits must skip whole prefill chunks, not just bookkeeping"
    assert hot_sch.pool.total_allocs < cold_sch.pool.total_allocs


def test_concurrent_sharers_and_eviction_leave_sharer_pages_intact():
    """Both sharers in flight at once; the short one finishes (its pages
    freed) while the other still decodes on the shared pages — outputs
    must equal the cold run and no refcount error may fire."""
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    prompts = _prompts(m.cfg.vocab_size)[:2]
    gens = [2, 9]
    cold, _ = _serve(m, params, prompts, prefix_cache=False, gens=gens,
                     together=True)
    hot, sch = _serve(m, params, prompts, prefix_cache=True, gens=gens,
                      together=True)
    assert hot == cold
    # after drain only the cache's own references remain
    assert sch.pool.used_pages == len(sch.prefix.pages())
    assert all(sch.pool.refcount(p) == 1 for p in sch.prefix.pages())


def test_shared_pages_counted_once_in_occupancy():
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    prompts = _prompts(m.cfg.vocab_size)[:2]
    _, sch = _serve(m, params, prompts, prefix_cache=True, gens=[8, 8],
                    together=False)
    occ = sch.stats["occupancy"]
    assert any(o.get("shared_pages", 0) > 0 for o in occ), \
        "the second request must actually share pages"
    for o in occ:
        assert o["internal_fragmentation"] >= 0.0, \
            "shared pages double-counted in used_tokens"


def test_preemption_under_starvation_never_frees_referenced_pages():
    """A pool too small for both sharers at full length: preemption and
    prefix eviction must recycle only unreferenced pages (any violation
    raises inside PagePool.free) and every request still completes."""
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    prompts = _prompts(m.cfg.vocab_size)[:2]
    hot, sch = _serve(m, params, prompts, prefix_cache=True, pages=9,
                      gens=[12, 12], together=True)
    assert sorted(hot) == [0, 1]
    assert all(len(v) == 12 for v in hot.values())
    assert sch.stats["preemptions"] >= 1, \
        "9 usable pages cannot hold both lanes at full length"
    cold, _ = _serve(m, params, prompts, prefix_cache=False, pages=40,
                     gens=[12, 12], together=True)
    assert hot == cold, "preemption/eviction must not change any token"


# ---------------------------------------------------------------------------
# chunked prefill scheduling
# ---------------------------------------------------------------------------


def test_chunk_count_is_ceil_of_prompt_over_chunk():
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(
        1, m.cfg.vocab_size, 30).tolist()
    _, sch = _serve(m, params, [prompt], prefix_cache=False, chunk=PS)
    assert sch.stats["chunks"] == -(-30 // PS)


def test_long_prefill_interleaves_with_running_decode():
    """A long prompt admitted while short requests decode must not stall
    them: the short requests finish BEFORE the long prefill completes,
    and the long request's tokens still match its solo run."""
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    rng = np.random.default_rng(1)
    long_p = rng.integers(1, m.cfg.vocab_size, 36).tolist()
    short_p = rng.integers(1, m.cfg.vocab_size, 4).tolist()
    # same slots as the combined run: decode row math is pinned bitwise
    # only at matched batch width
    solo, _ = _serve(m, params, [long_p], prefix_cache=False, chunk=4,
                     slots=3, max_len=6 * PS)
    sch = Scheduler(m, params, slots=3, pages=40, page_size=PS,
                    max_len=6 * PS, prefill_chunk=4)
    reqs = [Request(rid=0, prompt=list(short_p), max_new=3),
            Request(rid=1, prompt=list(short_p) + [7], max_new=3),
            Request(rid=2, prompt=list(long_p), max_new=6)]
    sch.run(reqs)
    done = {r.rid: r for r in sch.finished}
    assert done[2].out == solo[0]
    # 36 tokens at chunk 4 = 9 chunk steps; the short requests (admitted
    # in the same step wave) must complete while those are in flight
    assert done[0].t_done <= done[2].token_walls[0]
    assert done[1].t_done <= done[2].token_walls[0]


def test_chunked_mode_rejects_unchunkable_archs():
    m = _model("falcon-mamba-7b")
    params = m.init(random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        Scheduler(m, params, slots=1, pages=8, page_size=8, max_len=32,
                  prefill_chunk=8)
    with pytest.raises(NotImplementedError):
        Scheduler(m, params, slots=1, pages=8, page_size=8, max_len=32,
                  prefix_cache=True)


def test_ttft_reported_in_latency_summary():
    m = _model("qwen3-0.6b")
    params = m.init(random.PRNGKey(0))
    prompts = _prompts(m.cfg.vocab_size)[:2]
    _, sch = _serve(m, params, prompts, prefix_cache=True, together=True)
    s = sch.latency_summary()
    assert 0.0 <= s["p50_ttft_s"] <= s["p95_ttft_s"]
