"""Prefix-aware KV reuse + chunked prefill (serving/prefix.py, paged.py,
llm_batch.py): greedy bit-equality between the cold-prefill and
prefix-cache-hit paths, refcount/eviction correctness under
``llm.prefix_evict`` chaos, chunked-prefill resume across scheduler
ticks, up-front PromptTooLongError, and TTFT/ITL percentiles. CPU-only,
tier-1-fast."""

import importlib.util
import pathlib
import time

import jax
import pytest

from mlrun_tpu.chaos import FaultPoints, chaos
from mlrun_tpu.models import init_params, tiny_llama
from mlrun_tpu.serving.llm_batch import ContinuousBatchingEngine
from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine
from mlrun_tpu.serving.prefix import PrefixCache
from mlrun_tpu.serving.resilience import PromptTooLongError
from tests.greedy import assert_greedy_equal_up_to_tie


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama(attention_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _greedy_reference(cfg, params, prompt, n):
    import jax.numpy as jnp

    from mlrun_tpu.models.llama import forward

    seq = list(prompt)
    out = []
    for _ in range(n):
        logits = forward(cfg, params, jnp.asarray([seq], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        seq.append(nxt)
    return out


# -- PrefixCache unit behavior (no jax) --------------------------------------
def test_prefix_cache_match_register_refcounts():
    pc = PrefixCache(4)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 9]  # 2 full blocks + tail
    assert pc.match(prompt) == ([], [])
    held, claimed = pc.register(prompt, [10, 11, -1], [])
    assert claimed == [10, 11] and pc.cached_pages() == 2
    pages, nodes = pc.match(prompt)
    assert pages == [10, 11]
    assert [n.refcount for n in nodes] == [2, 2]  # register + match holds
    # a prompt of exactly N blocks matches at most N-1 (one token must
    # remain to prefill for last-position logits)
    pages_whole, nodes_whole = pc.match(prompt[:8])
    assert pages_whole == [10]
    pc.release(nodes)
    pc.release(nodes_whole)
    pc.release(held)
    assert all(n.refcount == 0 for n in nodes)
    assert pc.evictable_pages() == 2
    # duplicate registration keeps the caller's pages private (no claim)
    # but still holds the chain, pinning it against eviction
    held2, claimed2 = pc.register(prompt, [20, 21, -1], [])
    assert claimed2 == [] and len(held2) == 2
    assert [n.page_id for n in held2] == [10, 11]
    assert pc.evictable_pages() == 0
    pc.release(held2)
    assert pc.evictable_pages() == 2


def test_prefix_cache_eviction_leaf_first_lru_and_refcount_pinning():
    pc = PrefixCache(2)
    chain = [1, 2, 3, 4, 9]  # blocks (1,2) -> (3,4)
    held, _ = pc.register(chain, [0, 1, -1], [])
    # every page held: nothing reclaimable, evict() is a no-op
    assert pc.evictable_pages() == 0 and pc.evict(2) == []
    _, second_hold = pc.match(chain)
    pc.release(held)
    # still pinned by the second hold
    assert pc.evictable_pages() == 0 and pc.evict(2) == []
    pc.release(second_hold)
    assert pc.evictable_pages() == 2
    # leaf-first: the child page goes before its parent
    assert pc.evict(1) == [1]
    assert pc.evict(5) == [0]
    assert pc.cached_pages() == 0


# -- engine: cache-hit bit-equality ------------------------------------------
def test_prefix_hit_greedy_bit_identical(setup):
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(cfg, params, max_len=64, slots=2,
                                        prefill_buckets=(16,), page_size=8)
    eng.start()
    try:
        prompt = [1, 7, 3, 9, 2, 4, 6, 8, 5, 3, 1, 2]  # one full block
        cold, _ = eng.generate(prompt, max_new_tokens=6)
        assert eng.stats["prefix_hits"] == 0
        warm, warm_stats = eng.generate(prompt, max_new_tokens=6)
        stats = eng.stats
        # shared prefix, different suffix must also branch correctly
        other = prompt[:8] + [9, 9, 4]
        branch, _ = eng.generate(other, max_new_tokens=6)
    finally:
        eng.stop()
    ref = _greedy_reference(cfg, params, prompt, 6)
    assert cold == ref
    assert warm == ref  # cache-hit path bit-identical to cold prefill
    assert branch == _greedy_reference(cfg, params, other, 6)
    assert stats["prefix_hits"] >= 1 and stats["prefix_queries"] >= 2
    assert stats["prefix_cached_tokens"] >= 8
    assert stats["prefix_cached_pages"] >= 1
    assert warm_stats["ttft_s"] > 0


# -- engine: refcount/eviction under chaos -----------------------------------
@pytest.mark.chaos
def test_prefix_evict_only_at_refcount_zero(setup):
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(cfg, params, max_len=64, slots=2,
                                        prefill_buckets=(16,), page_size=8,
                                        n_pages=9)
    evicted = []

    def observe(point, ctx):
        # invariant: a page mapped by ANY active slot (refcount > 0) must
        # never be evicted — only refcount-0 cached pages are reclaimable
        active_pages = set()
        for i, slot in enumerate(eng._slot_state):
            if slot.active:
                active_pages.update(
                    int(p) for p in eng._page_table[i] if p >= 0)
        assert ctx["refcount"] == 0
        assert ctx["page_id"] not in active_pages
        evicted.append(ctx["page_id"])

    chaos.inject(FaultPoints.llm_prefix_evict, action=observe)
    eng.start()
    try:
        shared = list(range(1, 17))   # 16 tokens = 2 full blocks
        other = list(range(30, 46))   # a second cached chain
        cold, _ = eng.generate(shared, max_new_tokens=8)
        eng.generate(other, max_new_tokens=8)
        assert eng.stats["prefix_cached_pages"] == 4
        root = eng._prefix._root
        b0 = root.children[tuple(shared[:8])]
        b1 = b0.children[tuple(shared[8:16])]
        q0 = root.children[tuple(other[:8])]
        q1 = q0.children[tuple(other[8:16])]
        b_pages = {b0.page_id, b1.page_id}
        q_pages = {q0.page_id, q1.page_id}

        # f1 re-uses `shared` and HOLDS its whole chain while active;
        # f2's allocation (3 pages, only 1 free) must evict the
        # refcount-0 `other` chain and leave the held chain alone
        f1 = eng.submit(shared, max_new_tokens=24)
        f2 = eng.submit(list(range(100, 117)), max_new_tokens=7)
        t1, _ = f1.result(timeout=300)
        t2, _ = f2.result(timeout=300)
        # the prefix-hit rerun must be bit-identical to the engine's own
        # cold decode (a longer greedy budget shares the prefix)
        assert t1[:len(cold)] == cold
        assert len(t2) == 7
        assert q_pages <= set(evicted)
        assert not b_pages & set(evicted)

        # once nothing holds the shared chain (refcount 0), pool
        # pressure evicts it too: a long-running active request plus one
        # more allocation
        f3 = eng.submit(list(range(200, 208)), max_new_tokens=40)
        f4 = eng.submit(list(range(300, 316)), max_new_tokens=8)
        f3.result(timeout=300)
        f4.result(timeout=300)
        assert b_pages <= set(evicted)
        stats = eng.stats
    finally:
        eng.stop()
    assert stats["prefix_evictions"] == len(evicted) >= 4
    # conservation after drain: every page is either free or refcount-0
    # cached (nothing leaked, nothing still pinned)
    assert len(eng._free_pages) + eng._prefix.cached_pages() == eng.n_pages
    assert eng._prefix.evictable_pages() == eng._prefix.cached_pages()


# -- chunked prefill ---------------------------------------------------------
def test_chunked_prefill_resumes_across_ticks_dense(setup):
    cfg, params = setup
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, slots=2,
                                   prefill_buckets=(16,), prefill_chunk=8)
    eng.start()
    try:
        short = [1, 2, 3]
        f1 = eng.submit(short, max_new_tokens=30)
        # a max_len-bucket prompt: 56 tokens = 7 chunks resumed across
        # ticks while slot 0 keeps decoding
        long_prompt = [(i * 7 + 3) % 512 for i in range(56)]
        f2 = eng.submit(long_prompt, max_new_tokens=6)
        t1, _ = f1.result(timeout=300)
        t2, _ = f2.result(timeout=300)
        stats = eng.stats
    finally:
        eng.stop()
    # the already-decoding short request must be untouched by the chunks
    # interleaving with it — up to an argmax tie at bf16 resolution (this
    # prompt has one at token 6, margin 0.0027, with or without the long
    # request beside it)
    assert_greedy_equal_up_to_tie(
        cfg, params, short, t1, _greedy_reference(cfg, params, short, 30))
    assert t2 == _greedy_reference(cfg, params, long_prompt, 6)
    assert stats["prefill_chunks"] >= 8  # 1 (short) + 7 (long)
    # tick instrumentation: no scheduler iteration absorbed more than one
    # chunk of prefill compute, so decode never stalled longer than that
    assert 0 < stats["prefill_tokens_tick_max"] <= 8
    # percentile rings populated from the same run
    assert stats["ttft_p50_s"] > 0
    assert stats["ttft_p95_s"] >= stats["ttft_p50_s"]
    assert stats["itl_p50_s"] > 0
    assert stats["itl_p95_s"] >= stats["itl_p50_s"]


def test_chunked_prefill_paged_resumes_and_hits_prefix(setup):
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(cfg, params, max_len=64, slots=2,
                                        prefill_buckets=(16,), page_size=8,
                                        prefill_chunk=8)
    eng.start()
    try:
        prompt = [(i * 11 + 5) % 512 for i in range(20)]
        cold, _ = eng.generate(prompt, max_new_tokens=6)
        warm, _ = eng.generate(prompt, max_new_tokens=6)
        stats = eng.stats
    finally:
        eng.stop()
    ref = _greedy_reference(cfg, params, prompt, 6)
    assert cold == ref and warm == ref
    assert stats["prefix_hits"] == 1
    assert 0 < stats["prefill_tokens_tick_max"] <= 8
    # warm suffix (4 tokens past the 16-token cached prefix) is 1 chunk;
    # cold is 3 — the hit skipped prefill work, not just time
    assert stats["prefill_chunks"] == 4


def test_chunked_admission_not_killed_by_max_wait(setup):
    """max_wait is a QUEUE-time budget: once admitted, a request whose
    chunked prefill spans ticks past its budget is being served, not
    waiting — it must complete, exactly like the unchunked path."""
    cfg, params = setup
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, slots=1,
                                   prefill_buckets=(16,), prefill_chunk=8)
    eng.start = lambda: None  # drive scheduler ticks from the test
    future = eng.submit(list(range(1, 41)), max_new_tokens=4, max_wait=30)
    eng._admission_tick()  # dequeue + first chunk
    assert eng._admission is not None
    # budget expires mid-prefill — remaining chunks must still run
    eng._admission.expires = time.perf_counter() - 1.0
    for _ in range(20):
        if eng._admission is None:
            break
        eng._admission_tick()
    assert eng._admission is None
    while not future.done():
        eng._decode_tick()
    tokens, _ = future.result(timeout=0)
    assert len(tokens) == 4
    assert eng.stats["expired"] == 0


# -- typed 400-class rejection ------------------------------------------------
def test_prompt_too_long_rejected_up_front(setup):
    cfg, params = setup
    eng = ContinuousBatchingEngine(cfg, params, max_len=32, slots=1,
                                   prefill_buckets=(16,))
    future = eng.submit(list(range(20)), max_new_tokens=20)
    # rejected before any queueing: resolved without the scheduler running
    assert future.done()
    with pytest.raises(PromptTooLongError) as exc_info:
        future.result(timeout=0)
    assert exc_info.value.status_code == 400
    assert isinstance(exc_info.value, ValueError)  # pre-typed callers
    assert eng.stats["rejected_too_long"] == 1
    eng.stop()


def test_prompt_too_long_rejected_paged(setup):
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(cfg, params, max_len=32, slots=1,
                                        prefill_buckets=(16,), page_size=8)
    future = eng.submit(list(range(30)), max_new_tokens=10)
    assert future.done()
    with pytest.raises(PromptTooLongError):
        future.result(timeout=0)
    eng.stop()


# -- bench smoke (tier-1: exercises the cache-hit path every run) ------------
def test_bench_serve_smoke():
    path = pathlib.Path(__file__).resolve().parent.parent / "bench_serve.py"
    spec = importlib.util.spec_from_file_location("bench_serve", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.run(requests=4, prefix_tokens=32, suffix_tokens=4,
                  max_new=4, page_size=8, max_len=64, warmup=False)
    assert out["repeated"]["prefix_hit_rate"] > 0
    assert out["repeated"]["cold_ttft_ms"] > 0
    assert out["repeated"]["warm_p50_ttft_ms"] > 0
    assert out["repeated"]["nocache_p50_ttft_ms"] > 0
    assert out["unique"]["tokens_per_sec_cache_on"] > 0
    assert out["unique"]["tokens_per_sec_cache_off"] > 0
