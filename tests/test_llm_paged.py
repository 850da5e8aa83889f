"""Paged KV cache engine (serving/paged.py): exact greedy parity with the
full-forward reference, page reuse under churn, int8 pool, and
admission blocking when the pool is oversubscribed."""

import jax
import numpy as np
import pytest

from mlrun_tpu.models import init_params, tiny_llama
from mlrun_tpu.serving.llm_batch import ContinuousBatchingEngine
from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine
from tests.greedy import (
    assert_greedy_equal_up_to_tie,
    greedy_reference as _greedy_reference,
    record_prefills,
)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama(attention_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_paged_greedy_exact(setup):
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(cfg, params, max_len=64, slots=2,
                                        prefill_buckets=(16,), page_size=8)
    eng.warmup()
    eng.start()
    try:
        prompt = [1, 7, 3, 9, 2]
        tokens, stats = eng.generate(prompt, max_new_tokens=6)
    finally:
        eng.stop()
    assert tokens == _greedy_reference(cfg, params, prompt, 6)
    assert stats["ttft_s"] > 0


@pytest.mark.parametrize("engine", ["paged", "dense"])
@pytest.mark.parametrize("chunk", [0, 4], ids=["inline", "chunked"])
@pytest.mark.parametrize("length", [7, 8, 9],
                         ids=["below", "at", "one-above"])
def test_admission_is_one_prefill_dispatch(setup, length, chunk, engine):
    """A prompt below, at and one above a bucket (8 of (8, 16)), inline
    and in chunks of 4: the first token comes from the dispatch that
    completed the prompt, read at its last real position — no one-token
    dispatch follows a padded one, as many dispatches as
    ``prefill_chunks``, and the greedy stream is the full forward's."""
    cfg, params = setup
    kind = PagedContinuousBatchingEngine if engine == "paged" \
        else ContinuousBatchingEngine
    kw = {"page_size": 8, "prefix_cache": False} if engine == "paged" \
        else {}
    eng = kind(cfg, params, max_len=64, slots=2, prefill_buckets=(8, 16),
               prefill_chunk=chunk, **kw)
    eng.warmup()
    shapes = record_prefills(eng)
    eng.start()
    try:
        prompt = [(3 * i + 1) % 97 for i in range(length)]
        tokens, _ = eng.generate(prompt, max_new_tokens=5)
        stats = eng.stats
    finally:
        eng.stop()
    assert_greedy_equal_up_to_tie(
        cfg, params, prompt, tokens,
        _greedy_reference(cfg, params, prompt, 5))
    want = [(1, chunk)] * -(-length // chunk) if chunk \
        else [(1, 8 if length <= 8 else 16)]
    # padded or not: no (1, 1)
    assert [shape for shape, _ in shapes] == want
    assert len(shapes) == stats["prefill_chunks"] == len(want)


def test_warmup_compiles_no_one_token_prefill(setup):
    """Warm-up compiles one prefill program a bucket (and a chunk), the
    prefix-hit forms too, and none for a single token."""
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(
        cfg, params, max_len=64, slots=2, prefill_buckets=(8, 16),
        page_size=8, prefill_chunk=4, attention_impl="kernel")
    shapes = record_prefills(eng)
    eng.warmup()
    # each shape in its cold and its prefix-hit form
    assert sorted(shapes) == [((1, width), hit) for width in (4, 8, 16)
                              for hit in (False, True)]


def test_paged_concurrent_churn_reuses_pages(setup):
    """More requests than slots, pool sized to the dense equivalent —
    pages must cycle through the free list and all results stay exact."""
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(cfg, params, max_len=32, slots=2,
                                        prefill_buckets=(8,), page_size=8)
    eng.start()
    try:
        prompts = [[1, 2, 3], [9, 8, 7, 6, 5], [4], [11, 12], [5, 5, 5]]
        budgets = [5, 3, 7, 4, 6]
        futures = [eng.submit(p, max_new_tokens=b)
                   for p, b in zip(prompts, budgets)]
        results = [f.result(timeout=300) for f in futures]
    finally:
        eng.stop()
    for prompt, budget, (tokens, _) in zip(prompts, budgets, results):
        assert tokens == _greedy_reference(cfg, params, prompt, budget)
    assert len(eng._free_pages) == eng.n_pages  # every page returned


def test_paged_oversubscribed_pool_blocks_not_breaks(setup):
    """Pool half the dense size: admission must wait for pages, all
    requests still complete exactly."""
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(cfg, params, max_len=32, slots=4,
                                        prefill_buckets=(8,), page_size=8,
                                        n_pages=8)  # dense would need 16
    eng.start()
    try:
        prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
        futures = [eng.submit(p, max_new_tokens=5) for p in prompts]
        results = [f.result(timeout=300) for f in futures]
    finally:
        eng.stop()
    for prompt, (tokens, _) in zip(prompts, results):
        assert tokens == _greedy_reference(cfg, params, prompt, 5)


def test_paged_int8_close_to_native(setup):
    cfg, params = setup
    outs = {}
    for kv_dtype in ("native", "int8"):
        eng = PagedContinuousBatchingEngine(cfg, params, max_len=32,
                                            slots=2, prefill_buckets=(8,),
                                            page_size=8, kv_dtype=kv_dtype)
        eng.start()
        try:
            tokens, _ = eng.generate([3, 1, 4, 1, 5], max_new_tokens=6)
        finally:
            eng.stop()
        outs[kv_dtype] = tokens
    assert outs["int8"][:3] == outs["native"][:3]


def test_paged_request_too_big_for_pool_fails_fast(setup):
    """A request needing more pages than the pool has must error its
    future immediately, not block the queue head forever."""
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(cfg, params, max_len=32, slots=2,
                                        prefill_buckets=(8,), page_size=8,
                                        n_pages=2)  # 16 tokens capacity
    eng.start()
    try:
        too_big = eng.submit([1, 2, 3], max_new_tokens=25)  # needs 4 pages
        fits = eng.submit([4, 5], max_new_tokens=5)
        with pytest.raises(ValueError, match="pages"):
            too_big.result(timeout=120)
        tokens, _ = fits.result(timeout=120)
        assert tokens == _greedy_reference(cfg, params, [4, 5], 5)
    finally:
        eng.stop()
