"""LLM inference engine tests (CPU, tiny model)."""

import jax
import numpy as np
import pytest

from mlrun_tpu.models import init_params, tiny_llama
from mlrun_tpu.serving.llm import LLMEngine, init_kv_cache


@pytest.fixture(scope="module")
def engine():
    cfg = tiny_llama(attention_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = LLMEngine(cfg, params, max_len=128, prefill_buckets=(32, 64))
    # ``eng.params`` is the engine's own tree (wq, wk, wv in the serving
    # layout); the plain forward takes the logical one
    eng.logical_params = params
    return eng


def test_generate_greedy(engine):
    tokens, stats = engine.generate(list(range(10)), max_new_tokens=12)
    assert len(tokens) == 12
    assert stats["ttft_s"] > 0
    assert stats["prompt_len"] == 10


def test_generate_matches_full_forward(engine):
    """Cached decode must agree with a full uncached forward (greedy)."""
    import jax.numpy as jnp

    from mlrun_tpu.models.llama import forward

    prompt = [1, 7, 3, 9, 2]
    gen, _ = engine.generate(prompt, max_new_tokens=4)
    # replay with full forward: greedy argmax step by step
    cfg = engine.config
    seq = list(prompt)
    expected = []
    for _ in range(4):
        logits = forward(cfg, engine.logical_params,
                         jnp.asarray([seq], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        expected.append(nxt)
        seq.append(nxt)
    assert gen == expected, (gen, expected)


@pytest.mark.parametrize("batched", [False, True],
                         ids=["generate", "generate_batch"])
@pytest.mark.parametrize("length", [31, 32, 33],
                         ids=["below", "at", "one-above"])
def test_first_token_from_the_padded_prefill(engine, length, batched):
    """A prompt below, at and one above the bucket of 32: the first
    token is read from the prefill dispatch at the prompt's last real
    position — one prefill, no one-token dispatch through ``_decode`` —
    and the stream is the full forward's."""
    from tests.greedy import (
        assert_greedy_equal_up_to_tie,
        greedy_reference,
        record_prefills,
    )

    cfg = engine.config
    eng = LLMEngine(cfg, engine.params, max_len=128,
                    prefill_buckets=(32, 64), batch=2 if batched else 1)
    eng.decode_chunk = 4
    prefills, decodes, decode = record_prefills(eng), [], eng._decode

    def counted_decode(*args, **kwargs):
        decodes.append(1)
        return decode(*args, **kwargs)

    eng._decode = counted_decode
    prompt = [(7 * i + 2) % 101 for i in range(length)]
    if batched:
        (got, _), _ = eng.generate_batch([prompt, prompt], max_new_tokens=5)
    else:
        got, _ = eng.generate(prompt, max_new_tokens=5)
    assert_greedy_equal_up_to_tie(
        cfg, engine.logical_params, prompt, got,
        greedy_reference(cfg, engine.logical_params, prompt, 5))
    rows = 2 if batched else 1
    assert prefills == [((rows, 32 if length <= 32 else 64), False)]
    assert not decodes


def test_eos_stops_generation(engine):
    full, _ = engine.generate([1, 2, 3], max_new_tokens=16)
    eos = full[1]  # pretend the 2nd generated token is eos
    stopped, _ = engine.generate([1, 2, 3], max_new_tokens=16, eos_id=eos)
    assert stopped[-1] == eos
    assert len(stopped) <= len(full)


def test_kv_cache_shapes():
    cfg = tiny_llama()
    cache = init_kv_cache(cfg, batch=2, max_len=64)
    assert cache["k"].shape == (cfg.n_layers, 2, 64, cfg.n_kv_heads,
                                cfg.head_dim)
    assert cache["pos"].shape == (2,)


def test_generate_batch_matches_single(engine):
    """Equal-length batch: every row must match its single-prompt result."""
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7, 6, 5], [3, 3, 3, 3, 3]]
    cfg = engine.config
    eng = LLMEngine(cfg, engine.params, max_len=128,
                    prefill_buckets=(32,), batch=4)
    batch_out, stats = eng.generate_batch(prompts, max_new_tokens=8)
    assert stats["batch"] == 3
    for prompt, got in zip(prompts, batch_out):
        single, _ = eng.generate(prompt, max_new_tokens=8)
        assert got == single, (prompt, got, single)


def test_generate_batch_mixed_lengths_fallback(engine):
    cfg = engine.config
    eng = LLMEngine(cfg, engine.params, max_len=128, prefill_buckets=(32,),
                    batch=2)
    outs, stats = eng.generate_batch([[1, 2, 3], [4, 5, 6, 7, 8]],
                                     max_new_tokens=4)
    assert len(outs) == 2 and all(len(o) == 4 for o in outs)


def test_generate_batch_capacity_guard_matches_single(engine):
    """Regression: batch capacity guard keyed on prompt_len (not bucket)."""
    cfg = engine.config
    eng = LLMEngine(cfg, engine.params, max_len=64, prefill_buckets=(32,),
                    batch=2)
    single, _ = eng.generate([1, 2, 3, 4, 5], max_new_tokens=20)
    batch, _ = eng.generate_batch([[1, 2, 3, 4, 5], [1, 2, 3, 4, 5]],
                                  max_new_tokens=20)
    assert batch[0] == single
    assert len(batch[0]) == 20


def test_generate_batch_empty():
    cfg = tiny_llama(attention_impl="reference")
    import jax as _jax

    eng = LLMEngine(cfg, init_params(cfg, _jax.random.PRNGKey(0)),
                    max_len=64, prefill_buckets=(32,))
    outs, stats = eng.generate_batch([], max_new_tokens=4)
    assert outs == [] and stats["batch"] == 0


def test_sample_logits_properties():
    """On-device sampler: greedy rows exact, top-k respected, top-p keeps
    the head of the distribution, per-row settings independent."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlrun_tpu.serving.sampling import sample_logits

    v = 100
    logits = jnp.tile(jnp.linspace(0.0, 5.0, v)[None, :], (4, 1))
    temperature = jnp.asarray([0.0, 1.0, 1.0, 0.5])
    top_k = jnp.asarray([0, 1, 5, 0])
    top_p = jnp.asarray([1.0, 1.0, 1.0, 0.05])
    counts = {i: set() for i in range(4)}
    for s in range(200):
        out = np.asarray(sample_logits(logits, jax.random.PRNGKey(s),
                                       temperature, top_k, top_p))
        for i in range(4):
            counts[i].add(int(out[i]))
    assert counts[0] == {v - 1}                      # greedy row: argmax only
    assert counts[1] == {v - 1}                      # top_k=1: argmax only
    assert all(t >= v - 5 for t in counts[2])        # top_k=5: top 5 ids
    assert len(counts[2]) > 1                        # ...and actually samples
    assert all(t >= v - 3 for t in counts[3])        # tight nucleus: head only
