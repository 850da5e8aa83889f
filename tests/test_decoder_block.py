"""One decoder block, seven callers (``models/llama.decoder_block``): the
trainer's two layer bodies and the five serving programs each hand it an
``attend`` closure over their own cache. Every caller continues the same
prompt to the greedy tokens of the plain forward over the whole sequence;
the dense engine's two programs serve a model with q/k norms and experts
as the paged programs do; the head is the tree's own or the embedding's
transpose."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.models import llama, moe
from mlrun_tpu.ops.rotary import rope_table
from mlrun_tpu.serving import llm, llm_batch, paged

from .greedy import assert_greedy_equal_up_to_tie, greedy_reference

PAGE, MAX_LEN, NEW = 8, 32, 5
PROMPT = [(7 * i + 3) % 97 for i in range(11)]


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.tiny_llama()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, greedy_reference(cfg, params, PROMPT, NEW)


def _jit(program, *static):
    return jax.jit(functools.partial(program, *static))


def _layers_by_body(body, cfg, params, tokens,
                    slice_layer=llama.layer_slice):
    """The forward as a Python loop of one trainer layer body."""
    x = llama.embed(cfg, params, tokens)
    cos, sin = rope_table(jnp.arange(tokens.shape[1]), cfg.head_dim,
                          cfg.rope_theta)
    for layer in range(cfg.n_layers):
        x = body(x, slice_layer(params["layers"], layer), cos, sin)
    return llama.head_logits(cfg, params, x)


def _greedy_by_forward(logits_of, n):
    """Greedy tokens by a full forward a step, the sequence padded to one
    length (the causal mask keeps the padding out): one compilation."""
    logits_of = jax.jit(logits_of)
    seq, out = list(PROMPT), []
    for _ in range(n):
        padded = seq + [0] * (len(PROMPT) + n - len(seq))
        logits = logits_of(jnp.asarray([padded], jnp.int32))
        out.append(int(jnp.argmax(logits[0, len(seq) - 1])))
        seq.append(out[-1])
    return out


def _llama_trainer(cfg, params, want):
    body = functools.partial(llama._layer_body, cfg)
    return _greedy_by_forward(
        functools.partial(_layers_by_body, body, cfg, params), NEW)


def _moe_trainer(cfg, params, want):
    """One expert that every token is routed to with gate 1 and capacity
    to spare is the dense MLP over that expert's weights."""
    mcfg = moe.MoEConfig(
        **{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)},
        n_experts=1, top_k=1, capacity_factor=2.0)
    layers = {name: leaf for name, leaf in params["layers"].items()
              if not name.startswith("w_")}
    layers["router"] = jnp.zeros((cfg.n_layers, cfg.embed_dim, 1),
                                 jnp.float32)
    for name in ("gate", "up", "down"):
        layers[f"experts_{name}"] = params["layers"][f"w_{name}"][:, None]

    def body(x, lp, cos, sin):
        return moe._layer_body(mcfg, x, lp, cos, sin)[0]

    # models/moe.hidden_states scans the stacks, so a layer's experts
    # arrive sliced: every leaf indexed, the experts' too
    def slice_all(tree, layer):
        return jax.tree_util.tree_map(lambda a: a[layer], tree)

    return _greedy_by_forward(functools.partial(
        _layers_by_body, body, cfg, dict(params, layers=layers),
        slice_layer=slice_all), NEW)


def _prefilled(cfg, params):
    """The prompt through ``_forward_with_cache`` into a batch=1 cache,
    padded to a bucket of 16 and read at its last real position."""
    cache = llm.init_kv_cache(cfg, 1, MAX_LEN)
    padded = PROMPT + [0] * (16 - len(PROMPT))
    logits, cache = _jit(llm._forward_with_cache, cfg)(
        params, jnp.asarray([padded], jnp.int32), cache,
        logits_at=jnp.asarray(len(PROMPT) - 1, jnp.int32))
    cache["pos"] = jnp.asarray([len(PROMPT)], jnp.int32)
    return int(jnp.argmax(logits[0])), cache


def _forward_with_cache(cfg, params, want):
    token, cache = _prefilled(cfg, params)
    step = _jit(llm._forward_with_cache, cfg)
    out = [token]
    for _ in range(NEW - 1):
        logits, cache = step(params, jnp.asarray([[out[-1]]], jnp.int32),
                             cache)
        out.append(int(jnp.argmax(logits[0])))
    return out


def _decode_rowwise(cfg, params, want):
    token, cache = _prefilled(cfg, params)
    step = _jit(llm_batch._decode_rowwise, cfg)
    out = [token]
    for _ in range(NEW - 1):
        nxt, cache = step(params, jnp.asarray([[out[-1]]], jnp.int32), cache)
        out.append(int(nxt[0]))
    return out


def _verify_rowwise(cfg, params, want):
    """The first token and the forward's next ones as the proposals: the
    chunk's argmax at every position is the forward's continuation."""
    token, cache = _prefilled(cfg, params)
    chunk = jnp.asarray([[token] + want[1:NEW - 1]], jnp.int32)
    verified, _ = _jit(llm_batch._verify_rowwise, cfg)(params, chunk, cache)
    return [token] + [int(t) for t in verified[0]]


def _paged(cfg, params):
    """The prefilled cache's rows in pages 2.. of a pool, the slot's
    table and position beside them (a second slot is unmapped)."""
    token, cache = _prefilled(cfg, params)
    pool = paged.init_paged_pool(cfg, 8 + 1, PAGE)
    ids = jnp.asarray([2, 3, 4, 5], jnp.int32)
    pool = paged.insert_prompt_pages(pool, cache, ids, PAGE)
    table = jnp.stack([ids, jnp.full((4,), -1, jnp.int32)])
    return token, pool, table, jnp.asarray([len(PROMPT), 0], jnp.int32)


def _decode_paged(impl, cfg, params, want):
    token, pool, table, pos = _paged(cfg, params)
    step = _jit(paged._decode_rowwise_paged, cfg, PAGE, impl)
    out = [token]
    for _ in range(NEW - 1):
        nxt, pool, pos = step(params,
                              jnp.asarray([[out[-1]], [0]], jnp.int32),
                              pool, table, pos)
        pos = pos.at[1].set(0)
        out.append(int(nxt[0]))
    return out


def _verify_paged(impl, cfg, params, want):
    token, pool, table, pos = _paged(cfg, params)
    row = [token] + want[1:NEW - 1]
    chunk = jnp.asarray([row, [0] * len(row)], jnp.int32)
    verified, _ = _jit(paged._verify_rowwise_paged, cfg, PAGE, impl)(
        params, chunk, pool, table, pos)
    return [token] + [int(t) for t in verified[0]]


CALLERS = {
    "llama_layer_body": _llama_trainer,
    "moe_layer_body": _moe_trainer,
    "forward_with_cache": _forward_with_cache,
    "decode_rowwise": _decode_rowwise,
    "verify_rowwise": _verify_rowwise,
    "decode_rowwise_paged-reference": functools.partial(_decode_paged,
                                                        "reference"),
    "decode_rowwise_paged-kernel": functools.partial(_decode_paged,
                                                     "kernel"),
    "verify_rowwise_paged-reference": functools.partial(_verify_paged,
                                                        "reference"),
    "verify_rowwise_paged-kernel": functools.partial(_verify_paged,
                                                     "kernel"),
}


@pytest.mark.parametrize("caller", list(CALLERS))
def test_caller_continues_as_the_plain_forward(tiny, caller):
    """Prefill, then decode or verify, through each caller's cache: the
    greedy tokens of ``models.llama.forward`` over the whole sequence (up
    to a tie at bf16 resolution)."""
    cfg, params, want = tiny
    got = CALLERS[caller](cfg, params, want)
    assert_greedy_equal_up_to_tie(cfg, params, PROMPT, got, want)


# -- the dense engine's programs on a model with q/k norms and experts -------

@pytest.fixture(scope="module")
def sdar():
    """``tiny_sdar`` as a token-by-token model (``block_length`` 1), q/k
    norm scales away from 1 so that skipping them shows."""
    cfg = moe.tiny_sdar(block_length=1, dtype=jnp.float32)
    params = moe.init_params(cfg, jax.random.PRNGKey(1))
    for i, name in enumerate(("q_norm_scale", "k_norm_scale")):
        shape = params["layers"][name].shape
        params["layers"][name] = 1.0 + 0.5 * jax.random.normal(
            jax.random.PRNGKey(2 + i), shape, jnp.float32)
    return cfg, params


def _both_caches(cfg, slots):
    pages = MAX_LEN // PAGE
    cache = llm.init_kv_cache(cfg, slots, MAX_LEN)
    pool = paged.init_paged_pool(cfg, slots * pages + 1, PAGE)
    table = jnp.arange(slots * pages, dtype=jnp.int32).reshape(slots, pages)
    return cache, pool, table


def test_dense_decode_serves_qk_norms_and_experts(sdar):
    """``_decode_rowwise`` on the expert tree, rows at their own depths,
    gives the tokens of ``_decode_rowwise_paged`` (gather reference, every
    slot mapped). At the parent it skipped the q/k norm and read
    ``w_gate`` from a layer that has experts."""
    cfg, params = sdar
    cache, pool, table = _both_caches(cfg, 3)
    pos = jnp.zeros((3,), jnp.int32)
    tokens = jnp.asarray([[5], [17], [301]], jnp.int32)
    dense_step = _jit(llm_batch._decode_rowwise, cfg)
    paged_step = _jit(paged._decode_rowwise_paged, cfg, PAGE, "reference")
    for _ in range(6):
        dense, cache = dense_step(params, tokens, cache)
        pooled, pool, pos = paged_step(params, tokens, pool, table, pos)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(pooled))
        tokens = dense[:, None]
    np.testing.assert_array_equal(np.asarray(cache["pos"]), np.asarray(pos))


def test_dense_verify_serves_qk_norms_and_experts(sdar):
    """``_verify_rowwise`` over a chunk after a few decoded tokens gives
    the tokens of ``_verify_rowwise_paged`` (gather reference)."""
    cfg, params = sdar
    cache, pool, table = _both_caches(cfg, 2)
    pos = jnp.zeros((2,), jnp.int32)
    tokens = jnp.asarray([[9], [44]], jnp.int32)
    dense_step = _jit(llm_batch._decode_rowwise, cfg)
    paged_step = _jit(paged._decode_rowwise_paged, cfg, PAGE, "reference")
    for _ in range(3):
        nxt, cache = dense_step(params, tokens, cache)
        _, pool, pos = paged_step(params, tokens, pool, table, pos)
        tokens = nxt[:, None]
    chunk = jnp.asarray([[3, 8, 200, 41], [77, 6, 5, 123]], jnp.int32)
    dense, _ = _jit(llm_batch._verify_rowwise, cfg)(params, chunk, cache)
    pooled, _ = _jit(paged._verify_rowwise_paged, cfg, PAGE, "reference")(
        params, chunk, pool, table, pos)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(pooled))


# -- the head -----------------------------------------------------------------

@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_lm_head_is_the_trees_or_the_embeddings_transpose(tied):
    cfg = llama.tiny_llama(tie_embeddings=tied)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    head = llama.lm_head(params)
    assert head.shape == (cfg.embed_dim, cfg.vocab_size)
    want = params["embedding"].T if tied else params["lm_head"]
    np.testing.assert_array_equal(np.asarray(head), np.asarray(want))
    assert ("lm_head" in params) is (not tied)
    # and the serving head is the forward's: final norm, then that matrix
    tokens = jnp.asarray([PROMPT], jnp.int32)
    x = llama.hidden_states(cfg, params, tokens)
    logits = jnp.einsum("bse,ev->bsv", x, head,
                        preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(llama.forward(cfg, params, tokens)))
