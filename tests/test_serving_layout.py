"""The layout in which a serving engine holds ``wq``, ``wk``, ``wv``
(serving/llm.py ``serving_tree``; docs/serving.md "The serving layout"):
out-major and split into heads, [L, heads, head_dim, E], under the names
``wq_t``, ``wk_t``, ``wv_t``. The same products over another order of
storage: the programs on the engine's tree give the logits of the programs
on the logical tree, an engine serves the logical tree's greedy tokens, a
tenant's LoRA delta is what it was, a tree already relaid is taken as it
is, and which layout a tree has is read from its names alone.
``tests/test_tpu_compile.py::test_qkv_weights_read_as_stored`` asks the
chip's compiler what the layout is for. CPU, tiny models, float32."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.models import (
    init_lora_nonzero,
    init_params,
    tiny_llama,
    tiny_sdar,
    tiny_xing4,
)
from mlrun_tpu.models.llama import SERVING_LEAVES, forward
from mlrun_tpu.serving.adapters import AdapterRegistry
from mlrun_tpu.serving.llm import (
    LLMEngine,
    _forward_with_cache,
    init_kv_cache,
    relaid_bytes,
    relay_layers,
    serving_tree,
)
from mlrun_tpu.serving.llm_batch import (
    ContinuousBatchingEngine,
    _decode_rowwise,
)
from mlrun_tpu.serving.paged import (
    PagedContinuousBatchingEngine,
    _decode_rowwise_paged,
    init_paged_pool,
)

PAGE, MAX_LEN = 8, 64
PROMPT = [1, 7, 3, 9, 2, 4, 6, 8, 5, 3, 1]
FAMILIES = {"dense": tiny_llama, "block": tiny_sdar, "latent": tiny_xing4}


@functools.lru_cache(maxsize=None)
def _model(family: str):
    cfg = FAMILIES[family](dtype=jnp.float32, attention_impl="reference")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _qkv_bytes(params) -> int:
    return sum(int(params["layers"][name].nbytes) for name in SERVING_LEAVES)


def _prefill_logits(cfg, tree, tokens, **kw):
    cache = init_kv_cache(cfg, 1, MAX_LEN)
    logits, _ = _forward_with_cache(
        cfg, tree, jnp.asarray([tokens], jnp.int32), cache,
        logits_at=np.int32(len(tokens) - 1), **kw)
    return np.asarray(logits[0], np.float32)


# -- the tree -----------------------------------------------------------------
@pytest.mark.parametrize("family", ["dense", "block"])
def test_relaid_leaves_are_the_logical_ones_transposed(family):
    cfg, params = _model(family)
    tree = serving_tree(cfg, params)
    for name, relaid in SERVING_LEAVES.items():
        logical = params["layers"][name]                  # [L, E, H]
        assert name not in tree["layers"]
        assert tree["layers"][relaid].shape == (
            cfg.n_layers, logical.shape[2] // cfg.head_dim, cfg.head_dim,
            cfg.embed_dim)
        np.testing.assert_array_equal(
            np.asarray(tree["layers"][relaid]).reshape(
                cfg.n_layers, -1, cfg.embed_dim),
            np.swapaxes(np.asarray(logical), 1, 2))
    # every other leaf is the caller's own, and the caller's tree is whole
    assert all(tree["layers"][name] is leaf
               for name, leaf in params["layers"].items()
               if name not in SERVING_LEAVES)
    assert set(SERVING_LEAVES) <= set(params["layers"])
    assert relaid_bytes(tree) == _qkv_bytes(params)


def test_a_relaid_tree_is_taken_as_it_is():
    cfg, params = _model("dense")
    tree = serving_tree(cfg, params)
    assert serving_tree(cfg, tree) is tree
    engines = [kind(cfg, tree, max_len=MAX_LEN, slots=2,
                    prefill_buckets=(16,), **kw)
               for kind, kw in ((PagedContinuousBatchingEngine,
                                 {"page_size": PAGE}),
                                (ContinuousBatchingEngine, {}))]
    engines.append(LLMEngine(cfg, tree, max_len=MAX_LEN,
                             prefill_buckets=(16,)))
    for engine in engines:
        assert engine.params is tree
        assert all(engine.params["layers"][relaid] is tree["layers"][relaid]
                   for relaid in SERVING_LEAVES.values())


def test_relay_in_place_lets_go_of_the_logical_leaves():
    cfg, params = _model("dense")
    layers = dict(params["layers"])
    assert relay_layers(cfg, layers) is layers
    assert not set(SERVING_LEAVES) & set(layers)
    assert set(SERVING_LEAVES.values()) <= set(layers)
    before = dict(layers)
    relay_layers(cfg, layers)                # nothing left to relay
    assert all(layers[name] is leaf for name, leaf in before.items())


def test_a_latent_family_has_nothing_to_relay():
    cfg, params = _model("latent")
    assert serving_tree(cfg, params) is params
    layers = dict(params["layers"])
    assert relay_layers(cfg, layers) == params["layers"]


def test_the_trainer_refuses_a_relaid_tree():
    cfg, params = _model("dense")
    with pytest.raises(ValueError, match="serving engine's layout"):
        forward(cfg, serving_tree(cfg, params),
                jnp.asarray([PROMPT], jnp.int32))


# -- the programs -------------------------------------------------------------
@pytest.mark.parametrize("family", ["dense", "block"])
def test_prefill_logits_are_the_logical_trees(family):
    cfg, params = _model(family)
    np.testing.assert_allclose(
        _prefill_logits(cfg, serving_tree(cfg, params), PROMPT),
        _prefill_logits(cfg, params, PROMPT), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("program", ["paged", "rowwise"])
def test_decode_tick_is_the_logical_trees(program):
    """One decode tick of two rows at different depths, on the logical
    tree and on the engine's: the same tokens, the same rows written."""
    cfg, params = _model("dense")
    step = jnp.asarray([[5], [9]], jnp.int32)
    pos = jnp.asarray([3, 6], jnp.int32)
    if program == "paged":
        table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)

        def run(tree):
            pool = jax.tree_util.tree_map(
                lambda a: a + 0.01, init_paged_pool(cfg, 5, PAGE))
            token, pool, _ = _decode_rowwise_paged(
                cfg, PAGE, "reference", tree, step, pool, table, pos)
            return token, pool["k"], pool["v"]
    else:
        def run(tree):
            cache = jax.tree_util.tree_map(
                lambda a: a + 0.01, init_kv_cache(cfg, 2, MAX_LEN))
            cache["pos"] = pos
            token, cache = _decode_rowwise(cfg, tree, step, cache)
            return token, cache["k"], cache["v"]
    got, want = run(serving_tree(cfg, params)), run(params)
    np.testing.assert_array_equal(got[0], want[0])
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_lora_delta_on_qkv_is_unchanged():
    """A tenant's delta on wq, wk, wv (and wo) reads the projection's
    input and the bank's factors, never the weight: on the engine's tree
    the program adds what it added on the logical one."""
    cfg, params = _model("dense")
    registry = AdapterRegistry(cfg, sources={"t1": init_lora_nonzero(
        cfg, jax.random.PRNGKey(1), rank=4, alpha=8.0)})
    registry.pin("t1")
    kw = {"adapter_ids": jnp.asarray([registry.ensure_loaded("t1")],
                                     jnp.int32),
          "lora": registry.bank.tensors}
    tree = serving_tree(cfg, params)
    tenant = _prefill_logits(cfg, tree, PROMPT, **kw)
    np.testing.assert_allclose(
        tenant, _prefill_logits(cfg, params, PROMPT, **kw),
        rtol=1e-5, atol=1e-5)
    base = _prefill_logits(cfg, tree, PROMPT)
    np.testing.assert_allclose(                     # the delta itself
        tenant - base,
        _prefill_logits(cfg, params, PROMPT, **kw)
        - _prefill_logits(cfg, params, PROMPT), rtol=1e-4, atol=1e-5)
    assert np.abs(tenant - base).max() > 1e-3


# -- the engines --------------------------------------------------------------
def _greedy_by_the_program(cfg, params, prompt, n):
    """``n`` greedy tokens by the prefill program on the logical tree,
    the whole sequence anew at every step."""
    seq, out = list(prompt), []
    for _ in range(n):
        out.append(int(_prefill_logits(cfg, params, seq).argmax()))
        seq.append(out[-1])
    return out


@pytest.mark.parametrize("engine", ["paged", "dense", "llm"])
def test_engine_serves_the_logical_trees_tokens(engine):
    cfg, params = _model("dense")
    if engine == "llm":
        eng = LLMEngine(cfg, params, max_len=MAX_LEN, prefill_buckets=(16,))
        tokens, _ = eng.generate(PROMPT, max_new_tokens=6)
        assert eng.weights_relaid_bytes == _qkv_bytes(params)
    else:
        kind, kw = (PagedContinuousBatchingEngine, {"page_size": PAGE}) \
            if engine == "paged" else (ContinuousBatchingEngine, {})
        eng = kind(cfg, params, max_len=MAX_LEN, slots=2,
                   prefill_buckets=(16,), **kw)
        eng.start()
        try:
            tokens, _ = eng.generate(PROMPT, max_new_tokens=6)
        finally:
            eng.stop()
    assert tokens == _greedy_by_the_program(cfg, params, PROMPT, 6)
    # the engine's tree is its own; the caller's still is the logical one
    assert set(SERVING_LEAVES.values()) <= set(eng.params["layers"])
    assert set(SERVING_LEAVES) <= set(params["layers"])


@pytest.mark.parametrize("family", ["dense", "block", "latent"])
def test_weights_relaid_bytes(family):
    """Three leaves' bytes where the family's q/k/v are ``llama_qkv``'s,
    0 for the latent family, in ``stats`` and on the gauge."""
    from mlrun_tpu.obs import REGISTRY

    cfg, params = _model(family)
    eng = PagedContinuousBatchingEngine(
        cfg, params, max_len=MAX_LEN, slots=2, page_size=PAGE,
        prefill_buckets=(16,))
    want = 0 if family == "latent" else _qkv_bytes(params)
    assert eng.stats["weights_relaid_bytes"] == want
    eng.start()
    try:
        series = [line for line in REGISTRY.render().splitlines()
                  if line.startswith("mlt_llm_weights_relaid_bytes{")
                  and f'engine="{eng._obs_name}"' in line]
    finally:
        eng.stop()
    assert [float(line.rsplit(" ", 1)[1]) for line in series] == [want]


def test_a_resident_draft_is_relaid_and_counted():
    cfg, params = _model("dense")
    draft_cfg = tiny_llama(dtype=jnp.float32, attention_impl="reference",
                           n_layers=1)
    draft = init_params(draft_cfg, jax.random.PRNGKey(1))
    eng = ContinuousBatchingEngine(
        cfg, params, max_len=MAX_LEN, slots=2, prefill_buckets=(16,),
        speculative={"enabled": True, "k": 2, "draft_config": draft_cfg,
                     "draft_params": draft})
    assert set(SERVING_LEAVES.values()) <= set(
        eng._spec_draft_params["layers"])
    assert eng.stats["weights_relaid_bytes"] == \
        _qkv_bytes(params) + _qkv_bytes(draft)


def test_model_server_relays_its_own_tree_once():
    """``LLMModelServer.load`` owns the tree it makes: it is relaid in
    place before the engines are built, and a fleet's replicas share the
    relaid leaves."""
    from mlrun_tpu.serving.llm import LLMModelServer

    server = LLMModelServer(
        None, name="layout", model_preset="tiny", continuous_batching=True,
        paged=True, page_size=PAGE, slots=2, max_len=MAX_LEN, replicas=2,
        max_new_tokens=4, warmup=False)
    server.post_init()
    try:
        engines = [replica.engine
                   for replica in server.engine._workers.values()]
        out = server.predict({"inputs": [PROMPT]})
    finally:
        server.engine.stop()
    assert len(engines) == 2 and len(out[0]) == 4
    first, second = (engine.params["layers"] for engine in engines)
    assert not set(SERVING_LEAVES) & set(first)
    assert all(first[relaid] is second[relaid]
               for relaid in SERVING_LEAVES.values())
    assert all(engine.stats["weights_relaid_bytes"] == relaid_bytes(
        engine.params) > 0 for engine in engines)
