"""The Xing4.0 family (models/xing4.py) through the one decoder block and the
paged engine, against its plain reference (tests/xing4_reference.py, the
benchmark's): the whole forward, prefill then decode over the latent page
pool under both paged attention implementations, chunked prefill and a
prefix hit, the absorbed against the expanded attention, the residual mix,
the router, the tick's lookahead, and what the latent layout refuses by
type. CPU-only (Pallas interpret mode), tiny sizes, float32 where a number
is compared."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.models import init_params, tiny_xing4, xing4, xing4_29b_a4b
from mlrun_tpu.models.moe import moe_mlp, shared_expert
from mlrun_tpu.obs import get_tick_log
from mlrun_tpu.ops import mla_attention as mla
from mlrun_tpu.serving import llm
from mlrun_tpu.serving.llm import LatentCacheError, LLMEngine
from mlrun_tpu.serving.llm_batch import ContinuousBatchingEngine
from mlrun_tpu.serving.paged import (
    PagedContinuousBatchingEngine,
    init_paged_pool,
)

from . import xing4_reference as ref

PAGE, MAX_LEN = 16, 128
PROMPTS = [[(7 * i + 3) % 500 + 1 for i in range(n)] for n in (70, 23, 41)]


@pytest.fixture(scope="module")
def model():
    """(config, params) in float32 of bfloat16 values, and the reference's
    fields and weights: the same numbers on both sides."""
    params = init_params(tiny_xing4(), jax.random.PRNGKey(0))
    cfg = tiny_xing4(dtype=jnp.float32)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    fields = ref.fields_of(cfg)
    return cfg, params, fields, ref.make_weights(fields, 0)


def _engine(model, kind=PagedContinuousBatchingEngine, **over):
    cfg, params = model[:2]
    kwargs = dict(max_len=MAX_LEN, slots=2, page_size=PAGE,
                  prefill_buckets=(32, 64, 128), attention_impl="kernel",
                  prefill_chunk=32)
    kwargs.update(over)
    return kind(cfg, params, **kwargs)


def _serve(eng, prompts, n=6):
    eng.start()
    try:
        futures = [eng.submit(p, max_new_tokens=n) for p in prompts]
        return [f.result(timeout=600)[0] for f in futures], eng.stats
    finally:
        eng.stop()


def _gaps(model, prompt, served):
    """How far each served token's logit lies below the reference's best
    at its position, by the reference's full forward over prompt +
    served."""
    _cfg, _params, fields, weights = model
    logits = ref.served_logits(fields, weights, prompt, served,
                               len(prompt) + len(served))
    return ref.gap_below_best(logits, served)


# -- (a) the whole forward ----------------------------------------------------
def test_weights_are_the_references(model):
    cfg, params, _fields, weights = model
    theirs = dict(jax.tree_util.tree_leaves_with_path(weights))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        assert np.array_equal(np.asarray(leaf, np.float32),
                              np.asarray(theirs[path], np.float32)), path


def test_forward_matches_reference(model):
    cfg, params, fields, weights = model
    ids = PROMPTS[0][:48]
    want = np.asarray(ref.forward(fields, weights, ids))
    logits, _cache = llm._forward_with_cache(
        cfg, params, jnp.asarray([ids]), llm.init_kv_cache(cfg, 1, 64),
        all_logits=True)
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=2e-4)


# -- (b) prefill, then decode over the latent pool ----------------------------
@pytest.mark.parametrize("attention_impl", ["kernel", "reference"])
def test_paged_engine_against_reference_logits(model, attention_impl):
    """Chunked prefill into the pool, then decoding through the absorbed
    form over pages: every served token is the reference's best at its
    position, to float32 rounding, under both paged implementations."""
    outs, stats = _serve(_engine(model, attention_impl=attention_impl),
                         PROMPTS)
    assert stats["decode_attn_impl"] == attention_impl
    for prompt, served in zip(PROMPTS, outs):
        assert len(served) == 6
        assert float(_gaps(model, prompt, served).max()) < 1e-3


# -- (c) chunked prefill and a prefix hit -------------------------------------
def test_chunked_prefill_and_prefix_hit_against_unchunked(model):
    whole, _ = _serve(_engine(model, prefill_chunk=0), PROMPTS[:1])
    eng = _engine(model, prefill_chunk=16, prefix_cache=True)
    again = PROMPTS[0][:64] + [9, 8, 7]
    chunked, stats = _serve(eng, [PROMPTS[0]])
    assert stats["prefill_chunks"] == 5         # 70 tokens, 16 a dispatch
    # the second request shares four pages with the first
    eng2 = _engine(model, prefill_chunk=16, prefix_cache=True)
    eng2.start()
    try:
        first = eng2.submit(PROMPTS[0], max_new_tokens=6).result(600)[0]
        hit = eng2.submit(again, max_new_tokens=6).result(600)[0]
        stats2 = eng2.stats
    finally:
        eng2.stop()
    assert stats2["prefix_hits"] == 1
    assert stats2["prefix_cached_tokens"] == 64
    for served in (whole[0], chunked[0], first):
        assert float(_gaps(model, PROMPTS[0], served).max()) < 1e-3
    assert float(_gaps(model, again, hit).max()) < 1e-3


# -- (d) absorbed against expanded, on the same latents -----------------------
def test_absorbed_is_expanded(model):
    cfg = model[0]
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    w_ukv = jax.random.normal(keys[0], (
        cfg.kv_lora_rank, cfg.n_heads * (cfg.nope_dim + cfg.v_dim))) * 0.2
    rows = jax.random.normal(keys[1], (1, 24, cfg.kv_lora_rank
                                       + cfg.rope_dim))
    rows = jnp.pad(rows, ((0, 0), (0, 0),
                          (0, cfg.latent_dim - rows.shape[-1])))
    q = jax.random.normal(keys[2], (1, 24, cfg.n_heads, cfg.head_dim))
    k, v = xing4.expand_latents(cfg, w_ukv, rows[0])
    expanded, _lse = mla._dense_part(q[0], k, v, 0, 0, cfg.softmax_scale)
    visible = jnp.tril(jnp.ones((24, 24), bool))[None]
    o_lat = mla.absorbed_attention(
        xing4.absorb_query(cfg, w_ukv, q), rows, visible,
        rank=cfg.kv_lora_rank, scale=cfg.softmax_scale)
    absorbed = xing4.unfold_values(cfg, w_ukv, o_lat, jnp.float32)
    np.testing.assert_allclose(np.asarray(absorbed[0]),
                               np.asarray(expanded), atol=2e-5)


@pytest.mark.parametrize("start", [0, 32, 48])
def test_expanded_kernel_over_blocks(model, start):
    """``mla_flash`` block by block over the admission's rows (interpret
    mode) is the plain products over all of them, whether the chunk starts
    on a block or inside one."""
    cfg = model[0]
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    w_ukv = jax.random.normal(keys[0], (
        cfg.kv_lora_rank, cfg.n_heads * (cfg.nope_dim + cfg.v_dim))) * 0.2
    cache = jax.random.normal(keys[1], (MAX_LEN, cfg.latent_dim))
    q = jax.random.normal(keys[2], (32, cfg.n_heads, cfg.head_dim))

    def expand(rows):
        return xing4.expand_latents(cfg, w_ukv, rows)

    got = mla.expanded_cached_attention(
        q, cache, jnp.int32(start), expand, v_dim=cfg.v_dim,
        scale=cfg.softmax_scale, impl="flash")
    k, v = expand(cache)
    want, _lse = mla._dense_part(q, k, v, start, 0, cfg.softmax_scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_decode_kernel_is_the_gather_reference(model):
    cfg = model[0]
    keys = jax.random.split(jax.random.PRNGKey(6), 2)
    pool = jax.random.normal(keys[0], (2, 9, PAGE, cfg.latent_dim))
    q = jax.random.normal(keys[1], (3, cfg.n_heads, cfg.latent_dim))
    table = jnp.asarray([[0, 3, 5, -1], [7, 1, -1, -1], [-1, -1, -1, -1]])
    pos = jnp.asarray([40, 16, 0])
    got = mla.mla_paged_decode(q, pool, 1, table, pos, page_size=PAGE,
                               rank=cfg.kv_lora_rank,
                               scale=cfg.softmax_scale, interpret=True)
    dense = mla.gather_latents(pool, 1, table)
    visible = jnp.arange(dense.shape[1])[None, None, :] \
        <= pos[:, None, None]
    want = mla.absorbed_attention(q[:, None], dense, visible,
                                  rank=cfg.kv_lora_rank,
                                  scale=cfg.softmax_scale)[:, 0]
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                               atol=2e-5)


# -- (e) the residual mix, and each planted fault -----------------------------
def test_sinkhorn_is_doubly_stochastic(model):
    cfg, params = model[:2]
    lp = jax.tree_util.tree_map(lambda a: a[0], {
        name: leaf for name, leaf in params["layers"].items()
        if name.startswith("hc_")})
    x = jax.random.normal(jax.random.PRNGKey(7),
                          (2, 5, cfg.hc_mult, cfg.embed_dim))
    pre, post, res = xing4.mixing_coefficients(cfg, x, lp, "attn")
    # rows are normalised last: exact; twenty iterations leave the columns
    # of the least even of these matrices within 2e-4 of 1
    np.testing.assert_allclose(np.asarray(res.sum(-1)), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.sum(-2)), 1.0, atol=5e-4)
    even = xing4.sinkhorn(jnp.zeros((4, 4)).at[0, 1].set(1.5), 20, 1e-6,
                          30.0)
    np.testing.assert_allclose(np.asarray(even.sum(-2)), 1.0, atol=1e-5)
    eye = np.eye(cfg.hc_mult)
    assert float(np.abs(np.asarray(res) - eye).max()) > 0.05
    assert float(np.asarray(pre).std()) > 0.05      # not a uniform read
    assert 0.0 < float(post.min()) and float(post.max()) < 2.0
    want = ref.mixing(model[2], x.reshape(10, cfg.hc_mult, -1), lp, "attn")
    for got, theirs in zip((pre, post, res), want):
        np.testing.assert_allclose(
            np.asarray(got).reshape(theirs.shape), np.asarray(theirs),
            atol=2e-5)


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_planted_fault_is_visible(model, fault):
    """Each fault that the benchmark's readings plant moves the
    reference's logits by far more than the program differs from it."""
    _cfg, _params, fields, weights = model
    ids = PROMPTS[0][:48]
    clean = np.asarray(ref.forward(fields, weights, ids))
    broken = np.asarray(ref.forward(fields, weights, ids, fault=fault))
    assert float(np.abs(broken - clean).max()) > 0.02


# -- (f) the router -----------------------------------------------------------
def _expert_layer(model, layer=0):
    cfg, params = model[:2]
    return cfg, llm.layer_slice(params["layers"], cfg.first_k_dense + layer,
                                cfg.first_k_dense)


def test_router_against_reference(model):
    cfg, lp = _expert_layer(model)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, cfg.embed_dim))
    y, load = moe_mlp(cfg, x, lp, layer=0)
    want = ref.experts_mlp(model[2], x.reshape(24, -1), lp)
    np.testing.assert_allclose(np.asarray(y).reshape(24, -1),
                               np.asarray(want), atol=2e-5)
    assert int(load.sum()) == 24 * cfg.top_k        # no token is dropped
    gates, _ = ref.route(model[2], x.reshape(24, -1), lp)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)),
                               cfg.routed_scale, atol=1e-5)


def test_bias_chooses_and_scores_weigh(model):
    """A bias that lifts expert 5 above every score makes every token
    choose it; its gate is still its score's share, never the bias."""
    cfg, lp = _expert_layer(model)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 16, cfg.embed_dim))
    lifted = dict(lp, router_bias=lp["router_bias"].at[5].set(10.0))
    _y, load = moe_mlp(cfg, x, lifted, layer=0)
    assert int(load[5]) == 16
    gates, experts = ref.route(model[2], x[0], lifted)
    assert bool((experts == 5).any(axis=-1).all())
    assert float(gates.max()) <= cfg.routed_scale
    _y0, load0 = moe_mlp(cfg, x, lp, layer=0)
    assert int(load0[5]) < 16                        # the bias did that


def test_shared_expert_for_every_token(model):
    cfg, lp = _expert_layer(model)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 6, cfg.embed_dim))
    y, _ = moe_mlp(cfg, x, lp, layer=0)
    routed, _ = moe_mlp(cfg, x, {k: v for k, v in lp.items()
                                 if not k.startswith("shared_")}, layer=0)
    shared = shared_expert(x, lp)
    np.testing.assert_allclose(np.asarray(y - routed), np.asarray(shared),
                               atol=2e-5)
    assert float(jnp.abs(shared).min(axis=-1).max()) > 0  # no token skipped


def test_leading_dense_layer_then_expert_layers(model):
    cfg, params = model[:2]
    dense = llm.layer_slice(params["layers"], 0, cfg.first_k_dense)
    expert = llm.layer_slice(params["layers"], 1, cfg.first_k_dense)
    assert "w_gate" in dense and "experts_gate" not in dense
    assert "experts_gate" in expert and "w_gate" not in expert
    assert expert["router"].shape == (cfg.embed_dim, cfg.n_experts)
    assert expert["experts_gate"].shape[0] == cfg.n_moe_layers


# -- (h) the tick's lookahead -------------------------------------------------
class _SyncPaged(PagedContinuousBatchingEngine):
    """One tick built, dispatched, read and committed in one iteration."""

    def _plain_decode_tick(self, active) -> int:
        last, _ = self._tick_inputs(active)
        out = self._decode_paged(
            self.params, jnp.asarray(last), self._pool,
            jnp.array(self._page_table), jnp.array(self._pos))
        self._pool = out[1]
        tokens_host = np.asarray(out[0])
        for i in active:
            slot = self._slot_state[i]
            slot.tokens.append(int(tokens_host[i]))
            slot.remaining -= 1
            self._pos[i] += 1
            if slot.remaining <= 0:
                self._finish(i)
        self._tick.tokens_out = len(active)
        return len(active)


def test_lookahead_streams_are_the_synchronous_ticks(model):
    ahead, stats = _serve(_engine(model), PROMPTS, n=9)
    sync, _ = _serve(_engine(model, _SyncPaged), PROMPTS, n=9)
    assert ahead == sync
    assert stats["lookahead_ticks"] > 0


# -- counters, spans' inputs --------------------------------------------------
def test_ticks_and_prefills_report_expert_load(model):
    cfg = model[0]
    eng = _engine(model)
    outs, stats = _serve(eng, PROMPTS[:1], n=6)
    records = get_tick_log(eng._obs_name).records()
    pairs_a_token = cfg.top_k * cfg.n_moe_layers
    prefills = [r for r in records if r["prefill_tokens"]]
    assert sum(r["prefill_tokens"] for r in prefills) == 70
    # 70 tokens in chunks of 32: each attends its own and what precedes it
    assert sum(r["prefill_ctx_tokens"] for r in prefills) == 70 * 71 // 2
    ticks = [r for r in records if r["rows"]]
    assert all(r["expert_pairs"] >= r["rows"] * pairs_a_token
               for r in ticks)
    # a padded chunk routes its padding too: 3 dispatches of 32
    assert stats["expert_pairs"] == (96 + 5) * pairs_a_token
    assert stats["expert_pairs"] == sum(r["expert_pairs"] for r in records)
    assert 0 < stats["expert_load_max"] <= 32
    assert stats["kv_bytes_per_token"] == \
        cfg.n_layers * cfg.latent_dim * 4


# -- what the latent layout refuses by type -----------------------------------
def test_latent_pool_layout_follows_the_type(model):
    cfg = model[0]
    pool = init_paged_pool(cfg, 5, PAGE)
    assert set(pool) == {"ckr"}
    assert pool["ckr"].shape == (cfg.n_layers, 5, PAGE, cfg.latent_dim)
    cache = llm.init_kv_cache(cfg, 1, 32)
    assert cache["ckr"].shape == (cfg.n_layers, 1, 32, cfg.latent_dim)


@pytest.mark.parametrize("what", ["int8_pool", "int8_cache", "kv_tier",
                                  "speculation", "dense_engine",
                                  "llm_engine", "submit_prefill",
                                  "fetch_prefix"])
def test_refused_by_type(model, what):
    cfg, params = model[:2]
    with pytest.raises(LatentCacheError):
        if what == "int8_pool":
            _engine(model, kv_dtype="int8")
        elif what == "int8_cache":
            llm.init_kv_cache(cfg, 1, 32, kv_dtype="int8")
        elif what == "kv_tier":
            _engine(model, kv_tier=True, prefix_cache=True)
        elif what == "speculation":
            _engine(model, speculative={"enabled": True,
                                        "draft_config": cfg,
                                        "draft_params": params})
        elif what == "dense_engine":
            ContinuousBatchingEngine(cfg, params, max_len=MAX_LEN, slots=2)
        elif what == "llm_engine":
            LLMEngine(cfg, params, max_len=MAX_LEN)
        elif what == "submit_prefill":
            _engine(model).submit_prefill(PROMPTS[1])
        else:
            _engine(model).fetch_prefix(PROMPTS[1])


# -- counts -------------------------------------------------------------------
def test_param_counts():
    whole = xing4_29b_a4b()
    assert round(whole.param_count() / 1e9, 1) == 29.5
    assert round(whole.attention_params() / 1e6, 2) == 28.41
    assert round(whole.mixing_params() / 1e6, 2) == 0.72
    cut = dataclasses.replace(whole, n_layers=7, first_k_dense=1)
    assert round(cut.param_count() * 2 / 1e9, 2) == 11.08
    tiny = tiny_xing4()
    leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: init_params(tiny, jax.random.PRNGKey(0))))
    assert tiny.param_count() == sum(int(np.prod(a.shape)) for a in leaves)
    # four routed and one shared expert a token, not sixty-four
    active = whole.flops_per_token(0) / 6
    assert 3.5e9 < active < 4.5e9


# -- the normal path ----------------------------------------------------------
def test_model_server_serves_the_registered_preset():
    import mlrun_tpu
    from mlrun_tpu.frameworks.jax.auto_trainer import MODEL_PRESETS

    assert MODEL_PRESETS["tiny-xing4"]().latent_cache
    fn = mlrun_tpu.new_function("xing4-graph", kind="serving")
    fn.set_topology("router")
    route = fn.add_model(
        "llm", class_name="mlrun_tpu.serving.llm.LLMModelServer",
        model_preset="tiny-xing4", continuous_batching=True, paged=True,
        page_size=PAGE, slots=2, max_len=MAX_LEN, n_pages=16, warmup=False,
        max_new_tokens=5, prefill_chunk=32, prefix_cache=True,
        attention_impl="kernel")
    server = fn.to_mock_server()
    try:
        body = server.test("/v2/models/llm/infer",
                           body={"inputs": [PROMPTS[0], PROMPTS[1]]})
        stats = route.object.engine.stats
    finally:
        route.object.engine.stop()
    assert [len(t) for t in body["outputs"]] == [5, 5]
    assert stats["prefill_chunks"] >= 4 and stats["lookahead_ticks"] > 0
    assert stats["prefix_queries"] == 2
