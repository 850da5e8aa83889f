"""The Nemotron-H family (models/nemotron_h.py) through the one decoder block
and the paged engine, against its plain reference
(tests/nemotron_h_reference.py, the benchmark's, which runs the state-space
layer as the recurrence itself): the chunked scan, prefill into a slot's
state then decode through pages and state, a slot's second request, the
expert shares, the two kinds of expert through the one ``moe_mlp``, what a
recurrent state refuses by type, and the pool's and the state's layout.
CPU-only (Pallas interpret mode), tiny sizes, float32 where a number is
compared."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.models import (
    init_params,
    nemotron_3_nano_30b_a3b,
    tiny_nemotron_h,
)
from mlrun_tpu.models.llama import layer_slice
from mlrun_tpu.models.moe import moe_mlp, shared_expert
from mlrun_tpu.obs import get_tick_log
from mlrun_tpu.ops import ssm
from mlrun_tpu.serving import llm
from mlrun_tpu.serving.llm import LLMEngine, RecurrentStateError
from mlrun_tpu.serving.llm_batch import ContinuousBatchingEngine
from mlrun_tpu.serving.paged import (
    STATE,
    PagedContinuousBatchingEngine,
    init_paged_pool,
)

from . import nemotron_h_reference as ref
from .test_moe import _sdar_layer, _skewed, _xing4_layer

PAGE, MAX_LEN, NEW = 16, 128, 16
PROMPTS = [[(7 * i + 3) % 500 + 1 for i in range(n)] for n in (70, 23, 41)]


@pytest.fixture(scope="module")
def model():
    """(config, params) in float32 of bfloat16 values, and the reference's
    fields and weights: the same numbers on both sides."""
    params = init_params(tiny_nemotron_h(), jax.random.PRNGKey(0))
    cfg = tiny_nemotron_h(dtype=jnp.float32)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    fields = ref.fields_of(cfg)
    return cfg, params, fields, ref.make_weights(fields, 0)


def _engine(model, kind=PagedContinuousBatchingEngine, **over):
    cfg, params = model[:2]
    kwargs = dict(max_len=MAX_LEN, slots=2, page_size=PAGE,
                  prefill_buckets=(32, 64, 128), attention_impl="kernel")
    kwargs.update(over)
    return kind(cfg, params, **kwargs)


def _serve(eng, prompts, n=NEW, **submit):
    eng.start()
    try:
        futures = [eng.submit(p, max_new_tokens=n, **submit)
                   for p in prompts]
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


def test_weights_are_the_references(model):
    _cfg, params, _fields, weights = model
    theirs = dict(jax.tree_util.tree_leaves_with_path(weights))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        assert np.array_equal(np.asarray(leaf, np.float32),
                              np.asarray(theirs[path], np.float32)), path


# -- (a) the chunked scan is the recurrence ------------------------------------
@pytest.mark.parametrize("length", [32, 48, 37, 5])
def test_chunked_scan_is_the_recurrence(length):
    """Chunk-aligned and ragged lengths, from a state that is not zero; a
    token whose ``dt`` is 0 leaves the state where it was."""
    heads, p, groups, n, chunk = 4, 16, 2, 16, 16
    keys = jax.random.split(jax.random.PRNGKey(length), 6)
    x = jax.random.normal(keys[0], (length, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (length, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (heads,), maxval=2.7))
    b = jax.random.normal(keys[3], (length, groups, n))
    c = jax.random.normal(keys[4], (length, groups, n))
    h0 = jax.random.normal(keys[5], (heads, p, n))
    want_y, want_h = ref.recurrence(x, dt, a, b, c, h0)
    y, h = ssm.ssd_prefill(x, dt, a, b, c, h0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=2e-4)
    # the same prompt in a longer bucket: the padding has dt 0
    pad = lambda v: jnp.concatenate([v, jnp.ones((11,) + v.shape[1:])])
    _, padded_h = ssm.ssd_prefill(
        pad(x), jnp.concatenate([dt, jnp.zeros((11, heads))]), a, pad(b),
        pad(c), h0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(padded_h), np.asarray(want_h),
                               atol=2e-4)


def test_one_token_update_is_the_recurrence_in_place():
    heads, p, groups, n, rows, layers = 4, 16, 2, 16, 3, 2
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    states = jax.random.normal(keys[0], (layers, rows, heads, p, n))
    x = jax.random.normal(keys[1], (rows, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[2], (rows, heads)))
    dt = dt.at[1].set(0.0)                              # a dead row
    a = -jnp.exp(jax.random.uniform(keys[3], (heads,), maxval=2.7))
    b = jax.random.normal(keys[4], (rows, groups, n))
    c = jax.random.normal(keys[5], (rows, groups, n))
    new, y = ssm.ssm_decode(states, 1, x, dt, a, b, c)
    assert np.array_equal(np.asarray(new[0]), np.asarray(states[0]))
    assert np.array_equal(np.asarray(new[1, 1]), np.asarray(states[1, 1]))
    for row in range(rows):
        want_y, want_h = ref.recurrence(
            x[row:row + 1], dt[row:row + 1], a, b[row:row + 1],
            c[row:row + 1], states[1, row])
        np.testing.assert_allclose(np.asarray(y[row]),
                                   np.asarray(want_y[0]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(new[1, row]),
                                   np.asarray(want_h), atol=2e-5)


def test_forward_matches_reference(model):
    cfg, params, fields, weights = model
    ids = PROMPTS[0][:48]
    want = np.asarray(ref.forward(fields, weights, ids))
    logits, _cache = llm._forward_with_cache(
        cfg, params, jnp.asarray([ids]), llm.init_kv_cache(cfg, 1, 64),
        all_logits=True)
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=5e-4)


# -- (b) a padded bucket, a chunked prefill, then decode ----------------------
@pytest.mark.parametrize("prefill", ["bucket", "chunked"])
@pytest.mark.parametrize("attention_impl", ["kernel", "reference"])
def test_paged_engine_against_reference_logits(model, attention_impl,
                                               prefill):
    """A prompt in a padded bucket (70 tokens in 128, 23 in 32, 41 in 64)
    and the same prompts in chunks of 16 (the state carried chunk to
    chunk), then 16 tokens decoded through pages and the slot's state:
    every served token is the reference's best at its position, to float32
    rounding."""
    chunk = {"prefill_chunk": 16} if prefill == "chunked" else {}
    outs, stats = _serve(
        _engine(model, attention_impl=attention_impl, **chunk), PROMPTS)
    assert stats["decode_attn_impl"] == attention_impl
    assert stats["prefill_chunks"] == (10 if chunk else 3)
    for prompt, served in zip(PROMPTS, outs):
        assert len(served) == NEW
        assert float(_gaps(model, prompt, served).max()) < 2e-3


# -- (c) a slot's second request ----------------------------------------------
def test_second_request_of_a_slot_starts_from_its_own_state(model):
    """One slot: the first request ends on its end-of-sequence id, learnt
    a tick late with the next tick in flight for the row; the second is
    admitted into the slot and answers as a fresh engine does."""
    fresh, _ = _serve(_engine(model, slots=1), [PROMPTS[1]])
    first, _ = _serve(_engine(model, slots=1), [PROMPTS[0]])
    eng = _engine(model, slots=1)
    eng.start()
    try:
        a = eng.submit(PROMPTS[0], max_new_tokens=NEW, eos_id=first[0][3])
        b = eng.submit(PROMPTS[1], max_new_tokens=NEW)
        ended, again = a.result(600)[0], b.result(600)[0]
        stats = eng.stats
    finally:
        eng.stop()
    assert ended == first[0][:first[0].index(first[0][3]) + 1]
    assert again == fresh[0]
    assert stats["lookahead_ticks"] > 0
    assert float(_gaps(model, PROMPTS[1], again).max()) < 2e-3


# -- (d) the shares add up -----------------------------------------------------
def _expert_layer(n_experts=16, **over):
    """(config, the first expert layer's parameters with the experts'
    stacks as a layer's own, fields, the reference's weights)."""
    cfg = tiny_nemotron_h(dtype=jnp.float32, n_experts=n_experts, **over)
    params = init_params(cfg, jax.random.PRNGKey(0))
    layer = cfg.pattern.index("E")
    lp = layer_slice(params["layers"], layer, index_of=cfg.leaf_index)
    lp = {name: (value[0] if name.startswith("experts_") else value)
          for name, value in lp.items()}
    fields = ref.fields_of(cfg)
    return cfg, lp, fields


@pytest.mark.parametrize("routing", ["even", "skewed"])
def test_eight_shares_add_up_to_the_uncut_layer(routing):
    """Eight shares of the experts, each drawn alone by its own config
    (``experts_held``), the shared expert counted once: the sum is the
    reference's uncut layer, and the loads are the whole load's parts."""
    cfg, lp, fields = _expert_layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, cfg.embed_dim),
                          jnp.float32)
    if routing == "skewed":
        x, lp = _skewed(cfg, lp)
    whole, load = moe_mlp(cfg, x, lp)
    alike = shared_expert(x, lp)
    width = cfg.n_experts // 8
    total, loads = alike, []
    for lo in range(0, cfg.n_experts, width):
        held = (lo, lo + width)
        share = init_params(dataclasses.replace(cfg, experts_held=held),
                            jax.random.PRNGKey(0))["layers"]
        part = dict(lp, experts_up=share["experts_up"][0],
                    experts_down=share["experts_down"][0])
        assert np.array_equal(np.asarray(part["experts_up"]),
                              np.asarray(lp["experts_up"][lo:lo + width]))
        y, part_load = moe_mlp(cfg, x, part, held=held)
        total = total + (y - alike)
        loads.append(part_load)
    want = ref.experts_mlp(
        fields, x.reshape(-1, cfg.embed_dim),
        {name: (value[None] if name.startswith("experts_") else value)
         for name, value in lp.items()})
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(want).reshape(whole.shape),
                               np.asarray(total), atol=2e-5)
    assert np.array_equal(np.concatenate(loads), np.asarray(load))


# -- (e) two kinds of expert through the one moe_mlp ---------------------------
@pytest.mark.parametrize("routing", ["even", "skewed"])
@pytest.mark.parametrize("family", ["sdar", "xing4", "nemotronh"])
def test_one_expert_layer_for_every_family(family, routing):
    """SwiGLU experts under softmax routing, SwiGLU experts beside a shared
    one under sigmoid routing, and two-product ``relu^2`` experts beside a
    shared one: what the layer's leaves hold decides, and each is its own
    family's reference."""
    if family == "sdar":
        from . import sdar_reference as reference

        cfg, lp = _sdar_layer()
    elif family == "xing4":
        cfg, lp, reference = _xing4_layer()
    else:
        (cfg, lp, _fields), reference = _expert_layer(n_experts=8), ref
    assert ("experts_gate" in lp) == (family != "nemotronh")
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 12, cfg.embed_dim),
                          jnp.float32)
    if routing == "skewed":
        x, lp = _skewed(cfg, lp)
    got, load = moe_mlp(cfg, x, lp)
    stacked = lp if family == "sdar" else {
        name: (value[None] if name.startswith("experts_") else value)
        for name, value in lp.items()}
    want = reference.experts_mlp(reference.fields_of(cfg),
                                 x.reshape(-1, cfg.embed_dim), stacked)
    np.testing.assert_allclose(np.asarray(got).reshape(want.shape),
                               np.asarray(want), atol=2e-5)
    assert int(load.sum()) == 24 * cfg.top_k


def test_misaligned_expert_width_is_held_out_major():
    """An engine's tree holds ``experts_up`` [L, experts, E, width] whose
    width is no whole number of lanes as ``experts_up_t`` [L, experts,
    width, E] (the device would keep the logical one with E minor and copy
    all of it before every grouped product), and the one ``moe_mlp`` reads
    either to the same numbers."""
    cfg, lp, _fields = _expert_layer(n_experts=8, expert_dim=160)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tree = llm.serving_tree(cfg, params)
    assert "experts_up" not in tree["layers"]
    assert tree["layers"]["experts_up_t"].shape == (2, 8, 160, cfg.embed_dim)
    assert np.array_equal(
        np.asarray(tree["layers"]["experts_up_t"]),
        np.asarray(jnp.swapaxes(params["layers"]["experts_up"], 2, 3)))
    assert llm.relaid_bytes(tree) >= tree["layers"]["experts_up_t"].nbytes
    # a width of whole lanes, and the tiny ones under a lane, stay
    for width in (128, 32):
        other = tiny_nemotron_h(expert_dim=width)
        assert "experts_up" in llm.serving_tree(
            other, init_params(other, jax.random.PRNGKey(0)))["layers"]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 12, cfg.embed_dim),
                          jnp.float32)
    want, want_load = moe_mlp(cfg, x, lp)
    relaid = {name: value for name, value in lp.items()
              if name != "experts_up"}
    relaid["experts_up_t"] = jnp.swapaxes(lp["experts_up"], 1, 2)
    got, load = moe_mlp(cfg, x, relaid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.array_equal(np.asarray(load), np.asarray(want_load))


# -- (f) what a recurrent state refuses by type --------------------------------
@pytest.mark.parametrize("what", [
    "prefix_reuse", "speculation", "submit_prefill", "submit_prefilled",
    "fetch_prefix", "import_prefix", "kv_tier", "int8_pool", "int8_cache",
    "dense_engine", "llm_engine", "adapters"])
def test_refused_by_type(model, what):
    cfg, params = model[:2]
    with pytest.raises(RecurrentStateError):
        if what == "prefix_reuse":
            _engine(model, prefix_cache=True)
        elif what == "speculation":
            _engine(model, speculative={"enabled": True,
                                        "draft_config": cfg,
                                        "draft_params": params})
        elif what == "submit_prefill":
            _engine(model).submit_prefill(PROMPTS[1])
        elif what == "submit_prefilled":
            _engine(model).submit_prefilled(None)
        elif what == "fetch_prefix":
            _engine(model).fetch_prefix(PROMPTS[1])
        elif what == "import_prefix":
            _engine(model).import_prefix(None)
        elif what == "kv_tier":
            _engine(model, kv_tier=True)
        elif what == "int8_pool":
            _engine(model, kv_dtype="int8")
        elif what == "int8_cache":
            llm.init_kv_cache(cfg, 1, 32, kv_dtype="int8")
        elif what == "dense_engine":
            ContinuousBatchingEngine(cfg, params, max_len=MAX_LEN, slots=2)
        elif what == "llm_engine":
            LLMEngine(cfg, params, max_len=MAX_LEN)
        else:
            _engine(model, adapters={})


def test_prefix_lookup_finds_nothing_by_construction(model):
    """The default asks for a prefix cache where the configuration has one
    on; a recurrent family's engine builds no index to look a prompt up
    in, so the same prompt twice is prefilled twice."""
    eng = _engine(model)
    assert eng._prefix is None
    outs, stats = _serve(eng, [PROMPTS[1], PROMPTS[1]], n=4)
    assert outs[0] == outs[1]
    assert "prefix_hits" not in stats and stats["prefill_chunks"] == 2


# -- (g) the pool's layers and the state's -------------------------------------
def test_pool_and_state_layout_follow_the_pattern(model):
    cfg = model[0]
    pool = init_paged_pool(cfg, 5, PAGE, slots=3)
    assert set(pool) == {"k", "v", STATE}
    assert pool["k"].shape == (1, 5, PAGE, cfg.n_kv_heads, cfg.head_dim)
    assert pool[STATE]["ssm"].shape == (2, 3, cfg.ssm_heads,
                                        cfg.ssm_head_dim, cfg.ssm_state)
    assert pool[STATE]["ssm"].dtype == jnp.float32
    assert pool[STATE]["conv"].shape == (2, 3, cfg.conv_kernel - 1,
                                         cfg.conv_dim)
    cache = llm.init_kv_cache(cfg, 1, 32)
    assert cache["k"].shape[:3] == (1, 1, 32)
    assert cache["ssm"].shape[:2] == (2, 1)
    eng = _engine(model)
    per_slot = 2 * (4 * 16 * 16 * 4 + 3 * cfg.conv_dim * 4)
    assert eng.stats["state_bytes_per_slot"] == per_slot
    assert eng.stats["kv_bytes_per_token"] == \
        1 * 2 * cfg.n_kv_heads * cfg.head_dim * 4
    # a family without a recurrent state keeps none, and its pool is as it
    # was
    from mlrun_tpu.models import tiny_llama

    assert set(init_paged_pool(tiny_llama(), 5, PAGE, slots=3)) == {"k", "v"}


def test_published_cut_keeps_three_layers_of_pages_and_twelve_of_state():
    """The benchmark's cut (the pattern's first 26 characters, 16 experts
    held, a vocabulary of 16,384) at the published widths, by shapes
    alone."""
    whole = nemotron_3_nano_30b_a3b()
    cut = dataclasses.replace(whole, n_layers=26, pattern=whole.pattern[:26],
                              experts_held=(0, 16), vocab_size=16384)
    assert (cut.kind_layers("ssm"), cut.kind_layers("mlp"),
            cut.cache_layers) == (12, 11, 3)
    pool = jax.eval_shape(lambda: init_paged_pool(cut, 1281, 128, slots=128))
    assert pool["k"].shape == (3, 1281, 128, 2, 128)
    assert pool[STATE]["ssm"].shape == (12, 128, 64, 64, 128)
    assert pool[STATE]["conv"].shape == (12, 128, 3, 6144)
    per_slot = sum(int(np.prod(a.shape[2:])) * a.dtype.itemsize * a.shape[0]
                   for a in pool[STATE].values())
    assert per_slot == 25_608_192
    assert round(whole.param_count() / 1e9, 2) == 31.58
    assert round(whole.ssm_params() / 1e6, 2) == 38.74
    assert round(whole.attention_params() / 1e6, 2) == 23.40
    assert round(whole.expert_params() / 1e6, 2) == 9.98
    assert round(cut.param_count() * 2 / 1e9, 2) == 5.21
    tiny = tiny_nemotron_h()
    leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: init_params(tiny, jax.random.PRNGKey(0))))
    assert tiny.param_count() == sum(int(np.prod(a.shape)) for a in leaves)
    # six routed experts and the shared one a token, not 128
    assert 3.0e9 < whole.flops_per_token(0) / 6 < 3.6e9


# -- counters ------------------------------------------------------------------
def test_ticks_and_prefills_report_state_and_expert_load(model):
    cfg = model[0]
    eng = _engine(model)
    _outs, stats = _serve(eng, PROMPTS[:1], n=6)
    records = get_tick_log(eng._obs_name).records()
    prefills = [r for r in records if r["prefill_tokens"]]
    # 70 tokens in a bucket of 128: the scan integrated the prompt's own
    assert sum(r["prefill_tokens"] for r in prefills) == 70
    assert sum(r["state_tokens"] for r in prefills) == 70
    ticks = [r for r in records if r["rows"]]
    assert ticks and all(r["state_rows"] == r["rows"] == 1 for r in ticks)
    pairs_a_token = cfg.top_k * cfg.kind_layers("mlp")
    assert stats["expert_pairs"] == (128 + 5) * pairs_a_token
    assert stats["expert_pairs"] == sum(r["expert_pairs"] for r in records)


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_planted_fault_is_visible(model, fault):
    """Each fault the reference can plant moves the logits it gives a
    served sequence."""
    _cfg, _params, fields, weights = model
    prompt, served = PROMPTS[1], PROMPTS[2][:8]
    clean = np.asarray(ref.served_logits(fields, weights, prompt, served,
                                         64, buckets=(32,)))
    broken = np.asarray(ref.served_logits(fields, weights, prompt, served,
                                          64, fault=fault, buckets=(32,)))
    assert np.abs(clean - broken).max() > 1e-3


# -- the normal path -----------------------------------------------------------
def test_model_server_serves_the_registered_preset():
    import mlrun_tpu
    from mlrun_tpu.frameworks.jax.auto_trainer import MODEL_PRESETS

    assert MODEL_PRESETS["tiny-nemotron-h"]().recurrent_state
    fn = mlrun_tpu.new_function("nemotron-graph", kind="serving")
    fn.set_topology("router")
    route = fn.add_model(
        "llm", class_name="mlrun_tpu.serving.llm.LLMModelServer",
        model_preset="tiny-nemotron-h", continuous_batching=True, paged=True,
        page_size=PAGE, slots=2, max_len=MAX_LEN, n_pages=16, warmup=False,
        max_new_tokens=5, attention_impl="kernel")
    server = fn.to_mock_server()
    try:
        body = server.test("/v2/models/llm/infer",
                           body={"inputs": [PROMPTS[0], PROMPTS[1]]})
        stats = route.object.engine.stats
    finally:
        route.object.engine.stop()
    assert [len(t) for t in body["outputs"]] == [5, 5]
    assert stats["lookahead_ticks"] > 0
    assert stats["state_bytes_per_slot"] > 0
