"""The Laguna family (models/laguna.py) through the one decoder block and the
paged engine, against its plain reference (tests/laguna_reference.py, the
benchmark's): full layers on pages beside window layers on a ring of
constant size a slot, each kind with its own heads and rope, the window
wrapped in prefill chunks and in decode; the two window kernels against
plain masked attention; what a window cache refuses by type; the cache's
layout and byte counts; and the other families' programs, which this
family must leave as they were. CPU-only (Pallas interpret mode), tiny
sizes (a window of 16 over pages of 8, prompts of 40 to 100), float32
where a number is compared."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.models import init_params, laguna_xs2, tiny_laguna
from mlrun_tpu.models.llama import layer_slice
from mlrun_tpu.obs import get_tick_log
from mlrun_tpu.ops import window_attention as wattn
from mlrun_tpu.serving import llm
from mlrun_tpu.serving.llm import LLMEngine, WindowCacheError
from mlrun_tpu.serving.llm_batch import ContinuousBatchingEngine
from mlrun_tpu.serving.paged import (
    STATE,
    PagedContinuousBatchingEngine,
    init_paged_pool,
)
from mlrun_tpu.serving.window import window_pairs

from . import laguna_reference as ref

PAGE, MAX_LEN, NEW, WINDOW = 8, 128, 12, 16
PROMPTS = [[(7 * i + 3) % 500 + 1 for i in range(n)] for n in (100, 40, 67)]
# a float32 model of five layers against the float32 reference: the two sum
# in another order (blocks of the flash kernels, the grouped expert
# products, the window kernel's online softmax), which moves a logit by
# 1e-5 (measured); a planted fault moves one by 1 or more
LOGIT_ATOL = 5e-4
# a served token's logit below the reference's best at its position: 0 when
# it is the reference's own choice; an argmax that two float32 sums of
# nearly equal logits could order either way stays under this
GAP_LIMIT = 2e-3


@pytest.fixture(scope="module")
def model():
    """(config, params) in float32 of bfloat16 values, and the reference's
    fields and weights: the same numbers on both sides."""
    params = init_params(tiny_laguna(), jax.random.PRNGKey(0))
    cfg = tiny_laguna(dtype=jnp.float32)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    fields = ref.fields_of(cfg)
    return cfg, params, fields, ref.make_weights(fields, 0)


def _engine(model, kind=PagedContinuousBatchingEngine, **over):
    cfg, params = model[:2]
    kwargs = dict(max_len=MAX_LEN, slots=2, page_size=PAGE,
                  prefill_buckets=(32, 64, 128), attention_impl="kernel")
    kwargs.update(over)
    return kind(cfg, params, **kwargs)


def _serve(eng, prompts, n=NEW):
    eng.start()
    try:
        futures = [eng.submit(p, max_new_tokens=n) for p in prompts]
        return [f.result(timeout=600)[0] for f in futures], eng.stats
    finally:
        eng.stop()


def _gaps(model, prompt, served):
    _cfg, _params, fields, weights = model
    logits = ref.served_logits(fields, weights, prompt, served,
                               len(prompt) + len(served))
    return ref.gap_below_best(logits, served)


def _chunked_logits(model, ids, chunk: int, impl: str):
    """The program's logits at every position of ``ids``, prefilled in
    chunks of ``chunk`` (the last one padded) through the admission's
    staging cache, as the engine's prefill dispatches them: the ring of
    each window layer carried from chunk to chunk."""
    cfg, params = model[:2]
    cache = llm.init_kv_cache(cfg, 1, MAX_LEN)
    out, start = [], 0
    while start < len(ids):
        take = min(chunk, len(ids) - start)
        tokens = np.zeros((1, chunk), np.int32)
        tokens[0, :take] = ids[start:start + take]
        cache["pos"] = jnp.full((1,), start, jnp.int32)
        logits, cache = llm._forward_with_cache(
            cfg, params, jnp.asarray(tokens), cache, all_logits=True,
            attn_impl=impl, logits_at=np.int32(take - 1))
        out.append(np.asarray(logits[0, :take]))
        start += take
    return np.concatenate(out), cache


def test_weights_are_the_references(model):
    _cfg, params, _fields, weights = model
    theirs = dict(jax.tree_util.tree_leaves_with_path(weights))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        assert np.array_equal(np.asarray(leaf, np.float32),
                              np.asarray(theirs[path], np.float32)), path


def test_a_layer_gets_its_kinds_leaves(model):
    """A window layer's slice holds the window kind's projections (8 query
    heads here) under the plain names, a full layer's the full kind's (6);
    the dense MLP leads, experts follow."""
    cfg, params = model[:2]
    full = layer_slice(params["layers"], 0, index_of=cfg.leaf_index)
    window = layer_slice(params["layers"], 2, index_of=cfg.leaf_index)
    assert full["wq"].shape == (64, 6 * 16) and full["wo"].shape == (96, 64)
    assert window["wq"].shape == (64, 8 * 16)
    assert window["wo"].shape == (128, 64)
    assert "w_gate" in full and "experts_down" not in full
    assert "router" in window and "w_gate" not in window
    assert [cfg.cache_index(i) for i in range(cfg.n_layers)] == [
        ("full", 0), ("window", 0), ("window", 1), ("window", 2),
        ("full", 1)]


# -- (a) logits through the chunked prefill, every fault fails ---------------
@pytest.mark.parametrize("impl", ["flash", "dense"])
@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_prefill_logits_match_reference(model, chunk, impl):
    """Chunks of the window's length, of twice it (each chunk wraps the
    ring) and one bucket for the whole sequence; the kernels (interpret
    mode) and the reference path."""
    _cfg, _params, fields, weights = model
    ids = PROMPTS[0]
    want = np.asarray(ref.forward(fields, weights, ids))
    got, _ = _chunked_logits(model, ids, chunk, impl)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL)


@pytest.fixture(scope="module")
def program_logits(model):
    return _chunked_logits(model, PROMPTS[0], 32, "flash")[0]


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_planted_fault_fails_the_logits(model, program_logits, fault):
    """Each fault the reference can plant (the window off or one longer,
    YaRN on the window layers, all dims rotated in the full ones, no
    attention factor, no shared expert, softmax scores) puts the program's
    logits outside the tolerance."""
    _cfg, _params, fields, weights = model
    broken = np.asarray(ref.forward(fields, weights, PROMPTS[0],
                                    fault=fault))
    assert np.abs(program_logits - broken).max() > 20 * LOGIT_ATOL


# -- (b) prefill, then decode through pages and rings ------------------------
@pytest.mark.parametrize("prefill", ["bucket", "chunked"])
@pytest.mark.parametrize("attention_impl", ["kernel", "reference"])
def test_paged_engine_against_reference_logits(model, attention_impl,
                                               prefill):
    """Prompts of 100, 40 and 67 tokens in a bucket or in chunks of 32,
    then 12 tokens decoded over pages (full layers) and rings of 16 (window
    layers): every served token is the reference's best at its position."""
    chunk = {"prefill_chunk": 32} if prefill == "chunked" else {}
    outs, stats = _serve(
        _engine(model, attention_impl=attention_impl, **chunk), PROMPTS)
    assert stats["decode_attn_impl"] == attention_impl
    assert stats["prefill_chunks"] == (9 if chunk else 3)
    for prompt, served in zip(PROMPTS, outs):
        assert len(served) == NEW
        assert float(_gaps(model, prompt, served).max()) < GAP_LIMIT


def test_second_request_of_a_slot_starts_from_its_own_ring(model):
    """One slot: the second request's ring is its own prompt's, whatever
    the first left in it."""
    fresh, _ = _serve(_engine(model, slots=1), [PROMPTS[1]])
    again, _ = _serve(_engine(model, slots=1), [PROMPTS[0], PROMPTS[1]])
    assert again[1] == fresh[0]


# -- (c) the window kernels against plain masked attention -------------------
def _plain(q, keys, values, q_pos, k_pos, width):
    """Softmax of q [T, H, D] over keys [K, Hkv, D] at positions ``k_pos``
    for queries at ``q_pos``: causal and inside the window, nothing else."""
    n_rep = q.shape[1] // keys.shape[1]
    k = jnp.repeat(keys, n_rep, axis=1)
    v = jnp.repeat(values, n_rep, axis=1)
    logits = jnp.einsum("thd,khd->thk", q, k) * q.shape[-1] ** -0.5
    seen = (k_pos[None, :] <= q_pos[:, None]) \
        & (q_pos[:, None] - k_pos[None, :] < width)
    logits = jnp.where(seen[:, None, :], logits, -jnp.inf)
    return jnp.einsum("thk,khd->thd", jax.nn.softmax(logits, -1), v)


@pytest.mark.parametrize("start,n_real", [(0, 32), (0, 21), (32, 32),
                                          (48, 7), (8, 32)])
@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_window_prefill_is_plain_masked_attention(impl, start, n_real):
    """A chunk of 32 over a ring of 16 at a start that is and is not a
    multiple of the ring, with padding behind the real tokens: the output
    at every real position and the ring after the last real one."""
    width, chunk, heads, hkv, d = WINDOW, 32, 8, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(start + n_real), 3)
    seq_k = jax.random.normal(keys[0], (start + chunk, hkv, d))
    seq_v = jax.random.normal(keys[1], (start + chunk, hkv, d))
    q = jax.random.normal(keys[2], (chunk, heads, d))
    ring_k = jnp.zeros((width, hkv, d))
    ring_v = jnp.zeros((width, hkv, d))
    for p in range(max(0, start - width), start):
        ring_k = ring_k.at[p % width].set(seq_k[p])
        ring_v = ring_v.at[p % width].set(seq_v[p])
    out, new_k, new_v = wattn.window_prefill(
        q, seq_k[start:], seq_v[start:], ring_k, ring_v, jnp.int32(start),
        jnp.int32(n_real), impl=impl, interpret=True)
    positions = jnp.arange(start + chunk)
    want = _plain(q, seq_k, seq_v, start + jnp.arange(chunk), positions,
                  width)
    np.testing.assert_allclose(np.asarray(out[:n_real]),
                               np.asarray(want[:n_real]), atol=2e-5)
    end = start + n_real
    for p in range(max(0, end - width), end):
        assert np.array_equal(np.asarray(new_k[p % width]),
                              np.asarray(seq_k[p]))
        assert np.array_equal(np.asarray(new_v[p % width]),
                              np.asarray(seq_v[p]))


@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_window_decode_is_plain_masked_attention(impl):
    """Rows whose context is shorter than the ring, as long, and longer
    (the ring wrapped), on the second of two layers of rings."""
    width, heads, hkv, d = WINDOW, 8, 2, 16
    pos = jnp.asarray([3, 15, 16, 40], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    seq_k = jax.random.normal(keys[0], (4, 41, hkv, d))
    seq_v = jax.random.normal(keys[1], (4, 41, hkv, d))
    q = jax.random.normal(keys[2], (4, heads, d))
    rings_k = jnp.full((2, 4, width, hkv, d), 7.0)  # what no row reads
    rings_v = jnp.full((2, 4, width, hkv, d), 7.0)
    for row, last in enumerate(np.asarray(pos)):
        for p in range(max(0, last - width + 1), last + 1):
            rings_k = rings_k.at[1, row, p % width].set(seq_k[row, p])
            rings_v = rings_v.at[1, row, p % width].set(seq_v[row, p])
    out = wattn.window_decode(q, rings_k, rings_v, jnp.int32(1), pos,
                              impl=impl, interpret=True)
    for row, last in enumerate(np.asarray(pos)):
        want = _plain(q[row:row + 1], seq_k[row, :last + 1],
                      seq_v[row, :last + 1], jnp.asarray([last]),
                      jnp.arange(last + 1), width)
        np.testing.assert_allclose(np.asarray(out[row]),
                                   np.asarray(want[0]), atol=2e-5)


def test_partial_rotation_leaves_the_rest(model):
    """Tables of 4 pairs over heads of 16: the leading 8 dims rotate as a
    head of 8 would, the other 8 stay."""
    from mlrun_tpu.ops.rotary import apply_rope, rope_table

    x = jax.random.normal(jax.random.PRNGKey(3), (5, 2, 16))
    cos, sin = rope_table(jnp.arange(5), 8, 10000.0)
    got = apply_rope(x, cos, sin)
    np.testing.assert_allclose(np.asarray(got[..., :8]),
                               np.asarray(apply_rope(x[..., :8], cos, sin)),
                               atol=1e-6)
    assert np.array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))


# -- (d) what a window cache refuses by type ----------------------------------
@pytest.mark.parametrize("what", [
    "prefix_reuse", "speculation", "submit_prefill", "submit_prefilled",
    "fetch_prefix", "import_prefix", "kv_tier", "int8_pool", "int8_cache",
    "dense_engine", "llm_engine", "adapters"])
def test_refused_by_type(model, what):
    cfg, params = model[:2]
    with pytest.raises(WindowCacheError):
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


def test_no_prefix_index_is_built(model):
    eng = _engine(model)
    assert eng._prefix is None
    outs, stats = _serve(eng, [PROMPTS[1], PROMPTS[1]], n=4)
    assert outs[0] == outs[1]
    assert "prefix_hits" not in stats and stats["prefill_chunks"] == 2


# -- (e) the cache's layout and its bytes -------------------------------------
@pytest.mark.parametrize("length", [20, 100])
def test_window_bytes_do_not_grow_with_the_context(model, length):
    """A slot's window cache is three rings of 16 whatever the context: the
    pool's rings and the stat read the same after a prompt of 20 as after
    one of 100."""
    cfg = model[0]
    eng = _engine(model, slots=1)
    prompt = [(5 * i + 1) % 500 + 1 for i in range(length)]
    _serve(eng, [prompt], n=4)
    rings = 2 * cfg.state_layers * WINDOW * cfg.n_kv_heads * cfg.head_dim * 4
    assert eng.stats["window_bytes_per_slot"] == rings
    assert eng.stats["state_bytes_per_slot"] == 0      # reported once
    assert sum(a.nbytes for a in eng._pool[STATE].values()) == rings
    assert eng.stats["kv_bytes_per_token"] == \
        cfg.cache_layers * 2 * cfg.n_kv_heads * cfg.head_dim * 4


def test_pool_and_rings_follow_the_layer_kinds(model):
    cfg = model[0]
    pool = init_paged_pool(cfg, 5, PAGE, slots=3)
    assert set(pool) == {"k", "v", STATE}
    assert pool["k"].shape == (2, 5, PAGE, cfg.n_kv_heads, cfg.head_dim)
    assert {k: v.shape for k, v in pool[STATE].items()} == {
        name: (3, 3, WINDOW, cfg.n_kv_heads, cfg.head_dim)
        for name in ("window_k", "window_v")}
    cache = llm.init_kv_cache(cfg, 1, 32)
    assert cache["k"].shape[:3] == (2, 1, 32)
    assert cache["window_k"].shape[:3] == (3, 1, WINDOW)


def test_published_cut_by_shapes():
    """The benchmark's cut (layers 0-6 of 40) at the published widths, by
    shapes alone: the pages of two full layers, the rings of five window
    layers of 10,485,760 B a slot, 8,192 B of pages a token, and the
    parameter counts of PERF.md section 4."""
    whole = laguna_xs2()
    cut = dataclasses.replace(
        whole, n_layers=7, layer_types=whole.layer_types[:7],
        mlp_layer_types=whole.mlp_layer_types[:7],
        heads_per_layer=whole.heads_per_layer[:7])
    assert (cut.cache_layers, cut.state_layers, cut.first_k_dense) == (2, 5, 1)
    pool = jax.eval_shape(lambda: init_paged_pool(cut, 2049, 128, slots=32))
    assert pool["k"].shape == (2, 2049, 128, 8, 128)
    assert pool[STATE]["window_k"].shape == (5, 32, 512, 8, 128)
    per_slot = sum(a.size // 32 * a.dtype.itemsize
                   for a in pool[STATE].values())
    assert per_slot == 10_485_760
    per_token = sum(a.size // (2049 * 128) * a.dtype.itemsize
                    for k, a in pool.items() if k != STATE)
    assert per_token == 8192
    assert round(whole.param_count() / 1e9, 2) == 33.44
    assert round(cut.param_count() / 1e9, 3) == 5.563
    assert round(whole.attention_params("window") / 1e6, 2) == 37.75
    assert round(whole.expert_layer_params(256) / 1e6, 1) == 809.0
    tiny = tiny_laguna()
    leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: init_params(tiny, jax.random.PRNGKey(0))))
    assert tiny.param_count() == sum(int(np.prod(a.shape)) for a in leaves)


# -- (f) counters -------------------------------------------------------------
def test_ticks_and_chunks_report_window_work_and_expert_load(model):
    cfg = model[0]
    eng = _engine(model, prefill_chunk=32)
    _outs, stats = _serve(eng, PROMPTS[:1], n=6)
    records = get_tick_log(eng._obs_name).records()
    # 100 tokens: 15 see fewer than 16 keys, 85 see 16
    assert sum(r["window_prefill_pairs"] for r in records) == \
        window_pairs(0, 100, WINDOW) == 120 + 85 * 16
    ticks = [r for r in records if r["rows"]]
    # a tick's row at position p read min(p + 1, 16) keys a window layer
    assert all(r["window_keys"] == 16 * r["rows"] for r in ticks)
    # 4 chunks of 32 (the last padded) and 5 ticks: top_k a token a layer
    pairs_a_token = cfg.top_k * (cfg.n_layers - cfg.first_k_dense)
    assert stats["expert_pairs"] == (4 * 32 + 5) * pairs_a_token
    assert stats["expert_pairs"] == sum(r["expert_pairs"] for r in records)


# -- (g) the other families' programs are as they were ------------------------
# sha256 of each program's StableHLO text at the commit before the window
# family (tiny configs; the kernels under the interpreter, as on the CPU)
PROGRAMS_BEFORE = {
    "llama.kernel.decode":
        "6c0da188cff98c2d3604528b7da80217c14ec7b9635195cbceda1dbaa38ace08",
    "llama.kernel.prefill":
        "d5c25b5cd992465b07130d4143c8dd1d46a9d5fe7f90b6d773cd4d06d76dc005",
    "llama.reference.decode":
        "e1c405418bc6a83957d076e2bd30c9f7e4968ff0e5c87bcdcfebfe30830e0e01",
    "llama.reference.prefill":
        "9595cdcbbe884af6b08d7b69d1582f08072435476a9ab695b33a1078a238a957",
    "nemotronh.kernel.decode":
        "8fb37bf91ee971f91d8a3331ec1a1aab86a386b0fc7f2feb567ca727038148a0",
    "nemotronh.kernel.prefill":
        "1c797a441a50232969d5b265c07686f6df121a96cec0a6f5a10a40237a88e71f",
    "nemotronh.reference.decode":
        "1404553fbeb7ff8c1d33860cd5c5f7a86a4b68935e7488e00305b0a8375a33ab",
    "nemotronh.reference.prefill":
        "2821112cee1694c3e113c04676b1e30e8b208381f3fd1b5a8c6f4a1bcd3fe8a2",
    "sdar.kernel.denoise":
        "93e0f62289d201ac58af123724c97db66604d87c99d22ac3340319ec0f7e2e25",
    "sdar.kernel.prefill":
        "070aacdc2654f954c2a823da2bb5d07133cc1425052a4c91a3086d3655381648",
    "sdar.reference.denoise":
        "80b08b236c08ee949ceb907b5c748dd1b874c019ea3aedcedc5fb16ce58ec17d",
    "sdar.reference.prefill":
        "92c191ea589b7d9c43678b5f2df3dd112f8a450fba7a2e7f4ebd45d382623c2a",
    "xing4.kernel.decode":
        "5890b50a9d46136154f2305e97b4e7ef9502bec92f486c9595be4f073be6c6aa",
    "xing4.kernel.prefill":
        "6a437d8e31c12f41f8748f868d08077652e884965fda22e6f28131c807fa03d9",
    "xing4.reference.decode":
        "8f1153aa533edb762fdf797063678b63345a57525f11ae4fca804c22339eb159",
    "xing4.reference.prefill":
        "44d11fe80a50c2819bdb97865e3f535b7e2a73f567a7015f42a72da9979a20c6",
}


def _lowered(family: str, impl: str) -> dict:
    """A paged engine's programs of the family's tiny config, lowered as
    the engine dispatches them: the prefill at a bucket of 16, and the
    plain decode tick or, for a block model, the pass."""
    from mlrun_tpu.models import (tiny_llama, tiny_nemotron_h, tiny_sdar,
                                  tiny_xing4)

    cfg = {"llama": tiny_llama, "sdar": tiny_sdar, "xing4": tiny_xing4,
           "nemotronh": tiny_nemotron_h}[family]()
    eng = PagedContinuousBatchingEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)), max_len=32, slots=2,
        page_size=16, prefill_buckets=(16,), attention_impl=impl,
        prefix_cache=False)
    table = jnp.full((2, 2), -1, jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    lowered = {"prefill": eng._prefill.lower(
        eng.params, jnp.zeros((1, 16), jnp.int32),
        llm.init_kv_cache(cfg, 1, 32), logits_at=np.int32(15))}
    if cfg.block_length > 1:
        shape = (2, 2 * cfg.block_length)
        lowered["denoise"] = eng._denoise_paged.lower(
            eng.params, jnp.zeros(shape, jnp.int32), eng._pool, table, pos,
            masked=jnp.ones(shape, bool), count=jnp.zeros((2,), jnp.int32),
            prev_ids=eng._no_block[0], prev_masked=eng._no_block[1],
            from_prev=jnp.zeros((2,), bool))
    else:
        lowered["decode"] = eng._decode_paged.lower(
            eng.params, jnp.zeros((2, 1), jnp.int32), eng._pool, table, pos,
            prev_token=eng._no_tokens, from_prev=jnp.zeros((2,), bool))
    return lowered


@pytest.mark.parametrize("impl", ["kernel", "reference"])
@pytest.mark.parametrize("family", ["llama", "sdar", "xing4", "nemotronh"])
def test_other_families_programs_are_unchanged(family, impl):
    """The dense, block, latent and state-space families' prefill and decode
    (or pass) programs lower to the StableHLO they lowered to before the
    window family's seams (``cache_index``, ``layer_rope``, a kind's leaves,
    heads by layer, a partial rotation, an optional selection bias)."""
    for name, low in _lowered(family, impl).items():
        digest = hashlib.sha256(low.as_text().encode()).hexdigest()
        assert digest == PROGRAMS_BEFORE[f"{family}.{impl}.{name}"], name


# -- the normal path -----------------------------------------------------------
def test_model_server_serves_the_registered_preset():
    import mlrun_tpu
    from mlrun_tpu.frameworks.jax.auto_trainer import MODEL_PRESETS

    assert MODEL_PRESETS["tiny-laguna"]().window_cache
    fn = mlrun_tpu.new_function("laguna-graph", kind="serving")
    fn.set_topology("router")
    route = fn.add_model(
        "llm", class_name="mlrun_tpu.serving.llm.LLMModelServer",
        model_preset="tiny-laguna", continuous_batching=True, paged=True,
        page_size=PAGE, slots=2, max_len=MAX_LEN, n_pages=32, warmup=False,
        max_new_tokens=5, attention_impl="kernel", prefill_chunk=32)
    server = fn.to_mock_server()
    try:
        body = server.test("/v2/models/llm/infer",
                           body={"inputs": [PROMPTS[0], PROMPTS[1]]})
        stats = route.object.engine.stats
    finally:
        route.object.engine.stop()
    assert [len(t) for t in body["outputs"]] == [5, 5]
    assert stats["lookahead_ticks"] > 0
    assert stats["window_bytes_per_slot"] > 0
