"""Multi-token paged prefill kernel + int8 KV pages (docs/serving.md
"Attention kernels"): op-level parity of the merged prefix-in-place
prefill and the int8 decode/prefill kernels, the no-dense-gather
acceptance contract on prefix-hit admissions
(``prefill_gather_admissions`` stays 0 under ``attention_impl=
"kernel"``), int8 end-to-end parity on the paged engine (cold,
prefix-hit, and through a fleet ``KVHandoff``), the ~2x
pages-at-equal-bytes capacity claim, the ``ValueError`` on an unknown
``attention_impl`` at engine construction, and the
``make bench-prefill`` smoke. CPU-only (Pallas interpret mode),
tier-1-fast.

Tolerance contract: the hit path LSE-merges per-layer partial softmax
states (prefix pages via the paged prefill kernel, suffix rows via the
bounded local attention), so its k-block accumulation order differs
from the cold monolithic pass — outputs agree to f32 round-off
(op-level bound 2e-6 on unit-scale data) rather than bit-for-bit, and
greedy token streams agree (asserted). int8 adds the per-vector
symmetric quantization error (|x|_max / 254 per element; op-level
attention-output bound 2e-2 on 0.3-scale data, asserted) — kernel vs
reference on the SAME quantized pool stays at f32 round-off.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.models import init_params, tiny_llama
from mlrun_tpu.ops import paged_attention as pattn
from mlrun_tpu.ops.attention import _repeat_kv, attention_reference
from mlrun_tpu.serving.llm import _quantize_kv
from mlrun_tpu.serving.paged import (
    PagedContinuousBatchingEngine,
    init_paged_pool,
)
from tests.greedy import (
    assert_greedy_equal_up_to_tie,
    greedy_reference,
    record_prefills,
)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama(attention_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_len", 64)
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("page_size", 8)
    eng = PagedContinuousBatchingEngine(cfg, params, **kw)
    eng.start()
    return eng


PROMPT = [1, 7, 3, 9, 2, 4, 6, 8, 5, 3, 1, 2]  # one full block at ps=8


# -- op level -----------------------------------------------------------------
def _prefix_setup(key, n_pages, ps, hkv, d, scale=0.3, layers=1):
    """A pool [layers, n_pages + 1, ...] whose layers all differ."""
    kk, kv = jax.random.split(key)
    shape = (layers, n_pages + 1, ps, hkv, d)
    k_pool = jax.random.normal(kk, shape, jnp.float32) * scale
    v_pool = jax.random.normal(kv, shape, jnp.float32) * scale
    return k_pool, v_pool


PS, PPS, HKV, H, D = 8, 4, 2, 4, 32
BASE = 2 * PS


def _prefill_case(key, s, ids):
    """q [1, S, H, D] at positions BASE.., a local cache whose rows
    BASE..BASE+S-1 alone are live, and the page ids of the prefix."""
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, s, H, D), jnp.float32) * 0.5
    m = 32
    kc, vc = jax.random.split(jax.random.fold_in(key, 2))
    k_loc = jax.random.normal(kc, (1, m, HKV, D), jnp.float32) * 0.3
    v_loc = jax.random.normal(vc, (1, m, HKV, D), jnp.float32) * 0.3
    live = (jnp.arange(m) >= BASE) & (jnp.arange(m) < BASE + s)
    k_loc = k_loc * live[None, :, None, None]
    v_loc = v_loc * live[None, :, None, None]
    page_ids = np.full((PPS,), -1, np.int32)
    page_ids[:2] = ids
    return q, k_loc, v_loc, jnp.asarray(page_ids)


def _merged_prefill(q, k_loc, v_loc, k_pool, v_pool, layer, page_ids,
                    **scales):
    n_rep = H // HKV
    return pattn.paged_prefill_attention(
        q, _repeat_kv(k_loc, n_rep), _repeat_kv(v_loc, n_rep),
        jnp.int32(BASE), k_pool, v_pool, layer, page_ids,
        jnp.int32(BASE), page_size=PS, interpret=True, **scales)


def _dense_prefill(q, k_loc, v_loc, k_pages, v_pages, ids):
    """Plain causal attention over the densely concatenated [prefix;
    suffix] KV; ``k_pages``/``v_pages`` are ONE layer's pages, f32."""
    s = q.shape[1]
    k_pre = jnp.concatenate([k_pages[i] for i in ids], axis=0)[None]
    v_pre = jnp.concatenate([v_pages[i] for i in ids], axis=0)[None]
    k_full = jnp.concatenate([k_pre, k_loc[:, BASE:BASE + s]], axis=1)
    v_full = jnp.concatenate([v_pre, v_loc[:, BASE:BASE + s]], axis=1)
    return attention_reference(q, k_full, v_full, causal=True,
                               positions_q=BASE + jnp.arange(s),
                               positions_k=jnp.arange(BASE + s))


def test_paged_prefill_kernel_matches_dense_reference():
    """Merged prefix-in-place prefill (paged prefill kernel LSE-merged
    with the bounded local flash) vs plain causal attention over the
    densely concatenated [prefix; suffix] KV — the f32 round-off bound
    of the tolerance-parity contract."""
    key = jax.random.PRNGKey(0)
    k_pool, v_pool = _prefix_setup(key, 10, PS, HKV, D)
    q, k_loc, v_loc, page_ids = _prefill_case(key, 6, [3, 7])
    out = _merged_prefill(q, k_loc, v_loc, k_pool, v_pool, 0, page_ids)
    ref = _dense_prefill(q, k_loc, v_loc, k_pool[0], v_pool[0], [3, 7])
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-6


def test_int8_decode_kernel_matches_dequant_reference():
    """int8 decode kernel (in-register per-vector dequant) vs the
    dequant+gather reference on the SAME quantized pool: both read
    identical int8 values, so parity is f32 round-off — the
    quantization bound applies between pools, not between impls."""
    key = jax.random.PRNGKey(0)
    slots, pps, ps, hkv, d, h = 3, 4, 8, 2, 32, 4
    k_pool, v_pool = _prefix_setup(key, 10, ps, hkv, d)
    k8, ks = _quantize_kv(k_pool)
    v8, vs = _quantize_kv(v_pool)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (slots, h, d), jnp.float32) * 0.5
    table = np.full((slots, pps), -1, np.int32)
    table[0, :2] = [3, 7]
    table[1, :4] = [0, 1, 2, 8]
    table[2, :1] = [9]
    pos = jnp.asarray([11, 31, 0], jnp.int32)
    out_k = pattn._paged_decode_call(q, k8, v8, 0, jnp.asarray(table),
                                     pos, ps, k_scale=ks, v_scale=vs,
                                     interpret=True)
    out_r = pattn.paged_decode_reference(q, k8, v8, 0, jnp.asarray(table),
                                         pos, ps, k_scale=ks, v_scale=vs)
    assert float(jnp.max(jnp.abs(out_k - out_r))) < 2e-6
    # and the quantization bound itself vs the native pool: per-element
    # error <= |x|_max/254, attention output within 2e-2 on this data
    out_native = pattn.paged_decode_reference(
        q, k_pool, v_pool, 0, jnp.asarray(table), pos, ps)
    assert float(jnp.max(jnp.abs(out_k - out_native))) < 2e-2


def test_int8_prefill_kernel_matches_dequant_reference():
    """The paged prefill kernel over int8 pages + scales matches the
    dense dequantized reference to f32 round-off."""
    key = jax.random.PRNGKey(4)
    k_pool, v_pool = _prefix_setup(key, 10, PS, HKV, D)
    k8, ks = _quantize_kv(k_pool)
    v8, vs = _quantize_kv(v_pool)
    q, k_loc, v_loc, page_ids = _prefill_case(key, 5, [1, 6])
    out = _merged_prefill(q, k_loc, v_loc, k8, v8, 0, page_ids,
                          k_scale=ks, v_scale=vs)
    kd = k8.astype(jnp.float32) * ks[..., None]
    vd = v8.astype(jnp.float32) * vs[..., None]
    ref = _dense_prefill(q, k_loc, v_loc, kd[0], vd[0], [1, 6])
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-6


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_prefill_kernel_reads_its_layer(kv_dtype, layer):
    """The prefill kernel reaches layer ``layer`` of a three-layer pool
    through its index maps: parity with that layer's pages concatenated
    dense, and far from every other layer's (a wrong layer index must
    fail)."""
    key = jax.random.PRNGKey(11)
    k_pool, v_pool = _prefix_setup(key, 10, PS, HKV, D, layers=3)
    q, k_loc, v_loc, page_ids = _prefill_case(key, 5, [2, 9])
    scales = {}
    if kv_dtype == "int8":
        k_pool, scales["k_scale"] = _quantize_kv(k_pool)
        v_pool, scales["v_scale"] = _quantize_kv(v_pool)
        kd = k_pool.astype(jnp.float32) * scales["k_scale"][..., None]
        vd = v_pool.astype(jnp.float32) * scales["v_scale"][..., None]
    else:
        k_pool = k_pool.astype(jnp.bfloat16)
        v_pool = v_pool.astype(jnp.bfloat16)
        kd, vd = k_pool.astype(jnp.float32), v_pool.astype(jnp.float32)
    out = _merged_prefill(q, k_loc, v_loc, k_pool, v_pool,
                          jnp.int32(layer), page_ids, **scales)
    for other in range(3):
        ref = _dense_prefill(q, k_loc, v_loc, kd[other], vd[other], [2, 9])
        gap = float(jnp.max(jnp.abs(out - ref)))
        assert gap < 2e-6 if other == layer else gap > 0.02


# -- engine level -------------------------------------------------------------
def test_kernel_prefix_hit_never_gathers(setup):
    """ACCEPTANCE: with ``attention_impl="kernel"`` a prefix-hit
    admission runs the in-place merged prefill — no dense gather ever
    (``prefill_gather_admissions`` stays 0), and cold-vs-hit greedy
    outputs agree (the token-level instantiation of the tolerance
    bound)."""
    cfg, params = setup
    eng = _engine(cfg, params, attention_impl="kernel")
    try:
        cold, _ = eng.generate(PROMPT, max_new_tokens=6)
        warm, _ = eng.generate(PROMPT, max_new_tokens=6)
        stats = eng.stats
    finally:
        eng.stop()
    assert stats["prefix_hits"] >= 1
    assert stats["paged_prefill_impl"] == "kernel"
    assert stats["prefill_gather_admissions"] == 0
    assert stats["prefill_kernel_chunks"] > 0
    assert warm == cold
    # the reference arm of the same workload gathers once per hit
    eng = _engine(cfg, params, attention_impl="reference")
    try:
        ref_cold, _ = eng.generate(PROMPT, max_new_tokens=6)
        ref_warm, _ = eng.generate(PROMPT, max_new_tokens=6)
        ref_stats = eng.stats
    finally:
        eng.stop()
    assert ref_stats["paged_prefill_impl"] == "gather"
    assert ref_stats["prefill_gather_admissions"] == 1
    assert ref_stats["prefill_kernel_chunks"] == 0
    # cross-impl parity: kernel and gather arms agree token-for-token
    assert cold == ref_cold and warm == ref_warm


def test_kernel_prefix_chunked_resume_parity(setup):
    """A prefix-hit suffix longer than ``prefill_chunk`` resumes the
    merged kernel dispatch across scheduler ticks (decode ticks
    interleaved) — greedy output still matches the unchunked reference
    engine, and every chunk ran in place (no gather)."""
    cfg, params = setup
    shared = list(range(1, 17))           # 2 full blocks at ps=8
    branch = shared + list(range(40, 52))  # 12-token suffix, chunk=8
    eng = _engine(cfg, params, prefill_buckets=(32,),
                  attention_impl="reference")
    try:
        ref_seed, _ = eng.generate(shared, max_new_tokens=4)
        ref, _ = eng.generate(branch, max_new_tokens=5)
    finally:
        eng.stop()
    eng = _engine(cfg, params, prefill_buckets=(32,),
                  attention_impl="kernel", prefill_chunk=8)
    shapes = record_prefills(eng)
    try:
        seed, _ = eng.generate(shared, max_new_tokens=4)
        out, _ = eng.generate(branch, max_new_tokens=5)
        stats = eng.stats
    finally:
        eng.stop()
    # tolerance-parity contract (module docstring): the merged hit path
    # agrees with the monolithic one to round-off, so greedy streams
    # agree up to an argmax tie at bf16 resolution (this prompt's first
    # token has one: margin 0.0151, under one bf16 step)
    assert seed == ref_seed
    assert_greedy_equal_up_to_tie(cfg, params, branch, out, ref)
    assert stats["prefill_gather_admissions"] == 0
    # 12-token suffix at chunk 8 = two merged chunks; the first token is
    # read from the second at its last real position (no third dispatch)
    assert stats["prefill_kernel_chunks"] == 2
    # the seed's 16 tokens are two chunks more
    assert len(shapes) == stats["prefill_chunks"] == 4


@pytest.mark.parametrize("impl", ["kernel", "reference"])
@pytest.mark.parametrize("chunk", [0, 4], ids=["inline", "chunked"])
@pytest.mark.parametrize("suffix", [7, 8, 9],
                         ids=["below", "at", "one-above"])
def test_prefix_hit_admission_is_one_dispatch(setup, suffix, chunk, impl):
    """Through a prefix hit (16 cached tokens, then a suffix below, at
    and one above the bucket of 8; inline and in chunks of 4; the merged
    kernel path and the gather path): the suffix's first token comes from
    the dispatch that completed it, read at its last real position. No
    one-token dispatch, as many dispatches as ``prefill_chunks``, and
    the greedy stream is the full forward's up to a bf16 tie."""
    cfg, params = setup
    shared = list(range(1, 17))                  # 2 full blocks at ps=8
    branch = shared + [(5 * i + 40) % 97 for i in range(suffix)]
    eng = _engine(cfg, params, prefill_buckets=(8, 16, 32),
                  attention_impl=impl, prefill_chunk=chunk)
    shapes = record_prefills(eng)
    try:
        eng.generate(shared, max_new_tokens=2)
        cold = len(shapes)
        out, _ = eng.generate(branch, max_new_tokens=5)
        stats = eng.stats
    finally:
        eng.stop()
    assert_greedy_equal_up_to_tie(cfg, params, branch, out,
                                  greedy_reference(cfg, params, branch, 5))
    assert stats["prefix_hits"] == 1
    hit = shapes[cold:]
    width = chunk or (8 if suffix <= 8 else 16)
    count = -(-suffix // chunk) if chunk else 1
    assert hit == [((1, width), impl == "kernel")] * count
    assert stats["prefill_chunks"] == len(shapes)
    assert stats["prefill_kernel_chunks"] == \
        (count if impl == "kernel" else 0)
    assert stats["prefill_gather_admissions"] == \
        (0 if impl == "kernel" else 1)


def test_int8_engine_kernel_parity_cold_and_hit(setup):
    """int8 pools run the kernel path end to end: decode resolves to
    the kernel (the old silent downgrade is gone), greedy tokens match
    the int8 reference engine exactly (same quantized values both
    ways), cold and through a prefix hit — and, on this model/prompt,
    the native-pool tokens too (the quantization bound left greedy
    argmaxes untouched)."""
    cfg, params = setup
    outs = {}
    for impl in ("reference", "kernel"):
        eng = _engine(cfg, params, kv_dtype="int8", attention_impl=impl)
        try:
            cold, _ = eng.generate(PROMPT, max_new_tokens=6)
            warm, _ = eng.generate(PROMPT, max_new_tokens=6)
            stats = eng.stats
        finally:
            eng.stop()
        outs[impl] = (cold, warm)
        assert stats["decode_attn_impl"] == impl
        if impl == "kernel":
            assert stats["prefill_gather_admissions"] == 0
            assert stats["attn_gather_ticks"] == 0
            assert stats["attn_kernel_ticks"] > 0
    assert outs["kernel"][0] == outs["reference"][0]
    assert outs["kernel"][1] == outs["kernel"][0]
    eng = _engine(cfg, params, attention_impl="kernel")
    try:
        native, _ = eng.generate(PROMPT, max_new_tokens=6)
    finally:
        eng.stop()
    assert outs["kernel"][0] == native


def test_int8_handoff_parity_and_wire_format(setup):
    """Disaggregated prefill→decode on quantized pools: the KVHandoff
    ships int8 pages + f32 scales (never densified to fp32), decode
    after import matches the single-engine int8 path — cold AND through
    a prefill-side prefix hit (whose prefix rows are assembled from the
    pool pages, not a gather). A dtype-mismatched import fails typed."""
    cfg, params = setup
    pre = _engine(cfg, params, kv_dtype="int8", attention_impl="kernel")
    dec = _engine(cfg, params, kv_dtype="int8", attention_impl="kernel")
    try:
        # the decode engine's own cold generation is the single-engine
        # reference (imported handoffs never touch its prefix cache, so
        # this cannot contaminate the imports below)
        expect, _ = dec.generate(PROMPT, max_new_tokens=6)
        handoff = pre.submit_prefill(PROMPT).result(timeout=300)
        assert handoff.kv_dtype == "int8"
        assert handoff.kv["k"].dtype == np.int8
        assert handoff.kv["k_scale"].dtype == np.float32
        tokens, _ = dec.submit_prefilled(
            handoff, max_new_tokens=6).result(timeout=300)
        assert tokens == expect
        # second prefill = prefix hit on the prefill pool; the handoff
        # payload must still carry the full prompt KV (prefix rows come
        # straight from the shared pool pages)
        hit = pre.submit_prefill(PROMPT).result(timeout=300)
        assert hit.cached_prefix > 0
        assert pre.stats["prefill_gather_admissions"] == 0
        # prefix rows ship straight from the shared pool pages — byte-
        # identical to what the cold admission inserted there
        base = hit.cached_prefix
        np.testing.assert_array_equal(hit.kv["k"][:, :base],
                                      handoff.kv["k"][:, :base])
        np.testing.assert_array_equal(hit.kv["k_scale"][:, :base],
                                      handoff.kv["k_scale"][:, :base])
        # suffix rows were re-prefilled through the merged kernel path;
        # deeper layers' KV sees the merge's f32 round-off, so int8
        # values may flip one quantization step — the tolerance
        # contract: dequantized agreement within 2 steps
        for name in ("k", "v"):
            dq_cold = (handoff.kv[name].astype(np.float32)
                       * handoff.kv[f"{name}_scale"][..., None])
            dq_hit = (hit.kv[name].astype(np.float32)
                      * hit.kv[f"{name}_scale"][..., None])
            atol = 2 * float(handoff.kv[f"{name}_scale"].max())
            assert float(np.abs(dq_cold - dq_hit).max()) <= atol
        tokens_hit, _ = dec.submit_prefilled(
            hit, max_new_tokens=6).result(timeout=300)
        assert tokens_hit == expect
        # typed 400-class rejection on a quantization mismatch
        native = _engine(cfg, params, attention_impl="kernel")
        try:
            with pytest.raises(ValueError, match="dtype mismatch"):
                native.submit_prefilled(hit, max_new_tokens=6)
        finally:
            native.stop()
    finally:
        pre.stop()
        dec.stop()


def test_int8_pool_capacity_doubles_at_equal_bytes():
    """The capacity claim behind the whole int8 prong: at a fixed HBM
    byte budget an int8 pool holds ~2x the resident pages of a native
    bf16 pool (int8 values + f32 per-vector scales vs bf16 values; the
    ratio approaches 2 as head_dim grows — 1.94 at the production
    head_dim 128)."""
    cfg = tiny_llama(head_dim=128)
    page_bytes = {
        dt: sum(a.nbytes for a in init_paged_pool(
            cfg, 1, 128, dt).values())
        for dt in ("native", "int8")}
    ratio = page_bytes["native"] / page_bytes["int8"]
    assert ratio >= 1.8
    budget = 512 * page_bytes["native"]
    pages_native = budget // page_bytes["native"]
    pages_int8 = budget // page_bytes["int8"]
    assert pages_int8 >= 1.8 * pages_native


def test_unknown_impl_engine_raises_at_construction(setup):
    """Engine construction with an ``attention_impl`` no resolver knows
    raises ValueError instead of serving on some silently picked path;
    auto on the CPU constructs on the reference paths."""
    cfg, params = setup
    with pytest.raises(ValueError, match="bogus"):
        PagedContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2, prefill_buckets=(16,),
            page_size=8, kv_dtype="int8", attention_impl="bogus")
    eng = PagedContinuousBatchingEngine(
        cfg, params, max_len=64, slots=2, prefill_buckets=(16,),
        page_size=8, attention_impl="auto")
    assert eng.attn_impl == "reference"
    assert eng.paged_prefill_impl == "gather"


def test_bench_prefill_smoke():
    """`make bench-prefill` stays runnable and its acceptance fields
    hold: zero gather admissions on the kernel arm, parity on both
    arms, and the int8 pool's ~2x page capacity at the fixed byte
    budget."""
    import bench_serve

    result = bench_serve.run_prefill_kernel(
        requests=4, prefix_tokens=48, suffix_tokens=4, max_new=4,
        page_size=16, max_len=128, prefixes=3, requests_per_prefix=3,
        warmup=False)
    pk = result["prefill_kernel"]
    assert pk["gather_admissions_on_kernel_arm"] == 0
    assert pk["kernel"]["cold_vs_hit_parity_ok"]
    assert pk["gather"]["cold_vs_hit_parity_ok"]
    assert pk["kernel"]["prefill_kernel_chunks"] > 0
    assert pk["gather"]["prefill_gather_admissions"] > 0
    assert pk["hbm_bytes_per_hit_admission_gather"] > 0
    i8 = result["int8_pool_bytes"]
    assert i8["capacity_ratio"] >= 1.5  # tiny d=32; 1.94 at d=128
    assert i8["int8"]["n_pages_at_budget"] \
        > i8["native"]["n_pages_at_budget"]
    assert i8["int8"]["prefix_hit_rate"] \
        >= i8["native"]["prefix_hit_rate"]
