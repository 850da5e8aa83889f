"""Paged-decode & flash attention kernels in the serving/training hot
paths (ops/paged_attention.py + ops/attention.py): kernel vs gather+dense
parity, engine greedy token-equality with the kernel on, engine-cold vs
prefix-hit bit-equality, read-only shared pages under ``llm.prefix_evict``
chaos, a seq-2048 interpret smoke, the CPU dispatcher default (reference
unless interpret mode is forced), and the flash block-size clamp.
CPU-only (pallas interpret mode), tier-1-fast."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.chaos import FaultPoints, chaos
from mlrun_tpu.models import init_params, tiny_llama
from mlrun_tpu.ops import paged_attention as pattn
from mlrun_tpu.ops.attention import (
    _fit_block,
    _tuned_block_sizes,
    attention_reference,
    flash_attention_cached,
    resolve_prefill_impl,
)
from mlrun_tpu.serving.llm import _quantize_kv
from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_llama(attention_impl="reference")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _greedy_reference(cfg, params, prompt, n):
    from mlrun_tpu.models.llama import forward

    seq = list(prompt)
    out = []
    for _ in range(n):
        logits = forward(cfg, params, jnp.asarray([seq], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        seq.append(nxt)
    return out


# -- op level -----------------------------------------------------------------
def _random_pool(key, n_pages, page_size, hkv, d, layers=1):
    """A pool [layers, n_pages + 1, ...] whose layers all differ."""
    kk, kv = jax.random.split(key)
    shape = (layers, n_pages + 1, page_size, hkv, d)
    k_pool = jax.random.normal(kk, shape, jnp.float32) * 0.3
    v_pool = jax.random.normal(kv, shape, jnp.float32) * 0.3
    return k_pool, v_pool


def _decode_case(key):
    """q, page table (unmapped -1 entries in the mix) and mid-page
    positions over a 10-page pool of page_size 8."""
    slots, pps, h, d = 3, 4, 4, 32
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (slots, h, d), jnp.float32) * 0.5
    table = np.full((slots, pps), -1, np.int32)
    table[0, :2] = [3, 7]
    table[1, :4] = [0, 1, 2, 8]
    table[2, :1] = [9]
    return q, jnp.asarray(table), jnp.asarray([11, 31, 0], jnp.int32)


def test_paged_kernel_matches_gather_dense():
    """Tolerance-bounded parity: page-table-indexed kernel (interpret) vs
    the dense gathered view, with unmapped (-1) entries and mid-page
    positions in the mix."""
    key = jax.random.PRNGKey(0)
    k_pool, v_pool = _random_pool(key, 10, 8, 2, 32)
    q, table, pos = _decode_case(key)
    out_k = pattn._paged_decode_call(q, k_pool, v_pool, 0, table, pos, 8,
                                     interpret=True)
    out_r = pattn.paged_decode_reference(q, k_pool, v_pool, 0, table,
                                         pos, 8)
    assert float(jnp.max(jnp.abs(out_k - out_r))) < 2e-6


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_kernel_reads_its_layer(kv_dtype, layer):
    """The kernel reaches layer ``layer`` of a three-layer pool through
    its index maps: parity with that layer's plain slice gathered dense,
    and far from every other layer's (a wrong layer index must fail)."""
    key = jax.random.PRNGKey(7)
    k_pool, v_pool = _random_pool(key, 10, 8, 2, 32, layers=3)
    q, table, pos = _decode_case(key)
    scales = {}
    if kv_dtype == "int8":
        k_pool, scales["k_scale"] = _quantize_kv(k_pool)
        v_pool, scales["v_scale"] = _quantize_kv(v_pool)
    else:
        k_pool = k_pool.astype(jnp.bfloat16)
        v_pool = v_pool.astype(jnp.bfloat16)
    out = pattn._paged_decode_call(q, k_pool, v_pool, jnp.int32(layer),
                                   table, pos, 8, interpret=True,
                                   **scales)
    for other in range(3):
        one = {name: arr[other:other + 1] for name, arr in scales.items()}
        ref = pattn.paged_decode_reference(
            q, k_pool[other:other + 1], v_pool[other:other + 1], 0, table,
            pos, 8, **one)
        gap = float(jnp.max(jnp.abs(out - ref)))
        assert gap < 2e-6 if other == layer else gap > 0.05


def test_paged_kernel_interpret_smoke_seq2048():
    """The production shape class: page_size 128, 16 pages/slot (seq
    2048), GQA group of 2 — whole-grid interpret run stays correct."""
    key = jax.random.PRNGKey(42)
    slots, ps, pps, hkv, d = 2, 128, 16, 1, 64
    k_pool, v_pool = _random_pool(key, slots * pps, ps, hkv, d)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (slots, 2, d), jnp.float32) * 0.5
    table = np.arange(slots * pps, dtype=np.int32).reshape(slots, pps)
    pos = jnp.asarray([2047, 900], jnp.int32)
    out_k = pattn._paged_decode_call(q, k_pool, v_pool, 0,
                                     jnp.asarray(table), pos, ps,
                                     interpret=True)
    out_r = pattn.paged_decode_reference(q, k_pool, v_pool, 0,
                                         jnp.asarray(table), pos, ps)
    assert out_k.shape == (slots, 2, d)
    assert float(jnp.max(jnp.abs(out_k - out_r))) < 2e-6


def test_flash_cached_matches_dense_mask():
    """Offset-aware flash prefill (q rows at start + i over a KV cache)
    vs the dense masked softmax."""
    key = jax.random.PRNGKey(3)
    b, s, m, h, d = 1, 6, 32, 4, 16
    start = 10
    kc = jax.random.normal(key, (b, m, h, d), jnp.float32) * 0.3
    vc = jax.random.normal(jax.random.fold_in(key, 1),
                           (b, m, h, d), jnp.float32) * 0.3
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (b, s, h, d), jnp.float32) * 0.5
    out = flash_attention_cached(q, kc, vc, jnp.int32(start))
    scale = d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc) * scale
    mask = (start + jnp.arange(s))[:, None] >= jnp.arange(m)[None, :]
    logits = jnp.where(mask[None, None], logits, -2.0**30)
    ref = jnp.einsum("bhqk,bkhd->bqhd",
                     jax.nn.softmax(logits, axis=-1), vc)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-6
    # offset 0 reduces to plain causal self-attention over the cache head
    out0 = flash_attention_cached(q, kc[:, :s], vc[:, :s], jnp.int32(0))
    ref0 = attention_reference(q, kc[:, :s], vc[:, :s], causal=True)
    assert float(jnp.max(jnp.abs(out0 - ref0))) < 2e-6


# -- dispatcher / CI smoke ----------------------------------------------------
def test_dispatcher_reference_on_cpu_unless_interpret_forced(monkeypatch):
    monkeypatch.delenv("MLT_ATTN_INTERPRET", raising=False)
    assert pattn.resolve_paged_impl("auto") == "reference"
    assert resolve_prefill_impl("auto") == "dense"
    # explicit opt-ins stay explicit; "kernel" is the FULL kernel stack
    # (paged decode + flash/paged prefill — a prefix-hit admission must
    # never fall back to the dense gather)
    assert pattn.resolve_paged_impl("flash") == "kernel"
    assert pattn.resolve_paged_impl("kernel") == "kernel"
    assert resolve_prefill_impl("flash") == "flash"
    assert resolve_prefill_impl("kernel") == "flash"
    monkeypatch.setenv("MLT_ATTN_INTERPRET", "1")
    assert pattn.resolve_paged_impl("auto") == "kernel"
    assert resolve_prefill_impl("auto") == "flash"
    with pytest.raises(ValueError):
        pattn.resolve_paged_impl("bogus")


@pytest.mark.parametrize("resolve", [pattn.resolve_paged_impl,
                                     resolve_prefill_impl])
def test_unknown_impl_raises_from_both_resolvers(resolve):
    """An ``attention_impl`` string neither resolver knows is a
    ValueError at resolve (hence engine-construction) time — never a
    silent pick of some path."""
    with pytest.raises(ValueError, match="bogus"):
        resolve("bogus")


def test_tuned_block_sizes_clamped_to_seq():
    # short-prompt prefill: block equals the sequence, not the 512 floor
    bs = _tuned_block_sizes(64, 2048)
    assert bs.block_q == 64 and bs.block_k_major == 512
    # long sequences keep the big MXU block (sub-block tail just pads);
    # short ones clamp to a divisor or the length itself
    assert _fit_block(600, 512) == 512
    assert _fit_block(2048, 512) == 512
    assert _fit_block(384, 512) == 128
    assert _fit_block(16, 512) == 16
    assert _fit_block(200, 512) == 200
    for sq in (8, 96, 200, 600, 2048):
        picked = _tuned_block_sizes(sq, sq).block_q
        # the library kernel demands block | seq — never a non-divisor
        assert picked <= sq and sq % picked == 0


# -- engine level -------------------------------------------------------------
def test_kernel_engine_tokens_match_reference_engine(setup):
    """Acceptance: kernel-path decode produces identical greedy tokens to
    the gather+dense path, and the per-tick gather stat is 0 on the
    kernel path."""
    cfg, params = setup
    prompts = [[1, 7, 3, 9, 2], [4, 5, 6, 7, 8, 9, 1, 2, 3], [11, 12]]
    outs, stats = {}, {}
    for impl in ("reference", "kernel"):
        eng = PagedContinuousBatchingEngine(
            cfg, params, max_len=64, slots=2, prefill_buckets=(16,),
            page_size=8, attention_impl=impl)
        eng.start()
        try:
            futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            outs[impl] = [f.result(timeout=300)[0] for f in futs]
            stats[impl] = eng.stats
        finally:
            eng.stop()
    assert outs["kernel"] == outs["reference"]
    assert outs["reference"][0] == _greedy_reference(cfg, params,
                                                     prompts[0], 6)
    assert stats["kernel"]["attn_gather_ticks"] == 0
    assert stats["kernel"]["attn_kernel_ticks"] > 0
    assert stats["kernel"]["attn_hbm_bytes_avoided"] > 0
    assert stats["kernel"]["decode_attn_impl"] == "kernel"
    assert stats["reference"]["attn_kernel_ticks"] == 0
    assert stats["reference"]["attn_gather_ticks"] > 0


def test_flash_engine_cold_vs_hit_parity(setup):
    """Full kernel path (flash prefill + paged prefill kernel +
    paged-decode kernel): a prefix-cache hit replays the cold run's
    greedy tokens within the tolerance-parity contract (docs/serving.md
    "Attention kernels" — the hit path LSE-merges per-layer partial
    softmax states, so k-block accumulation order differs from the cold
    monolithic flash; the numeric gap is f32-round-off-sized and the
    greedy token stream agrees). The hit never gathers the cached KV
    densely: prefill_gather_admissions stays 0."""
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(
        cfg, params, max_len=64, slots=2, prefill_buckets=(16,),
        page_size=8, attention_impl="flash")
    eng.start()
    try:
        prompt = [1, 7, 3, 9, 2, 4, 6, 8, 5, 3, 1, 2]  # one full block
        cold, _ = eng.generate(prompt, max_new_tokens=6)
        assert eng.stats["prefix_hits"] == 0
        warm, _ = eng.generate(prompt, max_new_tokens=6)
        branch, _ = eng.generate(prompt[:8] + [9, 9, 4], max_new_tokens=6)
        stats = eng.stats
    finally:
        eng.stop()
    assert warm == cold
    assert stats["prefix_hits"] >= 1
    assert stats["attn_gather_ticks"] == 0
    assert stats["prefill_impl"] == "flash"
    assert stats["paged_prefill_impl"] == "kernel"
    # the acceptance stat: no hit admission seeded via the dense gather
    assert stats["prefill_gather_admissions"] == 0
    assert stats["prefill_kernel_chunks"] > 0
    assert len(branch) == 6
    # decode-tick latency percentiles ride the stats for obs
    assert stats["decode_tick_p50_s"] > 0
    assert stats["decode_tick_p95_s"] >= stats["decode_tick_p50_s"]


@pytest.mark.chaos
def test_prefix_shared_pages_readonly_under_evict_chaos(setup):
    """With the kernel on, shared prefix pages stay bit-identical across
    reuse (decode writes only land in private pages) and eviction still
    only reclaims refcount-0 pages."""
    cfg, params = setup
    eng = PagedContinuousBatchingEngine(
        cfg, params, max_len=64, slots=2, prefill_buckets=(16,),
        page_size=8, n_pages=6, attention_impl="flash")
    evicted = []

    def observe(point, ctx):
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
        shared = list(range(1, 17))   # 2 full blocks
        cold, _ = eng.generate(shared, max_new_tokens=8)
        root = eng._prefix._root
        b0 = root.children[tuple(shared[:8])]
        b1 = b0.children[tuple(shared[8:16])]
        snap_k = np.asarray(eng._pool["k"][:, [b0.page_id, b1.page_id]])
        snap_v = np.asarray(eng._pool["v"][:, [b0.page_id, b1.page_id]])

        warm, _ = eng.generate(shared, max_new_tokens=8)
        assert warm == cold
        # read-only: reuse + decode left the shared pages untouched
        assert np.array_equal(
            snap_k, np.asarray(eng._pool["k"][:, [b0.page_id, b1.page_id]]))
        assert np.array_equal(
            snap_v, np.asarray(eng._pool["v"][:, [b0.page_id, b1.page_id]]))

        # pool pressure: two admissions forcing eviction of refcount-0
        # cached pages; every generation stays exact
        f1 = eng.submit(list(range(100, 117)), max_new_tokens=7)
        f2 = eng.submit(list(range(200, 217)), max_new_tokens=7)
        t1, _ = f1.result(timeout=300)
        t2, _ = f2.result(timeout=300)
        assert len(t1) == 7 and len(t2) == 7
        stats = eng.stats
    finally:
        eng.stop()
    assert stats["prefix_evictions"] == len(evicted) >= 1
    assert len(eng._free_pages) + eng._prefix.cached_pages() == eng.n_pages


def test_llm_engine_flash_prefill_matches_reference(setup):
    """The non-batching LLMEngine with flash prefill generates the same
    greedy tokens as the dense path (bucket padding included: the first
    token is read at the prompt's last real position)."""
    from mlrun_tpu.serving.llm import LLMEngine

    cfg, params = setup
    outs = {}
    for impl in ("reference", "flash"):
        eng = LLMEngine(cfg, params, max_len=64, prefill_buckets=(16,),
                        attention_impl=impl)
        tokens, _ = eng.generate([5, 3, 8, 1, 9], max_new_tokens=6)
        outs[impl] = tokens
    assert outs["flash"] == outs["reference"]


def test_trainer_mlt_flash_step(setup):
    """TrainConfig.attention_impl threads our flash kernel (fwd pallas +
    custom-vjp blockwise bwd, interpret on CPU) through the whole train
    step."""
    import math

    from mlrun_tpu.training import (
        TrainConfig,
        Trainer,
        synthetic_token_stream,
    )

    losses = {}
    for impl in ("reference", "mlt_flash"):
        trainer = Trainer(tiny_llama(),
                          TrainConfig(total_steps=3, attention_impl=impl))
        trainer.init(0)
        # batch divisible by the virtual-device mesh the conftest forces
        stream = synthetic_token_stream(8, 32, 512)
        trainer.train_step(*next(stream))
        metrics = trainer.train_step(*next(stream))
        losses[impl] = float(metrics["loss"])
    assert all(math.isfinite(v) for v in losses.values())
    # different attention algorithms, same model: bf16-noise-level gap
    assert abs(losses["reference"] - losses["mlt_flash"]) < 5e-2
