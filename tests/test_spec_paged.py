"""In-engine batched speculative decoding (docs/serving.md "Speculative
decoding"): op-level kernel-vs-reference parity of the multi-token
verify chunk (native and int8 pools), engine-level spec-on vs spec-off
greedy token identity (cold, through a prefix-cache hit, through a
``KVHandoff``, and under an active adapter — with a per-tenant draft
adapter attached), the zero-dense-gather acceptance contract on the
kernel path (``attn_gather_ticks`` stays 0 with speculation live), the
page-accounting invariant after mid-round rejections (rollback is a
host ``pos`` rewind inside the row's reservation — the free list never
moves mid-round), ladder parking, acceptance-window adaptation, the
``llm.spec_verify`` chaos drill, and the ``make bench-spec`` smoke.
CPU-only (Pallas interpret mode).

Exactness rides the deterministic permutation models
(``models/llama.init_permutation_params``) whose argmax gaps are orders
of magnitude above jit-vs-eager float noise — the same construction
tests/test_speculative.py pins the batch=1 decoder with.
"""

import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlrun_tpu.chaos import FaultPoints, chaos, fail_first
from mlrun_tpu.models import (
    init_lora_nonzero,
    init_permutation_params,
    permutation_pair,
    tiny_llama,
)
from mlrun_tpu.ops import paged_attention as pattn
from mlrun_tpu.serving.llm import _quantize_kv
from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine

PROMPT = [1, 7, 3, 9, 2, 4, 6, 8, 5, 3, 1, 2]  # one full block at ps=8


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(tiny_llama(attention_impl="reference"),
                              vocab_size=64, tie_embeddings=False)
    target_perm, draft_perm = permutation_pair(cfg.vocab_size, overlap=0.7)
    target = init_permutation_params(cfg, target_perm)
    draft = init_permutation_params(cfg, draft_perm)
    return cfg, target, draft


def _spec(cfg, draft_params, **over):
    conf = {"enabled": True, "k": 4, "draft_config": cfg,
            "draft_params": draft_params}
    conf.update(over)
    return conf


def _engine(cfg, params, *, spec=None, **kw):
    kw.setdefault("max_len", 64)
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("page_size", 8)
    eng = PagedContinuousBatchingEngine(cfg, params, speculative=spec,
                                        **kw)
    eng.start()
    return eng


# -- op level -----------------------------------------------------------------
def _verify_case(layers):
    """A pool of ``layers`` differing layers, a 5-token chunk per slot,
    and rows with base 0 (cold chunk, nothing behind it), mid-page and
    deep into their pages."""
    ps, slots, hkv, h, d, s = 8, 3, 2, 4, 32, 5
    n_pages = 8
    kk, kv, kq, kc1, kc2 = jax.random.split(jax.random.PRNGKey(0), 5)
    shape = (layers, n_pages + 1, ps, hkv, d)
    k_pool = jax.random.normal(kk, shape, jnp.float32) * 0.3
    v_pool = jax.random.normal(kv, shape, jnp.float32) * 0.3
    q = jax.random.normal(kq, (slots, s, h, d), jnp.float32)
    chunk_k = jax.random.normal(kc1, (slots, s, hkv, d), jnp.float32) * 0.3
    chunk_v = jax.random.normal(kc2, (slots, s, hkv, d), jnp.float32) * 0.3
    base = jnp.asarray([13, 0, 27], jnp.int32)
    table = jnp.asarray([[0, 1, -1, -1],
                         [-1, -1, -1, -1],
                         [2, 3, 4, 5]], jnp.int32)
    return ps, k_pool, v_pool, q, chunk_k, chunk_v, table, base


def test_verify_chunk_kernel_vs_reference_parity():
    """The batched verify chunk attending the page pool in place
    (kernel) matches the dense-gather reference bit-for-bit up to f32
    accumulation order — native and int8 pools, including a base=0 row
    (cold chunk, nothing behind it) and a row deep into its pages."""
    ps, k_pool, v_pool, q, chunk_k, chunk_v, table, base = _verify_case(1)

    def both(kp, vp, **scales):
        ref = pattn.paged_verify_attention(
            q, chunk_k, chunk_v, kp, vp, 0, table, base, page_size=ps,
            impl="reference", **scales)
        ker = pattn.paged_verify_attention(
            q, chunk_k, chunk_v, kp, vp, 0, table, base, page_size=ps,
            impl="kernel", interpret=True, **scales)
        return float(jnp.max(jnp.abs(ker - ref)))

    assert both(k_pool, v_pool) < 2e-5
    k8, ks = _quantize_kv(k_pool)
    v8, vs = _quantize_kv(v_pool)
    assert both(k8, v8, k_scale=ks, v_scale=vs) < 2e-5


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_verify_kernel_reads_its_layer(kv_dtype, layer):
    """The verify kernel reaches layer ``layer`` of a three-layer pool
    through its index maps: parity with the reference over that layer's
    plain slice, and far from every other layer's (a wrong layer index
    must fail)."""
    ps, k_pool, v_pool, q, chunk_k, chunk_v, table, base = _verify_case(3)
    scales = {}
    if kv_dtype == "int8":
        k_pool, scales["k_scale"] = _quantize_kv(k_pool)
        v_pool, scales["v_scale"] = _quantize_kv(v_pool)
    else:
        k_pool = k_pool.astype(jnp.bfloat16)
        v_pool = v_pool.astype(jnp.bfloat16)
    out = pattn.paged_verify_attention(
        q, chunk_k, chunk_v, k_pool, v_pool, jnp.int32(layer), table,
        base, page_size=ps, impl="kernel", interpret=True, **scales)
    for other in range(3):
        one = {name: arr[other:other + 1] for name, arr in scales.items()}
        ref = pattn.paged_verify_reference(
            q, chunk_k, chunk_v, k_pool[other:other + 1],
            v_pool[other:other + 1], 0, table, base, ps, **one)
        # rows 0 and 2 have a prefix in the pool; row 1 (base 0) attends
        # its own chunk only, whatever the layer
        gap = float(jnp.max(jnp.abs(out - ref)[jnp.asarray([0, 2])]))
        assert gap < 2e-5 if other == layer else gap > 0.02


# -- engine level -------------------------------------------------------------
def test_spec_on_off_identity_cold_and_prefix_hit(setup):
    """Speculation on vs off is token-identical, cold AND through a
    prefix-cache hit; the spec arm genuinely speculated (mixed
    accept/reject rounds) and leaked no pages relative to the off arm."""
    cfg, target, draft = setup
    off = _engine(cfg, target)
    try:
        cold_off, _ = off.generate(PROMPT, max_new_tokens=10)
        warm_off, _ = off.generate(PROMPT, max_new_tokens=10)
        off_stats = off.stats
        off_free = len(off._free_pages)
    finally:
        off.stop()
    on = _engine(cfg, target, spec=_spec(cfg, draft))
    try:
        cold_on, _ = on.generate(PROMPT, max_new_tokens=10)
        warm_on, _ = on.generate(PROMPT, max_new_tokens=10)
        on_stats = on.stats
        on_free = len(on._free_pages)
    finally:
        on.stop()
    assert cold_on == cold_off
    assert warm_on == warm_off
    assert off_stats["prefix_hits"] >= 1 and on_stats["prefix_hits"] >= 1
    assert on_stats["spec_rounds"] > 0
    assert 0.0 < on_stats["acceptance_rate"] < 1.0
    assert on_stats["spec_tokens_per_round"] > 1.0
    # identical workload, identical residual page state (cached prefix
    # pages included) — speculation claimed nothing extra
    assert on_free == off_free


@pytest.mark.parametrize("kv_dtype", [
    "native", pytest.param("int8", marks=pytest.mark.slow)])
def test_spec_kernel_path_never_gathers(setup, kv_dtype):
    """ACCEPTANCE: with ``attention_impl="kernel"`` the speculative
    verify dispatch runs the paged verify kernel — zero dense gathers
    (``attn_gather_ticks`` stays 0), kernel ticks accrue, and the stream
    matches the non-speculative reference arm exactly."""
    cfg, target, draft = setup
    ref = _engine(cfg, target, kv_dtype=kv_dtype)
    try:
        expect, _ = ref.generate(PROMPT, max_new_tokens=8)
    finally:
        ref.stop()
    eng = _engine(cfg, target, spec=_spec(cfg, draft),
                  attention_impl="kernel", kv_dtype=kv_dtype)
    try:
        out, _ = eng.generate(PROMPT, max_new_tokens=8)
        stats = eng.stats
    finally:
        eng.stop()
    assert out == expect
    assert stats["attn_gather_ticks"] == 0
    assert stats["attn_kernel_ticks"] > 0
    assert stats["spec_rounds"] > 0


def test_spec_post_handoff_identity(setup):
    """Disaggregated prefill→decode with speculation live on the decode
    replica: the imported-KV row speculates (the draft prefills from the
    handoff's prompt tokens) and the stream matches the spec-off arm."""
    cfg, target, draft = setup
    off = _engine(cfg, target)
    try:
        expect, _ = off.generate(PROMPT, max_new_tokens=8)
    finally:
        off.stop()
    pre = _engine(cfg, target, spec=_spec(cfg, draft))
    dec = _engine(cfg, target, spec=_spec(cfg, draft))
    try:
        handoff = pre.submit_prefill(PROMPT).result(timeout=300)
        tokens, _ = dec.submit_prefilled(
            handoff, max_new_tokens=8).result(timeout=300)
        stats = dec.stats
    finally:
        pre.stop()
        dec.stop()
    assert tokens == expect
    assert stats["spec_rounds"] > 0


def test_spec_adapter_rows_identity_with_tenant_draft(setup):
    """Adapter-bearing rows keep exact greedy identity under
    speculation — verified under the tenant's target adapter — both with
    the base draft model and with a per-tenant draft adapter attached
    via ``AdapterRegistry.attach_draft``. Deltas are tiny relative to
    the permutation model's argmax gaps, so the tenant's stream equals
    the base stream's determinism class while still exercising the
    nonzero-delta dispatch."""
    cfg, target, draft = setup
    lora = init_lora_nonzero(cfg, jax.random.PRNGKey(5), rank=2,
                             alpha=0.1, b_scale=0.001)
    draft_lora = init_lora_nonzero(cfg, jax.random.PRNGKey(7), rank=2,
                                   alpha=0.1, b_scale=0.001)
    off = _engine(cfg, target, adapters={"t1": lora})
    try:
        expect = off.submit(PROMPT, max_new_tokens=8,
                            adapter="t1").result(timeout=300)[0]
        expect_base, _ = off.generate(PROMPT, max_new_tokens=8)
    finally:
        off.stop()
    on = _engine(cfg, target, spec=_spec(cfg, draft),
                 adapters={"t1": lora})
    try:
        on._adapters.attach_draft(cfg, sources={"t1": draft_lora})
        got = on.submit(PROMPT, max_new_tokens=8,
                        adapter="t1").result(timeout=300)[0]
        got_base, _ = on.generate(PROMPT, max_new_tokens=8)
        stats = on.stats
    finally:
        on.stop()
    assert got == expect
    assert got_base == expect_base
    assert stats["spec_rounds"] > 0


def test_page_accounting_after_mid_round_rejection(setup):
    """Mid-round rejections roll back as a host ``pos`` rewind inside
    each row's admission reservation: after a churn of overlapping
    requests (more requests than slots, partial-agreement draft → real
    rejections) every page is back on the free list, every page-table
    row is cleared, and all streams are exact."""
    cfg, target, draft = setup
    prompts = [[i + 1, i + 2, i + 3] for i in range(5)]  # < page_size:
    budgets = [5, 7, 4, 6, 8]            # nothing reaches the prefix cache
    off = _engine(cfg, target, max_len=32)
    try:
        futures = [off.submit(p, max_new_tokens=b)
                   for p, b in zip(prompts, budgets)]
        expect = [f.result(timeout=300)[0] for f in futures]
    finally:
        off.stop()
    on = _engine(cfg, target, spec=_spec(cfg, draft), max_len=32)
    try:
        futures = [on.submit(p, max_new_tokens=b)
                   for p, b in zip(prompts, budgets)]
        results = [f.result(timeout=300)[0] for f in futures]
        stats = on.stats
        free_after = len(on._free_pages)
        table_after = np.asarray(on._page_table)
    finally:
        on.stop()
    assert results == expect
    assert stats["spec_rejected"] > 0          # rejections really happened
    assert free_after == on.n_pages            # every page returned
    assert (table_after == -1).all()


def test_ladder_park_and_resume(setup):
    """The degradation ladder parks speculation fleet-wide: the
    ``speculative_enabled`` flag is re-derived from pressure at every
    submit, so a submit that lands while pages are pinned (with
    ``min_free_page_frac`` pinned to 1.0) flips it off for EVERY row's
    subsequent ticks — and a submit against the idle engine flips it
    back on (the rows resync their stale draft caches). Streams are
    exact in both regimes."""
    import time as _time

    cfg, target, draft = setup
    eng = _engine(cfg, target, spec=_spec(cfg, draft),
                  degradation={"min_free_page_frac": 1.0})
    try:
        f1 = eng.submit(PROMPT, max_new_tokens=16)
        deadline = _time.monotonic() + 30
        while len(eng._free_pages) == eng.n_pages:   # r1 admitted yet?
            assert _time.monotonic() < deadline
            _time.sleep(0.005)
        # this submit sees pinned pages → level 1 → fleet-wide park
        f2 = eng.submit([9, 2, 6, 4], max_new_tokens=8)
        out1, _ = f1.result(timeout=300)
        out2, _ = f2.result(timeout=300)
        parked_stats = eng.stats
        assert eng.speculative_enabled is False
        assert parked_stats["degraded"] >= 1
        rounds_at_park = parked_stats["spec_rounds"]
        # idle pool (cached refcount-0 pages count as headroom) → the
        # next submit clears the park and speculation resumes
        out3, _ = eng.generate([5, 3, 2], max_new_tokens=8)
        stats = eng.stats
        assert eng.speculative_enabled is True
    finally:
        eng.stop()
    ref = _engine(cfg, target)
    try:
        expect1, _ = ref.generate(PROMPT, max_new_tokens=16)
        expect2, _ = ref.generate([9, 2, 6, 4], max_new_tokens=8)
        expect3, _ = ref.generate([5, 3, 2], max_new_tokens=8)
    finally:
        ref.stop()
    assert (out1, out2, out3) == (expect1, expect2, expect3)
    assert stats["spec_rounds"] > rounds_at_park


def test_acceptance_window_adaptation(setup):
    """An adversarial draft (near-zero acceptance) drives the per-row
    gate into probation: after the optimistic warmup window the row
    falls back to plain decode with only periodic k=1 probes, so spec
    rounds stay far below one-per-token — and the stream is still the
    target's exact greedy output. A perfect draft rides high k."""
    cfg, target, _ = setup
    target_perm, _ = permutation_pair(cfg.vocab_size, overlap=0.7)
    adversarial = init_permutation_params(
        cfg, np.roll(np.asarray(target_perm), 7), seed=3)
    ref = _engine(cfg, target)
    try:
        expect, _ = ref.generate(PROMPT, max_new_tokens=24)
    finally:
        ref.stop()
    eng = _engine(cfg, target,
                  spec=_spec(cfg, adversarial, window=8, probe_every=8))
    try:
        out, _ = eng.generate(PROMPT, max_new_tokens=24)
        stats = eng.stats
    finally:
        eng.stop()
    assert out == expect
    assert stats["acceptance_rate"] < 0.35
    assert 0 < stats["spec_rounds"] < 24       # gate parked most rounds
    # perfect draft: every proposal accepted, k rides at the max
    eng = _engine(cfg, target, spec=_spec(cfg, target))
    try:
        out, _ = eng.generate(PROMPT, max_new_tokens=24)
        stats = eng.stats
    finally:
        eng.stop()
    assert out == expect
    assert stats["acceptance_rate"] > 0.9
    assert stats["spec_tokens_per_round"] > 2.0


@pytest.mark.chaos
def test_chaos_spec_verify_parks_tick_to_plain_decode(setup):
    """An armed ``llm.spec_verify`` error degrades those ticks to plain
    decode — never a client error — and once the fault clears the rows
    resync their draft caches and speculation resumes; the stream stays
    exact-greedy throughout."""
    cfg, target, draft = setup
    ref = _engine(cfg, target)
    try:
        expect, _ = ref.generate(PROMPT, max_new_tokens=12)
    finally:
        ref.stop()
    eng = _engine(cfg, target, spec=_spec(cfg, draft))
    try:
        with chaos.inject(FaultPoints.llm_spec_verify, fail_first(3),
                          error=RuntimeError("injected verify fault")):
            out, _ = eng.generate(PROMPT, max_new_tokens=12)
        stats = eng.stats
    finally:
        eng.stop()
    assert out == expect
    assert stats["spec_parked_ticks"] >= 1
    assert stats["spec_rounds"] > 0            # resumed after the fault
    assert stats["spec_resyncs"] >= 1          # plain ticks staled the draft


# -- bench smoke --------------------------------------------------------------
@pytest.mark.slow
def test_bench_spec_smoke():
    """`bench_serve.py --spec` runs end to end at toy sizes and reports
    the A/B contract: greedy parity in BOTH arms (adapter rows
    included), a spec-on speedup figure, and the adversarial leg."""
    path = pathlib.Path(__file__).resolve().parent.parent / "bench_serve.py"
    spec = importlib.util.spec_from_file_location("bench_serve", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    result = mod.run_spec(requests=4, prompt_tokens=12, max_new=8,
                          tick_cost_s=0.002, slots=2, warmup=False)
    assert result["mode"] == "spec"
    assert result["greedy_parity"] is True
    assert result["adapter_parity"] is True
    assert result["spec_on"]["tokens_per_sec"] > 0
    assert result["spec_off"]["tokens_per_sec"] > 0
    assert result["adversarial"]["tokens_per_sec"] > 0
    assert result["spec_on"]["acceptance_rate"] > 0.2
    assert result["adversarial"]["acceptance_rate"] < 0.35
