"""Serving prefix-cache / chunked-prefill microbench (one JSON line).

CPU-runnable on ``tiny_llama`` — counts and parity, not device speed
(a CPU time says nothing about a TPU). Two workloads against the paged
continuous-batching engine:

- **repeated**: every prompt shares a long system prefix and differs only
  in a short suffix (the production-dominant shape). Measures cold vs
  warm p50 TTFT on the prefix-cache engine, the same workload on a
  cache-disabled engine, and the hit rate.
- **unique**: every prompt is random (worst case for the cache). Measures
  end-to-end throughput with the cache on vs off — reuse must not tax
  traffic that can't reuse.

``--fleet`` runs the engine-fleet section instead (docs/serving.md
"Engine fleet"): a hot-prefix workload against an ``EngineFleet`` at
replicas=4 with page pools sized so one replica CANNOT hold every hot
prefix — prefix-affinity routing keeps each prefix cache-resident on its
ring owner, while random routing spreads them across replicas and churns
every pool's LRU. Records aggregate hit rate + p50/p95 TTFT per policy,
and the unique-prompt p50 per policy (affinity must not tax traffic that
can't reuse).

``--autoscale`` runs the closed scrape→scale loop instead
(docs/observability.md "Autoscaler"): the same synthetic load ramp is
driven against a static single-replica fleet (the baseline) and against
a fleet owned by a ``FleetAutoscaler`` acting on the aggregated signals.
Records per-phase p95 TTFT, the replica trajectory, scale-up/-down event
counts, whether each side met the derived SLO target, and that
scale-down leaked no ``replica``-labeled metric series.

Run: python bench_serve.py [--fleet|--autoscale] [--requests N] ...
"""

from __future__ import annotations

import argparse
import json
import time


def _percentile(samples, q):
    # same nearest-rank definition as the engine's stats keys (import is
    # deferred so --help stays jax-free)
    from mlrun_tpu.serving.llm_batch import _percentile as engine_pct

    return engine_pct(sorted(samples), q)


def _make_engine(config, params, *, prefix_cache, max_len, page_size,
                 prefill_buckets, warmup=True):
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine

    engine = PagedContinuousBatchingEngine(
        config, params, max_len=max_len, slots=4, page_size=page_size,
        prefill_buckets=prefill_buckets, prefix_cache=prefix_cache)
    if warmup:
        engine.warmup()
    engine.start()
    return engine


_SNAPSHOT_KEYS = (
    "requests", "completed", "queue_depth", "pressure_level",
    "prefill_chunks", "prefill_tokens_tick_max", "free_pages",
    "prefix_hit_rate", "prefix_cached_tokens", "prefix_cached_pages",
    "prefix_evictions", "ttft_p50_s", "ttft_p95_s", "itl_p50_s",
    "itl_p95_s")


def _metrics_snapshot(stats: dict) -> dict:
    """Engine-telemetry context frozen next to the latency numbers, so a
    future BENCH_*.json diff can tell a regression from a workload shift
    (different hit rate / queue depth / prefill chunking)."""
    return {key: stats[key] for key in _SNAPSHOT_KEYS if key in stats}


def _ttft_series(engine, prompts, max_new):
    """Serial generation (one request in flight) so each TTFT isolates
    the prefill path, not queueing behind other requests."""
    ttfts = []
    for prompt in prompts:
        _, stats = engine.generate(prompt, max_new_tokens=max_new)
        ttfts.append(stats["ttft_s"])
    return ttfts


def _throughput(engine, prompts, max_new):
    """Concurrent submission; tokens/sec over the whole batch wall time."""
    started = time.perf_counter()
    futures = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
    results = [f.result(timeout=600) for f in futures]
    wall = time.perf_counter() - started
    generated = sum(len(tokens) for tokens, _ in results)
    return generated / wall if wall > 0 else 0.0


def run(requests: int = 12, prefix_tokens: int = 960,
        suffix_tokens: int = 8, max_new: int = 16, page_size: int = 32,
        max_len: int = 1024, seed: int = 0, warmup: bool = True) -> dict:
    import jax
    import numpy as np

    from mlrun_tpu.models import init_params, tiny_llama

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    buckets = tuple(sorted({min(64, max_len), max_len}))

    def prompt_of(length):
        return rng.integers(0, config.vocab_size, length).tolist()

    prefix = prompt_of(prefix_tokens)
    repeated = [prefix + prompt_of(suffix_tokens) for _ in range(requests)]
    unique = [prompt_of(prefix_tokens + suffix_tokens)
              for _ in range(requests)]

    out = {"requests": requests, "prefix_tokens": prefix_tokens,
           "suffix_tokens": suffix_tokens, "page_size": page_size,
           "model": "tiny"}

    # repeated-prefix workload: cache on (cold first, then warm hits)
    engine = _make_engine(config, params, prefix_cache=True,
                          max_len=max_len, page_size=page_size,
                          prefill_buckets=buckets, warmup=warmup)
    try:
        ttfts = _ttft_series(engine, repeated, max_new)
        stats = engine.stats
    finally:
        engine.stop()
    warm_ttfts = ttfts[1:] or ttfts  # --requests 1: no warm samples
    out["repeated"] = {
        "cold_ttft_ms": round(ttfts[0] * 1000, 2),
        "warm_p50_ttft_ms": round(
            _percentile(warm_ttfts, 0.50) * 1000, 2),
        "prefix_hit_rate": round(stats["prefix_hit_rate"], 3),
        "prefix_cached_tokens": stats["prefix_cached_tokens"],
        "metrics": _metrics_snapshot(stats),
    }

    # same workload, cache disabled — the baseline p50 the speedup is vs
    engine = _make_engine(config, params, prefix_cache=False,
                          max_len=max_len, page_size=page_size,
                          prefill_buckets=buckets, warmup=warmup)
    try:
        base_ttfts = _ttft_series(engine, repeated, max_new)
    finally:
        engine.stop()
    out["repeated"]["nocache_p50_ttft_ms"] = round(
        _percentile(base_ttfts, 0.50) * 1000, 2)
    warm = _percentile(warm_ttfts, 0.50)
    out["repeated"]["p50_ttft_speedup"] = round(
        _percentile(base_ttfts, 0.50) / warm, 2) if warm > 0 else 0.0

    # unique-prompt workload: throughput must not regress with the cache
    tps = {}
    unique_metrics = {}
    for label, cache_on in (("cache_on", True), ("cache_off", False)):
        engine = _make_engine(config, params, prefix_cache=cache_on,
                              max_len=max_len, page_size=page_size,
                              prefill_buckets=buckets, warmup=warmup)
        try:
            tps[label] = round(_throughput(engine, unique, max_new), 1)
            if cache_on:
                unique_metrics = _metrics_snapshot(engine.stats)
        finally:
            engine.stop()
    out["unique"] = {"tokens_per_sec_cache_on": tps["cache_on"],
                     "tokens_per_sec_cache_off": tps["cache_off"],
                     "metrics": unique_metrics}
    return out


def run_prefill_kernel(requests: int = 10, prefix_tokens: int = 192,
                       suffix_tokens: int = 8, max_new: int = 8,
                       page_size: int = 32, max_len: int = 256,
                       seed: int = 0, prefixes: int = 6,
                       requests_per_prefix: int = 4,
                       warmup: bool = False) -> dict:
    """Multi-token paged prefill kernel + int8 KV pages A/B
    (docs/serving.md "Attention kernels"); rewrites BENCH_r15.json via
    ``make bench-prefill``.

    Two sections:

    - **prefill_kernel**: the repeated-prefix workload with
      ``attention_impl="kernel"`` (prefix-hit suffix prefill attends the
      cached pages IN PLACE, ``prefill_gather_admissions`` must stay 0)
      vs ``"reference"`` (dense ``gather_prefix_pages`` seed per hit
      admission). On CPU the kernel arm runs the Pallas INTERPRETER, so
      its wall clock measures the interpreter, not the TPU kernel — the
      honest CPU numbers are the parity check (cold-vs-hit greedy
      agreement on both arms) and the per-hit-admission HBM-bytes model
      of the eliminated dense seed copy.
    - **int8_pool_bytes**: hit rate at FIXED pool bytes, int8 on/off —
      ``prefixes`` hot prefixes cycled ``requests_per_prefix`` times
      over a byte budget sized so the bf16 pool cannot keep every
      prefix resident but the ~2x-pages int8 pool can. Both arms run
      the reference attention path (hit rate is an admission-side
      property; the int8 kernels' parity is covered by the first
      section and tests/test_paged_prefill.py).
    """
    import jax
    import numpy as np

    from mlrun_tpu.models import init_params, tiny_llama
    from mlrun_tpu.serving.paged import (
        PagedContinuousBatchingEngine,
        init_paged_pool,
    )

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    buckets = tuple(sorted({min(64, max_len), max_len}))

    def prompt_of(length):
        return rng.integers(0, config.vocab_size, length).tolist()

    prefix = prompt_of(prefix_tokens)
    repeated = [prefix + prompt_of(suffix_tokens) for _ in range(requests)]

    out = {"mode": "prefill_kernel", "requests": requests,
           "prefix_tokens": prefix_tokens, "page_size": page_size,
           "model": "tiny",
           "note": "CPU arms run Pallas in interpret mode — wall times "
                   "there measure the interpreter; the acceptance "
                   "numbers are parity + the HBM-bytes model"}

    arms = {}
    for label, impl in (("kernel", "kernel"), ("gather", "reference")):
        engine = PagedContinuousBatchingEngine(
            config, params, max_len=max_len, slots=4,
            page_size=page_size, prefill_buckets=buckets,
            prefix_cache=True, attention_impl=impl)
        if warmup:
            engine.warmup()
        engine.start()
        try:
            ttfts = []
            cold_tokens = None
            for prompt in repeated:
                tokens, stats = engine.generate(prompt,
                                                max_new_tokens=max_new)
                ttfts.append(stats["ttft_s"])
                if cold_tokens is None:
                    cold_tokens = tokens  # first request ran cold
            # cold-vs-hit greedy agreement on the SAME prompt (the
            # tolerance-parity contract's acceptance check): replaying
            # the first — cold — prompt now takes the prefix-hit path
            replay, _ = engine.generate(repeated[0],
                                        max_new_tokens=max_new)
            parity = replay == cold_tokens
            stats = engine.stats
        finally:
            engine.stop()
        warm = ttfts[1:] or ttfts
        arms[label] = {
            "cold_ttft_ms": round(ttfts[0] * 1000, 2),
            "warm_p50_ttft_ms": round(_percentile(warm, 0.50) * 1000, 2),
            "prefix_hit_rate": round(stats["prefix_hit_rate"], 3),
            "prefill_gather_admissions":
                stats["prefill_gather_admissions"],
            "prefill_kernel_chunks": stats["prefill_kernel_chunks"],
            "paged_prefill_impl": stats["paged_prefill_impl"],
            "cold_vs_hit_parity_ok": parity,
        }
    # the dense seed copy a gather-path hit admission materializes into
    # the batch=1 cache (k+v, every layer, the full max_len window) —
    # what the in-place kernel path eliminates
    itemsize = np.dtype(config.dtype).itemsize
    gather_bytes = (2 * config.n_layers * max_len * config.n_kv_heads
                    * config.head_dim * itemsize)
    out["prefill_kernel"] = {
        "kernel": arms["kernel"], "gather": arms["gather"],
        "hbm_bytes_per_hit_admission_gather": gather_bytes,
        "hbm_bytes_per_hit_admission_kernel": 0,
        "gather_admissions_on_kernel_arm":
            arms["kernel"]["prefill_gather_admissions"],
    }

    # -- int8 at fixed pool bytes -------------------------------------------
    pages_per_prompt = -(-(prefix_tokens + suffix_tokens + max_new)
                         // page_size)
    # budget: roughly half the pages every hot prefix would need at the
    # native dtype — the native pool churns its LRU, int8 holds ~2x the
    # pages at the same bytes and keeps the working set resident
    page_bytes = {
        dt: sum(a.nbytes for a in init_paged_pool(
            config, 1, page_size, dt).values())
        for dt in ("native", "int8")}
    budget = (prefixes * pages_per_prompt // 2 + 2) * page_bytes["native"]
    hot = [prompt_of(prefix_tokens) for _ in range(prefixes)]
    workload = [hot[i % prefixes] + prompt_of(suffix_tokens)
                for i in range(prefixes * requests_per_prefix)]
    int8_arms = {}
    for dt in ("native", "int8"):
        # floor: one admission must always fit (requests needing more
        # pages than the pool fail fast); slots queue for pages beyond
        n_pages = max(int(budget // page_bytes[dt]),
                      pages_per_prompt + 1)
        engine = PagedContinuousBatchingEngine(
            config, params, max_len=max_len, slots=4,
            page_size=page_size, prefill_buckets=buckets,
            prefix_cache=True, kv_dtype=dt, n_pages=n_pages)
        if warmup:
            engine.warmup()
        engine.start()
        try:
            ttfts = _ttft_series(engine, workload, max_new)
            stats = engine.stats
        finally:
            engine.stop()
        int8_arms[dt] = {
            "n_pages_at_budget": n_pages,
            "pool_bytes": n_pages * page_bytes[dt],
            "prefix_hit_rate": round(stats["prefix_hit_rate"], 3),
            "prefix_evictions": stats["prefix_evictions"],
            "p50_ttft_ms": round(
                _percentile(ttfts, 0.50) * 1000, 2),
        }
    out["int8_pool_bytes"] = {
        "pool_byte_budget": budget,
        "bytes_per_page_native": page_bytes["native"],
        "bytes_per_page_int8": page_bytes["int8"],
        "capacity_ratio": round(
            page_bytes["native"] / page_bytes["int8"], 2),
        "native": int8_arms["native"], "int8": int8_arms["int8"],
        "hit_rate_gain": round(
            int8_arms["int8"]["prefix_hit_rate"]
            - int8_arms["native"]["prefix_hit_rate"], 3),
    }
    return out


def run_kv_tier(prefixes: int = 6, requests_per_prefix: int = 2,
                prefix_tokens: int = 56, suffix_tokens: int = 8,
                max_new: int = 4, page_size: int = 8,
                max_len: int = 128, seed: int = 0,
                fleet_prefixes: int = 8, fleet_prefix_tokens: int = 352,
                warmup: bool = False,
                legs=("host_tier", "ring_fetch")) -> dict:
    """Hierarchical KV cache A/B (docs/serving.md "Hierarchical KV");
    rewrites BENCH_r18.json via ``make bench-kv-tier``.

    Two legs:

    - **host_tier**: ``prefixes`` hot prefixes cycled round-robin (the
      most LRU-hostile order) over a device pool sized to hold only
      about HALF the hot set, tier off vs on at the SAME device bytes.
      Untiered, a recurring prefix's pages were evicted by the time it
      comes back — the measured-round hit rate collapses toward zero.
      Tiered, eviction demotes the pages to host RAM and admission
      promotes them back, so the same requests are served from cache
      (``served_from_cache_rate`` = device-hit + promote-hit requests
      over measured requests).
    - **ring_fetch**: a 1-replica fleet warms ``fleet_prefixes`` long
      prefixes, then a second replica joins and takes over ~1/2 of the
      keyspace. First request per moved key, ``prefix_fetch`` on (pages
      pulled from the previous owner, then a prefix-hit suffix prefill)
      vs off (full re-prefill from tokens). The reported latency is the
      honest client view: engine TTFT plus the ``fetch`` ledger phase.
    """
    import jax
    import numpy as np

    from mlrun_tpu.models import init_params, tiny_llama
    from mlrun_tpu.serving.paged import (
        PagedContinuousBatchingEngine,
        init_paged_pool,
    )

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    buckets = tuple(sorted({min(64, max_len), max_len}))

    def prompt_of(length):
        return rng.integers(0, config.vocab_size, length).tolist()

    out = {"mode": "kv_tier", "prefixes": prefixes,
           "prefix_tokens": prefix_tokens, "page_size": page_size,
           "model": "tiny"}

    # -- leg A: host tier at fixed device bytes ------------------------------
    # (``legs`` lets the tier-1 bench smoke run one leg — the full A/B
    # is the make target's job)
    pages_per_prompt = -(-(prefix_tokens + suffix_tokens + max_new)
                         // page_size)
    # device pool ~half the hot set (floor: one admission must fit);
    # the host tier gets bytes to spare — the A/B is device-bytes-fixed
    n_pages = max(prefixes * pages_per_prompt // 2 + 2,
                  pages_per_prompt + 1)
    page_bytes = sum(a.nbytes for a in init_paged_pool(
        config, 1, page_size, "int8").values())
    hot = [prompt_of(prefix_tokens) for _ in range(prefixes)]
    workload = [hot[i % prefixes] + prompt_of(suffix_tokens)
                for i in range(prefixes * requests_per_prefix)]
    arms = {}
    arm_specs = (("untiered", None),
                 ("tiered", {"host_bytes": 256 << 20})) \
        if "host_tier" in legs else ()
    for label, tier in arm_specs:
        engine = PagedContinuousBatchingEngine(
            config, params, max_len=max_len, slots=4,
            page_size=page_size, prefill_buckets=buckets,
            prefix_cache=True, kv_dtype="int8", n_pages=n_pages,
            kv_tier=tier)
        if warmup:
            engine.warmup()
        engine.start()
        try:
            # round 1 is the cold fill; everything after is measured
            cold = {}
            for prompt in workload[:prefixes]:
                tokens, _ = engine.generate(prompt,
                                            max_new_tokens=max_new)
                cold[tuple(prompt)] = tokens
            base = dict(engine.stats)
            ttfts = []
            parity = True
            for prompt in workload[prefixes:]:
                tokens, stats = engine.generate(prompt,
                                                max_new_tokens=max_new)
                ttfts.append(stats["ttft_s"])
                if tuple(prompt) in cold:
                    parity = parity and tokens == cold[tuple(prompt)]
            stats = engine.stats
        finally:
            engine.stop()
        measured = len(workload) - prefixes
        hit_requests = stats["prefix_hits"] - base["prefix_hits"]
        promote_requests = stats.get("kv_promotes", 0) \
            - base.get("kv_promotes", 0)
        arms[label] = {
            "measured_requests": measured,
            "device_hit_requests": hit_requests,
            "promote_hit_requests": promote_requests,
            "served_from_cache_rate": round(
                (hit_requests + promote_requests) / measured, 3)
            if measured else 0.0,
            "p50_ttft_ms": round(_percentile(ttfts, 0.50) * 1000, 2),
            "greedy_parity_ok": parity,
        }
        if label == "tiered":
            arms[label]["kv_demoted_pages"] = stats["kv_demoted_pages"]
            arms[label]["kv_promoted_pages"] = stats["kv_promoted_pages"]
            arms[label]["tier"] = stats.get("kv_tier", {})
    if arms:
        out["host_tier"] = {
            "device_pages": n_pages,
            "device_pool_bytes": n_pages * page_bytes,
            "hot_set_pages": prefixes * pages_per_prompt,
            "untiered": arms["untiered"], "tiered": arms["tiered"],
            "hit_rate_gain": round(
                arms["tiered"]["served_from_cache_rate"]
                - arms["untiered"]["served_from_cache_rate"], 3),
            "note": "at tiny-model scale both arms' prefills pad to "
                    "the same bucket, so a promote hit saves compute "
                    "bytes (the hit-rate signal), not bucket wall time "
                    "— the latency win shows in ring_fetch's long "
                    "prompts",
        }
    if "ring_fetch" not in legs:
        return out

    # -- leg B: ring reassignment, fetch vs re-prefill -----------------------
    from mlrun_tpu.serving.fleet import EngineFleet

    fleet_max_len = 512
    fleet_page = 32
    fleet_buckets = (64, fleet_max_len)
    fleet_suffix = 8

    def factory(role):
        return PagedContinuousBatchingEngine(
            config, params, max_len=fleet_max_len, slots=4,
            page_size=fleet_page, prefill_buckets=fleet_buckets,
            prefix_cache=True, kv_dtype="int8",
            kv_tier={"host_bytes": 256 << 20})

    def fetch_leg(fetch_on: bool) -> dict:
        fleet = EngineFleet(factory, replicas=1)
        fleet._prefix_fetch = fetch_on
        fleet.start()
        if warmup:
            fleet.warmup()
        hot = [prompt_of(fleet_prefix_tokens)
               for _ in range(fleet_prefixes)]
        for prompt in hot:
            fleet.generate(prompt + prompt_of(fleet_suffix),
                           max_new_tokens=max_new)
        # a sacrificial prefix (shares nothing with the hot set) warms
        # the gather/scatter jit of the fetch/import path off the
        # measured clock — the compile-warmup analog of
        # ``engine.warmup()``'s prefill buckets; in production the pod
        # pre-warm pays this BEHIND the ring, never on a served request
        sacrificial = prompt_of(fleet_prefix_tokens) \
            + prompt_of(fleet_suffix)
        fleet.generate(sacrificial, max_new_tokens=max_new)
        rid2 = fleet.add_replica()
        if fetch_on:
            src = next(r for r in fleet.replicas if r.id != rid2)
            dst = next(r for r in fleet.replicas if r.id == rid2)
            payload = src.engine.fetch_prefix(sacrificial).result(
                timeout=60)
            if payload is not None:
                dst.engine.import_prefix(payload).result(timeout=60)
        if warmup:
            fleet.warmup()  # compile the joiner's buckets off the clock
        first_ttfts = []
        for prompt in hot:
            _, stats = fleet.generate(prompt + prompt_of(fleet_suffix),
                                      max_new_tokens=max_new)
            if stats["replica"] != rid2:
                continue  # key did not move — not a reassignment sample
            phases = stats["timing"]["phases"]
            first_ttfts.append(stats["ttft_s"]
                               + phases.get("fetch", 0.0))
        stats = fleet.stats
        fleet.stop()
        return {
            "moved_keys": len(first_ttfts),
            "first_request_p50_ttft_ms": round(
                _percentile(first_ttfts, 0.50) * 1000, 2)
            if first_ttfts else 0.0,
            "prefix_fetches": stats["prefix_fetches"],
            "prefix_fetch_fallbacks": stats["prefix_fetch_fallbacks"],
        }

    ring = {"fetch": fetch_leg(True), "reprefill": fetch_leg(False)}
    out["ring_fetch"] = {
        "prefix_tokens": fleet_prefix_tokens,
        "fetch": ring["fetch"], "reprefill": ring["reprefill"],
        "first_request_speedup": round(
            ring["reprefill"]["first_request_p50_ttft_ms"]
            / ring["fetch"]["first_request_p50_ttft_ms"], 2)
        if ring["fetch"]["first_request_p50_ttft_ms"] > 0 else 0.0,
    }
    return out


def run_reqtrace(requests: int = 16, prefix_tokens: int = 384,
                 suffix_tokens: int = 8, max_new: int = 8,
                 page_size: int = 32, max_len: int = 512, seed: int = 0,
                 rounds: int = 2, warmup: bool = True) -> dict:
    """Request-forensics overhead A/B (docs/observability.md "Request
    attribution, exemplars & trace assembly"): the SAME repeated-prefix
    workload against the paged engine with the per-request phase ledger
    + histogram exemplars ON vs OFF. Arms alternate across ``rounds``
    and each arm keeps its best round (CPU scheduling noise averages
    out of the RATIO, the acceptance number); the on-arm additionally
    verifies every request's attribution closed (Σ phases == wall) and
    that an exemplar trace id survives to the OpenMetrics render."""
    import jax
    import numpy as np

    from mlrun_tpu.models import init_params, tiny_llama
    from mlrun_tpu.obs import REGISTRY, get_tracer
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    buckets = tuple(sorted({min(64, max_len), max_len}))
    prefix = rng.integers(0, config.vocab_size, prefix_tokens).tolist()
    prompts = [prefix + rng.integers(0, config.vocab_size,
                                     suffix_tokens).tolist()
               for _ in range(requests)]
    tracer = get_tracer()

    def measure(ledger_on: bool):
        engine = PagedContinuousBatchingEngine(
            config, params, max_len=max_len, slots=4,
            page_size=page_size, prefill_buckets=buckets,
            prefix_cache=True, request_ledger=ledger_on)
        if warmup:
            engine.warmup()
        engine.start()
        try:
            ttfts, timings, trace_ids = [], [], []
            for prompt in prompts:
                # the on-arm runs under an active span (the production
                # shape: the gateway's server.run span is active), so
                # TTFT/phase exemplars and llm.* spans are exercised
                if ledger_on:
                    with tracer.span("bench.reqtrace") as span:
                        _, stats = engine.generate(prompt,
                                                   max_new_tokens=max_new)
                        trace_ids.append(span.trace_id)
                else:
                    _, stats = engine.generate(prompt,
                                               max_new_tokens=max_new)
                ttfts.append(stats["ttft_s"])
                if "timing" in stats:
                    timings.append(stats["timing"])
            tput = _throughput(engine, prompts, max_new)
        finally:
            engine.stop()
        warm = ttfts[1:] or ttfts
        return {"p50_ttft_s": _percentile(sorted(warm), 0.50),
                "p95_ttft_s": _percentile(sorted(warm), 0.95),
                "tokens_per_sec": tput,
                "timings": timings, "trace_ids": trace_ids}

    arms = {"ledger_on": [], "ledger_off": []}
    for _ in range(max(1, rounds)):
        arms["ledger_off"].append(measure(False))
        arms["ledger_on"].append(measure(True))

    def best(arm, key, pick=min):
        return pick(r[key] for r in arms[arm])

    on_timings = [t for r in arms["ledger_on"] for t in r["timings"]]
    closed = bool(on_timings) and all(t.get("attribution_closed")
                                      for t in on_timings)
    phases_sample = {k: round(v, 6) for k, v in sorted(
        (on_timings[-1].get("phases") or {}).items())} \
        if on_timings else {}
    exemplar_present = 'trace_id="' in REGISTRY.render(openmetrics=True)
    p50_on = best("ledger_on", "p50_ttft_s")
    p50_off = best("ledger_off", "p50_ttft_s")
    return {
        "mode": "reqtrace", "requests": requests, "rounds": rounds,
        "prefix_tokens": prefix_tokens, "model": "tiny",
        "ledger_on": {
            "p50_ttft_ms": round(p50_on * 1000, 3),
            "p95_ttft_ms": round(
                best("ledger_on", "p95_ttft_s") * 1000, 3),
            "tokens_per_sec": round(
                best("ledger_on", "tokens_per_sec", max), 1),
        },
        "ledger_off": {
            "p50_ttft_ms": round(p50_off * 1000, 3),
            "p95_ttft_ms": round(
                best("ledger_off", "p95_ttft_s") * 1000, 3),
            "tokens_per_sec": round(
                best("ledger_off", "tokens_per_sec", max), 1),
        },
        "overhead_ratio_p50_ttft": round(p50_on / p50_off, 4)
        if p50_off > 0 else 0.0,
        "attribution_closed": closed,
        "requests_with_timing": len(on_timings),
        "exemplar_present": exemplar_present,
        "phases_sample": phases_sample,
    }


def run_fleet(replicas: int = 4, prefixes: int = 12,
              requests_per_prefix: int = 5, prefix_tokens: int = 96,
              suffix_tokens: int = 8, max_new: int = 8,
              page_size: int = 32, max_len: int = 256,
              n_pages: int = 22, slots: int = 2, seed: int = 0,
              warmup: bool = True) -> dict:
    """Affinity-vs-random routing A/B on an EngineFleet.

    ``n_pages`` is deliberately tight: each replica's pool holds ~2-3
    cached prefix chains plus the working set, so under random routing
    the ``prefixes`` hot chains churn every replica's LRU while affinity
    keeps each chain resident on exactly one ring owner — the fleet-level
    locality the router exists for. The workload interleaves the prefix
    families round-robin (the adversarial order for per-replica LRU)."""
    import jax
    import numpy as np

    from mlrun_tpu.models import init_params, tiny_llama
    from mlrun_tpu.serving.fleet import EngineFleet
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    # a small bucket so a prefix-hit suffix prefill dispatches a short
    # program instead of padding back up to the cold-prefill bucket
    buckets = tuple(sorted({min(16, max_len), min(128, max_len), max_len}))

    def prompt_of(length):
        return rng.integers(0, config.vocab_size, length).tolist()

    families = [prompt_of(prefix_tokens) for _ in range(prefixes)]
    repeated = []
    for _ in range(requests_per_prefix):
        for family in families:
            repeated.append(family + prompt_of(suffix_tokens))
    unique = [prompt_of(prefix_tokens + suffix_tokens)
              for _ in range(2 * replicas)]

    def make_fleet(policy):
        def factory(role):
            return PagedContinuousBatchingEngine(
                config, params, max_len=max_len, slots=slots,
                page_size=page_size, n_pages=n_pages,
                prefill_buckets=buckets)

        fleet = EngineFleet(factory, replicas=replicas, routing=policy,
                            seed=seed)
        if warmup:
            fleet.warmup()
        fleet.start()
        return fleet

    out = {"replicas": replicas, "prefixes": prefixes,
           "requests": len(repeated), "prefix_tokens": prefix_tokens,
           "page_size": page_size, "n_pages": n_pages, "model": "tiny",
           "policies": {}}
    for policy in ("affinity", "random"):
        fleet = make_fleet(policy)
        try:
            ttfts = _ttft_series(fleet, repeated, max_new)
            stats = fleet.stats
            unique_ttfts = _ttft_series(fleet, unique, max_new)
        finally:
            fleet.stop()
        out["policies"][policy] = {
            "prefix_hit_rate": round(stats["prefix_hit_rate"], 3),
            "p50_ttft_ms": round(_percentile(ttfts, 0.50) * 1000, 2),
            "p95_ttft_ms": round(_percentile(ttfts, 0.95) * 1000, 2),
            "unique_p50_ttft_ms": round(
                _percentile(unique_ttfts, 0.50) * 1000, 2),
            "redispatches": stats["redispatches"],
            "per_replica_hit_rate": {
                rid: round(r["prefix_hit_rate"], 3)
                for rid, r in stats["per_replica"].items()},
        }
    affinity = out["policies"]["affinity"]
    rand = out["policies"]["random"]
    # None, not float("inf"): json.dumps would emit bare `Infinity`,
    # breaking the one-valid-JSON-line contract for non-Python consumers
    out["hit_rate_ratio"] = round(
        affinity["prefix_hit_rate"] / rand["prefix_hit_rate"], 2) \
        if rand["prefix_hit_rate"] > 0 else None
    out["p50_ttft_speedup"] = round(
        rand["p50_ttft_ms"] / affinity["p50_ttft_ms"], 2) \
        if affinity["p50_ttft_ms"] > 0 else 0.0
    return out


def run_failslow(replicas: int = 4, prefixes: int = 12,
                 detect_rounds: int = 2, measure_rounds: int = 4,
                 prefix_tokens: int = 48, suffix_tokens: int = 8,
                 max_new: int = 4, page_size: int = 16,
                 max_len: int = 128, slots: int = 2, seed: int = 0,
                 degrade_delay: float = 0.06, warmup: bool = True) -> dict:
    """Fail-slow detection A/B (docs/observability.md "Replica health &
    fail-slow detection").

    One replica of a ``replicas``-wide fleet is chaos-degraded with
    ``fleet.degrade`` (a per-scheduler-iteration delay — every request
    still succeeds, just late; it NEVER errors, so the error-path
    machinery is structurally blind to it). Both sides run the identical
    hot-prefix workload: ``detect_rounds`` sweeps where detection is
    allowed to converge (excluded from measurement on BOTH sides), then
    ``measure_rounds`` measured sweeps.

    - **detection off**: affinity routing keeps pinning the degraded
      replica's prefix families to it, round after round.
    - **detection on**: a ``ReplicaHealthScorer`` + acting
      ``FleetAutoscaler`` tick after every request on a logical clock —
      suspect → probation (ring de-weight) → persistent-probation
      drain-and-replace through the normal below-min repair, so the
      measured phase runs on a clean fleet.

    Reports p95 TTFT per side, the speedup, zero-drop / zero-redispatch
    accounting, detection latency in ticks, and the leaked-series check
    (the replaced replica must retire its dispatch + health series)."""
    import re

    import jax
    import numpy as np

    from mlrun_tpu.chaos import FaultPoints, chaos
    from mlrun_tpu.models import init_params, tiny_llama
    from mlrun_tpu.obs import REGISTRY
    from mlrun_tpu.obs.health import ReplicaHealthScorer
    from mlrun_tpu.serving.fleet import EngineFleet
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine
    from mlrun_tpu.serving.prefix import block_chain_key
    from mlrun_tpu.service.autoscaler import FleetAutoscaler

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    buckets = tuple(sorted({min(16, max_len), min(64, max_len), max_len}))

    def prompt_of(length):
        return rng.integers(0, config.vocab_size, length).tolist()

    families = [prompt_of(prefix_tokens) for _ in range(prefixes)]
    rounds = detect_rounds + measure_rounds
    # one prompt list shared by both sides — the A/B must differ only
    # in whether detection acts
    sweeps = [[family + prompt_of(suffix_tokens) for family in families]
              for _ in range(rounds)]

    def factory(role):
        engine = PagedContinuousBatchingEngine(
            config, params, max_len=max_len, slots=slots,
            page_size=page_size, prefill_buckets=buckets)
        if warmup:
            # warm in the factory, not on the fleet: the autoscaler's
            # replacement replica must arrive compiled, or its cold
            # first dispatch pollutes the measured window
            engine.warmup()
        return engine

    def degraded_rid(fleet):
        """The replica owning the MOST prefix families — degrading it
        maximizes the traffic share affinity keeps pinning wrong."""
        owners = {}
        for family in families:
            key = block_chain_key(family, fleet.route_block_tokens,
                                  fleet.route_blocks)
            rid = fleet._ring.lookup(key)
            owners[rid] = owners.get(rid, 0) + 1
        return max(sorted(owners), key=lambda r: owners[r]), owners

    def drive(detection: bool):
        fleet = EngineFleet(factory, replicas=replicas,
                            routing="affinity", seed=seed)
        fleet.start()
        injection = None
        try:
            # warm pass: every family cached + a fast-TTFT baseline on
            # every ring owner before the degradation begins
            for family in families:
                fleet.generate(family + [1], max_new_tokens=max_new)
            rid, owners = degraded_rid(fleet)
            scaler = None
            scorer = None
            if detection:
                scorer = ReplicaHealthScorer(
                    fleet, ewma_alpha=1.0, suspect_ticks=1,
                    probation_ticks=1, recover_ticks=10,
                    probation_weight=0.05, replace_after_ticks=2)
                scaler = FleetAutoscaler(
                    fleet, scorer=scorer, dry_run=False,
                    min_replicas=replicas, max_replicas=replicas + 1,
                    hysteresis_ticks=1, cooldown_up_s=0.0,
                    cooldown_down_s=0.0, drain_grace_s=30.0,
                    queue_high=1e9, queue_low=0.0,
                    ttft_p95_high_s=-1.0, failure_rate_high=1.0)
            injection = chaos.inject(
                FaultPoints.fleet_degrade, delay=degrade_delay,
                match=lambda ctx: ctx["replica"] == rid)
            now = 0.0
            probation_tick = None
            detect_ttfts, measured = [], []
            for rnd, sweep in enumerate(sweeps):
                bucket = detect_ttfts if rnd < detect_rounds else measured
                for prompt in sweep:
                    _, stats = fleet.generate(prompt,
                                              max_new_tokens=max_new)
                    bucket.append(stats["ttft_s"])
                    if scaler is not None:
                        now += 1.0
                        scaler.tick(now)
                        if probation_tick is None and scorer.state(
                                rid) == "probation":
                            probation_tick = now
            stats = fleet.stats
            live = {r.id for r in fleet.replicas}
            leaked = sorted(
                r for r in set(re.findall(r'replica="([^"]+)"',
                                          REGISTRY.render()))
                if r.startswith(fleet._fleet_id + "-") and r not in live)
            return {
                "degraded_replica": rid,
                "degraded_families": owners[rid],
                "p95_ttft_ms": round(
                    _percentile(measured, 0.95) * 1000, 2),
                "p50_ttft_ms": round(
                    _percentile(measured, 0.50) * 1000, 2),
                "detect_p95_ttft_ms": round(
                    _percentile(detect_ttfts, 0.95) * 1000, 2),
                "dropped_requests": 0,  # every generate() returned
                "redispatches": stats["redispatches"],
                "failed": stats["failed"],
                "replaced": rid not in live,
                "probation_tick": probation_tick,
                "leaked_series": leaked,
            }
        finally:
            if injection is not None:
                injection.remove()
            fleet.stop()

    off = drive(detection=False)
    on = drive(detection=True)
    p95_off = off["p95_ttft_ms"]
    p95_on = on["p95_ttft_ms"]
    return {
        "model": "tiny", "replicas": replicas, "prefixes": prefixes,
        "degrade_delay_ms": round(degrade_delay * 1000, 1),
        "detect_rounds": detect_rounds, "measure_rounds": measure_rounds,
        "requests_measured": measure_rounds * prefixes,
        "detection_off": off, "detection_on": on,
        "p95_ttft_speedup": round(p95_off / p95_on, 2)
        if p95_on > 0 else 0.0,
        "zero_dropped": off["dropped_requests"] == 0
        and on["dropped_requests"] == 0,
        "zero_degraded_redispatches": off["redispatches"] == 0
        and on["redispatches"] == 0,
        "zero_leaked_series": not off["leaked_series"]
        and not on["leaked_series"],
    }


def run_fleet_elastic(prefixes: int = 8, requests_per_prefix: int = 3,
                      prefix_tokens: int = 48, suffix_tokens: int = 8,
                      max_new: int = 4, page_size: int = 8,
                      max_len: int = 128, slots: int = 2, seed: int = 0,
                      n_pages: int | None = None, warmup: bool = True,
                      slo_factor: float = 8.0) -> dict:
    """Closed-loop pod-elasticity bench (serving/podfleet.py), no
    cluster needed — the JobSet lifecycle runs against tests/fake_k8s.

    Phase A (join A/B): a pod joins a warmed single-replica fleet cold
    (``prewarm_max_keys=0``) vs pre-warmed (reassigned hot keys replayed
    as ``register_prefix`` imports before the ring join); the measured
    number is p95 TTFT of the FIRST request per reassigned prefix on
    the joining replica — the requests a cold join forces back through
    full prefill.

    Phase B (SLO through a preemption): an autoscaled two-replica fleet
    takes a pod kill mid-stream; the SLO target derives from the
    unloaded warm p50 (``slo_factor`` ×, machine-independent) and the
    met/violated split is reported before, during (one replica,
    reassigned keys cold on the survivor) and after recovery (the
    replacement joined pre-warmed). Every admitted request must
    complete — ``dropped_requests`` is the no-drop acceptance count."""
    import sys

    import jax
    import numpy as np

    from mlrun_tpu.models import init_params, tiny_llama
    from mlrun_tpu.obs import REGISTRY
    from mlrun_tpu.serving.fleet import EngineFleet
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine
    from mlrun_tpu.serving.podfleet import ServingPodFleet
    from mlrun_tpu.service.autoscaler import FleetAutoscaler
    from tests import fake_k8s

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    buckets = tuple(sorted({min(16, max_len), max_len}))
    # unlike run_fleet's deliberately-starved pools, this A/B isolates
    # JOIN warmth — the pool must hold the whole hot prefix set or LRU
    # churn (not cold bring-up) dominates both arms
    if n_pages is None:
        chain = -(-(prefix_tokens + suffix_tokens + max_new) // page_size)
        n_pages = max(32, prefixes * (chain + 2))

    def make_factory(engines):
        def factory(role):
            engine = PagedContinuousBatchingEngine(
                config, params, max_len=max_len, slots=slots,
                page_size=page_size, n_pages=n_pages,
                prefill_buckets=buckets)
            if warmup:
                engine.warmup()
            engines.append(engine)
            return engine

        return factory

    def prompt_of(length):
        return rng.integers(0, config.vocab_size, length).tolist()

    families = [prompt_of(prefix_tokens) for _ in range(prefixes)]

    def workload():
        out = []
        for _ in range(requests_per_prefix):
            for family in families:
                out.append(family + prompt_of(suffix_tokens))
        return out

    dropped = 0
    pod_names: list = []

    def complete(fleet, prompts):
        nonlocal dropped
        ttfts = []
        for prompt in prompts:
            try:
                _, stats = fleet.generate(prompt, max_new_tokens=max_new,
                                          timeout=600)
                ttfts.append(stats["ttft_s"])
            except Exception:  # noqa: BLE001 - a drop is the finding
                dropped += 1
        return ttfts

    def join_drill(provider, prewarm_keys):
        """Warm a 1-replica fleet, join one pod (cold or pre-warmed),
        then measure the first request per REASSIGNED prefix family."""
        engines: list = []
        factory = make_factory(engines)
        fleet = EngineFleet(factory, replicas=1,
                            route_block_tokens=page_size)
        fleet.start()
        pods = ServingPodFleet(fleet, provider, factory,
                               prewarm_max_keys=prewarm_keys)
        try:
            complete(fleet, workload())  # owner cache + hot keys
            pod_names.append(pods.scale_up("unified"))
            for _ in range(3):  # pending -> warming -> ready -> joined
                pods.tick()
            rid = next(rec["rid"] for rec in pods._pods.values())
            joiner = engines[-1]
            moved = [family for family in families
                     if fleet._ring.lookup(
                         fleet.routing_key(family)) == rid]
            hits_before = joiner.stats.get("prefix_hits", 0)
            ttfts = complete(
                fleet, [family + prompt_of(suffix_tokens)
                        for family in moved])
            hits = joiner.stats.get("prefix_hits", 0) - hits_before
            return {
                "reassigned_keys": len(moved),
                "prefix_hit_rate": round(hits / len(moved), 3)
                if moved else 0.0,
                "p95_ttft_ms": round(
                    _percentile(ttfts, 0.95) * 1000, 2),
                "p50_ttft_ms": round(
                    _percentile(ttfts, 0.50) * 1000, 2),
            }
        finally:
            fleet.stop()
            for rec in list(pods._pods.values()):
                pods._retire(rec)

    def preemption_drill(provider, cluster):
        """Autoscaled fleet through a pod kill: SLO met/violated
        before, during (one replica), and after recovery."""
        engines: list = []
        factory = make_factory(engines)
        fleet = EngineFleet(factory, replicas=1,
                            route_block_tokens=page_size)
        fleet.start()
        pods = ServingPodFleet(fleet, provider, factory)
        scaler = FleetAutoscaler(
            fleet, pods=pods, dry_run=False, min_replicas=2,
            max_replicas=3, hysteresis_ticks=1, cooldown_up_s=0.0,
            cooldown_down_s=1e9, drain_grace_s=5.0, queue_low=0.0,
            queue_high=1e9)
        try:
            complete(fleet, workload())   # hot keys before the join
            now = 0.0
            for _ in range(4):            # scale_up + 3 lifecycle ticks
                scaler.tick(now)
                now += 1.0
            pod = next(iter(pods.pods()))
            pod_names.append(pod)
            before = complete(fleet, workload())
            slo_s = slo_factor * _percentile(before, 0.50)
            cluster.kill_pod(pod)
            scaler.tick(now)              # preempt + replacement submit
            now += 1.0
            during = complete(fleet, workload())
            for _ in range(3):            # replacement warms and joins
                scaler.tick(now)
                now += 1.0
            pod_names.extend(name for name in pods.pods()
                             if name not in pod_names)
            after = complete(fleet, workload())

            def split(ttfts):
                met = sum(1 for t in ttfts if t <= slo_s)
                return {"met": met, "violated": len(ttfts) - met,
                        "p95_ttft_ms": round(
                            _percentile(ttfts, 0.95) * 1000, 2)}

            return {"slo_target_ms": round(slo_s * 1000, 2),
                    "before": split(before), "during": split(during),
                    "after": split(after)}
        finally:
            fleet.stop()
            for rec in list(pods._pods.values()):
                pods._retire(rec)

    # the fake cluster stands in for the kubernetes module for the whole
    # bench (the provider seam is identical either way)
    saved = sys.modules.get("kubernetes")
    cluster = fake_k8s.FakeCluster()
    sys.modules["kubernetes"] = fake_k8s.make_fake_kubernetes(cluster)
    try:
        from mlrun_tpu.service.runtime_handlers import KubernetesProvider

        provider = KubernetesProvider(namespace="bench")
        cold = join_drill(provider, prewarm_keys=0)
        prewarmed = join_drill(provider, prewarm_keys=64)
        preemption = preemption_drill(provider, cluster)
    finally:
        if saved is None:
            sys.modules.pop("kubernetes", None)
        else:
            sys.modules["kubernetes"] = saved
    rendered = REGISTRY.render()
    leaked = sum(1 for name in pod_names if name in rendered)
    out = {"prefixes": prefixes, "prefix_tokens": prefix_tokens,
           "page_size": page_size, "n_pages": n_pages, "model": "tiny",
           "cold_join": cold, "prewarmed_join": prewarmed,
           "preemption": preemption,
           "dropped_requests": dropped, "leaked_series": leaked}
    out["p95_ttft_speedup"] = round(
        cold["p95_ttft_ms"] / prewarmed["p95_ttft_ms"], 2) \
        if prewarmed["p95_ttft_ms"] > 0 else None
    return out


def run_reconcile(pods: int = 2, prefixes: int = 24,
                  requests_per_prefix: int = 2, prefix_tokens: int = 48,
                  suffix_tokens: int = 8, max_new: int = 4,
                  page_size: int = 8, max_len: int = 128, slots: int = 2,
                  seed: int = 0, n_pages: int | None = None,
                  warmup: bool = True) -> dict:
    """Control-plane crash-recovery A/B (docs/fault_tolerance.md
    "Control-plane crash recovery"), no cluster needed.

    Both arms run the same pre-crash story — a seed replica plus
    ``pods`` serving pods brought to ``joined`` and warmed with the hot
    prefix workload — then the control plane dies (``controller_crash``)
    and a fresh one recovers:

    - **journal**: the restarted ``ServingPodFleet`` replays its intent
      journal, adopts the still-Running pods at the ready probe phase,
      and rejoins them in ONE tick — no JobSet churn, no pre-warm
      replay.
    - **cold**: no journal survived — the orphaned JobSets are invisible
      to the new plane, and the autoscaler's below-min repair rebuilds
      capacity from scratch: new JobSets, full pre-warm replay, one pod
      lifecycle each, with the old JobSets left leaking.

    Reported per arm: the recovery wall (restart start → every pod
    joined), control-plane ticks to converge, orphaned JobSets left on
    the cluster, and ``dropped_requests`` across the whole arm (the
    no-drop acceptance count — must be 0 on both sides)."""
    import os
    import sys
    import tempfile

    import jax
    import numpy as np

    from mlrun_tpu.common.journal import IntentJournal
    from mlrun_tpu.models import init_params, tiny_llama
    from mlrun_tpu.serving.fleet import EngineFleet
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine
    from mlrun_tpu.serving.podfleet import (
        ServingPodFleet,
        controller_crash,
    )
    from mlrun_tpu.service.autoscaler import FleetAutoscaler
    from tests import fake_k8s

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    buckets = tuple(sorted({min(16, max_len), max_len}))
    if n_pages is None:
        chain = -(-(prefix_tokens + suffix_tokens + max_new) // page_size)
        n_pages = max(32, prefixes * (chain + 2))

    def make_factory(engines, warm=lambda idx: True):
        def factory(role):
            engine = PagedContinuousBatchingEngine(
                config, params, max_len=max_len, slots=slots,
                page_size=page_size, n_pages=n_pages,
                prefill_buckets=buckets)
            if warmup and warm(len(engines)):
                engine.warmup()
            engines.append(engine)
            return engine

        return factory

    def prompt_of(length):
        return rng.integers(0, config.vocab_size, length).tolist()

    families = [prompt_of(prefix_tokens) for _ in range(prefixes)]

    def workload():
        out = []
        for _ in range(requests_per_prefix):
            for family in families:
                out.append(family + prompt_of(suffix_tokens))
        return out

    def arm(journal_path):
        """One full crash/recovery cycle on a fresh fake cluster."""
        cluster = fake_k8s.FakeCluster()
        sys.modules["kubernetes"] = fake_k8s.make_fake_kubernetes(cluster)
        from mlrun_tpu.service.runtime_handlers import KubernetesProvider

        provider = KubernetesProvider(namespace="bench")
        dropped = 0

        def complete(fleet, prompts):
            nonlocal dropped
            ttfts = []
            for prompt in prompts:
                try:
                    _, stats = fleet.generate(
                        prompt, max_new_tokens=max_new, timeout=600)
                    ttfts.append(stats["ttft_s"])
                except Exception:  # noqa: BLE001 - a drop is the finding
                    dropped += 1
            return ttfts

        # pre-crash: seed replica + `pods` serving pods joined + warmed
        engines1: list = []
        factory1 = make_factory(engines1)
        fleet1 = EngineFleet(factory1, replicas=1,
                             route_block_tokens=page_size)
        fleet1.start()
        journal = IntentJournal(journal_path) if journal_path else None
        podfleet1 = ServingPodFleet(fleet1, provider, factory1,
                                    journal=journal)
        for _ in range(pods):
            podfleet1.scale_up("unified")
        for _ in range(3):  # pending -> warming -> ready -> joined
            podfleet1.tick()
        complete(fleet1, workload())
        controller_crash(bench="reconcile",
                         arm="journal" if journal_path else "cold")
        if journal is not None:
            journal.close()
        fleet1.stop()
        for rec in list(podfleet1._pods.values()):
            podfleet1._retire(rec)

        # recovery: a fresh control plane over the same cluster
        t0 = time.perf_counter()
        engines2: list = []
        factory2 = make_factory(
            engines2,
            warm=(lambda idx: idx == 0) if journal_path
            else (lambda idx: True))
        fleet2 = EngineFleet(factory2, replicas=1,
                             route_block_tokens=page_size)
        fleet2.start()
        ticks = 0
        if journal_path:
            # adopted pods are still Running and warm — the restarted
            # plane reconnects at the ready probe phase, it does NOT
            # re-run warmup. Only the in-process seed replica (engine
            # index 0, rebuilt by fleet2.start() above) warms. The
            # cold arm's brand-new pods warm from scratch — that
            # bring-up is exactly what the journal makes avoidable.
            podfleet2 = ServingPodFleet(
                fleet2, provider, factory2,
                journal=IntentJournal(journal_path))
            while ticks < 4 * (pods + 2) and (
                    not podfleet2.pods()
                    or set(podfleet2.pods().values()) != {"joined"}):
                podfleet2.tick()
                ticks += 1
        else:
            podfleet2 = ServingPodFleet(fleet2, provider, factory2)
            scaler = FleetAutoscaler(
                fleet2, pods=podfleet2, dry_run=False,
                min_replicas=1 + pods, max_replicas=2 + pods,
                hysteresis_ticks=1, cooldown_up_s=0.0,
                cooldown_down_s=1e9, drain_grace_s=5.0,
                queue_low=0.0, queue_high=1e9)
            now = 0.0
            while ticks < 8 * (pods + 2) and sum(
                    1 for phase in podfleet2.pods().values()
                    if phase == "joined") < pods:
                scaler.tick(now)
                now += 1.0
                ticks += 1
        recovery_s = time.perf_counter() - t0
        joined = [name for name, phase in podfleet2.pods().items()
                  if phase == "joined"]
        ttfts = complete(fleet2, workload())
        orphaned = len(cluster.jobsets) - len(podfleet2.pods())
        fleet2.stop()
        for rec in list(podfleet2._pods.values()):
            podfleet2._retire(rec)
        return {
            "recovery_s": round(recovery_s, 4),
            "recovery_ticks": ticks,
            "joined_pods": len(joined),
            "orphaned_jobsets": orphaned,
            "dropped_requests": dropped,
            "post_recovery_p95_ttft_ms": round(
                _percentile(ttfts, 0.95) * 1000, 2) if ttfts else None,
        }

    saved = sys.modules.get("kubernetes")
    from mlrun_tpu.utils import compile_cache

    try:
        with tempfile.TemporaryDirectory() as tmp:
            # shared persistent compile cache: every engine after the
            # first loads its executables from disk, so the timed
            # recovery wall measures control-plane work (prewarm
            # replay, tick count) — not 6x the same XLA compile
            compile_cache.configure(os.path.join(tmp, "xla-cache"))
            journal_arm = arm(os.path.join(tmp, "podfleet.jsonl"))
            cold_arm = arm(None)
    finally:
        compile_cache.disable()
        if saved is None:
            sys.modules.pop("kubernetes", None)
        else:
            sys.modules["kubernetes"] = saved
    out = {"pods": pods, "prefixes": prefixes,
           "prefix_tokens": prefix_tokens, "page_size": page_size,
           "n_pages": n_pages, "model": "tiny",
           "journal": journal_arm, "cold": cold_arm}
    out["recovery_speedup"] = round(
        cold_arm["recovery_s"] / journal_arm["recovery_s"], 2) \
        if journal_arm["recovery_s"] > 0 else None
    return out


def run_autoscale(min_replicas: int = 1, max_replicas: int = 4,
                  slots: int = 2, page_size: int = 32, max_len: int = 128,
                  prompt_tokens: int = 48, max_new: int = 4,
                  burst: int = 8, ramp: tuple = (1, 1, 3, 3, 3, 1, 0, 0),
                  seed: int = 0, warmup: bool = True,
                  slo_factor: float = 15.0,
                  prefill_cost_s: float = 0.03) -> dict:
    """Closed-loop autoscaling A/B under a synthetic load ramp.

    ``ramp`` scales the per-step offered load (``step * burst``
    concurrent requests); the middle of the ramp oversubscribes a single
    ``slots``-wide replica several times over, so queueing — not model
    math — dominates the baseline's tail TTFT. ``prefill_cost_s`` is a
    fixed per-prefill device cost injected through the ``llm.prefill``
    chaos point (each replica's scheduler thread pays it independently,
    modeling per-pod-slice prefill time — the PR 5 simulated-input-cost
    trick); without it, replicas on one host CPU contend for the same
    cores and horizontal scaling shows nothing. The SLO target is
    derived from the measured unloaded p50 (``slo_factor`` ×), making
    the claim machine-independent: the static single replica must
    violate it at peak while the autoscaled fleet absorbs the same peak
    by scaling toward ``max_replicas``, then drains back down once the
    ramp ends.
    """
    import re

    import jax
    import numpy as np

    from mlrun_tpu.chaos import chaos, always
    from mlrun_tpu.models import init_params, tiny_llama
    from mlrun_tpu.obs import REGISTRY
    from mlrun_tpu.serving.fleet import EngineFleet
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine
    from mlrun_tpu.service.autoscaler import FleetAutoscaler

    config = tiny_llama(attention_impl="reference")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    buckets = tuple(sorted({min(64, max_len), max_len}))

    def factory(role):
        engine = PagedContinuousBatchingEngine(
            config, params, max_len=max_len, slots=slots,
            page_size=page_size, prefill_buckets=buckets)
        if warmup:
            # warm BEFORE start so a replica added mid-ramp serves its
            # first request without an inline compile in its TTFT
            engine.warmup()
        return engine

    def prompt_of():
        return rng.integers(0, config.vocab_size, prompt_tokens).tolist()

    def drive(fleet, autoscaler=None):
        """One ramp pass; returns (per-step ttft lists, replica
        trajectory, scale event counts). The autoscaler ticks right
        after each step's burst is SUBMITTED — while the queue is deep —
        so it sees the load the way a scrape loop would, and a replica
        it adds serves from the next step on (routing happens at
        submit)."""
        step_ttfts = []
        trajectory = []
        ups = downs = 0

        def tick():
            nonlocal ups, downs
            if autoscaler is None:
                return
            decision = autoscaler.tick(now=time.perf_counter())
            if decision["acted"] and decision["acted"]["action"] == "add":
                ups += 1
            if decision["acted"] and \
                    decision["acted"]["action"] == "drain":
                downs += 1

        for step_load in ramp:
            futures = [fleet.submit(prompt_of(), max_new_tokens=max_new)
                       for _ in range(step_load * burst)]
            tick()
            step_ttfts.append([f.result(timeout=600)[1]["ttft_s"]
                               for f in futures])
            trajectory.append(len([r for r in fleet.replicas
                                   if not r.draining]))
        # idle ticks so the drain path completes before teardown
        for _ in range(6 if autoscaler is not None else 0):
            tick()
        if autoscaler is not None:
            trajectory.append(len([r for r in fleet.replicas
                                   if not r.draining]))
        return step_ttfts, trajectory, ups, downs

    peak = max(ramp)

    def p95_at_peak(step_ttfts):
        """p95 of the LAST peak-load step — steady state for the
        autoscaled fleet (earlier peak steps mix in the scale-up
        transition), and just another identical burst for the static
        baseline."""
        last_peak = max(i for i, load in enumerate(ramp) if load == peak)
        samples = step_ttfts[last_peak]
        return _percentile(sorted(samples), 0.95) if samples else 0.0

    from contextlib import nullcontext

    synthetic_cost = (chaos.inject("llm.prefill", always(),
                                   delay=prefill_cost_s)
                      if prefill_cost_s > 0 else nullcontext())
    with synthetic_cost:
        # unloaded reference: serial requests against one replica — the
        # queue-free service time the SLO target is derived from
        fleet = EngineFleet(factory, replicas=1)
        fleet.start()
        try:
            unloaded = _ttft_series(fleet,
                                    [prompt_of() for _ in range(6)],
                                    max_new)
        finally:
            fleet.stop()
        unloaded_p50 = _percentile(sorted(unloaded), 0.50)
        slo_target_s = slo_factor * unloaded_p50

        # baseline: static single replica through the identical ramp
        fleet = EngineFleet(factory, replicas=1)
        fleet.start()
        try:
            base_ttfts, base_traj, _, _ = drive(fleet)
        finally:
            fleet.stop()

        # autoscaled: same ramp, loop closed over the fleet signals
        fleet = EngineFleet(factory, replicas=min_replicas)
        fleet.start()
        try:
            # queue-driven scaling: the bench's offered load IS the
            # signal (the windowed ttft_slo trigger is exercised
            # deterministically in tests; the fleet's cumulative TTFT
            # ring would hold peak samples long after the ramp ends and
            # pin the fleet scaled up)
            autoscaler = FleetAutoscaler(
                fleet, dry_run=False, min_replicas=min_replicas,
                max_replicas=max_replicas, hysteresis_ticks=1,
                cooldown_up_s=0.0, cooldown_down_s=0.0,
                drain_grace_s=30.0,
                queue_high=float(slots), queue_low=0.5,
                ttft_p95_high_s=0.0, failure_rate_high=1.0)
            auto_ttfts, auto_traj, ups, downs = drive(fleet, autoscaler)
            final_replicas = len([r for r in fleet.replicas
                                  if not r.draining])
            # scale-down hygiene, checked while the fleet is still
            # live: any replica id in the registry that is no longer in
            # the fleet was removed by the autoscaler and should have
            # retired its series
            live_ids = {r.id for r in fleet.replicas}
            leaked = sorted(
                rid for rid in set(
                    re.findall(r'replica="([^"]+)"', REGISTRY.render()))
                if rid.startswith(fleet._fleet_id + "-")
                and rid not in live_ids)
        finally:
            fleet.stop()

    base_p95 = p95_at_peak(base_ttfts)
    auto_p95 = p95_at_peak(auto_ttfts)
    return {
        "model": "tiny", "slots": slots, "burst": burst,
        "ramp": list(ramp), "prompt_tokens": prompt_tokens,
        "min_replicas": min_replicas, "max_replicas": max_replicas,
        "unloaded_p50_ttft_ms": round(unloaded_p50 * 1000, 2),
        "slo_target_ms": round(slo_target_s * 1000, 2),
        "baseline": {
            "replicas": base_traj[-1],
            "peak_p95_ttft_ms": round(base_p95 * 1000, 2),
            "slo_violated": base_p95 > slo_target_s,
        },
        "autoscaled": {
            "peak_p95_ttft_ms": round(auto_p95 * 1000, 2),
            "slo_met": auto_p95 <= slo_target_s,
            "replica_trajectory": auto_traj,
            "scale_ups": ups, "scale_downs": downs,
            "final_replicas": final_replicas,
            "leaked_replica_series": leaked,
        },
        "p95_ttft_speedup": round(base_p95 / auto_p95, 2)
        if auto_p95 > 0 else 0.0,
    }


def run_lora(tenants: int = 4, requests_per_tenant: int = 6,
             prompt_tokens: int = 48, max_new: int = 8,
             page_size: int = 16, max_len: int = 128, slots: int = 4,
             rank: int = 4, seed: int = 0, warmup: bool = True) -> dict:
    """Multi-tenant LoRA serving A/B (docs/serving.md "Multi-tenant
    LoRA"): N tenants round-robin on ONE batched multi-adapter engine vs
    serving the same workload with sequential merged-weights swaps (one
    dedicated engine per tenant, built/torn down in turn — the only
    option without per-row adapters). Reports:

    - ``throughput_ratio``: multi-tenant tokens/s over the sequential
      path INCLUDING its per-tenant engine swap cost (the honest
      comparison — avoiding weight swaps is the point), plus the
      serving-only ratio with swaps excluded.
    - ``one_tenant``: the no-regression guard — a single tenant through
      the adapter path vs a dedicated merged-weights engine. The lora
      math adds a bounded per-dispatch cost; the ratio must stay near 1.
    - ``parity_ok``: greedy tokens for a sampled request are identical
      between the multi-adapter engine and that tenant's merged engine.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlrun_tpu.models import (
        init_lora_nonzero,
        init_params,
        merge_lora,
        tiny_llama,
    )
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine

    # f32 keeps the batched-delta vs merged-weights comparison at
    # accumulation-order rounding (parity_ok is a token-identity claim)
    config = tiny_llama(attention_impl="reference", dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    buckets = tuple(sorted({min(64, max_len), max_len}))

    names = [f"tenant-{i}" for i in range(tenants)]
    # nonzero-B synthetic adapters: each tenant's delta actually moves
    # logits (models/lora.init_lora_nonzero — shared with tests/smoke)
    adapters = {name: init_lora_nonzero(
        config, jax.random.PRNGKey(100 + i), rank=rank)
        for i, name in enumerate(names)}
    prompts = {name: [rng.integers(0, config.vocab_size,
                                   prompt_tokens).tolist()
                      for _ in range(requests_per_tenant)]
               for name in names}

    def make_engine(engine_params, engine_adapters=None):
        engine = PagedContinuousBatchingEngine(
            config, engine_params, max_len=max_len, slots=slots,
            page_size=page_size, prefill_buckets=buckets,
            adapters=engine_adapters)
        if warmup:
            engine.warmup()
        engine.start()
        return engine

    # -- multi-tenant: one engine, tenants round-robin interleaved ---------
    engine = make_engine(params, adapters)
    try:
        started = time.perf_counter()
        futures = []
        for r in range(requests_per_tenant):
            for name in names:
                futures.append(engine.submit(
                    prompts[name][r], max_new_tokens=max_new,
                    adapter=name))
        results = [f.result(timeout=600) for f in futures]
        multi_wall = time.perf_counter() - started
        multi_tokens = sum(len(tokens) for tokens, _ in results)
        multi_stats = engine.stats
        sample_multi = results[0][0]  # tenant-0's first request
    finally:
        engine.stop()

    # -- sequential merged-weights swaps: one dedicated engine per tenant --
    seq_serving = 0.0
    seq_swap = 0.0
    seq_tokens = 0
    sample_merged = None
    one_merged_wall = 0.0
    merged_tokens = 0
    for name in names:
        t_swap = time.perf_counter()
        merged_engine = make_engine(merge_lora(params, adapters[name]))
        seq_swap += time.perf_counter() - t_swap
        try:
            t_serve = time.perf_counter()
            futures = [merged_engine.submit(p, max_new_tokens=max_new)
                       for p in prompts[name]]
            tenant_results = [f.result(timeout=600) for f in futures]
            wall = time.perf_counter() - t_serve
            seq_serving += wall
            seq_tokens += sum(len(tokens) for tokens, _ in tenant_results)
            if name == names[0]:
                sample_merged = tenant_results[0][0]
                # this leg IS the 1-tenant merged-weights baseline —
                # no extra engine build needed for the guard below
                one_merged_wall = wall
                merged_tokens = sum(len(tokens)
                                    for tokens, _ in tenant_results)
        finally:
            merged_engine.stop()

    # -- one-tenant no-regression guard ------------------------------------
    # adapter-path leg; the merged-weights side was measured above as
    # tenant-0's sequential serving leg (identical engine + workload)
    one_prompts = prompts[names[0]]
    engine = make_engine(params, {names[0]: adapters[names[0]]})
    try:
        t0 = time.perf_counter()
        futures = [engine.submit(p, max_new_tokens=max_new,
                                 adapter=names[0]) for p in one_prompts]
        one_tokens = sum(len(f.result(timeout=600)[0]) for f in futures)
        one_adapter_wall = time.perf_counter() - t0
    finally:
        engine.stop()

    multi_tps = multi_tokens / multi_wall if multi_wall > 0 else 0.0
    seq_tps = seq_tokens / seq_serving if seq_serving > 0 else 0.0
    seq_incl_swap_tps = seq_tokens / (seq_serving + seq_swap) \
        if seq_serving + seq_swap > 0 else 0.0
    one_adapter_tps = one_tokens / one_adapter_wall \
        if one_adapter_wall > 0 else 0.0
    one_merged_tps = merged_tokens / one_merged_wall \
        if one_merged_wall > 0 else 0.0
    return {
        "model": "tiny", "tenants": tenants,
        "requests_per_tenant": requests_per_tenant,
        "prompt_tokens": prompt_tokens, "rank": rank, "slots": slots,
        "multi_tokens_per_sec": round(multi_tps, 1),
        "sequential_tokens_per_sec": round(seq_tps, 1),
        "sequential_incl_swap_tokens_per_sec": round(seq_incl_swap_tps, 1),
        "swap_s_total": round(seq_swap, 3),
        "throughput_ratio": round(multi_tps / seq_incl_swap_tps, 2)
        if seq_incl_swap_tps > 0 else 0.0,
        "serving_only_ratio": round(multi_tps / seq_tps, 2)
        if seq_tps > 0 else 0.0,
        "one_tenant": {
            "adapter_tokens_per_sec": round(one_adapter_tps, 1),
            "merged_tokens_per_sec": round(one_merged_tps, 1),
            "throughput_ratio": round(one_adapter_tps / one_merged_tps, 2)
            if one_merged_tps > 0 else 0.0,
        },
        "parity_ok": sample_multi == sample_merged,
        "adapter_loads": multi_stats.get("adapter_loads", 0),
        "adapter_live": multi_stats.get("adapter_live", 0),
        "metrics": _metrics_snapshot(multi_stats),
    }


def run_spec(requests: int = 8, prompt_tokens: int = 24, max_new: int = 32,
             k: int = 4, page_size: int = 16, max_len: int = 128,
             slots: int = 4, tick_cost_s: float = 0.15,
             overlap: float = 0.85, seed: int = 0,
             warmup: bool = True) -> dict:
    """In-engine speculative decoding A/B on the paged engine
    (docs/serving.md "Speculative decoding"): the identical workload —
    half the rows under a LoRA tenant — served spec-off, spec-on with a
    partial-agreement draft, and spec-on with an adversarial draft
    (near-zero acceptance: the per-row gate must park, not regress).

    Deterministic permutation models (``init_permutation_params``) make
    acceptance a controlled dial (``overlap``) AND make greedy parity a
    hard token-identity assertion in every arm. A per-scheduler-tick
    ``fleet.degrade`` delay injection models the fixed device cost one
    dispatch costs a real accelerator at production model scale — the
    quantity speculation amortizes: a spec tick pays it once for
    k-plus-one-token verify, a plain tick pays it per token. The
    default (150 ms) is sized so it dominates this CPU harness's python
    scheduling overhead the way a large-model forward dominates the
    host loop on a TPU. Reports tokens/s per arm,
    ``speedup`` (spec-on over spec-off), ``adversarial_ratio`` (must
    stay ~1: parked speculation may not tax the fleet), and the parity
    booleans."""
    import dataclasses

    import jax
    import numpy as np

    from mlrun_tpu.chaos import FaultPoints, always, chaos
    from mlrun_tpu.models import (
        init_lora_nonzero,
        init_permutation_params,
        permutation_pair,
        tiny_llama,
    )
    from mlrun_tpu.serving.paged import PagedContinuousBatchingEngine

    config = dataclasses.replace(tiny_llama(attention_impl="reference"),
                                 vocab_size=64, tie_embeddings=False)
    target_perm, draft_perm = permutation_pair(config.vocab_size, overlap,
                                               seed=seed)
    target = init_permutation_params(config, target_perm)
    draft = init_permutation_params(config, draft_perm)
    adversarial = init_permutation_params(
        config, np.roll(np.asarray(target_perm), 7), seed=3)
    # tiny delta: exercises the adapter-bearing dispatch without leaving
    # the permutation model's argmax-stability regime (parity stays a
    # token-identity claim)
    lora = init_lora_nonzero(config, jax.random.PRNGKey(5), rank=2,
                             alpha=0.1, b_scale=0.001)

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, config.vocab_size, prompt_tokens).tolist()
               for _ in range(requests)]
    buckets = tuple(sorted({min(64, max_len), max_len}))

    def drive(spec_conf):
        engine = PagedContinuousBatchingEngine(
            config, target, max_len=max_len, slots=slots,
            page_size=page_size, prefill_buckets=buckets,
            adapters={"tenant-0": lora}, speculative=spec_conf,
            # the queue backlog is the offered load, not pressure — keep
            # the ladder parked at level 0 so the A/B measures the spec
            # path, not the ladder's fleet-wide park
            degradation={"queue_depth": requests + slots})
        if warmup:
            engine.warmup()
        engine.start()
        try:
            with chaos.inject(FaultPoints.fleet_degrade, always(),
                              delay=tick_cost_s):
                started = time.perf_counter()
                futures = [engine.submit(
                    prompt, max_new_tokens=max_new,
                    adapter="tenant-0" if i % 2 else None)
                    for i, prompt in enumerate(prompts)]
                results = [f.result(timeout=600) for f in futures]
                wall = time.perf_counter() - started
            stats = engine.stats
        finally:
            engine.stop()
        streams = [tokens for tokens, _ in results]
        tokens_total = sum(len(s) for s in streams)
        tps = tokens_total / wall if wall > 0 else 0.0
        return tps, stats, streams

    spec_on_conf = {"enabled": True, "k": k, "draft_config": config,
                    "draft_params": draft}
    adv_conf = {"enabled": True, "k": k, "draft_config": config,
                "draft_params": adversarial}
    off_tps, off_stats, off_streams = drive(None)
    on_tps, on_stats, on_streams = drive(spec_on_conf)
    adv_tps, adv_stats, adv_streams = drive(adv_conf)

    adapter_rows = [i for i in range(requests) if i % 2]

    def arm(tps, stats):
        return {
            "tokens_per_sec": round(tps, 1),
            "acceptance_rate": round(stats.get("acceptance_rate", 0.0), 3),
            "spec_rounds": stats.get("spec_rounds", 0),
            "spec_tokens_per_round": round(
                stats.get("spec_tokens_per_round", 0.0), 2),
        }

    return {
        "mode": "spec", "model": "tiny-perm", "requests": requests,
        "prompt_tokens": prompt_tokens, "max_new": max_new, "k": k,
        "slots": slots, "overlap": overlap,
        "tick_cost_ms": round(tick_cost_s * 1000, 3),
        "spec_off": arm(off_tps, off_stats),
        "spec_on": arm(on_tps, on_stats),
        "adversarial": arm(adv_tps, adv_stats),
        "speedup": round(on_tps / off_tps, 2) if off_tps > 0 else 0.0,
        "adversarial_ratio": round(adv_tps / off_tps, 2)
        if off_tps > 0 else 0.0,
        "greedy_parity": on_streams == off_streams
        and adv_streams == off_streams,
        "adapter_parity": all(on_streams[i] == off_streams[i]
                              for i in adapter_rows),
        "metrics": _metrics_snapshot(on_stats),
    }


def _canary_tune_handler(context, tenant="", output_path="", **kwargs):
    """The fine-tune job the canary bench's loop submits (local
    launcher): a deterministic 'retrained' adapter artifact."""
    import jax
    import jax.numpy as jnp

    from mlrun_tpu.models import init_lora_nonzero, tiny_llama
    from mlrun_tpu.serving.adapters import save_adapter

    config = tiny_llama(attention_impl="reference", dtype=jnp.float32)
    lora = init_lora_nonzero(config, jax.random.PRNGKey(4242), rank=4,
                             alpha=8.0)
    save_adapter(output_path, lora)
    context.log_result("adapter", output_path)


def run_canary(requests_per_step: int = 6, steps: int = 10,
               prompt_tokens: int = 24, max_new: int = 8,
               max_len: int = 64, slots: int = 2, rank: int = 4,
               fraction: float = 0.5, seed: int = 0,
               warmup: bool = True) -> dict:
    """Continuous fine-tune→canary→promote closed loop
    (docs/continuous_tuning.md): drift is injected deterministically via
    the ``monitor.drift`` chaos point, the loop runs on a virtual tick
    clock (the controller takes an explicit ``now``), and the bench
    measures the REAL wall costs the loop adds:

    - ``detection_to_promotion_s``: wall seconds from the tick that
      confirmed drift to the tick that promoted — retrain + canary
      evaluation machinery end to end.
    - ``stable_overhead_ratio``: p50 TTFT of STABLE-side requests while
      monitoring + the canary hash split are active, over a baseline
      engine with no monitoring at all (the no-regression guard for the
      stable path).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlrun_tpu.chaos import FaultPoints, chaos
    from mlrun_tpu.model_monitoring import ContinuousTuningController
    from mlrun_tpu.models import init_lora_nonzero, init_params, tiny_llama
    from mlrun_tpu.serving.llm_batch import ContinuousBatchingEngine

    config = tiny_llama(attention_impl="reference", dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    stable_adapter = init_lora_nonzero(config, jax.random.PRNGKey(100),
                                       rank=rank, alpha=8.0)
    tenant = "tenant-0"
    prompts = [rng.integers(0, config.vocab_size,
                            prompt_tokens).tolist()
               for _ in range(requests_per_step)]
    buckets = (min(32, max_len),)

    def make_engine():
        engine = ContinuousBatchingEngine(
            config, params, max_len=max_len, slots=slots,
            prefill_buckets=buckets, adapters={tenant: stable_adapter})
        if warmup:
            engine.warmup()
        engine.start()
        return engine

    def drive(engine, step):
        ttfts = []
        for i, prompt in enumerate(prompts):
            _, stats = engine.generate(prompt, max_new_tokens=max_new,
                                       adapter=tenant,
                                       request_key=f"s{step}-r{i}")
            ttfts.append(stats["ttft_s"])
        return ttfts

    # -- baseline: same engine + workload, no monitoring anywhere ----------
    engine = make_engine()
    try:
        baseline_ttfts = []
        for step in range(steps):
            baseline_ttfts += drive(engine, step)
    finally:
        engine.stop()

    # -- monitored: the closed loop on a virtual tick clock ----------------
    def drift_action(point, ctx):
        box = ctx["box"]
        if ctx["adapter"] == tenant:
            box["drifted"] = True
            box["stats"]["quality_mean"] = 0.5
        elif ctx["adapter"].startswith(tenant + "@"):
            box["stats"]["quality_mean"] = 0.9

    engine = make_engine()
    controller = ContinuousTuningController(
        engine, project="bench-canary", retrain_kind="local",
        retrain_handler=_canary_tune_handler, confirm_ticks=2,
        cooldown_s=600.0, fraction=fraction, warmup_s=0.0,
        fast_window_s=30.0, slow_window_s=60.0, ttft_target_s=10.0,
        promote_ticks=2, rollback_ticks=2, reference_min=4,
        window_min=4, vocab_size=config.vocab_size).start()
    injection = chaos.inject(FaultPoints.monitor_drift,
                             action=drift_action)
    stable_ttfts = []
    canary_requests = 0
    detected_wall = promoted_wall = None
    retrain_wall = 0.0
    now = 0.0
    started = time.perf_counter()
    try:
        for step in range(steps):
            router = controller.router
            for i, prompt in enumerate(prompts):
                key = f"s{step}-r{i}"
                _, stats = engine.generate(prompt, max_new_tokens=max_new,
                                           adapter=tenant,
                                           request_key=key)
                _, side = router.resolve(tenant, key)
                if side == "canary":
                    canary_requests += 1
                else:
                    stable_ttfts.append(stats["ttft_s"])
            now += 10.0
            t_tick = time.perf_counter()
            out = controller.tick(now)
            tick_wall = time.perf_counter() - t_tick
            for action in out["actions"]:
                if action["action"] == "retrain":
                    detected_wall = time.perf_counter() - started
                    retrain_wall = tick_wall
                if action["action"] == "promote" \
                        and promoted_wall is None:
                    promoted_wall = time.perf_counter() - started
            if promoted_wall is not None:
                break
    finally:
        injection.remove()
        controller.stop()
        engine.stop()

    base_p50 = _percentile(sorted(baseline_ttfts), 0.50) \
        if baseline_ttfts else 0.0
    stable_p50 = _percentile(sorted(stable_ttfts), 0.50) \
        if stable_ttfts else 0.0
    return {
        "model": "tiny", "steps": steps,
        "requests_per_step": requests_per_step,
        "prompt_tokens": prompt_tokens, "fraction": fraction,
        "promoted": promoted_wall is not None,
        "promoted_adapter": controller.router.stable_id(tenant),
        "detection_wall_s": round(detected_wall, 3)
        if detected_wall is not None else None,
        "detection_to_promotion_s": round(
            promoted_wall - detected_wall, 3)
        if promoted_wall is not None and detected_wall is not None
        else None,
        "retrain_tick_wall_s": round(retrain_wall, 3),
        "canary_requests": canary_requests,
        "stable_requests": len(stable_ttfts),
        "baseline_ttft_p50_s": round(base_p50, 5),
        "stable_ttft_p50_monitoring_s": round(stable_p50, 5),
        "stable_overhead_ratio": round(stable_p50 / base_p50, 3)
        if base_p50 > 0 else 0.0,
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--fleet", action="store_true",
                        help="run the engine-fleet routing A/B instead")
    parser.add_argument("--autoscale", action="store_true",
                        help="run the closed-loop autoscaling A/B instead")
    parser.add_argument("--lora", action="store_true",
                        help="run the multi-tenant LoRA serving A/B "
                             "instead")
    parser.add_argument("--canary", action="store_true",
                        help="run the continuous fine-tune→canary→"
                             "promote closed-loop bench instead")
    parser.add_argument("--reqtrace", action="store_true",
                        help="run the request-forensics (phase ledger + "
                             "exemplars) overhead A/B instead")
    parser.add_argument("--prefill-kernel", action="store_true",
                        help="run the paged prefill kernel + int8 KV "
                             "pages A/B instead")
    parser.add_argument("--fleet-elastic", action="store_true",
                        help="run the pod-elasticity bench (cold vs "
                             "pre-warmed join, SLO through a "
                             "preemption) instead")
    parser.add_argument("--reconcile", action="store_true",
                        help="run the control-plane crash-recovery A/B "
                             "(journaled reconcile vs cold rebuild) "
                             "instead")
    parser.add_argument("--failslow", action="store_true",
                        help="run the fail-slow replica detection A/B "
                             "(one chaos-degraded replica, detection "
                             "off vs on) instead")
    parser.add_argument("--kv-tier", action="store_true",
                        help="run the hierarchical KV cache A/B (host "
                             "tier at fixed device bytes + ring-"
                             "reassignment fetch vs re-prefill) instead")
    parser.add_argument("--spec", action="store_true",
                        help="run the in-engine speculative decoding "
                             "A/B (spec-off vs spec-on vs adversarial "
                             "draft on the paged engine) instead")
    parser.add_argument("--pods", type=int, default=2)
    parser.add_argument("--tenants", type=int, default=4)
    # shared flags default to None so each mode keeps its own scale:
    # the prefix-cache bench stresses ONE engine with long prompts,
    # while the fleet A/B spreads many short hot prefixes over pools
    # deliberately too small to hold them all
    parser.add_argument("--requests", type=int, default=12)
    parser.add_argument("--prefix-tokens", type=int, default=None)
    parser.add_argument("--suffix-tokens", type=int, default=None)
    parser.add_argument("--max-new", type=int, default=None)
    parser.add_argument("--page-size", type=int, default=None)
    parser.add_argument("--max-len", type=int, default=None)
    parser.add_argument("--replicas", type=int, default=4)
    parser.add_argument("--prefixes", type=int, default=12)
    parser.add_argument("--requests-per-prefix", type=int, default=5)
    args = parser.parse_args(argv)

    def overrides(**defaults):
        return {key: (value if getattr(
            args, key) is None else getattr(args, key))
            for key, value in defaults.items()}

    if args.spec:
        result = run_spec(requests=args.requests,
                          **overrides(max_new=32, page_size=16,
                                      max_len=128))
    elif args.failslow:
        result = run_failslow(
            replicas=args.replicas, prefixes=args.prefixes,
            **overrides(prefix_tokens=48, suffix_tokens=8, max_new=4,
                        page_size=16, max_len=128))
    elif args.kv_tier:
        result = run_kv_tier(
            prefixes=args.prefixes,
            requests_per_prefix=args.requests_per_prefix,
            **overrides(prefix_tokens=56, suffix_tokens=8, max_new=4,
                        page_size=8, max_len=128))
    elif args.reconcile:
        result = run_reconcile(
            pods=args.pods, prefixes=args.prefixes,
            requests_per_prefix=args.requests_per_prefix,
            **overrides(prefix_tokens=48, suffix_tokens=8, max_new=4,
                        page_size=8, max_len=128))
    elif args.fleet_elastic:
        result = run_fleet_elastic(
            prefixes=args.prefixes,
            requests_per_prefix=args.requests_per_prefix,
            **overrides(prefix_tokens=48, suffix_tokens=8, max_new=4,
                        page_size=8, max_len=128))
    elif args.prefill_kernel:
        result = run_prefill_kernel(
            requests=args.requests, prefixes=args.prefixes,
            requests_per_prefix=args.requests_per_prefix,
            **overrides(prefix_tokens=192, suffix_tokens=8, max_new=8,
                        page_size=32, max_len=256))
    elif args.reqtrace:
        result = run_reqtrace(requests=args.requests,
                              **overrides(prefix_tokens=384,
                                          suffix_tokens=8, max_new=8,
                                          page_size=32, max_len=512))
    elif args.canary:
        result = run_canary(**overrides(max_new=8, max_len=64))
    elif args.lora:
        result = run_lora(tenants=args.tenants,
                          **overrides(max_new=8, page_size=16,
                                      max_len=128))
    elif args.autoscale:
        result = run_autoscale(max_replicas=args.replicas)
    elif args.fleet:
        result = run_fleet(replicas=args.replicas, prefixes=args.prefixes,
                           requests_per_prefix=args.requests_per_prefix,
                           **overrides(prefix_tokens=96, suffix_tokens=8,
                                       max_new=8, page_size=32,
                                       max_len=256))
    else:
        result = run(requests=args.requests,
                     **overrides(prefix_tokens=960, suffix_tokens=8,
                                 max_new=16, page_size=32, max_len=1024))
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
