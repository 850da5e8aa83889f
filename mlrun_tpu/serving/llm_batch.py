"""Continuous batching for the TPU LLM engine.

Slot-based scheduler over a fixed-size decode batch (the vLLM-style design,
TPU-shaped): the KV cache is a static [layers, slots, max_len, heads, dim]
allocation so every decode dispatch is ONE compiled program regardless of
which requests occupy the slots. Requests are admitted into free slots by a
bucketed batch=1 prefill whose kv rows are inserted into the big cache with
`dynamic_update_slice`; decode then advances every active slot one token per
step with per-row positions (per-row RoPE tables + scatter cache writes).
Finished rows free their slot for the next queued request — no
head-of-line blocking on long generations.

The reference has no inference engine at all (its V2ModelServer calls user
predict(), mlrun/serving/v2_serving.py); this is the TPU-native capability
behind the <200ms p50 TTFT target under concurrency (BASELINE.md).
"""

from __future__ import annotations

import functools
import gc
import math
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field, replace as dataclass_replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..chaos import FaultPoints, fire
from ..config import mlconf
from ..models.llama import LlamaConfig, Params, embed, head_logits
from ..obs import (
    ADAPTER_LIVE,
    ADAPTER_LOADS,
    LLM_DECODE_TICK,
    LLM_EVENTS,
    LLM_FREE_PAGE_FRAC,
    LLM_ITL,
    LLM_KV_BYTES_PER_TOKEN,
    LLM_STATE_BYTES_PER_SLOT,
    LLM_WINDOW_BYTES_PER_SLOT,
    LLM_QUEUE_DEPTH,
    LLM_SPEC_ROUNDS,
    LLM_SPEC_TOKENS,
    LLM_TTFT,
    LLM_WEIGHTS_RELAID_BYTES,
    REGISTRY,
    RequestLedger,
    TickRecord,
    export_phases,
    flight_record,
    get_flight_recorder,
    get_tick_log,
    get_tracer,
    register_memory_collector,
    wall_now,
)
from ..obs import ticklog
from ..obs.stats import nearest_rank
from ..ops.rotary import rope_table
from ..utils import logger
from ..utils.profiler import annotate, named
from ..utils.profiler import tick as profiler_tick
from .canary import get_canary_router, split_key_for
from .llm import (
    _cached_attention,
    _dense_kv_write,
    LatentCacheError,  # noqa: F401 - re-exported beside BlockDecodingError
    RecurrentStateError,  # noqa: F401 - likewise
    _forward_with_cache,
    _serving_layers,
    _stacked_cache,
    init_kv_cache,
    refuse_layout,
    relaid_bytes,
    serving_tree,
)
from .window import window_pairs
from .samples import emit_sample, sampling_enabled
from .resilience import (  # noqa: F401 - EngineStoppedError re-exported
    DeadlineExceeded,
    DegradationLadder,
    EngineStoppedError,
    PromptTooLongError,
    QueueFullError,
)


def _decode_rowwise(config: LlamaConfig, params: Params, tokens: jax.Array,
                    cache: dict, rng: jax.Array = None,
                    temperature: jax.Array = None,
                    top_k: jax.Array = None, top_p: jax.Array = None,
                    lora=None, adapter_ids: jax.Array = None):
    """One decode token per row with PER-ROW positions (slots at different
    generation depths). tokens: [B, 1]; cache rows advance independently.

    Per-row sampling settings (temperature/top_k/top_p arrays) ride the
    same compiled program: greedy rows (temperature 0) take an exact
    argmax via jnp.where — see serving/sampling.py.

    ``lora``/``adapter_ids`` add per-row multi-tenant LoRA
    (docs/serving.md "Multi-tenant LoRA"): each slot gathers its OWN
    (A, B) factors from the stacked adapter bank by its [B] slot index
    (0 = base model / inactive rows), so a mixed-tenant batch decodes in
    one compiled program."""
    start = cache["pos"]                      # [B]
    positions = start[:, None]                # [B, 1]
    rows = jnp.arange(tokens.shape[0])
    x = embed(config, params, tokens)
    cos, sin = rope_table(positions, config.head_dim, config.rope_theta)
    new = {name: [] for name in cache if name != "pos"}

    def attend(layer, q, k, v):
        # per-row scatter at each row's own position
        k_attn, v_attn = _dense_kv_write(
            config, cache, new, layer, k[:, 0], v[:, 0],
            lambda buffer, token: buffer.at[rows, start].set(token))
        return _cached_attention(config, q, k_attn, v_attn, positions,
                                 cache["k"].shape[2])

    x, _ = _serving_layers(config, params, x, cos, sin, attend, lora,
                           adapter_ids)
    logits = head_logits(config, params, x)[:, 0]
    if rng is None:
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        from .sampling import sample_logits

        next_token = sample_logits(logits, rng, temperature, top_k, top_p)
    return next_token, _stacked_cache(new, cache["pos"] + 1)


def _verify_rowwise(config: LlamaConfig, params: Params, chunk: jax.Array,
                    cache: dict, lora=None, adapter_ids: jax.Array = None):
    """Batched multi-token speculative verify with PER-ROW positions
    (docs/serving.md "Speculative decoding"). ``chunk``: [B, S] = each
    row's committed last token followed by its k draft proposals, at
    positions ``pos[r]..pos[r]+S-1``. ONE forward computes the target's
    argmax at ALL S positions — the chunk attends the dense cache in
    place under per-position causal masking, no ``all_logits`` dense
    replay of the prefix.

    Rollback contract (same as the batch=1 path's ``cache['pos']``
    rewind): the chunk's KV is scattered at its positions BEFORE
    attention reads, but ``pos`` is NOT advanced here — the host commits
    it to the accepted length afterwards, so entries past the accepted
    position are stale-but-unreadable and get overwritten before any
    later query can attend them. Rows speculating fewer than S-1 tokens
    simply have their trailing writes land past the committed position
    (same stale-entry argument); writes past ``max_len`` drop
    (``mode="drop"``) rather than clamp, so a row at the cache tail
    never has a garbage lane collide with its real last entry."""
    b, s = chunk.shape
    start = cache["pos"]                               # [B]
    positions = start[:, None] + jnp.arange(s)[None, :]  # [B, S]
    rows = jnp.arange(b)[:, None]                      # [B, 1]
    x = embed(config, params, chunk)
    cos, sin = rope_table(positions, config.head_dim, config.rope_theta)
    new = {name: [] for name in cache if name != "pos"}

    def attend(layer, q, k, v):
        k_attn, v_attn = _dense_kv_write(
            config, cache, new, layer, k, v,
            lambda buffer, lanes: buffer.at[rows, positions].set(
                lanes, mode="drop"))
        return _cached_attention(config, q, k_attn, v_attn, positions,
                                 cache["k"].shape[2])

    x, _ = _serving_layers(config, params, x, cos, sin, attend, lora,
                           adapter_ids)
    logits = head_logits(config, params, x)
    verified = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B, S]
    return verified, _stacked_cache(new, cache["pos"])


# distinct `engine` label per instance on the shared gauges/counters
_ENGINE_SEQUENCE = iter(range(1, 1 << 30))


class BlockDecodingError(ValueError):
    """A model that generates by diffusion over blocks
    (``config.block_length`` > 1, docs/serving.md "Block-diffusion
    decoding") was asked for something its decoding cannot do here: a dense
    engine, speculation, a page or prefill chunk its blocks do not divide,
    more denoising steps than a block has positions, sampling inside a
    block, an unmasking rule that is not implemented, a KV handoff."""


def _percentile(sorted_samples: list, q: float) -> float:
    """Nearest-rank percentile over an already-sorted sample list (the
    shared ``obs.stats.nearest_rank`` helper; kept as a module name for
    existing importers, e.g. serving/fleet.py)."""
    return nearest_rank(sorted_samples, q)


@dataclass
class KVHandoff:
    """Prefill→decode KV handoff payload (docs/serving.md "Engine fleet").

    The serialization boundary is the batch=1 admission slot-cache — the
    same pytree ``gather_prefix_pages``/``insert_prompt_pages`` already
    move between the page pool and a slot — held as HOST numpy arrays
    trimmed to the prompt rows, so the payload can cross a process
    boundary as plain arrays. A prefill replica produces one via
    ``submit_prefill()``; a decode replica consumes it via
    ``submit_prefilled()`` and decodes token-identically to the
    single-engine path (greedy).

    Wire format: ``kv`` always carries ``k``/``v`` ``[L, prompt_len,
    Hkv, D]``; on an int8 pool (``kv_dtype == "int8"``) they stay int8
    and the per-vector f32 dequant scales ride alongside as
    ``k_scale``/``v_scale`` ``[L, prompt_len, Hkv]`` — a quantized
    handoff is never densified to the native dtype on either side
    (half the bytes on the wire, and the decode pool imports the exact
    int8 values the prefill pool computed)."""

    prompt: list
    first_token: int
    kv: dict                     # {"k","v"[, "k_scale","v_scale"]}: numpy
    prompt_len: int
    kv_dtype: str = "native"     # "native" | "int8" — the pool dtype the
    #                              payload was exported from
    cached_prefix: int = 0       # prompt tokens served from the prefill
    #                              replica's prefix cache
    sampling: tuple = (0.0, 0, 1.0)
    prefill_s: float = 0.0       # submit→export wall time on the prefill
    #                              replica (chunk scheduling included)
    replica: str = ""            # prefill replica id (fleet bookkeeping)
    adapter: str = ""            # tenant id the KV was computed under —
    #                              the decode replica MUST decode with the
    #                              same adapter (docs/serving.md
    #                              "Multi-tenant LoRA")
    timing: Optional[dict] = None  # prefill-side phase-ledger summary
    #                              (obs/reqledger.py) the fleet merges
    #                              into the request's end-to-end timing
    prewarm: bool = False        # pre-warm replay (serving/podfleet.py):
    #                              the importing engine REGISTERS the
    #                              imported pages in its prefix index so
    #                              the reassigned key's first real
    #                              request is a cache hit (a plain
    #                              decode-pool import never registers —
    #                              that pool serves no prefills)

    def nbytes(self) -> int:
        return int(sum(arr.nbytes for arr in self.kv.values()))


@dataclass
class _Admission:
    """A request claimed off the queue and being prefilled into a slot.

    With chunked prefill the same admission resumes across scheduler
    ticks: ``offset`` is the absolute prefill cursor (it starts at
    ``base`` > 0 on a paged prefix-cache hit, where the cached prefix KV
    was gathered into ``small`` instead of recomputed)."""

    slot: int
    request_id: int
    prompt: list
    max_new: int
    eos_id: Optional[int]
    future: Future
    submitted: float
    sampling: tuple
    expires: Optional[float]
    small: dict = None
    base: int = 0
    offset: int = 0
    chunks: int = 0
    first_token: int = -1
    # trace context captured at submit ((trace_id, parent_span_id)) and
    # the wall clock when the request was claimed off the queue — the
    # scheduler emits the llm.prefill span from these
    trace: Optional[tuple] = None
    claimed: float = 0.0
    # paged-engine bookkeeping (unused by the dense engine)
    page_ids: object = None
    pages: list = field(default_factory=list)
    prefix_nodes: list = field(default_factory=list)
    # prefix-hit kernel path (docs/serving.md "Attention kernels"): the
    # cached prefix was NOT gathered into ``small`` — prefill dispatches
    # attend the shared pages in place through ``prefix_ids`` (full
    # pages_per_slot length, -1 past the prefix) and LSE-merge
    kernel_prefix: bool = False
    prefix_ids: object = None
    # fleet disaggregation (docs/serving.md "Engine fleet"): an export
    # admission resolves its future with a KVHandoff instead of
    # activating a decode slot; a prefilled admission arrived WITH its
    # KV (imported handoff) and skips the prefill dispatch entirely
    export: bool = False
    prefilled: bool = False
    # prewarm import: register the imported pages in the prefix index
    # (see KVHandoff.prewarm)
    register_import: bool = False
    # multi-tenant LoRA: the request's adapter name and its device bank
    # slot (resolved at admission by AdapterRegistry.ensure_loaded)
    adapter: str = ""
    adapter_slot: int = 0
    # monitoring tap (serving/samples.py): first-token top1-top2 logit
    # gap, captured at prefill only while a sample observer is armed
    logit_margin: float = float("nan")
    # per-request phase ledger (obs/reqledger.py): phase transitions
    # sum to the request wall by construction; None when disabled
    ledger: Optional[RequestLedger] = None


@dataclass
class _Slot:
    request_id: int = -1
    tokens: list = field(default_factory=list)
    remaining: int = 0
    eos_id: Optional[int] = None
    future: Optional[Future] = None
    started: float = 0.0
    ttft: float = 0.0
    prompt_len: int = 0
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    # trace context + decode-phase start (wall clock) for the llm.decode
    # span emitted at finish
    trace: Optional[tuple] = None
    decode_started: float = 0.0
    # multi-tenant LoRA: the occupying request's adapter + bank slot
    # (the decode tick gathers per-row factors by adapter_slot)
    adapter: str = ""
    adapter_slot: int = 0
    # monitoring tap: threaded from the admission for the finish sample
    logit_margin: float = float("nan")
    # per-request phase ledger, handed over from the admission; the
    # decode loop flips it decode_active/decode_stall around every tick
    ledger: Optional[RequestLedger] = None
    # what the request asked for: ``_finish`` cuts the answer to it (a
    # block model denoises its last block whole and may overrun)
    max_new: int = 0
    # block-diffusion decoding (serving/paged.py ``_denoise_tick``). The
    # counts, which the host advances as it dispatches a pass: the block
    # being filled in starts at absolute position ``block_base``,
    # ``block_m0`` its masked count when it was opened, ``passes_in_block``
    # the denoising passes dispatched for it, ``block_left`` the positions
    # those leave masked. The values, which follow a pass behind as each
    # one lands: ``block_ids`` the block's ids so far, ``block_masked``
    # which positions are still masked (host state, never inferred from an
    # id), ``block_pass`` the pass that unmasked each position (-1: given
    # by the prompt) and ``block_confidence`` the confidence it had then.
    # ``unmask_pass`` and ``unmask_confidence`` are those two for every
    # committed generated position, in position order
    block_base: int = 0
    block_ids: list = field(default_factory=list)
    block_masked: list = field(default_factory=list)
    block_m0: int = 0
    passes_in_block: int = 0
    block_left: int = 0
    block_pass: list = field(default_factory=list)
    block_confidence: list = field(default_factory=list)
    unmask_pass: list = field(default_factory=list)
    unmask_confidence: list = field(default_factory=list)

    @property
    def active(self) -> bool:
        return self.request_id >= 0


class ContinuousBatchingEngine:
    """Admission + decode loop over a fixed slot batch.

    ``submit()`` is thread-safe and returns a Future resolving to
    (tokens, stats). All device dispatch happens on the single scheduler
    thread, so the engine serializes TPU access by construction.
    """

    # whether the engine has a tick for ``config.block_length`` > 1
    _serves_blocks = False
    # whether its cache takes a latent family's rows and a recurrent
    # family's state (the paged pool does)
    _serves_latent = False

    def __init__(self, config: LlamaConfig, params: Params,
                 max_len: int = 2048, slots: int = 4,
                 prefill_buckets: tuple = (128, 512, 1024),
                 seed: int = 0, kv_dtype: str = "native",
                 max_queue_size: int = 0, max_wait: float = 0.0,
                 degradation: dict | None = None,
                 prefill_chunk: int | None = None,
                 latency_window: int | None = None,
                 attention_impl: str | None = None,
                 adapters=None, max_live_adapters: int | None = None,
                 adapter_rate: float | None = None,
                 adapter_burst: float | None = None,
                 request_ledger: bool | None = None,
                 speculative: dict | None = None):
        from ..ops.attention import resolve_prefill_impl
        from .adapters import AdapterRegistry, TenantRateLimiter

        self.config = config
        # the engine's own tree: wq, wk, wv in the layout their products
        # contract over (serving/llm.py ``serving_tree``); a tree that
        # already is, is taken as it is
        self.params = serving_tree(config, params)
        self.max_len = max_len
        self.slots = slots
        self.kv_dtype = kv_dtype
        # a model that generates by diffusion over blocks: only the paged
        # engine has the tick that serves it
        self.block_length = int(getattr(config, "block_length", 1))
        if self.block_length > 1 and not self._serves_blocks:
            raise BlockDecodingError(
                f"{type(self).__name__} decodes one token a step; a model "
                f"with block_length {self.block_length} needs the paged "
                f"engine (paged=True)")
        refuse_layout(config, f"{type(self).__name__}'s dense rows (the "
                      f"paged engine serves it: paged=True)",
                      not self._serves_latent)
        # -- overload protection (docs/serving_resilience.md) --------------
        # max_queue_size: bounded admission queue, reject-newest shedding
        # (0 = unbounded, the pre-resilience behavior)
        # max_wait: per-request queue-time budget in seconds (0 = off) —
        # an overloaded engine fails queued requests fast instead of
        # hanging their futures until result(timeout=300)
        if max_queue_size < 0:
            raise ValueError("max_queue_size must be >= 0")
        if max_wait < 0:
            raise ValueError("max_wait must be >= 0")
        self.max_queue_size = int(max_queue_size)
        self.max_wait = float(max_wait)
        self.degradation = DegradationLadder.from_spec(degradation)
        # -- chunked prefill (docs/serving.md "Prefill & prefix cache") ----
        # at most prefill_chunk prompt tokens run per scheduler tick, so
        # admitting a long prompt never freezes inter-token latency for
        # the slots already decoding; 0 = whole-prompt prefill inline
        llm_defaults = mlconf.serving.llm
        if prefill_chunk is None:
            prefill_chunk = int(llm_defaults.prefill_chunk)
        if prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0")
        self.prefill_chunk = min(int(prefill_chunk), max_len)
        if self.prefill_chunk % self.block_length:
            raise BlockDecodingError(
                f"prefill_chunk {self.prefill_chunk} is not a multiple of "
                f"block_length {self.block_length}: a chunk would end "
                f"inside a block that sees all of itself")
        if latency_window is None:
            latency_window = int(llm_defaults.latency_window)
        if latency_window <= 0:
            raise ValueError("latency_window must be > 0")
        # bounded ring behind the p50/p95 TTFT percentiles in stats
        # (per-slot ttft alone was discarded); the inter-token and
        # decode-tick percentiles read the tick log's newest
        # latency_window records with rows
        self._ttft_ring: deque = deque(maxlen=latency_window)
        self._latency_window = latency_window
        # -- attention kernel dispatch (docs/serving.md "Attention kernels")
        # auto | flash | kernel | reference; prefill resolves to the
        # offset-aware flash kernel or the dense masked softmax. The
        # rowwise decode of THIS engine stays dense (per-row positions);
        # the paged subclass routes decode through the page-table kernel.
        if attention_impl is None:
            attention_impl = str(
                llm_defaults.get("attention_impl", "auto"))
        self.attention_impl = attention_impl
        self.prefill_impl = resolve_prefill_impl(attention_impl)
        # -- multi-tenant LoRA (docs/serving.md "Multi-tenant LoRA") -------
        # named adapters hot-loaded from the artifact store into a
        # device-resident bank; every prefill/decode dispatch gathers
        # per-row (A, B) deltas by bank slot index. None = single-tenant
        # engine, compile-identical to the pre-adapter programs.
        if adapters is not None and (config.recurrent_state
                                     or config.window_cache):
            # the bank's targets are the attention-then-MLP block's, of one
            # width a projection
            refuse_layout(config, "per-tenant adapters")
        if adapters is None:
            self._adapters = None
            self._owns_adapters = True
        elif isinstance(adapters, AdapterRegistry):
            # shared registry (advanced): engines share one device bank.
            # Registry-level telemetry (mlt_adapter_*, registry stats,
            # per-tenant queue split) is published by NO engine then —
            # the registry's pins/loads are global, and each engine
            # republishing them under its own labels would multiply
            # every federated sum by the engine count.
            self._adapters = adapters
            self._owns_adapters = False
        else:
            self._adapters = AdapterRegistry(config, sources=adapters,
                                             max_live=max_live_adapters)
            self._owns_adapters = True
        adapters_conf = llm_defaults.get("adapters", {})
        if adapter_rate is None:
            adapter_rate = float(adapters_conf.get("rate", 0.0))
        if adapter_burst is None:
            adapter_burst = float(adapters_conf.get("burst", 8.0))
        # per-tenant admission fairness: a token bucket per adapter id in
        # FRONT of the shared queue (0 = off)
        self._tenant_limiter = (
            TenantRateLimiter(adapter_rate, adapter_burst)
            if adapter_rate > 0 else None)
        # adapter label values this engine has emitted series for —
        # removed with the rest of its series on stop()
        self._adapter_labels_seen: set = set()
        # per-request phase ledger (obs/reqledger.py,
        # docs/observability.md "Request attribution"): off = one None
        # check per instrumented site, nothing allocated
        if request_ledger is None:
            from ..obs import ledger_enabled

            request_ledger = ledger_enabled()
        self.request_ledger = bool(request_ledger)
        # injectable for deterministic fake-clock closure tests; every
        # ledger transition reads THIS clock exactly once
        self._ledger_clock = time.perf_counter
        # the admission being prefilled right now (chunked mode resumes it
        # across ticks; only ever touched by the scheduler thread)
        self._admission: Optional[_Admission] = None
        # flipped by the degradation ladder; speculative decoders consult
        # it via their gate (serving/speculative.py)
        self.speculative_enabled = True
        self.prefill_buckets = tuple(
            b for b in sorted(prefill_buckets) if b <= max_len) or (max_len,)

        # every jitted step under a stable name (utils/profiler.named):
        # a profile's modules then read jit_mlt_prefill, jit_mlt_decode, ...
        self._prefill = jax.jit(named("mlt_prefill", functools.partial(
            _forward_with_cache, config, attn_impl=self.prefill_impl,
            **self._loads_kw())))
        self._decode = jax.jit(
            named("mlt_decode", functools.partial(_decode_rowwise, config)),
            donate_argnums=(2,))
        # the sampled variant is the same jit object called with the extra
        # (rng, temperature, top_k, top_p) args — jax.jit specializes per
        # argument structure, so greedy and sampled ticks each get their
        # own cached executable
        self._decode_sampled = self._decode
        self._rng = jax.random.PRNGKey(seed)

        def insert(big_cache, small, slot, pos):
            big_cache = dict(big_cache)
            for name in ("k", "v", "k_scale", "v_scale"):
                if name in big_cache:
                    idx = (0, slot) + (0,) * (big_cache[name].ndim - 2)
                    big_cache[name] = jax.lax.dynamic_update_slice(
                        big_cache[name],
                        small[name].astype(big_cache[name].dtype), idx)
            big_cache["pos"] = big_cache["pos"].at[slot].set(pos)
            return big_cache

        self._insert = jax.jit(named("mlt_insert", insert),
                               donate_argnums=(0,))

        self._cache = self._make_cache()
        self._slot_state = [_Slot() for _ in range(slots)]
        self._queue: queue.Queue = queue.Queue()
        self._running = False
        self._stopped = False
        self._crash_exc: Optional[Exception] = None
        self._thread: Optional[threading.Thread] = None
        # scheduler-epoch guard (docs/observability.md is unrelated; see
        # stop()): each scheduler thread runs one epoch; stop() and the
        # thread race for teardown ownership through these sets under
        # self._lock, so exactly one side fails the in-flight admission
        self._epoch = 0
        self._dead_epochs: set = set()
        self._stale_epochs: set = set()
        # /metrics identity + scrape-time collector handle; ``replica`` is
        # the fleet-assigned label on every mlt_llm_* series ("" for a
        # standalone engine) — set it BEFORE start()/first submit()
        self._obs_name = (f"{type(self).__name__}-"
                          f"{next(_ENGINE_SEQUENCE)}")
        # one record per scheduler iteration that did work
        # (obs/ticklog.py), kept by name so that it outlives the engine;
        # ``_tick`` is the iteration being filled (a scratch record where
        # a test drives the ticks itself)
        self._tick_log = get_tick_log(self._obs_name)
        self._tick = TickRecord()
        self._iterations = 0
        # what closes the records over wall time (docs/observability.md
        # "Tick log"): the last record's t1 and, since it, the end of the
        # first idle poll; since when the device is known to have nothing
        # queued (None: not known; ``_sent_seq`` counts the programs
        # enqueued, so that a fetch can tell whether it read the last of
        # them); when the last first token came back, until the next prefill
        # or decode goes out; the collector's sums as the last record left
        # them
        self._last_t1: Optional[float] = None
        self._idle_since: Optional[float] = None
        self._quiet_since: Optional[float] = None
        self._sent_seq = 0
        self._first_token_at: Optional[float] = None
        self._gc_seen = ticklog.gc_sums()
        self._gc_watched = False
        # the thread's clock and usage at their last reading (None: the next
        # record starts one), the records until the next (a system call
        # each, of 6-20 us on some hosts: PERF.md PR 39), and the wall and
        # the CPU seconds that the next reading covers beside its own
        # difference (what a reading cut short by an idle poll found)
        self._cpu_seen: Optional[float] = None
        self._usage_seen = (0, 0)
        self._cpu_due = ticklog.CPU_EVERY
        self._cpu_wall = self._cpu_carry = 0.0
        self.replica = ""
        self._metrics_collector = None
        self._next_id = 0
        # RLock: the expiry sweep holds it across drain/re-put while the
        # helpers it calls (stats, budget counter) re-acquire it
        self._lock = threading.RLock()
        # queued requests carrying a max_wait budget; the per-tick expiry
        # sweep is skipped entirely while this is zero
        self._budgeted = 0
        self._stats = {"requests": 0, "completed": 0, "ttft_sum": 0.0,
                       "tokens_out": 0, "shed": 0, "expired": 0,
                       "degraded": 0, "rejected_too_long": 0,
                       "prefill_chunks": 0,
                       "prefill_tokens_tick_max": 0,
                       "handoffs_out": 0, "handoff_bytes_out": 0,
                       "handoffs_in": 0, "handoff_bytes_in": 0,
                       "adapter_rate_limited": 0,
                       "sched_stalls": 0, "sched_stall_s_max": 0.0,
                       "weights_relaid_bytes": relaid_bytes(self.params)}
        if self.block_length > 1:
            # row-passes (each denoises) and those of them that also stored
            # a finished block, and the passes' expert counters: pairs
            # routed and experts that got a pair (both summed over layers
            # and passes), and the most pairs one expert got in one layer
            # of one pass
            self._stats.update({"denoise_passes": 0, "commit_passes": 0,
                                "expert_pairs": 0, "experts_touched": 0,
                                "expert_load_max": 0,
                                "unmasked_positions": 0})
        elif getattr(config, "n_experts", 0):
            # an expert model served token by token: token-expert pairs
            # routed and experts that got a pair (both summed over layers
            # and dispatches, prefill chunks included), and the most pairs
            # one expert got in one layer of one dispatch
            self._stats.update({"expert_pairs": 0, "experts_touched": 0,
                                "expert_load_max": 0})
        # (tick record, counters on their way to the host) of dispatches
        # whose experts' counters no fetch has brought yet
        self._pending_loads: list = []
        # -- in-engine speculative decoding (docs/serving.md
        # "Speculative decoding"): draft model resident alongside the
        # target, per-row adaptive k, one multi-token verify dispatch per
        # tick. Off unless a draft model is supplied.
        self._init_speculative(speculative)

    def _loads_kw(self) -> dict:
        """The prefill program's keyword that makes an expert model served
        token by token leave its experts' counters a dispatch; absent for
        every other model, whose program is then as it was."""
        reports = getattr(self.config, "n_experts", 0) \
            and self.block_length == 1
        return {"with_loads": True} if reports else {}

    # -- speculative decoding (shared by the dense and paged engines) ----

    def _init_speculative(self, speculative: dict | None):
        conf_node = mlconf.serving.llm.get("speculative")
        conf = dict(conf_node.to_dict()) if conf_node is not None else {}
        draft_config = None
        draft_params = None
        enabled = bool(conf.get("enabled", False))
        if isinstance(speculative, dict):
            draft_config = speculative.get("draft_config")
            draft_params = speculative.get("draft_params")
            conf.update({k: v for k, v in speculative.items()
                         if k not in ("draft_config", "draft_params")})
            enabled = bool(conf.get("enabled", True))
        self.spec_k = max(1, int(conf.get("k", 4) or 4))
        self.spec_min_acceptance = float(conf.get("min_acceptance", 0.35))
        self.spec_window = max(1, int(conf.get("window", 32) or 32))
        self.spec_probe_every = max(1, int(conf.get("probe_every", 16)
                                           or 16))
        self.spec_enabled = bool(enabled and draft_config is not None
                                 and draft_params is not None)
        if self.spec_enabled and self.block_length > 1:
            raise BlockDecodingError(
                "speculative decoding proposes one token after another; a "
                f"model with block_length {self.block_length} fills a "
                "block in any order: turn one of the two off")
        refuse_layout(self.config, "speculation", self.spec_enabled)
        if not self.spec_enabled:
            return
        if draft_config.vocab_size != self.config.vocab_size:
            raise ValueError("draft and target must share a vocabulary")
        self._spec_draft_config = draft_config
        self._spec_draft_params = serving_tree(draft_config, draft_params)
        self._stats["weights_relaid_bytes"] += relaid_bytes(
            self._spec_draft_params)
        # draft KV is always the dense slot layout (tiny model — the page
        # pool exists for the TARGET's HBM footprint, not the draft's)
        self._spec_dcache = init_kv_cache(draft_config, self.slots,
                                          self.max_len)
        # entries BEHIND each slot's last committed token in the draft
        # cache (same invariant as cache['pos'] on the target)
        self._spec_dpos = np.zeros((self.slots,), np.int32)
        # prompt tokens per slot — the draft resync source after plain
        # (non-speculative) ticks advanced the target without the draft
        self._spec_prompts: dict = {}
        self._spec_stale: set = set()
        # per-adapter bounded acceptance window: deque of
        # (proposed, accepted) per verify round, plus probation counters
        self._spec_windows: dict = {}
        self._spec_probe: dict = {}
        # adapters whose draft-bank load failed once — don't retry per tick
        self._spec_draft_block: set = set()
        self._stats.update({"spec_rounds": 0, "spec_proposed": 0,
                            "spec_accepted": 0, "spec_rejected": 0,
                            "spec_tokens": 0, "spec_parked_ticks": 0,
                            "spec_resyncs": 0})
        self._spec_draft_prefill = jax.jit(named(
            "mlt_draft_prefill",
            functools.partial(_forward_with_cache, draft_config)))
        k_max = self.spec_k

        def draft_steps(params, tokens, cache, lora=None, adapter_ids=None):
            """k_max greedy draft steps over the full slot batch; returns
            ([slots, k_max] proposals, cache)."""
            def body(carry, _):
                tok, c = carry
                nxt, c = _decode_rowwise(draft_config, params, tok, c,
                                         lora=lora, adapter_ids=adapter_ids)
                return (nxt[:, None], c), nxt

            (_, cache), proposals = jax.lax.scan(
                body, (tokens, cache), None, length=k_max)
            return proposals.T, cache

        self._spec_draft_steps = jax.jit(named("mlt_draft", draft_steps),
                                         donate_argnums=(2,))
        # engine-specific multi-token verify program, built lazily on the
        # first speculative tick (the paged subclass resolves its kernel
        # impl after this base ctor runs)
        self._spec_verify = None

    def _make_verify_fn(self):
        """Jitted (verified [B,S], new_cache) verify program (hook: the
        paged engine swaps in the page-pool verify)."""
        return jax.jit(
            named("mlt_verify",
                  functools.partial(_verify_rowwise, self.config)),
            donate_argnums=(2,))

    def _spec_verify_fn(self):
        if self._spec_verify is None:
            self._spec_verify = self._make_verify_fn()
        return self._spec_verify

    def _spec_lora_kwargs(self, adapter_ids) -> dict:
        """Draft-bank LoRA kwargs for the draft dispatches (None when no
        per-tenant draft adapters are attached → base draft model)."""
        draft = (getattr(self._adapters, "draft", None)
                 if self._adapters is not None else None)
        if draft is None or adapter_ids is None:
            return {}
        return {"lora": draft.bank.tensors,
                "adapter_ids": jnp.asarray(adapter_ids)}

    def _spec_slot_draft_ids(self, active):
        """Per-slot DRAFT bank slot ids (0 = base draft model). A tenant
        without a registered draft adapter — or whose draft-load failed —
        drafts with the base model; its verify still runs under the
        tenant's TARGET adapter, so the stream stays the adapter's exact
        greedy output either way (draft quality only buys speed)."""
        draft = (getattr(self._adapters, "draft", None)
                 if self._adapters is not None else None)
        if draft is None:
            return None
        ids = np.zeros((self.slots,), np.int32)
        for i in active:
            adapter = self._slot_state[i].adapter
            if not adapter or adapter in self._spec_draft_block:
                continue
            try:
                ids[i] = draft.ensure_loaded(adapter)
            except Exception as exc:  # noqa: BLE001 - missing/oversubscribed
                # draft adapter degrades to the base draft, never the request
                self._spec_draft_block.add(adapter)
                logger.warning("draft adapter unavailable, using base draft",
                               adapter=adapter, error=str(exc))
        return ids

    def _spec_prefill_slot(self, index: int, tokens_seq, adapter=None):
        """(Re)build one slot's draft KV by prefilling ``tokens_seq``;
        afterwards ``_spec_dpos[index] == len(tokens_seq)`` (the draft's
        next proposal step attends exactly these entries)."""
        total = len(tokens_seq)
        if total <= 0 or total > self.max_len:
            self._spec_dpos[index] = max(0, min(total, self.max_len))
            return
        small = init_kv_cache(self._spec_draft_config, 1, self.max_len)
        pad_len = self._bucket_for(total)
        padded = np.zeros((1, pad_len), np.int32)
        padded[0, :total] = tokens_seq
        draft_ids = None
        draft = (getattr(self._adapters, "draft", None)
                 if self._adapters is not None else None)
        if (draft is not None and adapter
                and adapter not in self._spec_draft_block):
            try:
                draft_ids = np.asarray([draft.ensure_loaded(adapter)],
                                       np.int32)
            except Exception:  # noqa: BLE001 - fall back to base draft
                self._spec_draft_block.add(adapter)
        lora_kw = self._spec_lora_kwargs(draft_ids)
        _, small = self._spec_draft_prefill(
            self._spec_draft_params, jnp.asarray(padded), small, **lora_kw)
        # garbage KV at the padded tail is masked by position until real
        # writes land there (same argument as the target's bucket pad)
        self._spec_dcache = self._insert(self._spec_dcache, small, index,
                                         total)
        self._spec_dpos[index] = total

    def _spec_admit_slot(self, adm: "_Admission"):
        """Draft prefill for a fresh admission. The draft always ingests
        the FULL prompt tokens regardless of how the target prefilled —
        cold, prefix-cache hit, or imported ``KVHandoff`` — because the
        draft has no prefix cache or handoff of its own; that one rule
        keeps all three target paths speculation-ready."""
        self._spec_prompts[adm.slot] = list(adm.prompt)
        self._spec_stale.discard(adm.slot)
        self._spec_prefill_slot(adm.slot, adm.prompt, adm.adapter)

    def _spec_resync_row(self, index: int):
        """Rebuild a stale draft cache row (plain ticks advanced the
        target without the draft): re-prefill prompt + committed tokens
        minus the last. Draft-side only — target output never depends on
        draft KV contents, so a resync can't change the stream."""
        slot = self._slot_state[index]
        stream = list(self._spec_prompts.get(index, ())) + slot.tokens
        if len(stream) > 1:
            self._spec_prefill_slot(index, stream[:-1], slot.adapter)
        else:
            self._spec_dpos[index] = 0
        self._spec_stale.discard(index)
        with self._lock:
            self._stats["spec_resyncs"] += 1

    def _spec_release_slot(self, index: int):
        if not getattr(self, "spec_enabled", False):
            return
        self._spec_prompts.pop(index, None)
        self._spec_stale.discard(index)
        self._spec_dpos[index] = 0

    def _spec_row_k(self, adapter) -> int:
        """Adaptive per-row proposal length from the adapter's bounded
        acceptance window. Cold window → full k (optimistic); paying
        window → k scaled to expected acceptance; under-threshold →
        parked at 0 (plain decode) with a k=1 probe every
        ``spec_probe_every`` consulted rounds so a recovered draft can
        re-earn its budget. Round counters, never wall clock."""
        state = self._spec_windows.get(adapter)
        if state is None:
            state = self._spec_windows[adapter] = deque(
                maxlen=self.spec_window)
        proposed = sum(p for p, _ in state)
        if proposed < 8:
            return self.spec_k
        acc = sum(a for _, a in state) / proposed
        if acc < self.spec_min_acceptance:
            count = self._spec_probe.get(adapter, 0) + 1
            self._spec_probe[adapter] = count
            return 1 if count % self.spec_probe_every == 0 else 0
        self._spec_probe.pop(adapter, None)
        return max(1, min(self.spec_k,
                          int(round(acc * (self.spec_k + 1)))))

    def _spec_feed_window(self, adapter, proposed: int, accepted: int):
        self._spec_windows[adapter].append((proposed, accepted))

    def _spec_tick_viable(self, active) -> bool:
        if not getattr(self, "spec_enabled", False):
            return False
        # fleet-wide park: the degradation ladder's existing flag still
        # gates everything; per-row policy only runs under it
        if not self.speculative_enabled:
            return False
        # mixed greedy/sampled batches tick plain: verify-chunk argmax
        # equivalence is a greedy contract (docs/serving.md)
        return all(self._slot_state[i].temperature == 0.0 for i in active)

    def _spec_apply_positions(self, committed: dict):
        """Commit accepted positions on the target KV (hook: the paged
        engine writes its host-side ``_pos`` instead). Rewinding is the
        whole rollback — rejected entries are overwritten before any
        later query can attend them."""
        pos = np.array(self._cache["pos"])   # copy: device views read-only
        for index, value in committed.items():
            pos[index] = value
        self._cache["pos"] = jnp.asarray(pos)

    def _spec_verify_dispatch(self, chunk, active):
        """ONE multi-token verify forward over every slot (hook: the
        paged engine dispatches the page-pool verify kernel)."""
        lora_kw = (self._lora_kwargs(self._slot_adapter_ids())
                   if self._adapters is not None else {})
        verified, self._cache = self._spec_verify_fn()(
            self.params, jnp.asarray(chunk), self._cache, **lora_kw)
        return np.asarray(verified)

    def _spec_decode_tick(self, active) -> Optional[int]:
        """One speculative scheduler tick: k batched draft steps + ONE
        multi-token verify dispatch, then per-row accept/rollback.
        Returns None to fall through to the plain tick (chaos park, or
        every row's gate parked this round)."""
        # chaos: an armed llm.spec_verify fault parks THIS tick to plain
        # decode — never a client error; the stream stays exact-greedy
        # because plain ticks emit the same target argmax
        try:
            fire(FaultPoints.llm_spec_verify, engine=self._obs_name,
                 replica=self.replica, rows=len(active))
        except Exception as exc:  # noqa: BLE001 - any armed error parks
            with self._lock:
                self._stats["spec_parked_ticks"] += 1
            flight_record("engine.spec_park", engine=self._obs_name,
                          replica=self.replica, error=str(exc))
            return None

        k_max = self.spec_k
        k_effs = np.zeros((self.slots,), np.int32)
        any_spec = False
        for i in active:
            slot = self._slot_state[i]
            if slot.remaining < 1:
                continue
            # gate consult BEFORE resync: a parked row's stale draft
            # cache is never read (its chunk lane is k_eff 0, its
            # rollback discards the writes), so rebuilding it every
            # tick would tax exactly the fleets whose drafts don't pay
            k_row = min(self._spec_row_k(slot.adapter), slot.remaining,
                        k_max)
            k_effs[i] = max(0, k_row)
            if k_row > 0:
                any_spec = True
                if i in self._spec_stale:
                    self._spec_resync_row(i)
        if not any_spec:
            return None

        # a round is logged no finer than draft-and-verify as one wait
        # (t_built = t_dispatched = t_admit)
        tick = self._tick
        tick.kind = "spec"
        # the round's wait starts where admission ended: what the device
        # stood dry before it ends there too
        self._enqueued(tick.t_dispatched)
        with annotate("mlt.sched.fetch"):
            last, tick.ctx_tokens = self._tick_inputs(active)
            self._ledger_mark(active, "decode_active")
            draft_lora_kw = self._spec_lora_kwargs(
                self._spec_slot_draft_ids(active))
            self._spec_dcache["pos"] = jnp.asarray(self._spec_dpos)
            proposals, self._spec_dcache = self._spec_draft_steps(
                self._spec_draft_params, jnp.asarray(last),
                self._spec_dcache, **draft_lora_kw)
            proposals_h = np.asarray(proposals)       # [slots, k_max]
            chunk = np.zeros((self.slots, k_max + 1), np.int32)
            chunk[:, 0] = last[:, 0]
            chunk[:, 1:] = proposals_h
            verified_h = self._spec_verify_dispatch(chunk, active)
        tick.t_fetched = self._quiet_since = time.perf_counter()
        with annotate("mlt.sched.commit"):
            return self._spec_commit(active, k_effs, proposals_h,
                                     verified_h)

    def _spec_commit(self, active, k_effs, proposals_h, verified_h) -> int:
        """Per-row accept/rollback of one verified round."""
        from .speculative import accept_tokens

        self._ledger_mark(active, "decode_stall")

        finished = []
        committed = {}
        rounds = proposed_total = accepted_total = tokens_total = 0
        emitted_total = 0
        for i in active:
            slot = self._slot_state[i]
            k_eff = int(k_effs[i])
            emitted, n_accept = accept_tokens(
                proposals_h[i, :k_eff], verified_h[i], k_eff)
            if k_eff > 0:
                rounds += 1
                proposed_total += k_eff
                accepted_total += n_accept
                self._spec_feed_window(slot.adapter, k_eff, n_accept)
            if slot.eos_id is not None and slot.eos_id in emitted:
                emitted = emitted[:emitted.index(slot.eos_id) + 1]
            emitted = emitted[:max(0, slot.remaining)]
            slot.tokens.extend(int(t) for t in emitted)
            slot.remaining -= len(emitted)
            emitted_total += len(emitted)
            if k_eff > 0:
                tokens_total += len(emitted)
            pos_i = slot.prompt_len + len(slot.tokens) - 1
            committed[i] = pos_i
            self._spec_dpos[i] = pos_i
            capacity = slot.prompt_len + len(slot.tokens) >= self.max_len
            if ((slot.eos_id is not None and slot.tokens[-1] == slot.eos_id)
                    or slot.remaining <= 0 or capacity):
                finished.append(i)
        self._spec_apply_positions(committed)
        with self._lock:
            self._stats["spec_rounds"] += rounds
            self._stats["spec_proposed"] += proposed_total
            self._stats["spec_accepted"] += accepted_total
            self._stats["spec_rejected"] += proposed_total - accepted_total
            self._stats["spec_tokens"] += tokens_total
        # beside what a drained plain tick committed in this iteration
        self._tick.tokens_out += emitted_total
        for i in finished:
            self._finish(i)
        return len(active)

    def _make_cache(self):
        """Slot KV storage (hook: the paged engine swaps in a page pool)."""
        return init_kv_cache(self.config, self.slots, self.max_len,
                             kv_dtype=self.kv_dtype)

    def _lora_kwargs(self, slots=None) -> dict:
        """jit kwargs threading the adapter bank + per-row bank-slot
        indices into a dispatch; {} (compile-identical to the
        pre-adapter programs) when no registry is attached. ``slots`` is
        an int (batch=1 admission prefill) or a [slots] array (decode
        tick); default = every row on the base slot 0."""
        if self._adapters is None:
            return {}
        if slots is None:
            ids = np.zeros((self.slots,), np.int32)
        elif isinstance(slots, (int, np.integer)):
            ids = np.full((1,), slots, np.int32)
        else:
            ids = np.asarray(slots, np.int32)
        return {"lora": self._adapters.bank.tensors,
                "adapter_ids": jnp.asarray(ids)}

    def _slot_adapter_ids(self):
        """Per-engine-slot bank indices for the decode dispatch (inactive
        rows decode on the base slot — their outputs are discarded)."""
        return np.fromiter(
            (s.adapter_slot if s.active else 0 for s in self._slot_state),
            np.int32, self.slots)

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        if self._running:
            return
        self._running = True
        self._epoch += 1
        # what the process holds by now (imports, compiled programs, the
        # weights' trees) leaves the collector's sight while the engine
        # runs: a full collection over it stops this thread, which owns the
        # device, for 0.1-0.3 s at moments of its own choosing (PERF.md,
        # PR 37); ``stop`` gives it back
        gc.freeze()
        if not self._gc_watched:
            self._gc_watched = True
            ticklog.watch_gc()
        self._last_t1 = self._idle_since = self._quiet_since = None
        self._first_token_at = self._cpu_seen = None
        self._cpu_wall = self._cpu_carry = 0.0
        self._gc_seen = ticklog.gc_sums()
        self._register_metrics()
        # device HBM / host RSS exposition while this engine lives
        # (mlt_device_mem_bytes — weakref, shared across owners)
        register_memory_collector(self)
        self._thread = threading.Thread(target=self._loop,
                                        args=(self._epoch,), daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 10.0):
        """Stop the scheduler and DRAIN the queue: every request still
        queued (or mid-generation in a slot) fails promptly with
        :class:`EngineStoppedError` instead of hanging its future until
        its own result() timeout.

        Epoch guard: ``join`` returning does NOT prove the scheduler is
        gone — it can still be wedged in a device dispatch past the
        timeout, and tearing down the in-flight admission here would race
        the live thread (page-table vs free-list divergence, both sides
        resolving one future → InvalidStateError). Teardown ownership is
        decided under the lock: if the scheduler's epoch already
        registered dead, stop() tears down; otherwise the epoch is marked
        stale ("disowned") and the scheduler runs the teardown itself on
        its way out — exactly one side ever does it.
        """
        self._running = False
        self._stopped = True
        gc.unfreeze()
        if self._gc_watched:
            self._gc_watched = False
            ticklog.unwatch_gc()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=timeout)
        exc = EngineStoppedError(
            "engine stopped while the request was pending")
        epoch = self._epoch
        with self._lock:
            scheduler_live = thread is not None \
                and epoch not in self._dead_epochs
            if scheduler_live:
                self._stale_epochs.add(epoch)
            else:
                self._dead_epochs.discard(epoch)
        if scheduler_live:
            logger.warning(
                "engine stop: scheduler still in a dispatch after join "
                "timeout — queued requests failed now, in-flight "
                "admission/slot teardown deferred to the scheduler",
                timeout=timeout, epoch=epoch)
            self._drain_queue(exc)
        else:
            self._fail_pending(exc)
        self._unregister_metrics()

    def close(self):
        """Alias for :meth:`stop` (context-manager friendly name)."""
        self.stop()

    # -- /metrics collector --------------------------------------------------
    # cumulative stats() keys mirrored as counter series at scrape time
    # NOTE: the adapter_* stats keys are deliberately NOT mirrored here —
    # mlt_adapter_loads_total{outcome} is their one canonical family
    # (publishing them under mlt_llm_events_total too would double-count
    # adapter activity in federated sums)
    _COUNTER_STATS = ("requests", "completed", "tokens_out", "shed",
                      "expired", "degraded", "rejected_too_long",
                      "prefill_chunks", "sched_stalls",
                      "prefix_queries", "prefix_hits",
                      "prefix_evictions", "prefix_cached_tokens",
                      "handoffs_out", "handoff_bytes_out", "handoffs_in",
                      "handoff_bytes_in")

    def _register_metrics(self):
        """Expose this engine on the process registry: queue-depth /
        free-page-fraction gauges, the cumulative stats counters, and
        the per-tenant adapter series, read at scrape time (weakly
        bound; retired on stop())."""
        if self._metrics_collector is not None:
            return
        import weakref

        ref = weakref.ref(self)
        name = self._obs_name
        replica = self.replica
        # shared mutable set: the engine adds adapter label values as it
        # serves tenants; removal drops exactly the series it created
        adapter_labels = self._adapter_labels_seen
        has_adapters = self._adapters is not None and self._owns_adapters
        # the fairness limiter exists independently of any registry —
        # its shed counter must be visible even on a base-model engine
        has_limiter = self._tenant_limiter is not None
        # speculation telemetry only exists on spec-capable engines; the
        # families are created lazily at first collect and retired here
        has_spec = getattr(self, "spec_enabled", False)

        counter_stats = self._COUNTER_STATS

        def remove_series():
            for adapter in adapter_labels | {""}:
                LLM_QUEUE_DEPTH.remove(engine=name, replica=replica,
                                       adapter=adapter)
            LLM_FREE_PAGE_FRAC.remove(engine=name, replica=replica)
            LLM_KV_BYTES_PER_TOKEN.remove(engine=name, replica=replica)
            LLM_STATE_BYTES_PER_SLOT.remove(engine=name, replica=replica)
            LLM_WINDOW_BYTES_PER_SLOT.remove(engine=name, replica=replica)
            LLM_WEIGHTS_RELAID_BYTES.remove(engine=name, replica=replica)
            if has_spec:
                LLM_SPEC_ROUNDS.remove(engine=name, replica=replica)
                for outcome in ("accepted", "rejected"):
                    LLM_SPEC_TOKENS.remove(engine=name, replica=replica,
                                           outcome=outcome)
            for key in counter_stats:
                LLM_EVENTS.remove(engine=name, replica=replica, event=key)
            if has_adapters:
                ADAPTER_LIVE.remove(engine=name, replica=replica)
                for outcome in ("ok", "evict", "error", "capacity",
                                "unknown"):
                    ADAPTER_LOADS.remove(engine=name, replica=replica,
                                         outcome=outcome)
            if has_adapters or has_limiter:
                ADAPTER_LOADS.remove(engine=name, replica=replica,
                                     outcome="rate_limited")
            if replica:
                # fleet replicas own their latency-histogram series too —
                # a scaled-down replica must not pin them; standalone
                # engines (replica "") share one series, never removed
                for adapter in adapter_labels | {""}:
                    for family in (LLM_TTFT, LLM_ITL):
                        family.remove(replica=replica, adapter=adapter)
                LLM_DECODE_TICK.remove(replica=replica)

        def collect():
            engine = ref()
            if engine is None:
                remove_series()
                return False
            stats = engine.stats
            # per-tenant queue depth: every LIVE adapter (resident or
            # active) gets its in-flight queued estimate — explicitly 0
            # when idle, so a drained tenant's gauge can't freeze at its
            # last busy value; "" carries the untenanted remainder, so
            # the sum over adapter values is the engine's total depth
            # (the autoscaler's federated sum stays correct)
            depth = stats.get("queue_depth", 0)
            named = engine._adapter_queue_depths()
            live = engine._live_adapter_labels() | set(named)
            for adapter in live:
                LLM_QUEUE_DEPTH.set(named.get(adapter, 0), engine=name,
                                    replica=replica, adapter=adapter)
            LLM_QUEUE_DEPTH.set(max(0, depth - sum(named.values())),
                                engine=name, replica=replica, adapter="")
            # retire series of tenants that are gone (evicted, idle, no
            # pins): lifetime ``adapter`` label values stay bounded by
            # the resident working set, not by every tenant ever served
            # — a rotating tenant population can't exhaust the families'
            # label-set bounds (fleet replicas retire their TTFT/ITL
            # series too; standalone engines share the replica="" series
            # and leave them)
            stale = adapter_labels - live - {""}
            for adapter in stale:
                LLM_QUEUE_DEPTH.remove(engine=name, replica=replica,
                                       adapter=adapter)
                if replica:
                    for family in (LLM_TTFT, LLM_ITL):
                        family.remove(replica=replica, adapter=adapter)
            adapter_labels.difference_update(stale)
            adapter_labels.update(live)
            frac = engine._free_page_frac()
            if frac is not None:
                LLM_FREE_PAGE_FRAC.set(frac, engine=name, replica=replica)
            if "kv_bytes_per_token" in stats:
                LLM_KV_BYTES_PER_TOKEN.set(stats["kv_bytes_per_token"],
                                           engine=name, replica=replica)
                LLM_STATE_BYTES_PER_SLOT.set(
                    stats["state_bytes_per_slot"], engine=name,
                    replica=replica)
                LLM_WINDOW_BYTES_PER_SLOT.set(
                    stats["window_bytes_per_slot"], engine=name,
                    replica=replica)
            LLM_WEIGHTS_RELAID_BYTES.set(stats["weights_relaid_bytes"],
                                         engine=name, replica=replica)
            for key in engine._COUNTER_STATS:
                if key in stats:
                    LLM_EVENTS.set_total(stats[key], engine=name,
                                         replica=replica, event=key)
            if has_spec:
                LLM_SPEC_ROUNDS.set_total(stats.get("spec_rounds", 0),
                                          engine=name, replica=replica)
                LLM_SPEC_TOKENS.set_total(stats.get("spec_accepted", 0),
                                          engine=name, replica=replica,
                                          outcome="accepted")
                LLM_SPEC_TOKENS.set_total(stats.get("spec_rejected", 0),
                                          engine=name, replica=replica,
                                          outcome="rejected")
            registry = engine._adapters if engine._owns_adapters else None
            if registry is not None:
                ADAPTER_LIVE.set(registry.live(), engine=name,
                                 replica=replica)
                reg_stats = registry.stats
                for outcome, key in (
                        ("ok", "adapter_loads"),
                        ("evict", "adapter_evictions"),
                        ("error", "adapter_load_errors"),
                        ("capacity", "adapter_rejected_capacity"),
                        ("unknown", "adapter_rejected_unknown")):
                    ADAPTER_LOADS.set_total(reg_stats[key], engine=name,
                                            replica=replica,
                                            outcome=outcome)
            if registry is not None or has_limiter:
                ADAPTER_LOADS.set_total(
                    stats.get("adapter_rate_limited", 0), engine=name,
                    replica=replica, outcome="rate_limited")
            return None

        self._metrics_collector = collect
        self._remove_metric_series = remove_series
        REGISTRY.add_collector(collect)

    def _adapter_queue_depths(self) -> dict:
        """{adapter: queued-but-not-active} derived from registry pins
        (one pin per in-flight request) minus rows already decoding —
        consistent on every completion path because pins die with the
        request future."""
        if self._adapters is None or not self._owns_adapters:
            # shared registry: pins are global across engines, so a
            # per-engine split would claim other engines' queued work —
            # the adapter="" series then carries this engine's full depth
            return {}
        pins = self._adapters.pinned_counts()
        if not pins:
            return {}
        active: dict = {}
        for slot in self._slot_state:
            if slot.active and slot.adapter:
                active[slot.adapter] = active.get(slot.adapter, 0) + 1
        adm = self._admission
        if adm is not None and adm.adapter:
            active[adm.adapter] = active.get(adm.adapter, 0) + 1
        return {adapter: max(0, count - active.get(adapter, 0))
                for adapter, count in pins.items()}

    def _live_adapter_labels(self) -> set:
        """Adapter names that should keep metric series right now:
        device residents (pinned or idle-cached) plus anything still
        occupying a slot/admission (belt-and-braces — an active slot's
        adapter is always pinned, hence resident)."""
        if self._adapters is None:
            return set()
        live = set(self._adapters.resident_names()) \
            if self._owns_adapters else set()
        live.update(s.adapter for s in self._slot_state
                    if s.active and s.adapter)
        adm = self._admission
        if adm is not None and adm.adapter:
            live.add(adm.adapter)
        return live

    def _unregister_metrics(self):
        """Drop the collector AND every labeled series this engine owns —
        a process churning engines (redeploys) must not pin dead series
        until the family's cardinality bound starts dropping live ones."""
        collector, self._metrics_collector = self._metrics_collector, None
        if collector is not None:
            REGISTRY.remove_collector(collector)
            self._remove_metric_series()

    def warmup(self):
        """Compile prefill buckets, decode step, and insertion."""
        started = time.perf_counter()
        # with a registry attached, warm the adapter-aware program
        # structure (bank on the base slot) — the serving-time dispatch
        # shape regardless of which tenant lands first
        prefill_kw = self._lora_kwargs(0)
        decode_kw = self._lora_kwargs()
        for bucket in self.prefill_buckets:
            small = init_kv_cache(self.config, 1, self.max_len,
                                  kv_dtype=self.kv_dtype)
            tokens = jnp.zeros((1, bucket), jnp.int32)
            # the index of the position whose logits come back is an input:
            # this one program serves every prompt length in the bucket
            small = self._prefill(self.params, tokens, small,
                                  logits_at=np.int32(bucket - 1),
                                  **prefill_kw)[1]
            self._cache = self._insert(self._cache, small, 0, bucket)
        if self.prefill_chunk and self.prefill_chunk not in \
                self.prefill_buckets:
            # chunked prefill dispatches a fixed (1, chunk) shape
            small = init_kv_cache(self.config, 1, self.max_len,
                                  kv_dtype=self.kv_dtype)
            self._prefill(self.params,
                          jnp.zeros((1, self.prefill_chunk), jnp.int32),
                          small, logits_at=np.int32(self.prefill_chunk - 1),
                          **prefill_kw)
        step = jnp.zeros((self.slots, 1), jnp.int32)
        tok, self._cache = self._decode(self.params, step, self._cache,
                                        **decode_kw)
        jax.block_until_ready(tok)
        # compile the sampled variant too (first sampled request must not
        # pay the compile)
        tok, self._cache = self._decode_sampled(
            self.params, step, self._cache, jax.random.PRNGKey(0),
            jnp.zeros((self.slots,), jnp.float32),
            jnp.zeros((self.slots,), jnp.int32),
            jnp.ones((self.slots,), jnp.float32), **decode_kw)
        float(jnp.sum(tok))
        self._cache["pos"] = jnp.zeros((self.slots,), jnp.int32)
        self._spec_warmup()
        logger.info("continuous batching engine warm",
                    slots=self.slots,
                    buckets=list(self.prefill_buckets),
                    warmup_s=round(time.perf_counter() - started, 2))

    def _spec_warmup(self):
        """Compile the speculative programs — draft prefill buckets, the
        k-step draft scan, and the engine's verify dispatch — so the
        first speculative tick doesn't pay the compiles. Garbage KV the
        warm dispatches write sits behind pos 0 / on the scratch page
        and is overwritten before any read (the bucket-pad argument)."""
        if not getattr(self, "spec_enabled", False):
            return
        ids = self._spec_slot_draft_ids(range(self.slots))
        row_kw = self._spec_lora_kwargs(
            None if ids is None else ids[:1])
        for bucket in self.prefill_buckets:
            small = init_kv_cache(self._spec_draft_config, 1, self.max_len)
            self._spec_draft_prefill(
                self._spec_draft_params, jnp.zeros((1, bucket), jnp.int32),
                small, **row_kw)
        step = jnp.zeros((self.slots, 1), jnp.int32)
        _, self._spec_dcache = self._spec_draft_steps(
            self._spec_draft_params, step, self._spec_dcache,
            **self._spec_lora_kwargs(ids))
        self._spec_dcache["pos"] = jnp.zeros((self.slots,), jnp.int32)
        self._spec_warmup_verify()

    def _spec_warmup_verify(self):
        """Verify-program compile (hook: the paged engine warms its
        page-pool verify against the scratch page instead)."""
        chunk = jnp.zeros((self.slots, self.spec_k + 1), jnp.int32)
        lora_kw = self._lora_kwargs(self._slot_adapter_ids()) \
            if self._adapters is not None else {}
        _, self._cache = self._spec_verify_fn()(
            self.params, chunk, self._cache, **lora_kw)
        self._cache["pos"] = jnp.zeros((self.slots,), jnp.int32)

    # -- API ----------------------------------------------------------------
    def _free_page_frac(self) -> Optional[float]:
        """Paged engines report KV-page headroom; dense engines None."""
        return None

    def _queue_depth(self) -> int:
        return self._queue.qsize()

    def pressure_level(self) -> int:
        """Degradation-ladder level: 0 normal, 1 degraded (speculative
        off + max_new_tokens clamp), 2 shedding (queue full)."""
        depth = self._queue_depth()
        if self.max_queue_size and depth >= self.max_queue_size:
            return 2
        if self.degradation is not None:
            return self.degradation.level(depth, self.max_queue_size,
                                          self._free_page_frac())
        return 0

    def submit(self, prompt_tokens, max_new_tokens: int = 64,
               eos_id: int | None = None, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0,
               max_wait: float | None = None, adapter: str = "",
               request_key=None, _extra=None, _trace=None) -> Future:
        """Thread-safe request submission. ``max_wait`` overrides the
        engine-level queue-time budget for this request. The returned
        future fails FAST — QueueFullError when shedding,
        EngineStoppedError after stop/crash — never silently hangs.

        ``adapter`` names a registry LoRA adapter applied to every
        decode row of this request (docs/serving.md "Multi-tenant
        LoRA"): unknown names fail typed 404, a pinned-full working set
        429, and the per-tenant token bucket sheds a flooding tenant
        429 BEFORE the shared queue.

        ``_extra``/``_trace`` are the fleet's internal channel: ``_extra``
        marks an export ("export") or carries an imported
        :class:`KVHandoff`; ``_trace`` overrides the thread-local span
        capture so a router dispatching from a callback thread still
        parents the engine's llm.* spans on the originating request."""
        from .adapters import AdapterError, UnknownAdapterError

        future: Future = Future()
        if self._stopped and not self._running:
            cause = f": {self._crash_exc}" if self._crash_exc else ""
            future.set_exception(EngineStoppedError(
                f"engine is stopped, not accepting requests{cause}"))
            return future
        if self.block_length > 1 and (temperature > 0 or _extra is not None):
            future.set_exception(BlockDecodingError(
                "a block model is served greedily and whole: no sampling "
                "with a temperature inside a block, no KV handoff"))
            return future
        prompt_len = len(prompt_tokens)
        if prompt_len + max_new_tokens > self.max_len:
            # 400-class rejection up front — past the largest bucket the
            # prefill path would otherwise pad/truncate undefined
            with self._lock:
                self._stats["rejected_too_long"] += 1
            future.set_exception(PromptTooLongError(
                f"prompt_len {prompt_len} + max_new_tokens "
                f"{max_new_tokens} exceeds max_len {self.max_len}"))
            return future
        adapter = adapter or ""
        # phase ledger from here on: everything submit-side (canary
        # resolution, 404 lookup, the pin) is "admission" time; the
        # limiter check is split out as "rate_limit_wait"
        ledger = RequestLedger(clock=self._ledger_clock) \
            if self.request_ledger else None
        split_tenant = split_side = ""
        if adapter and not isinstance(_extra, KVHandoff):
            # canary/version resolution (serving/canary.py): a tenant id
            # with loop state becomes its effective versioned id HERE,
            # before the prefix cache, the rate limiter and the bank see
            # it — canary traffic is a distinct identity end to end. An
            # imported handoff arrives already resolved (the prefill
            # side decided its side) and must not re-roll the split.
            # ``request_key`` pins the split side across requests (a
            # session id); absent, the prompt tokens decide. Metering
            # happens at admission (_meter_split), not here — shed
            # requests must not skew the split-fraction telemetry.
            router = get_canary_router()
            if router is not None:
                resolved, side = router.resolve(
                    adapter, split_key_for(prompt_tokens, request_key))
                if side:
                    split_tenant, split_side = adapter, side
                adapter = resolved
        if adapter:
            # the 404 check runs BEFORE the limiter: unknown names must
            # never mint rate-limit buckets (an untrusted client would
            # grow them unboundedly) and must fail 404, not 429
            if self._adapters is None:
                future.set_exception(UnknownAdapterError(
                    f"engine has no adapter registry "
                    f"(adapter='{adapter}')"))
                return future
            try:
                self._adapters.check_known(adapter)
            except AdapterError as exc:
                future.set_exception(exc)
                return future
        # per-tenant fairness BEFORE the shared queue: a flooding tenant
        # burns its own bucket, not everyone's queue capacity. The
        # internal prefill→decode hop (an imported KVHandoff) was
        # already charged once at its client-facing prefill admission —
        # charging again would 429 a request whose prefill compute and
        # handoff bytes are already spent.
        if self._tenant_limiter is not None \
                and not isinstance(_extra, KVHandoff):
            if ledger is not None:
                ledger.enter("rate_limit_wait")
            acquired = self._tenant_limiter.try_acquire(adapter)
            if ledger is not None:
                ledger.enter("admission")
            if not acquired:
                from .adapters import AdapterRateLimitError

                with self._lock:
                    self._stats["adapter_rate_limited"] += 1
                future.set_exception(AdapterRateLimitError(
                    f"tenant '{adapter or '<base>'}' is over its "
                    f"admission rate — shed to protect the shared queue"))
                return future
        # the chaos point fires BEFORE the pin: an armed error here must
        # not strand a refcount (the future below is the pin's lifetime
        # authority, and it does not exist as a completion path yet)
        fire(FaultPoints.llm_submit, prompt_len=prompt_len,
             max_new_tokens=max_new_tokens, adapter=adapter)
        if adapter:
            try:
                self._adapters.pin(adapter)
            except AdapterError as exc:
                future.set_exception(exc)
                return future
            # one pin per in-flight request, released on ANY completion
            # path (result, shed, expiry, stop) — the future is the
            # single lifetime authority
            future.add_done_callback(
                lambda _f, a=adapter: self._adapters.unpin(a))
            try:
                self._enqueue(future, prompt_tokens,
                              max_new_tokens, eos_id, temperature,
                              top_k, top_p, max_wait, adapter,
                              _extra, _trace, ledger)
            except Exception as exc:  # noqa: BLE001 - an exception past
                # the pin must complete the future (that runs the unpin
                # callback) instead of leaking a refcount forever
                if not future.done():
                    future.set_exception(exc)
                return future
            self._meter_split(split_tenant, split_side, future)
            return future
        self._enqueue(future, prompt_tokens, max_new_tokens,
                      eos_id, temperature, top_k, top_p, max_wait,
                      adapter, _extra, _trace, ledger)
        self._meter_split(split_tenant, split_side, future)
        return future

    @staticmethod
    def _meter_split(tenant: str, side: str, future: Future):
        """Count one ADMITTED request on the canary split telemetry —
        called after the queue put, so sheds/rejections (whose futures
        already failed) and fleet re-dispatch attempts that never
        enqueued don't skew the canary/(canary+stable) fraction."""
        from ..obs import CANARY_REQUESTS

        if side and (not future.done() or future.exception() is None):
            CANARY_REQUESTS.inc(adapter=tenant, side=side)

    def _enqueue(self, future: Future, prompt_tokens, max_new_tokens,
                 eos_id, temperature, top_k, top_p, max_wait, adapter,
                 _extra, _trace, ledger=None) -> Future:
        """Pressure/degradation checks + the actual queue put (the tail
        of :meth:`submit`, split out so the adapter-pinned path can
        armor it)."""
        level = self.pressure_level()
        if level >= 2:
            with self._lock:
                self._stats["shed"] += 1
            flight_record("engine.shed", engine=self._obs_name,
                          queue_depth=self._queue.qsize(),
                          adapter=adapter)
            future.set_exception(QueueFullError(
                f"engine queue is full (max_queue_size="
                f"{self.max_queue_size}, depth {self._queue.qsize()}) — "
                f"shedding"))
            return future
        if level >= 1:
            # degraded: clamp the token budget and park speculative
            # decoding before we have to start shedding
            if self.degradation is not None:
                max_new_tokens = self.degradation.clamp_max_new(
                    max_new_tokens, level)
            if self.speculative_enabled:
                logger.warning("engine degraded: speculative decoding off",
                               queue_depth=self._queue.qsize())
            self.speculative_enabled = False
            with self._lock:
                self._stats["degraded"] += 1
        else:
            self.speculative_enabled = True
        budget = self.max_wait if max_wait is None else float(max_wait)
        expires = (time.perf_counter() + budget) if budget > 0 else None
        # trace context crosses the thread boundary inside the queue item:
        # the scheduler emits llm.prefill/llm.decode spans parented on the
        # submitting step's span (docs/observability.md)
        if _trace is None:
            current_span = get_tracer().current()
            _trace = ((current_span.trace_id, current_span.span_id)
                      if current_span is not None else None)
        if ledger is not None:
            if _trace is not None:
                ledger.trace_id = _trace[0]
            # submit-side work done; the clock now charges the queue
            ledger.enter("queue_wait")
        # enqueue under the lock: the expiry sweep drains and re-puts the
        # queue atomically, so a racing put must not land mid-sweep and
        # jump ahead of older requests
        with self._lock:
            request_id = self._next_id
            self._next_id += 1
            self._stats["requests"] += 1
            if expires is not None:
                self._budgeted += 1
            self._queue.put((request_id, list(prompt_tokens),
                             max_new_tokens, eos_id, future,
                             time.perf_counter(),
                             (float(temperature), int(top_k), float(top_p)),
                             expires, _trace, _extra, adapter, ledger))
        if not self._running:
            self.start()
        return future

    # -- prefill/decode disaggregation (docs/serving.md "Engine fleet") ------
    def submit_prefill(self, prompt_tokens, eos_id: int | None = None,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0, max_wait: float | None = None,
                       adapter: str = "", request_key=None,
                       _trace=None) -> Future:
        """Run ONLY the (chunked) prefill for a prompt; the returned future
        resolves to a :class:`KVHandoff` a decode replica can import via
        :meth:`submit_prefilled`. The prompt's KV still lands in this
        engine's prefix cache (paged) under ``adapter``'s root, so hot
        prefixes stay cache-resident — per tenant — on the prefill pool.
        ``max_new_tokens=1`` bounds the paged page reservation to the
        prompt itself."""
        refuse_layout(self.config, "a KV handoff (submit_prefill)")
        return self.submit(prompt_tokens, max_new_tokens=1, eos_id=eos_id,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, max_wait=max_wait, adapter=adapter,
                           request_key=request_key,
                           _extra="export", _trace=_trace)

    def submit_prefilled(self, handoff: KVHandoff,
                         max_new_tokens: int = 64,
                         eos_id: int | None = None,
                         max_wait: float | None = None,
                         register_prefix: bool = False,
                         _trace=None) -> Future:
        """Admit an already-prefilled request: the handoff's KV is imported
        into the admission slot-cache and decode starts immediately — no
        prefill dispatch ever runs on this engine, so a decode pool's tick
        cadence is immune to fleet-wide long prompts. The handoff carries
        its adapter id: decode runs under the SAME adapter the KV was
        computed with. ``register_prefix`` is the pre-warm replay path
        (serving/podfleet.py): the imported prompt pages ALSO register in
        this engine's prefix index, so a reassigned hot key's first real
        request after a ring join is a cache hit."""
        refuse_layout(self.config, "a KV handoff (submit_prefilled)")
        expects_scales = self.kv_dtype == "int8"
        wire_dtype = getattr(handoff, "kv_dtype", None) or (
            "int8" if "k_scale" in handoff.kv else "native")
        if wire_dtype != self.kv_dtype or \
                ("k_scale" in handoff.kv) != expects_scales:
            raise ValueError(
                f"KV handoff dtype mismatch: engine kv_dtype="
                f"'{self.kv_dtype}' cannot import a '{wire_dtype}' "
                f"payload — prefill and decode pools must quantize "
                f"alike (docs/serving.md 'Engine fleet')")
        temperature, top_k, top_p = handoff.sampling
        if register_prefix and not handoff.prewarm:
            handoff = dataclass_replace(handoff, prewarm=True)
        return self.submit(handoff.prompt, max_new_tokens=max_new_tokens,
                           eos_id=eos_id, temperature=temperature,
                           top_k=top_k, top_p=top_p, max_wait=max_wait,
                           adapter=handoff.adapter, _extra=handoff,
                           _trace=_trace)

    def _handoff_kv(self, adm: _Admission, rows: int) -> dict:
        """Serialize an export admission's prompt KV to host numpy
        (the :class:`KVHandoff` payload — int8 pools ship int8 values +
        f32 scales, never densified to the native dtype). Hook: the
        paged engine's kernel-prefix path assembles the cached-prefix
        rows straight from its pool pages, since they were never
        gathered into the slot cache."""
        return {name: np.asarray(adm.small[name][:, 0, :rows])
                for name in ("k", "v", "k_scale", "v_scale")
                if name in adm.small}

    def _import_small(self, handoff: KVHandoff) -> dict:
        """Deserialize a handoff into the batch=1 admission cache (the
        inverse of :meth:`_export_admission`'s trim): prompt rows from the
        payload, zeros beyond — decode overwrites position >= prompt_len
        before ever attending over it."""
        shape = (self.config.n_layers, 1, self.max_len,
                 self.config.n_kv_heads, self.config.head_dim)
        dtypes = {"k": self.config.dtype, "v": self.config.dtype}
        if self.kv_dtype == "int8":
            dtypes = {"k": jnp.int8, "v": jnp.int8,
                      "k_scale": jnp.float32, "v_scale": jnp.float32}
        small = {}
        for name, dtype in dtypes.items():
            full_shape = shape if name in ("k", "v") else shape[:-1]
            host = np.zeros(full_shape, dtype)
            payload = handoff.kv.get(name)
            if payload is not None:
                rows = min(payload.shape[1], self.max_len)
                host[:, 0, :rows] = payload[:, :rows]
            small[name] = jnp.asarray(host)
        small["pos"] = jnp.full((1,), handoff.prompt_len, jnp.int32)
        return small

    def _export_admission(self, adm: _Admission):
        """Resolve an export admission's future with the KV handoff and
        free the slot storage immediately — a prefill replica never holds
        a decode slot. The paged engine's `_complete_storage` already
        registered the prompt blocks, so the prefix stays cache-resident
        here for the next request sharing it."""
        self._drain_tick(admitting=True)    # the export reads the pool
        if adm.ledger is not None:
            # the slot-cache trim/serialize below is the prefill-side
            # handoff cost; the ledger closes here and rides the payload
            adm.ledger.enter("handoff")
        rows = len(adm.prompt)
        kv = self._handoff_kv(adm, rows)
        prefill_s = time.perf_counter() - adm.submitted
        timing = None
        if adm.ledger is not None:
            timing = adm.ledger.close("handoff")
            export_phases(timing, adapter=adm.adapter)
        if adm.trace is not None:
            # the export admission's llm.prefill span is emitted HERE
            # (not in _finish_admission) so it can carry the closed
            # prefill-hop ledger — the assembled waterfall's ledger view
            # then spans both hops of a disaggregated request
            attrs = {"slot": adm.slot, "prompt_len": len(adm.prompt),
                     "chunks": adm.chunks, "cached_prefix": adm.base,
                     "imported": False, "exported": True,
                     "adapter": adm.adapter, "replica": self.replica}
            if timing is not None:
                attrs["timing"] = timing
            get_tracer().emit("llm.prefill", adm.trace[0], adm.trace[1],
                              start=adm.claimed, attrs=attrs)
        handoff = KVHandoff(
            prompt=list(adm.prompt), first_token=adm.first_token, kv=kv,
            prompt_len=len(adm.prompt), kv_dtype=self.kv_dtype,
            cached_prefix=adm.base, sampling=adm.sampling,
            prefill_s=prefill_s, replica=self.replica,
            adapter=adm.adapter, timing=timing)
        self._release_slot_storage(adm.slot)
        with self._lock:
            self._stats["handoffs_out"] += 1
            self._stats["handoff_bytes_out"] += handoff.nbytes()
            # a prefill replica's TTFT ring IS its prefill latency — the
            # first token ships inside the handoff
            self._ttft_ring.append(prefill_s)
            if adm.adapter:
                self._adapter_labels_seen.add(adm.adapter)
        LLM_TTFT.observe(prefill_s,
                         exemplar=(adm.trace[0] if adm.trace else None),
                         replica=self.replica, adapter=adm.adapter)
        if not adm.future.done():
            adm.future.set_result(handoff)

    def generate(self, prompt_tokens, max_new_tokens: int = 64,
                 eos_id: int | None = None, timeout: float = 300.0,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, adapter: str = "",
                 request_key=None):
        """Synchronous convenience wrapper around submit()."""
        return self.submit(prompt_tokens, max_new_tokens, eos_id,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, adapter=adapter,
                           request_key=request_key).result(timeout=timeout)

    # -- adapter source lifecycle (docs/continuous_tuning.md) ----------------
    def add_adapter_source(self, name: str, source):
        """Publish a named adapter at runtime (the canary hot-load
        path); requests naming it load through the normal pin/
        ensure_loaded admission flow — no engine restart, no
        recompile."""
        if self._adapters is None:
            raise ValueError(
                "engine has no adapter registry (build it with "
                "adapters=... to hot-load canaries)")
        self._adapters.add_source(name, source)

    def retire_adapter(self, name: str, keep_source: bool = False):
        """Drop an adapter from service (promotion's old-stable evict /
        a rollback's canary teardown); in-flight pins finish first."""
        if self._adapters is not None:
            self._adapters.retire(name, keep_source=keep_source)

    @property
    def stats(self) -> dict:
        with self._lock:
            out = dict(self._stats)
            ttfts = sorted(self._ttft_ring)
        itls, ticks = (sorted(values) for values in
                       self._tick_log.latest(self._latency_window))
        if out["completed"]:
            out["ttft_avg_s"] = out["ttft_sum"] / out["completed"]
        if ttfts:
            out["ttft_p50_s"] = _percentile(ttfts, 0.50)
            out["ttft_p95_s"] = _percentile(ttfts, 0.95)
        if itls:
            out["itl_p50_s"] = _percentile(itls, 0.50)
            out["itl_p95_s"] = _percentile(itls, 0.95)
        if ticks:
            out["decode_tick_p50_s"] = _percentile(ticks, 0.50)
            out["decode_tick_p95_s"] = _percentile(ticks, 0.95)
        # over the tick log's ring: live rows a decode tick, the loop's
        # share not blocked on the device, admission's share of the loop,
        # the share the thread was executing, the device known dry
        out.update(self._tick_log.summary())
        out["attention_impl"] = self.attention_impl
        out["prefill_impl"] = self.prefill_impl
        out["queue_depth"] = self._queue_depth()
        out["pressure_level"] = self.pressure_level()
        out["speculative_enabled"] = self.speculative_enabled
        if "denoise_passes" in out:
            passes = out["denoise_passes"]
            out["tokens_per_row_pass"] = (
                out.pop("unmasked_positions") / passes if passes else 0.0)
        if "spec_rounds" in out:
            out["acceptance_rate"] = (
                out["spec_accepted"] / out["spec_proposed"]
                if out["spec_proposed"] else 0.0)
            out["spec_tokens_per_round"] = (
                out["spec_tokens"] / out["spec_rounds"]
                if out["spec_rounds"] else 0.0)
        if self._adapters is not None and self._owns_adapters:
            out.update(self._adapters.stats)
            out["adapter_live"] = self._adapters.live()
            out["adapter_resident"] = self._adapters.resident_names()
        return out

    # -- scheduler ----------------------------------------------------------
    def _prompt_lead(self, prompt_len: int) -> int:
        """The prompt positions that the prefill covers: all of them, or
        for a block model its whole leading blocks (the trailing ``P mod
        B`` tokens open the first block already unmasked)."""
        return prompt_len - prompt_len % self.block_length

    def _bucket_for(self, length: int) -> int:
        for bucket in self.prefill_buckets:
            if length <= bucket:
                return bucket
        return self.max_len

    def _first_token(self, logits, sampling: tuple) -> int:
        """Sample/argmax the first generated token from last-position
        logits (shared by the inline and chunked prefill paths)."""
        temperature, top_k, top_p = sampling
        if temperature > 0:
            from .sampling import sample_logits

            self._rng, sub = jax.random.split(self._rng)
            return int(np.asarray(sample_logits(
                logits, sub, jnp.full((1,), temperature, jnp.float32),
                jnp.full((1,), top_k, jnp.int32),
                jnp.full((1,), top_p, jnp.float32)))[0])
        return int(np.asarray(jnp.argmax(logits, axis=-1))[0])

    def _run_prefill(self, adm: _Admission,
                     limit: int | None = None) -> bool:
        """Advance the admission's prefill by ONE dispatch: up to ``limit``
        prompt tokens (the whole remaining suffix, bucket-padded, when
        limit is None). The cursor starts at ``adm.base`` — on a paged
        prefix-cache hit the cached prefix KV is already in ``adm.small``
        and only the suffix runs. Returns True once the prompt is fully
        prefilled and the first token is sampled. In the profiler's trace
        the dispatch (build and enqueue) is ``mlt.sched.prefill`` and the
        wait for the first token ``mlt.sched.first_token``, siblings."""
        tick = self._tick
        with annotate("mlt.sched.prefill"):
            if adm.ledger is not None and \
                    adm.ledger.current_phase != "prefill":
                # first chunk dispatch: the request is in prefill from here
                # to the first token — decode ticks interleaved between
                # chunks included, that IS this request's prefill latency
                adm.ledger.enter("prefill")
                adm.ledger.note("tick_first", tick.n)
            fire(FaultPoints.llm_prefill, request_id=adm.request_id,
                 slot=adm.slot, offset=adm.offset, chunks=adm.chunks)
            prompt = adm.prompt
            # a block model takes no token from the prefill either
            total = self._prompt_lead(len(prompt))
            start = adm.offset
            remaining = total - start
            if remaining <= 0:
                return True                 # nothing left of it to prefill
            cap = self.max_len - start
            if limit is None:
                # prefer a warmed bucket shape that still fits the cache
                # tail (start > 0 after a prefix hit can rule the usual
                # bucket out); the cap fallback compiles once per distinct
                # tail
                pad_len = next(
                    (b for b in self.prefill_buckets
                     if remaining <= b <= cap),
                    min(self._bucket_for(remaining), cap))
            else:
                pad_len = min(limit, cap)
            take = min(remaining, pad_len)
            padded = np.zeros((1, pad_len), np.int32)
            padded[0, :take] = prompt[start:start + take]
            adm.small["pos"] = jnp.full((1,), start, jnp.int32)
            lora_kw = self._lora_kwargs(adm.adapter_slot)
            # the logits that come back are those of the chunk's last REAL
            # position: a padded prompt's first token needs no second
            # dispatch
            out = self._prefill_dispatch(
                adm, jnp.asarray(padded), np.int32(take - 1), lora_kw)
            self._enqueued(time.perf_counter())
            logits, adm.small = out[:2]
            if len(out) > 2:
                # an expert model's dispatch leaves its experts' counters;
                # a later fetch brings them (_settle_loads)
                out[2].copy_to_host_async()
                self._pending_loads.append((tick, out[2]))
            adm.offset += take
            adm.chunks += 1
            tick.prefill_tokens += take
            tick.prefill_dispatches += 1
            if self.config.recurrent_state:
                # the real tokens the scan integrated: the padding is left
                # out
                tick.state_tokens += take
            if self.config.window_cache:
                # the query-key pairs inside the window that the window
                # layers' chunk computed, a layer
                tick.window_prefill_pairs += window_pairs(
                    start, take, self.config.sliding_window)
            # the positions the chunk's tokens attended: each its own and
            # what precedes it
            tick.prefill_ctx_tokens += \
                take * start + take * (take + 1) // 2
            with self._lock:
                self._stats["prefill_chunks"] += 1
                # tick instrumentation: the most prefill compute any single
                # scheduler iteration absorbed (tests assert <=
                # prefill_chunk)
                if take > self._stats["prefill_tokens_tick_max"]:
                    self._stats["prefill_tokens_tick_max"] = take
            if adm.offset < total:
                return False
            if self.block_length > 1:
                return True
        with annotate("mlt.sched.first_token"):
            # from here the scheduler waits on the device: for the tick in
            # flight, which runs before the prefill, then for the prefill
            waited = time.perf_counter()
            self._await_tick()
            landed = time.perf_counter()
            if sampling_enabled():
                # monitoring tap: first-token top1-top2 logit gap (a cheap
                # model-confidence proxy for the drift analyzer's "logit
                # statistics"). Only while an observer is armed — the host
                # transfer of one logits row is not paid when dark.
                row = np.asarray(logits).reshape(-1)
                if row.size >= 2:
                    top2 = np.partition(row, -2)[-2:]
                    adm.logit_margin = float(top2[1] - top2[0])
            adm.first_token = self._first_token(logits, adm.sampling)
            # the token's program was the last one enqueued: the device has
            # nothing left, and the host's path to the next dispatch begins
            sampled = self._quiet_since = self._first_token_at = \
                time.perf_counter()
            tick.inflight_wait_s += landed - waited
            tick.prefill_wait_s += sampled - landed
            self._settle_loads()            # this prompt's chunks have run
            tick.admit_wait_s += time.perf_counter() - waited
        return True

    def _enqueued(self, now: float, work: bool = True):
        """The enqueueing call of a device program returned at ``now``:
        what the device stood dry until then goes to the iteration's
        record, and nothing is known of it until a fetch reads the last
        program enqueued. ``work``: the program is a prefill or a decode,
        where the host's path behind a first token ends (an insert is on
        that path)."""
        self._sent_seq += 1
        tick = self._tick
        quiet = self._quiet_since
        if quiet is not None:
            tick.dry_s += max(0.0, now - quiet)
            self._quiet_since = None
        if work and self._first_token_at is not None:
            tick.after_prefill_s += max(0.0, now - self._first_token_at)
            self._first_token_at = None

    def _prefill_dispatch(self, adm: _Admission, tokens, logits_at,
                          lora_kw, prefix_kv=None):
        """One prefill device dispatch for an admission: a chunk of the
        prompt, returning the logits of its position ``logits_at``. Hook:
        the paged engine routes prefix-hit admissions through the merged
        paged-prefill kernel (``prefix_kv``) so the cached prefix is
        attended in place instead of gathered."""
        # an absent keyword, not a None: one compiled program a shape
        hit_kw = {} if prefix_kv is None else {"prefix_kv": prefix_kv}
        return self._prefill(self.params, tokens, adm.small,
                             logits_at=logits_at, **hit_kw, **lora_kw)

    def _activate_slot(self, free: int, request_id: int, first_token: int,
                       max_new: int, eos_id, future, submitted: float,
                       prompt_len: int, sampling: tuple,
                       trace: tuple | None = None, adapter: str = "",
                       adapter_slot: int = 0,
                       logit_margin: float = float("nan"),
                       ledger: RequestLedger | None = None):
        """Fill slot bookkeeping after a successful prefill (shared by the
        dense and paged admission paths)."""
        temperature, top_k, top_p = sampling
        slot = self._slot_state[free]
        slot.request_id = request_id
        slot.max_new = max_new
        if self.block_length > 1:
            # no token yet: the first block's passes make the first ones
            slot.tokens, slot.remaining = [], max_new
        else:
            slot.tokens = [first_token]
            slot.remaining = max_new - 1
        slot.eos_id = eos_id
        slot.future = future
        slot.started = submitted
        slot.ttft = time.perf_counter() - submitted
        slot.prompt_len = prompt_len
        slot.temperature = temperature
        slot.top_k = top_k
        slot.top_p = top_p
        slot.trace = trace
        slot.adapter = adapter
        slot.adapter_slot = adapter_slot
        slot.logit_margin = logit_margin
        slot.ledger = ledger
        slot.decode_started = wall_now()
        if ledger is not None:
            # the row now waits for its first decode dispatch; every
            # tick flips decode_active around the device step
            ledger.enter("decode_stall")
        with self._lock:
            self._ttft_ring.append(slot.ttft)
            if adapter:
                self._adapter_labels_seen.add(adapter)
        LLM_TTFT.observe(slot.ttft,
                         exemplar=(trace[0] if trace else None),
                         replica=self.replica, adapter=adapter)
        if self.block_length > 1:
            return
        if (eos_id is not None and first_token == eos_id) or \
                slot.remaining <= 0:
            self._finish(free)

    # -- admission -----------------------------------------------------------
    def _validate_item(self, item) -> bool:
        """Expiry + capacity checks on a dequeued request. Returns False
        (consuming the item) when its future was already failed."""
        (_, prompt, max_new, _, future, submitted, _, expires) = item[:8]
        if self._request_expired(future, submitted, expires):
            return False
        if len(prompt) + max_new > self.max_len:
            # backstop for requests enqueued before a config change —
            # submit() already rejects these up front
            future.set_exception(PromptTooLongError(
                f"prompt_len {len(prompt)} + max_new_tokens {max_new} "
                f"exceeds max_len {self.max_len}"))
            return False
        return True

    def _prepare_admission(self) -> Optional[_Admission]:
        """Claim a free slot + the next valid queued request; build the
        admission (batch=1 prefill cache, cursor at 0). The paged engine
        overrides this with page reservation + prefix matching."""
        free = next((i for i, s in enumerate(self._slot_state)
                     if not s.active), None)
        if free is None:
            return None
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                return None
            self._consume_budget(item[7])
            if not self._validate_item(item):
                continue
            (request_id, prompt, max_new, eos_id, future, submitted,
             sampling, expires) = item[:8]
            extra = item[9] if len(item) > 9 else None
            adapter = item[10] if len(item) > 10 else ""
            ledger = item[11] if len(item) > 11 else None
            if ledger is not None:
                # claimed off the queue: queue_wait closes here
                ledger.enter("adapter_load_wait" if adapter
                             else "admission")
            adapter_slot = self._resolve_adapter(adapter, future)
            if ledger is not None and adapter:
                ledger.enter("admission")
            if adapter_slot is None:
                continue  # adapter load failed — request failed typed
            try:
                adm = _Admission(
                    slot=free, request_id=request_id, prompt=prompt,
                    max_new=max_new, eos_id=eos_id, future=future,
                    submitted=submitted, sampling=sampling,
                    expires=expires, trace=item[8], claimed=wall_now(),
                    adapter=adapter, adapter_slot=adapter_slot,
                    ledger=ledger)
                self._apply_directive(adm, extra)
                if adm.small is None:
                    adm.small = init_kv_cache(self.config, 1, self.max_len,
                                              kv_dtype=self.kv_dtype)
                # the staging cache is filled on the device
                self._enqueued(time.perf_counter(), work=False)
                return adm
            except Exception as exc:
                # dequeued but not yet tracked in self._admission — fail
                # the future before the scheduler dies or it would hang
                # outside every container _fail_pending drains
                if not future.done():
                    future.set_exception(exc)
                raise

    def _resolve_adapter(self, adapter: str, future: Future):
        """Materialize the request's adapter in the device bank (on the
        scheduler thread — the single device owner). Returns the bank
        slot, or None after failing the request's future: a corrupt or
        unreachable adapter artifact fails ONE request typed, never the
        engine."""
        if not adapter:
            return 0
        try:
            return self._adapters.ensure_loaded(adapter)
        except Exception as exc:  # noqa: BLE001 - per-request failure
            logger.warning("adapter load failed", adapter=adapter,
                           error=str(exc))
            if not future.done():
                future.set_exception(exc)
            return None

    def _apply_directive(self, adm: _Admission, extra):
        """Fold the fleet directive (item[9]) into a fresh admission:
        "export" flags a prefill-only request; a KVHandoff means the
        prefill already happened on another replica — import its KV and
        skip straight to slot activation."""
        if extra == "export":
            adm.export = True
        elif isinstance(extra, KVHandoff):
            if adm.ledger is not None:
                # deserialize + storage completion are the decode-side
                # handoff cost (the prefill side closed its own ledger
                # into "handoff" at export)
                adm.ledger.enter("handoff")
            adm.small = self._import_small(extra)
            adm.offset = len(adm.prompt)
            adm.first_token = extra.first_token
            adm.prefilled = True
            adm.register_import = bool(getattr(extra, "prewarm", False))
            with self._lock:
                self._stats["handoffs_in"] += 1
                self._stats["handoff_bytes_in"] += extra.nbytes()

    def _complete_storage(self, adm: _Admission):
        """Move the prefilled batch=1 cache into slot storage (the paged
        engine scatters into its page pool instead)."""
        self._cache = self._insert(self._cache, adm.small, adm.slot,
                                   len(adm.prompt))

    def _finish_admission(self, adm: _Admission):
        """The prefilled rows into the slot's storage, then the slot's
        bookkeeping: ``mlt.sched.insert | mlt.sched.activate``, siblings of
        the prefill and of the wait for its first token."""
        with annotate("mlt.sched.insert"):
            self._complete_storage(adm)
            self._enqueued(time.perf_counter(), work=False)
        self._tick.admissions += 1
        with annotate("mlt.sched.activate"):
            self._activate_admission(adm)

    def _activate_admission(self, adm: _Admission):
        if adm.ledger is not None:
            # an imported prefill dispatched none here
            adm.ledger.notes.setdefault("tick_first", self._tick.n)
            adm.ledger.note("prefill_chunks", adm.chunks)
            if adm.base:
                adm.ledger.note("cached_prefix", adm.base)
        if adm.trace is not None and not adm.export:
            # the prefill scheduler phase as a span under the submitting
            # step — chunk count, cached-prefix length and the serving
            # replica ride as attrs (imported=True marks a KV-handoff
            # import: no prefill ran); the replica attr is what lets a
            # /debug/trace waterfall tell the fleet hops apart. Export
            # admissions emit theirs in _export_admission instead, so
            # the span can carry the closed prefill-hop ledger.
            get_tracer().emit(
                "llm.prefill", adm.trace[0], adm.trace[1],
                start=adm.claimed, attrs={
                    "slot": adm.slot, "prompt_len": len(adm.prompt),
                    "chunks": adm.chunks, "cached_prefix": adm.base,
                    "imported": adm.prefilled, "exported": False,
                    "adapter": adm.adapter, "replica": self.replica})
        # scheduler decision on the flight ring: one admission completed
        # (prompt length, reused prefix, chunking — the inputs to every
        # later latency question a post-mortem asks)
        flight_record("engine.admit", engine=self._obs_name,
                      request_id=adm.request_id,
                      prompt_len=len(adm.prompt), cached_prefix=adm.base,
                      chunks=adm.chunks, slot=adm.slot,
                      adapter=adm.adapter, export=bool(adm.export))
        if adm.export:
            self._export_admission(adm)
            return
        if getattr(self, "spec_enabled", False):
            self._spec_admit_slot(adm)
        self._activate_slot(adm.slot, adm.request_id, adm.first_token,
                            adm.max_new, adm.eos_id, adm.future,
                            adm.submitted, len(adm.prompt), adm.sampling,
                            trace=adm.trace, adapter=adm.adapter,
                            adapter_slot=adm.adapter_slot,
                            logit_margin=adm.logit_margin,
                            ledger=adm.ledger)
        if self.block_length > 1:
            lead = self._prompt_lead(len(adm.prompt))
            self._open_block(self._slot_state[adm.slot], lead,
                             adm.prompt[lead:])

    def _abort_admission(self, adm: _Admission):
        """Release admission-held storage (expiry mid-prefill, stop). The
        dense engine's batch=1 cache just drops; the paged engine returns
        pages and prefix refs."""

    def _admit_one(self) -> bool:
        """Prefill one queued request into a free slot (returns True if a
        request was admitted). The admission is tracked in
        ``self._admission`` while prefill runs so a scheduler crash
        mid-prefill still fails its future (and frees its storage) via
        ``_fail_pending``."""
        with annotate("mlt.sched.claim"):
            adm = self._prepare_admission()
        if adm is None:
            return False
        self._admission = adm
        if not adm.prefilled:
            self._run_prefill(adm, limit=None)
        self._finish_admission(adm)
        self._admission = None
        return True

    def _admission_tick(self):
        """Admission work for one scheduler iteration. With chunked
        prefill at most ONE <= prefill_chunk dispatch runs per tick, so
        slots already decoding keep making progress while a long prompt
        prefills; otherwise admit whole prompts until slots or queue run
        out (the pre-chunking behavior)."""
        if not self.prefill_chunk:
            admitted = True
            while admitted:
                admitted = self._admit_one()
            return
        adm = self._admission
        if adm is None:
            with annotate("mlt.sched.claim"):
                adm = self._prepare_admission()
            if adm is None:
                return
            self._admission = adm
        # no expiry check here: max_wait is a QUEUE-time budget, spent the
        # moment the request was dequeued in _prepare_admission — a
        # mid-prefill admission is being served, not waiting (the
        # unchunked path behaves the same)
        done = adm.prefilled
        if not done:
            done = self._run_prefill(adm, limit=self.prefill_chunk)
        if done:
            self._finish_admission(adm)
            self._admission = None

    def _settle_loads(self, before=None):
        """Read the experts' counters that dispatches left behind
        (``_pending_loads``) into the records of the iterations that
        dispatched them: all of them, or those dispatched no later than
        the iteration of ``before`` (the record of a tick whose fetch just
        returned: a prefill goes out before its iteration's tick, so it
        has run)."""
        keep = []
        for record, counters in self._pending_loads:
            if before is not None and record.n > before.n:
                keep.append((record, counters))
            else:
                self._count_experts(record, np.asarray(counters))
        self._pending_loads = keep

    def _count_experts(self, record, counters):
        """One dispatch's experts' counters (pairs, experts touched, the
        most pairs on one expert) into its iteration's record and the
        engine's running sums."""
        pairs, touched, most = (int(v) for v in counters)
        record.expert_pairs += pairs
        record.experts_touched += touched
        record.expert_load_max = max(record.expert_load_max, most)
        with self._lock:
            self._stats["expert_pairs"] += pairs
            self._stats["experts_touched"] += touched
            self._stats["expert_load_max"] = max(
                self._stats["expert_load_max"], most)

    def _ledger_mark(self, active: list, phase: str):
        """Flip every active slot's ledger into ``phase`` (the
        decode_active/decode_stall split around each device dispatch —
        transition-based, so the split still sums to wall exactly)."""
        for i in active:
            ledger = self._slot_state[i].ledger
            if ledger is not None:
                ledger.enter(phase)

    def _finish(self, index: int):
        slot = self._slot_state[index]
        # a step may yield more than one token a row (a block, a
        # speculative round): the answer is what was asked for, no more
        if 0 < slot.max_new < len(slot.tokens):
            del slot.tokens[slot.max_new:]
        stats = {
            "ttft_s": slot.ttft,
            "generated": len(slot.tokens),
            "prompt_len": slot.prompt_len,
            "total_s": time.perf_counter() - slot.started,
        }
        if self.block_length > 1:
            # the pass, within its block, that unmasked each token returned,
            # and the confidence softmax(logits)[token] it had in that pass
            stats["unmask_pass"] = slot.unmask_pass[:len(slot.tokens)]
            stats["unmask_confidence"] = \
                slot.unmask_confidence[:len(slot.tokens)]
        timing = None
        if slot.ledger is not None:
            # the iteration that committed its last token: with
            # ``tick_first`` the tick records that served the request
            slot.ledger.note("tick_last", self._tick.n)
            timing = slot.ledger.close()
            stats["timing"] = timing
            export_phases(timing, adapter=slot.adapter)
        with self._lock:
            self._stats["completed"] += 1
            self._stats["ttft_sum"] += slot.ttft
            self._stats["tokens_out"] += len(slot.tokens)
        if slot.trace is not None:
            # the ledger rides the decode span so an assembled
            # /debug/trace waterfall can reconcile its critical path
            # against the request's own attribution (obs/traceview.py)
            attrs = {"slot": index, "generated": len(slot.tokens),
                     "replica": self.replica}
            if timing is not None:
                attrs["timing"] = timing
            get_tracer().emit(
                "llm.decode", slot.trace[0], slot.trace[1],
                start=slot.decode_started, attrs=attrs)
        if sampling_enabled():
            # monitoring tap (docs/continuous_tuning.md): one bounded
            # per-completion sample for the drift analyzer — output
            # token ids, lengths, latency, first-token logit margin
            emit_sample(adapter=slot.adapter, tokens=list(slot.tokens),
                        prompt_len=slot.prompt_len,
                        generated=len(slot.tokens), ttft_s=slot.ttft,
                        total_s=stats["total_s"],
                        logit_margin=slot.logit_margin,
                        engine=self._obs_name, replica=self.replica)
        future, tokens = slot.future, slot.tokens
        self._slot_state[index] = _Slot()
        self._release_slot_storage(index)
        if future is not None and not future.done():
            future.set_result((tokens, stats))

    def _release_slot_storage(self, index: int):
        # zero the freed row's position so decode writes land in its own
        # (now unused) region
        self._cache["pos"] = self._cache["pos"].at[index].set(0)
        self._enqueued(time.perf_counter(), work=False)
        self._spec_release_slot(index)

    def _decode_tick(self) -> int:
        active = [i for i, s in enumerate(self._slot_state) if s.active]
        if not active:
            return 0
        if self.block_length > 1:
            return self._denoise_tick(active)
        if self._spec_tick_viable(active):
            # a round drafts from the committed tokens: a plain tick in
            # flight is read first, and may end rows
            self._drain_tick()
            active = [i for i in active if self._slot_state[i].active]
            if not active:
                return 0
            done = self._spec_decode_tick(active)
            if done is not None:
                return done
        if getattr(self, "spec_enabled", False):
            # a plain tick advances the target without the draft: those
            # rows' draft caches go stale and resync on the next spec tick
            self._spec_stale.update(active)
        return self._plain_decode_tick(active)

    def _tick_inputs(self, active) -> tuple:
        """([slots, 1] last committed token of every live row, the tokens
        those rows attend in this tick: prompt + generated so far)."""
        last = np.zeros((self.slots, 1), np.int32)
        ctx_tokens = 0
        for i in active:
            slot = self._slot_state[i]
            last[i, 0] = slot.tokens[-1]
            ctx_tokens += slot.prompt_len + len(slot.tokens)
        return last, ctx_tokens

    def _sampling_args(self, active) -> tuple:
        """The sampled program's extra arguments (rng, temperature, top_k,
        top_p per slot), or () where every live row is greedy."""
        if not any(self._slot_state[i].temperature > 0 for i in active):
            return ()
        temp = np.zeros((self.slots,), np.float32)
        top_k = np.zeros((self.slots,), np.int32)
        top_p = np.ones((self.slots,), np.float32)
        for i in active:
            slot = self._slot_state[i]
            temp[i] = slot.temperature
            top_k[i] = slot.top_k
            top_p[i] = slot.top_p
        self._rng, sub = jax.random.split(self._rng)
        return (sub, jnp.asarray(temp), jnp.asarray(top_k),
                jnp.asarray(top_p))

    def _plain_decode_tick(self, active) -> int:
        # build | dispatch | fetch | commit: the tick record's inner
        # boundaries, and with tick and admit the iteration's siblings
        tick = self._tick
        with annotate("mlt.sched.build"):
            last, tick.ctx_tokens = self._tick_inputs(active)
            lora_kw = self._lora_kwargs(self._slot_adapter_ids()) \
                if self._adapters is not None else {}
            self._ledger_mark(active, "decode_active")
            # the sampled variant is the same jit object with extra args
            args = (jnp.asarray(last), self._cache) \
                + self._sampling_args(active)
        tick.t_built = time.perf_counter()
        with annotate("mlt.sched.dispatch"):
            next_token, self._cache = self._decode(self.params, *args,
                                                   **lora_kw)
        tick.t_dispatched = time.perf_counter()
        self._enqueued(tick.t_dispatched)
        with annotate("mlt.sched.fetch"):
            tokens_host = np.asarray(next_token)
        # this tick is synchronous: what it fetched was the last program
        tick.t_fetched = self._quiet_since = time.perf_counter()
        with annotate("mlt.sched.commit"):
            self._ledger_mark(active, "decode_stall")
            for i in active:
                slot = self._slot_state[i]
                token = int(tokens_host[i])
                slot.tokens.append(token)
                slot.remaining -= 1
                capacity = slot.prompt_len + len(slot.tokens) \
                    >= self.max_len
                if (slot.eos_id is not None and token == slot.eos_id) or \
                        slot.remaining <= 0 or capacity:
                    self._finish(i)
        tick.tokens_out = len(active)
        return len(active)

    def _consume_budget(self, expires: float | None):
        """A budgeted item left the admission queue for good."""
        if expires is not None:
            with self._lock:
                self._budgeted = max(0, self._budgeted - 1)

    def _request_expired(self, future: Future, submitted: float,
                         expires: float | None) -> bool:
        """Fail a request whose queue-time budget is spent (fast 504-class
        failure instead of a future hanging for result(timeout=300))."""
        if expires is None or time.perf_counter() < expires:
            return False
        waited = time.perf_counter() - submitted
        with self._lock:
            self._stats["expired"] += 1
        future.set_exception(DeadlineExceeded(
            f"request spent {waited:.2f}s queued, over its max_wait "
            f"budget — engine overloaded"))
        return True

    def _expire_queued(self):
        """Sweep the admission queue for requests past their queue-time
        budget. Runs every scheduler iteration, so even when every slot is
        busy with long generations the queued requests still fail within
        one decode tick of their budget. Free when no queued request
        carries a budget (the default), and atomic vs submit() so the
        drain/re-put can never reorder a racing newcomer ahead of older
        requests."""
        if self._budgeted <= 0 or self._queue.empty():
            return
        with self._lock:
            keep = []
            while True:
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    break
                if self._request_expired(item[4], item[5], item[7]):
                    self._consume_budget(item[7])
                else:
                    keep.append(item)
            for item in keep:  # FIFO order preserved
                self._queue.put(item)

    def _control_tick(self):
        """Scheduler-thread hook for out-of-band control work that must
        not race device dispatch (the paged engine drains its
        fetch_prefix/import_prefix control deque here — its page pool is
        donated through every decode dispatch, so off-thread access is
        unsafe by construction; docs/serving.md "Hierarchical KV").
        Base engine: nothing."""

    def _drain_tick(self, admitting: bool = False):
        """Read and commit a decode dispatch that was sent ahead of its
        predecessor's commit (hook: the paged engine's plain tick and its
        block model's pass look one dispatch ahead; this engine's tick is
        synchronous and has none in flight)."""

    def _await_tick(self):
        """The scheduler is about to block on a prefill (hook: the paged
        engine first reads the tick in flight, which the device runs
        before it)."""

    def _count_attention_tick(self):
        """Per-tick counters of the engine's attention path, taken under
        the lock the tick's bookkeeping holds (hook: the paged engine
        counts kernel and gather ticks)."""

    def _iterate(self, started: float) -> int:
        """One scheduler iteration: admission, then one decode tick over
        the live rows; returns how many rows it decoded. What it did goes
        to the tick log, and as spans into the profiler's trace: tick |
        admit | build | dispatch | fetch | commit, siblings that partition
        the iteration, and an admission's own parts siblings among them:
        admit (expiry, control) | claim (a request, its slot, its pages and
        its staging cache) | prefill | first_token | insert | activate,
        claim again for the next request.

        ``mlt.sched.tick`` opens the iteration and carries its index; it
        does not enclose the others. A tool that puts a device gap down
        to the host span overlapping it most (the benchmark's
        ``trace_reduce.attribute_gap``) would name an enclosing span for
        every gap, since a gap runs from one part into the next (measured,
        PERF.md PR 27)."""
        index = self._iterations
        self._iterations = index + 1
        with annotate("mlt.sched.tick", n=index):
            tick = self._tick = TickRecord(index, started)
        with annotate("mlt.sched.admit"):
            # fail-slow injection seam: an armed delay() narrowed to one
            # replica stretches every scheduler iteration there — TTFT
            # and ITL rise, nothing ever errors
            fire(FaultPoints.fleet_degrade, replica=self.replica,
                 engine=self._obs_name)
            self._expire_queued()
            self._control_tick()
        self._admission_tick()
        tick.admitted(time.perf_counter())
        # per-tenant ITL: one observation per adapter active in the tick
        # (captured BEFORE the tick — finished rows are reset inside it)
        tick_adapters = {s.adapter for s in self._slot_state if s.active}
        tick.rows = self._decode_tick()
        if not (tick.rows or tick.prefill_tokens or tick.tokens_out):
            # an idle poll writes nothing, and a device idle for want of
            # work is not the host's doing; from the first poll's end the
            # seconds are idle, no record's span and no reading's
            self._quiet_since = self._first_token_at = None
            if self._idle_since is None:
                self._idle_since = now = time.perf_counter()
                if self._cpu_seen is not None:
                    self._cpu_carry += time.thread_time() - self._cpu_seen
                    self._cpu_wall += now - self._last_t1
                    self._cpu_seen = None
            return 0
        stalled = self._close_record(tick)
        elapsed = tick.t1 - tick.t0
        tick_s = tick.t1 - tick.t_admit
        if tick.rows:
            with self._lock:
                self._adapter_labels_seen.update(
                    a for a in tick_adapters if a)
                self._count_attention_tick()
            for tick_adapter in tick_adapters:
                LLM_ITL.observe(elapsed, replica=self.replica,
                                adapter=tick_adapter)
            # decode dispatch alone (admission prefill excluded): the
            # per-tick attention cost the kernel work targets
            LLM_DECODE_TICK.observe(tick_s, replica=self.replica)
        if stalled:
            self._record_stall(tick)
        return tick.rows

    def _close_record(self, tick: TickRecord) -> bool:
        """The iteration's end: what closes its record over wall time (the
        gap before it and the idle part of that, what the device stood dry
        and the host's path behind a first token up to here, the
        collector's seconds since the last record, the thread's clock where
        a reading is due), then the record into the log. True where the log
        calls it a stall."""
        now = tick.t1 = time.perf_counter()
        if self._last_t1 is not None:
            tick.gap_s = tick.t0 - self._last_t1
            if self._idle_since is not None:
                tick.idle_s = tick.t0 - self._idle_since
        self._last_t1, self._idle_since = now, None
        # the rest of either interval belongs to the next record
        if self._quiet_since is not None:
            tick.dry_s += now - self._quiet_since
            self._quiet_since = now
        if self._first_token_at is not None:
            tick.after_prefill_s += now - self._first_token_at
            self._first_token_at = now
        collected = ticklog.gc_sums()
        if collected != self._gc_seen:
            seen, self._gc_seen = self._gc_seen, collected
            tick.gc_s = collected[0] - seen[0]
            tick.gc_gen = max((generation for generation in range(3)
                              if collected[1 + generation]
                              != seen[1 + generation]), default=0)
        self._read_thread(tick)
        return self._tick_log.append(tick)

    def _read_thread(self, tick: TickRecord):
        """The thread's clock and usage, every ``CPU_EVERY`` records and in
        a record long enough to be a stall: the difference since the
        reading before goes to this record with the wall seconds it covers
        (``cpu_s``, ``cpu_span_s``). After ``start()`` or an idle poll the
        record only starts a reading, at its end."""
        if self._cpu_seen is None:
            self._cpu_seen = time.thread_time()
            self._usage_seen = ticklog.thread_usage()
            return
        span_s = tick.span_s
        self._cpu_wall += span_s
        self._cpu_due -= 1
        if self._cpu_due > 0 and span_s <= ticklog.STALL_FLOOR_S:
            return
        cpu, usage = time.thread_time(), ticklog.thread_usage()
        tick.cpu_s = self._cpu_carry + cpu - self._cpu_seen
        tick.cpu_span_s = self._cpu_wall
        tick.nivcsw = usage[0] - self._usage_seen[0]
        tick.majflt = usage[1] - self._usage_seen[1]
        self._cpu_seen, self._usage_seen = cpu, usage
        self._cpu_wall = self._cpu_carry = 0.0
        self._cpu_due = ticklog.CPU_EVERY

    def _record_stall(self, tick: TickRecord):
        """A stalled iteration leaves its record, and its parts by cause,
        on the flight ring (``sched.stall``)."""
        fields = tick.as_dict()
        parts = ticklog.stall_parts(fields)
        with self._lock:
            self._stats["sched_stalls"] += 1
            self._stats["sched_stall_s_max"] = max(
                self._stats["sched_stall_s_max"], parts["span_s"])
        flight_record("sched.stall", engine=self._obs_name,
                      replica=self.replica, record=fields, **parts)

    def _loop(self, epoch: int = 0):
        try:
            while self._running:
                # the ITL sample spans the WHOLE iteration (admission
                # prefill included): an unchunked long-prompt prefill
                # between two decode ticks IS the inter-token gap clients
                # see, and the percentiles must show it
                started = time.perf_counter()
                # on-demand profiling (POST /debug/profile): claims or
                # advances an armed capture — one global check when dark
                profiler_tick(self._obs_name)
                if not self._iterate(started) \
                        and self._admission is None:
                    time.sleep(0.002)  # idle: poll admissions at 2ms
        except Exception as exc:  # noqa: BLE001 - a dead scheduler must
            # fail pending work loudly, not leave futures hanging forever
            logger.error("continuous batching scheduler died",
                         error=str(exc))
            flight_record("engine.crash", engine=self._obs_name,
                          replica=self.replica, error=str(exc),
                          error_type=type(exc).__name__)
            self._running = False
            self._stopped = True
            self._crash_exc = exc
            self._fail_pending(exc)
        finally:
            # epoch-guard handshake with stop(): register this epoch dead
            # and, if stop() already disowned teardown to us (its join
            # timed out while this thread was wedged in a dispatch), run
            # the teardown here — we are the only thread that may touch
            # the in-flight admission/slot state (_fail_pending is
            # idempotent, so the crash path above is safe to follow)
            with self._lock:
                self._dead_epochs.add(epoch)
                disowned = epoch in self._stale_epochs
                self._stale_epochs.discard(epoch)
            if disowned:
                self._fail_pending(EngineStoppedError(
                    "engine stopped while the request was pending"))

    def _drain_queue(self, exc: Exception):
        """Fail every request still in the (thread-safe) admission queue.
        Safe from any thread — each item is popped exactly once."""
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            future = item[4]
            if not future.done():
                future.set_exception(exc)

    def _fail_pending(self, exc: Exception):
        failed = int(self._admission is not None) \
            + sum(1 for s in self._slot_state
                  if s.active and s.future is not None
                  and not s.future.done()) + self._queue.qsize()
        flight_record("engine.fail_pending", engine=self._obs_name,
                      replica=self.replica, failed=failed,
                      error_type=type(exc).__name__)
        if not isinstance(exc, EngineStoppedError):
            # a crash teardown (scheduler death, not a clean stop) is a
            # post-mortem moment: the decision sequence into it — chaos
            # fires, admissions, breaker trips — is the debugging record
            get_flight_recorder().dump(
                "engine-crash", extra={"engine": self._obs_name,
                                       "error": str(exc)})
        adm, self._admission = self._admission, None
        if adm is not None:
            # a request parked mid-chunked-prefill fails with everything
            # else on stop/crash (and returns its storage)
            if not adm.future.done():
                adm.future.set_exception(exc)
            self._abort_admission(adm)
        with self._lock:
            self._budgeted = 0
        for i, slot in enumerate(self._slot_state):
            if not slot.active:
                continue
            if slot.future is not None and not slot.future.done():
                slot.future.set_exception(exc)
            self._slot_state[i] = _Slot()
            # return slot storage (paged: pages back to the free list,
            # prefix holds released) so teardown leaves the free list and
            # page table consistent; guarded — a crash mid-decode can
            # leave the dense cache donated, and storage cleanup must
            # never stop the remaining futures from failing
            try:
                self._release_slot_storage(i)
            except Exception:  # noqa: BLE001
                pass
        self._drain_queue(exc)
