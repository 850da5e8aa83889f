"""Paged KV cache for long-prompt serving (vLLM-style, TPU-shaped).

The dense continuous-batching cache reserves ``slots x max_len`` KV rows
even when most requests are short; a paged pool allocates KV in fixed-size
pages and maps each slot to pages through a page table, so the pool can be
sized for the EXPECTED total tokens, not slots x worst case — more
concurrent slots per chip at the same HBM.

TPU shaping (everything static under jit):
- pool:       [layers, n_pages + 1, page_size, kv_heads, head_dim] — the
  LAST physical page is a scratch page: writes for unmapped slots (-1 page
  ids) land there, so masked-out writes can never collide with a live
  page (scatter with duplicate indices has an undefined winner).
- page_table: [slots, pages_per_slot] int32 (page ids; -1 = unmapped)
- attention:  the kernel path reads the pool THROUGH the page table in
  place (decode: one token/slot; prefix-hit prefill: a suffix chunk over
  the cached pages, LSE-merged with the local flash — both in
  ops/paged_attention.py, int8 pools included via in-kernel dequant).
  The reference path gathers the slot's pages into a dense
  [slots, max_len] view per layer and runs the same masked attention as
  the dense engine — HBM-bandwidth work of the same order as
  attention's cache read, which is exactly what the kernels eliminate.
- page allocation/free is host-side bookkeeping in the scheduler thread
  (a free-list), exactly where the dense engine's slot bookkeeping lives.

Pages for prompt + max_new_tokens are reserved at admission, so decode can
never run out mid-generation (no preemption path needed).

Prefix-aware KV reuse (docs/serving.md "Prefill & prefix cache"): full
page-size blocks of each prompt are indexed in a radix trie
(serving/prefix.py) mapping block-chains to page ids with refcounts. On
admission the longest cached chain is shared read-only into the new
slot's page table (refcount++) and ONLY the uncached suffix is prefilled
— the dominant TTFT win on repeated-system-prompt traffic. Refcount-0
pages stay cached and are evicted LRU (leaf-first) when an allocation
needs them; eviction fires the ``llm.prefix_evict`` chaos point and the
evictable pool counts toward ``_free_page_frac`` so the PR 2 degradation
ladder sees reclaimable headroom, not just the raw free list.

No reference analog: the reference has no inference engine
(mlrun/serving/v2_serving.py calls user predict()).
"""

from __future__ import annotations

import functools
import queue
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..chaos import FaultPoints, fire
from ..config import mlconf
from ..models.llama import LlamaConfig, embed, head_logits
from ..obs import KV_TIER_BYTES, KV_TIER_EVENTS, KV_TIER_HITS, wall_now
from ..utils import logger
from ..utils.profiler import annotate, named
from .kv_tier import HostKVTier
from .llm import (
    _forward_with_cache,
    _kv_rows,
    _serving_layers,
    expert_counters,
    init_kv_cache,
    init_slot_state,
    refuse_layout,
)
from .llm_batch import (
    BlockDecodingError,
    ContinuousBatchingEngine,
    EngineStoppedError,
    KVHandoff,
    _Admission,
)
from .prefix import PrefixCache, block_chain_key


# where a pool that keeps a per-slot state holds it, beside its page buffers
STATE = "state"


def init_paged_pool(config: LlamaConfig, n_pages: int, page_size: int,
                    kv_dtype: str = "native", slots: int = 0) -> dict:
    """Page pool pytree with ``n_pages`` physical pages (callers that need
    a scratch page pass n_pages + 1 and keep the last id out of the free
    list): one buffer [layers, n_pages, page_size, *row] for each of
    ``config.cache_rows()`` over the layers that leave rows
    (``config.cache_layers``), so the config's type decides the layout
    (per-head keys and values; one latent and one rotated key a token for
    a latent family). The int8 variant carries per-vector scales.

    Beside the pages, for a family with a recurrent state and ``slots``
    given: under ``STATE`` what each of ``slots`` sequences keeps whatever
    its length, one buffer [state_layers, slots, *shape] for each of
    ``config.state_rows()`` (serving/llm.py ``init_slot_state``). It rides
    in the pool's tree, so every program that is handed the pool (and
    donates it) is handed the state."""
    if kv_dtype not in ("native", "int8"):
        raise ValueError(f"unknown kv_dtype '{kv_dtype}' (native | int8)")
    refuse_layout(config, "an int8 page pool", kv_dtype == "int8")
    lead = (config.cache_layers, n_pages, page_size)
    if kv_dtype == "int8":
        shape = lead + config.cache_rows()["k"]
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], jnp.float32),
        }
    pool = {name: jnp.zeros(lead + row, config.dtype)
            for name, row in config.cache_rows().items()}
    if slots and config.recurrent_state:
        pool[STATE] = init_slot_state(config, slots)
    return pool


def _buffers(pool: dict) -> list:
    """The names of the pool's page buffers, the rows' before their
    scales'."""
    return sorted((name for name in pool if name != STATE),
                  key=lambda name: (name.endswith("_scale"), name))


def _scratch_page(pool: dict) -> int:
    """The id of the pool's last physical page, which is never read."""
    return pool[_buffers(pool)[0]].shape[1] - 1


def insert_prompt_pages(pool: dict, small: dict, page_ids: jax.Array,
                        page_size: int, slot=None) -> dict:
    """Scatter a prefilled slot-cache (``small`` from init_kv_cache with
    batch=1, max_len a multiple of page_size) into the pool at
    ``page_ids`` ([pages_per_slot] int32). Ids < 0 write to the scratch
    page (last physical page) — never to a live one. ``slot`` (a traced
    int32, for a pool that keeps a per-slot state): the state ``small``
    holds after the prompt's last token overwrites the slot's, whatever
    the slot's last request or a tick still in flight for it left there."""
    scratch = _scratch_page(pool)
    pages = page_ids.shape[0]
    if slot is not None:
        pool = {**pool, STATE: {
            name: jax.lax.dynamic_update_slice_in_dim(
                buffer, small[name].astype(buffer.dtype), slot, axis=1)
            for name, buffer in pool[STATE].items()}}

    def body(p, pool_):
        pid = page_ids[p]
        pid_safe = jnp.where(pid >= 0, pid, scratch)
        out = dict(pool_)
        for name in _buffers(pool_):
            row = jax.lax.dynamic_slice_in_dim(
                small[name][:, 0], p * page_size, page_size, axis=1)
            out[name] = jax.lax.dynamic_update_index_in_dim(
                pool_[name], row.astype(pool_[name].dtype), pid_safe,
                axis=1)
        return out

    return jax.lax.fori_loop(0, pages, body, pool)


def gather_prefix_pages(pool: dict, small: dict, page_ids: jax.Array,
                        page_size: int) -> dict:
    """Inverse of :func:`insert_prompt_pages`: copy cached prefix pages
    from the pool into a batch=1 slot-cache (``small`` from
    init_kv_cache), so a suffix-only prefill can attend over the reused
    prefix KV without recomputing it. Ids < 0 leave the corresponding
    rows untouched (one compile covers every prefix length)."""
    pages = page_ids.shape[0]

    def body(p, small_):
        pid = page_ids[p]
        out = dict(small_)
        for name in _buffers(pool):
            if name not in small_:
                continue
            row = pool[name][:, jnp.maximum(pid, 0)]
            cur = jax.lax.dynamic_slice_in_dim(
                small_[name][:, 0], p * page_size, page_size, axis=1)
            row = jnp.where(pid >= 0, row.astype(small_[name].dtype), cur)
            out[name] = jax.lax.dynamic_update_slice_in_dim(
                small_[name][:, 0], row, p * page_size, axis=1)[:, None]
        return out

    return jax.lax.fori_loop(0, pages, body, small)


def _write_token_all_layers(pool: dict, k_tok, v_tok, page_table, pos,
                            page_size: int, scales=None) -> dict:
    """k_tok/v_tok: [L, slots, H, D]; write each slot's token into its
    current page at pos % page_size. Slots with an unmapped page (id < 0,
    e.g. inactive) write to the scratch page instead — duplicate scratch
    writes are harmless because the scratch page is never read."""
    scratch = _scratch_page(pool)
    page_idx = pos // page_size
    offset = pos % page_size
    pid = jnp.take_along_axis(page_table, page_idx[:, None], axis=1)[:, 0]
    pid_safe = jnp.where(pid >= 0, pid, scratch)

    out = dict(pool)
    rows = {"k": k_tok, "v": v_tok}
    if scales is not None:
        rows["k_scale"] = scales[0]
        rows["v_scale"] = scales[1]
    for name, row in rows.items():
        if name not in pool:
            continue
        out[name] = out[name].at[:, pid_safe, offset].set(
            row.astype(out[name].dtype))
    return out


def _pool_write(pool: dict, layer: int, pid_safe, offset, k, v) -> dict:
    """Layer ``layer``'s K and V into ``pool`` (a dict the caller owns,
    updated in place) at pages ``pid_safe`` and offsets ``offset``, before
    the layer's kernel reads them: quantised per vector on an int8 pool.
    Returns the rows as written ({"k", "v"[, "k_scale", "v_scale"]})."""
    rows = _kv_rows(pool, k, v)
    for name, row in rows.items():
        pool[name] = pool[name].at[layer, pid_safe, offset].set(row)
    return rows


def _pool_scales(pool: dict) -> dict:
    """The int8 pool's scales as the paged kernels' keyword arguments."""
    return {name: pool[name] for name in ("k_scale", "v_scale")
            if name in pool}


def _decode_rowwise_paged(config: LlamaConfig, page_size: int,
                          attn_impl: str, params,
                          tokens: jax.Array, pool: dict,
                          page_table: jax.Array, pos: jax.Array,
                          rng: jax.Array = None,
                          temperature: jax.Array = None,
                          top_k: jax.Array = None, top_p: jax.Array = None,
                          lora=None, adapter_ids: jax.Array = None,
                          prev_token: jax.Array = None,
                          from_prev: jax.Array = None,
                          with_loads: bool = False):
    """One decode token per slot against the page pool.

    ``attn_impl="reference"``: per layer, gather the slot's pages into a
    dense [slots, max_len] view, splice the just-computed token into the
    view for attention (it is only written to the pool once, for all
    layers, at the end), run the dense masked attention.

    ``attn_impl="kernel"``: per layer, scatter the token's KV into the
    pool FIRST (one [slots] page-table-routed write; int8 pools
    quantize per vector on the way in), then run the pallas
    paged-decode kernel which reads the pool THROUGH the page table —
    the dense view is never materialized, and on int8 pools the
    per-vector scales ride page-table-indexed operands with dequant
    in-register (ops/paged_attention.py). Both paths store and read
    identical bits at identical positions (int8 included — they share
    one _quantize_kv), so greedy decoding is token-identical between
    them.

    ``lora``/``adapter_ids`` add per-row multi-tenant LoRA exactly like
    the dense ``_decode_rowwise`` (docs/serving.md "Multi-tenant LoRA"):
    each slot gathers its own (A, B) bank factors by adapter slot index.

    tokens [slots, 1]; pos [slots] absolute positions. ``prev_token``
    [slots] with ``from_prev`` [slots] bool: a row marked there takes its
    input token from ``prev_token`` (the last tick's ``next_token``, which
    may still be on its way to the host) and not from ``tokens``.
    A pool that keeps a per-slot state (``pool[STATE]``, a family with a
    recurrent state) has the live rows' states advanced by the token, in
    place (models/nemotron_h.py ``mamba_step``); a dead row's stays.
    Returns (next_token, new_pool, new_pos); with ``with_loads`` (the
    engine's program of an expert model) a fourth output follows: one
    int32 vector of the tokens and behind them the tick's
    ``expert_counters``, which one fetch brings.
    """
    from ..ops.paged_attention import paged_attention
    from .llm import _cached_attention, _quantize_kv
    from .sampling import sample_logits

    if prev_token is not None:
        tokens = jnp.where(from_prev[:, None], prev_token[:, None], tokens)
    positions = pos[:, None]
    rows = jnp.arange(tokens.shape[0])
    safe_table = jnp.maximum(page_table, 0)            # [slots, pages]
    live = page_table[:, :1] >= 0       # [slots, 1]: the row holds a request
    x = embed(config, params, tokens)
    cos, sin = config.rope(positions)
    quantized = "k_scale" in pool
    use_kernel = attn_impl == "kernel"
    if use_kernel or config.latent_cache:
        scratch = _scratch_page(pool)
        page_idx = pos // page_size
        offset = pos % page_size
        pid = jnp.take_along_axis(page_table, page_idx[:, None],
                                  axis=1)[:, 0]
        pid_safe = jnp.where(pid >= 0, pid, scratch)
        pool = dict(pool)
    k_new, v_new = [], []
    ssm = None
    if config.recurrent_state:
        from ..models.nemotron_h import mamba_step

        pool = dict(pool)
        state = pool[STATE] = dict(pool[STATE])

        def ssm(layer, lp, h, proj):
            out, state["ssm"], state["conv"] = mamba_step(
                config, lp, h, state["ssm"], state["conv"], layer,
                live[:, 0], proj)
            return out

    def attend_kernel(layer, q, k, v):
        # token KV lands in the pool first (unmapped slots route to
        # the never-read scratch page), then the kernel attends
        # pool-side via the page table — no dense view, no gather.
        # int8 pools quantize the token per vector on the way in and
        # the kernel dequantizes in-register (scales ride
        # page-table-indexed operands)
        _pool_write(pool, layer, pid_safe, offset, k[:, 0], v[:, 0])
        # the kernel takes the pool as stored and the layer's
        # index: a sliced layer would be a copy per call
        return paged_attention(
            q[:, 0], pool["k"], pool["v"], layer, page_table,
            pos, page_size=page_size, impl="kernel",
            **_pool_scales(pool))[:, None]

    def attend_reference(layer, q, k, v):
        # dense per-layer view of this slot's pages (dequantized)
        kp = jnp.take(pool["k"][layer], safe_table, axis=0)
        vp = jnp.take(pool["v"][layer], safe_table, axis=0)
        s_, p_, ps_, hh, dd = kp.shape
        kd = kp.reshape(s_, p_ * ps_, hh, dd)
        vd = vp.reshape(s_, p_ * ps_, hh, dd)
        if quantized:
            ksc = jnp.take(pool["k_scale"][layer], safe_table,
                           axis=0).reshape(s_, p_ * ps_, hh)
            vsc = jnp.take(pool["v_scale"][layer], safe_table,
                           axis=0).reshape(s_, p_ * ps_, hh)
            kd = (kd.astype(jnp.float32) * ksc[..., None]).astype(
                config.dtype)
            vd = (vd.astype(jnp.float32) * vsc[..., None]).astype(
                config.dtype)
        else:
            kd = kd.astype(config.dtype)
            vd = vd.astype(config.dtype)
        # splice the new token into the dense view at each slot's
        # position
        kd = kd.at[rows, pos].set(k[:, 0])
        vd = vd.at[rows, pos].set(v[:, 0])
        k_new.append(k[:, 0])
        v_new.append(v[:, 0])
        return _cached_attention(config, q, kd, vd, positions, kd.shape[1])

    if config.latent_cache:
        attend_kernel, attend_reference = _latent_decode_attends(
            config, page_size, params, pool, page_table, pos, pid_safe,
            offset)
    x, loads = _serving_layers(
        config, params, x, cos, sin,
        attend_kernel if use_kernel else attend_reference, lora,
        adapter_ids, live=live, ssm=ssm)
    logits = head_logits(config, params, x)[:, 0]
    if rng is None:
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        next_token = sample_logits(logits, rng, temperature, top_k, top_p)
    # an expert model's tick also yields its experts' load, in one vector
    # with the tokens: one fetch brings both
    packed = ()
    if with_loads:
        with jax.named_scope("head"):
            packed = (jnp.concatenate([next_token, expert_counters(loads)]),)

    if use_kernel or config.latent_cache:
        # KV was written layer-by-layer before each attention call
        return (next_token, pool, pos + 1) + packed

    # one pooled write for all layers: [L, slots, H, D]
    k_tok = jnp.stack(k_new)
    v_tok = jnp.stack(v_new)
    if quantized:
        kq, ks = _quantize_kv(k_tok)
        vq, vs = _quantize_kv(v_tok)
        new_pool = _write_token_all_layers(
            pool, kq, vq, page_table, pos, page_size, scales=(ks, vs))
    else:
        new_pool = _write_token_all_layers(
            pool, k_tok, v_tok, page_table, pos, page_size)
    return (next_token, new_pool, pos + 1) + packed


def _latent_decode_attends(config, page_size: int, params, pool: dict,
                           page_table, pos, pid_safe, offset):
    """The two ``attend`` closures of a decode tick over a latent pool
    (``pool``: the tick's own dict, updated in place): the token's cache
    row (latent, then rotated key) into its page first, then the absorbed
    form over the slot's rows. ``kernel``: ``mla_paged_decode`` reads the
    pool through the page table; ``reference``: the pages gathered into a
    dense view
    and attended by plain products. Both store and read the same rows."""
    from ..models.xing4 import absorb_query, unfold_values
    from ..ops.mla_attention import (
        absorbed_attention,
        gather_latents,
        mla_paged_decode,
    )

    scale = config.softmax_scale
    rank = config.kv_lora_rank

    def written(layer, rows):
        pool["ckr"] = pool["ckr"].at[layer, pid_safe, offset].set(
            rows[:, 0].astype(pool["ckr"].dtype))
        return params["layers"]["w_ukv"][layer]

    def attend_kernel(layer, q, rows, _):
        w_ukv = written(layer, rows)
        o_lat = mla_paged_decode(
            absorb_query(config, w_ukv, q[:, 0]), pool["ckr"], layer,
            page_table, pos, page_size=page_size, rank=rank, scale=scale)
        return unfold_values(config, w_ukv, o_lat, q.dtype)[:, None]

    def attend_reference(layer, q, rows, _):
        w_ukv = written(layer, rows)
        dense = gather_latents(pool["ckr"], layer, page_table)
        visible = jnp.arange(dense.shape[1])[None, None, :] \
            <= pos[:, None, None]
        o_lat = absorbed_attention(absorb_query(config, w_ukv, q), dense,
                                   visible, rank=rank, scale=scale)
        return unfold_values(config, w_ukv, o_lat, q.dtype)

    return attend_kernel, attend_reference


def _verify_rowwise_paged(config: LlamaConfig, page_size: int,
                          attn_impl: str, params, chunk: jax.Array,
                          pool: dict, page_table: jax.Array,
                          pos: jax.Array, lora=None,
                          adapter_ids: jax.Array = None,
                          masked: jax.Array = None,
                          count: jax.Array = None,
                          prev_ids: jax.Array = None,
                          prev_masked: jax.Array = None,
                          from_prev: jax.Array = None):
    """Batched multi-token forward of one chunk a slot against the page
    pool: the speculative verify (docs/serving.md "Speculative decoding")
    and, with ``masked`` given, the pass of a block-diffusion model
    (docs/serving.md "Block-diffusion decoding"). ``chunk``: [slots, S]
    ids at absolute positions ``pos[r]..pos[r]+S-1``; for the verify, each
    slot's committed last token plus its k draft proposals. ONE forward
    computes the target argmax at all S positions per slot.

    A **denoising or commit pass** (``masked`` [slots, S] bool, S =
    ``config.block_length``, ``pos[r]`` the block's start): masked lanes
    embed ``config.mask_token_id`` whatever id the chunk holds there, the
    chunk attends its prefix pages and, under the block mask, all of
    itself. A row that rides from the pass in flight (``from_prev[r]``)
    takes its ids and its mask from that pass's block state, still on the
    device (``prev_ids``, ``prev_masked`` [slots, S]), as the plain tick
    takes ``prev_token``; every other row from ``chunk`` and ``masked``.
    After the head the pass unmasks its share of each row itself, under
    ``low_confidence_static``: ``count[r]`` (int32, the host's schedule; 0
    for a row in its commit pass) of the row's still-masked lanes, the
    most confident first and equal confidences by lower position, are set
    to their argmax (``_most_confident``). It returns ``(packed, new_pool,
    ids_after, masked_after)``: the last two the block state for the next
    pass, on the device; ``packed`` one int32 vector of, in order, the
    argmax ``x0`` of every lane [slots * S], the bits of its float32
    confidence ``softmax(logits)[x0]`` [slots * S], which lanes the pass
    unmasked [slots * S], and three counters of the expert layers over the
    live rows (pairs routed and experts that got a pair, both summed over
    layers, and the most pairs one expert got in one layer; zeros for a
    dense MLP): one fetch brings all of it. A pass writes the chunk's keys
    and values like the verify does: a denoising pass's are overwritten by
    the block's commit pass before anything reads them (the prefix part
    reads positions below ``pos[r]`` only), the rule the speculative path
    relies on too. Rows in a commit pass (no lane masked) and rows in a
    denoising pass share the dispatch; which is which is the host's.

    ``attn_impl="kernel"``: per layer, the chunk's KV scatters into the
    pool through the page table FIRST (int8 pools quantize per vector on
    the way in), then ``paged_verify_attention`` attends the prefix
    pages IN PLACE — the verify chunk is the prefill kernel's q-chunk
    form batched per slot, LSE-merged with the chunk's local causal
    part. No dense gather, no ``all_logits`` dense forward.

    ``attn_impl="reference"``: the gather+dense fallback
    (``paged_verify_reference``), bit-consistent with the reference
    decode path (raw chunk KV spliced into the dequantized view).

    Rollback is the host's ``_pos`` rewind: chunk writes land inside the
    slot's admission-reserved pages (``k_eff <= remaining`` keeps every
    accepted lane under the reservation; over-reservation lanes of rows
    speculating fewer than S-1 tokens route to the scratch page), and
    entries past the accepted position are overwritten before any later
    query can attend them — no page ever has to move back to the free
    list mid-round. ``pos`` is NOT advanced here; the host commits it.

    The verify returns (verified [slots, S] int32, new_pool).
    """
    from ..ops.paged_attention import paged_verify_attention
    from .llm import _dequantize_kv

    b, s = chunk.shape
    pps = page_table.shape[1]
    positions = pos[:, None] + jnp.arange(s)[None, :]     # [slots, S]
    live = jnp.broadcast_to(page_table[:, :1] >= 0, (b, s))
    if masked is not None:
        ids = jnp.where(from_prev[:, None], prev_ids, chunk)
        masked = jnp.where(from_prev[:, None], prev_masked, masked)
        chunk = jnp.where(masked, config.mask_token_id, ids)
    x = embed(config, params, chunk)
    cos, sin = config.rope(positions)
    use_kernel = attn_impl == "kernel"
    scratch = _scratch_page(pool)
    page_idx = positions // page_size
    offset = positions % page_size
    pid = jnp.take_along_axis(page_table,
                              jnp.minimum(page_idx, pps - 1), axis=1)
    # lanes past the slot's mapped reservation (rows speculating fewer
    # than S-1 tokens this round) route to the never-read scratch page;
    # distinct in-reservation positions can never collide (one page id
    # per page index, one offset per position)
    pid_safe = jnp.where((pid >= 0) & (page_idx < pps), pid, scratch)
    pool = dict(pool)

    def attend(layer, q, k, v):
        written = _pool_write(pool, layer, pid_safe, offset, k, v)
        if use_kernel and "k_scale" in written:
            # the kernel's local chunk part must see the SAME bits a
            # later decode tick reads back from the int8 pool
            chunk_k = _dequantize_kv(written["k"], written["k_scale"],
                                     config.dtype)
            chunk_v = _dequantize_kv(written["v"], written["v_scale"],
                                     config.dtype)
        else:
            # reference decode splices the RAW token KV into its
            # dequantized view — the verify fallback matches it
            chunk_k, chunk_v = k, v
        return paged_verify_attention(
            q, chunk_k, chunk_v, pool["k"], pool["v"], layer,
            page_table, pos, page_size=page_size,
            impl="kernel" if use_kernel else "reference",
            block_length=config.block_length,
            **_pool_scales(pool)).astype(q.dtype)

    x, loads = _serving_layers(config, params, x, cos, sin, attend, lora,
                               adapter_ids, live=live)
    logits = head_logits(config, params, x)
    verified = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if masked is None:
        return verified, pool
    with jax.named_scope("head"):
        # softmax(logits)[x0] = exp(max - logsumexp)
        confidence = jnp.exp(jnp.max(logits, axis=-1)
                             - jax.nn.logsumexp(logits, axis=-1))
        confidence = confidence.astype(jnp.float32)
        chosen = _most_confident(confidence, masked, count)
        counters = expert_counters(loads) if loads \
            else jnp.zeros((3,), jnp.int32)
        packed = jnp.concatenate([
            verified.reshape(-1),
            jax.lax.bitcast_convert_type(confidence, jnp.int32).reshape(-1),
            chosen.astype(jnp.int32).reshape(-1),
            counters])
    return packed, pool, jnp.where(chosen, verified, ids), masked & ~chosen


def _most_confident(confidence: jax.Array, masked: jax.Array,
                    count: jax.Array) -> jax.Array:
    """The lanes a pass unmasks under ``low_confidence_static`` ([slots, S]
    bool): of each row's ``masked`` lanes the ``count[r]`` of highest
    ``confidence`` (float32), equal confidences by lower position. A lane's
    rank is the masked lanes that go before it, a pairwise comparison: no
    sort, and no ``top_k`` whose order among equals is unspecified."""
    lane = jnp.arange(confidence.shape[1])
    ours, theirs = confidence[:, None, :], confidence[:, :, None]
    before = (theirs > ours) | ((theirs == ours)
                                & (lane[:, None] < lane[None, :]))
    rank = jnp.sum(before & masked[:, :, None], axis=1)
    return masked & (rank < count[:, None])


class _RowPass(NamedTuple):
    """What a dispatched pass is to one of its rows, all of it known at
    dispatch by count: ``base`` the block's start, ``index`` the pass's
    number within the block (None: the block's commit pass), ``outlived``
    whether the row is a row of the next pass too (False only where the
    commit completes the answer or reaches the cache's end)."""

    base: int
    index: Optional[int]
    outlived: bool = True


@dataclass
class _TickInFlight:
    """A decode dispatch (a plain tick, or a block model's pass) that was
    sent and is not yet read: ``rows`` the slots whose next token, or
    whose block's values, it yields (a row whose request turns out to have
    ended before it leaves the list: what the dispatch holds for it is
    thrown away), ``next_token`` [slots] on the device, ``host`` what was
    fetched once it is there, ``rng`` the engine's key as it was before
    this tick drew from it (None: every row was greedy)."""

    rows: list
    next_token: Optional[jax.Array]
    rng: Optional[jax.Array] = None
    host: Optional[np.ndarray] = None
    # what the host fetches: ``next_token``, or for an expert model the
    # vector that holds the experts' counters behind the tokens, and the
    # record of the iteration that dispatched the tick, which gets them
    fetched: Optional[jax.Array] = None
    record: Optional[object] = None
    # a pass (``record.kind`` "denoise") has no ``next_token``: what it is
    # to each of its rows, and the block state it leaves on the device for
    # the pass behind it (ids and mask after its own unmasking, [slots, S])
    passes: Optional[dict] = None
    block: Optional[tuple] = None
    # the engine's count of programs enqueued as this one went out: the
    # fetch that reads it read the device's last program where none followed
    seq: int = 0


class PagedContinuousBatchingEngine(ContinuousBatchingEngine):
    """Continuous batching over a paged KV pool.

    Same scheduler contract as ContinuousBatchingEngine (submit/generate/
    start/stop/warmup/stats), but slot KV lives in a shared page pool:
    ``n_pages`` defaults to the dense equivalent (slots x pages_per_slot);
    size it SMALLER to oversubscribe memory when typical prompt+generation
    lengths are below max_len. Pages for prompt+max_new are reserved at
    admission and requests wait (in order) until enough pages are free.

    A model with ``config.block_length`` > 1 generates by diffusion over
    blocks (docs/serving.md "Block-diffusion decoding"): its ticks are
    ``_denoise_tick``, ``denoising_steps`` passes a block (default: the
    block length) under the ``remasking`` rule, then a commit pass.
    """

    _serves_blocks = True
    _serves_latent = True
    REMASKING = ("low_confidence_static",)

    def __init__(self, config: LlamaConfig, params, max_len: int = 2048,
                 slots: int = 4, prefill_buckets: tuple = (128, 512, 1024),
                 seed: int = 0, kv_dtype: str = "native",
                 page_size: int = 128, n_pages: int | None = None,
                 max_queue_size: int = 0, max_wait: float = 0.0,
                 degradation: dict | None = None,
                 prefill_chunk: int | None = None,
                 latency_window: int | None = None,
                 prefix_cache: bool | None = None,
                 attention_impl: str | None = None,
                 adapters=None, max_live_adapters: int | None = None,
                 adapter_rate: float | None = None,
                 adapter_burst: float | None = None,
                 request_ledger: bool | None = None,
                 kv_tier=None, speculative: dict | None = None,
                 denoising_steps: int | None = None,
                 remasking: str = "low_confidence_static"):
        from ..ops.paged_attention import resolve_paged_impl

        if max_len % page_size:
            raise ValueError(
                f"max_len {max_len} must be a multiple of page_size "
                f"{page_size} (a partial last page would misalign KV rows)")
        block = int(getattr(config, "block_length", 1))
        if page_size % block:
            raise BlockDecodingError(
                f"page_size {page_size} is not a multiple of block_length "
                f"{block}: a cached prefix page would depend on the page "
                f"after it")
        self.denoising_steps = block if denoising_steps is None \
            else int(denoising_steps)
        if not 1 <= self.denoising_steps <= block:
            raise BlockDecodingError(
                f"denoising_steps {self.denoising_steps} must lie in "
                f"[1, block_length {block}]")
        if remasking not in self.REMASKING:
            raise BlockDecodingError(
                f"remasking rule {remasking!r} is not supported (have "
                f"{list(self.REMASKING)})")
        self.remasking = remasking
        # set before super().__init__ — _make_cache runs during it
        self.page_size = page_size
        self.pages_per_slot = max_len // page_size
        self.n_pages = n_pages or slots * self.pages_per_slot
        # _pending exists before super().__init__ so _queue_depth /
        # pressure_level are safe during construction
        self._pending: deque = deque()
        # a prefix hit skips tokens a recurrent state needs: such a family
        # gets no index to look a prompt up in, and one asked for is refused
        refuse_layout(config, "prefix reuse", bool(prefix_cache)
                      and config.recurrent_state)
        if prefix_cache is None:
            prefix_cache = bool(mlconf.serving.llm.prefix_cache) \
                and not config.recurrent_state
        self._prefix = PrefixCache(page_size) if prefix_cache else None
        # trie nodes each slot holds a refcount on (matched + registered)
        self._slot_prefix_nodes: dict[int, list] = {}
        # host-RAM KV tier (docs/serving.md "Hierarchical KV"): evicted
        # prefix chains demote host-side and promote back on admission.
        # ``kv_tier`` accepts True/False, a config-style dict, or None
        # (mlconf.serving.llm.kv_tier decides); needs the prefix cache
        conf = mlconf.serving.llm.get("kv_tier")
        tier_conf = dict(conf.to_dict()) if conf is not None else {}
        if isinstance(kv_tier, dict):
            # an explicit dict arg opts in unless it says otherwise
            tier_conf.update(kv_tier)
            kv_tier = kv_tier.get("enabled", True)
        elif kv_tier is None:
            kv_tier = tier_conf.get("enabled", False)
        self._kv_tier = (
            HostKVTier(int(tier_conf.get("host_bytes", 64 << 20)))
            if kv_tier and self._prefix is not None else None)
        # (a recurrent family has no prefix index for a tier to hang on)
        refuse_layout(config, "the host KV tier", self._kv_tier is not None
                      or bool(kv_tier) and config.recurrent_state)
        # fetch_prefix/import_prefix control ops queue here and run on
        # the scheduler thread between ticks (_control_tick): the page
        # pool is donated through every decode dispatch, so off-thread
        # pool access is unsafe by construction
        self._control: deque = deque()
        super().__init__(config, params, max_len=max_len, slots=slots,
                         prefill_buckets=prefill_buckets, seed=seed,
                         kv_dtype=kv_dtype, max_queue_size=max_queue_size,
                         max_wait=max_wait, degradation=degradation,
                         prefill_chunk=prefill_chunk,
                         latency_window=latency_window,
                         attention_impl=attention_impl,
                         adapters=adapters,
                         max_live_adapters=max_live_adapters,
                         adapter_rate=adapter_rate,
                         adapter_burst=adapter_burst,
                         request_ledger=request_ledger,
                         speculative=speculative)
        # decode path: pallas paged kernel (page-table indexed) or the
        # gather+dense reference — resolved once, from the same knob the
        # base class resolved the prefill path from. int8 pools run the
        # SAME kernel (per-vector dequant scales ride page-table-indexed
        # operands); an explicit kernel request that cannot be honored
        # raised typed inside resolve_paged_impl.
        self.attn_impl = resolve_paged_impl(self.attention_impl)
        # prefix-hit suffix prefill: "kernel" attends the cached prefix
        # pages IN PLACE (multi-row paged prefill kernel LSE-merged with
        # the local flash over the suffix — docs/serving.md "Attention
        # kernels"); "gather" is the dense gather_prefix_pages seed
        # (reference/CPU fallback)
        # a latent pool's prefix pages are small: a hit gathers them into
        # the admission's rows, and its chunks expand them like its own
        self.paged_prefill_impl = (
            "kernel" if self.prefill_impl == "flash"
            and not config.latent_cache else "gather")
        # +1 physical page: the scratch page for masked writes
        self._pool = init_paged_pool(config, self.n_pages + 1, page_size,
                                     kv_dtype, slots=slots)
        self._page_table = np.full((slots, self.pages_per_slot), -1,
                                   np.int32)
        self._pos = np.zeros((slots,), np.int32)
        self._free_pages: deque = deque(range(self.n_pages))
        self._slot_pages: dict[int, list] = {}
        # the plain tick and a block model's pass look one dispatch ahead
        # (docs/serving.md "The scheduler's iteration"): the one sent and
        # not yet read, and what a dispatch with none before it is handed
        # in place of that one's tokens or block state
        self._in_flight: Optional[_TickInFlight] = None
        self._no_tokens = jnp.zeros((slots,), jnp.int32)
        self._no_block = (jnp.zeros((slots, block), jnp.int32),
                          jnp.zeros((slots, block), bool))
        # HBM bytes the gather path would copy per decode tick (the dense
        # k+v view of every slot, per layer) — what the kernel path avoids
        pages = {name: self._pool[name] for name in _buffers(self._pool)}
        self._gather_bytes_per_tick = sum(
            arr.dtype.itemsize * arr.shape[0] * slots * max_len
            * int(np.prod(arr.shape[3:]))
            for name, arr in pages.items() if not name.endswith("_scale"))
        # bytes a token leaves in the pool over the layers that leave rows,
        # scales included
        self.kv_bytes_per_token = sum(
            arr.dtype.itemsize * arr.shape[0]
            * int(np.prod(arr.shape[3:])) for arr in pages.values())
        # bytes a slot keeps beside its pages whatever its length: a
        # recurrent family's state over its layers (0 for any other)
        self.state_bytes_per_slot = sum(
            arr.dtype.itemsize * arr.shape[0] * int(np.prod(arr.shape[2:]))
            for arr in self._pool.get(STATE, {}).values())
        self._stats.update({"attn_kernel_ticks": 0, "attn_gather_ticks": 0,
                            "attn_hbm_bytes_avoided": 0,
                            "lookahead_ticks": 0, "lookahead_drains": 0,
                            "prefill_kernel_chunks": 0,
                            "prefill_gather_admissions": 0,
                            "kv_demotes": 0, "kv_demoted_pages": 0,
                            "kv_promotes": 0, "kv_promoted_pages": 0,
                            "kv_fetches": 0, "kv_fetched_pages": 0,
                            "kv_imports": 0, "kv_imported_pages": 0})
        # the paged engine's prefill carries the pool page size so a
        # prefix-hit dispatch can attend pool pages in place
        # (prefix_kv= — see _prefill_dispatch)
        self._prefill = jax.jit(named("mlt_prefill", functools.partial(
            _forward_with_cache, config, attn_impl=self.prefill_impl,
            page_size=page_size, **self._loads_kw())))
        self._decode_paged = jax.jit(
            named("mlt_decode", functools.partial(
                _decode_rowwise_paged, config, page_size, self.attn_impl,
                **self._loads_kw())),
            donate_argnums=(2,))
        # a block model's pass: the verify program with a mask bitmap
        self._denoise_paged = jax.jit(
            named("mlt_denoise", functools.partial(
                _verify_rowwise_paged, config, page_size, self.attn_impl)),
            donate_argnums=(2,))
        self._insert_paged = jax.jit(
            named("mlt_insert", functools.partial(
                insert_prompt_pages, page_size=page_size)),
            donate_argnums=(0,))
        self._gather_paged = jax.jit(
            named("mlt_gather", functools.partial(
                gather_prefix_pages, page_size=page_size)),
            donate_argnums=(1,))

    def _make_cache(self):
        return None  # slot KV lives in the page pool

    def warmup(self):
        started = time.perf_counter()
        ids = jnp.full((self.pages_per_slot,), -1, jnp.int32)
        prefill_kw = self._lora_kwargs(0)
        decode_kw = self._lora_kwargs()
        for bucket in self.prefill_buckets:
            small = init_kv_cache(self.config, 1, self.max_len,
                                  kv_dtype=self.kv_dtype)
            small = self._prefill(
                self.params, jnp.zeros((1, bucket), jnp.int32), small,
                logits_at=np.int32(bucket - 1), **prefill_kw)[1]
            self._pool = self._insert_paged(self._pool, small, ids,
                                            **self._state_slot(0))
        if self.prefill_chunk and self.prefill_chunk not in \
                self.prefill_buckets:
            small = init_kv_cache(self.config, 1, self.max_len,
                                  kv_dtype=self.kv_dtype)
            self._prefill(self.params,
                          jnp.zeros((1, self.prefill_chunk), jnp.int32),
                          small, logits_at=np.int32(self.prefill_chunk - 1),
                          **prefill_kw)
        if self._prefix is not None:
            if self.paged_prefill_impl == "kernel":
                # compile the merged prefix-hit prefill programs (every
                # bucket/chunk shape) — the first cache hit must not pay
                # the compile. All-(-1) ids route to the never-read
                # scratch page; outputs are discarded
                ids = jnp.full((self.pages_per_slot,), -1, jnp.int32)
                prefix_kv = {"k": self._pool["k"], "v": self._pool["v"],
                             "page_ids": ids,
                             "base": jnp.int32(self.page_size)}
                if "k_scale" in self._pool:
                    prefix_kv["k_scale"] = self._pool["k_scale"]
                    prefix_kv["v_scale"] = self._pool["v_scale"]
                shapes = set(self.prefill_buckets)
                if self.prefill_chunk:
                    shapes.add(self.prefill_chunk)
                for shape in sorted(shapes):
                    small = init_kv_cache(self.config, 1, self.max_len,
                                          kv_dtype=self.kv_dtype)
                    self._prefill(self.params,
                                  jnp.zeros((1, shape), jnp.int32),
                                  small, prefix_kv=prefix_kv,
                                  logits_at=np.int32(shape - 1),
                                  **prefill_kw)
            else:
                # compile the prefix-page gather (first cache hit must
                # not pay the compile); all-(-1) ids touch no live page
                small = init_kv_cache(self.config, 1, self.max_len,
                                      kv_dtype=self.kv_dtype)
                self._gather_paged(
                    self._pool, small,
                    jnp.full((self.pages_per_slot,), -1, jnp.int32))
        step = jnp.zeros((self.slots, 1), jnp.int32)
        table = jnp.asarray(self._page_table)
        pos = jnp.asarray(self._pos)
        if self.block_length > 1:
            # the one program a block model decodes with (all-(-1) table:
            # every write lands on the scratch page), as the tick dispatches
            # it: each row's block state is the host's or the last pass's,
            # still on the device
            shape = (self.slots, self.block_length)
            state = self._no_block
            for _ in range(2):
                packed, self._pool, *state = self._denoise_paged(
                    self.params, jnp.zeros(shape, jnp.int32), self._pool,
                    table, pos, masked=jnp.ones(shape, bool),
                    count=jnp.zeros((self.slots,), jnp.int32),
                    prev_ids=state[0], prev_masked=state[1],
                    from_prev=jnp.zeros((self.slots,), bool), **decode_kw)
            jax.block_until_ready(packed)
            logger.info("paged engine warm", slots=self.slots,
                        pages=self.n_pages, page_size=self.page_size,
                        block_length=self.block_length,
                        warmup_s=round(time.perf_counter() - started, 2))
            return
        # as the tick dispatches them: each row's input token is the
        # host's or the last tick's, still on the device
        decode_kw.update(prev_token=self._no_tokens,
                         from_prev=jnp.zeros((self.slots,), bool))
        tok, self._pool = self._decode_paged(
            self.params, step, self._pool, table, pos, **decode_kw)[:2]
        jax.block_until_ready(tok)
        tok, self._pool = self._decode_paged(
            self.params, step, self._pool, table, pos,
            jax.random.PRNGKey(0),
            jnp.zeros((self.slots,), jnp.float32),
            jnp.zeros((self.slots,), jnp.int32),
            jnp.ones((self.slots,), jnp.float32), **decode_kw)[:2]
        jax.block_until_ready(tok)
        self._spec_warmup()
        logger.info("paged engine warm", slots=self.slots,
                    pages=self.n_pages, page_size=self.page_size,
                    warmup_s=round(time.perf_counter() - started, 2))

    def _spec_warmup_verify(self):
        # all-(-1) table routes every chunk write to the scratch page
        # and marks zero pages live; outputs are discarded
        chunk = jnp.zeros((self.slots, self.spec_k + 1), jnp.int32)
        table = jnp.full((self.slots, self.pages_per_slot), -1, jnp.int32)
        pos = jnp.zeros((self.slots,), jnp.int32)
        lora_kw = self._lora_kwargs(self._slot_adapter_ids()) \
            if self._adapters is not None else {}
        _, self._pool = self._spec_verify_fn()(
            self.params, chunk, self._pool, table, pos, **lora_kw)

    # -- resilience: page-pool pressure + pending-deque expiry ---------------
    def _free_page_frac(self) -> float:
        """KV-page headroom — the degradation ladder degrades (speculative
        off, max_new clamp) before admission would start blocking on an
        exhausted pool. Refcount-0 cached prefix pages are reclaimable on
        demand, so they count as headroom."""
        if not self.n_pages:
            return 1.0
        free = len(self._free_pages)
        if self._prefix is not None:
            free += self._prefix.evictable_pages()
        return free / self.n_pages

    def _queue_depth(self) -> int:
        return self._queue.qsize() + len(self._pending)

    def _expire_queued(self):
        super()._expire_queued()
        # head-of-line requests parked waiting for pages also carry a
        # queue-time budget
        while self._pending and self._request_expired(
                self._pending[0][4], self._pending[0][5],
                self._pending[0][7]):
            self._pending.popleft()

    # -- admission: page reservation + prefix reuse -------------------------
    def _reclaim_pages(self, needed: int):
        """Evict LRU refcount-0 cached prefix pages until the free list
        covers ``needed`` pages. Fires the ``llm.prefix_evict`` chaos
        point per evicted page. With the host KV tier enabled each
        victim demotes host-side first (docs/serving.md "Hierarchical
        KV") — a failed demote loses the chain to the tier but never
        blocks the reclaim."""
        if self._prefix is None or len(self._free_pages) >= needed:
            return
        # a victim's page is read from the host (a demote), and the tick in
        # flight may hold the rows whose end frees the pages wanted
        self._drain_tick(admitting=True)
        tier = self._kv_tier
        # _Node doesn't know its adapter — recover it from which
        # per-adapter root the victim's chain hangs off (one map per
        # reclaim, not per victim)
        root_adapters = {id(root): name for name, root
                         in self._prefix._roots.items()} \
            if tier is not None else None

        def on_evict(node):
            fire(FaultPoints.llm_prefix_evict, page_id=node.page_id,
                 refcount=node.refcount, last_used=node.last_used)
            if tier is None:
                return
            try:
                self._demote_node(node, root_adapters)
            except Exception:  # noqa: BLE001 - demote is best-effort:
                # the page is reclaimed either way, the chain is simply
                # lost to the tier
                with self._lock:
                    self._stats["kv_demotes"] += 1
                KV_TIER_EVENTS.inc(engine=self._obs_name,
                                   replica=self.replica, op="demote",
                                   outcome="error")

        freed = self._prefix.evict(needed - len(self._free_pages),
                                   on_evict)
        self._free_pages.extend(freed)

    def _demote_node(self, node, root_adapters: dict):
        """Copy one eviction victim's page host-side into the KV tier,
        keyed by its block-chain identity (chaos ``llm.kv_demote``).
        Eviction is leaf-first, so a chain demotes child-before-parent;
        the tier's ancestors-outlive-descendants eviction keeps promote
        probes hole-free regardless."""
        blocks = []
        cur = node
        while cur.parent is not None:
            blocks.append(cur.block)
            cur = cur.parent
        adapter = root_adapters.get(id(cur), "")
        blocks.reverse()
        flat = [t for block in blocks for t in block]
        key = block_chain_key(flat, self.page_size, adapter=adapter)
        parent_key = block_chain_key(
            flat[:-self.page_size], self.page_size, adapter=adapter) \
            if len(blocks) > 1 else None
        fire(FaultPoints.llm_kv_demote, key=key, page_id=node.page_id,
             blocks=len(blocks), adapter=adapter)
        pages = {name: np.asarray(self._pool[name][:, node.page_id])
                 for name in self._pool}
        stored = self._kv_tier.put(key, parent_key, pages)
        with self._lock:
            self._stats["kv_demotes"] += 1
            if stored:
                self._stats["kv_demoted_pages"] += 1
        KV_TIER_EVENTS.inc(engine=self._obs_name, replica=self.replica,
                           op="demote",
                           outcome="ok" if stored else "fallback")
        KV_TIER_BYTES.set(self._kv_tier.bytes_used,
                          engine=self._obs_name, replica=self.replica)

    def _tier_probe(self, prompt, adapter: str, k: int) -> list:
        """Consecutive host-tier payloads for the blocks just past the
        first ``k`` device-matched ones, probed root-down and stopped at
        the first miss (the tier's ancestors-outlive-descendants
        invariant makes deeper probes pointless). Same cap as
        ``PrefixCache.match``: at least one suffix token always remains
        to prefill."""
        limit = max(0, (len(prompt) - 1) // self.page_size)
        hits: list = []
        for i in range(k, limit):
            payload = self._kv_tier.get(block_chain_key(
                prompt[:(i + 1) * self.page_size], self.page_size,
                adapter=adapter))
            if payload is None:
                break
            hits.append(payload)
        return hits

    def _tier_import(self, hits: list, ids, k: int) -> int:
        """Write probed host-tier payloads into the admission's already
        reserved fresh pages — the ``gather_prefix_pages``-inverse
        import: host rows land at the pool pages the slot's page table
        already points at, bit-identical to what was demoted (chaos
        ``llm.kv_promote``). Returns the number of promoted blocks."""
        fire(FaultPoints.llm_kv_promote, blocks=len(hits), base_blocks=k)
        pids = jnp.asarray(np.asarray(ids[k:k + len(hits)], np.int32))
        for name in self._pool:
            rows = jnp.asarray(np.stack([h[name] for h in hits], axis=1))
            self._pool[name] = self._pool[name].at[:, pids].set(
                rows.astype(self._pool[name].dtype))
        with self._lock:
            self._stats["kv_promotes"] += 1
            self._stats["kv_promoted_pages"] += len(hits)
        KV_TIER_EVENTS.inc(engine=self._obs_name, replica=self.replica,
                           op="promote", outcome="ok")
        KV_TIER_HITS.inc(len(hits), engine=self._obs_name,
                         replica=self.replica, tier="host")
        return len(hits)

    # -- hierarchical KV: cross-replica page fetch ---------------------------
    def fetch_prefix(self, prompt_tokens, adapter: str = "") -> Future:
        """Assemble this engine's cached KV for ``prompt_tokens``'s
        leading full blocks into a prefix-only :class:`KVHandoff`
        (device pages first, extended through the host tier) — the wire
        payload a reassigned key's new ring owner imports via
        :meth:`import_prefix` instead of re-prefilling (docs/serving.md
        "Hierarchical KV"). Resolves to None when nothing is cached.
        The op runs on the scheduler thread between ticks
        (``_control_tick``): the page pool is donated through every
        decode dispatch, so off-thread pool reads are unsafe."""
        refuse_layout(self.config, "a KV handoff (fetch_prefix)")
        future: Future = Future()
        self._control.append(("fetch", (list(prompt_tokens), adapter),
                              future))
        if not self._running:
            self.start()
        return future

    def import_prefix(self, handoff: KVHandoff) -> Future:
        """Import a :meth:`fetch_prefix` payload's full blocks into the
        page pool + prefix index without admitting a request — the
        receiving side of the fetch hop. Resolves to the number of newly
        cached pages (0 = already cached, or no pages free)."""
        refuse_layout(self.config, "a KV handoff (import_prefix)")
        expects_scales = self.kv_dtype == "int8"
        wire_dtype = getattr(handoff, "kv_dtype", None) or (
            "int8" if "k_scale" in handoff.kv else "native")
        if wire_dtype != self.kv_dtype or \
                ("k_scale" in handoff.kv) != expects_scales:
            raise ValueError(
                f"KV handoff dtype mismatch: engine kv_dtype="
                f"'{self.kv_dtype}' cannot import a '{wire_dtype}' "
                f"payload — fetch and import pools must quantize alike "
                f"(docs/serving.md 'Engine fleet')")
        future: Future = Future()
        self._control.append(("import", (handoff,), future))
        if not self._running:
            self.start()
        return future

    def _control_tick(self):
        if self._control:
            self._drain_tick(admitting=True)    # the ops read the pool
            # and may write it: nothing is known of the device behind them
            self._enqueued(time.perf_counter(), work=False)
        while self._control:
            kind, args, future = self._control.popleft()
            if future.done():
                continue
            try:
                if kind == "fetch":
                    future.set_result(self._do_fetch_prefix(*args))
                else:
                    future.set_result(self._do_import_prefix(*args))
            except Exception as exc:  # noqa: BLE001 - a control op must
                # fail its own future, never the scheduler
                future.set_exception(exc)

    def _do_fetch_prefix(self, prompt, adapter: str):
        if self._prefix is None:
            return None
        matched_pages, nodes = self._prefix.match(prompt, adapter=adapter)
        k = len(matched_pages)
        try:
            kv: dict = {}
            if k:
                pids = np.asarray(matched_pages, np.int64)
                for name in self._pool:
                    rows = np.asarray(self._pool[name][:, pids])
                    kv[name] = rows.reshape(
                        rows.shape[0], k * self.page_size,
                        *rows.shape[3:])
            tier_rows = [] if self._kv_tier is None \
                else self._tier_probe(prompt, adapter, k)
            if tier_rows:
                for name in self._pool:
                    stacked = np.stack([h[name] for h in tier_rows],
                                       axis=1)
                    rows = stacked.reshape(
                        stacked.shape[0],
                        len(tier_rows) * self.page_size,
                        *stacked.shape[3:])
                    kv[name] = np.concatenate([kv[name], rows], axis=1) \
                        if name in kv else rows
        finally:
            self._prefix.release(nodes)
        total = k + len(tier_rows)
        if not total:
            KV_TIER_EVENTS.inc(engine=self._obs_name,
                               replica=self.replica, op="fetch",
                               outcome="miss")
            return None
        rows_tok = total * self.page_size
        handoff = KVHandoff(
            prompt=list(prompt[:rows_tok]), first_token=-1, kv=kv,
            prompt_len=rows_tok, kv_dtype=self.kv_dtype,
            cached_prefix=rows_tok, replica=self.replica,
            adapter=adapter, prewarm=True)
        with self._lock:
            self._stats["kv_fetches"] += 1
            self._stats["kv_fetched_pages"] += total
        KV_TIER_EVENTS.inc(engine=self._obs_name, replica=self.replica,
                           op="fetch", outcome="ok")
        return handoff

    def _do_import_prefix(self, handoff: KVHandoff) -> int:
        if self._prefix is None:
            return 0
        prompt = list(handoff.prompt)
        full = min(len(prompt), handoff.prompt_len) // self.page_size
        full = min(full, self.pages_per_slot)
        if full <= 0:
            return 0
        adapter = handoff.adapter
        # a fetch payload is EXACTLY full blocks; match() always leaves
        # one suffix token unmatched, so probe with a sentinel token to
        # see every already-cached block (the sentinel is never indexed)
        _, nodes = self._prefix.match(prompt + [0], adapter=adapter)
        k = len(nodes)
        fresh: list = []
        try:
            need = full - k
            if need > 0:
                self._reclaim_pages(need)
            if need > len(self._free_pages):
                # partial import stays contiguous root-down, so the
                # chain invariant holds for whatever fits
                need = len(self._free_pages)
                full = k + need
            if need <= 0:
                return 0
            fresh = [self._free_pages.popleft() for _ in range(need)]
            ids = np.full((self.pages_per_slot,), -1, np.int32)
            ids[k:full] = fresh
            pids = jnp.asarray(np.asarray(fresh, np.int32))
            for name in self._pool:
                payload = np.asarray(handoff.kv[name][
                    :, k * self.page_size:full * self.page_size])
                payload = payload.reshape(
                    payload.shape[0], need, self.page_size,
                    *payload.shape[2:])
                self._pool[name] = self._pool[name].at[:, pids].set(
                    jnp.asarray(payload).astype(self._pool[name].dtype))
            new_nodes, claimed = self._prefix.register(
                prompt[:full * self.page_size], ids, nodes,
                adapter=adapter)
            claimed_set = set(claimed)
            self._free_pages.extend(
                p for p in fresh if p not in claimed_set)
            fresh = []
            nodes = nodes + new_nodes
            with self._lock:
                self._stats["kv_imports"] += 1
                self._stats["kv_imported_pages"] += len(claimed)
            KV_TIER_HITS.inc(len(claimed), engine=self._obs_name,
                             replica=self.replica, tier="remote")
            return len(claimed)
        except Exception:
            self._free_pages.extend(fresh)
            raise
        finally:
            self._prefix.release(nodes)

    def _remove_kv_tier_series(self):
        """Drop this engine's hierarchical-KV series on stop — the same
        series-lifecycle contract the stats-mirror families follow
        (scale-down must not leak series)."""
        labels = {"engine": self._obs_name, "replica": self.replica}
        KV_TIER_BYTES.remove(**labels)
        for tier in ("device", "host", "remote"):
            KV_TIER_HITS.remove(tier=tier, **labels)
        for op in ("demote", "promote", "fetch"):
            for outcome in ("ok", "miss", "fallback", "error"):
                KV_TIER_EVENTS.remove(op=op, outcome=outcome, **labels)

    def _unregister_metrics(self):
        super()._unregister_metrics()
        self._remove_kv_tier_series()

    def _prepare_admission(self) -> _Admission | None:
        free = next((i for i, s in enumerate(self._slot_state)
                     if not s.active), None)
        if free is None:
            return None
        while True:
            if not self._pending:
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    return None
                # the item left the admission queue; the head-of-line
                # sweep in _expire_queued tracks it from here
                self._consume_budget(item[7])
                self._pending.append(item)
            item = self._pending[0]
            if not self._validate_item(item):
                self._pending.popleft()
                continue
            (request_id, prompt, max_new, eos_id, future, submitted,
             sampling, expires) = item[:8]
            extra = item[9] if len(item) > 9 else None
            adapter = item[10] if len(item) > 10 else ""
            ledger = item[11] if len(item) > 11 else None
            prompt_len = len(prompt)
            # a block model denoises its last block whole and may overrun
            # by up to B - 1 positions: a page holds whole blocks
            # (page_size % B == 0), so these pages cover that too
            needed = -(-(prompt_len + max_new) // self.page_size)
            if needed > self.n_pages:
                # would never fit — fail fast instead of blocking the
                # queue head forever
                self._pending.popleft()
                future.set_exception(ValueError(
                    f"request needs {needed} pages but the pool has only "
                    f"{self.n_pages}; raise n_pages or lower "
                    f"max_new_tokens"))
                continue
            matched_pages: list = []
            matched_nodes: list = []
            # an imported handoff arrives with its full prompt KV — a
            # local prefix match would only re-gather what the payload
            # already carries, so imports always take fresh pages.
            # Matching is per ADAPTER root: KV computed under adapter A
            # is never served to adapter B (same-tenant hits still
            # share — docs/serving.md "Multi-tenant LoRA")
            if self._prefix is not None and not isinstance(extra, KVHandoff):
                matched_pages, matched_nodes = self._prefix.match(
                    prompt, adapter=adapter)
            k = len(matched_pages)
            fresh_needed = needed - k
            available = len(self._free_pages)
            if self._prefix is not None:
                available += self._prefix.evictable_pages()
            if available < fresh_needed:
                # head-of-line waits for pages (in order); drop the match
                # holds so the cached prefix stays evictable meanwhile —
                # the parked time keeps charging queue_wait on the
                # ledger (the request is still waiting, not being served)
                if self._prefix is not None:
                    self._prefix.release(matched_nodes)
                return None
            if ledger is not None and adapter:
                ledger.enter("adapter_load_wait")
            adapter_slot = self._resolve_adapter(adapter, future)
            if adapter_slot is None:
                # adapter load failed — request failed typed; release
                # the match holds and move on
                if self._prefix is not None:
                    self._prefix.release(matched_nodes)
                self._pending.popleft()
                continue
            if ledger is not None:
                # claimed for good: page reservation + prefix gather
                # below are admission work
                ledger.enter("admission")
            self._pending.popleft()
            fresh: list = []
            try:
                if self._prefix is not None \
                        and not isinstance(extra, KVHandoff):
                    self._prefix.queries += 1
                    if k:
                        self._prefix.hits += 1
                        self._prefix.cached_tokens += k * self.page_size
                        if self._kv_tier is not None:
                            KV_TIER_HITS.inc(
                                k, engine=self._obs_name,
                                replica=self.replica, tier="device")
                self._reclaim_pages(fresh_needed)
                fresh = [self._free_pages.popleft()
                         for _ in range(fresh_needed)]
                ids = np.full((self.pages_per_slot,), -1, np.int32)
                ids[:k] = matched_pages
                ids[k:needed] = fresh
                # host-tier promote (docs/serving.md "Hierarchical KV"):
                # blocks just past the device match that are resident in
                # the host tier import into their already-reserved fresh
                # pages instead of prefilling from tokens. A failed
                # promote degrades to plain token prefill — the fresh
                # pages are simply prefilled over — never a client error
                if self._kv_tier is not None and k < needed \
                        and not isinstance(extra, KVHandoff):
                    hits = self._tier_probe(prompt, adapter, k)
                    if hits:
                        if ledger is not None:
                            ledger.enter("promote")
                        try:
                            promoted = self._tier_import(hits, ids, k)
                            new_nodes, claimed = self._prefix.register(
                                prompt[:(k + promoted) * self.page_size],
                                ids, matched_nodes, adapter=adapter)
                            matched_nodes = matched_nodes + new_nodes
                            if claimed:
                                claimed_set = set(claimed)
                                fresh = [p for p in fresh
                                         if p not in claimed_set]
                            k += promoted
                        except Exception:  # noqa: BLE001 - fall back
                            # to prefilling the suffix from tokens
                            KV_TIER_EVENTS.inc(
                                engine=self._obs_name,
                                replica=self.replica, op="promote",
                                outcome="error")
                        if ledger is not None:
                            ledger.enter("admission")
                adm = _Admission(
                    slot=free, request_id=request_id, prompt=prompt,
                    max_new=max_new, eos_id=eos_id, future=future,
                    submitted=submitted, sampling=sampling,
                    expires=expires, trace=item[8], claimed=wall_now(),
                    base=k * self.page_size, offset=k * self.page_size,
                    adapter=adapter, adapter_slot=adapter_slot,
                    ledger=ledger)
                adm.page_ids = ids
                adm.pages = fresh
                adm.prefix_nodes = matched_nodes
                self._apply_directive(adm, extra)
                if adm.small is None:
                    adm.small = init_kv_cache(self.config, 1, self.max_len,
                                              kv_dtype=self.kv_dtype)
                if k:
                    prefix_ids = ids.copy()
                    prefix_ids[k:] = -1
                    if self.paged_prefill_impl == "kernel":
                        # the suffix prefill attends the shared prefix
                        # pages IN PLACE through the page ids (merged
                        # paged-prefill kernel) — the cached KV is
                        # never materialized densely (the acceptance
                        # stat: prefill_gather_admissions stays 0)
                        adm.kernel_prefix = True
                        adm.prefix_ids = prefix_ids
                    else:
                        # reference fallback: seed the batch=1 cache
                        # with a dense gather of the prefix KV; the
                        # suffix-only prefill attends over it from
                        # pos=base
                        with self._lock:
                            self._stats[
                                "prefill_gather_admissions"] += 1
                        adm.small = self._gather_paged(
                            self._pool, adm.small,
                            jnp.asarray(prefix_ids))
                # the staging cache is filled on the device (and a promote
                # or a gather wrote into it)
                self._enqueued(time.perf_counter(), work=False)
                return adm
            except Exception as exc:
                # popped but not yet tracked in self._admission: fail the
                # future and give back the storage before the scheduler
                # dies (e.g. an armed llm.prefix_evict error), or the
                # request would hang outside every drained container
                self._free_pages.extend(fresh)
                if self._prefix is not None:
                    self._prefix.release(matched_nodes)
                if not future.done():
                    future.set_exception(exc)
                raise

    def _prefill_dispatch(self, adm: _Admission, tokens, logits_at,
                          lora_kw):
        """Prefix-hit admissions on the kernel path attend the cached
        prefix pages in place: the pool + page ids ride the dispatch as
        ``prefix_kv`` and every chunk LSE-merges the paged-prefill
        kernel's partial state with the local attention over the suffix
        rows."""
        if not adm.kernel_prefix:
            return super()._prefill_dispatch(adm, tokens, logits_at,
                                             lora_kw)
        prefix_kv = {"k": self._pool["k"], "v": self._pool["v"],
                     "page_ids": jnp.asarray(adm.prefix_ids),
                     "base": jnp.int32(adm.base)}
        if "k_scale" in self._pool:
            prefix_kv["k_scale"] = self._pool["k_scale"]
            prefix_kv["v_scale"] = self._pool["v_scale"]
        with self._lock:
            self._stats["prefill_kernel_chunks"] += 1
        return super()._prefill_dispatch(adm, tokens, logits_at, lora_kw,
                                         prefix_kv=prefix_kv)

    def _handoff_kv(self, adm: _Admission, rows: int) -> dict:
        kv = super()._handoff_kv(adm, rows)
        k = adm.base // self.page_size
        if not adm.kernel_prefix or not k:
            return kv
        # kernel-prefix exports: rows < base were never gathered into
        # the slot cache — assemble them from the shared pool pages at
        # serialization time (a host copy of exactly the prefix pages,
        # the unavoidable wire copy; int8 pages + scales ship as-is,
        # never densified to fp32)
        ids = np.asarray(adm.page_ids[:k], np.int64)
        for name, payload in list(kv.items()):
            if not payload.flags.writeable:
                payload = kv[name] = payload.copy()
            pages = np.asarray(self._pool[name][:, ids])
            payload[:, :adm.base] = pages.reshape(
                pages.shape[0], adm.base, *pages.shape[3:])
        return kv

    def _complete_storage(self, adm: _Admission):
        k = adm.base // self.page_size
        insert_ids = np.asarray(adm.page_ids, np.int32).copy()
        # shared prefix pages are read-only — route their rows to scratch
        insert_ids[:k] = -1
        self._pool = self._insert_paged(self._pool, adm.small,
                                        jnp.asarray(insert_ids),
                                        **self._state_slot(adm.slot))
        held = list(adm.prefix_nodes)
        pages = list(adm.pages)
        # imported handoffs skip registration: a decode-pool replica never
        # serves prefills, so caching their blocks would only displace
        # pages without ever producing a hit. Exception: a pre-warm
        # replay (register_import, serving/podfleet.py) imports exactly
        # to seed this engine's prefix index before it takes ring traffic
        if self._prefix is not None and \
                (not adm.prefilled or adm.register_import):
            # index this prompt's freshly written full blocks for future
            # reuse UNDER THE REQUEST'S ADAPTER ROOT; claimed pages
            # become cache-owned (not freed on release — they stay
            # cached until evicted)
            new_nodes, claimed = self._prefix.register(
                adm.prompt, adm.page_ids, adm.prefix_nodes,
                adapter=adm.adapter)
            held.extend(new_nodes)
            if claimed:
                claimed_set = set(claimed)
                pages = [p for p in pages if p not in claimed_set]
        self._slot_pages[adm.slot] = pages
        self._slot_prefix_nodes[adm.slot] = held
        self._page_table[adm.slot] = adm.page_ids
        # where the next step writes: the prompt's end, or for a block
        # model the start of the block that the prompt's tail opens
        self._pos[adm.slot] = self._prompt_lead(len(adm.prompt))

    def _state_slot(self, slot: int) -> dict:
        """The insert program's keyword that names the slot whose state an
        admission overwrites; absent for a pool without one, whose program
        is then as it was."""
        return {"slot": np.int32(slot)} if STATE in self._pool else {}

    def _abort_admission(self, adm: _Admission):
        self._free_pages.extend(adm.pages)
        if self._prefix is not None:
            self._prefix.release(adm.prefix_nodes)

    def _fail_pending(self, exc: Exception):
        # head-of-line requests parked in the pending deque must fail
        # with everything else on stop/crash
        while self._pending:
            future = self._pending.popleft()[4]
            if not future.done():
                future.set_exception(exc)
        # queued fetch/import control ops fail the same way — a fetch
        # hop waiting on a stopping replica must not hang
        while self._control:
            future = self._control.popleft()[2]
            if not future.done():
                future.set_exception(exc)
        if isinstance(exc, EngineStoppedError):
            try:
                # a clean stop answers a request whose last tick has run,
                # and reads the counters its last dispatches left
                self._drain_tick()
                self._settle_loads()
            except Exception:  # noqa: BLE001 - teardown goes on
                pass
        # after a crash the commit may have stopped half way: the tick in
        # flight is not read, its rows fail with the rest
        self._in_flight = None
        super()._fail_pending(exc)

    def _release_slot_storage(self, index: int):
        for pid in self._slot_pages.pop(index, []):
            self._free_pages.append(pid)
        if self._prefix is not None:
            # cache-owned pages: drop this slot's holds; refcount-0 pages
            # STAY cached (hot prefixes survive across requests) until
            # the LRU eviction reclaims them under pool pressure
            self._prefix.release(self._slot_prefix_nodes.pop(index, []))
        self._page_table[index] = -1
        self._pos[index] = 0
        self._spec_release_slot(index)

    # paged-only cumulative stats mirrored to mlt_llm_events_total
    _COUNTER_STATS = ContinuousBatchingEngine._COUNTER_STATS + (
        "attn_kernel_ticks", "attn_gather_ticks", "attn_hbm_bytes_avoided",
        "lookahead_ticks", "lookahead_drains",
        "prefill_kernel_chunks", "prefill_gather_admissions",
        "kv_demotes", "kv_demoted_pages", "kv_promotes",
        "kv_promoted_pages", "kv_fetches", "kv_fetched_pages",
        "kv_imports", "kv_imported_pages", "expert_pairs",
        "experts_touched")

    @property
    def stats(self) -> dict:
        out = ContinuousBatchingEngine.stats.fget(self)
        out["decode_attn_impl"] = self.attn_impl
        out["paged_prefill_impl"] = self.paged_prefill_impl
        out["kv_bytes_per_token"] = self.kv_bytes_per_token
        out["state_bytes_per_slot"] = self.state_bytes_per_slot
        out["free_pages"] = len(self._free_pages)
        if self._prefix is not None:
            queries = self._prefix.queries
            out["prefix_queries"] = queries
            out["prefix_hits"] = self._prefix.hits
            out["prefix_hit_rate"] = (
                self._prefix.hits / queries if queries else 0.0)
            out["prefix_cached_tokens"] = self._prefix.cached_tokens
            out["prefix_evictions"] = self._prefix.evictions
            out["prefix_cached_pages"] = self._prefix.cached_pages()
        if self._kv_tier is not None:
            out["kv_tier"] = self._kv_tier.stats()
        return out

    # -- speculative decoding (paged hooks; policy lives in the base) ----

    def _make_verify_fn(self):
        return jax.jit(
            named("mlt_verify", functools.partial(
                _verify_rowwise_paged, self.config, self.page_size,
                self.attn_impl)),
            donate_argnums=(2,))

    def _spec_apply_positions(self, committed: dict):
        # the pool-side rollback: rejected draft positions simply aren't
        # committed — their pool entries are overwritten before any read
        # (docs/serving.md "Speculative decoding"). Pages were reserved
        # at admission for prompt+max_new, and k_eff <= remaining keeps
        # every chunk write inside that reservation, so nothing moves on
        # the free list and _free_page_frac stays honest by construction.
        for index, value in committed.items():
            self._pos[index] = value

    def _spec_verify_dispatch(self, chunk, active):
        table = jnp.asarray(self._page_table)
        pos = jnp.asarray(self._pos)
        lora_kw = self._lora_kwargs(self._slot_adapter_ids()) \
            if self._adapters is not None else {}
        verified, self._pool = self._spec_verify_fn()(
            self.params, jnp.asarray(chunk), self._pool, table, pos,
            **lora_kw)
        return np.asarray(verified)

    def _count_attention_tick(self):
        # the microbench/acceptance stat, once per decoded iteration (a
        # verify dispatch is one attention tick like any other): on the
        # kernel path the tick never gathers a dense view
        # (attn_gather_ticks stays 0) and the avoided HBM copy is
        # accounted per tick
        if self.attn_impl == "kernel":
            self._stats["attn_kernel_ticks"] += 1
            self._stats["attn_hbm_bytes_avoided"] += \
                self._gather_bytes_per_tick
        else:
            self._stats["attn_gather_ticks"] += 1

    def _plain_decode_tick(self, active) -> int:
        """Dispatch the next tick, then read the one before it: build |
        dispatch | fetch | commit, the first two for tick k+1 and the last
        two for tick k, so the device runs k+1 while the host reads k. A
        row of k takes its input token from k's ``next_token`` on the
        device; its position is a count the host keeps (``_pos`` advances
        at dispatch). A row that k completes by count (its last token, the
        cache's end) is no row of k+1; one that ends on an end-of-sequence
        id is learnt at commit(k), rode in k+1 too, and that token is
        thrown away (its K/V write lands in the row's own page: whatever
        takes the page next is ordered after k+1 through the pool).
        Returns the rows dispatched."""
        tick = self._tick
        ahead = self._in_flight
        riding = set(ahead.rows) if ahead is not None else ()
        rows = [i for i in active if i not in riding
                or self._outlives_tick(self._slot_state[i])]
        if not rows:
            self._drain_tick()
            return 0
        with annotate("mlt.sched.build"):
            last = np.zeros((self.slots, 1), np.int32)
            from_prev = np.zeros((self.slots,), bool)
            for i in rows:
                if i in riding:
                    from_prev[i] = True
                else:
                    last[i, 0] = self._slot_state[i].tokens[-1]
            # the tick's own copies (the host's arrays move on while it
            # runs), and only its rows: any other writes to the scratch page
            table = np.full_like(self._page_table, -1)
            table[rows] = self._page_table[rows]
            pos = np.zeros_like(self._pos)
            pos[rows] = self._pos[rows]
            tick.ctx_tokens = int(pos.sum()) + len(rows)
            if STATE in self._pool:
                tick.state_rows = len(rows)
            self._pos[rows] += 1
            lora_kw = self._lora_kwargs(self._slot_adapter_ids()) \
                if self._adapters is not None else {}
            self._ledger_mark(rows, "decode_active")
            rng = self._rng
            sampling = self._sampling_args(rows)
            args = (jnp.asarray(last), self._pool, jnp.asarray(table),
                    jnp.asarray(pos)) + sampling
            prev_token = ahead.next_token if ahead is not None \
                else self._no_tokens
        tick.t_built = time.perf_counter()
        with annotate("mlt.sched.dispatch"):
            out = self._decode_paged(
                self.params, *args, prev_token=prev_token,
                from_prev=jnp.asarray(from_prev), **lora_kw)
            next_token, self._pool = out[:2]
            # an expert model's tokens come with its experts' counters
            fetched = out[3] if len(out) > 3 else next_token
            fetched.copy_to_host_async()
        self._sent(_TickInFlight(rows, next_token,
                                 rng if sampling else None,
                                 fetched=fetched, record=tick), ahead)
        return len(rows)

    def _sent(self, sent: _TickInFlight, ahead: Optional[_TickInFlight]):
        """The tick or pass just dispatched is in flight, and the one
        before it, ``ahead``, is read while it runs."""
        tick = self._tick
        self._in_flight = sent
        tick.t_dispatched = tick.t_fetched = time.perf_counter()
        self._enqueued(tick.t_dispatched)
        sent.seq = self._sent_seq
        if ahead is not None:
            tick.lookahead = 1
            with self._lock:
                self._stats["lookahead_ticks"] += 1
            self._land(ahead)

    def _outlives_tick(self, slot) -> bool:
        """Whether a row of the tick in flight is a row of the next one
        too, by count: the tick in flight yields neither the last token
        asked for nor the cache's last position."""
        return slot.remaining > 1 and \
            slot.prompt_len + len(slot.tokens) + 1 < self.max_len

    def _await_tick(self):
        """The scheduler is about to wait on a prefill queued behind the
        tick in flight: that tick's tokens come first, and its rows wait
        from here (``decode_stall``) until their next dispatch. The caller
        times the wait (``inflight_wait_s``); the prefill went out behind
        the tick, so the device is not dry when this returns."""
        ahead = self._in_flight
        if ahead is not None and ahead.host is None:
            ahead.host = np.asarray(ahead.fetched)
            self._ledger_mark(ahead.rows, "decode_stall")

    def _drain_tick(self, admitting: bool = False):
        """Read and commit the tick in flight with nothing dispatched
        behind it: before whatever needs the committed state or the pool
        on the host, and when no row is left to dispatch. ``admitting``:
        the wait falls into the iteration's admission part."""
        ahead, self._in_flight = self._in_flight, None
        if ahead is None:
            return
        with self._lock:
            self._stats["lookahead_drains"] += 1
        self._land(ahead, admitting)

    def _land(self, ahead: _TickInFlight, admitting: bool = False):
        """fetch | commit of a dispatched tick or pass; ``self._in_flight``
        is the dispatch behind it, or None. What the commit is follows from
        the kind of the iteration that dispatched: a token a row, or a
        block's values."""
        tick = self._tick
        behind = self._in_flight
        rides_on = set(behind.rows) if behind is not None else ()
        denoise = ahead.record.kind == "denoise"
        if denoise:
            tick.kind = "denoise"       # also where it only reads a pass
        values = 3 * self.slots * self.block_length if denoise \
            else self.slots
        started = time.perf_counter()
        with annotate("mlt.sched.fetch"):
            host = ahead.host if ahead.host is not None \
                else np.asarray(ahead.fetched)
        # dispatches that ran before this tick have left their counters
        self._settle_loads(before=ahead.record)
        if len(host) > values:
            self._count_experts(ahead.record, host[values:])
        now = time.perf_counter()
        if admitting:
            tick.admit_wait_s += now - started
            tick.inflight_wait_s += now - started
        else:
            tick.t_fetched = now
        if ahead.seq == self._sent_seq:
            self._quiet_since = now         # nothing went out behind it
        with annotate("mlt.sched.commit"):
            # a row that rides in the tick behind stays decode_active
            self._ledger_mark([i for i in ahead.rows if i not in rides_on],
                              "decode_stall")
            commit = self._commit_pass if denoise else self._commit_tokens
            tick.tokens_out += commit(ahead, host[:values])
            for i in ahead.rows:
                if i in rides_on and not self._slot_state[i].active:
                    behind.rows.remove(i)       # ended unseen: rode along
            if behind is not None and behind.rng is not None and not any(
                    self._slot_state[i].temperature > 0
                    for i in behind.rows):
                # the sampled rows it drew for had all ended: the key is
                # as if it had not drawn, as the next draw will find it
                self._rng, behind.rng = behind.rng, None

    def _commit_tokens(self, ahead: _TickInFlight, host) -> int:
        """A landed plain tick's token into each of its rows; a row ends on
        its end-of-sequence id, its last token or the cache's end."""
        for i in ahead.rows:
            slot = self._slot_state[i]
            token = int(host[i])
            slot.tokens.append(token)
            slot.remaining -= 1
            capacity = slot.prompt_len + len(slot.tokens) >= self.max_len
            if (slot.eos_id is not None and token == slot.eos_id) or \
                    slot.remaining <= 0 or capacity:
                self._finish(i)
        return len(ahead.rows)

    # -- block-diffusion decoding (docs/serving.md) -------------------------

    def _denoise_tick(self, active) -> int:
        """Dispatch the next pass over every live row's current block, then
        read the one before it: build | dispatch for pass p+1, fetch |
        commit for pass p, as the plain tick does (``_plain_decode_tick``).
        Rows whose block still has masked positions are denoised (the pass
        unmasks this pass's share of them, the most confident first), rows
        whose block is full are committed (the pass stores the block's keys
        and values for good, yields no token, and the next block opens).
        One dispatch for both; which row is which is decided here, by
        count: how many lanes a pass unmasks, whether it is a commit, where
        the next block starts and whether the row outlives its commit need
        none of pass p's values. *Which* lanes and which ids do, and stay
        on the device: a row of p takes its block state from p there. A row
        that a commit ends on an end-of-sequence id is learnt at commit(p),
        rode in p+1 too, and p+1's values for it are thrown away (its
        writes fell into the row's own pages). Returns the rows
        dispatched."""
        tick = self._tick
        tick.kind = "denoise"
        size = self.block_length
        ahead = self._in_flight
        riding = set(ahead.rows) if ahead is not None else ()
        rows = [i for i in active
                if i not in riding or ahead.passes[i].outlived]
        if not rows:
            self._drain_tick()
            return 0
        with annotate("mlt.sched.build"):
            chunk = np.zeros((self.slots, size), np.int32)
            masked = np.zeros((self.slots, size), bool)
            count = np.zeros((self.slots,), np.int32)
            from_prev = np.zeros((self.slots,), bool)
            # the pass's own copies, and only its rows: any other writes to
            # the scratch page
            table = np.full_like(self._page_table, -1)
            table[rows] = self._page_table[rows]
            pos = np.zeros_like(self._pos)
            passes = {}
            for i in rows:
                slot = self._slot_state[i]
                base = pos[i] = slot.block_base
                tick.ctx_tokens += base + size
                if i not in riding:
                    chunk[i] = slot.block_ids
                    masked[i] = slot.block_masked
                elif ahead.passes[i].index is None:
                    masked[i] = True        # behind a commit: a new block
                else:
                    from_prev[i] = True
                count[i], passes[i] = self._schedule_pass(i)
            tick.commit_rows = sum(row.index is None
                                   for row in passes.values())
            tick.positions = len(rows) * size
            lora_kw = self._lora_kwargs(self._slot_adapter_ids()) \
                if self._adapters is not None else {}
            self._ledger_mark(rows, "decode_active")
            args = (jnp.asarray(chunk), self._pool, jnp.asarray(table),
                    jnp.asarray(pos))
            prev_ids, prev_masked = ahead.block if ahead is not None \
                else self._no_block
            state_kw = dict(masked=jnp.asarray(masked),
                            count=jnp.asarray(count), prev_ids=prev_ids,
                            prev_masked=prev_masked,
                            from_prev=jnp.asarray(from_prev))
        tick.t_built = time.perf_counter()
        with annotate("mlt.sched.dispatch"):
            packed, self._pool, *block = self._denoise_paged(
                self.params, *args, **state_kw, **lora_kw)
            packed.copy_to_host_async()
        with self._lock:
            self._stats["commit_passes"] += tick.commit_rows
            self._stats["denoise_passes"] += len(rows) - tick.commit_rows
        self._sent(_TickInFlight(rows, None, fetched=packed, record=tick,
                                 passes=passes, block=tuple(block)), ahead)
        return len(rows)

    def _schedule_pass(self, index: int) -> tuple:
        """Advance the slot's counts by the pass being dispatched for it:
        (the lanes the pass unmasks, what the pass is to the row). Pass
        ``s`` of a block opened with ``m0`` masked positions takes ``m0 //
        steps``, one more while ``s < m0 mod steps``; a block with none
        left is committed, and where the row outlives that (more to
        generate than the block yields, room for another block) the next
        block's counts open and ``_pos`` moves on."""
        slot = self._slot_state[index]
        size, steps = self.block_length, self.denoising_steps
        base = slot.block_base
        if slot.block_left:
            s, m0 = slot.passes_in_block, slot.block_m0
            share = m0 // steps + (s < m0 % steps)
            slot.block_left -= share
            slot.passes_in_block = s + 1
            return share, _RowPass(base, s)
        yields = size - max(0, slot.prompt_len - base)
        outlived = slot.remaining > yields \
            and base + 2 * size <= self.max_len
        if outlived:
            self._schedule_block(slot, base + size, size)
            self._pos[index] = base + size
        return 0, _RowPass(base, None, outlived)

    def _commit_pass(self, ahead: _TickInFlight, host) -> int:
        """A landed pass's values (``host``: ``x0``, the confidences' bits
        and the lanes chosen, [3 * slots * S]) into each of its rows: the
        lanes the pass unmasked take its ids, its number and its
        confidences, and a row in its commit pass commits its block.
        Returns the positions unmasked."""
        x0, confidence, chosen = host.reshape(3, self.slots, -1)
        confidence = confidence.view(np.float32)
        unmasked = 0
        for i in ahead.rows:
            slot = self._slot_state[i]
            row = ahead.passes[i]
            if row.index is None:
                self._commit_block(i, row)
                continue
            for j in np.flatnonzero(chosen[i]):
                slot.block_ids[j] = int(x0[i, j])
                slot.block_masked[j] = False
                slot.block_pass[j] = row.index
                slot.block_confidence[j] = float(confidence[i, j])
                unmasked += 1
        with self._lock:
            self._stats["unmasked_positions"] += unmasked
        return unmasked

    def _schedule_block(self, slot, base: int, masked: int):
        """The counts of the block at ``base``, which the host advances
        as it dispatches: ``masked`` positions to unmask, no pass yet."""
        slot.block_base = base
        slot.block_m0 = slot.block_left = masked
        slot.passes_in_block = 0

    def _blank_block(self, slot, known=()):
        """The values of a block no pass has landed in: ``known`` ids (the
        prompt's tail, in the first block) stand unmasked, every other
        position masked."""
        size = self.block_length
        slot.block_ids = list(known) + [0] * (size - len(known))
        slot.block_masked = [False] * len(known) \
            + [True] * (size - len(known))
        slot.block_pass = [-1] * len(known) + [0] * (size - len(known))
        slot.block_confidence = [1.0] * size

    def _open_block(self, slot, base: int, known=()):
        """Open an admission's first block at ``base``: its counts and its
        values, the prompt's tail ``known`` unmasked."""
        self._schedule_block(slot, base, self.block_length - len(known))
        self._blank_block(slot, known)

    def _commit_block(self, index: int, row: _RowPass):
        """The slot's block is full and its commit pass ``row`` has stored
        it: its generated positions extend the answer in position order,
        and the values of the next block open (its counts did when the
        commit was dispatched), unless the answer is complete: by count
        (``max_new`` tokens or the cache's end, ``row.outlived``), or on an
        end-of-sequence id found here."""
        slot = self._slot_state[index]
        first = max(0, slot.prompt_len - row.base)
        new = slot.block_ids[first:]
        slot.unmask_pass.extend(slot.block_pass[first:])
        slot.unmask_confidence.extend(slot.block_confidence[first:])
        ended = slot.eos_id is not None and slot.eos_id in new
        if ended:
            new = new[:new.index(slot.eos_id) + 1]
        slot.tokens.extend(new)
        slot.remaining -= len(new)
        if ended or not row.outlived:
            self._finish(index)
        else:
            self._blank_block(slot)
