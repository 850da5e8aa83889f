"""Paged-decode attention: one token per slot straight off the KV page pool.

The paged engine (serving/paged.py) historically materialized a dense
``[slots, max_len]`` KV view per layer per decode tick (``jnp.take`` over
the page table) and ran plain masked attention on it — HBM traffic on the
order of the whole cache for every generated token. This module computes
the same attention by indexing the page pool THROUGH the page table inside
a Pallas kernel: each grid step DMAs exactly one physical page from
HBM into VMEM, so the bytes read per tick are the slot's *live* pages once
— never a gathered copy of the full view.

Layout (the pool as serving/paged.py stores it, all layers stacked):

- q:          [slots, n_heads, head_dim]   — the current decode token,
  post-RoPE (its KV must already be written into the pool; the kernel
  masks ``k_pos <= pos`` so the current position participates).
- k/v pool:   [n_layers, n_pages + 1, page_size, n_kv_heads, head_dim]
  — the LAST physical page of a layer is the scratch page; page-table
  entries < 0 are routed to it (they are masked out by ``pos`` anyway,
  the routing just keeps the DMA addresses in-bounds).
- layer:      int32 scalar, traced — which layer of the pool to attend.
- page_table: [slots, pages_per_slot] int32, -1 = unmapped.
- pos:        [slots] int32 absolute position of the current token
  (valid cache length is ``pos + 1``).

Grid ``(slots, pages_per_slot)``: for a fixed slot the kernel streams
that slot's pages in order — all kv heads of a page per step, which is
the blocking the TPU lowering accepts for this pool layout — carrying
the online-softmax running max/denominator/accumulator of every GQA
query group in VMEM scratch — the same accumulation scheme as the
verified flash_v2 kernel (ops/attention.py), so numerics match the dense
reference to float32 round-off. The layer index, the page table and the
positions ride scalar prefetch (``PrefetchScalarGridSpec``) because the
k/v BlockSpec index maps need them to translate (slot, page-slot) ->
(layer, physical page id) before the DMA.

Why the whole pool and an index, not ``pool[layer]``: an operand of a
``pallas_call`` is a buffer of its own, so a sliced layer is a copy XLA
must make before every call — a whole pool layer moved to read a few
live pages of it. Handed the pool as stored, the kernel reaches the
layer through its index maps and no program copies anything; because
``layer`` is a value and not a Python constant, all layers of a program
are one kernel (tests/test_tpu_compile.py holds both).

Beyond decode, this module carries the other two KV-heavy moments of the
serving path (docs/serving.md "Attention kernels"), both on ONE
multi-row chunk kernel (``_paged_chunk_call``: per-row page ids and
per-row ``base`` on scalar prefetch, grid (row, q_block, page), emitting
a partial softmax state ``(o, lse)`` that ``merge_softmax_states``
LSE-merges with the chunk's local causal part):

- **multi-row paged prefill** (``paged_prefix_part`` /
  ``paged_prefill_attention``): on a prefix-cache hit, a chunk of query
  tokens attends the ``base`` cached prefix tokens IN PLACE (one row),
  merged with the local causal flash over the suffix — the
  admission-time dense ``gather_prefix_pages`` copy becomes the
  CPU/reference fallback only.
- **batched speculative verify** (``paged_verify_attention``): the
  in-engine speculative-decoding verify dispatch (docs/serving.md
  "Speculative decoding") — every decode slot's (k+1)-token chunk
  attends its own prefix pages in place (one row per slot), merged with
  the chunk's closed-form causal part; ``paged_verify_reference`` is
  the gather+dense fallback.
- **int8 KV pages**: all kernels take optional per-vector f32 dequant
  scales riding the same page-table-indexed operands as the pages, so a
  ``kv_dtype="int8"`` pool (double the resident pages per HBM byte)
  runs the kernel path instead of downgrading to the reference.

Dispatch mirrors ``ops.attention.attention``: ``resolve_paged_impl``
picks the kernel on TPU, the gather+dense reference on CPU — unless
interpret mode is forced (``MLT_ATTN_INTERPRET=1``), which runs the real
kernel code path under the Pallas interpreter so tier-1 exercises it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import (
    NEG_INF,
    _fit_block,
    _flash_fwd_v2_cached_bounded,
    causal_bound,
    _on_tpu,
    _repeat_kv,
    interpret_default,
    interpret_forced,
)


def resolve_paged_impl(impl: str = "auto") -> str:
    """Resolve a serving ``attention_impl`` knob to the paged-decode path:
    ``kernel`` (Pallas, page-table indexed) or ``reference``
    (gather+dense). ``flash`` counts as an explicit kernel opt-in;
    ``dense`` as an explicit reference opt-in. ``auto`` is the kernel on
    a TPU (or under forced interpret mode), the reference elsewhere."""
    if impl in ("kernel", "flash"):
        return "kernel"
    if impl in ("reference", "dense"):
        return "reference"
    if impl != "auto":
        raise ValueError(
            f"unknown paged attention impl '{impl}' "
            "(auto | flash | kernel | reference | dense)")
    if _on_tpu() or interpret_forced():
        return "kernel"
    return "reference"


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------
#
# Blocking (what the TPU lowering accepts — tests/test_tpu_compile.py): a
# block's last two dims must be (8, 128)-aligned or span the array's, so
# every kernel takes ALL kv heads of a page per grid step (``[1,
# page_size, Hkv, D]`` of the 5-D pool, the layer dim squeezed — the pool
# keeps its layout) and loops the GQA groups in the body, reading head
# ``h`` as ``k_ref[0, :, h, :]``.

def _attend_page(q_ref, k_ref, v_ref, scale_refs, m_scr, l_scr, acc_scr,
                 *, p, limit, page_size: int, scale: float):
    """Online-softmax update of every GQA query group over the resident
    page: q_ref [1, Hkv, rows, d], k/v [1, page_size, Hkv, d];
    positions at or past ``limit`` are masked. ``scale_refs`` (int8
    pools) are the per-vector f32 dequant scales ([1, page_size, Hkv])
    riding the same page-table-indexed blocks — dequantization happens
    in-register, everything else is one code path. The scratch refs
    ([Hkv, rows, 1|d]) carry each group's running
    max/denominator/accumulator across the page-slot grid dim."""
    for h in range(k_ref.shape[2]):
        k = k_ref[0, :, h, :].astype(jnp.float32)       # [page_size, d]
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if scale_refs:
            ks_ref, vs_ref = scale_refs
            k = k * ks_ref[0, :, h:h + 1]
            v = v * vs_ref[0, :, h:h + 1]
        q = q_ref[0, h].astype(jnp.float32) * scale     # [rows, d]
        logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        k_pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(k_pos < limit, logits, NEG_INF)
        m_prev = m_scr[h]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        weight = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * alpha + jnp.sum(weight, axis=-1,
                                              keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
            weight, v, preferred_element_type=jnp.float32)
        m_scr[h] = m_new


def _reset_softmax_state(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _safe_table(page_table, k_pool):
    """Page ids with -1 (unmapped) routed to the pool's scratch page —
    masked out by position anyway, this keeps the DMA in bounds."""
    scratch_page = k_pool.shape[1] - 1
    return jnp.where(page_table >= 0, page_table,
                     scratch_page).astype(jnp.int32)


def _layer_prefetch(layer):
    """The layer index as a scalar-prefetch operand (int32[1]): a traced
    value, so every layer of a program runs one kernel."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _paged_decode_kernel(layer_ref, pt_ref, pos_ref, q_ref, k_ref, v_ref,
                         *refs, page_size: int, pages_per_slot: int,
                         scale: float, quantized: bool):
    """Grid (slot, page-slot); refs: q [1, Hkv, n_rep, d] (the slot's
    token, heads grouped per kv head), k/v [1, page_size, Hkv, d] (the
    physical page the index map resolved via ``layer_ref`` and the page
    table; only the index maps read ``layer_ref``). Scratch
    ([Hkv, n_rep, 1|d]) carries each group's online softmax across the
    page-slot grid dim.

    ``quantized`` (static) inserts two extra refs after v: the int8
    pool's dequant scales (see :func:`_attend_page`)."""
    scale_refs, (o_ref, m_scr, l_scr, acc_scr) = (
        (refs[:2], refs[2:]) if quantized else ((), refs))
    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        _reset_softmax_state(m_scr, l_scr, acc_scr)

    pos = pos_ref[s]
    # pages wholly past the current position contribute nothing — skip the
    # flops (the DMA already happened; it fetched the scratch page or a
    # masked page, both harmless)
    live = p * page_size <= pos

    @pl.when(live)
    def _compute():
        _attend_page(q_ref, k_ref, v_ref, scale_refs, m_scr, l_scr,
                     acc_scr, p=p, limit=pos + 1, page_size=page_size,
                     scale=scale)

    @pl.when(p == pages_per_slot - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def _paged_decode_call(q, k_pool, v_pool, layer, page_table, pos,
                       page_size: int, k_scale=None, v_scale=None,
                       interpret=None):
    """q [slots, H, D] x layer ``layer`` (traced int32 scalar) of the
    pool [L, P+1, page_size, Hkv, D] -> [slots, H, D]. ``page_table``
    may contain -1 (routed to the scratch page). ``k_scale``/``v_scale``
    ([L, P+1, page_size, Hkv] f32) select the int8 kernel: pages are
    dequantized per vector inside the kernel."""
    if interpret is None:
        interpret = interpret_default()
    slots, h, d = q.shape
    hkv = k_pool.shape[3]
    n_rep = h // hkv
    pages_per_slot = page_table.shape[1]
    scale = d ** -0.5
    safe_table = _safe_table(page_table, k_pool)
    pos = pos.astype(jnp.int32)
    quantized = k_scale is not None

    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size,
        pages_per_slot=pages_per_slot, scale=scale, quantized=quantized)

    def q_map(s, p, ly, pt, ps):
        return (s, 0, 0, 0)

    def kv_map(s, p, ly, pt, ps):
        return (ly[0], pt[s, p], 0, 0, 0)

    def sc_map(s, p, ly, pt, ps):
        return (ly[0], pt[s, p], 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, n_rep, d), q_map),
        pl.BlockSpec((None, 1, page_size, hkv, d), kv_map),
        pl.BlockSpec((None, 1, page_size, hkv, d), kv_map),
    ]
    # heads h*n_rep..(h+1)*n_rep are kv head h's GQA group (matches
    # _repeat_kv order), so grouping q per kv head is a free reshape
    operands = [_layer_prefetch(layer), safe_table, pos,
                q.reshape(slots, hkv, n_rep, d), k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((None, 1, page_size, hkv), sc_map),
                     pl.BlockSpec((None, 1, page_size, hkv), sc_map)]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots, pages_per_slot),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, n_rep, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, n_rep, 1), jnp.float32),   # running max
            pltpu.VMEM((hkv, n_rep, 1), jnp.float32),   # running denom
            pltpu.VMEM((hkv, n_rep, d), jnp.float32),   # accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, hkv, n_rep, d), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(*operands)
    return out.reshape(slots, h, d)


# ---------------------------------------------------------------------------
# multi-row chunks over pool pages in place: prefix-hit prefill (one
# admission's prompt chunk) and speculative verify (a chunk per slot)
# ---------------------------------------------------------------------------

def _paged_chunk_kernel(layer_ref, ids_ref, base_ref, q_ref, k_ref, v_ref,
                        *refs, page_size: int, pages_per_slot: int,
                        scale: float, quantized: bool):
    """Grid (row, q_block, page-slot); refs: q [1, Hkv, block_rows, d]
    (row ``r``'s chunk, rows = token x n_rep grouped per kv head), k/v
    [1, page_size, Hkv, d] — the physical page the index map resolved
    through ``layer_ref`` and row ``r``'s page ids. Every prefix
    position (0..base[r]-1) precedes every query row, so no causal mask
    is needed; pages at or past ``base[r]`` (and -1 entries, routed to
    the scratch page) are masked out wholesale. Scratch carries each
    group's online softmax across the page-slot grid dim; the finalize
    step emits (o, lse) so the caller can LSE-merge with the chunk's
    local causal part.

    ``quantized`` (static) inserts two extra refs after v: the int8
    pool's dequant scales (see :func:`_attend_page`)."""
    scale_refs, (o_ref, lse_ref, m_scr, l_scr, acc_scr) = (
        (refs[:2], refs[2:]) if quantized else ((), refs))
    r = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        _reset_softmax_state(m_scr, l_scr, acc_scr)

    base = base_ref[r]
    live = p * page_size < base

    @pl.when(live)
    def _compute():
        _attend_page(q_ref, k_ref, v_ref, scale_refs, m_scr, l_scr,
                     acc_scr, p=p, limit=base, page_size=page_size,
                     scale=scale)

    @pl.when(p == pages_per_slot - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = acc_scr[:] / l
        lse_ref[0] = jnp.broadcast_to(m_scr[:] + jnp.log(l),
                                      lse_ref.shape[1:])


@functools.partial(jax.jit,
                   static_argnames=("page_size", "interpret", "name"))
def _paged_chunk_call(q, k_pool, v_pool, layer, page_table, base,
                      page_size: int, k_scale=None, v_scale=None,
                      interpret=None, name: str = "paged_verify"):
    """q [R, S, H, D] (a chunk of S query tokens per row) attends each
    row's prefix tokens 0..base[r]-1 IN PLACE in layer ``layer`` (traced
    int32 scalar) of the pool [L, P+1, page_size, Hkv, D] through
    ``page_table`` ([R, pages_per_slot] int32, -1 past the prefix →
    scratch page) — never gathered. Returns (o [R, S, H, D] f32, lse
    [R, H, S] f32) partial softmax states in the flash lse layout, ready
    for :func:`merge_softmax_states` with the chunk's local causal part.
    ``name`` labels the kernel in a device trace: the prefix-hit prefill
    (R = 1) and the speculative verify (R = slots) are one kernel."""
    if interpret is None:
        interpret = interpret_default()
    r_, s, h, d = q.shape
    hkv = k_pool.shape[3]
    n_rep = h // hkv
    pages_per_slot = page_table.shape[1]
    scale = d ** -0.5
    safe_table = _safe_table(page_table, k_pool)
    base = base.astype(jnp.int32)
    quantized = k_scale is not None

    # rows grouped per kv head (head h*n_rep+r is kv head h's GQA group,
    # matching _repeat_kv order): [R, S, H, D] -> [R, Hkv, S*n_rep, D]
    rows = s * n_rep
    qg = q.reshape(r_, s, hkv, n_rep, d).transpose(
        0, 2, 1, 3, 4).reshape(r_, hkv, rows, d)
    block_rows = _fit_block(rows, 256)
    pad_rows = (-rows) % block_rows
    if pad_rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, pad_rows), (0, 0)))
    padded_rows = rows + pad_rows

    kernel = functools.partial(
        _paged_chunk_kernel, page_size=page_size,
        pages_per_slot=pages_per_slot, scale=scale, quantized=quantized)

    def q_map(r, qb, p, ly, ids, b):
        return (r, 0, qb, 0)

    def kv_map(r, qb, p, ly, ids, b):
        return (ly[0], ids[r, p], 0, 0, 0)

    def sc_map(r, qb, p, ly, ids, b):
        return (ly[0], ids[r, p], 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, block_rows, d), q_map),
        pl.BlockSpec((None, 1, page_size, hkv, d), kv_map),
        pl.BlockSpec((None, 1, page_size, hkv, d), kv_map),
    ]
    operands = [_layer_prefetch(layer), safe_table, base, qg, k_pool,
                v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((None, 1, page_size, hkv), sc_map),
                     pl.BlockSpec((None, 1, page_size, hkv), sc_map)]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(r_, padded_rows // block_rows, pages_per_slot),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, hkv, block_rows, d), q_map),
            pl.BlockSpec((1, hkv, block_rows, 8), q_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, block_rows, 1), jnp.float32),  # running max
            pltpu.VMEM((hkv, block_rows, 1), jnp.float32),  # running denom
            pltpu.VMEM((hkv, block_rows, d), jnp.float32),  # accumulator
        ],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r_, hkv, padded_rows, d), jnp.float32),
            jax.ShapeDtypeStruct((r_, hkv, padded_rows, 8), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(*operands)
    o = o[:, :, :rows].reshape(r_, hkv, s, n_rep, d).transpose(
        0, 2, 1, 3, 4).reshape(r_, s, h, d)
    lse = lse[:, :, :rows, 0].reshape(r_, hkv, s, n_rep).transpose(
        0, 1, 3, 2).reshape(r_, h, s)
    return o, lse


def merge_softmax_states(o_a, lse_a, o_b, lse_b):
    """LSE-merge two partial attention states over disjoint kv sets:
    ``o_*`` [B, S, H, D] (any float dtype), ``lse_*`` [B, H, S] f32
    (the flash kernels' lse layout). Returns the combined f32 output —
    exactly softmax over the union, up to accumulation-order round-off
    (the documented cold-vs-hit tolerance contract, docs/serving.md
    "Attention kernels")."""
    la = lse_a.transpose(0, 2, 1)[..., None]       # [B, S, H, 1]
    lb = lse_b.transpose(0, 2, 1)[..., None]
    m = jnp.maximum(la, lb)
    wa = jnp.exp(la - m)
    wb = jnp.exp(lb - m)
    return (o_a.astype(jnp.float32) * wa
            + o_b.astype(jnp.float32) * wb) / (wa + wb)


def paged_prefix_part(q, k_pool, v_pool, layer, page_ids, base, *,
                      page_size: int, k_scale=None, v_scale=None,
                      interpret=None):
    """The prefix-hit prefill form of :func:`_paged_chunk_call` — one
    row: q [1, S, H, D] (one admission's prompt chunk) over the ``base``
    prefix tokens stored in pages ``page_ids`` ([pages_per_slot] int32,
    -1 past the prefix) of pool layer ``layer`` -> (o [1, S, H, D] f32,
    lse [1, H, S] f32) in the flash lse layout, ready for
    :func:`merge_softmax_states`."""
    return _paged_chunk_call(
        q, k_pool, v_pool, layer, page_ids[None],
        jnp.asarray(base, jnp.int32).reshape(1), page_size,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret,
        name="paged_prefill")


def paged_prefill_attention(q, k_cache, v_cache, q_start, k_pool,
                            v_pool, layer, page_ids, base, *,
                            page_size: int, k_scale=None, v_scale=None,
                            interpret=None):
    """Merged suffix-prefill attention on a prefix-cache hit: q
    [1, S, H, D] rows at absolute positions ``q_start + i``; local cache
    k_cache/v_cache [1, M, H, D] (kv repeated to q heads, rows valid
    from ``base``); prefix tokens 0..base-1 live in pages of pool layer
    ``layer`` and are attended IN PLACE through ``page_ids``. Returns
    the merged [1, S, H, D] f32 output — the hit-path analog of
    flash_attention_cached over a densely gathered cache, without the
    gather."""
    o_loc, lse_loc = _flash_fwd_v2_cached_bounded(
        q, k_cache, v_cache, q_start, base, interpret=interpret)
    o_pre, lse_pre = paged_prefix_part(
        q, k_pool, v_pool, layer, page_ids, base, page_size=page_size,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret)
    return merge_softmax_states(o_pre, lse_pre, o_loc, lse_loc)


def chunk_causal_part(q, k, v, block_length: int = 1):
    """Closed-form partial softmax of a chunk over ITSELF under the block
    mask: q [B, S, H, D], k/v [B, S, Hkv, D] (the chunk's own
    just-computed KV — for int8 pools the caller passes the
    quantize->dequantize round-trip so the chunk attends exactly what
    the pool stores). Lane ``i`` sees lane ``j`` iff ``j <=
    causal_bound(i, block_length)``: causal at 1 (the speculative verify
    chunk), full for a chunk that is one block (a denoising pass; the
    chunk starts on a block boundary, so lanes stand for positions).
    S is tiny (k draft tokens + 1, or a block), so a dense S x S pass
    beats a flash instance. Returns (o [B, S, H, D] f32, lse [B, H, S]
    f32) for :func:`merge_softmax_states` with the paged prefix part."""
    b, s, h, d = q.shape
    n_rep = h // k.shape[2]
    k = _repeat_kv(k.astype(jnp.float32), n_rep)
    v = _repeat_kv(v.astype(jnp.float32), n_rep)
    scale = d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k,
                        preferred_element_type=jnp.float32) * scale
    i = jnp.arange(s)
    causal = i[None, :] <= causal_bound(i, block_length)[:, None]  # [q, kv]
    logits = jnp.where(causal[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                       # [B, H, S]
    w = jnp.exp(logits - m[..., None])
    l = jnp.maximum(jnp.sum(w, axis=-1), 1e-30)
    o = jnp.einsum("bhqk,bkhd->bqhd", w / l[..., None], v)
    return o, m + jnp.log(l)


def paged_verify_reference(q, chunk_k, chunk_v, k_pool, v_pool, layer,
                           page_table, base, page_size: int,
                           k_scale=None, v_scale=None,
                           block_length: int = 1):
    """Dense-view verify reference: gather every slot's pages into
    [slots, max_len] (the materialization the verify kernel avoids),
    splice the chunk KV at positions ``base[r] + i``, and run one masked
    softmax with the per-position bound ``k_pos <= causal_bound(base[r] +
    i)`` (the position itself, or its block's end under ``block_length``).
    Chunk lanes past the view tail drop (see below); lanes past a row's
    accepted length are computed-and-discarded garbage, exactly like the
    kernel path."""
    r_, s, h, d = q.shape
    n_rep = h // k_pool.shape[3]
    kd = _gather_dense(k_pool, k_scale, layer, page_table)
    vd = _gather_dense(v_pool, v_scale, layer, page_table)
    m = kd.shape[1]
    positions = base[:, None] + jnp.arange(s)[None, :]   # [B, S]
    rows = jnp.arange(r_)[:, None]
    # mode="drop": a chunk lane past the view tail (row at the very end
    # of its budget speculating fewer than S-1 tokens) must vanish, not
    # clamp onto the row's real final entry
    kd = kd.at[rows, positions].set(chunk_k.astype(jnp.float32),
                                    mode="drop")
    vd = vd.at[rows, positions].set(chunk_v.astype(jnp.float32),
                                    mode="drop")
    kd = _repeat_kv(kd, n_rep)
    vd = _repeat_kv(vd, n_rep)
    scale = d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kd,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(m)[None, None, :]
    mask = k_pos <= causal_bound(positions, block_length)[:, :, None]
    logits = jnp.where(mask[:, None], logits, NEG_INF)          # [B, S, M]
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, vd)


def paged_verify_attention(q, chunk_k, chunk_v, k_pool, v_pool, layer,
                           page_table, base, *, page_size: int,
                           impl: str = "auto", k_scale=None,
                           v_scale=None, interpret=None,
                           block_length: int = 1):
    """Speculative multi-token verify attention over the page pool: q
    [slots, S, H, D] are each row's draft positions ``base[r]..base[r] +
    S - 1`` (S = k + 1: the committed last token plus k draft tokens);
    their KV (``chunk_k``/``chunk_v`` [slots, S, Hkv, D]) has already
    been written into layer ``layer`` of the pool. The kernel path
    attends the prefix pages in place — the verify chunk is literally
    the prefill kernel's q-chunk form, batched per slot — and LSE-merges
    the chunk's local causal part; no dense gather, int8 pools included.
    Under ``block_length`` > 1 the chunk is one block of a block-diffusion
    model starting at ``base[r]`` (a multiple of the length): the prefix
    part is unchanged (it reads positions ``< base``) and the local part
    is full (:func:`chunk_causal_part`).
    Returns the merged [slots, S, H, D] f32 output."""
    impl = resolve_paged_impl(impl)
    if impl == "reference":
        return paged_verify_reference(
            q, chunk_k, chunk_v, k_pool, v_pool, layer, page_table,
            base, page_size, k_scale=k_scale, v_scale=v_scale,
            block_length=block_length)
    o_pre, lse_pre = _paged_chunk_call(
        q, k_pool, v_pool, layer, page_table, base, page_size,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret)
    o_loc, lse_loc = chunk_causal_part(q, chunk_k, chunk_v, block_length)
    return merge_softmax_states(o_pre, lse_pre, o_loc, lse_loc)


# ---------------------------------------------------------------------------
# gather+dense reference (the pre-kernel engine math)
# ---------------------------------------------------------------------------

def _gather_dense(pool, pool_scale, layer, page_table):
    """One layer of the pool gathered through ``page_table`` into the
    dense f32 view [slots, max_len, Hkv, D] (the materialization the
    kernels exist to avoid); -1 entries read page 0 and are masked by
    position downstream. int8 pools dequantize by ``pool_scale`` ([L,
    P+1, page_size, Hkv] f32) after the gather."""
    safe = jnp.maximum(page_table, 0)
    dense = jnp.take(pool[layer], safe, axis=0)  # [slots, pps, ps, hkv, d]
    s_, p_, ps_, hh, dd = dense.shape
    dense = dense.reshape(s_, p_ * ps_, hh, dd).astype(jnp.float32)
    if pool_scale is not None:
        sc = jnp.take(pool_scale[layer], safe, axis=0)
        dense = dense * sc.reshape(s_, p_ * ps_, hh, 1)
    return dense


def paged_decode_reference(q, k_pool, v_pool, layer, page_table, pos,
                           page_size: int, k_scale=None, v_scale=None):
    """Dense-view reference: gather every slot's pages of pool layer
    ``layer`` into [slots, max_len] and run masked attention. Used for
    parity tests and as the CPU path."""
    slots, h, d = q.shape
    n_rep = h // k_pool.shape[3]
    kd = _gather_dense(k_pool, k_scale, layer, page_table)
    vd = _gather_dense(v_pool, v_scale, layer, page_table)
    kd = _repeat_kv(kd, n_rep)
    vd = _repeat_kv(vd, n_rep)
    scale = d ** -0.5
    logits = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), kd,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(kd.shape[1])[None, None, :]
    logits = jnp.where(k_pos <= pos[:, None, None], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", weights, vd).astype(q.dtype)


def paged_attention(q, k_pool, v_pool, layer, page_table, pos, *,
                    page_size: int, impl: str = "auto",
                    k_scale=None, v_scale=None, interpret=None):
    """Dispatching paged-decode attention over layer ``layer`` of the
    pool (see module docstring). ``k_scale``/``v_scale`` select the int8
    path in both impls."""
    impl = resolve_paged_impl(impl)
    if impl == "reference":
        return paged_decode_reference(q, k_pool, v_pool, layer,
                                      page_table, pos, page_size,
                                      k_scale=k_scale, v_scale=v_scale)
    return _paged_decode_call(q, k_pool, v_pool, layer, page_table, pos,
                              page_size, k_scale=k_scale,
                              v_scale=v_scale, interpret=interpret)
