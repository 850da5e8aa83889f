"""Latent attention (MLA) over what a token leaves behind: one normalised
latent ``c_kv`` [kv_lora_rank] and one rotated key ``k_r`` [rope_dim] a
token a layer, shared by every head (models/xing4.py; docs/serving.md
"Latent attention and the latent page pool").

Two forms of the same numbers:

- **expanded** (prefill): keys ``[k_nope; k_r]`` and values a head are
  expanded from the latents (``W_ukv``) and attended by ``mla_flash``, a
  flash kernel whose key width (nope + rope) differs from its value width;
  :func:`expanded_cached_attention` runs a prompt chunk against the dense
  latent rows of its admission, block by block: the rows are expanded a
  block at a time, each block's partial softmax merged into the running
  one, so no more than a block of expanded keys exists at once and rows
  past the chunk are never touched.
- **absorbed** (decode): the query folded through ``W_uk`` attends the
  latent rows themselves, read through the page table by
  ``mla_paged_decode`` (one "kv head" of width kv_lora_rank + rope_dim
  for all the query heads, stored whole as one row; the values are the
  latent part of the same rows), and the result is unfolded through
  ``W_uv`` by the caller.
  :func:`absorbed_attention` over :func:`gather_latents` is the
  gather-and-dense form of it.

Both kernels take the softmax scale from the caller (YaRN carries a factor
in it) and appear under their own names in a device trace.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, _fit_block, interpret_default
from .paged_attention import (
    _layer_prefetch,
    _reset_softmax_state,
    _safe_table,
    merge_softmax_states,
)


# ---------------------------------------------------------------------------
# expanded form: a flash kernel with unequal key and value widths
# ---------------------------------------------------------------------------

def _mla_flash_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr,
                      l_scr, acc_scr, *, num_kb: int, scale: float):
    """Grid (head, q_block, k_block). q rows sit at absolute positions
    ``off[0] + i``, kv rows at ``off[1] + j``; causal. bfloat16 operands on
    the MXU, float32 scores, softmax state and accumulator."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        _reset_softmax_state(m_scr, l_scr, acc_scr)

    q_start = off_ref[0] + qi * block_q
    k_start = off_ref[1] + kb * block_k

    @pl.when(k_start <= q_start + block_q - 1)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = acc_scr[:] / l
        lse_ref[0] = jnp.broadcast_to(m_scr[:] + jnp.log(l), (block_q, 8))


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k",
                                             "interpret"))
def mla_flash(q, k, v, q_offset, k_offset, *, scale: float,
              block_q: int = 512, block_k: int = 512, interpret=None):
    """Causal flash over unequal widths: q [S, H, Dk] at positions
    ``q_offset + i``, k [T, H, Dk] and v [T, H, Dv] at ``k_offset + j``.
    Returns (o [S, H, Dv] float32, lse [H, S] float32), a partial softmax
    state for :func:`merge_softmax_states`. A q row that sees no row of k
    comes back with an ``lse`` far below any real one, so a merge gives it
    no weight."""
    if interpret is None:
        interpret = interpret_default()
    sq, h, dk = q.shape
    sk, dv = k.shape[0], v.shape[-1]
    block_q = _fit_block(sq, block_q)
    block_k = _fit_block(sk, block_k)
    if sq % block_q or sk % block_k:
        raise ValueError(f"mla_flash: {sq} x {sk} rows do not divide into "
                         f"blocks of {block_q} x {block_k}")
    num_kb = sk // block_k
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32)])

    def q_map(head, i, j, off):
        return (head, i, 0)

    def k_map(head, i, j, off):
        return (head, j, 0)

    o, lse = pl.pallas_call(
        functools.partial(_mla_flash_kernel, num_kb=num_kb, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, sq // block_q, num_kb),
            in_specs=[pl.BlockSpec((1, block_q, dk), q_map),
                      pl.BlockSpec((1, block_k, dk), k_map),
                      pl.BlockSpec((1, block_k, dv), k_map)],
            out_specs=[pl.BlockSpec((1, block_q, dv), q_map),
                       pl.BlockSpec((1, block_q, 8), q_map)],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, dv), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((h, sq, dv), jnp.float32),
                   jax.ShapeDtypeStruct((h, sq, 8), jnp.float32)],
        interpret=interpret,
        name="mla_flash",
    )(offsets, q.transpose(1, 0, 2), k.transpose(1, 0, 2),
      v.transpose(1, 0, 2))
    return o.transpose(1, 0, 2), lse[:, :, 0]


def _dense_part(q, k, v, q_offset, k_offset, scale: float):
    """:func:`mla_flash` in plain products, float32 (the CPU path)."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    q_pos = q_offset + jnp.arange(q.shape[0])[:, None]
    k_pos = k_offset + jnp.arange(k.shape[0])[None, :]
    s = jnp.where((q_pos >= k_pos)[None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    w = jnp.exp(s - m[..., None])
    l = jnp.maximum(jnp.sum(w, axis=-1), 1e-30)
    o = jnp.einsum("hqk,khd->qhd", w / l[..., None], v)
    return o, m + jnp.log(l)


def cache_block(chunk: int, max_len: int, limit: int = 1024) -> int:
    """Rows of the cache that :func:`expanded_cached_attention` expands at
    a time: the chunk's own length where that divides the cache (a chunk
    that starts on a multiple of it is then the last block), cut to
    ``limit``."""
    block = math.gcd(chunk, max_len)
    while block > limit and block % 2 == 0:
        block //= 2
    return block


def expanded_cached_attention(q, cache, start, expand, *, v_dim: int,
                              scale: float, impl: str = "flash"):
    """A prompt chunk's attention in the expanded form: q [S, H, Dk] at
    positions ``start + i`` against the latent rows of its admission,
    ``cache`` [M, C + R] (latent, then rotated key; the chunk's own rows
    already written at ``start``). ``expand(rows) -> (k [T, H, Dk], v [T,
    H, Dv])`` is the layer's expansion. Blocks of the cache up to the
    chunk's end are expanded and attended one after another (``impl``
    ``flash``: the ``mla_flash`` kernel; ``dense``: plain products) and
    their partial softmax states merged; how many is a value, so one
    program serves every start. Returns [S, H, Dv] float32."""
    sq, h, _ = q.shape
    max_len = cache.shape[0]
    block = cache_block(sq, max_len)
    part = functools.partial(mla_flash, scale=scale) if impl == "flash" \
        else functools.partial(_dense_part, scale=scale)

    def body(j, state):
        o_acc, lse_acc = state
        at = j * block
        k, v = expand(
            jax.lax.dynamic_slice_in_dim(cache, at, block, axis=0))
        o, lse = part(q, k, v, start, at)
        return (merge_softmax_states(o_acc[None], lse_acc[None], o[None],
                                     lse[None])[0],
                jnp.logaddexp(lse_acc, lse))

    blocks = (start + sq + block - 1) // block
    o, _ = jax.lax.fori_loop(
        0, blocks, body,
        (jnp.zeros((sq, h, v_dim), jnp.float32),
         jnp.full((h, sq), -jnp.inf, jnp.float32)))
    return o


# ---------------------------------------------------------------------------
# absorbed form: one token a slot over the latent page pool
# ---------------------------------------------------------------------------

def _mla_decode_kernel(layer_ref, pt_ref, pos_ref, q_ref, kv_ref, o_ref,
                       m_scr, l_scr, acc_scr, *, page_size: int,
                       pages_per_slot: int, rank: int, scale: float):
    """Grid (slot, page-slot). q [1, H, C + R]: the slot's absorbed query;
    kv [1, page_size, C + R]: the physical page the index map resolved
    through ``layer_ref`` and the page table (a page-slot past the slot's
    position names the page before it again, so nothing new is fetched).
    Every head attends the same rows: scores over the whole row (the
    latent part and the rope part as two products), values the latent part
    (the first ``rank`` entries) of the same rows."""
    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        _reset_softmax_state(m_scr, l_scr, acc_scr)

    pos = pos_ref[s]

    @pl.when(p * page_size <= pos)
    def _compute():
        # operands as stored (bfloat16 products are exact in the float32
        # they accumulate in); the softmax's weights rounded to the
        # values' dtype for the second product, as a flash kernel does
        q = q_ref[0]                                     # [H, C + R]
        kv = kv_ref[0]                                   # [page_size, C + R]
        c = kv[:, :rank]
        contract = (((1,), (1,)), ((), ()))
        logits = (jax.lax.dot_general(
            q[:, :rank], c, contract, preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                q[:, rank:], kv[:, rank:], contract,
                preferred_element_type=jnp.float32)) * scale
        k_pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(k_pos <= pos, logits, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        weight = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(weight, axis=-1,
                                              keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            weight.astype(c.dtype), c, preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(p == pages_per_slot - 1)
    def _finalize():
        o_ref[0] = acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)


@functools.partial(jax.jit, static_argnames=("page_size", "rank", "scale",
                                             "interpret"))
def mla_paged_decode(q, pool, layer, page_table, pos, *, page_size: int,
                     rank: int, scale: float, interpret=None):
    """q [slots, H, C + R] (the absorbed query: ``q_nope W_uk^T``, then
    ``q_rope``) x layer ``layer`` (a traced int32 scalar) of the latent pool
    [L, P+1, page_size, C + R] -> the softmax's weighted sum of latents
    [slots, H, C] float32 (C = ``rank``) over each slot's positions ``<=
    pos``. ``page_table`` may hold -1 (routed to the scratch page and
    masked by position)."""
    if interpret is None:
        interpret = interpret_default()
    slots, h, width = q.shape
    pages_per_slot = page_table.shape[1]

    def q_map(s, p, ly, pt, ps):
        return (s, 0, 0)

    def page_map(s, p, ly, pt, ps):
        # a page-slot past the position repeats the last live page: the
        # block index does not change, so no page is fetched for it
        return (ly[0], pt[s, jnp.minimum(p, ps[s] // page_size)], 0, 0)

    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, page_size=page_size,
                          pages_per_slot=pages_per_slot, rank=rank,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, pages_per_slot),
            in_specs=[
                pl.BlockSpec((1, h, width), q_map),
                pl.BlockSpec((None, 1, page_size, width), page_map),
            ],
            out_specs=pl.BlockSpec((1, h, rank), q_map),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((slots, h, rank), jnp.float32),
        interpret=interpret,
        name="mla_paged_decode",
    )(_layer_prefetch(layer), _safe_table(page_table, pool),
      jnp.maximum(pos.astype(jnp.int32), 0), q, pool)


def absorbed_attention(q, rows, visible, *, rank: int, scale: float):
    """The absorbed form in plain products: q [B, S, H, C + R] (the
    absorbed query) over cache rows [B, M, C + R]; ``visible`` [B, S, M]
    bool. Returns the weighted sum of latents [B, S, H, C] float32."""
    q, rows = q.astype(jnp.float32), rows.astype(jnp.float32)
    logits = jnp.einsum("bqhw,bkw->bhqk", q, rows) * scale
    logits = jnp.where(visible[:, None], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkc->bqhc", weights, rows[..., :rank])


def gather_latents(pool, layer, page_table):
    """One layer of the latent pool [L, P+1, page_size, W] gathered through
    ``page_table`` into the dense view [slots, max_len, W] (-1 entries read
    page 0 and are masked by position downstream)."""
    dense = jnp.take(pool[layer], jnp.maximum(page_table, 0), axis=0)
    slots, pages, size, width = dense.shape
    return dense.reshape(slots, pages * size, width)
