"""Attention ops: reference, fused pallas flash kernel, and dispatch.

The MXU wants large fused matmuls; the HBM wants O(S) memory — flash-style
blockwise softmax delivers both. Three implementations:

- ``attention_reference``: pure jnp (einsum), GQA, causal — differentiable
  everywhere (CPU mesh tests, small shapes, fallback).
- ``flash_attention_mlt``: our pallas TPU kernel (forward) with a custom-vjp
  blockwise backward (lax.scan recompute, O(S·D) residual memory).
- ``attention``: dispatcher — on TPU training paths prefers the jax pallas
  library kernels (which include tuned fwd+bwd), otherwise reference.

No reference-repo analog: the reference has no attention code at all
(SURVEY.md §5.7) — this capability is TPU-native new work.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType, PartitionSpec

NEG_INF = -2.0**30


def interpret_forced() -> bool:
    """``MLT_ATTN_INTERPRET=1`` makes every ``auto`` dispatcher pick the
    Pallas kernels even off-TPU (interpret mode) — how tier-1 exercises
    the real kernel code paths on the CPU mesh."""
    return os.environ.get("MLT_ATTN_INTERPRET", "").strip().lower() in (
        "1", "true", "yes", "on")


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def interpret_default() -> bool:
    """What ``interpret=None`` means on a Pallas call: the interpreter
    when forced (``MLT_ATTN_INTERPRET``) or when the backend JAX reports
    is not a TPU (the only way a TPU kernel runs there), the compiled
    kernel on a TPU. A device query that fails raises — it never
    selects the interpreter."""
    return interpret_forced() or not _on_tpu()


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """GQA: repeat kv heads to match q heads. [B, S, Hkv, D] -> [B, S, Hkv*n, D]."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        positions_q: jax.Array | None = None,
                        positions_k: jax.Array | None = None,
                        softmax_scale: float | None = None) -> jax.Array:
    """[B, Sq, Hq, D] x [B, Sk, Hkv, D] -> [B, Sq, Hq, D]; f32 softmax."""
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = softmax_scale or (q.shape[-1] ** -0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        if positions_q is None:
            positions_q = jnp.arange(q.shape[1])
        if positions_k is None:
            positions_k = jnp.arange(k.shape[1])
        mask = positions_q[:, None] >= positions_k[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


# ---------------------------------------------------------------------------
# our pallas flash kernel (forward), causal, MHA/GQA via pre-repeated kv
# ---------------------------------------------------------------------------

def _fit_block(n: int, preferred: int) -> int:
    """Block size for a sequence of length ``n``: ``preferred`` for long
    sequences (a sub-block tail just pads — big MXU blocks over the
    <1-block padding, gain not measured; see ``_tuned_block_sizes``);
    below ``preferred``, the largest of (256, 128) that divides n, else
    the length itself — a short-prompt prefill no longer rounds up to
    the 512 block minimum."""
    if n >= preferred:
        return preferred
    for c in (256, 128):
        if c < preferred and n >= c and n % c == 0:
            return c
    return n


def causal_bound(q_pos, block_length: int = 1):
    """The last position that ``q_pos`` sees under the block mask: the end
    of its block of ``block_length`` positions, blocks aligned to multiples
    of the length from 0 (``k_pos <= (q_pos // B) * B + B - 1``). At 1 it is
    ``q_pos`` itself, the causal mask, and nothing is computed."""
    if block_length == 1:
        return q_pos
    return (q_pos // block_length) * block_length + (block_length - 1)


def _flash_v2_body(q_off, k_lo, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *,
                   num_kb: int, kv_len: int, scale: float, causal: bool,
                   block_length: int = 1):
    """Grid-pipelined flash forward body: grid (bh, q_blocks, k_blocks).

    Each program sees one (q_block, k_block) tile, not the full KV
    resident in VMEM — pallas double-buffers the HBM→VMEM streams
    across the innermost grid dim, so sequence length is bounded by HBM,
    not VMEM. Running max/denominator/accumulator live in scratch that
    persists across the k grid steps of a fixed (bh, qi).

    ``q_off`` shifts every q position by an absolute offset: 0 (a static
    python int — the training/self-attention form) or a traced scalar
    (the cached-prefill form, where q rows sit at ``start + i`` against a
    KV cache whose rows start at position 0).

    ``k_lo`` masks kv positions BELOW a lower bound: 0 (static — the
    plain forms) or a traced scalar (the paged-prefill-merge form, where
    cache rows < k_lo belong to shared prefix pages attended separately
    by the paged prefill kernel and LSE-merged afterwards —
    ops/paged_attention.py).

    ``block_length`` (static) widens the causal bound of a q position to
    the end of its block (:func:`causal_bound`); 1 is the causal mask.
    """
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    bounded = not (isinstance(k_lo, int) and k_lo == 0)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kb * block_k
    # causal: whole tile masked out when every k is beyond every q
    # (python bool when q_off is the static 0, a traced predicate when it
    # is the dynamic cached-prefill offset — pl.when takes both)
    live = (not causal) or (k_start <= causal_bound(
        q_off + q_start + block_q - 1, block_length))
    if bounded:
        # tiles wholly below the lower bound contribute nothing
        live = live & (k_start + block_k - 1 >= k_lo)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = q_off + q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(causal_bound(q_pos, block_length) >= k_pos, s,
                          NEG_INF)
        if bounded:
            s = jnp.where(k_pos >= k_lo, s, NEG_INF)
        s = jnp.where(k_pos < kv_len, s, NEG_INF)
        m_prev = m_scr[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_scr[:] + jnp.log(l), (block_q, 8))


def _flash_fwd_kernel_v2(q_ref, k_ref, v_ref, o_ref, lse_ref,
                         m_scr, l_scr, acc_scr, **kw):
    """Self-attention form: q positions aligned with kv position 0."""
    _flash_v2_body(0, 0, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, **kw)


def _flash_fwd_kernel_v2_cached(q_off_ref, q_ref, k_ref, v_ref, o_ref,
                                lse_ref, m_scr, l_scr, acc_scr, **kw):
    """Cached-prefill form: q rows live at absolute positions
    ``q_off + i`` against a KV cache indexed from 0 (serving engines'
    chunked/suffix prefill — ops/attention.flash_attention_cached)."""
    _flash_v2_body(q_off_ref[0], 0, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, **kw)


def _flash_fwd_kernel_v2_bounded(q_off_ref, k_lo_ref, q_ref, k_ref, v_ref,
                                 o_ref, lse_ref, m_scr, l_scr, acc_scr,
                                 **kw):
    """Bounded cached form: like the cached form, but kv rows below
    ``k_lo`` are masked out — they hold zeros where a shared prefix
    lives in pool pages instead, attended by the paged prefill kernel
    and LSE-merged with this kernel's partial state
    (ops/paged_attention.paged_prefill_attention)."""
    _flash_v2_body(q_off_ref[0], k_lo_ref[0], q_ref, k_ref, v_ref, o_ref,
                   lse_ref, m_scr, l_scr, acc_scr, **kw)


def _flash_v2_call(q, k, v, causal, block_q, block_k, interpret, q_offset,
                   k_lo=None, block_length: int = 1):
    """Shared v2 plumbing (block fit, padding, fold batch*heads, grid,
    scratch) for the self-attention and cached-prefill forms — one body,
    so the two can never diverge (the cold-vs-hit parity contract rides
    on identical block/padding choices). ``q_offset=None`` selects
    the static-zero kernel; otherwise the offset rides a (1,) SMEM
    operand. ``k_lo`` (requires ``q_offset``) additionally masks kv
    rows below a traced lower bound — the paged-prefill-merge form."""
    if interpret is None:
        interpret = interpret_default()
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(sq, block_q)
    block_k = _fit_block(sk, block_k)
    orig_sq, orig_sk = sq, sk
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        sq += pad_q
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        sk += pad_k
    scale = d ** -0.5
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    num_kb = sk // block_k
    grid = (b * h, sq // block_q, num_kb)
    static = dict(num_kb=num_kb, kv_len=orig_sk, scale=scale, causal=causal,
                  block_length=block_length)
    if q_offset is None:
        kernel = functools.partial(_flash_fwd_kernel_v2, **static)
        off_specs, off_args = [], ()
    elif k_lo is None:
        kernel = functools.partial(_flash_fwd_kernel_v2_cached, **static)
        off_specs = [pl.BlockSpec((1,), lambda bh, i, j: (0,),
                                  memory_space=pltpu.SMEM)]
        off_args = (jnp.asarray(q_offset, jnp.int32).reshape(1),)
    else:
        kernel = functools.partial(_flash_fwd_kernel_v2_bounded, **static)
        off_specs = [pl.BlockSpec((1,), lambda bh, i, j: (0,),
                                  memory_space=pltpu.SMEM)] * 2
        off_args = (jnp.asarray(q_offset, jnp.int32).reshape(1),
                    jnp.asarray(k_lo, jnp.int32).reshape(1))
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=off_specs + [
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 8), lambda bh, i, j: (bh, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # accumulator
        ],
        interpret=interpret,
        name="flash_v2",
    )(*off_args, qt, kt, vt)
    o = o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    lse = lse[:, :, 0].reshape(b, h, sq)
    if pad_q:
        o = o[:, :orig_sq]
        lse = lse[:, :, :orig_sq]
    return o, lse


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_fwd_v2(q, k, v, causal=True, block_q=512, block_k=512,
                  interpret=None):
    """Grid-pipelined flash forward; q,k,v [B, S, H, D] (kv pre-repeated)."""
    return _flash_v2_call(q, k, v, causal, block_q, block_k, interpret,
                          None)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "block_length"))
def _flash_fwd_v2_cached(q, k, v, q_offset, block_q=512, block_k=512,
                         interpret=None, block_length: int = 1):
    """Causal grid-pipelined flash where q rows sit at absolute positions
    ``q_offset + i`` against kv rows indexed from 0 — the serving prefill
    form (q is a prompt chunk, k/v the full KV cache with the chunk
    already written at ``q_offset``..). kv pre-repeated to q heads.
    Returns (o, lse). The k-block accumulation order for a given q row is
    identical whatever ``q_offset``/``block_q`` split the prompt arrived
    under — chunked and unchunked prefills of the same gathered cache
    stay bit-identical; the paged prefix-hit path merges a SEPARATE
    prefix state instead and carries a tolerance contract
    (docs/serving.md "Attention kernels")."""
    return _flash_v2_call(q, k, v, True, block_q, block_k, interpret,
                          q_offset, block_length=block_length)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "block_length"))
def _flash_fwd_v2_cached_bounded(q, k, v, q_offset, k_lo, block_q=512,
                                 block_k=512, interpret=None,
                                 block_length: int = 1):
    """Causal cached flash with a kv lower bound: rows < ``k_lo`` are
    masked out (the serving engines' suffix-prefill form on a paged
    prefix-cache hit — those positions live in shared pool pages, not
    the local cache, and are attended by the paged prefill kernel).
    Returns (o, lse) so the caller can LSE-merge the two partial
    softmax states (ops/paged_attention.merge_softmax_states)."""
    return _flash_v2_call(q, k, v, True, block_q, block_k, interpret,
                          q_offset, k_lo=k_lo, block_length=block_length)


def flash_attention_cached(q, k, v, q_start,
                           block_length: int = 1) -> jax.Array:
    """Forward-only flash over a KV cache: q [B, S, H, D] rows at
    positions ``q_start + i``; k/v [B, M, H, D] the cache (kv already
    repeated to q heads, current rows written at q_start..q_start+S).
    Rows past the last q row's bound (its own position; under
    ``block_length`` > 1 the end of its block, :func:`causal_bound`) are
    excluded by the mask, so the cache tail needs no explicit length."""
    o, _ = _flash_fwd_v2_cached(q, k, v, q_start, block_length=block_length)
    return o


def _blockwise_bwd(q, k, v, o, lse, g, causal: bool, block: int = 512):
    """Memory-efficient backward: recompute attention blockwise over k."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = d ** -0.5
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    of = o.astype(jnp.float32)
    delta = jnp.sum(of * gf, axis=-1)  # [B, Sq, H]

    orig_sk = sk
    pad_k = (-sk) % min(block, sk)
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        sk += pad_k
    num_kb = max(1, sk // min(block, sk))
    kb_size = sk // num_kb

    def body(carry, kb):
        dq = carry
        ks = jax.lax.dynamic_slice_in_dim(kf, kb * kb_size, kb_size, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vf, kb * kb_size, kb_size, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, ks,
                       preferred_element_type=jnp.float32) * scale
        k_pos = kb * kb_size + jnp.arange(kb_size)[None, :]
        if causal:
            q_pos = jnp.arange(sq)[:, None]
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        s = jnp.where(k_pos[None, None] < orig_sk, s, NEG_INF)
        p = jnp.exp(s - lse[:, :, :, None])  # [B,H,Sq,Kb]
        dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vs)
        ds = p * (dp - delta.transpose(0, 2, 1)[:, :, :, None]) * scale
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, ks)
        dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        dv = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
        return dq, (dk, dv)

    dq0 = jnp.zeros_like(qf)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, jnp.arange(num_kb))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, sk, h, d)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, sk, h, d)
    if pad_k:
        dk = dk[:, :orig_sk]
        dv = dv[:, :orig_sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention_mlt(q, k, v, causal: bool = True):
    """Our pallas flash attention (kv must already match q heads); forward
    is the grid-pipelined v2 kernel."""
    o, _ = _flash_fwd_v2(q, k, v, causal=causal)
    return o


def _flash_mlt_fwd(q, k, v, causal):
    o, lse = _flash_fwd_v2(q, k, v, causal=causal)
    return o, (q, k, v, o, lse)


def _flash_mlt_bwd(causal, residuals, g):
    q, k, v, o, lse = residuals
    return _blockwise_bwd(q, k, v, o, lse, g, causal)


flash_attention_mlt.defvjp(_flash_mlt_fwd, _flash_mlt_bwd)


# ---------------------------------------------------------------------------
# library pallas kernels (tuned fwd+bwd) and the dispatcher
# ---------------------------------------------------------------------------

def _tuned_block_sizes(sq: int, sk: int):
    """Big (512) pallas blocks for the library flash kernel.

    The library default is 128x128 blocks, small tiles for the MXU at
    head_dim 64 (the gain over them on a v5e: not measured — ROADMAP S7
    owns it). Pick the
    largest of 512/256/128 that divides each sequence length, for both the
    forward and the dq/dkv backward passes. ``pick`` only ever returns a
    divisor of the length (the library kernel requires block | seq), so
    blocks are inherently clamped to the sequence; the short-prompt
    block clamping for OUR v2 kernel path lives in ``_fit_block``.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    def pick(n: int) -> int:
        for c in (512, 256, 128):
            if n % c == 0:
                return c
        return n

    bq, bk = pick(sq), pick(sk)
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq,
        block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq)


def _jax_flash(q, k, v, causal: bool):
    """jax pallas library flash attention over [B, S, H, D].

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so under a mesh — the abstract mesh the train step
    traces under (training/train.make_train_step) — the call runs per
    shard: batch over the data axes, heads over ``tensor``, each device's
    kernel on its own batch shard."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as _fa,
    )

    def call(q, k, v):
        # the library kernel expects [B, H, S, D]
        out = _fa(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                  v.transpose(0, 2, 1, 3), causal=causal,
                  sm_scale=q.shape[-1] ** -0.5,
                  block_sizes=_tuned_block_sizes(q.shape[1], k.shape[1]))
        return out.transpose(0, 2, 1, 3)

    mesh = jax.sharding.get_abstract_mesh()
    split = set() if mesh.empty else {
        name for name, size, kind in zip(mesh.axis_names, mesh.axis_sizes,
                                         mesh.axis_types)
        if size > 1 and kind != AxisType.Manual}
    batch = tuple(a for a in ("data", "fsdp") if a in split)
    heads = "tensor" if "tensor" in split else None
    if not batch and heads is None:
        return call(q, k, v)
    from ..parallel.compat import shard_map

    spec = PartitionSpec(batch or None, None, heads, None)
    return shard_map(call, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def resolve_prefill_impl(impl: str = "auto") -> str:
    """Resolve a serving ``attention_impl`` knob to the engines' prefill
    attention path: ``flash`` (flash_attention_cached — interpret mode
    off-TPU) or ``dense`` (the masked-softmax `_cached_attention`).
    ``kernel`` is the full kernel stack — paged decode kernel AND flash/
    paged prefill (a prefix-hit admission must never fall back to the
    dense gather; docs/serving.md "Attention kernels")."""
    if impl in ("flash", "kernel"):
        return "flash"
    if impl in ("reference", "dense"):
        return "dense"
    if impl != "auto":
        raise ValueError(
            f"unknown prefill attention impl '{impl}' "
            "(auto | flash | kernel | reference | dense)")
    if _on_tpu() or interpret_forced():
        return "flash"
    return "dense"


def attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
              impl: str = "auto") -> jax.Array:
    """Dispatching attention: [B, S, H|Hkv, D] in, [B, S, H, D] out."""
    n_rep = q.shape[2] // k.shape[2]
    if impl == "reference":
        return attention_reference(q, k, v, causal=causal)
    if impl == "auto":
        min_dim = 128
        use_kernel = (
            _on_tpu()
            and q.shape[1] >= min_dim and k.shape[1] >= min_dim
            and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0
        )
        if use_kernel:
            impl = "flash"
        elif not _on_tpu() and interpret_forced():
            # forced interpret mode: run our pallas kernel (fwd + blockwise
            # custom-vjp bwd) so CPU test runs cover the real kernel path
            impl = "mlt_flash"
        else:
            impl = "reference"
    if impl == "reference":
        return attention_reference(q, k, v, causal=causal)
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    if impl == "flash":
        return _jax_flash(q, k, v, causal)
    if impl == "mlt_flash":
        return flash_attention_mlt(q, k, v, causal)
    raise ValueError(f"unknown attention impl '{impl}'")
