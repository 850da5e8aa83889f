"""Sliding-window attention over a window cache of constant size a slot.

A layer that attends to its last ``W`` positions alone (``i - j < W``:
the window holds the position itself) needs no page that grows with the
context: what a sequence keeps of such a layer is a **ring** of ``W``
rows, the row of position ``p`` at ``p mod W`` (serving/window.py keeps
one ring a window layer a slot, beside the page pool). Two kernels read it:

- **decode** (``window_decode``): one token a row, after its key and value
  went into the ring. Ring entry ``j`` holds position ``pos - ((pos - j)
  mod W)``, which is a position at all iff ``j <= pos``: no other mask. The
  ring is handed as stored, all window layers stacked ``[Lw, slots, W, Hkv,
  D]``, with the layer's index on scalar prefetch, so no program copies a
  layer of it; a grid step reads one slot's ring, every kv head, and loops
  the GQA groups (the blocking ``ops/paged_attention.py`` uses for a page).
  It reads ``W`` keys a row a layer whatever the context.
- **a prefill chunk** (``window_prefill``): ``C`` tokens of one prompt at
  positions ``start .. start + C - 1`` over the ring as it stood before
  the chunk (positions ``start - W .. start - 1``) followed by the chunk's
  own keys: ``W + C`` keys in position order. Query ``i`` of the chunk sees
  key ``t`` of that order iff ``i < t <= i + W`` and the key's position is
  not negative. Queries and keys go in blocks of ``blk`` positions; a query
  block visits the ``W / blk + 1`` key blocks that its window touches and
  no other (grid ``(kv head, query block, key block)``), each with its
  online softmax as in the flash kernels. What comes back beside the
  output is the ring after the chunk's last **real** token: a padded
  chunk's padding never enters it.

Each kernel has a reference of plain masked products (the CPU's path, and
what the kernels are tested against); ``impl`` chooses as
``ops/paged_attention.resolve_paged_impl`` does for the paged kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, interpret_default
from .paged_attention import (
    _layer_prefetch,
    _reset_softmax_state,
    resolve_paged_impl,
)

# positions a block of the prefill kernel: a query block of 128 tokens
# visits 640 keys for the 512 its window needs at W = 512
PREFILL_BLOCK = 128


def ring_order(ring, start):
    """The ring [W, ...] in position order as it stands before position
    ``start`` (traced): row ``t`` holds position ``start - W + t``."""
    return jnp.roll(ring, -(start % ring.shape[0]), axis=0)


def ring_store(last, end):
    """``last`` [W, ...], the rows of positions ``end - W .. end - 1`` in
    order, as the ring keeps them (the row of ``p`` at ``p mod W``)."""
    return jnp.roll(last, end % last.shape[0], axis=0)


# -- decode -------------------------------------------------------------------
def _window_decode_kernel(layer_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, *,
                          scale: float):
    """Grid (slot,); refs: q [1, Hkv, n_rep, d] (the slot's token, heads
    grouped per kv head), k/v [1, W, Hkv, d] (the slot's ring of the layer
    the index map resolved through ``layer_ref``)."""
    s = pl.program_id(0)
    pos = pos_ref[s]
    for h in range(k_ref.shape[2]):
        k = k_ref[0, :, h, :].astype(jnp.float32)       # [W, d]
        v = v_ref[0, :, h, :].astype(jnp.float32)
        q = q_ref[0, h].astype(jnp.float32) * scale     # [n_rep, d]
        logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        entry = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(entry <= pos, logits, NEG_INF)
        m = jnp.max(logits, axis=-1, keepdims=True)
        weight = jnp.exp(logits - m)
        denom = jnp.maximum(jnp.sum(weight, axis=-1, keepdims=True), 1e-30)
        o_ref[0, h] = (jnp.dot(weight, v, preferred_element_type=jnp.float32)
                       / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _window_decode_call(q, k_ring, v_ring, layer, pos, interpret=None):
    if interpret is None:
        interpret = interpret_default()
    slots, h, d = q.shape
    width, hkv = k_ring.shape[2], k_ring.shape[3]
    n_rep = h // hkv

    def q_map(s, ly, ps):
        return (s, 0, 0, 0)

    def ring_map(s, ly, ps):
        return (ly[0], s, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[pl.BlockSpec((1, hkv, n_rep, d), q_map),
                  pl.BlockSpec((None, 1, width, hkv, d), ring_map),
                  pl.BlockSpec((None, 1, width, hkv, d), ring_map)],
        out_specs=pl.BlockSpec((1, hkv, n_rep, d), q_map),
    )
    # heads h*n_rep..(h+1)*n_rep are kv head h's group: a free reshape
    out = pl.pallas_call(
        functools.partial(_window_decode_kernel, scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, hkv, n_rep, d), q.dtype),
        interpret=interpret,
        name="window_decode",
    )(_layer_prefetch(layer), pos.astype(jnp.int32),
      q.reshape(slots, hkv, n_rep, d), k_ring, v_ring)
    return out.reshape(slots, h, d)


def window_decode_reference(q, k_ring, v_ring, layer, pos):
    """Plain masked attention of q [slots, H, D] over layer ``layer`` of the
    rings [Lw, slots, W, Hkv, D]: entry ``j`` is seen iff ``j <= pos``."""
    slots, h, d = q.shape
    k = k_ring[layer].astype(jnp.float32)
    v = v_ring[layer].astype(jnp.float32)
    n_rep = h // k.shape[2]
    qg = q.reshape(slots, k.shape[2], n_rep, d).astype(jnp.float32)
    logits = jnp.einsum("sgrd,swgd->sgrw", qg, k) * d ** -0.5
    entry = jnp.arange(k.shape[1])[None, None, None, :]
    logits = jnp.where(entry <= pos[:, None, None, None], logits, NEG_INF)
    out = jnp.einsum("sgrw,swgd->sgrd", jax.nn.softmax(logits, axis=-1), v)
    return out.reshape(slots, h, d).astype(q.dtype)


def window_decode(q, k_ring, v_ring, layer, pos, *, impl: str = "auto",
                  interpret=None):
    """One token a row: q [slots, H, D] (post-rope, its own key and value
    already in the ring at ``pos % W``) over layer ``layer`` (traced) of
    the rings [Lw, slots, W, Hkv, D]; ``pos`` [slots] the token's position.
    Returns [slots, H, D]."""
    if resolve_paged_impl(impl) == "reference":
        return window_decode_reference(q, k_ring, v_ring, layer, pos)
    return _window_decode_call(q, k_ring, v_ring, layer, pos,
                               interpret=interpret)


# -- a prefill chunk ----------------------------------------------------------
def _window_prefill_kernel(start_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                           l_scr, acc_scr, *, blk: int, n_rep: int,
                           width: int, scale: float):
    """Grid (kv head, query block, key block j); refs: q [1, blk * n_rep, d]
    (the block's tokens, each token's group of heads in a row), k/v [1,
    blk, d] (key block ``qb + j`` of the chunk's ``W + C`` keys)."""
    qb, j = pl.program_id(1), pl.program_id(2)
    start = start_ref[0]

    @pl.when(j == 0)
    def _init():
        _reset_softmax_state(m_scr, l_scr, acc_scr)

    first_key = (qb + j) * blk
    # a block of keys wholly before position 0 (the ring of a prompt's first
    # chunk) holds nothing
    @pl.when(first_key + blk - 1 >= width - start)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # [blk * n_rep, d]
        k = k_ref[0].astype(jnp.float32)                # [blk, d]
        logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        query = qb * blk + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 0) // n_rep
        key = first_key + jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                                   1)
        seen = (key > query) & (key <= query + width) & (key >= width - start)
        logits = jnp.where(seen, logits, NEG_INF)
        m_prev = m_scr[0]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        weight = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[0] = l_scr[0] * alpha + jnp.sum(weight, axis=-1, keepdims=True)
        acc_scr[0] = acc_scr[0] * alpha + jnp.dot(
            weight, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_scr[0] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = acc_scr[0] / jnp.maximum(l_scr[0], 1e-30)


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def _window_prefill_call(q, keys, values, start, blk: int, interpret=None):
    """q [C, H, D] over ``keys``/``values`` [W + C, Hkv, D] in position
    order from ``start - W`` -> [C, H, D] float32."""
    if interpret is None:
        interpret = interpret_default()
    c, h, d = q.shape
    hkv = keys.shape[1]
    width = keys.shape[0] - c
    n_rep = h // hkv
    rows = blk * n_rep
    qg = q.reshape(c, hkv, n_rep, d).transpose(1, 0, 2, 3).reshape(
        hkv, c * n_rep, d)
    kt = keys.transpose(1, 0, 2)                        # [Hkv, W + C, D]
    vt = values.transpose(1, 0, 2)

    def q_map(g, qb, j, st):
        return (g, qb, 0)

    def kv_map(g, qb, j, st):
        return (g, qb + j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hkv, c // blk, width // blk + 1),
        in_specs=[pl.BlockSpec((1, rows, d), q_map),
                  pl.BlockSpec((1, blk, d), kv_map),
                  pl.BlockSpec((1, blk, d), kv_map)],
        out_specs=pl.BlockSpec((1, rows, d), q_map),
        scratch_shapes=[pltpu.VMEM((1, rows, 1), jnp.float32),   # max
                        pltpu.VMEM((1, rows, 1), jnp.float32),   # denom
                        pltpu.VMEM((1, rows, d), jnp.float32)],  # acc
    )
    out = pl.pallas_call(
        functools.partial(_window_prefill_kernel, blk=blk, n_rep=n_rep,
                          width=width, scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, c * n_rep, d), jnp.float32),
        interpret=interpret,
        name="window_prefill",
    )(jnp.asarray(start, jnp.int32).reshape(1), qg, kt, vt)
    return out.reshape(hkv, c, n_rep, d).transpose(1, 0, 2, 3).reshape(
        c, h, d)


def window_prefill_reference(q, keys, values, start):
    """Plain masked attention of the chunk q [C, H, D] over ``keys`` /
    ``values`` [W + C, Hkv, D] in position order from ``start - W``:
    query ``i`` sees key ``t`` iff ``i < t <= i + W`` and ``t >= W -
    start``. Returns [C, H, D] float32."""
    c, h, d = q.shape
    hkv = keys.shape[1]
    width = keys.shape[0] - c
    n_rep = h // hkv
    qg = q.reshape(c, hkv, n_rep, d).astype(jnp.float32)
    logits = jnp.einsum("igrd,tgd->igrt", qg, keys.astype(jnp.float32)) \
        * d ** -0.5
    i = jnp.arange(c)[:, None]
    t = jnp.arange(width + c)[None, :]
    seen = (t > i) & (t <= i + width) & (t >= width - start)
    logits = jnp.where(seen[:, None, None, :], logits, NEG_INF)
    out = jnp.einsum("igrt,tgd->igrd", jax.nn.softmax(logits, axis=-1),
                     values.astype(jnp.float32))
    return out.reshape(c, h, d)


def prefill_block(width: int, chunk: int) -> int:
    """The kernel's block of positions: ``PREFILL_BLOCK``, or the largest
    power of two below it that divides the window and the chunk."""
    blk = PREFILL_BLOCK
    while blk > 1 and (width % blk or chunk % blk):
        blk //= 2
    return blk


def window_prefill(q, k, v, k_ring, v_ring, start, n_real, *,
                   impl: str = "auto", interpret=None):
    """A chunk of one prompt through a window layer: q [C, H, D] and the
    chunk's own k, v [C, Hkv, D] at positions ``start ..`` (traced), of
    which the first ``n_real`` (traced) are the prompt's; ``k_ring`` /
    ``v_ring`` [W, Hkv, D] the layer's ring before the chunk. Returns (out
    [C, H, D] float32, the ring's k and v after the chunk's last real
    token)."""
    width = k_ring.shape[0]
    ordered_k, ordered_v = ring_order(k_ring, start), ring_order(v_ring,
                                                                 start)
    keys = jnp.concatenate([ordered_k, k.astype(k_ring.dtype)], axis=0)
    values = jnp.concatenate([ordered_v, v.astype(v_ring.dtype)], axis=0)
    if resolve_paged_impl(impl) == "reference":
        out = window_prefill_reference(q, keys, values, start)
    else:
        out = _window_prefill_call(q, keys, values, start,
                                   blk=prefill_block(width, q.shape[0]),
                                   interpret=interpret)
    end = start + n_real
    last_k = jax.lax.dynamic_slice_in_dim(keys, n_real, width, axis=0)
    last_v = jax.lax.dynamic_slice_in_dim(values, n_real, width, axis=0)
    return out, ring_store(last_k, end), ring_store(last_v, end)
