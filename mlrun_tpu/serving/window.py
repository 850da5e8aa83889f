"""The window cache: what a family with sliding-window layers
(``config.window_cache``, models/laguna.py) keeps of them, a ring of
``sliding_window`` rows a window layer a slot, beside the page pool of its
full layers (docs/serving.md "Window layers and the window cache").

The rings are the family's slot state (``config.state_rows()``:
``window_k``, ``window_v`` [W, Hkv, D] each, over the window layers): an
admission's staging cache holds one ring a window layer, which its prefill
chunks read and advance, and the insert copies it into the slot's (as it
copies a recurrent state); a decode tick writes each live row's key and
value at ``pos mod W`` and attends the ring. The full layers keep their
pages and are attended as any family's are: the closures here take the
caller's ``attend`` for them and route a layer by ``config.cache_index``,
``(kind, index within the kind)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models.llama import WINDOW
from ..ops.window_attention import window_decode, window_prefill


def _routed(window, full):
    """``attend(at, q, k, v)`` over ``at = (kind, index)``: the window
    layers' closure or the caller's, each under its profiler scope
    (``layer/attn/window``, ``layer/attn/full``)."""
    def attend(at, q, k, v):
        kind, index = at
        with jax.named_scope(kind):
            return (window if kind == WINDOW else full)(index, q, k, v)

    return attend


def window_prefill_attend(cache: dict, new: dict, start, n_real, full,
                          impl: str):
    """``attend`` of a prompt chunk (serving/llm.py ``_forward_with_cache``,
    batch 1): a window layer's chunk over the admission's ring before it,
    and the ring after the chunk's ``n_real``-th token appended to
    ``new``; a full layer through ``full``."""
    def window(index, q, k, v):
        out, ring_k, ring_v = window_prefill(
            q[0], k[0], v[0], cache["window_k"][index, 0],
            cache["window_v"][index, 0], start, n_real,
            impl="kernel" if impl == "flash" else "reference")
        new["window_k"].append(ring_k[None])
        new["window_v"].append(ring_v[None])
        return out[None].astype(q.dtype)

    return _routed(window, full)


def window_decode_attend(config, state: dict, pos, live, full, impl: str):
    """``attend`` of a decode tick over the slots' rings (``state``: the
    tick's own dict of the pool's slot state, updated in place): a live
    row's key and value into its ring at ``pos mod W`` first (a dead row's
    entry stays), then ``window_decode``; a full layer through ``full``."""
    rows = jnp.arange(pos.shape[0])
    entry = pos % config.sliding_window

    def window(index, q, k, v):
        for name, row in (("window_k", k[:, 0]), ("window_v", v[:, 0])):
            ring = state[name]
            kept = ring[index, rows, entry]
            state[name] = ring.at[index, rows, entry].set(
                jnp.where(live[:, None, None], row.astype(ring.dtype), kept))
        return window_decode(q[:, 0], state["window_k"], state["window_v"],
                             index, pos, impl=impl)[:, None]

    return _routed(window, full)


def window_pairs(start: int, tokens: int, width: int) -> int:
    """Query-key pairs inside the window of ``tokens`` prompt tokens from
    position ``start``: the token at ``p`` sees ``min(p + 1, width)``."""
    full = max(0, min(tokens, width - 1 - start))
    pairs = full * start + full * (full + 1) // 2
    return pairs + (tokens - full) * width
