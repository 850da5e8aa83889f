"""Rotary position embeddings (RoPE), llama-3 style with optional NTK scaling."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("head_dim", "theta"))
def rope_table(positions: jax.Array, head_dim: int,
               theta: float = 500000.0) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for given positions: [seq, head_dim/2] each."""
    freqs = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [S, D/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Apply rotary embedding. x: [..., seq, heads, head_dim] (interleaved
    pair convention: (x1, x2) halves). Tables of fewer pairs than half a
    head rotate its leading ``2 * pairs`` dims alone (a partial rotary
    factor) and leave the rest as they are."""
    rotated = 2 * cos.shape[-1]
    if rotated < x.shape[-1]:
        return jnp.concatenate([apply_rope(x[..., :rotated], cos, sin),
                                x[..., rotated:]], axis=-1)
    dtype = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    # broadcast cos/sin [seq, half] over heads: [..., seq, 1, half]
    while cos.ndim < x1.ndim:
        cos = cos[..., None, :] if cos.ndim == x1.ndim - 1 else cos[None]
        sin = sin[..., None, :] if sin.ndim == x1.ndim - 1 else sin[None]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(dtype)


def apply_rope_qk(q: jax.Array, k: jax.Array, positions: jax.Array,
                  theta: float = 500000.0) -> tuple[jax.Array, jax.Array]:
    """Apply RoPE to q & k: [batch, seq, heads, head_dim]."""
    cos, sin = rope_table(positions, q.shape[-1], theta)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin)
