"""Normalization ops.

RMSNorm is the transformer hot elementwise op; XLA fuses the jnp version into
neighboring ops, which on TPU is usually optimal (HBM-bound fusion). A pallas
variant is provided for cases where fusion is blocked (e.g. explicit
checkpoint boundaries).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm in f32 accumulation, cast back to input dtype."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    variance = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(variance + eps)
    return (normed * scale.astype(jnp.float32)).astype(dtype)


def _rms_norm_kernel(x_ref, scale_ref, out_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(variance + eps)
    out_ref[:] = (normed * scale_ref[:].astype(jnp.float32)).astype(
        out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rms_norm_pallas(x: jax.Array, scale: jax.Array, eps: float = 1e-5,
                    block_rows: int = 256) -> jax.Array:
    """Pallas RMSNorm over the last dim; x is [..., rows, features]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    orig_shape = x.shape
    features = orig_shape[-1]
    rows = 1
    for dim in orig_shape[:-1]:
        rows *= dim
    x2 = x.reshape(rows, features)
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    out = pl.pallas_call(
        functools.partial(_rms_norm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((rows, features), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, features), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((features,), lambda i: (0,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, features), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        name="rms_norm",
    )(x2, scale)
    return out.reshape(orig_shape)
