"""State-space duality (Mamba-2) over what a sequence keeps: one recurrent
state ``h`` [heads, head_dim, state] float32 a layer, whatever its length
(models/nemotron_h.py; docs/serving.md "State-space layers and the per-slot
state").

The recurrence, a head ``h`` of group ``g`` (``a_t = dt_t A`` <= 0)::

    h_t = exp(a_t) h_{t-1} + dt_t x_t (outer) B_t        y_t = h_t . C_t

Two forms of the same numbers:

- :func:`ssd_prefill` (a chunk of a prompt, a state in and a state out):
  the chunked matmul form. Inside a chunk of ``chunk`` tokens the outputs
  are ``((C B^T) * L) (dt x)`` with ``L[t, s] = exp(sum_{s < r <= t} a_r)``
  below the diagonal, the state the chunk started from adds ``exp(cum_t)
  C_t . h``, and the chunk hands on ``exp(cum_Q) h + (w x)^T B`` with ``w_s
  = exp(cum_Q - cum_s) dt_s``. One Pallas kernel, grid (group, chunk), the
  chunks in order with the group's states resident in the output block.
- :func:`ssm_decode` (one token a row): the recurrence's own step on the
  rows' states, read and written **in place** in the stack of every
  layer's states (the kernel is handed the stack as stored and a layer
  index, and its output aliases the stack).

A token whose ``dt`` is 0 leaves the state as it was (decay 1, input 0):
that is how a caller keeps a bucket's padding and a tick's dead rows out.
Both kernels appear under their own names in a device trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import interpret_default

HIGHEST = jax.lax.Precision.HIGHEST
# heads of a row that ssm_decode's loop runs in one body
UNROLL = 8


def _column(tile, lane):
    """Lane ``lane`` of ``tile`` [rows, lanes] as a column [rows, 1]."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.sum(jnp.where(lanes == lane, tile, 0.0), axis=-1,
                   keepdims=True)


# ---------------------------------------------------------------------------
# prefill: the chunked form
# ---------------------------------------------------------------------------

def _ssd_prefill_kernel(decay_ref, x_ref, b_ref, c_ref, dt_ref, cum_ref,
                        cum_row_ref, h0_ref, y_ref, h_ref, *,
                        heads_per_group: int):
    """Grid (group, chunk). x, y [hpg, Q, P] (the group's heads); b, c [Q,
    N]; dt, cum [Q, hpg] (a column a head) and cum_row [hpg, Q] (the same,
    a row a head); h0 and h [hpg, P, N]. ``h_ref`` keeps its block over the
    chunks: it is the running state. ``decay_ref`` [chunks, heads] (scalar
    memory): ``exp`` of each chunk's whole ``sum a``."""
    g = pl.program_id(0)
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[...]

    q = x_ref.shape[1]
    b = b_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    # C B^T is the group's: every head of it weights the same products
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             precision=HIGHEST,
                             preferred_element_type=jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    dt = dt_ref[...]
    cum = cum_ref[...]
    for j in range(heads_per_group):
        cum_col = _column(cum, j)                       # [Q, 1]
        cum_row = cum_row_ref[j:j + 1, :]               # [1, Q]
        total = _column(cum_row, q - 1)                 # [1, 1]
        dt_col = _column(dt, j)
        x = x_ref[j].astype(jnp.float32)                # [Q, P]
        h = h_ref[j]                                    # [P, N]
        # s > t would be exp of a positive sum: masked before the exp
        decay = jnp.exp(jnp.where(rows >= cols, cum_col - cum_row, -1e30))
        within = jax.lax.dot_general(
            cb * decay, x * dt_col, (((1,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32)
        carried = jax.lax.dot_general(
            c, h, (((1,), (1,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32) * jnp.exp(cum_col)
        y_ref[j] = within + carried
        weight = jnp.exp(total - cum_col) * dt_col      # [Q, 1]
        h_ref[j] = decay_ref[ci, g * heads_per_group + j] * h \
            + jax.lax.dot_general(
                x * weight, b, (((0,), (0,)), ((), ())), precision=HIGHEST,
                preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_prefill(x, dt, a, b, c, h0, chunk: int = 128, interpret=None):
    """x [T, H, P]; dt [T, H] float32 (after its softplus; 0 where a token
    must not move the state); a [H] float32 (negative); b, c [T, G, N]; h0
    [H, P, N] float32, the state before the first token. Returns (y [T, H,
    P] float32, without the skip term; the state after the last token [H,
    P, N] float32). T is padded to whole chunks here (with ``dt`` 0)."""
    if interpret is None:
        interpret = interpret_default()
    t, heads, p = x.shape
    groups, n = b.shape[1:]
    hpg = heads // groups
    pad = (-t) % chunk
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, pad), (0, 0)))
        b = jnp.pad(b, ((0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, pad), (0, 0), (0, 0)))
    padded = t + pad
    chunks = padded // chunk
    dt = dt.astype(jnp.float32)
    # the sums of a inside each chunk, inclusive: small, so made here
    cum = jnp.cumsum((dt * a).reshape(chunks, chunk, heads), axis=1)
    decay = jnp.exp(cum[:, -1])                          # [chunks, H]
    cum = cum.reshape(padded, heads)
    by_group = lambda v: v.reshape(padded, groups, hpg).transpose(1, 0, 2)
    operands = [
        decay,
        x.transpose(1, 0, 2),                            # [H, T, P]
        b.transpose(1, 0, 2), c.transpose(1, 0, 2),      # [G, T, N]
        by_group(dt), by_group(cum),                     # [G, T, hpg]
        by_group(cum).transpose(0, 2, 1),                # [G, hpg, T]
        h0.astype(jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(groups, chunks),
        in_specs=[
            pl.BlockSpec((hpg, chunk, p), lambda g, ci, d: (g, ci, 0)),
            pl.BlockSpec((None, chunk, n), lambda g, ci, d: (g, ci, 0)),
            pl.BlockSpec((None, chunk, n), lambda g, ci, d: (g, ci, 0)),
            pl.BlockSpec((None, chunk, hpg), lambda g, ci, d: (g, ci, 0)),
            pl.BlockSpec((None, chunk, hpg), lambda g, ci, d: (g, ci, 0)),
            pl.BlockSpec((None, hpg, chunk), lambda g, ci, d: (g, 0, ci)),
            pl.BlockSpec((hpg, p, n), lambda g, ci, d: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((hpg, chunk, p), lambda g, ci, d: (g, ci, 0)),
            pl.BlockSpec((hpg, p, n), lambda g, ci, d: (g, 0, 0)),
        ],
    )
    y, h = pl.pallas_call(
        functools.partial(_ssd_prefill_kernel, heads_per_group=hpg),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((heads, padded, p), jnp.float32),
                   jax.ShapeDtypeStruct((heads, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_prefill",
    )(*operands)
    return y.transpose(1, 0, 2)[:t], h


# ---------------------------------------------------------------------------
# decode: one step of the recurrence, the rows' states in place
# ---------------------------------------------------------------------------

def _ssm_decode_kernel(layer_ref, decay_ref, u_ref, b_ref, c_ref, state_ref,
                       new_ref, y_ref, *, heads_per_group: int):
    """Grid (row,). state, new [H, P, N] (the row's states of the layer the
    index maps resolved through ``layer_ref``; ``new`` is the same buffer);
    u [P, H]: ``dt x``, a column a head; b, c [G, N]; y [P, H]: a column a
    head. ``decay_ref`` [rows, H] (scalar memory): ``exp(dt A)``."""
    s = pl.program_id(0)
    heads = state_ref.shape[0]
    u = u_ref[...]
    lanes = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)

    def head(q, y):
        g = q // heads_per_group
        b = b_ref[pl.ds(g, 1), :]                       # [1, N]
        c = c_ref[pl.ds(g, 1), :]
        mine = lanes == q
        column = jnp.sum(jnp.where(mine, u, 0.0), axis=-1, keepdims=True)
        h = decay_ref[s, q] * state_ref[q] + column * b
        new_ref[q] = h
        return jnp.where(mine, jnp.sum(h * c, axis=-1, keepdims=True), y)

    # UNROLL heads a loop body: a head's two lane reductions overlap the
    # next heads' work (the lowering unrolls a loop whole or not at all)
    span = min(heads, UNROLL)

    def body(at, y):
        for j in range(span):
            y = head(at * span + j, y)
        return y

    y_ref[...] = jax.lax.fori_loop(0, heads // span, body,
                                   jnp.zeros(y_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode(states, layer, x, dt, a, b, c, interpret=None):
    """One token a row. ``states`` [L, rows, H, P, N] float32: the stack of
    every state-space layer's states as the engine stores it, of which
    layer ``layer`` (a traced int32) is read and written; x [rows, H, P];
    dt [rows, H] float32 (0: the row's state stays); a [H]; b, c [rows, G,
    N]. Returns (the stack, updated in place where the caller donates it; y
    [rows, H, P] float32 without the skip term)."""
    if interpret is None:
        interpret = interpret_default()
    rows, heads, p = x.shape
    groups, n = b.shape[1:]
    dt = dt.astype(jnp.float32)
    operands = [
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.exp(dt * a),                                           # [R, H]
        (dt[:, :, None] * x.astype(jnp.float32)).transpose(0, 2, 1),
        b.astype(jnp.float32), c.astype(jnp.float32),
        states,
    ]
    state_spec = pl.BlockSpec((None, None, heads, p, n),
                              lambda s, ly, d: (ly[0], s, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((None, p, heads), lambda s, ly, d: (s, 0, 0)),
            pl.BlockSpec((None, groups, n), lambda s, ly, d: (s, 0, 0)),
            pl.BlockSpec((None, groups, n), lambda s, ly, d: (s, 0, 0)),
            state_spec,
        ],
        out_specs=[
            state_spec,
            pl.BlockSpec((None, p, heads), lambda s, ly, d: (s, 0, 0)),
        ],
    )
    block_bytes = heads * p * n * 4
    states, y = pl.pallas_call(
        functools.partial(_ssm_decode_kernel,
                          heads_per_group=heads // groups),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(states.shape, states.dtype),
                   jax.ShapeDtypeStruct((rows, p, heads), jnp.float32)],
        # the stack is operand 5 (the two scalar operands count)
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # a row's states in and out, each double-buffered
            vmem_limit_bytes=max(32 << 20, 6 * block_bytes)),
        interpret=interpret,
        name="ssm_decode",
    )(*operands)
    return states, y.transpose(0, 2, 1)
