"""Pipeline parallelism — GPipe-style stages over a ``pipe`` mesh axis.

SURVEY.md §2.4: the reference has NO pipeline parallelism; this adds it
TPU-natively (cf. PAPERS.md MPMD pipeline-parallel reference, implemented
here as SPMD collective pipelining): the stacked llama layer tree
``[L, ...]`` is split into P stages sharded over the ``pipe`` axis via
``shard_map``; microbatch activations rotate stage→stage with
``jax.lax.ppermute`` (ICI/DCN neighbor transfers) while every stage computes
its slice — the classic fill/drain schedule with M microbatches and P-1
bubble steps. Differentiable end-to-end (ppermute has a transpose rule), so
``jax.grad`` of the pipelined loss just works.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.llama import LlamaConfig, _layer_body, embed, lm_head
from ..ops.norms import rms_norm
from ..ops.rotary import rope_table
from .compat import shard_map


def split_layers_for_stages(layers: dict, n_stages: int) -> dict:
    """[L, ...] stacked layer tree -> [P, L/P, ...]."""

    def reshape(leaf):
        if leaf.shape[0] % n_stages:
            raise ValueError(
                f"n_layers {leaf.shape[0]} not divisible by "
                f"{n_stages} stages")
        return leaf.reshape(n_stages, leaf.shape[0] // n_stages,
                            *leaf.shape[1:])

    return jax.tree_util.tree_map(reshape, layers)


def make_pipeline_forward(config: LlamaConfig, mesh: Mesh,
                          num_microbatches: int,
                          pipe_axis: str = "pipe",
                          batch_axis: str | None = None):
    """Build fn(params, tokens) -> logits with layers pipelined over
    ``pipe_axis``. ``params["layers"]`` must be pre-split via
    split_layers_for_stages(mesh.shape[pipe_axis]).

    Batch must divide into ``num_microbatches``. Embedding/unembedding run
    replicated outside the pipelined region (they are cheap relative to the
    decoder at scale; sharding them rides the other mesh axes).

    ``batch_axis`` composes data parallelism with the pipeline: each
    microbatch's batch dim is sharded over that mesh axis inside the
    pipelined region (stage weights stay replicated across it), so a
    ``data x pipe`` mesh runs D independent pipelines in lockstep.
    """
    n_stages = mesh.shape[pipe_axis]

    def stage_fn(stage_layers, x, cos, sin):
        """Run this stage's L/P layers (scan over the local stack)."""

        def body(carry, lp):
            return _layer_body(config, carry, lp, cos, sin, None), None

        out, _ = jax.lax.scan(body, x, stage_layers)
        return out

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(pipe_axis), P(None, batch_axis), P(), P()),
        out_specs=P(None, batch_axis), check_vma=False)
    def pipelined_decoder(stage_layers, x_micro, cos, sin):
        """x_micro: [M, mb, S, E] (replicated); stage_layers carries the
        leading [1, L/P, ...] shard of this device's stage."""
        stage_layers = jax.tree_util.tree_map(lambda a: a[0], stage_layers)
        idx = jax.lax.axis_index(pipe_axis)
        m_total = x_micro.shape[0]
        mb_shape = x_micro.shape[1:]
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        state = jnp.zeros(mb_shape, x_micro.dtype)
        outputs = jnp.zeros_like(x_micro)

        for t in range(m_total + n_stages - 1):
            # stage 0 injects microbatch t during the fill phase
            if t < m_total:
                state = jnp.where(idx == 0, x_micro[t], state)
            state = stage_fn(stage_layers, state, cos, sin)
            out_t = t - (n_stages - 1)
            if out_t >= 0:
                # the last stage just finished microbatch out_t
                outputs = outputs.at[out_t].set(
                    jnp.where(idx == n_stages - 1, state, outputs[out_t]))
            if t < m_total + n_stages - 2:
                state = jax.lax.ppermute(state, pipe_axis, perm)

        # replicate results: only the last stage holds real outputs
        outputs = jnp.where(idx == n_stages - 1, outputs, 0.0)
        return jax.lax.psum(outputs, pipe_axis)

    def forward(params, tokens):
        b, s = tokens.shape
        if b % num_microbatches:
            raise ValueError(
                f"batch {b} not divisible by {num_microbatches} microbatches")
        mb = b // num_microbatches
        if batch_axis and mb % mesh.shape[batch_axis]:
            raise ValueError(
                f"microbatch size {mb} (batch {b} / {num_microbatches} "
                f"microbatches) must divide over the '{batch_axis}' mesh "
                f"axis ({mesh.shape[batch_axis]}); grow the batch or "
                "shrink the data axis")
        x = embed(config, params, tokens)
        cos, sin = rope_table(jnp.arange(s), config.head_dim,
                              config.rope_theta)
        x_micro = x.reshape(num_microbatches, mb, s, -1)
        hidden = pipelined_decoder(params["layers"], x_micro, cos, sin)
        hidden = hidden.reshape(b, s, -1)
        hidden = rms_norm(hidden, params["final_norm_scale"],
                          config.norm_eps)
        return jnp.einsum("bse,ev->bsv", hidden, lm_head(params),
                          preferred_element_type=jnp.float32)

    return forward


def pipeline_loss_fn(config: LlamaConfig, mesh: Mesh,
                     num_microbatches: int, pipe_axis: str = "pipe",
                     batch_axis: str | None = None):
    """Cross-entropy over the pipelined forward (for train steps)."""
    forward = make_pipeline_forward(config, mesh, num_microbatches,
                                    pipe_axis, batch_axis=batch_axis)

    def loss(params, tokens, targets):
        logits = forward(params, tokens)
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            log_probs, targets[..., None], axis=-1)[..., 0]
        loss_value = jnp.mean(nll)
        accuracy = jnp.mean(jnp.argmax(logits, -1) == targets)
        return loss_value, {"loss": loss_value, "accuracy": accuracy}

    return loss
