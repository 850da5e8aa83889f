"""Context-parallel llama training — sequence axis inside the train step.

Long-context fine-tuning where activations are sharded along the sequence on
a ``seq`` mesh axis: the decoder runs under ``jax.shard_map`` manual over
``seq`` only (other mesh axes stay ``auto`` so GSPMD keeps handling
fsdp/tensor sharding of the weights), and attention is exact ring attention
(ICI neighbor ppermutes) or Ulysses all-to-all. RoPE positions and the
causal mask use global offsets derived from the shard index.

This is the capability the reference lacks entirely (SURVEY.md §5.7) wired
end-to-end: loss and gradients match the plain (non-CP) path exactly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.norms import rms_norm
from ..ops.ring_attention import ring_attention
from ..ops.rotary import rope_table
from ..ops.ulysses import ulysses_attention
from ..parallel.compat import shard_map
from ..utils.profiler import named
from .llama import LlamaConfig, Params, _layer_body, embed, lm_head


def _cp_hidden(config: LlamaConfig, params: Params, tokens: jax.Array,
               seq_axis: str, attn_impl: str,
               lora: Optional[Params] = None) -> jax.Array:
    """Per-shard decoder body (runs inside shard_map manual over seq)."""
    b, s_local = tokens.shape
    shard = jax.lax.axis_index(seq_axis)
    positions = shard * s_local + jnp.arange(s_local)
    cos, sin = rope_table(positions, config.head_dim, config.rope_theta)

    if attn_impl == "ring":
        def attn_fn(q, k, v):
            return ring_attention(q, k, v, axis_name=seq_axis, causal=True)
    elif attn_impl == "ulysses":
        from ..ops.attention import _repeat_kv

        def attn_fn(q, k, v):
            n_rep = q.shape[2] // k.shape[2]
            return ulysses_attention(q, _repeat_kv(k, n_rep),
                                     _repeat_kv(v, n_rep),
                                     axis_name=seq_axis, causal=True)
    else:
        raise ValueError(f"unknown cp attention impl '{attn_impl}'")

    x = embed(config, params, tokens)

    body = functools.partial(_layer_body, config)
    if config.remat:
        from .llama import _remat_policy

        body = jax.checkpoint(body,
                              policy=_remat_policy(config.remat_policy))

    if lora is not None:
        def scan_fn(carry, scanned):
            layer_params, layer_lora = scanned
            return body(carry, layer_params, cos, sin, layer_lora,
                        attention_fn=attn_fn), None

        x, _ = jax.lax.scan(scan_fn, x, (params["layers"], lora))
    else:
        def scan_fn(carry, layer_params):
            return body(carry, layer_params, cos, sin, None,
                        attention_fn=attn_fn), None

        x, _ = jax.lax.scan(scan_fn, x, params["layers"])
    return rms_norm(x, params["final_norm_scale"], config.norm_eps)


def make_context_parallel_loss(config: LlamaConfig, mesh: Mesh,
                               seq_axis: str = "seq",
                               attn_impl: str = "ring",
                               data_axes: tuple | None = None):
    """Build loss(params, tokens, targets, lora=None) with sequence-sharded
    activations.

    tokens/targets: [B, S_global]; params: plain llama tree.

    Two sharding modes:
    - ``data_axes=None`` (seq-only): manual over ``seq_axis`` alone; other
      mesh axes stay auto so GSPMD keeps sharding weights. Backward through
      this partial-manual form CHECK-crashes in jax 0.9 when another axis
      is ACTIVE, so it is for seq-only meshes.
    - ``data_axes=("data",...)``: FULL-manual over data+seq — batch is
      split across ``data_axes`` inside the same shard_map (params ride
      replicated; shard_map AD psums their cotangents over the manual
      axes), which sidesteps the partial-manual backward bug for mixed
      data x seq training.
    """
    data_axes = tuple(data_axes or ())
    manual = frozenset({seq_axis, *data_axes})
    batch_spec = tuple(data_axes) or None
    data_spec = P(batch_spec, seq_axis)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), data_spec, data_spec, P()),
        out_specs=P(batch_spec, seq_axis),
        check_vma=False,
        axis_names=manual)
    def nll_shards(params, tokens, targets, lora):
        x = _cp_hidden(config, params, tokens, seq_axis, attn_impl,
                       lora=lora)
        logits = jnp.einsum("bse,ev->bsv", x, lm_head(params),
                            preferred_element_type=jnp.float32)
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        # per-token nll [B_local, s_local]; the global [B, S] array
        # reassembles along the manual axes
        nll = -jnp.take_along_axis(
            log_probs, targets[..., None], axis=-1)[..., 0]
        if not data_axes:
            # pin the auto (batch) axes replicated: GSPMD may otherwise
            # pick a sharding the out_specs (manual axes only) cannot
            # express. NamedSharding (not a bare spec): a bare
            # PartitionSpec constraint needs a mesh context.
            nll = jax.lax.with_sharding_constraint(
                nll, NamedSharding(mesh, P(None, None)))
        return nll

    def loss(params, tokens, targets, lora=None):
        nll = nll_shards(params, tokens, targets, lora)
        loss_value = jnp.mean(nll)
        return loss_value, {"loss": loss_value,
                            "tokens": jnp.asarray(nll.size, jnp.float32)}

    # NOTE: must run under jit — jax 0.9's eager path for partial-manual
    # shard_map re-enters with full specs and rejects them
    return jax.jit(loss)


def make_cp_train_step(config: LlamaConfig, mesh: Mesh, optimizer,
                       seq_axis: str = "seq", attn_impl: str = "ring",
                       lora_rank: int = 0, lora_alpha: float = 32.0,
                       grad_accum: int = 1):
    """Jitted context-parallel train step: full fine-tune or LoRA, with
    optional gradient accumulation (the batch-scaling knob for CP, where
    chips are spent on the sequence axis instead of data parallelism).

    Signature: step(params, lora, opt_state, tokens, targets) ->
    (params, lora, opt_state, metrics); ``lora`` is None for full FT.
    A mesh with an active ``data`` axis uses the full-manual data x seq
    mode (params replicated over data — see make_context_parallel_loss).
    """
    import optax

    from ..parallel.sharding import tree_shardings

    is_lora = lora_rank > 0
    accum = max(1, grad_accum)
    data_axes = tuple(a for a in ("data",)
                      if a in mesh.axis_names and mesh.shape[a] > 1)
    loss_fn = make_context_parallel_loss(config, mesh, seq_axis, attn_impl,
                                         data_axes=data_axes or None)

    def compute_grads(params, lora, tokens, targets):
        if is_lora:
            (loss, metrics), grads = jax.value_and_grad(
                lambda lo: loss_fn(params, tokens, targets, lora=lo),
                has_aux=True)(lora)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, tokens, targets, lora=lora),
                has_aux=True)(params)
        return grads, metrics

    def step(params, lora, opt_state, tokens, targets):
        if accum > 1:
            from ..training.train import accumulate_grads

            grads, metrics = accumulate_grads(
                lambda t, g: compute_grads(params, lora, t, g),
                lora if is_lora else params, tokens, targets, accum)
        else:
            grads, metrics = compute_grads(params, lora, tokens, targets)

        target_tree = lora if is_lora else params
        updates, opt_state = optimizer.update(grads, opt_state, target_tree)
        new_target = optax.apply_updates(target_tree, updates)
        if is_lora:
            return params, new_target, opt_state, metrics
        return new_target, lora, opt_state, metrics

    shapes = jax.eval_shape(
        lambda: __import__("mlrun_tpu.models.llama", fromlist=["init_params"]
                           ).init_params(config, jax.random.PRNGKey(0)))
    replicated = NamedSharding(mesh, P())
    if data_axes:
        # full-manual mode replicates the weights across the data axis
        param_sh = jax.tree_util.tree_map(lambda _: replicated, shapes)
    else:
        param_sh = tree_shardings(shapes, mesh)
    if is_lora:
        from .lora import init_lora

        lora_shapes = jax.eval_shape(
            lambda: init_lora(config, jax.random.PRNGKey(0), lora_rank,
                              lora_alpha))
        lora_sh = jax.tree_util.tree_map(lambda _: replicated, lora_shapes)
        opt_sh = jax.tree_util.tree_map(
            lambda _: replicated, jax.eval_shape(optimizer.init,
                                                 lora_shapes))
    else:
        lora_sh = None
        target_shapes = shapes
        opt_sh = (jax.tree_util.tree_map(
            lambda _: replicated,
            jax.eval_shape(optimizer.init, target_shapes)) if data_axes
            else tree_shardings(jax.eval_shape(optimizer.init,
                                               target_shapes), mesh))
    batch_spec = data_axes or None
    data_sh = NamedSharding(mesh, P(batch_spec, seq_axis))
    # NOTE: no donation — donating through partial-manual shard_map trips an
    # XLA CPU CHECK ("Invalid binary instruction opcode copy") in jax 0.9
    return jax.jit(named("mlt_train_step", step),
                   in_shardings=(param_sh, lora_sh, opt_sh, data_sh,
                                 data_sh),
                   out_shardings=(param_sh, lora_sh, opt_sh, None))
