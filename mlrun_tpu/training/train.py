"""Distributed train step + trainer loop.

This is the TPU-native replacement for the reference's Horovod training path
(mlrun/frameworks/pytorch/mlrun_interface.py:106 train loop, :561-566 hvd
init, :849 metric allreduce, :903 DistributedSampler): no ranks, no
allreduce calls — the step function is jit-compiled with NamedShardings
derived from parallel/sharding.py rules and XLA emits all ICI/DCN
collectives. Data "sharding" replaces DistributedSampler: the global batch
array is placed with a (data×fsdp)-sharded NamedSharding.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

from ..models import llama as llama_mod
from ..models.llama import LlamaConfig
from ..parallel.mesh import make_mesh
from ..parallel.sharding import (
    DEFAULT_RULES,
    batch_sharding,
    tree_shardings,
)
from ..utils import logger
from ..utils.profiler import named
from .mfu import ThroughputTracker, chip_peak_flops, mfu


@dataclasses.dataclass
class TrainConfig:
    learning_rate: float = 2e-4
    warmup_steps: int = 10
    total_steps: int = 100
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    grad_accum: int = 1
    b1: float = 0.9
    b2: float = 0.95
    lora_rank: int = 0          # 0 = full fine-tune; >0 = LoRA
    lora_alpha: float = 32.0
    mesh_shape: dict | None = None
    seq_axis: str | None = None  # set to e.g. "seq" for context parallelism
    # chunked cross-entropy: avoids the [B,S,vocab] logits allocation
    # (0 = full logits). 512 is a good default for 128k vocab.
    loss_chunk: int = 512
    # long-context: "ring" | "ulysses" shards the SEQUENCE over seq_axis
    # inside the step (models/llama_cp). Composes with LoRA and grad_accum;
    # mesh may be seq-only or data x seq (fsdp/tensor can't combine with
    # CP under jax 0.9 — see make_train_step).
    context_parallel: str | None = None
    # pipeline parallelism (parallel/pipeline.py): >1 splits the layer
    # stack into that many GPipe stages over a 'pipe' mesh axis; composes
    # with a 'data' axis (D independent pipelines) and grad_accum.
    pipeline_stages: int = 0
    # microbatches per pipeline step (0 = pipeline_stages; more shrinks
    # the fill/drain bubble at the cost of smaller per-stage matmuls)
    pipeline_microbatches: int = 0
    # expert parallelism (models/moe.py): >0 swaps the dense MLP for that
    # many routed experts (MoEConfig) sharded over an 'expert' mesh axis
    # when present; composes with data/fsdp/tensor axes.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # attention kernel for the train step (None = keep the model config's
    # own setting): "mlt_flash" runs our pallas flash kernel (custom-vjp
    # blockwise backward; interpret mode off-TPU so CPU runs exercise the
    # real kernel path), "flash" the tuned library kernel, "reference"
    # plain XLA — see ops/attention.attention and
    # docs/training_performance.md "Flash attention in the step"
    attention_impl: str | None = None


class TrainState:
    """Minimal train state pytree (params/lora/opt_state/step)."""

    def __init__(self, params, opt_state, step, lora=None):
        self.params = params
        self.opt_state = opt_state
        self.step = step
        self.lora = lora

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step, self.lora), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], children[3])


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def accumulate_grads(compute_grads: Callable, target_tree, tokens, targets,
                     accum: int):
    """Gradient accumulation shared by the plain and context-parallel
    steps: split the batch into ``accum`` micro-batches, scan
    ``compute_grads(tokens, targets) -> (grads, metrics)``, average the
    gradients, and report the last micro-batch's metrics."""
    b = tokens.shape[0]
    if b < accum or b % accum:
        raise ValueError(
            f"grad_accum={accum} needs a batch divisible by it "
            f"(got batch={b}); a non-multiple would silently drop samples "
            "and an empty micro-batch yields NaN loss")
    micro = b // accum
    tok = tokens.reshape(accum, micro, -1)
    tgt = targets.reshape(accum, micro, -1)

    def body(grads_sum, xs):
        t, g = xs
        grads, metrics = compute_grads(t, g)
        return jax.tree_util.tree_map(
            lambda a, b_: a + b_, grads_sum, grads), metrics

    zero = jax.tree_util.tree_map(jnp.zeros_like, target_tree)
    grads, metrics_stack = jax.lax.scan(body, zero, (tok, tgt))
    grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
    metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics_stack)
    return grads, metrics


def resolve_model_config(model_config, train_config: TrainConfig):
    """Apply TrainConfig model-shaping options: ``moe_experts`` converts a
    dense LlamaConfig into an MoEConfig with the same backbone dims, so a
    user reaches expert parallelism through TrainConfig exactly like
    ``context_parallel``/``pipeline_stages`` (SURVEY §2.4);
    ``attention_impl`` overrides the model's attention dispatch for the
    whole step (flash kernels in the training hot path)."""
    from ..models.moe import MoEConfig

    if train_config.moe_experts and not isinstance(model_config, MoEConfig):
        model_config = MoEConfig(
            **dataclasses.asdict(model_config),
            n_experts=train_config.moe_experts,
            top_k=train_config.moe_top_k,
            capacity_factor=train_config.moe_capacity_factor)
    if train_config.attention_impl is not None and \
            hasattr(model_config, "attention_impl"):
        model_config = dataclasses.replace(
            model_config, attention_impl=train_config.attention_impl)
    return model_config


def _model_api(model_config):
    """(loss_fn, param_shapes, init_params, default_rules) for the
    config's model family — the dense llama path and the MoE path share
    the whole trainer below this indirection. Every loss adapter takes
    the SAME signature (config, params, tokens, targets, lora=,
    act_spec=, loss_chunk=) so the step builder has exactly one call
    site per family decision."""
    from ..models import moe as moe_mod

    if isinstance(model_config, moe_mod.MoEConfig):
        def moe_loss(config, params, tokens, targets, lora=None,
                     act_spec=None, loss_chunk=0):
            # lora is rejected up-front for MoE; act_spec only applies to
            # Explicit-mode meshes of the dense path
            return moe_mod.loss_fn(config, params, tokens, targets,
                                   loss_chunk=loss_chunk)

        return (moe_loss, moe_mod.param_shapes, moe_mod.init_params,
                moe_mod.make_moe_rules())
    return (llama_mod.loss_fn, llama_mod.param_shapes,
            llama_mod.init_params, None)


def make_optimizer(config: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, config.learning_rate, config.warmup_steps,
        max(config.total_steps, config.warmup_steps + 1))
    chain = []
    if config.grad_clip:
        chain.append(optax.clip_by_global_norm(config.grad_clip))
    chain.append(optax.adamw(schedule, b1=config.b1, b2=config.b2,
                             weight_decay=config.weight_decay))
    return optax.chain(*chain)


def make_train_step(model_config: LlamaConfig, train_config: TrainConfig,
                    optimizer: optax.GradientTransformation,
                    mesh: Mesh, rules=None) -> Callable:
    """Build the jitted sharded train step: (state, tokens, targets) ->
    (state, metrics). Works for full fine-tune and LoRA (frozen base),
    dense and MoE (``moe_experts``), plain and pipelined
    (``pipeline_stages``)."""
    model_config = resolve_model_config(model_config, train_config)
    from ..models.moe import MoEConfig

    is_moe = isinstance(model_config, MoEConfig)
    is_lora = train_config.lora_rank > 0
    accum = max(1, train_config.grad_accum)

    if is_moe and is_lora:
        raise ValueError("moe_experts does not compose with lora_rank yet")
    if is_moe and train_config.context_parallel:
        raise ValueError(
            "moe_experts does not compose with context_parallel yet")

    if train_config.pipeline_stages > 1:
        return _make_pp_step(model_config, train_config, optimizer, mesh,
                             rules=rules)

    if train_config.context_parallel:
        seq_axis = train_config.seq_axis or "seq"
        if seq_axis not in mesh.axis_names:
            raise ValueError(
                f"context_parallel needs a '{seq_axis}' axis in the mesh")
        offending = [a for a in mesh.axis_names
                     if a not in (seq_axis, "data") and mesh.shape[a] > 1]
        if offending:
            # jax 0.9 XLA CHECK-crashes on backward through partial-manual
            # shard_map when an auto axis is active. The 'data' axis is
            # supported via the full-manual data x seq mode (params
            # replicated over data); fsdp/tensor cannot combine with CP
            # until the compiler bug is fixed — scale batch with
            # grad_accum instead.
            raise ValueError(
                f"context_parallel training supports seq-only or "
                f"data x seq meshes in this jax version (active axes "
                f"{offending} cannot combine with '{seq_axis}')")
        return _make_cp_step(model_config, train_config, optimizer, mesh,
                             seq_axis, rules)

    # under Auto axis types GSPMD resolves the embedding gather itself;
    # act_spec stays available for Explicit-mode meshes
    act_spec = None
    if any(t == AxisType.Explicit for t in mesh.axis_types):
        batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names
                           and mesh.shape[a] > 1) or None
        tensor_axis = "tensor" if ("tensor" in mesh.axis_names
                                   and mesh.shape["tensor"] > 1) else None
        act_spec = NamedSharding(
            mesh,
            PartitionSpec(batch_axes, train_config.seq_axis, tensor_axis))

    family_loss, shapes_fn, _, family_rules = _model_api(model_config)

    def loss_for(params, lora, tokens, targets):
        return family_loss(model_config, params, tokens, targets,
                           lora=lora, act_spec=act_spec,
                           loss_chunk=train_config.loss_chunk)

    def compute_grads(params, lora, tokens, targets):
        if is_lora:
            def lora_loss(lora_):
                return loss_for(params, lora_, tokens, targets)

            (loss, metrics), grads = jax.value_and_grad(
                lora_loss, has_aux=True)(lora)
        else:
            def full_loss(params_):
                return loss_for(params_, lora, tokens, targets)

            (loss, metrics), grads = jax.value_and_grad(
                full_loss, has_aux=True)(params)
        return grads, metrics

    def step_fn(state: TrainState, tokens, targets):
        # traced under the mesh so what GSPMD cannot partition (the
        # Mosaic flash kernel) can shard_map itself over it
        # (ops/attention._jax_flash)
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            if accum > 1:
                grads, metrics = accumulate_grads(
                    lambda t, g: compute_grads(state.params, state.lora,
                                               t, g),
                    state.lora if is_lora else state.params,
                    tokens, targets, accum)
            else:
                grads, metrics = compute_grads(state.params, state.lora,
                                               tokens, targets)

        target_tree = state.lora if is_lora else state.params
        updates, new_opt_state = optimizer.update(
            grads, state.opt_state, target_tree)
        new_target = optax.apply_updates(target_tree, updates)
        new_state = TrainState(
            params=state.params if is_lora else new_target,
            opt_state=new_opt_state,
            step=state.step + 1,
            lora=new_target if is_lora else state.lora,
        )
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        return new_state, metrics

    # shardings
    rules = rules if rules is not None else (
        family_rules if family_rules is not None else DEFAULT_RULES)
    params_shapes = shapes_fn(model_config)
    param_shardings = tree_shardings(params_shapes, mesh, rules)
    data_sh = batch_sharding(mesh, train_config.seq_axis)
    replicated = NamedSharding(mesh, PartitionSpec())

    if is_lora:
        from ..models.lora import init_lora

        lora_shapes = jax.eval_shape(
            lambda: init_lora(model_config, jax.random.PRNGKey(0),
                              train_config.lora_rank,
                              train_config.lora_alpha))
        lora_shardings = tree_shardings(lora_shapes, mesh, rules)
        opt_state_shapes = jax.eval_shape(optimizer.init, lora_shapes)
        opt_state_shardings = tree_shardings(opt_state_shapes, mesh, rules)
        state_shardings = TrainState(param_shardings, opt_state_shardings,
                                     replicated, lora_shardings)
    else:
        opt_state_shapes = jax.eval_shape(optimizer.init, params_shapes)
        opt_state_shardings = tree_shardings(opt_state_shapes, mesh, rules)
        state_shardings = TrainState(param_shardings, opt_state_shardings,
                                     replicated, None)

    jitted = jax.jit(
        named("mlt_train_step", step_fn),   # the module's name in a profile
        in_shardings=(state_shardings, data_sh, data_sh),
        out_shardings=(state_shardings, replicated),
        donate_argnums=(0,),
    )
    jitted._state_shardings = state_shardings
    jitted._data_sharding = data_sh
    return jitted


def _make_cp_step(model_config, train_config, optimizer, mesh, seq_axis,
                  rules):
    """Context-parallel step adapter: wraps models/llama_cp's train step in
    the (state, tokens, targets) -> (state, metrics) contract. Supports
    full fine-tune and LoRA, with gradient accumulation."""
    from ..models.llama_cp import make_cp_train_step

    raw_step = make_cp_train_step(
        model_config, mesh, optimizer, seq_axis=seq_axis,
        attn_impl=train_config.context_parallel,
        lora_rank=train_config.lora_rank,
        lora_alpha=train_config.lora_alpha,
        grad_accum=train_config.grad_accum)

    def step_fn(state: TrainState, tokens, targets):
        params, lora, opt_state, metrics = raw_step(
            state.params, state.lora, state.opt_state, tokens, targets)
        new_state = TrainState(params, opt_state, state.step + 1, lora)
        return new_state, metrics

    batch_axes = tuple(a for a in ("data",) if a in mesh.axis_names
                       and mesh.shape[a] > 1) or None
    step_fn._data_sharding = NamedSharding(
        mesh, PartitionSpec(batch_axes, seq_axis))
    step_fn._state_shardings = None
    return step_fn


# pipelined params: the stacked-stage layer tree [P, L/P, ...] shards its
# stage dim over 'pipe'; everything else (embedding, head, opt scalars)
# replicates — the pipelined region's shard_map expects exactly this
PP_RULES: list[tuple[str, tuple]] = [
    (r".*layers.*", ("pipe",)),
    (r".*", ()),
]


def _pp_setup(model_config, train_config: TrainConfig, mesh: Mesh,
              rules=None):
    """Validate the mesh and build (batch_axis, split_fn, split param
    shapes, param shardings) for pipeline-parallel training."""
    from ..parallel.pipeline import split_layers_for_stages

    if rules is not None:
        # loud, like the lora/context_parallel compositions: the pipelined
        # region's shard_map fixes the stage sharding, so user rules would
        # be silently dropped if accepted
        raise ValueError(
            "pipeline_stages uses its own stage sharding (PP_RULES); "
            "custom sharding rules are not supported with the pipeline "
            "trainer")
    stages = train_config.pipeline_stages
    if "pipe" not in mesh.axis_names or mesh.shape["pipe"] != stages:
        raise ValueError(
            f"pipeline_stages={stages} needs a 'pipe' mesh axis of that "
            f"size (mesh: {dict(mesh.shape)})")
    if train_config.lora_rank:
        raise ValueError(
            "pipeline_stages does not compose with lora_rank yet")
    if train_config.context_parallel or train_config.moe_experts:
        raise ValueError(
            "pipeline_stages composes with data parallelism only (not "
            "context_parallel/moe_experts)")
    offending = [a for a in mesh.axis_names
                 if a not in ("pipe", "data") and mesh.shape[a] > 1]
    if offending:
        raise ValueError(
            f"pipeline training runs on pipe (+ optional data) mesh axes; "
            f"active axes {offending} are not supported inside the "
            "pipelined region")
    batch_axis = "data" if ("data" in mesh.axis_names
                            and mesh.shape["data"] > 1) else None

    def split(params):
        out = dict(params)
        out["layers"] = split_layers_for_stages(params["layers"], stages)
        return out

    shapes = jax.eval_shape(split, llama_mod.param_shapes(model_config))
    shardings = tree_shardings(shapes, mesh, PP_RULES)
    return batch_axis, split, shapes, shardings


def _make_pp_step(model_config, train_config: TrainConfig, optimizer,
                  mesh: Mesh, rules=None):
    """GPipe train step: layers pipelined over the 'pipe' axis via
    parallel/pipeline.py, composing with a 'data' axis (independent
    pipelines per data shard) and with grad_accum."""
    from ..parallel.pipeline import pipeline_loss_fn

    batch_axis, _, shapes, param_shardings = _pp_setup(
        model_config, train_config, mesh, rules=rules)
    microbatches = (train_config.pipeline_microbatches
                    or train_config.pipeline_stages)
    loss = pipeline_loss_fn(model_config, mesh, microbatches, "pipe",
                            batch_axis=batch_axis)
    accum = max(1, train_config.grad_accum)

    def compute_grads(params, tokens, targets):
        (_, metrics), grads = jax.value_and_grad(
            loss, has_aux=True)(params, tokens, targets)
        return grads, metrics

    def step_fn(state: TrainState, tokens, targets):
        if accum > 1:
            grads, metrics = accumulate_grads(
                lambda t, g: compute_grads(state.params, t, g),
                state.params, tokens, targets, accum)
        else:
            grads, metrics = compute_grads(state.params, tokens, targets)
        updates, new_opt_state = optimizer.update(
            grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        return TrainState(new_params, new_opt_state, state.step + 1,
                          None), metrics

    replicated = NamedSharding(mesh, PartitionSpec())
    opt_shardings = tree_shardings(
        jax.eval_shape(optimizer.init, shapes), mesh, PP_RULES)
    state_shardings = TrainState(param_shardings, opt_shardings,
                                 replicated, None)
    data_sh = NamedSharding(mesh, PartitionSpec(batch_axis))
    jitted = jax.jit(
        named("mlt_train_step", step_fn),   # the module's name in a profile
        in_shardings=(state_shardings, data_sh, data_sh),
        out_shardings=(state_shardings, replicated),
        donate_argnums=(0,),
    )
    jitted._state_shardings = state_shardings
    jitted._data_sharding = data_sh
    return jitted


def init_train_state(model_config: LlamaConfig, train_config: TrainConfig,
                     optimizer, mesh: Mesh, key: jax.Array,
                     rules=None) -> TrainState:
    """Initialize params directly sharded on the mesh (jit with
    out_shardings so no host-memory staging of the full model)."""
    model_config = resolve_model_config(model_config, train_config)
    if train_config.pipeline_stages > 1:
        _, split, shapes, param_shardings = _pp_setup(
            model_config, train_config, mesh, rules=rules)
        params = jax.jit(
            lambda k: split(llama_mod.init_params(model_config, k)),
            out_shardings=param_shardings)(key)
        opt_state = jax.jit(
            optimizer.init,
            out_shardings=tree_shardings(
                jax.eval_shape(optimizer.init, shapes), mesh, PP_RULES),
        )(params)
        step = jax.device_put(jnp.zeros((), jnp.int32),
                              NamedSharding(mesh, PartitionSpec()))
        return TrainState(params, opt_state, step, None)

    _, shapes_fn, init_fn, family_rules = _model_api(model_config)
    rules = rules if rules is not None else (
        family_rules if family_rules is not None else DEFAULT_RULES)
    is_lora = train_config.lora_rank > 0
    params_shapes = shapes_fn(model_config)
    param_shardings = tree_shardings(params_shapes, mesh, rules)

    init_params_sharded = jax.jit(
        functools.partial(init_fn, model_config),
        out_shardings=param_shardings)
    params = init_params_sharded(key)

    if is_lora:
        from ..models.lora import init_lora

        lora_shapes = jax.eval_shape(
            lambda: init_lora(model_config, key, train_config.lora_rank,
                              train_config.lora_alpha))
        lora_shardings = tree_shardings(lora_shapes, mesh, rules)
        lora = jax.jit(
            functools.partial(init_lora, model_config,
                              rank=train_config.lora_rank,
                              alpha=train_config.lora_alpha),
            out_shardings=lora_shardings)(key)
        opt_state = jax.jit(
            optimizer.init,
            out_shardings=tree_shardings(
                jax.eval_shape(optimizer.init, lora_shapes), mesh, rules),
        )(lora)
    else:
        lora = None
        opt_state = jax.jit(
            optimizer.init,
            out_shardings=tree_shardings(
                jax.eval_shape(optimizer.init, params_shapes), mesh, rules),
        )(params)
    step = jax.device_put(jnp.zeros((), jnp.int32),
                          NamedSharding(mesh, PartitionSpec()))
    return TrainState(params, opt_state, step, lora)


def _all_hosts_agree(flag: bool) -> bool:
    """Max-reduce a local boolean across hosts (PreemptionGuard.agreed's
    construction): under multi-host JAX every host must take the same
    stop decision in the same step, or the hosts still stepping deadlock
    in the slice collectives. Single-process: the flag itself."""
    if jax.process_count() <= 1:
        return flag
    import numpy as np
    from jax.experimental import multihost_utils

    flags = multihost_utils.process_allgather(
        np.asarray(flag, np.int32))
    return bool(np.max(flags))


# distinct on-demand-profiler tick source per trainer instance: two
# concurrent fit loops must not jointly drain one steps-bound capture
# (utils/profiler.tick counts down only the claiming source's ticks)
_TRAINER_SEQUENCE = iter(range(1, 1 << 30))


class Trainer:
    """High-level trainer used by the jax framework adapter and bench."""

    def __init__(self, model_config: LlamaConfig,
                 train_config: TrainConfig | None = None,
                 mesh: Mesh | None = None, rules=None):
        # wire the persistent XLA compilation cache BEFORE anything can
        # trigger a jit compile: a resubmitted JobSet carrying
        # COMPILE_CACHE_ENV then loads step-fn executables from disk
        # instead of recompiling (utils/compile_cache.py); no-op when
        # mlconf.training.compile_cache_dir is unset
        from ..utils import compile_cache

        compile_cache.configure_from_mlconf()
        self.train_config = train_config or TrainConfig()
        self.model_config = resolve_model_config(model_config,
                                                 self.train_config)
        self.mesh = mesh or make_mesh(self.train_config.mesh_shape)
        self.rules = rules
        self.optimizer = make_optimizer(self.train_config)
        self.step_fn = make_train_step(
            self.model_config, self.train_config, self.optimizer,
            self.mesh, rules)
        self.state: Optional[TrainState] = None
        self._metrics_history: list[dict] = []
        # warmup() products: wall seconds of the last step-fn compile and
        # the AOT executable train_step dispatches through when shapes
        # match (no in-process recompile even without a persistent cache)
        self.compile_seconds: Optional[float] = None
        self._compiled = None
        self._warmed_shape: Optional[tuple] = None
        # goodput accounting (docs/observability.md "Goodput & badput"):
        # fit() builds a fresh per-run ledger here; after fit it holds
        # the final attribution (bench/debug read .summary())
        self.goodput = None
        self._compile_attributed = False
        self._profiler_source = f"trainer-{next(_TRAINER_SEQUENCE)}"
        # device HBM + host RSS exposition while this trainer lives
        # (mlt_device_mem_bytes / mlt_host_rss_bytes, scrape-time)
        from ..obs import register_memory_collector

        register_memory_collector(self)

    def init(self, seed: int = 0) -> TrainState:
        self.state = init_train_state(
            self.model_config, self.train_config, self.optimizer, self.mesh,
            jax.random.PRNGKey(seed), self.rules)
        return self.state

    def warmup(self, batch_size: int, seq_len: int) -> dict:
        """AOT-lower/compile the step function for ``(batch_size,
        seq_len)`` int32 batches before the loop starts.

        Records the compile wall time (``compile_seconds``, also the
        ``mlt_train_compile_seconds`` gauge) and keeps the compiled
        executable so matching-shape ``train_step`` calls dispatch
        through it directly. With ``mlconf.training.compile_cache_dir``
        set, the compile also lands in the persistent cache, so the NEXT
        process — a preemption-resume resubmit, a second A-B bench run —
        warms up in loader-time instead of compile-time. Step functions
        without an AOT path (context-parallel wrapper) skip gracefully.
        """
        assert self.state is not None, "call init() first"
        from ..obs import TRAIN_COMPILE_SECONDS
        from ..utils import compile_cache

        cache_dir = compile_cache.configure_from_mlconf()
        if not hasattr(self.step_fn, "lower"):
            logger.warning("warmup skipped: step function has no AOT "
                           "lowering path", step_fn=type(self.step_fn))
            return {"skipped": True}
        spec = jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32)
        started = time.perf_counter()
        self._compiled = self.step_fn.lower(self.state, spec, spec).compile()
        elapsed = time.perf_counter() - started
        self._warmed_shape = (batch_size, seq_len)
        self.compile_seconds = elapsed
        TRAIN_COMPILE_SECONDS.set(elapsed)
        logger.info("train step compiled", batch=batch_size, seq=seq_len,
                    compile_s=round(elapsed, 3),
                    cache_dir=cache_dir or "(off)")
        return {"compile_seconds": elapsed, "cache_dir": cache_dir,
                "batch_size": batch_size, "seq_len": seq_len}

    def shard_batch(self, tokens, targets):
        sharding = self.step_fn._data_sharding
        return (jax.device_put(tokens, sharding),
                jax.device_put(targets, sharding))

    def train_step(self, tokens, targets) -> dict:
        tokens, targets = self.shard_batch(tokens, targets)
        return self._dispatch(tokens, targets)

    def _dispatch(self, tokens, targets) -> dict:
        """Dispatch one step on already-sharded batches (fit() times the
        h2d placement and the dispatch as separate goodput phases)."""
        fn = self.step_fn
        if (self._compiled is not None
                and tokens.shape == self._warmed_shape
                and tokens.dtype == jnp.int32):
            fn = self._compiled
        self.state, metrics = fn(self.state, tokens, targets)
        return metrics

    def _maybe_resume(self, checkpoint_manager, context) -> bool:
        """Honor the service's checkpoint-resume directive
        (MLT_RESUME_FROM_CHECKPOINT / MLT_RESUME_STEP, written into a
        resubmitted JobSet by runtime_handlers.TpuJobHandler): restore the
        train state before the first step so the rescheduled slice resumes
        rather than restarting. No directive, no manager, or an
        already-advanced state (explicit restore) → no-op. Returns
        whether a directive was honored — a resumed run's first-dispatch
        warmup is ``re_warm`` badput (elasticity tax), not a cold
        ``compile`` (obs/goodput.py)."""
        from ..obs import flight_record
        from .checkpoint import resume_directive

        directive = resume_directive()
        if directive is None or checkpoint_manager is None:
            # the common no-directive entry must not force a device sync:
            # int(state.step) blocks the host on everything in flight,
            # and fit() may be entered with steps still dispatching
            return False
        if int(self.state.step) != 0:
            # a directive exists — only now is the sync warranted, to let
            # an explicit prior restore win over the env contract
            return True
        path, step = directive
        try:
            self.state = checkpoint_manager.restore(self.state, step=step)
        except Exception as exc:  # noqa: BLE001 - a missing/corrupt
            # checkpoint must not turn a resumable run into a crash loop;
            # training from step 0 is the correct degraded behavior
            logger.warning("checkpoint resume failed — starting fresh",
                           path=path, step=step, error=str(exc))
            return True
        logger.info("resumed from checkpoint", path=path,
                    step=int(self.state.step))
        flight_record("train.resume", path=str(path),
                      step=int(self.state.step))
        if context is not None and hasattr(context, "log_result"):
            context.log_result("resumed_from_step", int(self.state.step))
        return True

    def reshard(self, devices, checkpoint_manager=None,
                num_slices: int | None = None) -> dict:
        """Rebuild the mesh + step function over ``devices`` and move the
        train state onto it — the elastic slice-loss/grow-back core
        (docs/fault_tolerance.md "Elastic training"). The logical mesh
        shape is refit by rescaling one axis (``parallel.mesh.refit_shape``
        — conventionally the DCN/data axis that spanned the lost slice).

        State transfer has two modes: with a checkpoint available the
        state is RESTORED from it under the new shardings — the only
        honest source after a slice death, since on real hardware the
        dead slice's shards are gone (``CheckpointManager.restore`` is
        sharding-agnostic, so the cross-world-size restore is exact).
        Without one (grow-back, where the survivors hold everything; or
        a simulated shrink that never checkpointed) the LIVE state is
        resharded in place via ``device_put`` — no step rewind. Returns
        the decision record the flight-recorder chain carries."""
        from ..parallel.mesh import _detect_num_slices, make_mesh, refit_shape

        assert self.state is not None, "call init() first"
        devices = list(devices)
        old_world = int(self.mesh.devices.size)
        new_shape = refit_shape(dict(self.mesh.shape), len(devices))
        # slice count for the NEW mesh: the caller (ElasticGuard via fit)
        # knows how many slices survive; detection — and especially the
        # global MLT_NUM_SLICES override — describes the FULL device set
        # and must not be trusted for a survivor subset (it would fail
        # the refit shape's DCN divisibility check mid-recovery)
        num_slices = int(num_slices or _detect_num_slices(devices))
        if next(iter(new_shape.values())) % max(1, num_slices):
            num_slices = 1
        started = time.perf_counter()
        mesh = make_mesh(new_shape, devices=devices, num_slices=num_slices)
        step_fn = make_train_step(self.model_config, self.train_config,
                                  self.optimizer, mesh, self.rules)
        shardings = getattr(step_fn, "_state_shardings", None)
        if shardings is None:
            raise ValueError(
                "elastic resharding needs a step function that exposes "
                "its state shardings (the context-parallel wrapper does "
                "not)")
        latest = checkpoint_manager.latest_step() \
            if checkpoint_manager is not None else None
        if latest is not None:
            abstract = jax.tree_util.tree_map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                  sharding=s),
                self.state, shardings)
            state = checkpoint_manager.restore(abstract, step=latest)
            decision = "restore_checkpoint"
        else:
            state = jax.device_put(self.state, shardings)
            decision = "carry_live_state"
        # swap atomically only once the transfer succeeded — a failed
        # restore leaves the trainer on its old (still valid) world
        self.mesh = mesh
        self.step_fn = step_fn
        self.state = state
        self._compiled = None        # the AOT executable binds the OLD mesh
        self._warmed_shape = None
        elapsed = time.perf_counter() - started
        info = {"world_from": old_world,
                "world_to": int(mesh.devices.size),
                "decision": decision,
                "restored_step": int(self.state.step),
                "reshard_s": elapsed}
        logger.info("resharded train state", **{
            k: round(v, 3) if isinstance(v, float) else v
            for k, v in info.items()})
        return info

    def fit(self, data_iter, steps: int, context=None,
            log_every: int = 10, callbacks: list | None = None,
            checkpoint_manager=None, preemption_guard=None,
            elastic_guard=None,
            epoch_steps: int = 0, prefetch: int | None = None,
            defer_metrics: bool | None = None) -> dict:
        """Run the training loop; logs metrics to the run context
        rank-0-only. With ``preemption_guard`` + ``checkpoint_manager``, a
        SIGTERM (TPU slice eviction) triggers one final synchronous
        checkpoint and a clean early return with ``preempted: True`` — the
        JobSet restart then resumes from that step (training/preemption.py).

        With ``elastic_guard`` (:class:`~.elastic.ElasticGuard`), a
        multi-slice run survives losing a slice mid-fit: the guard is
        polled once per step, a ``fail`` event reshards the run onto the
        survivors (:meth:`reshard` — mesh refit, sharding-agnostic
        checkpoint restore, step-fn rebuild) and training continues at
        reduced world size, taxed as ``degraded`` badput until a
        ``join`` event grows it back. The full
        detect→reshard→continue→grow chain lands in the flight recorder
        (docs/fault_tolerance.md "Elastic training").

        The hot loop is pipelined (docs/training_performance.md):
        ``prefetch`` (default ``mlconf.training.prefetch``) wraps
        ``data_iter`` in a :class:`~.data.DevicePrefetchIterator` so host
        batch production and the H2D transfer overlap the previous step's
        compute; ``defer_metrics`` (default
        ``mlconf.training.defer_metrics``) stages log-point metric reads
        as async device->host copies drained one log interval later —
        the host never stalls dispatch on ``float(loss)``. Callbacks are
        handed same-step host values at log points, so their presence
        forces the synchronous read path. ``tokens_per_sec``/``mfu`` are
        steady-state (post compile/ramp window); the first-step compile
        is reported separately as ``compile_seconds``.

        ``callbacks`` take structured ``frameworks._common.Callback``
        objects (on_train_begin / on_step_end / on_epoch_end /
        on_train_end; returning False from a step/epoch hook stops
        training gracefully with ``stopped_early: True``) as well as the
        legacy bare ``callback(step, metrics, trainer)`` callables.
        ``epoch_steps`` groups steps into epochs for the epoch hooks
        (0 = no epoch structure)."""
        from ..config import mlconf
        from ..frameworks._common.callbacks import CallbackList
        from ..obs import (
            TRAIN_COMPILE_SECONDS,
            TRAIN_H2D_BYTES,
            TRAIN_INPUT_WAIT,
            TRAIN_STEP_TIME,
            GoodputLedger,
            flight_record,
            get_flight_recorder,
        )
        from ..utils import profiler as profiler_mod
        from .data import DevicePrefetchIterator

        assert self.state is not None, "call init() first"
        # goodput ledger: every wall-second of this fit lands in the
        # 'step' goodput phase or a typed badput bucket, and the phase
        # transitions below make the attribution sum to wall time by
        # construction (docs/observability.md "Goodput & badput")
        run_uid = str(getattr(context, "uid", "") or "") \
            if context is not None else ""
        ledger = self.goodput = GoodputLedger(run=run_uid)
        with ledger.phase("checkpoint"):
            resumed = self._maybe_resume(checkpoint_manager, context)
        if self.compile_seconds is not None and not self._compile_attributed:
            # warmup() compiled before this fit's wall window opened —
            # attribute it out-of-band, once per trainer
            self._compile_attributed = True
            ledger.attribute("re_warm" if resumed else "compile",
                             self.compile_seconds)
        flight_record("train.fit_begin", run=run_uid, steps=steps,
                      resumed=resumed)
        hooks = CallbackList(callbacks, context=context, trainer=self)

        train_cfg = mlconf.training
        depth = (int(train_cfg.get("prefetch", 0) or 0)
                 if prefetch is None else int(prefetch))
        prefetcher = (data_iter
                      if isinstance(data_iter, DevicePrefetchIterator)
                      else None)
        owned = None
        if depth > 0 and prefetcher is None:
            data_iter = owned = prefetcher = DevicePrefetchIterator(
                data_iter,
                sharding=getattr(self.step_fn, "_data_sharding", None),
                depth=depth)
        defer = (bool(train_cfg.get("defer_metrics", True))
                 if defer_metrics is None else bool(defer_metrics))
        defer = defer and not hooks.callbacks

        tracker = ThroughputTracker(
            int(train_cfg.get("warmup_steps_excluded", 1) or 0))
        input_wait = 0.0     # host seconds blocked in next(data_iter)
        wait_flushed = 0.0   # portion already on the registry counter
        h2d_inline = 0       # bytes counted on the no-prefetch path
        # a caller-owned prefetcher may carry bytes from a PREVIOUS fit —
        # baseline the flush so the counter only gets this fit's delta.
        # (an owned one starts at 0: its pre-baseline staging is ours)
        h2d_flushed = (prefetcher.stats()["h2d_bytes"]
                       if prefetcher is not None and owned is None else 0)
        pending = None       # staged log point awaiting its drain

        def _flush_obs():
            nonlocal wait_flushed, h2d_flushed
            if input_wait > wait_flushed:
                TRAIN_INPUT_WAIT.inc(input_wait - wait_flushed)
                wait_flushed = input_wait
            total = (prefetcher.stats()["h2d_bytes"]
                     if prefetcher is not None else h2d_inline)
            if total > h2d_flushed:
                TRAIN_H2D_BYTES.inc(total - h2d_flushed)
                h2d_flushed = total

        def _log_view(view: dict) -> dict:
            self._metrics_history.append(view)
            if context is not None:
                context.log_metrics(view, step=view["step"])
            else:
                logger.info("train step", **{
                    k: round(v, 4) if isinstance(v, float) else v
                    for k, v in view.items()})
            return view

        def _stage(metrics: dict, extras: dict):
            """Issue async device->host copies for the log point; the
            values are read (cheaply, already resident) at the NEXT log
            point or the loop-exit flush — dispatch never stalls here."""
            staged = {}
            for key, value in metrics.items():
                try:
                    value.copy_to_host_async()
                except AttributeError:
                    pass
                staged[key] = value
            # state.step itself is donated into the NEXT dispatch
            # (donate_argnums=0) — stage a fresh derived array instead
            step_arr = self.state.step + 0
            try:
                step_arr.copy_to_host_async()
            except AttributeError:
                pass
            return (step_arr, staged, extras)

        def _drain(entry) -> dict:
            step_arr, staged, extras = entry
            view = {k: float(v) for k, v in staged.items()}
            view.update(extras)
            view["step"] = int(step_arr)
            return _log_view(view)

        # elastic degraded-capacity accounting: while the run is at W' of
        # W devices, the (1 - W'/W) share of every step-second is moved
        # from goodput into the 'degraded' bucket — attribution still
        # sums to wall because transfer() only reclassifies, and the tax
        # lands BEFORE each export so the counters stay monotone
        degraded_lost = 0.0   # capacity fraction currently lost
        degraded_mark = 0.0   # goodput seconds already taxed
        reshard_pending = False  # next dispatch recompiles → 'reshard'

        def _degraded_tax():
            nonlocal degraded_mark
            good = ledger.goodput_seconds()
            if degraded_lost <= 0.0:
                degraded_mark = good
                return
            delta = good - degraded_mark
            if delta > 0:
                moved = delta * degraded_lost
                ledger.transfer("step", "degraded", moved)
                degraded_mark = good - moved

        hooks.on_train_begin()
        seq_len = None
        last = {}
        epoch = 0
        stopped = False
        local_stop = False  # pending stop vote, acted on at uniform points
        if epoch_steps:
            hooks.on_epoch_begin(0)
        try:
            for step in range(steps):
                # agreed() (not .requested): all hosts must latch in the SAME
                # step or the ones still stepping deadlock the slice collectives
                if preemption_guard is not None and preemption_guard.agreed():
                    logger.warning("preempted — checkpointing before exit",
                                   step=int(self.state.step))
                    flight_record("train.preempt", run=run_uid,
                                  step=int(self.state.step))
                    # a staged log point must land before the early return —
                    # its metrics are what the post-mortem sees
                    if pending is not None:
                        with ledger.phase("metric_flush"):
                            last = _drain(pending)
                        pending = None
                    if checkpoint_manager is not None:
                        with ledger.phase("checkpoint"):
                            checkpoint_manager.save(int(self.state.step),
                                                    self.state, force=True)
                            checkpoint_manager.wait()
                        if context is not None and \
                                hasattr(context, "log_checkpoint"):
                            # the service reads status.checkpoint when it
                            # resubmits the evicted slice — this write is what
                            # makes the restart a *resume*
                            context.log_checkpoint(
                                checkpoint_manager.directory,
                                step=int(self.state.step), commit=False)
                    last = dict(last)
                    last["preempted"] = True
                    last["step"] = int(self.state.step)
                    if context is not None:
                        context.log_result("preempted", True)
                    # the black-box artifact is what the post-eviction
                    # debugging session reads — dump BEFORE the process
                    # can be SIGKILLed at grace-period end
                    flight_record("train.preempt_exit", run=run_uid,
                                  step=int(self.state.step))
                    get_flight_recorder().dump(
                        "preemption", extra={"run": run_uid,
                                             "step": int(self.state.step)})
                    # preempted runs still finalize callbacks (close writers,
                    # log the tensorboard dir) — they matter MOST here, since
                    # the artifacts are what survives the eviction
                    hooks.on_train_end(last)
                    return last
                if elastic_guard is not None:
                    event = elastic_guard.poll()
                    if event is not None:
                        # a staged log point must land before the world
                        # changes — its device arrays live on the OLD mesh
                        if pending is not None:
                            with ledger.phase("metric_flush"):
                                last = _drain(pending)
                            pending = None
                        _degraded_tax()  # settle the tax at the OLD rate
                        if event.kind == "fail":
                            flight_record(
                                "train.slice_fail", run=run_uid,
                                step=int(self.state.step),
                                slice=event.slice_index,
                                survivors=len(event.devices),
                                survivor_devices=[str(d)
                                                  for d in event.devices])
                        else:
                            flight_record(
                                "train.slice_join", run=run_uid,
                                step=int(self.state.step),
                                slice=event.slice_index,
                                world=len(event.devices))
                        with ledger.phase("reshard"):
                            # shrink restores from the last checkpoint
                            # (the dead slice's shards are gone on real
                            # hardware); grow carries the live state —
                            # the survivors hold everything
                            info = self.reshard(
                                event.devices,
                                checkpoint_manager
                                if event.kind == "fail" else None,
                                num_slices=elastic_guard.num_slices
                                - len(elastic_guard.failed_slices))
                        reshard_pending = True
                        degraded_lost = elastic_guard.lost_fraction()
                        degraded_mark = ledger.goodput_seconds()
                        info_flat = {
                            k: (round(v, 3) if isinstance(v, float) else v)
                            for k, v in info.items()}
                        if event.kind == "fail":
                            # black-box artifact: survivor set + reshard
                            # decision, dumped BEFORE training resumes
                            # (the PR 10 post-mortem path)
                            get_flight_recorder().dump(
                                "slice-preemption",
                                extra={"run": run_uid,
                                       "slice": event.slice_index,
                                       "survivors": [str(d) for d
                                                     in event.devices],
                                       **info_flat})
                            flight_record("train.reshard", run=run_uid,
                                          **info_flat)
                        else:
                            flight_record("train.grow", run=run_uid,
                                          **info_flat)
                        if context is not None and \
                                hasattr(context, "log_result"):
                            context.log_result("world_size",
                                               info["world_to"])
                        if prefetcher is not None:
                            # already-staged batches re-place through
                            # shard_batch; future ones stage straight
                            # onto the new mesh
                            prefetcher._sharding = getattr(
                                self.step_fn, "_data_sharding", None)
                ledger.enter("data_wait")
                t_input = time.perf_counter()
                tokens, targets = next(data_iter)
                input_wait += time.perf_counter() - t_input
                seq_len = tokens.shape[1]
                if prefetcher is None:
                    h2d_inline += (getattr(tokens, "nbytes", 0)
                                   + getattr(targets, "nbytes", 0))
                ledger.enter("h2d")
                tokens, targets = self.shard_batch(tokens, targets)
                ledger.enter("step")
                t_dispatch = time.perf_counter()
                metrics = self._dispatch(tokens, targets)
                if step == 0 and self.compile_seconds is None:
                    # tracing + XLA compile block the host inside the first
                    # dispatch (execution does not) — compile-class time,
                    # kept OUT of the steady-state throughput window
                    self.compile_seconds = time.perf_counter() - t_dispatch
                    TRAIN_COMPILE_SECONDS.set(self.compile_seconds)
                    # ...and out of goodput: land the dispatch interval,
                    # then reclassify the compile-class share (a RESUMED
                    # run's warm re-compile is the elasticity tax bucket)
                    self._compile_attributed = True
                    ledger.enter("step")
                    ledger.transfer(
                        "step", "re_warm" if resumed else "compile",
                        self.compile_seconds)
                elif reshard_pending:
                    # the first dispatch after a reshard re-traces +
                    # compiles for the new mesh (warm when the persistent
                    # compile cache holds the program) — reshard-class
                    # time, not goodput
                    reshard_pending = False
                    recompile = time.perf_counter() - t_dispatch
                    ledger.enter("step")
                    ledger.transfer("step", "reshard", recompile)
                    degraded_mark = ledger.goodput_seconds()
                    flight_record("train.reshard_warm", run=run_uid,
                                  loop_step=step,
                                  compile_s=round(recompile, 3))
                # on-demand profiling: claims/advances an armed
                # POST /debug/profile capture; one global check when dark
                profiler_mod.tick(self._profiler_source, context)
                tracker.note_step(tokens.shape[0] * tokens.shape[1])
                log_point = (step + 1) % log_every == 0 or step == steps - 1
                # non-log steps hand callbacks the RAW device metrics — no
                # float() there, so the host keeps dispatching ahead of the
                # device; a callback that reads a value pays its own sync
                step_metrics: dict = dict(metrics)
                if log_point:
                    _degraded_tax()
                    tps = tracker.tokens_per_sec()
                    extras = {
                        "tokens_per_sec": tps,
                        "tokens_per_sec_per_chip": tps / jax.device_count(),
                        "mfu": mfu(tps,
                                   self.model_config.flops_per_token(seq_len)),
                        "input_wait_seconds": input_wait,
                    }
                    if self.compile_seconds is not None:
                        extras["compile_seconds"] = self.compile_seconds
                    if elastic_guard is not None:
                        extras["world_size"] = int(self.mesh.devices.size)
                    extras["goodput_fraction"] = ledger.goodput_fraction()
                    if tps > 0:
                        TRAIN_STEP_TIME.set(
                            tokens.shape[0] * seq_len / tps, timer="fit")
                    _flush_obs()
                    flight_record("train.step", run=run_uid,
                                  step=step + 1,
                                  goodput_fraction=round(
                                      extras["goodput_fraction"], 4))
                    if defer:
                        if pending is not None:
                            with ledger.phase("metric_flush"):
                                last = _drain(pending)
                        pending = _stage(metrics, extras)
                    else:
                        with ledger.phase("metric_flush"):
                            step_metrics = {k: float(v)
                                            for k, v in metrics.items()}
                            step_metrics.update(extras)
                            step_metrics["step"] = int(self.state.step)
                            last = _log_view(step_metrics)
                    # flush attribution deltas onto the mlt_goodput_*
                    # counters at every log point (the federation loop
                    # sees a live fraction, not an end-of-run dump)
                    ledger.export()
                if hooks.callbacks:
                    multihost = jax.process_count() > 1
                    if not hooks.on_step_end(step, step_metrics,
                                             log_point=log_point):
                        local_stop = True
                    if not multihost:
                        stopped = stopped or local_stop
                    elif log_point:
                        # multi-host: a stop vote driven by host-local state
                        # must flip every host in the SAME step or the
                        # still-stepping hosts deadlock in the slice
                        # collectives (PreemptionGuard.agreed construction).
                        # Agreement runs only at log points — deterministic
                        # step indices every host reaches — so pure-observer
                        # callbacks don't cost an allgather per step; a vote
                        # takes effect within log_every steps.
                        stopped = _all_hosts_agree(local_stop)
                    epoch_boundary = epoch_steps and \
                        ((step + 1) % epoch_steps == 0 or step == steps - 1
                         or stopped)
                    if epoch_boundary:
                        # epoch hooks always see host-readable floats — a
                        # boundary off the log cadence would otherwise hand
                        # TensorBoard/metrics logging raw device arrays
                        epoch_view = step_metrics if log_point else \
                            {k: float(v) for k, v in metrics.items()}
                        epoch_vote = not hooks.on_epoch_end(epoch, epoch_view)
                        local_stop = local_stop or epoch_vote
                        if not multihost:
                            stopped = stopped or epoch_vote
                        elif not stopped:
                            # uniform: every host reaches this iff `stopped`
                            # (agreed) is False everywhere, and the boundary
                            # condition itself is step-index-deterministic
                            stopped = _all_hosts_agree(local_stop)
                        epoch += 1
                        if not stopped and step < steps - 1:
                            hooks.on_epoch_begin(epoch)
                    if stopped:
                        if isinstance(last, dict) and last:
                            last = dict(last)
                        else:
                            last = {k: float(v) for k, v in metrics.items()}
                        last["stopped_early"] = True
                        last.setdefault("step", int(self.state.step))
                        break
            if pending is not None:
                with ledger.phase("metric_flush"):
                    last = _drain(pending)
                pending = None
            hooks.on_train_end(last)
            return last
        except BaseException as unwinding:
            # crash post-mortem: the event sequence into the failure is
            # the artifact (docs/observability.md "Flight recorder &
            # debug endpoints"). An explicit except — NOT
            # sys.exc_info() in the finally, which also sees an
            # exception a CALLER frame is busy handling and would dump
            # a spurious crash artifact for a successful fit. Guarded:
            # the original exception must win the unwind.
            try:
                flight_record("train.exception", run=run_uid,
                              error=str(unwinding),
                              error_type=type(unwinding).__name__)
                get_flight_recorder().dump(
                    "train-crash", extra={"run": run_uid,
                                          "error": str(unwinding)})
            except Exception:  # noqa: BLE001
                pass
            raise
        finally:
            if pending is not None:
                # exception exit with a staged log point: land it in the
                # history/context before unwinding (the preemption branch
                # does the same — these are the post-mortem metrics)
                try:
                    _drain(pending)
                except Exception:  # noqa: BLE001 - the original
                    pass           # exception must win the unwind
            _flush_obs()
            try:
                # settle any trailing degraded-capacity tax, then close:
                # trailing open interval -> its current phase; final
                # counter flush + fraction gauge. summary() stays
                # readable on self.goodput
                _degraded_tax()
                ledger.close()
            except Exception:  # noqa: BLE001 - accounting must not
                pass           # replace the loop's own outcome
            if owned is not None:
                # created here -> closed here; drains staged batches so a
                # producer blocked on a full queue can never outlive fit
                owned.close()

    @property
    def metrics_history(self) -> list[dict]:
        return list(self._metrics_history)
