"""JAX auto-trainer — the TPU-native replacement for the reference's
PyTorch+Horovod trainer (mlrun/frameworks/pytorch/__init__.py:46 ``train``,
mlrun_interface.py:106 training loop, :561-566 hvd, :849 allreduce).

``train(...)`` runs a sharded fine-tune of a Llama-family model inside a run
context: builds the mesh from config/runtime spec, streams data, logs
per-step metrics + final MFU, checkpoints via orbax, and registers the model
(adapters or full weights) in the artifact registry — rank-0-only through the
ctx layer.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Iterator, Optional

from ...config import mlconf
from ...execution import MLClientCtx
from ...models import llama as llama_mod
from ...models import nemotron_h as nemotron_h_mod
from ...models import xing4 as xing4_mod
from ...models.llama import LlamaConfig
from ...utils import logger

MODEL_PRESETS = {
    "llama3-8b": llama_mod.llama3_8b,
    "llama3-70b": llama_mod.llama3_70b,
    "llama3-1b": llama_mod.llama3_1b,
    "tiny": llama_mod.tiny_llama,
    "xing4-29b-a4b": xing4_mod.xing4_29b_a4b,
    "tiny-xing4": xing4_mod.tiny_xing4,
    "nemotron-3-nano-30b-a3b": nemotron_h_mod.nemotron_3_nano_30b_a3b,
    "tiny-nemotron-h": nemotron_h_mod.tiny_nemotron_h,
}


def apply_mlrun(model=None, context: MLClientCtx | None = None,
                model_name: str = "model", tag: str = "", **kwargs):
    """Wrap a (model_config, params) pair with context logging hooks."""
    return JaxTrainerInterface(model=model, context=context,
                               model_name=model_name, tag=tag, **kwargs)


class JaxTrainerInterface:
    """Lifecycle hooks around a training loop (metric logging + model
    registration), the `MLRunInterface` analog for JAX."""

    def __init__(self, model=None, context=None, model_name="model", tag="",
                 **kwargs):
        self.model = model
        self.context = context
        self.model_name = model_name
        self.tag = tag
        self._extra = kwargs

    def log_metrics(self, metrics: dict, step: int | None = None):
        if self.context is not None:
            self.context.log_metrics(metrics, step=step)

    def log_model(self, checkpoint_dir: str = "", metrics: dict | None = None,
                  parameters: dict | None = None, framework: str = "jax"):
        if self.context is None:
            return None
        return self.context.log_model(
            self.model_name, model_dir=checkpoint_dir or None,
            framework=framework, metrics=metrics, parameters=parameters,
            upload=False, target_path=checkpoint_dir or None, tag=self.tag)


def _resolve_model_config(model: str | LlamaConfig | dict,
                          overrides: dict | None = None) -> LlamaConfig:
    import dataclasses

    if isinstance(model, LlamaConfig):
        config = model
    elif isinstance(model, dict):
        config = LlamaConfig(**model)
    elif isinstance(model, str):
        preset = MODEL_PRESETS.get(model)
        if preset is None:
            raise ValueError(
                f"unknown model preset '{model}' "
                f"(have {sorted(MODEL_PRESETS)})")
        config = preset()
    else:
        raise ValueError(f"unsupported model spec {model!r}")
    if overrides:
        config = dataclasses.replace(config, **overrides)
    return config


def _make_stream(dataset: str | None, tokenizer: str | None, batch_size: int,
                 seq_len: int, vocab_size: int, seed: int) -> Iterator:
    """Resolve a dataset url (tokens .npy or text) into an LM batch stream;
    synthetic stream when no dataset is given."""
    from ...training import synthetic_token_stream
    from ...training.data import array_token_stream, text_file_stream

    if not dataset:
        return synthetic_token_stream(batch_size, seq_len, vocab_size,
                                      seed=seed)
    import numpy as np

    from ...datastore import store_manager

    local = store_manager.object(url=dataset).local()
    if local.endswith(".npy"):
        return array_token_stream(np.load(local), batch_size, seq_len,
                                  seed=seed)
    if not tokenizer:
        raise ValueError("text datasets need a tokenizer= id")
    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(tokenizer)
    return text_file_stream(local, tok, batch_size, seq_len, seed=seed)


def train(context: MLClientCtx | None = None,
          model: str | LlamaConfig | dict = "tiny",
          model_overrides: dict | None = None,
          dataset: str | None = None,
          tokenizer: str | None = None,
          batch_size: int = 8,
          seq_len: int = 512,
          steps: int = 100,
          learning_rate: float = 2e-4,
          lora_rank: int = 0,
          lora_alpha: float = 32.0,
          grad_accum: int = 1,
          mesh_shape: dict | None = None,
          context_parallel: str | None = None,
          seq_axis: str | None = None,
          pipeline_stages: int = 0,
          pipeline_microbatches: int = 0,
          moe_experts: int = 0,
          moe_top_k: int = 2,
          moe_capacity_factor: float = 1.25,
          checkpoint_dir: str = "",
          checkpoint_every: int = 0,
          resume: bool = True,
          epoch_steps: int = 0,
          early_stop: dict | None = None,
          tensorboard: bool = False,
          callbacks: list | None = None,
          model_name: str = "model",
          log_every: int = 10,
          seed: int = 0,
          prefetch: int | None = None,
          warmup: bool = True) -> dict:
    """Run a (LoRA) fine-tune end-to-end inside a run context.

    This is the handler the ``tpujob`` runtime executes on every host of the
    pod-slice (SPMD): same code everywhere, jax.distributed handles the rest.
    """
    import jax

    from ...parallel.mesh import initialize_distributed, make_mesh
    from ...training import (
        CheckpointManager,
        TrainConfig,
        Trainer,
        synthetic_token_stream,
    )
    from ...training.data import array_token_stream

    initialize_distributed()

    model_config = _resolve_model_config(model, model_overrides)
    if context_parallel and not mesh_shape:
        # long-context default: all chips on the sequence axis
        mesh_shape = {seq_axis or "seq": jax.device_count()}
    if pipeline_stages and not mesh_shape:
        # pipeline default: stages on 'pipe', the rest on 'data'
        n = jax.device_count()
        if n % pipeline_stages:
            raise ValueError(
                f"pipeline_stages={pipeline_stages} does not divide "
                f"{n} devices; pass mesh_shape explicitly")
        mesh_shape = {"data": n // pipeline_stages,
                      "pipe": pipeline_stages}
    if moe_experts and not mesh_shape:
        # expert default: as much of the expert dim on 'expert' as the
        # chip count divides, the rest on 'fsdp'
        import math

        n = jax.device_count()
        e = math.gcd(moe_experts, n)
        mesh_shape = {"expert": e, "fsdp": n // e}
    train_config = TrainConfig(
        learning_rate=learning_rate, total_steps=steps, lora_rank=lora_rank,
        lora_alpha=lora_alpha, grad_accum=grad_accum, mesh_shape=mesh_shape,
        context_parallel=context_parallel,
        seq_axis=seq_axis or ("seq" if context_parallel else None),
        pipeline_stages=pipeline_stages,
        pipeline_microbatches=pipeline_microbatches,
        moe_experts=moe_experts, moe_top_k=moe_top_k,
        moe_capacity_factor=moe_capacity_factor)
    mesh = make_mesh(mesh_shape)
    trainer = Trainer(model_config, train_config, mesh=mesh)
    trainer.init(seed)

    stream = _make_stream(dataset, tokenizer, batch_size, seq_len,
                          model_config.vocab_size, seed)

    # checkpointing
    manager = None
    if checkpoint_dir or checkpoint_every:
        checkpoint_dir = checkpoint_dir or os.path.join(
            (context.artifact_path if context else mlconf.home_dir),
            "checkpoints", model_name)
        manager = CheckpointManager(checkpoint_dir)
        if resume and manager.latest_step() is not None:
            trainer.state = manager.restore(trainer.state)
            logger.info("resumed from checkpoint",
                        step=int(trainer.state.step))

    from .._common.callbacks import (
        CheckpointCallback,
        EarlyStoppingCallback,
        TensorBoardCallback,
    )

    callbacks = list(callbacks or [])
    if manager is not None and checkpoint_every:
        callbacks.append(CheckpointCallback(manager,
                                            every_steps=checkpoint_every))
    if early_stop:
        # e.g. early_stop={"monitor": "loss", "patience": 3} — JSON-able
        # so it works as a run parameter through the handler contract
        callbacks.append(EarlyStoppingCallback(**early_stop))
    if tensorboard:
        callbacks.append(TensorBoardCallback(
            name=f"{model_name}-tensorboard"))

    interface = apply_mlrun(context=context, model_name=model_name)
    # SIGTERM (spot-slice eviction) → final checkpoint + clean resumable
    # exit instead of a killed run (training/preemption.py)
    from ...training.preemption import PreemptionGuard

    if warmup:
        # AOT-compile the step before the loop: compile time lands in
        # compile_seconds (kept out of steady-state MFU), and with
        # mlconf.training.compile_cache_dir set — threaded into
        # resubmitted JobSets by the service — a preemption-resume
        # restart skips XLA entirely (docs/training_performance.md)
        try:
            warm = trainer.warmup(batch_size, seq_len)
        except Exception as exc:  # noqa: BLE001 - a warmup failure must
            # degrade to a first-step compile, not kill the run
            logger.warning("warmup failed — compiling on first step",
                           error=str(exc))
        else:
            if context is not None and warm.get("compile_seconds"):
                context.log_result("compile_seconds",
                                   warm["compile_seconds"])

    guard = PreemptionGuard().install()
    start = time.perf_counter()
    try:
        final_metrics = trainer.fit(
            stream, steps=steps, context=context, log_every=log_every,
            callbacks=callbacks, checkpoint_manager=manager,
            preemption_guard=guard, epoch_steps=epoch_steps,
            prefetch=prefetch)
    finally:
        guard.restore()
    elapsed = time.perf_counter() - start

    final_metrics = {k: (v if isinstance(v, bool) else float(v))
                     for k, v in final_metrics.items()}
    final_metrics["train_time_s"] = elapsed
    if context is not None:
        context.log_results(final_metrics)

    if manager is not None:
        manager.save(int(trainer.state.step), trainer.state, force=True)
        manager.wait()
        interface.log_model(
            checkpoint_dir=manager.directory, metrics={
                "loss": final_metrics.get("loss"),
                "mfu": final_metrics.get("mfu"),
            },
            parameters={
                "model": str(model), "lora_rank": lora_rank,
                "steps": steps, "seq_len": seq_len,
            })
        manager.close()
    return final_metrics


def evaluate(context: MLClientCtx | None = None,
             model: str | LlamaConfig | dict = "tiny",
             model_overrides: dict | None = None,
             checkpoint_dir: str = "", dataset: str | None = None,
             tokenizer: str | None = None,
             batch_size: int = 8, seq_len: int = 512, steps: int = 10,
             mesh_shape: dict | None = None, seed: int = 0) -> dict:
    """Eval loop: average loss/accuracy over ``steps`` batches
    (reference analog: frameworks/pytorch/__init__.py:212 evaluate)."""
    import jax
    import jax.numpy as jnp

    from ...parallel.mesh import make_mesh
    from ...parallel.sharding import batch_sharding, tree_shardings

    model_config = _resolve_model_config(model, model_overrides)
    mesh = make_mesh(mesh_shape)
    params_shapes = llama_mod.param_shapes(model_config)
    shardings = tree_shardings(params_shapes, mesh)

    if checkpoint_dir:
        from ...training import CheckpointManager

        manager = CheckpointManager(checkpoint_dir)
        import functools

        init = jax.jit(functools.partial(llama_mod.init_params, model_config),
                       out_shardings=shardings)
        params = init(jax.random.PRNGKey(seed))
        restored = manager.restore({"params": params,
                                    "opt_state": None, "step": 0})
        params = restored["params"]
    else:
        import functools

        init = jax.jit(functools.partial(llama_mod.init_params, model_config),
                       out_shardings=shardings)
        params = init(jax.random.PRNGKey(seed))

    data_sh = batch_sharding(mesh)
    eval_step = jax.jit(
        lambda p, t, g: llama_mod.loss_fn(model_config, p, t, g)[1],
        in_shardings=(shardings, data_sh, data_sh))

    stream = _make_stream(dataset, tokenizer, batch_size, seq_len,
                          model_config.vocab_size, seed)
    totals: dict[str, float] = {}
    for _ in range(steps):
        tokens, targets = next(stream)
        metrics = eval_step(params, jax.device_put(tokens, data_sh),
                            jax.device_put(targets, data_sh))
        for key, value in metrics.items():
            totals[key] = totals.get(key, 0.0) + float(value)
    results = {f"eval_{k}": v / steps for k, v in totals.items()}
    if context is not None:
        context.log_results(results)
    return results
