#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, one TPU v5e chip, through the entry points a user calls, at
the full width and depth of ``llama3-1b`` (16 layers, hidden 2048, vocab
128256, bf16, weights from a seed — no network, no download):

- phase ``kernels``: each Pallas kernel of the serving path against its
  gather+dense reference on a seeded pool (bf16 and int8), compiled
  (``interpret=False``), max-abs error printed and bounded;
- phase ``serve``: ``new_function(kind="serving")`` -> router ->
  ``LLMModelServer`` (paged continuous batching) -> ``to_mock_server()``
  and a handful of ``/v2/models/llm/infer`` requests, checked against the
  engine's own ``stats``;
- phase ``train``: ``new_function(kind="local")`` whose handler calls
  ``mlrun_tpu.frameworks.jax.train`` for a few LoRA steps.

Any phase that raises ends the run non-zero. Only after every phase the
last line of stdout is ``{"ok": true, "device": {...}}`` with the device
as JAX reports it. Without a TPU the script refuses: non-zero exit, no
``ok`` line. Every number it prints is a smoke reading, not a metric.

    python chip_smoke.py              # the one-chip run (the driver's)
    python chip_smoke.py --chips 4    # only the fsdp=4 train step and the
                                      # one-device step it is compared with
    JAX_PLATFORMS=cpu MLT_ATTN_INTERPRET=1 python chip_smoke.py \\
        --preset tiny                 # CPU rehearsal: same phases at tiny
                                      # widths, then exits non-zero

The compile cache is where ``JAX_COMPILATION_CACHE_DIR`` says, else
``<checkout>/.jax_cache`` (mlrun_tpu/utils/compile_cache.py). The script
starts no child process: the chip belongs to this one.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

# serving/training geometry per preset — "tiny" exists for the CPU
# rehearsal only
GEOMETRY = {
    "llama3-1b": dict(page_size=128, slots=16, max_len=2048, n_pages=512,
                      long_prompt=1500, shared_prefix=300,
                      max_new_tokens=16, seq_len=2048, batch_size=8),
    "tiny": dict(page_size=16, slots=4, max_len=128, n_pages=32,
                 long_prompt=90, shared_prefix=40, max_new_tokens=4,
                 seq_len=128, batch_size=8),
}
LORA_RANK = 16
TRAIN_STEPS = 6
VERIFY_ROWS = 5            # speculative k + 1
KERNEL_TOLERANCE = 2e-2    # max-abs error over the reference's max-abs
LOSS_TOLERANCE = 5e-2      # step-1 loss ~ ln(vocab) in bf16 compute


def say(phase: str, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# -- phase: kernels -----------------------------------------------------------
def phase_kernels(config, geo, interpret: bool, seed: int):
    """Kernel vs gather+dense reference at the serving shapes. The
    references run at ``highest`` matmul precision so the reading is the
    kernel's own error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlrun_tpu.ops import paged_attention as pattn
    from mlrun_tpu.ops.attention import (
        _flash_fwd_v2_cached,
        _repeat_kv,
        attention_reference,
    )
    from mlrun_tpu.serving.llm import (
        _cached_attention,
        _dequantize_kv,
        _quantize_kv,
        init_kv_cache,
    )
    from mlrun_tpu.serving.paged import gather_prefix_pages

    ps, slots, max_len = geo["page_size"], geo["slots"], geo["max_len"]
    n_pages = geo["n_pages"]
    pps = max_len // ps
    h, hkv, d = config.n_heads, config.n_kv_heads, config.head_dim
    n_rep = h // hkv
    key = jax.random.PRNGKey(seed)
    rng = np.random.default_rng(seed)

    def normal(i, shape, scale, dtype=config.dtype):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale).astype(dtype)

    # the kernels take the pool as the engine stores it and a layer
    # index: two layers that differ, the second attended, and each
    # reference handed that layer alone (a wrong index cannot pass)
    layer = 1
    k_pool = normal(0, (2, n_pages + 1, ps, hkv, d), 0.3)
    v_pool = normal(1, (2, n_pages + 1, ps, hkv, d), 0.3)
    k8, ks = _quantize_kv(k_pool)
    v8, vs = _quantize_kv(v_pool)
    pools = {"bf16": (k_pool, v_pool, {}),
             "int8": (k8, v8, {"k_scale": ks, "v_scale": vs})}

    def only(arr):
        return arr[layer:layer + 1]

    alone = {label: {n: only(s) for n, s in scales.items()}
             for label, (_, _, scales) in pools.items()}

    # every slot maps a distinct run of shuffled pages; positions span
    # one token, mid-page, page edges and the full context; pages past
    # a slot's position stay unmapped (-1 -> the scratch page)
    pos = np.array([0, 5, ps - 1, ps, 2 * ps + 3, max_len // 2,
                    max_len - ps - 1, max_len - 1], np.int32)
    pos = np.resize(pos, slots)
    pos[len(pos) // 2:] = rng.integers(0, max_len, slots - len(pos) // 2)
    order = rng.permutation(n_pages)[:slots * pps].reshape(slots, pps)
    table = np.where(np.arange(pps)[None, :] <= (pos // ps)[:, None],
                     order, -1).astype(np.int32)
    table_j, pos_j = jnp.asarray(table), jnp.asarray(pos)

    readings = {}

    def check(name, out, ref):
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        assert out.shape == ref.shape, (name, out.shape, ref.shape)
        assert np.isfinite(out).all(), f"{name}: non-finite output"
        err = float(np.max(np.abs(out - ref)))
        scale = float(np.max(np.abs(ref)))
        readings[name] = err
        say("kernels", kernel=name, max_abs_err=f"{err:.3e}",
            ref_max_abs=f"{scale:.3e}", shape=out.shape)
        assert err <= KERNEL_TOLERANCE * scale, (
            f"{name}: max-abs error {err} over {KERNEL_TOLERANCE} of the "
            f"reference's {scale}")

    with jax.default_matmul_precision("highest"):
        # paged decode: one token per slot
        q = normal(2, (slots, h, d), 0.5)
        for label, (kp, vp, scales) in pools.items():
            out = pattn._paged_decode_call(q, kp, vp, layer, table_j,
                                           pos_j, ps, interpret=interpret,
                                           **scales)
            ref = pattn.paged_decode_reference(q, only(kp), only(vp), 0,
                                               table_j, pos_j, ps,
                                               **alone[label])
            check(f"paged_decode/{label}", out, ref)

        # paged verify: k+1 rows per slot over the prefix, chunk merged
        base = np.minimum(pos, max_len - VERIFY_ROWS).astype(np.int32)
        base_j = jnp.asarray(base)
        qv = normal(3, (slots, VERIFY_ROWS, h, d), 0.5)
        ck = normal(4, (slots, VERIFY_ROWS, hkv, d), 0.3)
        cv = normal(5, (slots, VERIFY_ROWS, hkv, d), 0.3)
        vtable = jnp.asarray(np.where(
            np.arange(pps)[None, :]
            <= ((base + VERIFY_ROWS - 1) // ps)[:, None], order,
            -1).astype(np.int32))
        for label, (kp, vp, scales) in pools.items():
            out = pattn.paged_verify_attention(
                qv, ck, cv, kp, vp, layer, vtable, base_j, page_size=ps,
                impl="kernel", interpret=interpret, **scales)
            ref = pattn.paged_verify_reference(
                qv, ck, cv, only(kp), only(vp), 0, vtable, base_j, ps,
                **alone[label])
            check(f"paged_verify/{label}", out, ref)

        # paged prefill on a prefix hit: a suffix chunk over `cached`
        # prefix pages in place, against the engines' gather path
        # (gather_prefix_pages, then the dense cached attention)
        chunk = min(512, max_len // 4)
        cached = (max_len // 2 // ps) * ps
        ids = np.full((pps,), -1, np.int32)
        ids[:cached // ps] = order[0, :cached // ps]
        ids_j = jnp.asarray(ids)
        one_layer = dataclasses.replace(config, n_layers=1)
        qp = normal(6, (1, chunk, h, d), 0.5)
        k_suf = normal(7, (1, chunk, hkv, d), 0.3)
        v_suf = normal(8, (1, chunk, hkv, d), 0.3)
        positions = cached + jnp.arange(chunk)[None, :]
        suffix_rows = slice(cached, cached + chunk)
        for label, (kp, vp, scales) in pools.items():
            pool = {"k": only(kp), "v": only(vp), **alone[label]}
            small = gather_prefix_pages(
                pool, init_kv_cache(one_layer, 1, max_len,
                                    kv_dtype="int8" if scales else "native"),
                ids_j, ps)

            def local_and_dense(name, suffix):
                """(the kernel path's local cache: zeros below `cached`;
                the gather path's dense f32 cache: prefix + suffix)"""
                local = jnp.zeros((1, max_len, hkv, d), config.dtype
                                  ).at[:, suffix_rows].set(suffix)
                dense = small[name][0]
                if scales:
                    dense = _dequantize_kv(dense, small[f"{name}_scale"][0],
                                           jnp.float32)
                dense = dense.astype(jnp.float32).at[:, suffix_rows].set(
                    suffix.astype(jnp.float32))
                return _repeat_kv(local, n_rep), dense

            k_loc, k_dense = local_and_dense("k", k_suf)
            v_loc, v_dense = local_and_dense("v", v_suf)
            out = pattn.paged_prefill_attention(
                qp, k_loc, v_loc, jnp.int32(cached), kp, vp, layer, ids_j,
                jnp.int32(cached), page_size=ps, interpret=interpret,
                **scales)
            ref = _cached_attention(one_layer, qp.astype(jnp.float32),
                                    k_dense, v_dense, positions, max_len)
            check(f"paged_prefill/{label}", out, ref)

        # flash v2 cached: a prompt chunk at an offset over a dense cache
        start = max_len // 4
        kc = normal(9, (1, max_len, hkv, d), 0.3)
        vc = normal(10, (1, max_len, hkv, d), 0.3)
        out, _ = _flash_fwd_v2_cached(
            qp, _repeat_kv(kc, n_rep), _repeat_kv(vc, n_rep),
            jnp.int32(start), interpret=interpret)
        ref = attention_reference(
            qp.astype(jnp.float32), kc.astype(jnp.float32),
            vc.astype(jnp.float32), causal=True,
            positions_q=start + jnp.arange(chunk),
            positions_k=jnp.arange(max_len))
        check("flash_v2_cached", out, ref)
    say("kernels", passed=True, checks=len(readings),
        worst=f"{max(readings.values()):.3e}")


# -- phase: serve -------------------------------------------------------------
def phase_serve(preset: str, config, geo, seed: int):
    import jax
    import numpy as np

    import mlrun_tpu

    rng = np.random.default_rng(seed)
    new = geo["max_new_tokens"]

    def prompt(n):
        return rng.integers(1, config.vocab_size, n).tolist()

    fn = mlrun_tpu.new_function("chip-smoke-llm", kind="serving")
    fn.set_topology("router")
    route = fn.add_model(
        "llm", class_name="mlrun_tpu.serving.llm.LLMModelServer",
        model_preset=preset, continuous_batching=True, paged=True,
        page_size=geo["page_size"], slots=geo["slots"],
        max_len=geo["max_len"], n_pages=geo["n_pages"], warmup=True,
        max_new_tokens=new)
    started = time.perf_counter()
    server = fn.to_mock_server()          # builds, warms, starts the engine
    warmup_s = time.perf_counter() - started
    engine = route.object.engine
    say("serve", warmup_compile_s=f"{warmup_s:.2f}")
    try:
        def infer(*prompts):
            t0 = time.perf_counter()
            body = server.test("/v2/models/llm/infer",
                               body={"inputs": list(prompts)})
            outputs = body["outputs"]
            assert len(outputs) == len(prompts), body
            for tokens in outputs:
                assert len(tokens) == new, (len(tokens), new)
                assert all(0 <= t < config.vocab_size for t in tokens)
            return time.perf_counter() - t0

        shared = prompt(geo["shared_prefix"])
        timings = {
            "short": infer(prompt(12)),
            "long": infer(prompt(geo["long_prompt"])),
            "prefix_cold": infer(shared + prompt(20)),
            "prefix_hit": infer(shared + prompt(20)),
        }
        t0 = time.perf_counter()
        with ThreadPoolExecutor(4) as pool:
            list(pool.map(infer, [prompt(24 + 8 * i) for i in range(4)]))
        timings["four_concurrent"] = time.perf_counter() - t0
        stats = engine.stats
    finally:
        engine.stop()
    say("serve", **{f"{k}_s": f"{v:.3f}" for k, v in timings.items()})
    watched = ("decode_attn_impl", "paged_prefill_impl",
               "attn_kernel_ticks", "attn_gather_ticks", "prefix_hits",
               "prefill_kernel_chunks", "prefill_gather_admissions",
               "completed")
    say("serve", **{k: stats.get(k) for k in watched})
    say("serve", **{f"smoke_{k}": stats.get(k) for k in (
        "decode_tick_p50_s", "ttft_p50_s", "itl_p50_s")})
    assert stats["prefix_hits"] >= 1, stats
    assert stats["decode_attn_impl"] == "kernel", stats
    assert stats["paged_prefill_impl"] == "kernel", stats
    assert stats["attn_gather_ticks"] == 0, stats
    assert stats["attn_kernel_ticks"] > 0, stats
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    say("serve", passed=True, requests=8, peak_bytes_in_use=peak)


# -- phase: train -------------------------------------------------------------
class _TrainProbe:
    """What the smoke asserts on, collected through the trainer's own
    callback hooks (a failing callback is logged, not raised, by the
    trainer — so the probe only records; the phase asserts afterwards)."""

    def __init__(self):
        self.steps = []
        self.lora_before = self.lora_after = None
        self.step_text = ""
        self.compile_seconds = None
        self.state = self.mesh = None

    def callbacks(self):
        import jax

        from mlrun_tpu.frameworks._common.callbacks import Callback

        probe = self

        class Probe(Callback):
            def on_train_begin(self):
                probe.lora_before = jax.device_get(self.trainer.state.lora)

            def on_step_end(self, step, metrics):
                probe.steps.append({k: float(v) for k, v in metrics.items()
                                    if k in ("loss", "grad_norm",
                                             "tokens_per_sec")})

            def on_train_end(self, metrics):
                trainer = self.trainer
                probe.lora_after = jax.device_get(trainer.state.lora)
                probe.compile_seconds = trainer.compile_seconds
                if trainer._compiled is not None:
                    probe.step_text = trainer._compiled.as_text()
                probe.state, probe.mesh = trainer.state, trainer.mesh

        return [Probe()]


def run_train(preset: str, geo, seed: int, mesh_shape=None):
    """``train`` inside a local run, as a user's handler would call it."""
    import numpy as np

    import mlrun_tpu

    probe = _TrainProbe()

    def handler(context):
        from mlrun_tpu.frameworks.jax import train

        return train(context, model=preset, lora_rank=LORA_RANK,
                     seq_len=geo["seq_len"], batch_size=geo["batch_size"],
                     steps=TRAIN_STEPS, mesh_shape=mesh_shape, seed=seed,
                     log_every=1, callbacks=probe.callbacks())

    fn = mlrun_tpu.new_function("chip-smoke-train", kind="local",
                                handler=handler)
    run = fn.run(local=True)
    assert run.state() == "completed", (run.state(), run.status.error)
    assert len(probe.steps) == TRAIN_STEPS, probe.steps
    for i, step in enumerate(probe.steps):
        assert np.isfinite(step["loss"]), (i, step)
        assert np.isfinite(step["grad_norm"]) and step["grad_norm"] > 0, \
            (i, step)
    import jax

    changed = jax.tree_util.tree_map(
        lambda a, b: bool(np.any(np.asarray(a) != np.asarray(b))),
        probe.lora_before, probe.lora_after)
    assert any(jax.tree_util.tree_leaves(changed)), \
        "no LoRA factor changed"
    return run, probe


def phase_train(preset: str, geo, on_chip: bool, seed: int):
    import jax

    run, probe = run_train(preset, geo, seed)
    say("train", losses=[round(s["loss"], 4) for s in probe.steps],
        grad_norms=[round(s["grad_norm"], 4) for s in probe.steps])
    if on_chip:
        # the library flash kernel, not attention_reference's einsums
        assert "tpu_custom_call" in probe.step_text, \
            "no tpu_custom_call in the compiled train step"
    results = run.status.results
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    say("train", passed=True, steps=TRAIN_STEPS,
        compile_s=f"{probe.compile_seconds:.2f}",
        flash_kernel_in_step="tpu_custom_call" in probe.step_text,
        smoke_tokens_per_sec=f"{results.get('tokens_per_sec', 0):.1f}",
        peak_bytes_in_use=peak)


def phase_train_sharded(preset: str, geo, on_chip: bool, seed: int):
    """The fsdp=4 train step against the one-device step from the same
    seed and batch, and where the sharded train state lives."""
    import jax
    import numpy as np

    from mlrun_tpu.parallel.sharding import path_str, tree_pspecs

    _, single = run_train(preset, geo, seed, mesh_shape={"fsdp": 1})
    _, probe = run_train(preset, geo, seed, mesh_shape={"fsdp": 4})
    loss_1, loss_4 = single.steps[0]["loss"], probe.steps[0]["loss"]
    say("train4", step1_loss_one_device=f"{loss_1:.5f}",
        step1_loss_fsdp4=f"{loss_4:.5f}",
        abs_diff=f"{abs(loss_1 - loss_4):.2e}",
        losses_one_device=[round(s["loss"], 4) for s in single.steps],
        losses_fsdp4=[round(s["loss"], 4) for s in probe.steps])
    assert abs(loss_1 - loss_4) <= LOSS_TOLERANCE, (loss_1, loss_4)
    if on_chip:
        assert "tpu_custom_call" in probe.step_text

    devices = [d.id for d in probe.mesh.devices.flat]
    assert len(devices) == 4, devices
    per_device = dict.fromkeys(devices, 0)
    sharded_bytes = replicated_bytes = 0
    replicated = []
    leaves = jax.tree_util.tree_leaves_with_path(probe.state)
    specs = jax.tree_util.tree_leaves(
        tree_pspecs(probe.state, probe.mesh),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(leaves) == len(specs)
    for (path, leaf), spec in zip(leaves, specs):
        name = path_str(path)
        shards = leaf.addressable_shards
        assert {s.device.id for s in shards} == set(devices), (
            f"{name} lives on {sorted(s.device.id for s in shards)}, "
            f"not on the mesh")
        whole = all(s.data.shape == leaf.shape for s in shards)
        if whole:
            # whole on every device: only where the rules replicate
            assert not any(spec), f"{name}: rules shard it {spec}, " \
                                  f"yet every device holds it whole"
            replicated.append(name)
            replicated_bytes += leaf.nbytes
        else:
            sharded_bytes += leaf.nbytes
            for s in shards:
                per_device[s.device.id] += s.data.nbytes
    say("train4", sharded_bytes=sharded_bytes,
        per_device_sharded_bytes=per_device,
        replicated_by_rule_bytes=replicated_bytes,
        replicated_leaves=len(replicated))
    for dev, held in per_device.items():
        assert abs(held - sharded_bytes / 4) <= 0.01 * sharded_bytes, (
            dev, held, sharded_bytes)
    say("train4", passed=True,
        compile_s=f"{probe.compile_seconds:.2f}",
        peak_bytes_in_use=[(d.memory_stats() or {}).get("peak_bytes_in_use")
                           for d in probe.mesh.devices.flat])


# -- entry --------------------------------------------------------------------
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--preset", default="llama3-1b",
                        choices=sorted(GEOMETRY),
                        help="'tiny' is the CPU rehearsal: the phases run, "
                             "then the script exits non-zero")
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4 = only the fsdp=4 train step and its "
                             "one-device comparison")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    os.environ.setdefault("MLT_HOME", tempfile.mkdtemp(prefix="mlt-smoke-"))
    import jax
    import jaxlib

    from mlrun_tpu.frameworks.jax.auto_trainer import MODEL_PRESETS
    from mlrun_tpu.utils import compile_cache

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    say("device", platform=device.platform, kind=device.device_kind,
        count=len(jax.devices()), jax=jax.__version__,
        jaxlib=jaxlib.__version__, libtpu=libtpu_version)
    if not on_chip and args.preset != "tiny":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"'{device.platform}') — refusing to run", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {len(jax.devices())}", file=sys.stderr)
        return 2
    say("cache", dir=compile_cache.configure_default(),
        from_env=bool(os.environ.get(compile_cache.CACHE_DIR_ENV)))

    config = MODEL_PRESETS[args.preset]()
    geo = GEOMETRY[args.preset]
    started = time.perf_counter()
    if args.chips == 4:
        phase_train_sharded(args.preset, geo, on_chip, args.seed)
    else:
        phase_kernels(config, geo, interpret=not on_chip, seed=args.seed)
        phase_serve(args.preset, config, geo, args.seed)
        phase_train(args.preset, geo, on_chip, args.seed)
    say("done", total_s=f"{time.perf_counter() - started:.1f}")
    if not on_chip:
        print("chip_smoke: rehearsal finished, but no chip was found — "
              "this is not a chip run", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
