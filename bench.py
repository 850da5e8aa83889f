"""Benchmark: Llama LoRA fine-tune train-step MFU on a TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline (BASELINE.md): >=35% MFU for Llama-3-8B LoRA on v5e — on a single
chip we measure the same train-step code path on the 1B-class Llama config
at the largest batch that fits, and report achieved MFU; vs_baseline is
achieved_mfu / 0.35. Without a TPU the default mode exits non-zero and
prints no result: a CPU run says nothing about the chip.

One process per chip: the parent never imports JAX, and each config
attempt runs in its OWN subprocess, one at a time — a failed attempt (OOM,
compile error) otherwise leaves HBM allocations behind on the chip and
poisons every later attempt in the same process (observed 2026-07-29: after
one compile-OOM at batch 32, even the tiny model hit RESOURCE_EXHAUSTED).
The compile cache is placed from outside (``JAX_COMPILATION_CACHE_DIR``,
else ``<checkout>/.jax_cache`` — utils/compile_cache.configure_default).

``bench.py --train`` runs the hot-loop pipelining A-B microbench instead
(``make bench-train``, CPU-runnable): prefetch-off vs prefetch-on steps/s
+ input-wait seconds on the same tiny model and a simulated host input
cost, plus a cold-vs-warm ``Trainer.warmup()`` through the persistent
compile cache (docs/training_performance.md). One JSON line, same
envelope as bench_serve.py.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys


NO_TPU_RC = 2


def _bench(model_scale: str, batch: int, seq: int, steps: int = 8,
           remat_policy: str = "nothing"):
    """Run one measured config in THIS process (subprocess entry point)."""
    import dataclasses

    import jax

    from mlrun_tpu.models import llama3_1b
    from mlrun_tpu.parallel.mesh import make_mesh
    from mlrun_tpu.training import TrainConfig, Trainer, synthetic_token_stream
    from mlrun_tpu.training.mfu import chip_peak_flops
    from mlrun_tpu.utils import compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench: JAX found no TPU (platform '{platform}') — "
              "refusing to measure", file=sys.stderr)
        raise SystemExit(NO_TPU_RC)
    compile_cache.configure_default()
    config = dataclasses.replace({"1b": llama3_1b}[model_scale](),
                                 remat_policy=remat_policy)

    n = jax.device_count()
    mesh = make_mesh({"fsdp": n})
    # chunked-CE peak memory ~ batch*chunk*vocab*4B — hold batch*chunk at
    # ~4k tokens so larger batches don't blow the loss allocation
    loss_chunk = max(64, 4096 // batch)
    train_config = TrainConfig(
        total_steps=steps + 4, lora_rank=16, lora_alpha=32.0, grad_accum=1,
        loss_chunk=loss_chunk)
    trainer = Trainer(config, train_config, mesh=mesh)
    trainer.init(0)
    stream = synthetic_token_stream(batch, seq, config.vocab_size)

    import time

    # warmup (compile), closed before the timed window opens
    tokens, targets = next(stream)
    for _ in range(2):
        metrics = trainer.train_step(tokens, targets)
    jax.block_until_ready(metrics["loss"])

    start = time.perf_counter()
    for _ in range(steps):
        tokens, targets = next(stream)
        metrics = trainer.train_step(tokens, targets)
    final_loss = float(metrics["loss"])
    elapsed = time.perf_counter() - start

    tokens_total = steps * batch * seq
    tps = tokens_total / elapsed
    flops_per_token = config.flops_per_token(seq)
    achieved = tps * flops_per_token / n
    peak = chip_peak_flops()
    return {
        "tokens_per_sec_per_chip": tps / n,
        "mfu": achieved / peak,
        "elapsed_s": elapsed,
        "loss": final_loss,
        "n_chips": n,
        "seq": seq,
        "batch": batch,
        "platform": platform,
        "device": str(jax.devices()[0].device_kind),
    }


def _subprocess_main():
    """Entry for one isolated attempt: bench.py --one scale batch seq policy."""
    import signal

    def _watchdog(signum, frame):
        raise SystemExit("attempt: watchdog fired (hung init or bench)")

    import time

    signal.signal(signal.SIGALRM, _watchdog)
    started = time.monotonic()
    signal.alarm(180)
    import jax

    jax.devices()
    # keep a watchdog armed for the WHOLE attempt, budgeted against total
    # child lifetime so it always fires BEFORE the parent's 900s hard kill
    # and the child lets go of the chip on its own
    elapsed = time.monotonic() - started
    signal.alarm(max(60, int(840 - elapsed)))
    _, _, scale, batch, seq, policy = sys.argv
    result = _bench(scale, int(batch), int(seq), remat_policy=policy)
    signal.alarm(0)
    print("@@RESULT@@" + json.dumps(result))


# -- hot-loop pipelining A-B (make bench-train) ------------------------------

def run_train(steps: int = 20, batch: int = 8, seq: int = 128,
              depth: int = 2, input_delay_s: float = 0.025,
              cache_dir: str | None = None, log_every: int = 0) -> dict:
    """Prefetch-off vs prefetch-on A-B on the tiny model (CPU-runnable).

    ``input_delay_s`` simulates per-batch host input cost (tokenization/
    IO); the prefetch arm should hide it under step compute, so steps/s
    rises and ``input_wait_seconds`` drops. The default (25ms against a
    ~100-250ms CPU step) keeps the expected gap well above CPU-load
    timing noise, so the A-B stays monotone run to run. Both arms init from the same
    seed and consume the same synthetic stream, so the final losses must
    match bit-exactly (asserted in the tier-1 smoke test). The OFF arm's
    ``warmup()`` is the cold compile and the ON arm's the warm one —
    with a persistent cache dir the second skips XLA.
    """
    import tempfile
    import time

    from mlrun_tpu.config import mlconf
    from mlrun_tpu.models import tiny_llama
    from mlrun_tpu.training import TrainConfig, Trainer, \
        synthetic_token_stream

    from mlrun_tpu.utils import compile_cache

    cache_dir = cache_dir or tempfile.mkdtemp(prefix="mlt-compile-cache-")
    previous_cache = str(mlconf.training.get("compile_cache_dir", "") or "")
    mlconf.training.compile_cache_dir = cache_dir
    config = tiny_llama(attention_impl="reference", remat=False)
    log_every = log_every or steps

    def _delayed(stream):
        for item in stream:
            if input_delay_s:
                time.sleep(input_delay_s)
            yield item

    def _arm(prefetch: int) -> dict:
        trainer = Trainer(config, TrainConfig(total_steps=steps + 4))
        trainer.init(0)
        warm = trainer.warmup(batch, seq)
        stream = _delayed(synthetic_token_stream(batch, seq,
                                                 config.vocab_size))
        out = trainer.fit(stream, steps=steps, log_every=log_every,
                          prefetch=prefetch)
        tps = out["tokens_per_sec"]
        # per-run goodput attribution (docs/observability.md "Goodput &
        # badput"): fraction + per-bucket seconds from the fit's ledger
        goodput = trainer.goodput.summary()
        return {
            "steps_per_sec": tps / (batch * seq),
            "tokens_per_sec": tps,
            "input_wait_seconds": out["input_wait_seconds"],
            "compile_seconds": warm.get("compile_seconds", 0.0),
            "loss": out["loss"],
            "mfu": out["mfu"],
            "goodput_fraction": goodput["goodput_fraction"],
            "goodput": goodput,
        }

    try:
        off = _arm(0)
        on = _arm(depth)
    finally:
        # restore the caller's cache config (the smoke test runs this
        # in-process — a leaked global would re-point every later
        # Trainer at the bench's tmp dir)
        mlconf.training.compile_cache_dir = previous_cache
        if previous_cache:
            compile_cache.configure(previous_cache)
        else:
            compile_cache.disable()
    ratio = (on["steps_per_sec"] / off["steps_per_sec"]
             if off["steps_per_sec"] else 0.0)

    def _round(arm: dict) -> dict:
        return {k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in arm.items()}

    return {
        "metric": "train_prefetch_steps_per_sec_ratio",
        "value": round(ratio, 4),
        "unit": "ratio",
        # parity (1.0) is the floor: prefetch must never cost throughput
        "vs_baseline": round(ratio, 4),
        "detail": {
            "prefetch_off": _round(off),
            "prefetch_on": _round(on),
            "prefetch_depth": depth,
            "steps": steps, "batch": batch, "seq": seq,
            "input_delay_s": input_delay_s,
            "compile_cold_s": round(off["compile_seconds"], 3),
            "compile_warm_s": round(on["compile_seconds"], 3),
            "loss_parity": off["loss"] == on["loss"],
            "cache_dir": cache_dir,
        },
    }


def run_goodput(**kwargs) -> dict:
    """``bench.py --train --goodput`` (``make bench-goodput``): the same
    A-B as ``run_train``, re-enveloped around the goodput ledger — the
    headline is the pipelined (prefetch-on) arm's goodput fraction, the
    detail the per-bucket badput seconds of both arms. The prefetch arm
    should convert most ``data_wait`` badput into goodput; the compile
    bucket dominates only because the bench run is seconds long."""
    train = run_train(**kwargs)
    detail = train["detail"]
    off = detail["prefetch_off"]["goodput"]
    on = detail["prefetch_on"]["goodput"]
    return {
        "metric": "train_goodput_fraction",
        "value": round(on["goodput_fraction"], 4),
        "unit": "fraction",
        # the prefetch arm must not attribute WORSE than the sync arm
        "vs_baseline": round(
            on["goodput_fraction"] / off["goodput_fraction"], 4)
        if off["goodput_fraction"] else 0.0,
        "detail": {
            "prefetch_off": {
                "goodput_fraction": round(off["goodput_fraction"], 4),
                "goodput_s": round(off["goodput_s"], 4),
                "wall_s": round(off["wall_s"], 4),
                "badput_s": {k: round(v, 4)
                             for k, v in off["badput"].items()},
            },
            "prefetch_on": {
                "goodput_fraction": round(on["goodput_fraction"], 4),
                "goodput_s": round(on["goodput_s"], 4),
                "wall_s": round(on["wall_s"], 4),
                "badput_s": {k: round(v, 4)
                             for k, v in on["badput"].items()},
            },
            "steps_per_sec_ratio": train["value"],
            "attribution_closed": all(
                abs(arm["goodput_s"] + sum(arm["badput"].values())
                    - arm["wall_s"]) < 0.05 for arm in (off, on)),
            "steps": detail["steps"], "batch": detail["batch"],
            "seq": detail["seq"],
            "input_delay_s": detail["input_delay_s"],
        },
    }


# -- elastic vs full-resubmit A-B (make bench-elastic) -----------------------

def run_elastic(steps: int = 16, batch: int = 8, seq: int = 128,
                fail_at: int = 6, rejoin_at: int = 11,
                checkpoint_every: int = 2, downtime_s: float = 5.0,
                cache_dir: str | None = None) -> dict:
    """``bench.py --elastic`` (``make bench-elastic`` → BENCH_r13.json):
    the same injected slice-kill schedule run two ways —

    - **full resubmit** (the pre-elastic behavior): the kill step ends
      the whole run via the preemption path (final checkpoint), the
      eviction→replacement gap is attributed out-of-band as
      ``preemption_downtime`` (``downtime_s``, the service's default
      first-retry backoff — exactly how the monitor prices it in
      production), and a fresh trainer resumes from the checkpoint and
      finishes the remaining steps (its warm restart rides the
      persistent compile cache, generous to the baseline);
    - **elastic**: an :class:`ElasticGuard` + ``train.slice_fail`` chaos
      injection kill one of two virtual slices mid-fit, the run
      reshards onto the survivors (checkpoint restore at the shrunk
      world), pays the ``degraded`` capacity tax until the replacement
      joins at ``rejoin_at``, and grows back — one fit, no downtime.

    All three mesh programs are prewarmed into the shared persistent
    compile cache first so the A-B prices the *elasticity mechanics*
    (downtime + redone steps vs reshard + degraded capacity), not
    compile-order luck. Attribution sums to wall by construction in
    both arms; the headline is the elastic arm's goodput fraction and
    ``vs_baseline`` its ratio over the resubmit arm's. Both arms are
    judged against the same ``SLO(kind="goodput")`` objective.
    """
    import tempfile

    import jax

    from mlrun_tpu.chaos import chaos, fail_nth
    from mlrun_tpu.config import mlconf
    from mlrun_tpu.models import tiny_llama
    from mlrun_tpu.obs.slo import SLO
    from mlrun_tpu.parallel.mesh import make_mesh
    from mlrun_tpu.training import (
        CheckpointManager,
        ElasticGuard,
        PreemptionGuard,
        TrainConfig,
        Trainer,
        synthetic_token_stream,
    )
    from mlrun_tpu.utils import compile_cache

    n = jax.device_count()
    if n < 2 or n % 2:
        raise SystemExit(f"bench --elastic needs an even device count "
                         f"(got {n}) — run with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    full_shape = {"data": 2, "fsdp": n // 2}
    shrunk_shape = {"data": 1, "fsdp": n // 2}
    config = tiny_llama(attention_impl="reference", remat=False)
    cache_dir = cache_dir or tempfile.mkdtemp(prefix="mlt-compile-cache-")
    previous_cache = str(mlconf.training.get("compile_cache_dir", "") or "")
    mlconf.training.compile_cache_dir = cache_dir

    def _trainer(shape, devices=None):
        trainer = Trainer(config, TrainConfig(total_steps=steps + 4),
                          mesh=make_mesh(shape, devices=devices))
        trainer.init(0)
        return trainer

    def _ckpt_cb(manager):
        def cb(step, metrics, trainer):
            s = int(trainer.state.step)
            if s and s % checkpoint_every == 0:
                manager.save(s, trainer.state, force=True)
                manager.wait()
        return cb

    try:
        # prewarm every mesh program into the persistent cache so
        # neither arm pays compile-order luck
        for shape, devs in ((full_shape, None),
                            (shrunk_shape, list(jax.devices())[: n // 2])):
            _trainer(shape, devs).warmup(batch, seq)

        # -- arm A: full resubmit (pre-elastic behavior) -------------------
        ckdir_a = tempfile.mkdtemp(prefix="mlt-elastic-a-")
        manager_a = CheckpointManager(ckdir_a)
        guard_a = PreemptionGuard()
        counted = iter(range(1 << 20))

        def killing(base):
            for item in base:
                if next(counted) == fail_at:
                    guard_a.request()  # the slice eviction kills the JOB
                yield item

        trainer_a = _trainer(full_shape)
        trainer_a.warmup(batch, seq)
        out_a = trainer_a.fit(
            killing(synthetic_token_stream(batch, seq, config.vocab_size)),
            steps=steps, log_every=1, callbacks=[_ckpt_cb(manager_a)],
            checkpoint_manager=manager_a, preemption_guard=guard_a,
            prefetch=0)
        summary_a1 = trainer_a.goodput.summary()
        resumed_step = int(out_a.get("step", 0))
        trainer_a2 = _trainer(full_shape)
        trainer_a2.warmup(batch, seq)  # warm restart via the cache
        trainer_a2.state = manager_a.restore(trainer_a2.state)
        stream_a2 = synthetic_token_stream(batch, seq, config.vocab_size)
        for _ in range(resumed_step):
            next(stream_a2)
        out_a2 = trainer_a2.fit(stream_a2, steps=steps - resumed_step,
                                log_every=1, prefetch=0)
        summary_a2 = trainer_a2.goodput.summary()
        manager_a.close()
        badput_a: dict = {"preemption_downtime": downtime_s}
        for part in (summary_a1, summary_a2):
            for bucket, seconds in part["badput"].items():
                badput_a[bucket] = badput_a.get(bucket, 0.0) + seconds
        goodput_a = summary_a1["goodput_s"] + summary_a2["goodput_s"]
        wall_a = summary_a1["wall_s"] + downtime_s + summary_a2["wall_s"]
        fraction_a = goodput_a / wall_a if wall_a else 0.0

        # -- arm B: elastic -----------------------------------------------
        ckdir_b = tempfile.mkdtemp(prefix="mlt-elastic-b-")
        manager_b = CheckpointManager(ckdir_b)
        trainer_b = _trainer(full_shape)
        trainer_b.warmup(batch, seq)
        elastic_guard = ElasticGuard(num_slices=2)
        with chaos.inject(
                "train.slice_fail", fail_nth(fail_at + 1),
                action=lambda p, ctx: ctx["box"].__setitem__("fail", 1)), \
             chaos.inject(
                "train.slice_fail", fail_nth(rejoin_at + 1),
                action=lambda p, ctx: ctx["box"].__setitem__("join", 1)):
            out_b = trainer_b.fit(
                synthetic_token_stream(batch, seq, config.vocab_size),
                steps=steps, log_every=1,
                callbacks=[_ckpt_cb(manager_b)],
                checkpoint_manager=manager_b,
                elastic_guard=elastic_guard, prefetch=0)
        summary_b = trainer_b.goodput.summary()
        manager_b.close()
        fraction_b = summary_b["goodput_fraction"]
    finally:
        mlconf.training.compile_cache_dir = previous_cache
        if previous_cache:
            compile_cache.configure(previous_cache)
        else:
            compile_cache.disable()

    # both arms judged against the same goodput objective: burn is the
    # badput fraction over the error budget (1 - target), the burn-rate
    # definition SLO(kind="goodput") evaluates over federated windows
    slo = SLO("train-goodput", "goodput", target=0.5, run="bench-elastic")
    burn_a = (1.0 - fraction_a) / slo.budget if slo.budget else 0.0
    burn_b = (1.0 - fraction_b) / slo.budget if slo.budget else 0.0

    def _closed(goodput, badput, wall):
        return abs(goodput + sum(badput.values()) - wall) < 0.05

    return {
        "metric": "train_elastic_goodput_fraction",
        "value": round(fraction_b, 4),
        "unit": "fraction",
        # >1.0 = elastic beats full resubmit under the same kill schedule
        "vs_baseline": round(fraction_b / fraction_a, 4) if fraction_a
        else 0.0,
        "detail": {
            "full_resubmit": {
                "goodput_fraction": round(fraction_a, 4),
                "goodput_s": round(goodput_a, 4),
                "wall_s": round(wall_a, 4),
                "badput_s": {k: round(v, 4)
                             for k, v in sorted(badput_a.items())},
                "final_step": int(out_a2.get("step", 0)),
                "downtime_s": downtime_s,
            },
            "elastic": {
                "goodput_fraction": round(fraction_b, 4),
                "goodput_s": round(summary_b["goodput_s"], 4),
                "wall_s": round(summary_b["wall_s"], 4),
                "badput_s": {k: round(v, 4)
                             for k, v in
                             sorted(summary_b["badput"].items())},
                "final_step": int(out_b.get("step", 0)),
                "world_sizes": [h.get("world_size")
                                for h in trainer_b.metrics_history],
            },
            "slo": {"kind": "goodput", "target": slo.target,
                    "budget": round(slo.budget, 4),
                    "full_resubmit_burn": round(burn_a, 4),
                    "elastic_burn": round(burn_b, 4),
                    "full_resubmit_meets": burn_a <= 1.0,
                    "elastic_meets": burn_b <= 1.0},
            "attribution_closed": (
                _closed(goodput_a, badput_a, wall_a)
                and _closed(summary_b["goodput_s"], summary_b["badput"],
                            summary_b["wall_s"])),
            "steps": steps, "batch": batch, "seq": seq,
            "fail_at": fail_at, "rejoin_at": rejoin_at,
            "checkpoint_every": checkpoint_every,
            "cache_dir": cache_dir,
        },
    }


def _train_main():
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--train", action="store_true")
    parser.add_argument("--goodput", action="store_true",
                        help="re-envelope the A-B around the goodput "
                        "ledger (make bench-goodput -> BENCH_r10.json)")
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--depth", type=int, default=2)
    parser.add_argument("--input-delay-ms", type=float, default=25.0)
    args = parser.parse_args()
    runner = run_goodput if args.goodput else run_train
    out = runner(steps=args.steps, batch=args.batch, seq=args.seq,
                 depth=args.depth,
                 input_delay_s=args.input_delay_ms / 1000.0)
    print(json.dumps(out))


def _elastic_main():
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--elastic", action="store_true")
    parser.add_argument("--steps", type=int, default=16)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--fail-at", type=int, default=6)
    parser.add_argument("--rejoin-at", type=int, default=11)
    parser.add_argument("--checkpoint-every", type=int, default=2)
    parser.add_argument("--downtime-s", type=float, default=5.0,
                        help="eviction->replacement gap charged to the "
                        "full-resubmit arm (the service's default "
                        "first-retry backoff)")
    args = parser.parse_args()
    out = run_elastic(steps=args.steps, batch=args.batch, seq=args.seq,
                      fail_at=args.fail_at, rejoin_at=args.rejoin_at,
                      checkpoint_every=args.checkpoint_every,
                      downtime_s=args.downtime_s)
    print(json.dumps(out))


def main():
    # chunked CE keeps the loss memory flat, so larger batches fit; walk
    # down until one fits on the chip. save_attn remat (keep attention
    # outputs, recompute only the MLP) trades a little memory for less
    # backward recompute.
    attempts = [
        ("1b", 32, 2048, "save_attn"), ("1b", 32, 2048, "nothing"),
        ("1b", 16, 2048, "save_attn"), ("1b", 16, 2048, "nothing"),
        ("1b", 8, 2048, "save_attn"), ("1b", 8, 2048, "nothing"),
        ("1b", 4, 2048, "nothing")]
    here = os.path.dirname(os.path.abspath(__file__))
    result = None
    last_error = None
    for scale, batch, seq, policy in attempts:
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one", scale,
                 str(batch), str(seq), policy],
                capture_output=True, text=True, timeout=900, cwd=here)
        except subprocess.TimeoutExpired:
            last_error = f"{scale}/b{batch}: timeout"
            print(f"bench config {scale}/b{batch}/s{seq}/{policy} timed out",
                  file=sys.stderr)
            continue
        if proc.returncode == NO_TPU_RC:
            # no chip: nothing smaller to walk down to, nothing to print
            print(proc.stderr[-400:], file=sys.stderr)
            raise SystemExit(NO_TPU_RC)
        marker = [ln for ln in proc.stdout.splitlines()
                  if ln.startswith("@@RESULT@@")]
        if proc.returncode == 0 and marker:
            result = json.loads(marker[-1][len("@@RESULT@@"):])
            result["model"] = scale
            result["remat_policy"] = policy
            break
        last_error = (proc.stderr or proc.stdout)[-400:]
        print(f"bench config {scale}/b{batch}/s{seq}/{policy} failed "
              f"(rc={proc.returncode}): {last_error}", file=sys.stderr)
    if result is None:
        print(f"bench: every config failed on the chip: {last_error}",
              file=sys.stderr)
        raise SystemExit(1)

    out = {
        "metric": "llama_lora_train_mfu",
        "value": round(result["mfu"], 4),
        "unit": "mfu_fraction",
        "vs_baseline": round(result["mfu"] / 0.35, 4),
        "detail": {k: (round(v, 2) if isinstance(v, float) else v)
                   for k, v in result.items()},
    }
    print(json.dumps(out))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--one":
        _subprocess_main()
    elif "--elastic" in sys.argv:
        _elastic_main()
    elif "--train" in sys.argv:
        _train_main()
    else:
        main()
