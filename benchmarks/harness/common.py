"""What every kind of cell needs around its window: the device it may run
on, the compile cache, the count of compilations, the profiler, the result
line."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

from .cells import ROOT

STATE_DIR = os.path.join(ROOT, ".bench_state")
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def prepare_process():
    """Before the program is imported: a home for what it writes, inside
    the checkout, and the compile cache where the program's own helper puts
    it (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``:
    a fixed path, because the path is part of the cache's key)."""
    os.makedirs(STATE_DIR, exist_ok=True)
    os.environ["MLT_HOME"] = os.path.join(STATE_DIR, "mlt_home")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from mlrun_tpu.utils import compile_cache

    cache_dir = compile_cache.configure_default()
    # the helper sets these only where it sets the directory itself
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def device_info(chips: int, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu" and not rehearse:
        raise NoChip(f"JAX found no TPU (platform {first.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX reports "
                     f"{len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    """The peak on the fullest chip, as the backend reports it. It leaves
    out a program's temporaries (PERF.md section 6, PR 22)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:max(1, chips)]]
    return int(max(peaks))


class CompileCounter:
    """Counts backend compilations and cache loads, through
    ``jax.monitoring``. ``mark()`` opens the window; ``since_mark()`` is
    what a run may not have."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _seconds, **_kw):
        if name in COMPILE_EVENTS:
            self.count += 1

    def mark(self):
        self._mark = self.count

    def since_mark(self) -> int:
        return self.count - self._mark


class Tracer:
    """The JAX profiler around a part of the window, written inside the
    checkout and removed once reduced."""

    def __init__(self, workload: str):
        self.directory = os.path.join(STATE_DIR, "trace", workload)

    def start(self):
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def path(self) -> str | None:
        for base, _dirs, files in os.walk(self.directory):
            for name in files:
                if name.endswith(".xplane.pb"):
                    return os.path.join(base, name)
        return None

    def remove(self):
        shutil.rmtree(self.directory, ignore_errors=True)


def traced_metrics(tracer, layer_metrics: list, ctx: dict, device: dict,
                   rehearse: bool) -> tuple[dict, dict | None]:
    """The ``--trace 1`` side of a run: reduce the profile, remove it, let
    every per-layer reader of the cell read the context, and put busy and
    window seconds into ``device``. Returns (metrics, breakdown)."""
    from . import costs, readers, trace_reduce

    path = tracer.path()
    summary = trace_reduce.reduce(trace_reduce.load(path),
                                  chips=ctx["chips"]) if path else None
    tracer.remove()
    # a rehearsal has no chip and so no peaks: readers of a share of a peak
    # then find nothing to read
    peak = None if rehearse else costs.peaks(device["kind"])
    metrics = readers.read_all(layer_metrics,
                               dict(ctx, peak=peak, trace=summary))
    if not summary:
        return metrics, None
    device["busy_s"] = summary["busy_s"]
    device["window_s"] = summary["window_s"]
    return metrics, {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}


def stamp(process_start: float, what: str):
    """A stage of set-up on standard error, with the seconds since the
    process started, so that a set-up that grew says where."""
    print(f"[bench +{time.perf_counter() - process_start:.1f}s] {what}",
          file=sys.stderr, flush=True)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all the values (no ring, no sampling)."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no values")
    rank = max(1, int(-(-q * len(ordered) // 1)))
    return float(ordered[min(len(ordered), rank) - 1])


def report_compared(compared: dict):
    """Each number compared beside its limit, as the last lines on stderr."""
    for name, entry in compared.items():
        print(f"compared {name}: value={entry['value']} "
              f"limit={entry['limit']} ok={entry['ok']}", file=sys.stderr)
    sys.stderr.flush()


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, compared: dict, breakdown: dict | None = None,
                notes: dict | None = None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    if notes:
        line["notes"] = notes
    line["compared"] = compared
    return json.dumps(line)
