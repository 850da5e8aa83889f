"""Profiling/tracing utilities — the TPU observability layer.

Reference has no distributed tracer (SURVEY.md §5.1); on TPU the equivalents
are XLA device traces (jax.profiler → TensorBoard) plus per-step wall-time
tracking. ``profile_run`` captures a device trace into the run's artifact
path and registers it; ``arm_profile``/``tick`` let a live trainer or engine
be profiled for the next N steps/seconds WITHOUT a restart (the ``POST
/debug/profile`` endpoints arm it; the hot loops tick it —
docs/observability.md "Flight recorder & debug endpoints"); ``annotate``
writes the program's own spans (the scheduler's ``mlt.sched.*``) into that
trace and ``named`` gives a jitted step the module name it is found by.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Optional

from .helpers import logger, now_iso


def _resolve_trace_dir(context, key: str, output_dir: str = "") -> str:
    return output_dir or os.path.join(
        (context.artifact_path if context is not None else "/tmp"),
        "traces", key)


def _register_trace(context, key: str, output_dir: str, elapsed: float):
    """Best-effort trace finalization: log line, capture wall time on the
    run's metrics (not just the log), artifact registration. Never
    raises — this runs on unwind paths where the block's own exception
    must win."""
    logger.info("xla trace captured", dir=output_dir,
                wall_s=round(elapsed, 3))
    if context is None:
        return
    try:
        if hasattr(context, "log_metrics"):
            context.log_metrics({"xla_trace_wall_s": round(elapsed, 6)})
        elif hasattr(context, "log_result"):
            context.log_result("xla_trace_wall_s", round(elapsed, 6))
    except Exception as exc:  # noqa: BLE001
        logger.warning("failed to record trace wall time", error=str(exc))
    try:
        context.log_artifact(
            key, target_path=output_dir, upload=False,
            labels={"viewer": "tensorboard"})
    except Exception as exc:  # noqa: BLE001
        logger.warning("failed to register trace artifact",
                       error=str(exc))


@contextlib.contextmanager
def profile_run(context=None, key: str = "xla-trace",
                output_dir: str = ""):
    """Capture a jax/XLA profiler trace around a code block and register it
    as a run artifact (TensorBoard-compatible). A ``stop_trace`` failure
    on the way out never masks an exception raised by the profiled block;
    the capture wall time lands on the run's metrics
    (``xla_trace_wall_s``), not just the log line."""
    import jax

    output_dir = _resolve_trace_dir(context, key, output_dir)
    os.makedirs(output_dir, exist_ok=True)
    jax.profiler.start_trace(output_dir)
    started = time.perf_counter()
    try:
        yield output_dir
    finally:
        elapsed = time.perf_counter() - started
        try:
            jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001 - a failing stop must not
            # mask the profiled block's own exception (the original bug:
            # a bare stop_trace() here turned any block error into the
            # profiler's)
            logger.warning("profiler stop_trace failed", error=str(exc))
        _register_trace(context, key, output_dir, elapsed)


# -- on-demand profiling (POST /debug/profile) -------------------------------
# One capture at a time, process-wide: arm_profile() stages a request;
# the FIRST instrumented hot loop (Trainer.fit step, engine scheduler
# tick) to call tick() claims it, starts the trace, and stops it after
# the requested step count or wall seconds. The dark-path cost in the
# hot loops is one module-global None check.
_profile_lock = threading.Lock()
_armed: Optional[dict] = None
_active: Optional[dict] = None
_last_profile: Optional[dict] = None

# a capture whose claiming loop stopped ticking (fit returned, engine
# stopped) would otherwise hold jax.profiler open forever — ANY other
# source's tick past this silence rescues it by forcing the stop
ORPHAN_TICK_TIMEOUT_S = 60.0


def arm_profile(steps: int = 0, seconds: float = 0.0,
                output_dir: str = "", key: str = "xla-trace") -> dict:
    """Arm a device-trace capture for the next ticking hot loop. At
    least one bound is required (``steps`` of the claiming loop, or wall
    ``seconds``); with both, whichever hits first stops the trace.
    Re-arming replaces a pending (unclaimed) request; an ACTIVE capture
    is never interrupted — callers get its status instead."""
    global _armed

    steps = int(steps)
    seconds = float(seconds)
    if steps <= 0 and seconds <= 0:
        raise ValueError("arm_profile needs steps > 0 and/or seconds > 0")
    spec = {"steps": steps, "seconds": seconds,
            "output_dir": str(output_dir or ""), "key": str(key),
            "armed_at": now_iso()}
    with _profile_lock:
        if _active is not None:
            return {"armed": False, "active": True,
                    "capture": dict(_active["public"])}
        _armed = spec
    try:
        from ..obs import flight_record

        flight_record("profile.armed", steps=steps, seconds=seconds,
                      key=key)
    except Exception:  # noqa: BLE001 - telemetry only
        pass
    return {"armed": True, **spec}


def disarm_profile(stop_active: bool = False) -> bool:
    """Drop a pending (unclaimed) arm request; with ``stop_active`` also
    stop a running capture (the operator remedy for a capture whose
    claiming loop went away — the HTTP disarm passes it). Returns
    whether anything was pending or stopped."""
    global _armed
    finished = None
    with _profile_lock:
        pending = _armed is not None
        _armed = None
        if stop_active and _active is not None \
                and not _active.get("stopping"):
            _active["stopping"] = True
            finished = _active
    if finished is not None:
        _finalize_capture(finished, None, reason="disarmed")
        return True
    return pending


def profile_status() -> dict:
    """Armed/active/last-capture view (GET /debug/profile)."""
    with _profile_lock:
        return {
            "armed": dict(_armed) if _armed is not None else None,
            "active": dict(_active["public"]) if _active is not None
            else None,
            "last": dict(_last_profile) if _last_profile is not None
            else None,
        }


def tick(source: str = "", context=None) -> Optional[str]:
    """Hot-loop hook: claim a pending arm request (starting the XLA
    trace) or count down the active capture this ``source`` owns.
    Returns ``"started"`` / ``"active"`` / ``"stopped"`` for the owning
    loop, ``None`` otherwise. Dark-path cost: one global check."""
    if _armed is None and _active is None:
        return None
    return _tick_slow(source, context)


def _tick_slow(source: str, context) -> Optional[str]:
    global _armed, _active, _last_profile

    finished = None
    outcome = None
    with _profile_lock:
        if _active is None:
            spec = _armed
            if spec is None:
                return None
            _armed = None
            try:
                # dir resolution INSIDE the guard: a duck-typed context
                # without artifact_path must not break the hot loop
                output_dir = _resolve_trace_dir(context, spec["key"],
                                                spec["output_dir"])
                os.makedirs(output_dir, exist_ok=True)
                import jax

                jax.profiler.start_trace(output_dir)
            except Exception as exc:  # noqa: BLE001 - a failed start must
                # not break the hot loop that happened to tick first
                logger.warning("on-demand profile start failed",
                               error=str(exc))
                _last_profile = {"error": str(exc), "at": now_iso()}
                return None
            now = time.perf_counter()
            _active = {
                "spec": spec,
                "source": source,
                "dir": output_dir,
                "started": now,
                "last_tick": now,
                "steps_left": spec["steps"],
                "deadline": (now + spec["seconds"])
                if spec["seconds"] > 0 else None,
                "public": {"source": source, "dir": output_dir,
                           "steps": spec["steps"],
                           "seconds": spec["seconds"],
                           "started_at": now_iso()},
            }
            outcome = "started"
        else:
            active = _active
            if active.get("stopping"):
                # mid-stop the capture stays claimed so a racing
                # arm+claim cannot start_trace over the closing trace
                return None
            now = time.perf_counter()
            if source != active["source"]:
                # another loop's ticks must not count down a capture of
                # the trainer (or vice versa) — UNLESS the claiming loop
                # stopped ticking entirely (fit returned, engine
                # stopped): then any live loop rescues the orphan, or
                # jax.profiler would stay open for the process lifetime
                if now - active["last_tick"] <= ORPHAN_TICK_TIMEOUT_S:
                    return None
                active["stopping"] = True
                finished = active
                outcome = "stopped"
            else:
                active["last_tick"] = now
                if active["steps_left"] > 0:
                    active["steps_left"] -= 1
                done = (active["spec"]["steps"] > 0
                        and active["steps_left"] <= 0) or (
                    active["deadline"] is not None
                    and now >= active["deadline"])
                if not done:
                    return "active"
                active["stopping"] = True
                finished = active
                outcome = "stopped"
    if outcome == "started":
        try:
            from ..obs import flight_record

            flight_record("profile.start", source=source,
                          dir=_active["dir"] if _active else "")
        except Exception:  # noqa: BLE001
            pass
        return outcome
    _finalize_capture(finished, context,
                      reason="bound" if source == finished["source"]
                      else "orphaned")
    return outcome


def _finalize_capture(finished: dict, context, reason: str):
    """Stop the trace and publish the result — OUTSIDE the profile lock
    (stop_trace does real work); the claim is released only after the
    stop completes so a racing arm+claim can never double-start."""
    global _active, _last_profile

    elapsed = time.perf_counter() - finished["started"]
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception as exc:  # noqa: BLE001
        logger.warning("on-demand profile stop failed", error=str(exc))
    _register_trace(context, finished["spec"]["key"], finished["dir"],
                    elapsed)
    result = {"dir": finished["dir"], "wall_s": round(elapsed, 6),
              "source": finished["source"], "reason": reason,
              "finished_at": now_iso()}
    with _profile_lock:
        _last_profile = result
        if _active is finished:  # release the claim only now
            _active = None
    try:
        from ..obs import flight_record

        flight_record("profile.stop", source=finished["source"],
                      dir=finished["dir"], wall_s=round(elapsed, 6),
                      reason=reason)
    except Exception:  # noqa: BLE001
        pass


# (TraceAnnotation, the tracer's current() or None): resolved on first use,
# so that a scheduler tick pays neither an import nor a config read
_annotation = None


def _resolve_annotation():
    import jax

    current = None
    try:
        from ..config import mlconf
        from ..obs import get_tracer

        if bool(mlconf.observability.xla_annotations):
            current = get_tracer().current
    except Exception:  # noqa: BLE001 - annotation is best-effort telemetry
        pass
    return jax.profiler.TraceAnnotation, current


def annotate(name: str, **metadata):
    """Named region in the profiler's trace, on the device trace's clock
    (a ``TraceAnnotation``; ``metadata`` lands in the event's stats). When
    a request span is active on this thread the trace id is stamped into
    the region name (``<name>|trace=<id16>``), so an XLA device trace in
    TensorBoard joins the span timeline of the request that dispatched the
    compute (docs/observability.md). ``mlconf.observability.
    xla_annotations`` is read once, at the first call. With no capture
    running the region costs under a microsecond."""
    global _annotation

    if _annotation is None:
        _annotation = _resolve_annotation()
    region, current = _annotation
    if current is not None:
        span = current()
        if span is not None:
            name = f"{name}|trace={span.trace_id[:16]}"
    return region(name, **metadata)


def named(name: str, fn):
    """``fn`` under a stable ``__name__``. ``jax.jit`` names the compiled
    module after the function it is given (``jit_<name>``), and a
    ``functools.partial`` has no name: its module reads ``jit__unknown`` in
    a profile. Metadata only; nothing about the program changes."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return program


def memory_sample() -> dict:
    """Numeric memory snapshot for the metrics collector
    (``mlt_device_mem_bytes{device,kind}`` + ``mlt_host_rss_bytes``,
    obs.register_memory_collector): per-device in_use/peak/limit bytes
    (None where the backend reports no stats — CPU) and host RSS bytes."""
    out: dict = {"devices": {}}
    try:
        import jax

        for device in jax.local_devices():
            stats = device.memory_stats() or {}
            out["devices"][str(device)] = {
                "in_use": stats.get("bytes_in_use"),
                "peak": stats.get("peak_bytes_in_use"),
                "limit": stats.get("bytes_limit"),
            }
    except Exception:  # noqa: BLE001 - no backend yet is a valid state
        pass
    try:
        with open("/proc/self/status") as fp:
            for line in fp:
                if line.startswith("VmRSS"):
                    out["host_rss_bytes"] = \
                        int(line.split()[1]) * 1024
                    break
    except (OSError, ValueError, IndexError):
        pass
    return out


def memory_report() -> dict:
    """Device + host memory snapshot (reference analog: the objgraph memory
    reports, server/api/utils/memory_reports.py:26 — here device-centric)."""
    out: dict = {}
    try:
        import jax

        for device in jax.local_devices():
            stats = device.memory_stats() or {}
            out[str(device)] = {
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            }
    except Exception as exc:  # noqa: BLE001
        out["error"] = str(exc)
    try:
        with open("/proc/self/status") as fp:
            for line in fp:
                if line.startswith(("VmRSS", "VmHWM")):
                    key, _, value = line.partition(":")
                    out[f"host_{key.lower()}"] = value.strip()
    except OSError:
        pass
    return out
