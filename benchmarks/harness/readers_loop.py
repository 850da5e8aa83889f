"""Readers over the scheduler loop's own account of its wall time: the tick
log's records since they close over it (``mlrun_tpu/obs/ticklog.py``:
``gap_s`` and its idle part, the two named parts of ``admit_wait_s``,
``dry_s``, ``after_prefill_s``, ``admissions``, ``cpu_s`` over
``cpu_span_s``, ``gc_s``).

Unlike ``readers_ticks.py`` these read **the whole window**, first ``sent``
to last ``done`` of ``ctx["finished"]``, whatever ``ctx["traced"]`` holds:
the records are kept with the profiler off, and a stall or a wave of
admissions falls outside the traced seconds nine times in ten. A record
counts where it and the gap before it lie inside the window (the first
record's gap reaches back into the idle time before the window opened). A
test may hand the records in as ``ctx["ticks"]``.

As every reader, each returns its number, or ``None`` where it finds
nothing to read: the records of a program that does not keep these fields
(the parent of the PR that added them) give ``None`` and never raise.
"""

from __future__ import annotations

import json
import sys

from . import readers_ticks


def _window(ctx):
    """(records of the whole window that close over wall time, the window's
    seconds), or (None, None)."""
    whole = dict(ctx, traced=None)
    span = readers_ticks._interval(whole)
    ticks = readers_ticks._ticks(whole)
    if span is None or not ticks or "gap_s" not in ticks[0]:
        return None, None
    ticks = [r for r in ticks if r["t0"] - r["gap_s"] >= span[0]]
    return (ticks, span[1] - span[0]) if ticks else (None, None)


def _span(record) -> float:
    """The iteration and the gap before it, idle polls apart."""
    return record["gap_s"] - record.get("idle_s", 0.0) \
        + record["t1"] - record["t0"]


def _device_wait(record) -> float:
    return record["t_fetched"] - record["t_dispatched"] \
        + record["admit_wait_s"]


def device_dry_share(ctx, scale: float = 100.0):
    """Seconds in which the host knew the device had nothing queued, of the
    window's seconds: a lower bound of the device's idle over the whole
    window, the part the host alone causes."""
    ticks, seconds = _window(ctx)
    if ticks is None or seconds <= 0:
        return None
    return scale * sum(r["dry_s"] for r in ticks) / seconds


def after_prefill_ms(ctx, scale: float = 1e3):
    """The host's path from a prefill's first token to the next prefill or
    decode enqueue, an admission: what keeping the first token on the
    device would take off the loop. 0 where no admission fetches a token
    (a block model)."""
    ticks, _ = _window(ctx)
    admissions = sum(r["admissions"] for r in ticks or ())
    if not admissions:
        return None
    return scale * sum(r["after_prefill_s"] for r in ticks) / admissions


def loop_share(ctx, part: str, scale: float = 100.0):
    """``admit_own``: expiry, control and admission without the wait for
    the tick in flight, of the loop's seconds (``tick_admit_share`` less
    what the lookahead put into it). ``cpu``: the seconds the scheduler
    thread was executing, of the wall seconds that the readings of its
    clock cover (iterations and the gaps between them, idle polls apart)."""
    ticks, _ = _window(ctx)
    if part == "admit_own":
        named = sum(r["t_admit"] - r["t0"] - r["inflight_wait_s"]
                    for r in ticks or ())
        total = sum(r["t1"] - r["t0"] for r in ticks or ())
    elif part == "cpu":
        named = sum(r["cpu_s"] for r in ticks or ())
        total = sum(r.get("cpu_span_s", 0.0) for r in ticks or ())
    else:
        raise ValueError(f"unknown part {part!r}")
    return scale * named / total if total > 0 else None


def iteration_max(ctx, part: str = "span", scale: float = 1e3):
    """The window's longest iteration with the gap before it, idle polls
    apart (``span``), or that iteration's seconds not blocked on the device
    (``host``): a stall of the host against one inside a device wait.
    Reading ``span`` also says on standard error which record it was and
    where it went."""
    ticks, _ = _window(ctx)
    if ticks is None:
        return None
    worst = max(ticks, key=_span)
    if part == "host":
        return scale * (_span(worst) - _device_wait(worst))
    if part != "span":
        raise ValueError(f"unknown part {part!r}")
    print("[bench] longest iteration " + json.dumps(_parts(worst)),
          file=sys.stderr, flush=True)
    return scale * _span(worst)


def _parts(record) -> dict:
    """The record by cause, as the program's own stall record has it."""
    from mlrun_tpu.obs import ticklog

    parts = ticklog.stall_parts(record)
    return {key: round(value, 6) if isinstance(value, float) else value
            for key, value in parts.items()} \
        | {key: record[key] for key in ("kind", "rows", "prefill_tokens",
                                        "admissions", "lookahead")}
