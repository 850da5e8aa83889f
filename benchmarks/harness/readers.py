"""The per-layer metrics' readers. A metric's file names one of these
functions under ``reader`` and gives its arguments under ``args``; a later
PR adds a metric over a reader that is here as a data file alone, and a new
reader as a module of its own beside this one (``readers_<x>.py``, named in
the metric's ``module``).

A reader takes the run's context and returns its number, or ``None`` where
it finds nothing to read: the harness then leaves the metric out of the
line. It never returns 0 for a share of a roofline or of a peak.

The context: ``cell``, ``fields``, ``costs`` (the cost module of the
configuration's family; ``costs.py`` where the context names none),
``chips``, ``peak`` (the chip's row of peaks.json; None in a rehearsal),
``window_s``, ``trace`` (the reduced profile of the traced part, or None),
``traced`` (that part's start and stop on the host's clock, where known),
and for a serve cell ``finished`` (the window's requests, each with
``sent``, ``done``, ``prompt``, ``tokens``, ``timing``) and
``engine_stats``; for a train cell ``tokens_per_s``, ``batch_size`` and
``seq_len``.
"""

from __future__ import annotations

import importlib
from statistics import median

from . import costs, trace_reduce
from .common import percentile


def _costs(ctx):
    """The cost functions of the cell's family."""
    return ctx.get("costs", costs)


def engine_stat(ctx, key: str, scale: float = 1.0):
    """A number the engine keeps about itself, as its ``stats`` had it when
    the window closed."""
    value = (ctx.get("engine_stats") or {}).get(key)
    return None if value is None else float(value) * scale


def request_overhead(ctx, scale: float = 1e3):
    """Median over the requests of the client's wall time minus the time
    the engine's ledger saw: what the graph, the router and the model
    server add around the engine."""
    finished = ctx.get("finished") or []
    gaps = [(r["done"] - r["sent"]) - r["timing"]["wall_s"]
            for r in finished if r.get("timing")]
    return None if not gaps else median(gaps) * scale


def phase_share(ctx, phases: list, scale: float = 100.0):
    """Share of all ledger seconds of the window's requests that lie in
    the named phases."""
    finished = ctx.get("finished") or []
    total = sum(r["timing"]["wall_s"] for r in finished if r.get("timing"))
    if total <= 0:
        return None
    named = sum(r["timing"]["phases"].get(p, 0.0) for r in finished
                if r.get("timing") for p in phases)
    return scale * named / total


def phase_percentile(ctx, exclude: list, q: float = 0.95,
                     scale: float = 1e3):
    """A percentile (nearest rank) over the window's requests of the
    ledger seconds outside the ``exclude`` phases: with the two decode
    phases excluded, the time before decoding starts."""
    finished = [r for r in ctx.get("finished") or [] if r.get("timing")]
    if not finished:
        return None
    return scale * percentile(
        [sum(v for k, v in r["timing"]["phases"].items() if k not in exclude)
         for r in finished], q)


def window_mfu(ctx, cost: str):
    """Required operations of all the window's work over the window's
    seconds and the chip's peak, in percent."""
    if not ctx.get("peak"):
        return None
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    if cost == "serve_requests":
        finished = ctx.get("finished") or []
        if not finished or ctx["window_s"] <= 0:
            return None
        flops = sum(_costs(ctx).serve_request_flops(
            ctx["fields"], len(r["prompt"]), len(r["tokens"]))
            for r in finished)
        return 100.0 * flops / ctx["window_s"] / peak
    if cost == "lora_train":
        rate = ctx.get("tokens_per_s")
        if not rate:
            return None
        return 100.0 * rate * _costs(ctx).lora_train_flops_per_token(
            ctx["fields"], ctx["seq_len"]) / peak
    raise ValueError(f"unknown cost {cost!r}")


def device_idle(ctx):
    """1 - busy / window of the traced part, in percent."""
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def _decoding_rows(finished: list, traced, window_s: float) -> float:
    """Mean number of rows in a decode phase: each request's ledger share
    of decoding, over the part of it that overlaps the traced interval
    ``(start, stop)`` on the host's clock, or over the whole window where
    the context names no interval."""
    seconds = 0.0
    for r in finished:
        phases, wall = r["timing"]["phases"], r["timing"]["wall_s"]
        decoding = phases.get("decode_active", 0.0) \
            + phases.get("decode_stall", 0.0)
        if traced is None:
            seconds += decoding
        elif wall > 0:
            overlap = min(r["done"], traced[1]) - max(r["sent"], traced[0])
            seconds += max(0.0, overlap) * decoding / wall
    span = window_s if traced is None else traced[1] - traced[0]
    return seconds / span if span > 0 else 0.0


def kernel_roofline(ctx, pattern: str, cost: str, kernels: list | None = None):
    """The least time the chip could take for the calls of the kernel that
    the trace holds, over the time they took, in percent.

    ``paged_decode``: each call is one layer of one tick; its rows are the
    mean over the traced interval of the rows in a decode phase by the
    ledger, their context the window's average (prompt + half the
    output). ``flash``: each entry
    of ``kernels`` names one kernel by ``pattern`` with the ``products``
    and ``tensors`` of ``costs.flash_call``."""
    trace = ctx.get("trace")
    if not trace or not ctx.get("peak"):
        return None
    seconds, count = trace_reduce.matching(trace, pattern)
    if count == 0 or seconds <= 0:
        return None
    fields, peak = ctx["fields"], ctx["peak"]
    if cost == "paged_decode":
        finished = [r for r in ctx.get("finished") or [] if r.get("timing")]
        if not finished or ctx["window_s"] <= 0:
            return None
        rows = _decoding_rows(finished, ctx.get("traced"), ctx["window_s"])
        context = sum(len(r["prompt"]) + len(r["tokens"]) / 2.0
                      for r in finished) / len(finished)
        call = _costs(ctx).paged_decode_call(fields, rows, context)
        least = count * costs.roofline_seconds(call, peak)[0]
    elif cost == "flash":
        least, seconds = 0.0, 0.0
        for kernel in kernels:
            spent, calls = trace_reduce.matching(trace, kernel["pattern"])
            call = _costs(ctx).flash_call(
                fields, ctx["batch_size"], ctx["seq_len"],
                kernel["products"], kernel["tensors"])
            least += calls * costs.roofline_seconds(call, peak)[0]
            seconds += spent
        if seconds <= 0:
            return None
    else:
        raise ValueError(f"unknown cost {cost!r}")
    return 100.0 * least / seconds


def op_share(ctx, pattern: str):
    """Share of the device's busy time spent in the operations whose name
    matches, in percent."""
    trace = ctx.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    seconds, count = trace_reduce.matching(trace, pattern)
    return None if count == 0 else 100.0 * seconds / trace["busy_s"]


def read_all(layer_metrics: list, ctx: dict) -> dict:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for metric in layer_metrics:
        module = importlib.import_module(
            f"benchmarks.harness.{metric.get('module', 'readers')}")
        value = getattr(module, metric["reader"])(
            ctx, **metric.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out
