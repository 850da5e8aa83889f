"""Readers over what the program records about its own scheduler loop: the
tick log (``mlrun_tpu/obs/ticklog.py``: one record per scheduler iteration
that did work, on ``time.perf_counter()``, the harness's clock) and the
request spans of ``obs/tracing.py``.

As in ``readers.py`` a reader takes the run's context and returns its
number, or ``None`` where it finds nothing to read: a program that keeps no
tick log (the parent of the PR that added it) reports none of these.

The records are those whose ``[t0, t1]`` lies inside ``ctx["traced"]``, or
inside the whole window (first ``sent`` to last ``done``) where the run
names no interval. A test may hand them in as ``ctx["ticks"]`` (dicts with
the record's fields) and the spans as ``ctx["spans"]`` (dicts with
``trace_id`` and ``duration_s``).
"""

from __future__ import annotations

from statistics import median

from . import costs, trace_reduce


def _interval(ctx):
    traced = ctx.get("traced")
    if traced:
        return float(traced[0]), float(traced[1])
    finished = ctx.get("finished") or []
    if not finished:
        return None
    return (min(r["sent"] for r in finished),
            max(r["done"] for r in finished))


def _ticks(ctx) -> list:
    span = _interval(ctx)
    if span is None:
        return []
    given = ctx.get("ticks")
    if given is not None:
        return [r for r in given
                if r["t0"] >= span[0] and r["t1"] <= span[1]]
    try:
        from mlrun_tpu.obs import ticklog
    except ImportError:
        return []
    # one engine serves a cell; were there several, the one that worked
    # most in the interval is the one the cell measures
    found = [log.records(*span) for log in ticklog.tick_logs().values()]
    return max(found, key=len, default=[])


def tick_share(ctx, part: str, scale: float = 100.0):
    """Share of the scheduler loop's seconds, over the interval's
    iterations. ``host``: not blocked on a device result (the decode
    tick's fetch, a prefill's first-token fetch). ``admit``: expiry,
    control and admission, prefill and insert included."""
    ticks = _ticks(ctx)
    total = sum(r["t1"] - r["t0"] for r in ticks)
    if total <= 0:
        return None
    if part == "host":
        named = sum(r["t1"] - r["t0"] - (r["t_fetched"] - r["t_dispatched"])
                    - r["admit_wait_s"] for r in ticks)
    elif part == "admit":
        named = sum(r["t_admit"] - r["t0"] for r in ticks)
    else:
        raise ValueError(f"unknown part {part!r}")
    return scale * named / total


def tick_rows(ctx):
    """Mean live rows over the interval's decode ticks."""
    rows = [r["rows"] for r in _ticks(ctx) if r["rows"] > 0]
    return None if not rows else sum(rows) / len(rows)


def kernel_roofline_ticks(ctx, pattern: str, tolerance: float = 0.2):
    """As ``readers.kernel_roofline`` for ``paged_decode``, with every
    decode tick costed at its own live rows and the tokens they attend,
    once per layer, and the sum scaled to the trace's own count of calls
    (the profile and the log start and stop a tick apart). ``None`` where
    ticks x layers and that count differ by more than ``tolerance``: the
    log and the trace then do not describe the same interval."""
    trace = ctx.get("trace")
    if not trace or not ctx.get("peak"):
        return None
    seconds, count = trace_reduce.matching(trace, pattern)
    decoding = [r for r in _ticks(ctx) if r["rows"] > 0]
    if count == 0 or seconds <= 0 or not decoding:
        return None
    fields, peak = ctx["fields"], ctx["peak"]
    expected = len(decoding) * fields["n_layers"]
    if abs(count - expected) > tolerance * expected:
        return None
    call = ctx.get("costs", costs).paged_decode_call
    least = fields["n_layers"] * sum(
        costs.roofline_seconds(call(
            fields, r["rows"], r["ctx_tokens"] / r["rows"]), peak)[0]
        for r in decoding)
    return 100.0 * least * (count / expected) / seconds


def span_self(ctx, span: str = "server.run", least: int = 100,
              scale: float = 1e3):
    """Median over the window's requests of the named request span's
    duration minus the engine ledger's ``wall_s`` of the same request,
    joined on the trace id: what the entry points spend around the engine.
    Over the requests whose span the tracer's ring still holds; ``None``
    under ``least`` of them."""
    spans = ctx.get("spans")
    if spans is None:
        try:
            from mlrun_tpu.obs import get_tracer
        except ImportError:
            return None
        spans = [s.to_dict() for s in get_tracer().spans(name=span)]
    lasted = {s["trace_id"]: s["duration_s"] for s in spans
              if s.get("duration_s") is not None}
    gaps = []
    for record in ctx.get("finished") or []:
        timing = record.get("timing") or {}
        if timing.get("trace_id") in lasted:
            gaps.append(lasted[timing["trace_id"]] - timing["wall_s"])
    return None if len(gaps) < least else median(gaps) * scale
