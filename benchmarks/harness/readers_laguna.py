"""Readers of what the ``laguna`` family adds to the program: the window
layers' two kernels (``window_decode`` a window layer a plain tick,
``window_prefill`` a window layer a prefill dispatch), each costed from the
tick log's own counts (``mlrun_tpu/obs/ticklog.py``: ``window_keys``,
``window_prefill_pairs``, ``prefill_tokens``, ``prefill_dispatches``). The
routed experts read through ``readers_nemotronh.experts_roofline`` (three
products an expert here). As in ``readers.py`` a reader returns its number,
or ``None`` where it finds nothing to read: a program without these kernels
or counters (the parent of the PR that added them) reports none of
these."""

from __future__ import annotations

from . import costs
from .readers_ticks import _ticks
from .readers_xing4 import _share, _traced


def window_decode_roofline(ctx, pattern: str, tolerance: float = 0.2):
    """The windowed decode: every plain tick of the interval costed by
    ``costs.window_decode_call`` at its own rows and ``window_keys``, once
    a window layer."""
    traced = _traced(ctx, pattern)
    ticks = [r for r in _ticks(ctx) if r.get("window_keys", 0) > 0]
    if traced is None or not ticks:
        return None
    fields, peak, family = ctx["fields"], ctx["peak"], ctx["costs"]
    layers = family.kind_layers(fields, "window")
    least = layers * sum(costs.roofline_seconds(
        family.window_decode_call(fields, r["rows"], r["window_keys"]),
        peak)[0] for r in ticks)
    return _share(least, len(ticks) * layers, traced, tolerance)


def window_prefill_roofline(ctx, pattern: str, tolerance: float = 0.2):
    """The windowed chunk attention: every iteration that prefilled costed
    by ``costs.window_prefill_call`` at its own ``window_prefill_pairs``
    and ``prefill_tokens`` in its ``prefill_dispatches`` calls, once a
    window layer."""
    traced = _traced(ctx, pattern)
    records = [r for r in _ticks(ctx)
               if r.get("window_prefill_pairs", 0) > 0]
    if traced is None or not records:
        return None
    fields, peak, family = ctx["fields"], ctx["peak"], ctx["costs"]
    layers = family.kind_layers(fields, "window")
    calls = sum(r.get("prefill_dispatches", 1) for r in records)
    least = layers * sum(costs.roofline_seconds(family.window_prefill_call(
        fields, r["window_prefill_pairs"], r["prefill_tokens"],
        r.get("prefill_dispatches", 1)), peak)[0] for r in records)
    return _share(least, calls * layers, traced, tolerance)
