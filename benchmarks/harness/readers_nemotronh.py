"""Readers of what the ``nemotronh`` family adds to the program: the
state-space layers' two kernels (``ssm_decode`` a layer a plain tick,
``ssd_prefill`` a layer a prefill dispatch) and the routed experts' grouped
products under plain ticks and prefill dispatches, each costed from the
tick log's own counts (``mlrun_tpu/obs/ticklog.py``: ``state_rows``,
``state_tokens``, ``prefill_dispatches``, ``expert_pairs``,
``experts_touched``). As in ``readers.py`` a reader returns its number, or
``None`` where it finds nothing to read: a program without these kernels or
counters (the parent of the PR that added them) reports none of these."""

from __future__ import annotations

from . import costs
from .readers_ticks import _ticks
from .readers_xing4 import _share, _traced


def ssm_decode_roofline(ctx, pattern: str, tolerance: float = 0.2):
    """The one-token state update: every plain tick of the interval costed
    by ``costs.ssm_decode_call`` at its own ``state_rows``, once a
    state-space layer."""
    traced = _traced(ctx, pattern)
    ticks = [r for r in _ticks(ctx) if r.get("state_rows", 0) > 0]
    if traced is None or not ticks:
        return None
    fields, peak, family = ctx["fields"], ctx["peak"], ctx["costs"]
    layers = family.kind_layers(fields, "ssm")
    least = layers * sum(costs.roofline_seconds(
        family.ssm_decode_call(fields, r["state_rows"]), peak)[0]
        for r in ticks)
    return _share(least, len(ticks) * layers, traced, tolerance)


def ssd_prefill_roofline(ctx, pattern: str, tolerance: float = 0.2):
    """The chunked scan: every iteration that prefilled costed by
    ``costs.ssd_prefill_call`` at its own ``state_tokens`` (the real
    tokens, a bucket's padding left out) in its ``prefill_dispatches``
    calls, once a state-space layer."""
    traced = _traced(ctx, pattern)
    records = [r for r in _ticks(ctx) if r.get("state_tokens", 0) > 0]
    if traced is None or not records:
        return None
    fields, peak, family = ctx["fields"], ctx["peak"], ctx["costs"]
    layers = family.kind_layers(fields, "ssm")
    calls = sum(r.get("prefill_dispatches", 1) for r in records)
    least = layers * sum(costs.roofline_seconds(family.ssd_prefill_call(
        fields, r["state_tokens"], r.get("prefill_dispatches", 1)),
        peak)[0] for r in records)
    return _share(least, calls * layers, traced, tolerance)


def experts_roofline(ctx, pattern: str, products: int = 2,
                     tolerance: float = 0.2):
    """The routed experts' grouped products: every iteration's dispatches
    (a plain tick, its prefill dispatches) costed by
    ``costs.moe_experts_call`` at the iteration's own ``expert_pairs`` and
    ``experts_touched`` (summed: the cost is linear in both), against
    ``products`` calls an expert layer a dispatch."""
    traced = _traced(ctx, pattern)
    records = [r for r in _ticks(ctx) if r.get("expert_pairs", 0) > 0]
    if traced is None or not records:
        return None
    fields, peak, family = ctx["fields"], ctx["peak"], ctx["costs"]
    dispatches = sum((r["rows"] > 0) + r.get(
        "prefill_dispatches", int(r["prefill_tokens"] > 0))
        for r in records)
    least = sum(costs.roofline_seconds(family.moe_experts_call(
        fields, r["expert_pairs"], r["experts_touched"]), peak)[0]
        for r in records)
    return _share(least, products * family.kind_layers(fields, "mlp")
                  * dispatches, traced, tolerance)
