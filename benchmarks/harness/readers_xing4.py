"""Readers of what the ``xing4`` family adds to the program: the latent
attention's two kernels (``mla_paged_decode`` a layer a plain tick,
``mla_flash`` a block a layer a prefill chunk) and the routed experts'
grouped products under plain ticks and prefill dispatches, each costed from
the tick log's own counts (``mlrun_tpu/obs/ticklog.py``: ``rows``,
``ctx_tokens``, ``prefill_tokens``, ``prefill_ctx_tokens``,
``expert_pairs``, ``experts_touched``). As in ``readers.py`` a reader
returns its number, or ``None`` where it finds nothing to read: a program
without these kernels or counters (the parent of the PR that added them)
reports none of these."""

from __future__ import annotations

import math

from . import costs, trace_reduce
from .readers_ticks import _ticks


def _traced(ctx, pattern: str):
    """(seconds, calls) of the device operations that match, or None."""
    trace = ctx.get("trace")
    if not trace or not ctx.get("peak"):
        return None
    seconds, count = trace_reduce.matching(trace, pattern)
    return None if count == 0 or seconds <= 0 else (seconds, count)


def _share(least: float, expected: float, traced, tolerance: float):
    """``least`` seconds for ``expected`` calls, scaled to the trace's own
    count of calls, over the time they took, in percent; ``None`` where the
    log's count and the trace's differ by more than ``tolerance`` (they then
    do not describe the same interval)."""
    seconds, count = traced
    if expected <= 0 or abs(count - expected) > tolerance * expected:
        return None
    return 100.0 * least * (count / expected) / seconds


def mla_decode_roofline(ctx, pattern: str, tolerance: float = 0.2):
    """The absorbed decode kernel: every plain tick of the interval costed
    by ``costs.mla_decode_call`` at its own live rows and the tokens they
    attend, once a layer."""
    traced = _traced(ctx, pattern)
    ticks = [r for r in _ticks(ctx)
             if r["rows"] > 0 and r.get("kind") == "plain"]
    if traced is None or not ticks:
        return None
    fields, peak = ctx["fields"], ctx["peak"]
    layers = fields["n_layers"]
    least = layers * sum(costs.roofline_seconds(
        ctx["costs"].mla_decode_call(fields, r["rows"], r["ctx_tokens"]),
        peak)[0] for r in ticks)
    return _share(least, len(ticks) * layers, traced, tolerance)


def mla_prefill_roofline(ctx, pattern: str, tolerance: float = 0.2):
    """The expanded prefill kernel: every iteration that prefilled costed
    by ``costs.mla_prefill_call`` at its own ``prefill_tokens`` and the
    pairs they attended (``prefill_ctx_tokens``), once a layer. The program
    calls the kernel once a block of the cache up to the chunk's end (a
    block is the cell's ``prefill_chunk``): that is the count the trace is
    held to; the blocks before the chunk's own are the cached prefix,
    counted with it."""
    traced = _traced(ctx, pattern)
    chunks = [r for r in _ticks(ctx) if r.get("prefill_ctx_tokens", 0) > 0]
    if traced is None or not chunks:
        return None
    fields, peak = ctx["fields"], ctx["peak"]
    layers = fields["n_layers"]
    block = int((ctx["cell"].get("server") or {}).get("prefill_chunk")
                or 1024)
    least, calls = 0.0, 0
    for r in chunks:
        tokens, pairs = r["prefill_tokens"], r["prefill_ctx_tokens"]
        least += layers * costs.roofline_seconds(
            ctx["costs"].mla_prefill_call(fields, tokens, pairs), peak)[0]
        end = (pairs - tokens * (tokens + 1) / 2.0) / tokens + block
        calls += layers * math.ceil(end / block)
    return _share(least, calls, traced, tolerance)


def experts_roofline(ctx, pattern: str, products: int = 3,
                     tolerance: float = 0.2):
    """The routed experts' grouped products: every iteration's dispatches
    (a plain tick, a prefill chunk) costed by ``costs.moe_experts_call`` at
    the iteration's own ``expert_pairs`` and ``experts_touched`` (the
    tick's and the chunk's, summed: the cost is linear in both), against
    ``products`` calls an expert layer a dispatch."""
    traced = _traced(ctx, pattern)
    records = [r for r in _ticks(ctx) if r.get("expert_pairs", 0) > 0]
    if traced is None or not records:
        return None
    fields, peak = ctx["fields"], ctx["peak"]
    moe_layers = fields["n_layers"] - fields["first_k_dense"]
    dispatches = sum((r["rows"] > 0) + (r["prefill_tokens"] > 0)
                     for r in records)
    least = sum(costs.roofline_seconds(ctx["costs"].moe_experts_call(
        fields, r["expert_pairs"], r["experts_touched"]), peak)[0]
        for r in records)
    return _share(least, products * moe_layers * dispatches, traced,
                  tolerance)
