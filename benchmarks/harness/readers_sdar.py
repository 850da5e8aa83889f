"""Readers of what the ``sdar`` family adds to the program: the ``denoise``
ticks of the tick log (``mlrun_tpu/obs/ticklog.py``: ``tokens_out``,
``commit_rows``, ``expert_pairs``, ``experts_touched``) and the device
operations of the served expert layer and of the prefix kernel under a
denoising pass. As in ``readers.py`` a reader returns its number, or
``None`` where it finds nothing to read: a program with no such tick (the
parent of the PR that added it) reports none of these."""

from __future__ import annotations

from . import costs, trace_reduce
from .readers_ticks import _ticks


def _denoise(ctx) -> list:
    return [r for r in _ticks(ctx) if r.get("kind") == "denoise"
            and r["rows"] > 0]


def tokens_per_row_pass(ctx):
    """Positions unmasked over row-passes, over the interval's ``denoise``
    ticks: ``sum(tokens_out) / sum(rows)``. A block of B at S steps costs S
    + 1 passes for B tokens: B / (S + 1)."""
    ticks = _denoise(ctx)
    rows = sum(r["rows"] for r in ticks)
    return None if not rows else sum(r["tokens_out"] for r in ticks) / rows


def experts_roofline(ctx, pattern: str, products: int = 3,
                     tolerance: float = 0.2):
    """The least time the chip could take for the expert products that the
    trace holds, over the time they took, in percent. Each ``denoise`` tick
    is costed by ``costs.moe_experts_call`` at its own ``expert_pairs`` and
    ``experts_touched``; each iteration that prefilled at its
    ``prefill_tokens`` (top_k pairs a token a layer, every expert touched
    that the pairs can reach). The sum is scaled to the trace's count of
    product calls (``products`` a layer a dispatch); ``None`` where the
    log's dispatches and that count differ by more than ``tolerance``."""
    trace = ctx.get("trace")
    if not trace or not ctx.get("peak"):
        return None
    seconds, count = trace_reduce.matching(trace, pattern)
    passes = _denoise(ctx)
    if count == 0 or seconds <= 0 or not passes:
        return None
    fields, peak, cost = ctx["fields"], ctx["peak"], ctx["costs"]
    layers, top_k = fields["n_layers"], fields["top_k"]
    calls = [cost.moe_experts_call(fields, r["expert_pairs"],
                                   r["experts_touched"]) for r in passes]
    for record in _ticks(ctx):
        tokens = record["prefill_tokens"]
        if tokens > 0:
            calls.append(cost.moe_experts_call(
                fields, tokens * top_k * layers,
                layers * min(fields["n_experts"], tokens * top_k)))
    expected = products * layers * len(calls)
    if abs(count - expected) > tolerance * expected:
        return None
    # the three products of a layer are costed as one: the sum of their
    # least times is the least time of the sum
    least = sum(costs.roofline_seconds(call, peak)[0] for call in calls)
    return 100.0 * least * (count / expected) / seconds


def chunk_roofline_ticks(ctx, pattern: str, tolerance: float = 0.2):
    """The prefix part of a pass's attention (the ``_paged_chunk_call``
    kernel): every ``denoise`` tick costed by ``costs.paged_chunk_call`` at
    its own live rows and the committed prefix they attend (``ctx_tokens``
    less the block's own ``positions``), once per layer, scaled to the
    trace's count of calls; ``None`` where ticks x layers and that count
    differ by more than ``tolerance``."""
    trace = ctx.get("trace")
    if not trace or not ctx.get("peak"):
        return None
    seconds, count = trace_reduce.matching(trace, pattern)
    passes = _denoise(ctx)
    if count == 0 or seconds <= 0 or not passes:
        return None
    fields, peak = ctx["fields"], ctx["peak"]
    expected = len(passes) * fields["n_layers"]
    if abs(count - expected) > tolerance * expected:
        return None
    least = fields["n_layers"] * sum(
        costs.roofline_seconds(ctx["costs"].paged_chunk_call(
            fields, fields["block_length"],
            r["ctx_tokens"] - r["positions"], r["rows"]), peak)[0]
        for r in passes)
    return 100.0 * least * (count / expected) / seconds
