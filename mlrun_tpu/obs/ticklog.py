"""The serving scheduler's tick log: one record per scheduler iteration
that did work (docs/observability.md "Tick log").

The per-request ledger (``obs/reqledger.py``) sees a request's phases; this
sees the loop that produces them. An engine keeps its records in a bounded
ring and registers the ring here under its name, so that the log stays
readable after ``engine.stop()`` and after the engine is freed
(:func:`get_tick_log`, as ``get_tracer()`` and ``get_flight_recorder()``
are process-wide).

All times are ``time.perf_counter()`` seconds, the ledger's clock. The same
boundaries are written into the profiler's trace as ``mlt.sched.*`` spans by
the scheduler (``serving/llm_batch.py``); ``n`` is on both.

Stdlib only (the ``obs/metrics.py`` bottom-layer rule).
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque
from typing import Optional

# nine minutes of a 30 ms tick
TICK_LOG_RECORDS = 16384
# logs kept by name, live engines and gone ones alike: the oldest goes first
KEPT_LOGS = 16

FIELDS = ("n", "t0", "t_admit", "t_built", "t_dispatched", "t_fetched",
          "t1", "admit_wait_s", "rows", "ctx_tokens", "prefill_tokens",
          "kind", "positions", "tokens_out", "commit_rows", "expert_pairs",
          "experts_touched", "expert_load_max", "lookahead",
          "prefill_ctx_tokens", "state_rows", "state_tokens",
          "prefill_dispatches")


class TickRecord:
    """One scheduler iteration.

    ``t0`` iteration start; ``t_admit`` expiry, control and admission done;
    ``t_built`` the host inputs of the decode tick dispatched in this
    iteration built and uploaded; ``t_dispatched`` that program enqueued;
    ``t_fetched`` the tokens the iteration waited for on the host; ``t1``
    the commit loop done. ``admit_wait_s`` is the part of ``[t0, t_admit]``
    spent blocked on a device result (a prefill's first-token fetch, a
    drain). ``rows`` rows of the decode tick dispatched (0 where the
    iteration only admitted, or only read a tick), ``ctx_tokens`` the tokens
    those rows attend (prompt + generated so far, summed),
    ``prefill_tokens`` prompt tokens prefilled in this iteration in
    ``prefill_dispatches`` dispatches (a chunk each, or a whole prompt in
    its bucket: several where several requests were admitted) and
    ``prefill_ctx_tokens`` the positions they attended (each token its own
    and what precedes it in its prompt, summed), ``kind``
    ``plain``, ``spec`` or ``denoise``. ``tokens_out`` is what the iteration
    committed over all rows: one a row (plain), the tokens a speculative
    round emitted, the positions a denoising pass unmasked (none in a row
    whose pass committed its block).

    The paged engine's plain tick looks one tick ahead, and a block model's
    pass one pass ahead (docs/serving.md "The scheduler's iteration"): the
    fetch and the commit of an iteration are those of the tick or pass
    dispatched in the iteration before, and ``lookahead`` is 1 where this
    iteration's dispatch overlapped that one in flight. An iteration that
    only reads the tick in flight has no ``rows`` and its ``tokens_out``.

    A ``denoise`` tick (a pass of a block-diffusion model,
    docs/serving.md "Block-diffusion decoding") also fills, for the pass
    dispatched in the iteration, ``positions`` (row-positions in the pass:
    its rows x block length), ``commit_rows`` (rows for which the pass is
    the commit of a finished block) and, from that pass's own fetch an
    iteration later, the expert layers' counters over its rows:
    ``expert_pairs`` (token-expert pairs routed, summed over layers),
    ``experts_touched`` (experts that got at least one pair, summed over
    layers) and ``expert_load_max`` (the most pairs one expert got in one
    layer). There ``ctx_tokens`` is the positions the rows attend: each
    row's committed prefix and its block.

    An expert model served token by token fills the three expert counters
    on ``plain`` records too: those of the tick dispatched in the iteration
    (read with that tick's own fetch, an iteration later) and of its
    prefill dispatches, summed.

    A family with a recurrent state (docs/serving.md "State-space layers
    and the per-slot state") also fills ``state_rows``, the rows whose
    state the tick dispatched in the iteration advanced, and
    ``state_tokens``, the real prompt tokens its prefill dispatches
    integrated into a state (a bucket's padding left out).

    A boundary that an iteration never reaches stays at the one before it,
    so every interval is defined and non-negative.
    """

    __slots__ = FIELDS

    def __init__(self, n: int = 0, t0: float = 0.0):
        self.n = n
        self.admit_wait_s = 0.0
        self.rows = self.ctx_tokens = self.prefill_tokens = 0
        self.positions = self.tokens_out = self.commit_rows = 0
        self.expert_pairs = self.experts_touched = self.expert_load_max = 0
        self.lookahead = self.prefill_ctx_tokens = 0
        self.state_rows = self.state_tokens = self.prefill_dispatches = 0
        self.kind = "plain"
        self.t0 = t0
        self.admitted(t0)

    def admitted(self, now: float):
        """Admission is done at ``now``; the later boundaries start
        there."""
        self.t_admit = self.t_built = self.t_dispatched = \
            self.t_fetched = self.t1 = now

    @property
    def loop_s(self) -> float:
        return self.t1 - self.t0

    @property
    def device_wait_s(self) -> float:
        """Seconds of the iteration blocked on a device result."""
        return (self.t_fetched - self.t_dispatched) + self.admit_wait_s

    def as_dict(self) -> dict:
        return {name: getattr(self, name) for name in FIELDS}


class TickLog:
    """A bounded ring of :class:`TickRecord` with running sums over what
    the ring holds, so that the three scalars an engine's ``stats`` quotes
    cost nothing to read."""

    def __init__(self, size: int = TICK_LOG_RECORDS):
        self._lock = threading.Lock()
        self._ring: deque = deque()
        self._size = max(1, int(size))
        self._loop_s = self._wait_s = self._admit_s = 0.0
        self._rows = self._decode_ticks = 0

    def _account(self, record: TickRecord, sign: int):
        self._loop_s += sign * record.loop_s
        self._wait_s += sign * record.device_wait_s
        self._admit_s += sign * (record.t_admit - record.t0)
        if record.rows:
            self._rows += sign * record.rows
            self._decode_ticks += sign

    def append(self, record: TickRecord):
        with self._lock:
            if len(self._ring) >= self._size:
                self._account(self._ring.popleft(), -1)
            self._ring.append(record)
            self._account(record, 1)

    def records(self, start: Optional[float] = None,
                end: Optional[float] = None) -> list[dict]:
        """The records whose ``[t0, t1]`` lies inside ``[start, end]``,
        oldest first, as dicts."""
        with self._lock:
            snapshot = list(self._ring)
        return [r.as_dict() for r in snapshot
                if (start is None or r.t0 >= start)
                and (end is None or r.t1 <= end)]

    def summary(self) -> dict:
        """``tick_rows_mean`` (live rows of a decode tick),
        ``tick_host_share`` (loop seconds not blocked on the device, of
        all loop seconds) and ``tick_admit_share`` (admission's part of
        them), over the ring; empty while nothing is logged."""
        with self._lock:
            loop_s, wait_s, admit_s = self._loop_s, self._wait_s, \
                self._admit_s
            rows, ticks = self._rows, self._decode_ticks
        out = {}
        if ticks:
            out["tick_rows_mean"] = rows / ticks
        if loop_s > 0:
            out["tick_host_share"] = max(0.0, loop_s - wait_s) / loop_s
            out["tick_admit_share"] = admit_s / loop_s
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


_logs: "OrderedDict[str, TickLog]" = OrderedDict()
_logs_lock = threading.Lock()


def get_tick_log(name: str) -> TickLog:
    """The tick log of the engine ``name`` (its ``_obs_name``, the
    ``engine`` label of its ``/metrics`` series), made on first use."""
    with _logs_lock:
        log = _logs.get(name)
        if log is None:
            log = _logs[name] = TickLog()
            while len(_logs) > KEPT_LOGS:
                _logs.popitem(last=False)
        return log


def tick_logs() -> dict:
    """Every log kept, by engine name, oldest first."""
    with _logs_lock:
        return dict(_logs)
