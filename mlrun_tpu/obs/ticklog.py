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

The records close over the loop's wall time: each holds the gap since the
one before it, so that ``sum(gap_s + loop_s)`` over the records after the
first is the last ``t1`` less the first, and an iteration far longer than
the loop's own (``TickLog.append`` says which) is a stall, whose parts by
cause :func:`stall_parts` names.

Stdlib only (the ``obs/metrics.py`` bottom-layer rule).
"""

from __future__ import annotations

import gc
import threading
import time
from collections import OrderedDict, deque
from statistics import median
from typing import Optional

try:
    from resource import RUSAGE_THREAD, getrusage
except ImportError:                     # no per-thread usage on the platform
    getrusage = None

# nine minutes of a 30 ms tick
TICK_LOG_RECORDS = 16384
# logs kept by name, live engines and gone ones alike: the oldest goes first
KEPT_LOGS = 16
# an iteration is a stall where it and the gap before it, the idle polls
# and the wait for its own prefills apart (``TickRecord.span_s`` less
# ``prefill_wait_s``: a request that ends a quiet spell and an iteration
# that admits five are work, not stalls), take longer than both the floor
# and the factor times the median ``loop_s`` of the last STALL_WINDOW
# records with rows; none is declared before the ring holds STALL_LEAST of
# them, and the median is taken anew after as many appends as it rests on
# (so a threshold set from a warm-up's quick ticks does not stand through
# the ramp), every STALL_WINDOW at most
STALL_FLOOR_S = 0.1
STALL_FACTOR = 8
STALL_WINDOW = 256
STALL_LEAST = 32
# records between two readings of the thread's clock and usage: a system
# call each, of 6-20 us on a sandboxed kernel (PERF.md, PR 39)
CPU_EVERY = 32

FIELDS = ("n", "t0", "t_admit", "t_built", "t_dispatched", "t_fetched",
          "t1", "admit_wait_s", "rows", "ctx_tokens", "prefill_tokens",
          "kind", "positions", "tokens_out", "commit_rows", "expert_pairs",
          "experts_touched", "expert_load_max", "lookahead",
          "prefill_ctx_tokens", "state_rows", "state_tokens",
          "prefill_dispatches", "gap_s", "inflight_wait_s",
          "prefill_wait_s", "dry_s", "after_prefill_s", "admissions",
          "cpu_s", "gc_s", "gc_gen", "nivcsw", "majflt", "idle_s",
          "cpu_span_s")


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

    What closes the record over wall time, and says where it went.
    ``gap_s``: seconds from the ``t1`` of the record before to this ``t0``
    (metric observations, the profiler's check, idle polls and their
    sleeps); 0 in the first record after ``start()``. ``idle_s``: the part
    of ``gap_s`` from the end of the first idle poll (no row, no queue) to
    this ``t0``: the loop had nothing to do, which is nobody's stall;
    ``span_s`` is the iteration and the gap before it without that.
    ``inflight_wait_s`` and ``prefill_wait_s`` are the two parts of
    ``admit_wait_s`` that have a name: blocked on the decode tick or pass
    in flight (the device decodes), and blocked on the prefill's own result
    with the first token's sampling (the device prefills); their sum is at
    most ``admit_wait_s``. ``dry_s``: seconds of ``[t0 - gap_s, t1]`` in
    which the host *knows* that the device has nothing queued: from the
    return of a fetch of the last program enqueued to the return of the
    next enqueueing call. After a dispatch whose result is never fetched
    (an insert, a prefill chunk) nothing is known until a later fetch, so
    ``dry_s`` is a lower bound of the device's idle: the part the host
    alone causes. ``after_prefill_s``: seconds from the return of a
    prefill's first-token fetch to the return of the next prefill or decode
    enqueue (slot activation, the insert's enqueue, the next tick's build):
    an upper bound of the same idle, the insert's own run apart; 0 for a
    block model, whose admission fetches no token. ``admissions``:
    admissions completed in the iteration. ``gc_s`` and ``gc_gen``: seconds
    of ``[t0 - gap_s, t1]`` inside a collection of any thread (a collection
    holds the interpreter's lock) and the highest generation collected.

    The thread's clock is read every ``CPU_EVERY`` records, and in a record
    whose ``span_s`` is over ``STALL_FLOOR_S``, not in each. The record
    that holds a reading has ``cpu_s``, the ``time.thread_time()`` seconds
    the scheduler thread was executing since the reading before, and
    ``cpu_span_s``, the wall seconds those cover: the ``span_s`` of the
    records since then, this one included (idle polls are left out of
    both; the first record after ``start()`` or after an idle poll starts a
    reading and is in none). ``cpu_s <= cpu_span_s`` to the clocks'
    resolution, every other record has 0 in both, and ``sum(cpu_s) /
    sum(cpu_span_s)`` is the share of the loop the thread was executing.
    ``nivcsw``, ``majflt``: the thread's involuntary context switches and
    major page faults between the same two readings (0 where the platform
    has no per-thread usage).
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
        self.gap_s = self.inflight_wait_s = self.prefill_wait_s = 0.0
        self.dry_s = self.after_prefill_s = self.cpu_s = self.gc_s = 0.0
        self.idle_s = self.cpu_span_s = 0.0
        self.admissions = self.gc_gen = self.nivcsw = self.majflt = 0
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
    def span_s(self) -> float:
        """The iteration and the gap before it, idle polls apart."""
        return self.gap_s - self.idle_s + self.t1 - self.t0

    @property
    def device_wait_s(self) -> float:
        """Seconds of the iteration blocked on a device result."""
        return (self.t_fetched - self.t_dispatched) + self.admit_wait_s

    def as_dict(self) -> dict:
        return {name: getattr(self, name) for name in FIELDS}


class TickLog:
    """A bounded ring of :class:`TickRecord` with running sums over what
    the ring holds, so that the five scalars an engine's ``stats`` quotes
    cost nothing to read."""

    def __init__(self, size: int = TICK_LOG_RECORDS):
        self._lock = threading.Lock()
        self._ring: deque = deque()
        self._size = max(1, int(size))
        self._loop_s = self._wait_s = self._admit_s = 0.0
        self._gap_s = self._dry_s = self._cpu_s = self._cpu_span_s = 0.0
        self._rows = self._decode_ticks = 0
        # seconds of gap and loop beyond which an iteration is a stall, and
        # the appends until the median behind it is taken anew
        self._stall_s = float("inf")
        self._refresh_in = STALL_LEAST

    def _account(self, record: TickRecord, sign: int):
        self._loop_s += sign * record.loop_s
        self._wait_s += sign * record.device_wait_s
        self._admit_s += sign * (record.t_admit - record.t0)
        self._gap_s += sign * record.gap_s
        self._dry_s += sign * record.dry_s
        self._cpu_s += sign * record.cpu_s
        self._cpu_span_s += sign * record.cpu_span_s
        if record.rows:
            self._rows += sign * record.rows
            self._decode_ticks += sign

    def append(self, record: TickRecord) -> bool:
        """Log the record; True where the iteration is a stall (the
        module's ``STALL_*`` constants)."""
        with self._lock:
            if len(self._ring) >= self._size:
                self._account(self._ring.popleft(), -1)
            self._ring.append(record)
            self._account(record, 1)
            self._refresh_in -= 1
            if self._refresh_in <= 0:
                self._refresh_stall()
            return record.span_s - record.prefill_wait_s > self._stall_s

    def _decoding(self, count: int) -> list:
        """The newest ``count`` records with rows, newest first (the caller
        holds the lock)."""
        found = []
        for record in reversed(self._ring):
            if record.rows:
                found.append(record)
                if len(found) >= count:
                    break
        return found

    def _refresh_stall(self):
        loops = [record.loop_s for record in self._decoding(STALL_WINDOW)]
        self._refresh_in = max(len(loops), STALL_LEAST - len(loops))
        if len(loops) >= STALL_LEAST:
            self._stall_s = max(STALL_FLOOR_S, STALL_FACTOR * median(loops))

    def latest(self, count: int) -> tuple[list, list]:
        """(``loop_s``, ``t1 - t_admit``) of the newest ``count`` records
        with rows: an inter-token interval as a client sees it (admission
        included), and the decode part alone."""
        with self._lock:
            found = self._decoding(count)
        return ([record.loop_s for record in found],
                [record.t1 - record.t_admit for record in found])

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
        all loop seconds), ``tick_admit_share`` (admission's part of
        them), ``device_dry_share`` (seconds the host knew the device dry,
        of all gap and loop seconds) and ``sched_cpu_share`` (the seconds
        the scheduler thread was executing, of the wall seconds its
        clock's readings cover: once the ring holds one), over the ring;
        empty while nothing is logged."""
        with self._lock:
            loop_s, wait_s, admit_s = self._loop_s, self._wait_s, \
                self._admit_s
            gap_s, dry_s = self._gap_s, self._dry_s
            cpu_s, cpu_span_s = self._cpu_s, self._cpu_span_s
            rows, ticks = self._rows, self._decode_ticks
        out = {}
        if ticks:
            out["tick_rows_mean"] = rows / ticks
        if loop_s > 0:
            out["tick_host_share"] = max(0.0, loop_s - wait_s) / loop_s
            out["tick_admit_share"] = admit_s / loop_s
            out["device_dry_share"] = max(0.0, dry_s) / (gap_s + loop_s)
        if cpu_span_s > 0:
            out["sched_cpu_share"] = max(0.0, cpu_s) / cpu_span_s
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


def stall_parts(record: dict) -> dict:
    """A record (its fields as a dict) by cause, over ``span_s``: the
    iteration and the gap before it, idle polls apart. The waits for the
    device (``admit_wait``, of which ``inflight_wait`` and ``prefill_wait``
    have a name, and ``fetch_wait``), the thread's own work (``cpu``) and
    what is left, ``off_cpu`` (floored at 0: the interpreter's lock,
    descheduled, a page fault), sum to ``span_s`` where nothing was
    floored. ``cpu`` is the record's reading, which covers ``cpu_span``
    seconds: where that is more than ``span_s`` (up to ``CPU_EVERY - 1``
    ordinary iterations before this one) ``off_cpu`` is a lower bound.
    ``gap``, ``dry`` and ``gc`` lie across those; ``phase`` is the part of
    the iteration that holds most of it."""
    loop_s = record["t1"] - record["t0"]
    gap_s = record["gap_s"] - record["idle_s"]
    fetch_wait = record["t_fetched"] - record["t_dispatched"]
    phases = {"gap": gap_s,
              "admit": record["t_admit"] - record["t0"],
              "build": record["t_built"] - record["t_admit"],
              "dispatch": record["t_dispatched"] - record["t_built"],
              "fetch": fetch_wait,
              "commit": record["t1"] - record["t_fetched"]}
    return {
        "n": record["n"], "span_s": gap_s + loop_s, "loop_s": loop_s,
        "gap": gap_s, "admit_wait": record["admit_wait_s"],
        "inflight_wait": record["inflight_wait_s"],
        "prefill_wait": record["prefill_wait_s"], "fetch_wait": fetch_wait,
        "dry": record["dry_s"], "cpu": record["cpu_s"],
        "cpu_span": record["cpu_span_s"],
        "gc": record["gc_s"], "gc_gen": record["gc_gen"],
        "off_cpu": max(0.0, gap_s + loop_s - fetch_wait
                       - record["admit_wait_s"] - record["cpu_s"]),
        "nivcsw": record["nivcsw"], "majflt": record["majflt"],
        "phase": max(phases, key=phases.get)}


# -- what the process does to the loop from outside it ------------------------
# seconds inside a collection and collections of generation 0, 1, 2 since
# the watch began: monotonic, an engine takes the difference an iteration
_gc_sums = [0.0, 0, 0, 0]
_gc_began = 0.0
_gc_watchers = 0
_gc_lock = threading.Lock()


def _on_gc(phase: str, info: dict):
    global _gc_began
    if phase == "start":
        _gc_began = time.perf_counter()
    elif _gc_began:
        _gc_sums[0] += time.perf_counter() - _gc_began
        _gc_sums[1 + info["generation"]] += 1
        _gc_began = 0.0


def watch_gc():
    """Count the collector's seconds from here on (the first watcher
    registers the callback; an engine's ``start()``)."""
    global _gc_watchers
    with _gc_lock:
        _gc_watchers += 1
        if _gc_watchers == 1:
            gc.callbacks.append(_on_gc)


def unwatch_gc():
    """The last watcher to go removes the callback (``stop()``)."""
    global _gc_watchers
    with _gc_lock:
        _gc_watchers = max(0, _gc_watchers - 1)
        if not _gc_watchers and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)


def gc_sums() -> tuple:
    """(seconds inside a collection, collections of generation 0, 1, 2)
    of any thread, while watched."""
    return tuple(_gc_sums)


def thread_usage() -> tuple:
    """(involuntary context switches, major page faults) of the calling
    thread so far; zeros where the platform keeps no per-thread usage."""
    if getrusage is None:
        return 0, 0
    usage = getrusage(RUSAGE_THREAD)
    return usage.ru_nivcsw, usage.ru_majflt
