"""Lightweight cross-service tracer — spans, the ``X-MLT-Trace`` header
contract, and JSONL/ring export.

A request entering the serving gateway gets a root span; each graph step,
outbound ``RemoteStep``/``BatchHttpRequests`` call, and LLM scheduler
phase (prefill/decode) becomes a child span. The trace id rides the
``X-MLT-Trace: <trace_id>-<parent_span_id>`` header across HTTP hops, so
a nested GraphServer's spans join the caller's trace — the span JSONL of
both sides shares one trace id and the parent links line up. The run
lifecycle (submit → schedule → running → retry/resume) uses a
deterministic trace id derived from the run uid (:func:`trace_id_for`),
so every monitor decision about a run lands on one timeline.

Export targets:

- an in-memory ring (always on; tests and ``/__stats__``-style
  introspection read it), and
- a JSONL file (one span object per line) when a path is configured —
  the per-run span artifact that can be joined with an XLA device trace
  in TensorBoard because ``utils/profiler.annotate`` stamps the active
  trace id into ``jax.profiler.TraceAnnotation`` region names.

Stdlib only (same bottom-layer rule as ``obs/metrics.py`` and
``chaos/registry.py``): the tracer must be importable below every layer
that emits spans.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

# header the serving/service layers understand (case-insensitive):
#   X-MLT-Trace: <32-hex trace id>-<16-hex parent span id>
# (a bare trace id with no span part is accepted too)
TRACE_HEADER = "x-mlt-trace"

_HEX = set("0123456789abcdef")

# One clock for spans: ``time.perf_counter()`` (the request ledger's and the
# tick log's clock) carried onto the wall by one anchor read at import. A
# span's duration is then monotone whatever the wall clock does meanwhile,
# and a span, a ledger phase and a tick record can be laid side by side.
WALL0 = time.time()
PERF0 = time.perf_counter()


def wall_at(perf: float) -> float:
    """The wall-clock reading of a ``time.perf_counter()`` reading."""
    return WALL0 + (perf - PERF0)


def wall_now() -> float:
    return wall_at(time.perf_counter())


def _is_hex(value: str) -> bool:
    return bool(value) and set(value) <= _HEX


def new_trace_id() -> str:
    return uuid.uuid4().hex


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def trace_id_for(seed: str) -> str:
    """Deterministic trace id for an out-of-band correlation key (run
    uid): every lifecycle span of one run shares a trace without any
    header plumbing through k8s manifests."""
    return hashlib.md5(str(seed).encode()).hexdigest()  # noqa: S324


def _header_text(value) -> str:
    """Header keys/values may arrive as bytes from raw ASGI/WSGI layers;
    decode rather than str() (which would mangle b"x-mlt-trace" into
    "b'x-mlt-trace'" and silently drop the caller's trace)."""
    if isinstance(value, (bytes, bytearray)):
        return bytes(value).decode("latin-1", "replace")
    return str(value)


def parse_trace_header(headers: dict | None
                       ) -> tuple[Optional[str], Optional[str]]:
    """(trace_id, parent_span_id) from request headers; (None, None) when
    absent or malformed — a garbage header must never fail a request.
    The contract is load-bearing for cross-replica trace assembly
    (docs/observability.md), so malformed shapes (mixed-case names, bare
    trace ids, overlong/non-hex/empty span parts, bytes values) are
    pinned by tests."""
    if not headers:
        return None, None
    value = None
    for key, candidate in headers.items():
        if _header_text(key).lower() == TRACE_HEADER:
            value = _header_text(candidate)
            break
    if not value:
        return None, None
    trace_id, _, parent = value.strip().lower().partition("-")
    if not _is_hex(trace_id) or len(trace_id) > 64:
        return None, None
    if parent and (not _is_hex(parent) or len(parent) > 32):
        parent = ""
    return trace_id, parent or None


def format_trace_header(trace_id: str, span_id: str | None = None) -> str:
    return f"{trace_id}-{span_id}" if span_id else trace_id


@dataclass
class Span:
    name: str
    trace_id: str
    span_id: str = field(default_factory=new_span_id)
    parent_id: Optional[str] = None
    start: float = field(default_factory=wall_now)
    end: Optional[float] = None
    status: str = "ok"
    attrs: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start": self.start,
            "end": self.end,
            "duration_s": (self.end - self.start)
            if self.end is not None else None,
            "status": self.status,
            "attrs": self.attrs,
        }


class Tracer:
    """Span factory + exporter. One process-wide instance by default
    (:func:`get_tracer`); tests may build isolated instances (e.g. one
    per GraphServer) to assert on each side of an HTTP hop."""

    # JSONL rotation default: one predecessor kept, so the on-disk span
    # footprint of a long-running replica is bounded at ~2x this
    DEFAULT_MAX_BYTES = 64 * 1024 * 1024

    def __init__(self, ring: int = 2048, path: str | None = None,
                 max_bytes: int | None = None):
        self._ring: deque[Span] = deque(maxlen=max(1, int(ring)))
        self._path = path or None
        self._max_bytes = int(max_bytes if max_bytes is not None
                              else self.DEFAULT_MAX_BYTES)
        self._size: Optional[int] = None  # bytes in the active file
        self._file_lock = threading.Lock()
        self._local = threading.local()
        self._lock = threading.Lock()

    # -- configuration -------------------------------------------------------
    def configure(self, path: str | None = None, ring: int | None = None,
                  max_bytes: int | None = None):
        if ring is not None:
            with self._lock:
                self._ring = deque(self._ring, maxlen=max(1, int(ring)))
        if path is not None:
            with self._file_lock:
                self._path = path or None
                self._size = None  # re-measured on the next export
        if max_bytes is not None:
            self._max_bytes = int(max_bytes)
        return self

    @property
    def path(self) -> Optional[str]:
        return self._path

    # -- span lifecycle ------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current(self) -> Optional[Span]:
        """Innermost active span on THIS thread (None off-request)."""
        stack = self._stack()
        return stack[-1] if stack else None

    def start_span(self, name: str, trace_id: str | None = None,
                   parent_id: str | None = None, attrs: dict | None = None,
                   activate: bool = False) -> Span:
        """Open a span. Without an explicit trace/parent the thread's
        current span (if any) becomes the parent; otherwise a fresh
        trace starts. ``activate`` pushes it on the thread-local stack so
        nested code (engine submit, outbound calls) sees it as current."""
        if trace_id is None:
            current = self.current()
            if current is not None:
                trace_id = current.trace_id
                if parent_id is None:
                    parent_id = current.span_id
            else:
                trace_id = new_trace_id()
        span = Span(name=name, trace_id=trace_id, parent_id=parent_id,
                    attrs=dict(attrs or {}))
        if activate:
            self._stack().append(span)
        return span

    def end_span(self, span: Span, status: str | None = None):
        if span.end is not None:
            return
        span.end = wall_now()
        if status:
            span.status = status
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        self._export(span)

    @contextlib.contextmanager
    def span(self, name: str, trace_id: str | None = None,
             parent_id: str | None = None, attrs: dict | None = None):
        """Context-managed activated span; errors mark status and
        propagate."""
        span = self.start_span(name, trace_id=trace_id, parent_id=parent_id,
                               attrs=attrs, activate=True)
        try:
            yield span
        except BaseException:
            self.end_span(span, status="error")
            raise
        self.end_span(span)

    def emit(self, name: str, trace_id: str, parent_id: str | None = None,
             start: float | None = None, end: float | None = None,
             status: str = "ok", attrs: dict | None = None) -> Span:
        """Record an already-finished span (scheduler phases measured with
        perf counters resolve start/end after the fact; ``start``/``end``
        are :func:`wall_now` readings)."""
        now = wall_now()
        span = Span(name=name, trace_id=trace_id, parent_id=parent_id,
                    start=start if start is not None else now,
                    status=status, attrs=dict(attrs or {}))
        span.end = end if end is not None else now
        self._export(span)
        return span

    # -- header propagation --------------------------------------------------
    def inject(self, headers: dict | None = None,
               span: Span | None = None) -> dict:
        """Headers dict carrying the trace context of ``span`` (or the
        thread's current span). A copy is returned; absent context leaves
        the headers untouched."""
        headers = dict(headers or {})
        span = span or self.current()
        if span is not None:
            headers["X-MLT-Trace"] = format_trace_header(
                span.trace_id, span.span_id)
        return headers

    # -- export --------------------------------------------------------------
    def _export(self, span: Span):
        with self._lock:
            self._ring.append(span)
        path = self._path
        if path:
            try:
                line = json.dumps(span.to_dict(), default=str) + "\n"
                with self._file_lock:
                    directory = os.path.dirname(path)
                    if directory:
                        os.makedirs(directory, exist_ok=True)
                    if self._size is None:
                        try:
                            self._size = os.path.getsize(path)
                        except OSError:
                            self._size = 0
                    # size-capped rotation (mlconf.observability.
                    # trace_max_bytes): rotate BEFORE the write that
                    # would cross the cap, keeping exactly one `.1`
                    # predecessor — a long-running emit loop never holds
                    # more than 2x the cap on disk
                    if self._max_bytes > 0 and self._size \
                            and self._size + len(line) > self._max_bytes:
                        os.replace(path, path + ".1")
                        self._size = 0
                    with open(path, "a") as fp:
                        fp.write(line)
                    self._size += len(line)
            except OSError:
                # span export must never fail the traced operation
                pass

    # -- introspection (tests / smoke) ---------------------------------------
    def spans(self, trace_id: str | None = None,
              name: str | None = None) -> list[Span]:
        with self._lock:
            snapshot = list(self._ring)
        return [s for s in snapshot
                if (trace_id is None or s.trace_id == trace_id)
                and (name is None or s.name == name)]

    def clear(self):
        with self._lock:
            self._ring.clear()


# process-wide tracer: serving gateway, service API, engines, run monitor
tracer = Tracer()


def get_tracer() -> Tracer:
    return tracer


def configure_from_mlconf():
    """Apply ``mlconf.observability`` to the global tracer (called by the
    serving gateway and service entrypoints; imports config lazily so
    this module stays bottom-layer)."""
    try:
        from ..config import mlconf

        obs_conf = mlconf.get("observability")
        if obs_conf is None:
            return tracer
        path = str(obs_conf.get("trace_path") or "") or None
        ring = obs_conf.get("trace_ring")
        max_bytes = obs_conf.get("trace_max_bytes")
        tracer.configure(path=path, ring=int(ring) if ring else None,
                         max_bytes=(int(max_bytes)
                                    if max_bytes is not None else None))
    except Exception:  # noqa: BLE001 - observability must not block startup
        pass
    return tracer
