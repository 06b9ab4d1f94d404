"""Per-request span tracing with tail-based retention.

The serving stack's metrics (serving/metrics.py) can say WHERE a latency
percentile lives (queue vs device vs e2e) but not WHY one specific p999
request was slow — the reservoirs aggregate away the request identity.
This module is the per-request view: a :class:`TraceContext` rides a
request from the HTTP front end through cache → admission → batcher
queue → replica/shard dispatch → kernel → compose, accumulating named
spans, and a :class:`SpanRecorder` keeps the *interesting* traces in a
bounded ring exposed at ``GET /debug/traces``.

Retention is TAIL-BASED, the only sampling policy that answers tail
questions: head-based sampling at p=0.01 keeps one in a hundred of the
*shed* requests too, so the trace buffer is statistically empty exactly
where the incident is. Here the retention decision happens at FINISH
time, when the outcome is known:

- every non-OK trace (shed / degraded / deadline-exceeded / error) is
  always retained;
- the slowest-N OK traces seen so far are retained (a min-heap of the
  N largest durations — a new tail entrant evicts the fastest member);
- the remaining OK traces are retained with probability
  ``KMLS_TRACE_SAMPLE`` (the baseline that keeps the buffer
  representative of normal traffic).

Zero-cost when off: ``KMLS_TRACE_SAMPLE=0`` (the default) makes
:attr:`SpanRecorder.enabled` False, and every call site checks that one
attribute before allocating anything — no context object, no id
generation, no per-request work. The ``began`` counter proves it the
same way the compile counter proves zero-compile serving: a test drives
traffic with tracing off and asserts the counter never moved.

The trace id travels in the ``X-KMLS-Trace`` header (request:
``<trace_id>`` or ``<trace_id>:<parent_id>``; response echoes the trace
id), so a replay/bench client can join its client-side timing to the
server-side span breakdown for the same request.

Spans form a TREE: every span has a small integer id local to its
trace and a parent id; the root (id 0) is the trace itself, ``request``
or ``batch``. A dispatched batch has a trace of its own —
``batch -> {stage -> {fill_embed, put_embed, enqueue_embed,
fill_rules, put_rules}, dispatch -> {enqueue_rules}, handoff,
fetch_embed, fetch_rules, compose, resolve}`` in a hybrid batch (the
``*_embed`` spans absent where the embedding family does not answer) —
and each member
request's ``batch`` span names it by ``batch_id``, so a request reads
``request -> {parse, cache, admit, queue, batch, respond, write}`` and
the batch's inside is looked up once, not copied into every member.
``stage`` and ``dispatch`` take their ids when they begin
(:meth:`TraceContext.reserve`), so their children, recorded while they
are open, can name them; each keeps the endpoints it has without them.

CAPTURE MODE puts the spans on the device trace's clock. While a
``/debug/profile`` capture is open (``utils/profiling.start_capture``
switches it; there is no knob) every request and every batch is traced,
whatever the sample rate, and kept in a list of its own; the capture
thread emits clock anchors into the profiler session
(:func:`emit_clock_anchor`: a ``TraceAnnotation`` whose name carries
``perf_counter_ns``) and, after each, runs :class:`ClockProbe` (a
one-element program on every local device, between two
``perf_counter_ns`` readings); when the capture closes the spans are
written beside the ``.xplane.pb`` as ``kmls_spans.jsonl`` with the
anchors and the probes in a header line. A reader maps ``perf_counter``
onto the capture's host plane by the anchors, and each device plane
onto it by its probes. Every ``TraceAnnotation`` of the serving path
lives in this module.
"""

from __future__ import annotations

import collections
import heapq
import itertools
import random
import threading
import time

# a trace's root span: the request or the batch itself
ROOT = 0
# written beside the capture's .xplane.pb when a /debug/profile capture
# closes; benchmark/spans.py reads it
SPANS_FILENAME = "kmls_spans.jsonl"
# a clock anchor is a TraceAnnotation named <prefix><perf_counter_ns>
CLOCK_ANCHOR_PREFIX = "kmls/clock:"

# ids are [-A-Za-z0-9_.]{1,64}: anything else in the header is treated
# as absent (a hostile or corrupted header must not flow into JSON
# output verbatim beyond this charset)
_ID_OK = frozenset(
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789-_."
)
_MAX_ID_LEN = 64


def _valid_id(s: str) -> bool:
    return 0 < len(s) <= _MAX_ID_LEN and all(c in _ID_OK for c in s)


def emit_clock_anchor() -> tuple[int, int]:
    """Write one clock anchor into the open profiler session → (the
    ``perf_counter_ns`` its name carries, ``perf_counter_ns`` read just
    after the annotation opened). The annotation's start on the
    capture's host plane lies between the two, so their distance bounds
    what the mapping can be off by."""
    import jax

    named = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(f"{CLOCK_ANCHOR_PREFIX}{named}"):
        opened = time.perf_counter_ns()
    return named, opened


def kmls_clock_probe(x):
    """The probe's program (its XLA module is ``jit_kmls_clock_probe``)."""
    return x + 1


class ClockProbe:
    """One tiny program per local device, for the device planes' clock.

    Built before a capture opens: it places its one-element inputs and
    compiles the program on every device then, so that no compile lands
    inside the capture. Each call runs the program on each device in
    turn, ``rounds`` times over → ``[[device id, perf_counter_ns before
    the call, perf_counter_ns after block_until_ready], ...]``. The
    program's execution on that device's plane lies between the two
    readings, so each probe bounds the plane's offset from the host
    clock from both sides. On a TPU v5e the two readings lie 1.2-2.1 ms
    apart around a program of 0.6 µs (the launch and the completion's
    way back to the host), so a reader intersects several probes."""

    rounds = 4

    def __init__(self):
        import jax
        import numpy as np

        self._run = jax.jit(kmls_clock_probe)
        self._inputs = [
            (d.id, jax.device_put(np.zeros((1,), np.int32), d))
            for d in jax.local_devices()
        ]
        for _, x in self._inputs:
            self._run(x).block_until_ready()

    def __call__(self) -> list[list[int]]:
        out = []
        for _ in range(self.rounds):
            for device_id, x in self._inputs:
                before = time.perf_counter_ns()
                self._run(x).block_until_ready()
                out.append([device_id, before, time.perf_counter_ns()])
        return out


class TraceContext:
    """One request's — or one batch's — spans. Append-only; list.append
    is GIL-atomic, so the batcher's completion thread and the HTTP
    thread can both record without a lock (the same benign-race budget
    the batcher's in-flight counters run on — on the normal path spans
    are recorded before the future resolves, so the finishing thread
    observes a complete list). When the app thread finishes a trace
    EARLY (deadline expiry, shed), the completer may still be running —
    ``finished`` makes its late span() a no-op (best-effort; the check
    is unsynchronized). The hard immutability guarantee lives in
    :class:`SpanRecorder`, which retains a trace as its rendered dict
    frozen at finish time.

    A span is ``(span_id, parent_id, name, t_start, t_end, attrs)``;
    the root (``ROOT``, named by ``kind``) spans ``t0`` to the finish
    and is rendered first. ``cursor`` serves :meth:`lap`: back-to-back
    spans, each starting where the last one ended, which is how a batch
    trace is recorded across the dispatching thread, the executor thread
    and the resolving one (they hand the trace on, never share it)."""

    __slots__ = (
        "kind", "trace_id", "parent_id", "t0", "wall_start", "spans",
        "attrs", "status", "duration_s", "finished", "deferred",
        "cursor", "_ids",
    )

    def __init__(
        self, trace_id: str, parent_id: str | None, t0: float,
        kind: str = "request",
    ):
        self.kind = kind
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.t0 = t0  # perf_counter at begin
        self.wall_start = time.time()
        self.spans: list[
            tuple[int, int, str, float, float, dict | None]
        ] = []
        self.attrs: dict[str, object] = {}
        self.status = "open"
        self.duration_s = 0.0
        self.finished = False
        # True: a response builder only stamps the status, and the
        # transport finishes the trace once its write has returned
        self.deferred = False
        self.cursor = t0
        self._ids = itertools.count(ROOT + 1)  # next() is GIL-atomic

    def span(
        self, name: str, t_start: float, t_end: float,
        attrs: dict | None = None, parent: int = ROOT,
        span_id: int | None = None,
    ) -> int:
        """Record a named span (perf_counter endpoints) under ``parent``
        → its id (``span_id``, where :meth:`reserve` gave one). No-op
        (→ -1) once the trace is finished: a deadline-expired request is
        retained at resolve time, and the kernel's eventual completion
        must not rewrite what ``/debug/traces`` already served."""
        if self.finished:
            return -1
        if span_id is None:
            span_id = next(self._ids)
        self.spans.append((span_id, parent, name, t_start, t_end, attrs))
        return span_id

    def reserve(self) -> int:
        """An id for a span that is still open, so that the spans inside
        it can name it as their parent before it closes (it closes with
        ``lap(..., span_id=)``). Ids follow the order spans begin in."""
        return next(self._ids)

    def lap(
        self, name: str, attrs: dict | None = None,
        span_id: int | None = None,
    ) -> int:
        """Close a span that began where the last lap ended (or at the
        last :meth:`skip`) and ends now."""
        now = time.perf_counter()
        span_id = self.span(name, self.cursor, now, attrs, span_id=span_id)
        self.cursor = now
        return span_id

    def child(
        self, name: str, parent: int, t_start: float,
        attrs: dict | None = None,
    ) -> int:
        """Record a span under the open span ``parent`` (an id from
        :meth:`reserve`) from ``t_start`` to now. The lap cursor
        does not move: the parent's own lap still starts and ends where
        it would without its children."""
        return self.span(name, t_start, time.perf_counter(), attrs, parent)

    def skip(self) -> None:
        """Move the lap cursor to now: what lies between the last lap
        and here belongs to no span."""
        self.cursor = time.perf_counter()

    def annotate(self, key: str, value) -> None:
        self.attrs[key] = value

    def to_dict(self, absolute: bool = False) -> dict:
        """JSON-ready. With ``absolute`` every span also carries its
        ``perf_counter`` endpoints in whole nanoseconds (the capture
        file's form: the clock the anchors are on)."""
        t0 = self.t0
        rows = [(ROOT, None, self.kind, t0, t0 + self.duration_s, None)]
        # by id, which is the order the spans began in: a reserved parent
        # is recorded after the children that name it
        rows += sorted(self.spans)
        spans = []
        for span_id, parent, name, t_start, t_end, attrs in rows:
            span = {
                "id": span_id,
                "parent": parent,
                "name": name,
                "start_ms": round((t_start - t0) * 1e3, 4),
                "duration_ms": round((t_end - t_start) * 1e3, 4),
            }
            if absolute:
                span["t_start_ns"] = int(t_start * 1e9)
                span["t_end_ns"] = int(t_end * 1e9)
            if attrs:
                span["attrs"] = attrs
            spans.append(span)
        return {
            "kind": self.kind,
            "trace_id": self.trace_id,
            "parent_id": self.parent_id,
            "status": self.status,
            "start_unix": round(self.wall_start, 6),
            "duration_ms": round(self.duration_s * 1e3, 4),
            "attrs": dict(self.attrs),
            "spans": spans,
        }


class SpanRecorder:
    """Bounded ring of finished traces with tail-based retention.

    ``sample <= 0`` disables the recorder (``enabled`` False); call
    sites check ``active`` — ``enabled``, or a capture open — before
    :meth:`begin` so the disabled hot path does literally nothing. The
    retention lock is taken at most twice per FINISHED trace (never per
    span) and guards only ring + heap + capture-list mutation — no I/O,
    no rendering, no blocking calls ever run under it."""

    def __init__(
        self,
        sample: float = 0.0,
        capacity: int = 512,
        slow_n: int = 32,
        rng: random.Random | None = None,
    ):
        self.sample = min(max(sample, 0.0), 1.0)
        self.capacity = max(1, capacity)
        self.slow_n = max(0, slow_n)
        self.enabled = self.sample > 0.0
        # the ONE attribute every call site checks: tracing on by the
        # knob, or a profile capture open (capture mode)
        self.active = self.enabled
        # contexts created — the zero-cost proof counters (compile-
        # counter discipline: both must stay 0 while nothing is active)
        self.began = 0
        self.batches_began = 0
        self.retained_total = 0
        # retained traces are stored PRE-RENDERED (to_dict at finish
        # time): the live TraceContext stays reachable from the batcher
        # completer, and its `finished` no-op guard on span() is only
        # best-effort (an unsynchronized check the completer can have
        # already passed) — freezing the rendered form is what actually
        # guarantees a scraped trace never changes between scrapes
        self._buf: "collections.deque[dict]" = collections.deque(
            maxlen=self.capacity
        )
        # batch traces while the knob is on: the newest `capacity`, so a
        # retained request's `batch_id` can be looked up
        self._batches: "collections.deque[dict]" = collections.deque(
            maxlen=self.capacity
        )
        # capture mode: every finished trace, rendered with absolute
        # endpoints, until the capture closes (None = no capture open)
        self._capture: list[dict] | None = None
        # min-heap of the N largest OK durations retained so far: the
        # root is the admission bar a new trace must clear to count as
        # "slowest-N"
        self._slow: list[float] = []
        self._lock = threading.Lock()
        self._rng = rng or random.Random()
        self._batch_ids = itertools.count(1)

    # ---------- lifecycle ----------

    def begin(
        self, header: str | None = None, t0: float | None = None,
    ) -> TraceContext | None:
        """Open a trace for one request; ``header`` is the raw
        ``X-KMLS-Trace`` request value (``id`` or ``id:parent``), ``t0``
        when the request began (default: now). Only called when
        :attr:`active` — returns None defensively so a miswired call
        site degrades to untraced rather than crashing."""
        if not self.active:
            return None
        self.began += 1  # benign race: diagnostic counter, GIL-coalesced
        trace_id = ""
        parent_id: str | None = None
        if header:
            head, _, tail = header.partition(":")
            head = head.strip()
            tail = tail.strip()
            if _valid_id(head):
                trace_id = head
            if tail and _valid_id(tail):
                parent_id = tail
        if not trace_id:
            trace_id = f"{self._rng.getrandbits(64):016x}"
        return TraceContext(
            trace_id, parent_id, time.perf_counter() if t0 is None else t0
        )

    def begin_batch(self, t0: float, **attrs) -> TraceContext | None:
        """Open the trace of one dispatched batch, formed at ``t0``;
        its ``batch_id`` attribute is what the members' ``batch`` spans
        name. Same contract as :meth:`begin`."""
        if not self.active:
            return None
        self.batches_began += 1
        batch_id = next(self._batch_ids)
        trace = TraceContext(f"batch-{batch_id}", None, t0, kind="batch")
        trace.attrs["batch_id"] = batch_id
        trace.attrs.update(attrs)
        return trace

    def finish(
        self, trace: TraceContext, status: str, duration_s: float
    ) -> bool:
        """Close the trace and decide retention → whether the ring kept
        it. ``status``: ``"ok"`` | ``"shed"`` | ``"degraded"`` |
        ``"error"`` (degraded traces carry the reason in
        ``attrs["reason"]``). A capture keeps every trace besides."""
        trace.status = status
        trace.duration_s = duration_s
        trace.finished = True  # best-effort: stops further span() appends
        if self._capture is not None:
            frozen = trace.to_dict(absolute=True)
            with self._lock:
                if self._capture is not None:
                    self._capture.append(frozen)
        if not self.enabled:
            return False
        with self._lock:
            keep = status != "ok"
            if not keep and self.slow_n > 0:
                # slowest-N admission: the heap root is the bar
                if len(self._slow) < self.slow_n:
                    heapq.heappush(self._slow, duration_s)
                    keep = True
                elif duration_s > self._slow[0]:
                    heapq.heapreplace(self._slow, duration_s)
                    keep = True
            if not keep:
                keep = self._rng.random() < self.sample
        if keep:
            # render OUTSIDE the lock (allocation-heavy), then append the
            # frozen dict: a completer thread racing past the `finished`
            # check mutates only the live context, never the retained form
            frozen = trace.to_dict()
            with self._lock:
                self._buf.append(frozen)
                self.retained_total += 1
        return keep

    def finish_batch(self, trace: TraceContext, status: str = "ok") -> None:
        """Close a batch trace (its root ends now). Kept for the open
        capture, and among the newest batches while the knob is on."""
        trace.status = status
        trace.duration_s = time.perf_counter() - trace.t0
        trace.finished = True
        captured = trace.to_dict(absolute=True) if self._capture is not None else None
        kept = trace.to_dict() if self.enabled else None
        with self._lock:
            if captured is not None and self._capture is not None:
                self._capture.append(captured)
            if kept is not None:
                self._batches.append(kept)

    # ---------- capture mode ----------

    def capture_begin(self) -> None:
        """A profile capture opens: trace everything until
        :meth:`capture_end`."""
        with self._lock:
            self._capture = []
            self.active = True

    def capture_end(self) -> list[dict]:
        """The capture closed → every trace finished while it was open
        (rendered with absolute endpoints), oldest first."""
        with self._lock:
            traces, self._capture = self._capture or [], None
            self.active = self.enabled
        return traces

    # ---------- exposition ----------

    def retained(self) -> int:
        with self._lock:
            return len(self._buf)

    def snapshot(self) -> list[dict]:
        """Retained traces, oldest first (JSON-ready; frozen at finish —
        callers must not mutate the returned dicts)."""
        with self._lock:
            return list(self._buf)

    def debug_payload(self) -> dict:
        """The ``GET /debug/traces`` response body."""
        with self._lock:
            batches = list(self._batches) if self.enabled else []
        return {
            "enabled": self.enabled,
            "sample": self.sample,
            "capacity": self.capacity,
            "slow_n": self.slow_n,
            "began": self.began,
            "batches_began": self.batches_began,
            "retained_total": self.retained_total,
            "traces": self.snapshot() if self.enabled else [],
            "batches": batches,
        }
