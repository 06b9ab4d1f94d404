"""Request micro-batcher: aggregate concurrent ``/api/recommend/`` calls
into batched device kernel invocations, pipelined, with an adaptive
deadline-aware collection window and explicit load shedding.

The reference serves each request with per-request Python dict merges
(rest_api/app/main.py:240-253); the TPU hot path is a batched kernel, and at
1k QPS (BASELINE.json config 5) per-request device calls would serialize on
the device lock. This batcher collects requests and issues a single
:meth:`RecommendEngine.recommend_many_async` call per group. With the
second model family published, that one call dispatches BOTH model
kernels (rule max-merge + embedding cosine top-k) onto the chosen
replica and merges on the completion side — the batcher needs no
hybrid-awareness; a batch slot is a batch slot whichever models answer
it.

Dispatch and completion run on SEPARATE threads: the collector dispatches a
batch to the device (async, returns immediately) and keeps collecting while
a completion thread blocks on the in-order results and resolves futures.
A dispatch-block-respond loop caps throughput at batch_size over the
blocked call's latency (device time plus the host<->device round trip);
pipelining up to ``max_inflight`` batches removes that ceiling while jax's
in-order execution queue preserves result ordering.

Three tail-latency disciplines (a fixed 2 ms window left p99 several
times p50 at 1k QPS):

- **Idle fast path** (unchanged): the window is SKIPPED entirely when the
  device is idle — waiting only buys throughput when a batch is already in
  flight, so a lone request dispatches immediately.
- **Adaptive window**: when the device IS busy, the wait is sized from the
  observed arrival rate (mean gap over a sliding window of arrivals) —
  roughly the time the current rate needs to fill the batch — clamped to
  [``window_min_ms``, ``window_ms``]. A fixed window
  taxes every request the full window at low rates and is too short to
  amortize at high rates; the controller tracks the traffic instead. The
  wait is additionally capped so the batch LEADER's queue wait can never
  cross the shed budget — the deadline-aware part.
- **Adaptive admission control** (ISSUE 8 — replaces the static
  cliff-edge shed): an :class:`AdmissionController` tracks PRESSURE =
  effective queue wait / ``shed_queue_budget_ms``, where the effective
  wait is the max of the instantaneous projection (batches ahead ×
  device-time EWMA) and a time-decaying EWMA of the queue waits admitted
  requests actually measured (the projection alone undershoots when
  batches run larger than estimated; the measured EWMA alone would hold
  stale overload after a burst drains, so it decays with a half-life of
  one budget). Admission escalates through a LADDER instead of flipping
  at the threshold: below ``soft_ratio`` every request is admitted at
  full quality; between ``soft_ratio`` and 1.0 a rising fraction of
  requests degrades (:class:`OverloadDegraded` → the app answers from
  the popularity fallback, 200 + ``X-KMLS-Degraded: overload`` — cache
  hits are untouched, so the cache-favored rung costs only the
  compute-needing tail); between 1.0 and ``hard_ratio`` a rising
  fraction sheds (:class:`Overloaded` → HTTP 429) and the rest still
  degrades; past ``hard_ratio`` everything sheds. ``Retry-After``
  carries bounded jitter (± ``retry_jitter`` of the base) — a constant
  value synchronizes every shed client into the next retry storm.
  ``soft_ratio=hard_ratio=1.0`` reproduces the legacy cliff exactly.

Per-request enqueue/dispatch/complete timestamps are threaded through and
reported to :class:`~.metrics.ServingMetrics` as ``queue_wait`` /
``device`` / ``e2e`` attributions, so ``/metrics`` can say WHERE the tail
lives. A worker failure is propagated to every waiting request — the
batcher threads themselves never die.

**Multi-device dispatch**: when the engine publishes more than one
replica (``KMLS_SERVE_DEVICES``), the batcher becomes a least-loaded
multi-queue dispatcher — each batch goes to the replica with the fewest
batches in flight (ties rotate so an all-idle fleet still spreads), with
per-replica in-flight accounting and one completion lane per replica
(jax's in-order execution guarantee holds per device, not across
devices). The pipeline bound and the shed projection are computed against
AGGREGATE capacity: ``max_inflight`` batches per replica, and a projected
queue wait of (batches ahead × device-time EWMA) / replica count —
N devices drain the same queue N times faster. Engines without a replica
set (``n_replicas`` absent or 1) get the exact single-lane behavior the
fakes expect: the ``replica`` kwarg is only passed when there is a choice
to make.

**Replica health management** (``eject_threshold > 0``): a per-replica
consecutive-failure circuit breaker. A replica whose batches keep failing
is EJECTED from the least-loaded pick — its failed batch's requests are
re-dispatched to the surviving replicas (bounded per-request retries),
and the shed projection + idle fast path re-project against HEALTHY
capacity, not nominal. An ejected replica is probed for re-admission
every ``probe_interval_s``: one half-open trial batch; success re-admits,
failure re-arms the timer. With every replica ejected and no probe due,
admission raises :class:`NoHealthyReplicas` — the HTTP layer degrades
those requests to the popularity fallback instead of 500ing. Default OFF
(``eject_threshold=0``) so directly-constructed batchers (tests, replay
harnesses) keep the exact propagate-the-error behavior they always had;
the app layer wires KMLS_REPLICA_EJECT_THRESHOLD through.

**Deadlines**: ``submit(seeds, deadline=...)`` carries a per-request
perf_counter deadline through the pipeline. A request still queued at its
deadline fails with :class:`DeadlineExceeded` instead of dispatching dead
work to the device; in-flight overruns surface as the same exception from
the blocking ``recommend()`` wait (threaded) or a loop timer (async), and
the HTTP layer turns either into a degraded answer.
"""

from __future__ import annotations

import collections
import dataclasses
import inspect
import itertools
import logging
import math
import queue
import random
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FuturesTimeout

from .engine import RecommendEngine

logger = logging.getLogger("kmlserver_tpu.serving")

# EWMA smoothing for the device-batch-time estimate: new sample weighted
# 0.2 — reactive enough to track a load swing within ~10 batches, smooth
# enough that one straggler doesn't flip the shedding decision
_EWMA_ALPHA = 0.2


class Overloaded(RuntimeError):
    """Raised by :meth:`MicroBatcher.recommend` instead of enqueueing when
    admission pressure says this request would outwait the shed budget.
    ``retry_after_s`` carries the controller's jitter — the HTTP layer
    forwards it verbatim so shed clients don't re-arrive in lockstep."""

    def __init__(self, retry_after_s: float, projected_wait_ms: float):
        super().__init__(
            f"projected queue wait {projected_wait_ms:.0f}ms exceeds the "
            f"shed budget; retry after {retry_after_s:.1f}s"
        )
        self.retry_after_s = retry_after_s
        self.projected_wait_ms = projected_wait_ms


class OverloadDegraded(RuntimeError):
    """Admission pressure is in the controller's degrade band: instead of
    queueing (or 429ing) this request, answer it from the popularity
    fallback — the HTTP layer maps this to 200 + ``X-KMLS-Degraded:
    overload``, one rung BEFORE any 429. Cache hits never reach admission
    (the cache sits in front), so under rising pressure cached answers
    keep full quality and only the compute-needing tail degrades."""

    def __init__(self, pressure: float):
        super().__init__(
            f"admission pressure {pressure:.2f} in the degrade band; "
            "answering from the popularity fallback"
        )
        self.pressure = pressure


class AdmissionController:
    """Pressure-proportional admission: admit → degrade → shed.

    Pressure is the effective queue wait over the shed budget. Effective
    wait = max(instantaneous projection, measured queue-wait EWMA with
    time decay). Decision bands (ratios of the budget):

    - ``p < soft_ratio``            → admit
    - ``soft_ratio <= p < 1``       → degrade with prob (p-soft)/(1-soft)
    - ``1 <= p < hard_ratio``       → shed with prob (p-1)/(hard-1),
                                      degrade otherwise
    - ``p >= hard_ratio``           → shed

    ``soft_ratio >= 1`` disables the degrade band and ``hard_ratio <= 1``
    makes the shed band a cliff at the budget — together they restore the
    pre-controller DECISION ladder (admit below the budget, shed above,
    nothing in between). The pressure ESTIMATE is still the new one:
    effective wait includes the measured queue-wait EWMA, so a cliff-mode
    controller can keep shedding for ~one decay half-life after a burst
    the projection alone would already have forgotten.

    All state is plain floats — single-writer per field (the completion
    side notes queue waits, admission only reads), and a stale read costs
    at most one request landing a band early/late, the same benign-race
    budget the batcher's in-flight counters already run on. No locks, so
    the loop-confined async twin shares the class unchanged.
    """

    def __init__(
        self,
        budget_s: float,
        *,
        soft_ratio: float = 0.6,
        hard_ratio: float = 1.5,
        retry_after_s: float = 1.0,
        retry_jitter: float = 0.5,
        rng: random.Random | None = None,
        lag_source=None,
    ):
        self.budget_s = budget_s
        self.soft_ratio = max(0.0, soft_ratio)
        self.hard_ratio = max(self.soft_ratio, hard_ratio, 1.0)
        self.retry_after_s = retry_after_s
        self.retry_jitter = min(max(retry_jitter, 0.0), 1.0)
        self._rng = rng or random.Random()
        self._wait_ewma: float | None = None
        self._wait_noted_at = 0.0
        # decay half-life: one budget width (floored so a sub-ms budget
        # doesn't make the memory vanish between completions)
        self._half_life_s = max(budget_s, 0.25)
        # runtime-health fold (ISSUE 9, closing the PR 8 inline-path
        # blind spot): an optional zero-arg callable returning the
        # current event-loop/scheduler stall estimate in SECONDS
        # (observability.runtime.LoopLagMonitor.lag_s). A wedged loop
        # means requests are ALREADY waiting at least that long in the
        # socket backlog where the queue projection cannot see them, so
        # the stall is an effective-wait floor — it escalates the
        # degrade→shed ladder exactly like a saturated queue.
        self._lag_source = lag_source

    def note_queue_wait(self, wait_s: float, now: float | None = None) -> None:
        """Completion-side: fold an admitted request's MEASURED queue wait
        into the EWMA (the projection's ground truth)."""
        now = time.perf_counter() if now is None else now
        # first sample adopted outright (the device-time EWMA does the
        # same): a cold controller must not spend ~10 batches warming up
        # while an overload is already measurable
        self._wait_ewma = (
            wait_s if self._wait_ewma is None
            else (1 - _EWMA_ALPHA) * self._decayed_wait(now)
            + _EWMA_ALPHA * wait_s
        )
        self._wait_noted_at = now

    def _decayed_wait(self, now: float) -> float:
        """The EWMA, decayed by the time since the last completion noted a
        sample — a burst's high waits must not keep degrading traffic
        after the queue has drained (completions stop, so only time can
        bring the estimate back down)."""
        if self._wait_ewma is None or self._wait_ewma <= 0.0:
            return 0.0
        age = max(now - self._wait_noted_at, 0.0)
        return self._wait_ewma * math.exp(-age * math.log(2) / self._half_life_s)

    def pressure(self, projected_s: float, now: float | None = None) -> float:
        """Effective queue wait over the budget (0 with shedding off).
        The effective wait is the max of the instantaneous projection,
        the measured queue-wait EWMA, and — when a lag source is wired —
        the decayed event-loop stall estimate."""
        if self.budget_s <= 0.0:
            return 0.0
        now = time.perf_counter() if now is None else now
        wait = max(projected_s, self._decayed_wait(now))
        if self._lag_source is not None:
            wait = max(wait, self._lag_source())
        return wait / self.budget_s

    def decide(self, projected_s: float) -> tuple[str, float]:
        """→ ``(decision, pressure)`` for a request seeing ``projected_s``
        of projected queue wait right now; decision is ``"admit"`` |
        ``"degrade"`` | ``"shed"``. The pressure that drove the decision
        rides along so callers report the value the band was judged on
        (re-computing it would both double the hot-path work and skew —
        the EWMA decays between calls)."""
        p = self.pressure(projected_s)
        if p < self.soft_ratio:
            return "admit", p
        if p < 1.0:
            span = 1.0 - self.soft_ratio
            frac = (p - self.soft_ratio) / span if span > 0 else 1.0
            return ("degrade" if self._rng.random() < frac else "admit"), p
        if p < self.hard_ratio:
            span = self.hard_ratio - 1.0
            frac = (p - 1.0) / span if span > 0 else 1.0
            return ("shed" if self._rng.random() < frac else "degrade"), p
        return "shed", p

    def retry_after_jittered_s(self) -> float:
        """Retry-After with bounded jitter: uniform on
        ``base·(1 ± retry_jitter)``, floored at 100 ms. A constant value
        re-synchronizes every shed client into one retry wave exactly one
        Retry-After later — the storm the shed was absorbing."""
        if self.retry_jitter <= 0.0:
            return self.retry_after_s
        spread = 1.0 + self.retry_jitter * (2.0 * self._rng.random() - 1.0)
        return max(self.retry_after_s * spread, 0.1)


class DeadlineExceeded(RuntimeError):
    """A request's deadline budget ran out before (or while) the device
    could answer it. The HTTP layer degrades this to the latency-budgeted
    popularity fallback with an ``X-KMLS-Degraded`` header — never a 500."""


class NoHealthyReplicas(RuntimeError):
    """Every serving replica is currently ejected by the circuit breaker
    (and no re-admission probe is due). Degraded like
    :class:`DeadlineExceeded` — total replica loss serves fallbacks, not
    errors."""


@dataclasses.dataclass
class _Pending:
    seeds: list[str]
    future: Future
    t_enqueue: float
    # perf_counter deadline (None = no budget) and how many times this
    # request has been re-dispatched after a replica failure
    deadline: float | None = None
    retries: int = 0
    # per-request TraceContext (observability.trace) riding the pipeline
    # so completion can record queue/batch spans; None = untraced — the
    # default, costing nothing (tracing-off requests never construct one)
    trace: object | None = None
    # traced requests only: when the admission decision was made, which
    # is where the queue span starts (0.0 = untraced; t_enqueue then)
    t_admitted: float = 0.0


def _engine_takes(engine, kwarg: str) -> bool:
    """True when the engine's ``recommend_many_async`` accepts ``kwarg``
    (the real engine takes ``deadline`` and ``trace``; test fakes with
    the bare legacy signature must keep working)."""
    try:
        sig = inspect.signature(engine.recommend_many_async)
    except (TypeError, ValueError, AttributeError):
        return False
    return kwarg in sig.parameters


def _begin_batch_trace(recorder, t_dispatch: float, n_requests: int, idx: int):
    """The batch is formed: it gets a trace of its own while the app's
    recorder is active (→ None otherwise: one attribute check)."""
    if recorder is None or not recorder.active:
        return None
    return recorder.begin_batch(t_dispatch, requests=n_requests, replica=idx)


def _record_request_spans(
    batch: list[_Pending], btrace, t_dispatch: float, t_slot: float,
    idx: int, finish,
) -> None:
    """The ONE place both batchers record a batch's spans on its member
    requests, BEFORE their futures resolve (the finishing thread must
    observe a complete span list when the result lands): ``queue``
    (admitted → batch formed; ``slot_wait_ms`` is the part of it spent
    with the pipeline full, from ``t_slot``, the rest being the batch
    window) and ``batch`` (batch formed → here), which names the
    batch's own trace by ``batch_id``. An untraced member costs one
    is-None check."""
    now = time.perf_counter()
    batch_id = btrace.attrs["batch_id"] if btrace is not None else None
    # hedge outcome (ISSUE 18): the mesh finish() stamps its won/lost/
    # cancelled decision on itself; ride it onto every traced request
    hedged = getattr(finish, "_kmls_hedge", None)
    for pending in batch:
        trace = pending.trace
        if trace is None:
            continue
        t_queued = pending.t_admitted or pending.t_enqueue
        slot_s = t_dispatch - max(t_slot, t_queued) if t_slot else 0.0
        trace.span(
            "queue", t_queued, t_dispatch,
            {
                "batch": len(batch),
                "slot_wait_ms": round(max(slot_s, 0.0) * 1e3, 4),
            },
        )
        trace.span(
            "batch", t_dispatch, now,
            {"batch_id": batch_id, "replica": idx},
        )
        if hedged is not None:
            trace.annotate("hedged", hedged)


def _batch_deadline(batch: list[_Pending]) -> float | None:
    """The earliest pending deadline in the batch — the budget the whole
    device call (and any mesh hop under it) must fit inside."""
    deadlines = [p.deadline for p in batch if p.deadline is not None]
    return min(deadlines) if deadlines else None


class MicroBatcher:
    def __init__(
        self,
        engine: RecommendEngine,
        *,
        max_size: int = 32,
        window_ms: float = 2.0,
        max_inflight: int = 4,
        adaptive: bool = True,
        window_min_ms: float = 1.0,
        shed_queue_budget_ms: float = 0.0,
        shed_retry_after_s: float = 1.0,
        shed_soft_ratio: float = 0.6,
        shed_hard_ratio: float = 1.5,
        shed_retry_jitter: float = 0.5,
        eject_threshold: int = 0,
        probe_interval_s: float = 5.0,
        redispatch_max: int = 2,
        metrics=None,
        lag_monitor=None,
        forecaster=None,
        recorder=None,
    ):
        self.engine = engine
        self.max_size = max_size
        self.window_s = window_ms / 1e3
        # the app's SpanRecorder (None = batches are never traced): a
        # dispatched batch gets a trace of its own while it is active
        self.recorder = recorder
        # predictive serving (ISSUE 17): a serving.forecast
        # .TrafficForecaster, or None (the default — every forecast
        # touchpoint below is one is-None check, the zero-cost contract)
        self.forecaster = forecaster
        self.prewarm_total = 0
        self._prewarm_armed = True  # one pre-touch per ramp episode
        self.adaptive = adaptive
        self.window_min_s = min(window_min_ms / 1e3, self.window_s)
        self.shed_budget_s = shed_queue_budget_ms / 1e3
        self.shed_retry_after_s = shed_retry_after_s
        # runtime-health signal (observability.runtime.LoopLagMonitor):
        # folded into admission pressure so a host-scheduling stall the
        # queue projection can't see still escalates the ladder
        self._admission = AdmissionController(
            self.shed_budget_s,
            soft_ratio=shed_soft_ratio,
            hard_ratio=shed_hard_ratio,
            retry_after_s=shed_retry_after_s,
            retry_jitter=shed_retry_jitter,
            lag_source=lag_monitor.lag_s if lag_monitor is not None else None,
        )
        self.metrics = metrics
        self.shed_total = 0
        self.degrade_total = 0  # OverloadDegraded raised at admission
        # replica health: consecutive-failure circuit breaker (0 = off —
        # the legacy propagate-the-error behavior, which fakes and
        # single-replica harnesses rely on)
        self.eject_threshold = eject_threshold
        self.probe_interval_s = probe_interval_s
        self.redispatch_max = max(0, redispatch_max)
        # deadline propagation (ISSUE 18): engines that accept a
        # ``deadline`` kwarg get the batch's earliest pending deadline
        # (the mesh stamps it on peer frames as remaining budget).
        # Detected once here so fakes with the bare legacy signature
        # keep working untouched.
        self._engine_takes_deadline = _engine_takes(engine, "deadline")
        self._engine_takes_trace = _engine_takes(engine, "trace")
        self._consec_failures: dict[int, int] = {}
        self._ejected: dict[int, float] = {}  # idx -> perf_counter at eject
        self._probing: set[int] = set()  # half-open: one trial batch out
        self.eject_total = 0
        self.readmit_total = 0
        self.redispatch_total = 0
        # pipeline depth PER REPLICA; the aggregate bound is this times
        # the engine's live replica count (clamped: depth 0 would deadlock
        # the collector — "no pipelining" is depth 1, not 0)
        self.max_inflight = max(1, max_inflight)
        # priority queue of (priority, seq, pending): fresh arrivals ride
        # at priority 1, re-dispatched requests at 0 — they have waited
        # longest and must not starve behind new traffic (the async twin
        # front-inserts for the same reason). seq keeps FIFO within a
        # priority band and spares the heap from comparing _Pending.
        self._queue: "queue.PriorityQueue[tuple[int, int, _Pending]]" = (
            queue.PriorityQueue()
        )
        self._seq = itertools.count()
        # one completion lane PER REPLICA: (batch, finish_fn, t_dispatch,
        # t_slot, batch trace) tuples awaiting their device results, FIFO
        # within a lane — jax executes dispatches in order per device, so
        # completion order matches per lane (but NOT across lanes; a
        # single global lane would head-of-line-block fast devices behind
        # a slow one).
        # Lanes + their completer threads are created on first dispatch
        # to a replica index, by the collector thread only.
        self._completions: dict[int, "queue.Queue"] = {}
        # dispatched-but-uncompleted batches per replica, read by the
        # collector's idle-fast-path, the least-loaded pick, and the
        # shedding projection (a stale read is benign: worst case one
        # batch waits a window it didn't need, or one request
        # sheds/admits marginally early)
        self._inflight_by_replica: dict[int, int] = {}
        # rotation point for least-loaded ties: an all-idle replica set
        # must still spread consecutive batches across devices
        self._rr = 0
        # per-replica dispatch times of in-flight batches, FIFO: the
        # OLDEST entry's age is a live lower bound on the current device
        # time, which lets the shedding projection react to a
        # stalled/slow device before the first completion ever lands
        # (the EWMA alone is blind while cold)
        self._dispatch_times: dict[int, "collections.deque[float]"] = {}
        self._n_lock = threading.Lock()
        # collector blocks here while every replica's pipeline is full;
        # completions notify (replaces the old single-lane semaphore,
        # whose fixed depth couldn't track a replica count that appears
        # only at the engine's first load)
        self._pipe_cond = threading.Condition(self._n_lock)
        # controller state: a sliding window of arrival timestamps
        # (written under _rate_lock by every recommend() call) and a
        # device-batch-time EWMA (written by the completion thread only).
        # The window-mean gap, not a per-gap EWMA: closed-loop clients
        # arrive in bursts (a completed batch releases its waiters at
        # once) and a per-gap EWMA saturates near zero inside a burst,
        # collapsing the window and splitting the wave into undersized
        # batches; the mean over ~64 arrivals spans several bursts and
        # tracks the true rate.
        self._rate_lock = threading.Lock()
        self._arrivals: "collections.deque[float]" = collections.deque(
            maxlen=64
        )
        self._device_s_ewma: float | None = None
        self._collector = threading.Thread(
            target=self._collect_loop, daemon=True, name="kmls-microbatcher"
        )
        self._collector.start()

    # ---------- replica bookkeeping ----------

    def _n_replicas(self) -> int:
        return max(1, getattr(self.engine, "n_replicas", 1))

    def _total_inflight_locked(self) -> int:
        return sum(self._inflight_by_replica.values())

    def _n_healthy_locked(self, n: int) -> int:
        if self.eject_threshold <= 0:
            return n
        return n - sum(1 for i in self._ejected if i < n)

    def _n_effective_locked(self, n: int) -> int:
        """Capacity the shed projection and the idle fast path may COUNT
        ON — stricter than healthy (ISSUE 8 satellite): a replica inside
        a consecutive-failure run (breaker advancing but not yet
        tripped) is mid-incident and likely to fail its next batch too,
        and an ejected replica under a half-open probe is one trial
        batch, not a replica's worth of throughput (it stays in
        ``_ejected`` until the probe SUCCEEDS, so it is excluded here by
        construction). Counting either at full capacity over-admits
        exactly while the fleet is degraded — the old projection only
        discounted replicas already ejected."""
        if self.eject_threshold <= 0:
            return n
        return n - sum(
            1 for i in range(n)
            if i in self._ejected or self._consec_failures.get(i, 0) > 0
        )

    def _probe_due_locked(self, n: int, now: float) -> bool:
        return any(
            i < n and i not in self._probing
            and now - t >= self.probe_interval_s
            for i, t in self._ejected.items()
        )

    def ejected_replicas(self) -> list[int]:
        """Currently-ejected replica indices (readyz/metrics/tests)."""
        with self._n_lock:
            return sorted(self._ejected)

    def _pick_replica_locked(self, n: int) -> int:
        """Least-loaded HEALTHY replica index; ties broken by a rotating
        start so an idle fleet spreads consecutive batches instead of
        hammering replica 0. An ejected replica whose probe interval has
        elapsed gets ONE half-open trial batch instead. → -1 when every
        replica is ejected and no probe is due (total replica loss).
        Caller holds ``_n_lock``."""
        if self.eject_threshold > 0 and self._ejected:
            now = time.perf_counter()
            for i, t in self._ejected.items():
                if (
                    i < n and i not in self._probing
                    and now - t >= self.probe_interval_s
                ):
                    self._probing.add(i)
                    return i
        best, best_load = -1, None
        for off in range(n):
            i = (self._rr + off) % n
            if i in self._ejected:
                continue
            load = self._inflight_by_replica.get(i, 0)
            if best_load is None or load < best_load:
                best, best_load = i, load
        if best >= 0:
            self._rr = (best + 1) % n
        return best

    def _completion_lane(self, idx: int) -> "queue.Queue":
        """The collector is the only caller, so lane creation is
        single-writer; completer threads are per-lane and never die."""
        lane = self._completions.get(idx)
        if lane is None:
            lane = queue.Queue()
            self._completions[idx] = lane
            threading.Thread(
                target=self._complete_loop, args=(idx,), daemon=True,
                name=f"kmls-batch-completer-{idx}",
            ).start()
        return lane

    def per_replica_inflight(self) -> dict[int, int]:
        """Snapshot for tests/diagnostics."""
        with self._n_lock:
            return dict(self._inflight_by_replica)

    # ---------- admission ----------

    def projected_queue_wait_s(self) -> float:
        """Expected queue wait for a request enqueued NOW: batches ahead of
        it (in flight + already queued) times the per-batch device-time
        estimate, divided by the replica count — N devices drain the same
        queue N times faster, so the budget is against AGGREGATE capacity.
        The estimate is the completion EWMA, floored by the age of the
        oldest still-in-flight batch on any replica (a stalled device
        shows up in the age before any completion can move the EWMA).
        0 while there's no evidence at all — shedding needs measurements,
        not guesses."""
        now = time.perf_counter()
        device_s = self._device_s_ewma or 0.0
        n = self._n_replicas()
        with self._n_lock:
            inflight = self._total_inflight_locked()
            # neither ejected, half-open, nor mid-failure-run replicas
            # are capacity: shed capacity re-projects against the
            # replicas that can actually be EXPECTED to complete work,
            # so the budget tightens the moment a device starts failing,
            # not only once the breaker trips
            capacity = max(1, self._n_effective_locked(n))
            for lane in self._dispatch_times.values():
                if lane:
                    device_s = max(device_s, now - lane[0])
        if device_s <= 0.0:
            return 0.0
        queued_batches = self._queue.qsize() / max(self.max_size, 1)
        return (inflight + queued_batches) * device_s / capacity

    def utilization(self) -> float:
        """The HPA-compatible utilization signal (ISSUE 8), rendered at
        ``/metrics`` as the ``kmls_utilization`` gauge: the max of

        - **pipeline occupancy** — in-flight batches over the aggregate
          pipeline depth of the EFFECTIVE replica set (present even with
          shedding disabled), and
        - **queue pressure** — the admission controller's effective
          queue wait over the shed budget.

        1.0 means at capacity; shedding begins above it (the controller's
        degrade band starts at ``soft_ratio``), so an HPA target in the
        0.5–0.7 range scales the fleet out BEFORE any request degrades.
        Taking the max makes the signal rise with whichever saturates
        first: a device-bound fleet fills its pipelines, a queue-bound
        one grows its projected wait.

        With a forecaster attached (ISSUE 17, actuator b) the reactive
        max gains a bounded predictive lead: the reactive value scaled
        by the forecast growth ratio, clamped to [reactive, util_cap] —
        the HPA sees a ramp ``horizon_s`` early, the signal never drops
        below what is measured, and prediction alone never reports past
        the cap. The admission ladder does not read this value, so a
        wrong forecast can only over-provision, never shed."""
        reactive, led = self.utilization_parts()
        return led

    def utilization_parts(self) -> tuple[float, float]:
        """→ ``(reactive, forecast_led)``: the reactive occupancy/
        pressure max and the bounded forecast-led value actually
        exported as ``kmls_utilization`` (identical with no forecaster —
        the difference is the ``kmls_utilization_forecast`` gauge)."""
        n = self._n_replicas()
        with self._n_lock:
            inflight = self._total_inflight_locked()
            capacity = max(1, self._n_effective_locked(n))
        occupancy = inflight / (self.max_inflight * capacity)
        reactive = max(
            occupancy, self._admission.pressure(self.projected_queue_wait_s())
        )
        f = self.forecaster
        if f is None:
            return reactive, reactive
        return reactive, f.utilization_lead(reactive)

    def _arrival_gap_s(self) -> float | None:
        """Mean inter-arrival gap over the sliding window, or None before
        any rate evidence exists."""
        with self._rate_lock:
            n = len(self._arrivals)
            if n < 2:
                return None
            span = self._arrivals[-1] - self._arrivals[0]
        return span / (n - 1)

    def submit(
        self, seeds: list[str], deadline: float | None = None, trace=None,
    ) -> Future:
        """Non-blocking admission: shed-or-enqueue, → the request's
        Future. The async transport resolves it via a done-callback; the
        threaded transport blocks on it in :meth:`recommend`.
        ``deadline`` (perf_counter seconds) rides the pending entry
        through collection and dispatch; ``trace`` (a TraceContext, None
        when tracing is off) rides it so completion can record the
        queue/batch spans."""
        now = time.perf_counter()
        with self._rate_lock:
            self._arrivals.append(now)
        f = self.forecaster
        if f is not None:
            # predictive serving (ISSUE 17): every arrival feeds the
            # rate/mix model BEFORE the shed decision — demand the
            # ladder turns away is still demand the forecast must see.
            # The forecaster keeps its own clock; its window math never
            # mixes with these perf_counter timestamps.
            f.observe(seeds)
        if self.eject_threshold > 0 and self._ejected:
            # unlocked pre-check on _ejected: the healthy common case must
            # not pay a contended _n_lock acquisition per request (same
            # benign stale-read pattern as faults._armed — worst case one
            # request's rejection shifts by a dispatch)
            with self._n_lock:
                n = self._n_replicas()
                if (
                    self._n_healthy_locked(n) == 0
                    and not self._probe_due_locked(n, now)
                ):
                    # total replica loss, nothing to probe yet: degrade NOW
                    # instead of letting the request rot in the queue
                    raise NoHealthyReplicas(
                        "all serving replicas ejected; next probe in "
                        f"<= {self.probe_interval_s:.1f}s"
                    )
        decision, pressure = "admit", 0.0
        if self.shed_budget_s > 0:
            decision, pressure = self._admission.decide(
                self.projected_queue_wait_s()
            )
        t_admitted = 0.0
        if trace is not None:
            # the ladder's decision (and the breaker's pre-check above)
            t_admitted = time.perf_counter()
            trace.span("admit", now, t_admitted, {"decision": decision})
        if decision == "shed":
            with self._rate_lock:  # += from concurrent request threads
                self.shed_total += 1
            if self.metrics is not None:
                self.metrics.record_shed()
            # report the EFFECTIVE wait the decision was made on, not
            # the bare projection — an EWMA-driven shed right after a
            # burst would otherwise claim a sub-budget wait exceeded
            # the budget
            raise Overloaded(
                self._admission.retry_after_jittered_s(),
                pressure * self.shed_budget_s * 1e3,
            )
        if decision == "degrade":
            with self._rate_lock:
                self.degrade_total += 1
            # the app layer answers from the popularity fallback
            # (record_degraded("overload") happens there, next to the
            # deadline/replica-loss reasons)
            raise OverloadDegraded(pressure)
        pending = _Pending(
            seeds=seeds, future=Future(), t_enqueue=now, deadline=deadline,
            trace=trace, t_admitted=t_admitted,
        )
        self._queue.put((1, next(self._seq), pending))
        return pending.future

    def recommend(
        self, seeds: list[str], timeout: float = 30.0,
        deadline: float | None = None, trace=None,
    ) -> tuple[list[str], str]:
        future = self.submit(seeds, deadline=deadline, trace=trace)
        if deadline is not None:
            timeout = max(deadline - time.perf_counter(), 0.0)
        try:
            return future.result(timeout=timeout)
        except FuturesTimeout:
            if deadline is not None:
                # in-flight overrun (a stalled device, a kernel delayed
                # past the budget): same degradation contract as a
                # queue-side expiry
                raise DeadlineExceeded(
                    f"request exceeded its deadline budget after "
                    f"{timeout * 1e3:.0f}ms in flight"
                ) from None
            raise

    # ---------- collection ----------

    def _busy_window_s(self, batch: list[_Pending], now: float) -> float:
        """Collection wait while a batch is in flight: the fixed ceiling,
        or (adaptive) the time the observed arrival rate needs to fill the
        rest of the batch — so a nearly-full batch stops waiting for one
        straggler; always capped so the batch leader's queue wait stays
        inside the shed budget.

        With a ramp forecast (ISSUE 17, actuator a) the window is sized
        from the PREDICTED arrival gap instead of the trailing measured
        one when the prediction is tighter: the trailing window-mean gap
        lags a ramp by construction, so without the forecast the batcher
        holds early-ramp batches open for stragglers that are in fact
        about to arrive in bulk — sizing to the incoming rate keeps
        batches full-and-moving through the onset instead of discovering
        the rate through queue growth. The forecast can only SHRINK the
        estimated gap (min), so the shed-budget cap and the window floor
        bind exactly as reactively."""
        window = self.window_s
        if self.adaptive:
            gap = self._forecast_gap_s(self._arrival_gap_s())
            if gap is not None:
                need = (self.max_size - len(batch)) * gap
                window = min(self.window_s, max(self.window_min_s, need))
        if self.shed_budget_s > 0:
            leader_wait = now - batch[0].t_enqueue
            window = min(window, max(0.0, self.shed_budget_s - leader_wait))
        return window

    def _forecast_gap_s(self, gap: float | None) -> float | None:
        """Fold the forecast into the arrival-gap estimate (shared by
        both twins — no batcher state touched): under a predicted ramp,
        the tighter of the measured and predicted gaps; otherwise the
        measured gap unchanged. Also drives the once-per-episode shape
        pre-touch, since this runs per batch collection — not per
        request — on both twins."""
        f = self.forecaster
        if f is None:
            return gap
        ramping = f.ramp_predicted()
        self._note_ramp(ramping)
        if not ramping:
            return gap
        predicted = f.expected_gap_s()
        if predicted == float("inf"):
            return gap
        return predicted if gap is None else min(gap, predicted)

    def _note_ramp(self, ramping: bool) -> None:
        """Once per ramp EPISODE (the signal clearing re-arms it), kick
        the engine's largest-shape pre-touch on a daemon thread — off
        both the collection loop and the event loop, because the touch
        blocks on a device dispatch."""
        if not ramping:
            self._prewarm_armed = True
            return
        if not self._prewarm_armed:
            return
        self._prewarm_armed = False
        touch = getattr(self.engine, "prewarm_touch", None)
        if touch is None:
            return

        def _touch() -> None:
            try:
                self.prewarm_total += touch()
            except Exception:
                logger.exception("predictive pre-touch failed (ignored)")

        threading.Thread(
            target=_touch, daemon=True, name="kmls-prewarm"
        ).start()

    def _collect_loop(self) -> None:
        while True:
            _, _, first = self._queue.get()  # block for the batch leader
            batch = [first]
            # sweep everything already waiting, without blocking
            while len(batch) < self.max_size:
                try:
                    batch.append(self._queue.get_nowait()[2])
                except queue.Empty:
                    break
            with self._n_lock:
                # idle fast path fires while ANY EFFECTIVE replica sits
                # idle: waiting only buys amortization when every
                # dependable device already has work (an ejected,
                # half-open, or mid-failure-run replica isn't capacity —
                # counting it here over-admitted during re-admission
                # probes, dispatching real traffic windowless onto a
                # replica still being auditioned)
                device_idle = self._total_inflight_locked() < max(
                    1, self._n_effective_locked(self._n_replicas())
                )
            if not device_idle:
                # all replicas busy: the window buys amortization — keep
                # collecting up to it (a full batch exits immediately)
                now = time.perf_counter()
                deadline = now + self._busy_window_s(batch, now)
                while len(batch) < self.max_size:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    try:
                        batch.append(self._queue.get(timeout=remaining)[2])
                    except queue.Empty:
                        break
            # bound the pipeline AGGREGATELY: past max_inflight
            # undispatched-but-queued device calls PER replica, block here
            # (requests keep queueing upstream and land in bigger batches
            # — backpressure, not failure).
            t_slot = 0.0  # when the wait for a pipeline slot began, if any
            with self._pipe_cond:
                while (
                    self._total_inflight_locked()
                    >= self.max_inflight
                    * max(1, self._n_healthy_locked(self._n_replicas()))
                ):
                    if not t_slot:
                        t_slot = time.perf_counter()
                    self._pipe_cond.wait(timeout=1.0)
            # deadline check AFTER the capacity wait (which can block for
            # seconds under overload — exactly when deadlines matter): a
            # request already past its budget must not burn device time.
            # Outside the lock: expiry resolves futures, whose callbacks
            # take the cache's lock. The freed capacity can't be stolen —
            # this is the only dispatching thread; completions only add.
            batch = self._expire_overdue(batch)
            if not batch:
                continue
            # Reserve the least-loaded replica under the lock so the pick
            # and the accounting can't race a concurrent completion.
            with self._pipe_cond:
                n = self._n_replicas()
                if n > 1 or self.eject_threshold > 0:
                    idx = self._pick_replica_locked(n)
                else:
                    idx = 0
                if idx >= 0:
                    self._inflight_by_replica[idx] = (
                        self._inflight_by_replica.get(idx, 0) + 1
                    )
                    t_dispatch = time.perf_counter()
                    self._dispatch_times.setdefault(
                        idx, collections.deque()
                    ).append(t_dispatch)
            if idx < 0:
                # every replica ejected, no probe due: fail fast so the
                # app degrades instead of queueing dead work. Futures are
                # resolved OUTSIDE the lock — their done-callbacks (cache
                # singleflight retirement) take locks of their own.
                err = NoHealthyReplicas("all serving replicas ejected")
                for pending in batch:
                    if not pending.future.done():
                        pending.future.set_exception(err)
                continue
            btrace = _begin_batch_trace(
                self.recorder, t_dispatch, len(batch), idx
            )
            try:
                # the replica kwarg is passed only when there's a choice:
                # single-replica engines (fakes) keep the bare
                # signature they always had; the deadline
                # and trace kwargs only when the engine declared them
                # (deadline propagation across the mesh; batch spans)
                kwargs = {}
                if n > 1:
                    kwargs["replica"] = idx
                if self._engine_takes_deadline:
                    kwargs["deadline"] = _batch_deadline(batch)
                if btrace is not None and self._engine_takes_trace:
                    kwargs["trace"] = btrace
                finish = self.engine.recommend_many_async(
                    [p.seeds for p in batch], **kwargs
                )
            except Exception as exc:  # propagate, don't die
                with self._pipe_cond:
                    self._inflight_by_replica[idx] -= 1
                    lane = self._dispatch_times.get(idx)
                    if lane:
                        lane.pop()
                    self._pipe_cond.notify_all()
                if btrace is not None:
                    self.recorder.finish_batch(btrace, "error")
                self._on_replica_failure(idx, batch, exc)
                continue
            if self.metrics is not None:
                self.metrics.record_batch_size(len(batch))
            self._completion_lane(idx).put(
                (batch, finish, t_dispatch, t_slot, btrace)
            )

    def _complete_loop(self, idx: int) -> None:
        lane = self._completions[idx]
        while True:
            batch, finish, t_dispatch, t_slot, btrace = lane.get()
            try:
                results = finish()
                err = None
            except Exception as exc:  # propagate, don't die
                err = exc
            t_complete = time.perf_counter()
            # decrement BEFORE resolving futures: set_result unblocks the
            # client, and its immediate next request must not observe a
            # counter that still says busy (it would pay a full window
            # against an idle device — ping-pong traffic regression)
            device_s = t_complete - t_dispatch
            with self._pipe_cond:
                self._inflight_by_replica[idx] -= 1
                times = self._dispatch_times.get(idx)
                if times:
                    times.popleft()
                if err is None:
                    # EWMA updated under the lock: per-replica completer
                    # threads race here, and a torn read-modify-write
                    # would corrupt the shedding estimate
                    self._device_s_ewma = (
                        device_s if self._device_s_ewma is None
                        else (1 - _EWMA_ALPHA) * self._device_s_ewma
                        + _EWMA_ALPHA * device_s
                    )
                    self._note_replica_ok_locked(idx)
                self._pipe_cond.notify_all()
            if err is not None:
                if btrace is not None:
                    self.recorder.finish_batch(btrace, "error")
                self._on_replica_failure(idx, batch, err)
                continue
            # the batch LEADER's measured queue wait grounds the admission
            # controller's pressure estimate (it waited longest — the
            # worst wait an admitted request actually paid)
            self._admission.note_queue_wait(
                t_dispatch - batch[0].t_enqueue, now=t_complete
            )
            _record_request_spans(
                batch, btrace, t_dispatch, t_slot, idx, finish
            )
            for pending, result in zip(batch, results):
                if not pending.future.done():  # deadline may have expired it
                    pending.future.set_result(result)
            if btrace is not None:
                # finish() returned → futures set
                btrace.lap("resolve")
                self.recorder.finish_batch(btrace)
            if self.metrics is not None:
                for pending in batch:
                    self.metrics.record_attribution(
                        queue_wait_s=t_dispatch - pending.t_enqueue,
                        device_s=device_s,
                        e2e_s=t_complete - pending.t_enqueue,
                    )

    # ---------- replica health (threaded) ----------

    def _expire_overdue(self, batch: list[_Pending]) -> list[_Pending]:
        """Split out pendings whose deadline already passed; their futures
        fail with DeadlineExceeded (degraded at the app layer) and the
        survivors proceed to dispatch."""
        now = time.perf_counter()
        live: list[_Pending] = []
        for pending in batch:
            if pending.deadline is not None and now >= pending.deadline:
                if not pending.future.done():
                    pending.future.set_exception(DeadlineExceeded(
                        "deadline expired before dispatch"
                    ))
            else:
                live.append(pending)
        return live

    def _note_replica_ok_locked(self, idx: int) -> None:
        """Successful completion on ``idx`` (caller holds the lock): reset
        the breaker's consecutive-failure count; a succeeding half-open
        probe re-admits the replica."""
        if self.eject_threshold <= 0:
            return
        self._consec_failures[idx] = 0
        if idx in self._probing:
            self._probing.discard(idx)
            if self._ejected.pop(idx, None) is not None:
                self.readmit_total += 1
                if self.metrics is not None:
                    self.metrics.record_replica_readmitted()
                logger.info(
                    "replica %d re-admitted after successful probe", idx
                )

    def _on_replica_failure(
        self, idx: int, batch: list[_Pending], err: Exception
    ) -> None:
        """A batch failed on replica ``idx``: advance the circuit breaker
        (eject past the threshold; a failed half-open probe re-arms the
        timer), then RE-DISPATCH the batch's requests to the surviving
        replicas — bounded per-request retries — and only propagate the
        error to requests that are out of retries or out of replicas.
        Futures are resolved outside the lock (their done-callbacks take
        the cache's lock)."""
        healthy_other = False
        with self._pipe_cond:
            if self.eject_threshold > 0:
                if idx in self._probing:
                    # failed probe: stay ejected, timer re-armed
                    self._probing.discard(idx)
                    self._ejected[idx] = time.perf_counter()
                else:
                    fails = self._consec_failures.get(idx, 0) + 1
                    self._consec_failures[idx] = fails
                    if (
                        fails >= self.eject_threshold
                        and idx not in self._ejected
                    ):
                        self._ejected[idx] = time.perf_counter()
                        self.eject_total += 1
                        if self.metrics is not None:
                            self.metrics.record_replica_ejected()
                        logger.warning(
                            "replica %d ejected after %d consecutive "
                            "failures; re-admission probe every %.1fs",
                            idx, fails, self.probe_interval_s,
                        )
            n = self._n_replicas()
            # re-dispatch only with the breaker ON: disabled (threshold 0)
            # means the documented legacy contract — errors propagate
            # untouched, no silent retries tripling device work
            healthy_other = self.eject_threshold > 0 and any(
                i != idx and i not in self._ejected for i in range(n)
            )
            retriable: list[_Pending] = []
            dead: list[_Pending] = []
            for pending in batch:
                if healthy_other and pending.retries < self.redispatch_max:
                    pending.retries += 1
                    retriable.append(pending)
                else:
                    dead.append(pending)
            if retriable:
                self.redispatch_total += len(retriable)
                if self.metrics is not None:
                    self.metrics.record_redispatch(len(retriable))
        for pending in retriable:
            # priority 0: ahead of fresh arrivals — these have waited
            # longest (mirrors the async twin's front-insert)
            self._queue.put((0, next(self._seq), pending))
        for pending in dead:
            if not pending.future.done():
                pending.future.set_exception(err)


class AsyncMicroBatcher:
    """Loop-native twin of :class:`MicroBatcher` for the asyncio transport
    (serving/aioserver.py).

    Why a twin instead of putting the threaded pipeline behind the event
    loop: per-request cross-thread handoffs are exactly what the async
    front end exists to avoid. Profiled on a 2-core host, the threaded
    batcher driven from the loop spent most of its time re-acquiring the
    GIL — four thread hops per request (loop → collector → completer →
    per-request ``call_soon_threadsafe``), ~1.8 ms CPU each, capping the
    whole server near 550 QPS. Here admission, collection, and future
    resolution all run ON the loop (plain ints, no locks), the batch
    compute runs as ONE executor task, and the loop wakes once per BATCH.

    Policy-identical to :class:`MicroBatcher` — idle fast path, adaptive
    deadline-aware window, shed-before-budget, least-loaded multi-replica
    dispatch, queue/device attribution — with the same knobs; the policy
    methods mirror their threaded namesakes line for line, minus the
    locking (all state here is loop-confined: plain ints and dicts).
    """

    def __init__(
        self,
        engine: RecommendEngine,
        *,
        max_size: int = 32,
        window_ms: float = 2.0,
        max_inflight: int = 4,
        adaptive: bool = True,
        window_min_ms: float = 1.0,
        shed_queue_budget_ms: float = 0.0,
        shed_retry_after_s: float = 1.0,
        shed_soft_ratio: float = 0.6,
        shed_hard_ratio: float = 1.5,
        shed_retry_jitter: float = 0.5,
        eject_threshold: int = 0,
        probe_interval_s: float = 5.0,
        redispatch_max: int = 2,
        metrics=None,
        lag_monitor=None,
        forecaster=None,
        recorder=None,
    ):
        from concurrent.futures import ThreadPoolExecutor

        self.engine = engine
        self.max_size = max_size
        # the app's SpanRecorder, as in MicroBatcher (None = no batch
        # traces); _slot_wait_from is when a flush was first refused for
        # a full pipeline while it was active (None = not waiting)
        self.recorder = recorder
        self._slot_wait_from: float | None = None
        self.max_inflight = max(1, max_inflight)  # per replica
        # predictive serving (ISSUE 17), mirroring MicroBatcher: None =
        # every touchpoint is one is-None check (the zero-cost contract)
        self.forecaster = forecaster
        self.prewarm_total = 0
        self._prewarm_armed = True
        self.window_s = window_ms / 1e3
        self.adaptive = adaptive
        self.window_min_s = min(window_min_ms / 1e3, self.window_s)
        self.shed_budget_s = shed_queue_budget_ms / 1e3
        self.shed_retry_after_s = shed_retry_after_s
        # runtime health: a stalled event loop piles backpressure into
        # the socket backlog where the queue projection is blind (the
        # PR 8 postmortem); the controller folds the monitor's decayed
        # lag peak into pressure
        self._admission = AdmissionController(
            self.shed_budget_s,
            soft_ratio=shed_soft_ratio,
            hard_ratio=shed_hard_ratio,
            retry_after_s=shed_retry_after_s,
            retry_jitter=shed_retry_jitter,
            lag_source=lag_monitor.lag_s if lag_monitor is not None else None,
        )
        self.metrics = metrics
        self.shed_total = 0
        self.degrade_total = 0
        # replica health (mirrors MicroBatcher; loop-confined, no locks)
        self.eject_threshold = eject_threshold
        self.probe_interval_s = probe_interval_s
        self.redispatch_max = max(0, redispatch_max)
        # deadline propagation (ISSUE 18): engines that accept a
        # ``deadline`` kwarg get the batch's earliest pending deadline
        # (the mesh stamps it on peer frames as remaining budget).
        # Detected once here so fakes with the bare legacy signature
        # keep working untouched.
        self._engine_takes_deadline = _engine_takes(engine, "deadline")
        self._engine_takes_trace = _engine_takes(engine, "trace")
        self._consec_failures: dict[int, int] = {}
        self._ejected: dict[int, float] = {}
        self._probing: set[int] = set()
        self.eject_total = 0
        self.readmit_total = 0
        self.redispatch_total = 0
        self._pending: list[_Pending] = []
        self._inflight_by_replica: dict[int, int] = {}
        self._rr = 0
        self._dispatch_times: dict[int, "collections.deque[float]"] = {}
        self._arrivals: "collections.deque[float]" = collections.deque(maxlen=64)
        self._device_s_ewma: float | None = None
        self._flush_handle = None
        # the loop this batcher is confined to, recorded on first submit:
        # off-loop callers that must reach submit() — the app's post-delta
        # predictive pre-fetch (ISSUE 17) — hop here via
        # call_soon_threadsafe instead of calling in from their thread
        self._loop = None
        # finish() blocks (the results' device → host copies) — it must
        # run off-loop; pool depth = aggregate pipeline
        # depth. The replica count isn't known until the engine's first
        # load, so the pool is sized for the largest realistic replica set
        # (threads spawn on demand — headroom costs nothing) and the
        # ADMISSION bound in _flush clamps to this same number: a batch
        # the pool couldn't run concurrently must not be admitted, or its
        # executor queue wait would masquerade as device time in the
        # attribution and the shedding EWMA.
        self._executor_workers = min(32, self.max_inflight * 8)
        self._executor = ThreadPoolExecutor(
            max_workers=self._executor_workers,
            thread_name_prefix="kmls-abatch",
        )

    # ---------- replica bookkeeping (mirrors MicroBatcher, no locks) ----

    def _n_replicas(self) -> int:
        return max(1, getattr(self.engine, "n_replicas", 1))

    def _total_inflight(self) -> int:
        return sum(self._inflight_by_replica.values())

    def _n_healthy(self, n: int) -> int:
        if self.eject_threshold <= 0:
            return n
        return n - sum(1 for i in self._ejected if i < n)

    def _n_effective(self, n: int) -> int:
        """Mirrors MicroBatcher._n_effective_locked: capacity excludes
        ejected, half-open-probing, AND mid-failure-run replicas."""
        if self.eject_threshold <= 0:
            return n
        return n - sum(
            1 for i in range(n)
            if i in self._ejected or self._consec_failures.get(i, 0) > 0
        )

    def _probe_due(self, n: int, now: float) -> bool:
        return any(
            i < n and i not in self._probing
            and now - t >= self.probe_interval_s
            for i, t in self._ejected.items()
        )

    def ejected_replicas(self) -> list[int]:
        return sorted(self._ejected)

    def _pick_replica(self, n: int) -> int:
        """Mirrors MicroBatcher._pick_replica_locked: half-open probe for
        an ejected replica whose interval elapsed, else least-loaded
        healthy, else -1 (total replica loss)."""
        if self.eject_threshold > 0 and self._ejected:
            now = time.perf_counter()
            for i, t in self._ejected.items():
                if (
                    i < n and i not in self._probing
                    and now - t >= self.probe_interval_s
                ):
                    self._probing.add(i)
                    return i
        best, best_load = -1, None
        for off in range(n):
            i = (self._rr + off) % n
            if i in self._ejected:
                continue
            load = self._inflight_by_replica.get(i, 0)
            if best_load is None or load < best_load:
                best, best_load = i, load
        if best >= 0:
            self._rr = (best + 1) % n
        return best

    # ---------- policy (mirrors MicroBatcher, loop-confined) ----------

    def projected_queue_wait_s(self) -> float:
        now = time.perf_counter()
        device_s = self._device_s_ewma or 0.0
        for lane in self._dispatch_times.values():
            if lane:
                device_s = max(device_s, now - lane[0])
        if device_s <= 0.0:
            return 0.0
        queued_batches = len(self._pending) / max(self.max_size, 1)
        return (
            (self._total_inflight() + queued_batches)
            * device_s / max(1, self._n_effective(self._n_replicas()))
        )

    def utilization(self) -> float:
        """Mirrors MicroBatcher.utilization (loop-confined, no locks),
        forecast lead term included — see the threaded twin's contract."""
        reactive, led = self.utilization_parts()
        return led

    def utilization_parts(self) -> tuple[float, float]:
        """Mirrors MicroBatcher.utilization_parts."""
        capacity = max(1, self._n_effective(self._n_replicas()))
        occupancy = self._total_inflight() / (self.max_inflight * capacity)
        reactive = max(
            occupancy, self._admission.pressure(self.projected_queue_wait_s())
        )
        f = self.forecaster
        if f is None:
            return reactive, reactive
        return reactive, f.utilization_lead(reactive)

    def _arrival_gap_s(self) -> float | None:
        n = len(self._arrivals)
        if n < 2:
            return None
        return (self._arrivals[-1] - self._arrivals[0]) / (n - 1)

    # the forecast fold and per-episode pre-touch are state-light and
    # lock-free, so the twins SHARE one implementation instead of
    # mirroring it (the pre-touch daemon thread is equally legal from
    # the event loop — it never blocks the caller)
    _forecast_gap_s = MicroBatcher._forecast_gap_s
    _note_ramp = MicroBatcher._note_ramp

    def _busy_window_s(self, now: float) -> float:
        window = self.window_s
        if self.adaptive:
            gap = self._forecast_gap_s(self._arrival_gap_s())
            if gap is not None:
                need = (self.max_size - len(self._pending)) * gap
                window = min(self.window_s, max(self.window_min_s, need))
        if self.shed_budget_s > 0 and self._pending:
            leader_wait = now - self._pending[0].t_enqueue
            window = min(window, max(0.0, self.shed_budget_s - leader_wait))
        return window

    # ---------- admission (loop thread only) ----------

    def submit(
        self, seeds: list[str], deadline: float | None = None, trace=None,
    ) -> "asyncio.Future":
        import asyncio

        loop = asyncio.get_running_loop()
        if self._loop is None:
            self._loop = loop
        now = time.perf_counter()
        self._arrivals.append(now)
        f = self.forecaster
        if f is not None:
            # mirrors the threaded twin: demand is observed before the
            # shed decision, on the forecaster's own clock
            f.observe(seeds)
        if self.eject_threshold > 0 and self._ejected:
            n = self._n_replicas()
            if self._n_healthy(n) == 0 and not self._probe_due(n, now):
                raise NoHealthyReplicas(
                    "all serving replicas ejected; next probe in "
                    f"<= {self.probe_interval_s:.1f}s"
                )
        decision, pressure = "admit", 0.0
        if self.shed_budget_s > 0:
            decision, pressure = self._admission.decide(
                self.projected_queue_wait_s()
            )
        t_admitted = 0.0
        if trace is not None:
            # the ladder's decision (mirrors the threaded twin)
            t_admitted = time.perf_counter()
            trace.span("admit", now, t_admitted, {"decision": decision})
        if decision == "shed":
            self.shed_total += 1
            if self.metrics is not None:
                self.metrics.record_shed()
            # effective wait, mirroring the threaded twin
            raise Overloaded(
                self._admission.retry_after_jittered_s(),
                pressure * self.shed_budget_s * 1e3,
            )
        if decision == "degrade":
            self.degrade_total += 1
            raise OverloadDegraded(pressure)
        future = loop.create_future()
        pending = _Pending(
            seeds=seeds, future=future, t_enqueue=now, deadline=deadline,
            trace=trace, t_admitted=t_admitted,
        )
        self._pending.append(pending)
        if deadline is not None:
            # in-flight overruns included: the timer fires regardless of
            # where the request is stuck (queue, device, executor) and the
            # app degrades the DeadlineExceeded to a fallback answer.
            # Cancelled on completion — at QPS scale an uncancelled
            # ~1s timer per sub-ms answer piles thousands of live handles
            # (each pinning its pending) into the loop's heap.
            handle = loop.call_later(
                max(deadline - now, 0.0), self._expire, pending
            )
            future.add_done_callback(lambda _f: handle.cancel())
        if len(self._pending) >= self.max_size:
            self._flush(loop)  # full batch: dispatch now
        elif self._total_inflight() < max(
            1, self._n_effective(self._n_replicas())
        ):
            # idle fast path: some EFFECTIVE replica is free (ejected,
            # half-open, and mid-failure-run replicas aren't capacity)
            self._flush(loop)
        elif self._flush_handle is None:
            self._flush_handle = loop.call_later(
                self._busy_window_s(now), self._flush, loop
            )
        return future

    # ---------- dispatch / completion (loop thread only) ----------

    def _expire(self, pending: _Pending) -> None:
        """Deadline timer callback: fail the future (the app degrades it)
        unless the answer already landed. A later set_result is guarded by
        the done() checks in _flush/_resolve."""
        if not pending.future.done():
            pending.future.set_exception(
                DeadlineExceeded("request exceeded its deadline budget")
            )

    def _flush(self, loop) -> None:
        if self._flush_handle is not None:
            self._flush_handle.cancel()
            self._flush_handle = None
        # expired/cancelled requests must not burn device time: their
        # futures are already resolved (the _expire timer ran)
        if any(p.future.done() for p in self._pending):
            self._pending = [p for p in self._pending if not p.future.done()]
        if not self._pending:
            self._slot_wait_from = None  # nobody is left waiting
            return
        n = self._n_replicas()
        if self._total_inflight() >= min(
            self.max_inflight * max(1, self._n_healthy(n)),
            self._executor_workers,
        ):
            # aggregate pipeline full — or past what the executor pool
            # can actually run concurrently: the next completion
            # re-flushes and pending requests pile into a bigger batch
            # (backpressure, not failure)
            rec = self.recorder
            if (
                rec is not None and rec.active
                and self._slot_wait_from is None
            ):
                self._slot_wait_from = time.perf_counter()
            return
        batch = self._pending[: self.max_size]
        del self._pending[: len(batch)]
        t_slot = self._slot_wait_from or 0.0
        if not self._pending:
            self._slot_wait_from = None  # nobody is left waiting
        idx = self._pick_replica(n) if (n > 1 or self.eject_threshold > 0) else 0
        if idx < 0:
            # total replica loss, no probe due: degrade, don't dispatch
            err = NoHealthyReplicas("all serving replicas ejected")
            for pending in batch:
                if not pending.future.done():
                    pending.future.set_exception(err)
            return
        t_dispatch = time.perf_counter()
        btrace = _begin_batch_trace(
            self.recorder, t_dispatch, len(batch), idx
        )
        try:
            # replica kwarg only when there's a choice — single-replica
            # engines (fakes) keep the bare signature; deadline and
            # trace only when the engine declared them (mirroring the
            # threaded twin)
            kwargs = {}
            if n > 1:
                kwargs["replica"] = idx
            if self._engine_takes_deadline:
                kwargs["deadline"] = _batch_deadline(batch)
            if btrace is not None and self._engine_takes_trace:
                kwargs["trace"] = btrace
            finish = self.engine.recommend_many_async(
                [p.seeds for p in batch], **kwargs
            )
        except Exception as exc:  # propagate, don't die
            if btrace is not None:
                self.recorder.finish_batch(btrace, "error")
            self._on_replica_failure(idx, batch, exc, loop)
            if self._pending:
                loop.call_soon(self._flush, loop)
            return
        if self.metrics is not None:
            self.metrics.record_batch_size(len(batch))
        self._inflight_by_replica[idx] = (
            self._inflight_by_replica.get(idx, 0) + 1
        )
        self._dispatch_times.setdefault(
            idx, collections.deque()
        ).append(t_dispatch)
        def run_finish():
            try:
                return finish(), None
            except Exception as exc:
                return None, exc

        task = self._executor.submit(run_finish)
        task.add_done_callback(
            lambda f: loop.call_soon_threadsafe(
                self._complete, batch, f, t_dispatch, loop, idx, finish,
                t_slot, btrace,
            )
        )
        if self._pending:
            # overflow past max_size: keep draining
            loop.call_soon(self._flush, loop)

    def _complete(
        self, batch, task, t_dispatch: float, loop, idx: int, finish=None,
        t_slot: float = 0.0, btrace=None,
    ) -> None:
        # kmls-verify: allow[loopblock] — scheduled via
        # call_soon_threadsafe from the executor task's done-callback,
        # so the task is complete and result() returns immediately
        outcome = task.result()
        self._resolve(
            batch, outcome, t_dispatch, loop, idx, finish, t_slot, btrace
        )

    def _resolve(
        self, batch, outcome, t_dispatch: float, loop, idx: int, finish=None,
        t_slot: float = 0.0, btrace=None,
    ) -> None:
        results, err = outcome
        t_complete = time.perf_counter()
        self._inflight_by_replica[idx] -= 1
        lane = self._dispatch_times.get(idx)
        if lane:
            lane.popleft()
        if err is not None:
            if btrace is not None:
                self.recorder.finish_batch(btrace, "error")
            self._on_replica_failure(idx, batch, err, loop)
        else:
            self._note_replica_ok(idx)
            device_s = t_complete - t_dispatch
            self._device_s_ewma = (
                device_s if self._device_s_ewma is None
                else (1 - _EWMA_ALPHA) * self._device_s_ewma
                + _EWMA_ALPHA * device_s
            )
            # leader's measured queue wait grounds the admission pressure
            # (mirrors the threaded completer)
            if batch:
                self._admission.note_queue_wait(
                    t_dispatch - batch[0].t_enqueue, now=t_complete
                )
            _record_request_spans(
                batch, btrace, t_dispatch, t_slot, idx, finish
            )
            for pending, result in zip(batch, results):
                if not pending.future.done():
                    pending.future.set_result(result)
            if btrace is not None:
                # finish() returned → futures set: the executor→loop hop
                btrace.lap("resolve")
                self.recorder.finish_batch(btrace)
            if self.metrics is not None:
                for pending in batch:
                    self.metrics.record_attribution(
                        queue_wait_s=t_dispatch - pending.t_enqueue,
                        device_s=device_s,
                        e2e_s=t_complete - pending.t_enqueue,
                    )
        if self._pending and self._flush_handle is None:
            # mirror the threaded collector waking on a completion: the
            # freed pipeline slot dispatches the waiting batch immediately
            self._flush(loop)

    # ---------- replica health (loop-confined twin of the threaded
    # helpers; no locks — all state is loop-owned) ----------

    def _note_replica_ok(self, idx: int) -> None:
        if self.eject_threshold <= 0:
            return
        self._consec_failures[idx] = 0
        if idx in self._probing:
            self._probing.discard(idx)
            if self._ejected.pop(idx, None) is not None:
                self.readmit_total += 1
                if self.metrics is not None:
                    self.metrics.record_replica_readmitted()
                logger.info(
                    "replica %d re-admitted after successful probe", idx
                )

    def _on_replica_failure(self, idx: int, batch, err, loop) -> None:
        if self.eject_threshold > 0:
            if idx in self._probing:
                # failed probe: stay ejected, timer re-armed
                self._probing.discard(idx)
                self._ejected[idx] = time.perf_counter()
            else:
                fails = self._consec_failures.get(idx, 0) + 1
                self._consec_failures[idx] = fails
                if fails >= self.eject_threshold and idx not in self._ejected:
                    self._ejected[idx] = time.perf_counter()
                    self.eject_total += 1
                    if self.metrics is not None:
                        self.metrics.record_replica_ejected()
                    logger.warning(
                        "replica %d ejected after %d consecutive failures; "
                        "re-admission probe every %.1fs",
                        idx, fails, self.probe_interval_s,
                    )
        n = self._n_replicas()
        # breaker off = legacy propagate-the-error contract (see the
        # threaded twin)
        healthy_other = self.eject_threshold > 0 and any(
            i != idx and i not in self._ejected for i in range(n)
        )
        retriable: list[_Pending] = []
        dead: list[_Pending] = []
        for pending in batch:
            if pending.future.done():  # deadline timer beat us to it
                continue
            if healthy_other and pending.retries < self.redispatch_max:
                pending.retries += 1
                retriable.append(pending)
            else:
                dead.append(pending)
        if retriable:
            self.redispatch_total += len(retriable)
            if self.metrics is not None:
                self.metrics.record_redispatch(len(retriable))
            # front of the queue: re-dispatched requests have waited
            # longest and must not starve behind fresh arrivals
            self._pending[:0] = retriable
            loop.call_soon(self._flush, loop)
        for pending in dead:
            pending.future.set_exception(err)
