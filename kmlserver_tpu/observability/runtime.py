"""Runtime-health collection: event-loop lag.

The PR 8 postmortem (ROADMAP "load-adaptive serving") names the blind
spot this closes: whatever blocks the asyncio event loop (then, a serve
kernel computed ON the loop; any callback that overstays, now that every
batch's ``finish()`` runs on the executor) keeps requests in the socket
accept backlog where the admission controller's queue-wait projection
cannot see them — the projection measures the batcher's queue, and
nothing reaches the batcher while the loop is wedged.

:class:`LoopLagMonitor` measures the stall with a **timer-drift tick**:
``loop.call_later`` re-arms every ``interval_s``; the difference between
when the tick was due and when it actually ran IS the time something
blocked the loop (the same technique node.js exposes as
``eventLoopDelay``). A thread variant (:meth:`start_thread`) gives the
threaded transport host-scheduling visibility with the same signal
shape. :meth:`note` folds one measured blockage in; the ticks call it,
and so may any caller that has timed a stall itself.

The signal is a peak-hold with exponential decay (half-life
``half_life_s``): one 200 ms stall registers immediately and fades over
~a second instead of flapping per tick. It is exported at ``/metrics``
as ``kmls_loop_lag_ms`` and — the part that closes the blind spot —
folded into :class:`~..serving.batcher.AdmissionController` pressure
via ``lag_source``, so a wedged loop escalates the admission ladder
(degrade → shed) exactly like a saturated queue would. All state is
plain floats, single-writer-ish with benign races — no locks on any
hot path (the controller's documented discipline).
"""

from __future__ import annotations

import math
import threading
import time


class LoopLagMonitor:
    """Peak-hold, time-decaying lag estimate for one event loop (or the
    host scheduler, under the thread driver)."""

    def __init__(self, interval_s: float = 0.05, half_life_s: float = 1.0):
        self.interval_s = max(interval_s, 0.005)
        self.half_life_s = max(half_life_s, 0.05)
        self._lag = 0.0
        self._noted_at = 0.0
        self.ticks = 0  # drift-tick count (diagnostics/tests)
        self._running = False
        self._thread: threading.Thread | None = None

    # ---------- signal ----------

    def note(self, lag_s: float, now: float | None = None) -> None:
        """Fold one measured blockage (seconds) into the estimate.
        Peak-hold: a new stall larger than the decayed current value
        replaces it; smaller ones leave the decaying peak in place (the
        admission ladder must see the worst recent stall, not a mean
        diluted by healthy ticks)."""
        if lag_s <= 0.0:
            return
        now = time.perf_counter() if now is None else now
        if lag_s >= self._decayed(now):
            self._lag = lag_s
            self._noted_at = now

    def _decayed(self, now: float) -> float:
        if self._lag <= 0.0:
            return 0.0
        age = max(now - self._noted_at, 0.0)
        return self._lag * math.exp(-age * math.log(2) / self.half_life_s)

    def lag_s(self, now: float | None = None) -> float:
        """The current decayed lag estimate (seconds). Cheap enough for
        the admission hot path: two floats and an exp."""
        return self._decayed(time.perf_counter() if now is None else now)

    # ---------- drivers ----------

    def start_on_loop(self, loop) -> None:
        """Arm the drift tick on an asyncio loop (call from the loop
        thread). Re-arms itself forever; daemon-equivalent — the loop's
        shutdown cancels nothing because each handle is one-shot and the
        process exits with the loop."""
        if self._running:
            return
        self._running = True
        expected = [time.perf_counter() + self.interval_s]

        def tick() -> None:
            now = time.perf_counter()
            self.ticks += 1
            self.note(max(now - expected[0], 0.0), now=now)
            expected[0] = now + self.interval_s
            loop.call_later(self.interval_s, tick)

        loop.call_later(self.interval_s, tick)

    def start_thread(self) -> threading.Thread | None:
        """Thread driver for the threaded transport: the same drift
        signal measured against ``time.sleep`` — host scheduling stalls
        (CPU starvation, GIL convoy) show up the same way loop stalls
        do. Daemon thread; runs for the process lifetime. Re-entry
        safe like :meth:`start_on_loop`: the thread is immortal, so a
        second driver would double-count ticks for the process
        lifetime with no way to stop either."""
        if self._running:
            return self._thread
        self._running = True

        def loop_() -> None:
            while True:
                expected = time.perf_counter() + self.interval_s
                time.sleep(self.interval_s)
                now = time.perf_counter()
                self.ticks += 1
                self.note(max(now - expected, 0.0), now=now)

        thread = threading.Thread(
            target=loop_, daemon=True, name="kmls-loop-lag"
        )
        self._thread = thread
        thread.start()
        return thread
