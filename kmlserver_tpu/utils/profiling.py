"""Tracing / profiling — the subsystem the reference does NOT have.

The reference's entire observability for compute cost is a wall-clock bracket
around rule generation printed to stdout (reference:
machine-learning/main.py:264,306-308) plus the disabled sweep harness's
per-support durations (machine-learning/main.py:462-473). SURVEY.md §5
prescribes the TPU-native replacement: ``jax.profiler`` device traces plus
``block_until_ready``-bracketed host timers, while preserving the printed
``Time elapsed in rule generation`` line for log parity.

Two layers, both zero-cost when disabled:

- :func:`trace_session` — a ``jax.profiler`` trace of a whole region, dumped
  to ``$KMLS_PROFILE_DIR`` (TensorBoard/XProf-readable; contains XLA device
  timelines, HLO names, HBM allocations). Enabled only when the env var is
  set: profiling must be opt-in in production serving.
- :class:`PhaseTimer` — named host-side phase timings with explicit
  ``block_until_ready`` discipline (a device call isn't "done" at dispatch;
  timing without a sync fence measures nothing). Each phase is also wrapped
  in a ``jax.profiler.TraceAnnotation`` so host phases line up against the
  device timeline inside the dumped trace.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Iterator

import jax

PROFILE_DIR_ENV = "KMLS_PROFILE_DIR"


def profile_dir() -> str | None:
    """The trace dump directory, or None when profiling is disabled."""
    raw = os.getenv(PROFILE_DIR_ENV)
    return raw if raw else None


@contextlib.contextmanager
def trace_session(label: str) -> Iterator[None]:
    """``jax.profiler`` trace of the enclosed region when profiling is
    enabled (``$KMLS_PROFILE_DIR`` set), else a no-op. Safe to nest inside —
    but not around — another active trace."""
    target = profile_dir()
    if target is None:
        yield
        return
    path = os.path.join(target, label)
    os.makedirs(path, exist_ok=True)
    with jax.profiler.trace(path):
        yield


def start_capture(label: str, seconds: float, recorder=None) -> "object":
    """Timed on-demand capture (ISSUE 12, the ``/debug/profile``
    endpoint): run :func:`trace_session` for ``seconds`` on a daemon
    thread → the thread (join it to wait; the endpoint doesn't). The
    trace covers whatever the process executes while the window is open
    — for a live server, the serving kernels under real traffic. A no-op
    thread when profiling is disabled (the caller gates on
    :func:`profile_dir`, this is belt-and-braces).

    With ``recorder`` (the app's ``observability.trace.SpanRecorder``)
    the capture also puts the host's spans on the device trace's clock
    (ISSUE 26). The recorder is in capture mode from before the session
    starts until after it stops, so every batch whose programs the
    session sees has a trace; inside the session the thread emits a
    clock anchor right after the start, once a second, and right before
    the stop, and after each anchor runs the clock probe
    (``observability.trace.ClockProbe``: a one-element program named
    ``kmls_clock_probe`` on every local device, timed on the host before
    the call and after ``block_until_ready``; compiled on every device
    before the recorder and the session open, so nothing compiles in the
    capture); and the spans land beside the ``.xplane.pb`` as
    ``kmls_spans.jsonl``. Two log lines bracket it: ``profile capture
    open: dir=<session directory>`` before anything starts, ``profile
    capture closed: dir=<the span file's directory> ...`` once the file
    is written (a reader that looked too early for the second finds the
    file under the first's directory)."""
    import logging
    import threading

    def run() -> None:
        if recorder is None or profile_dir() is None:
            with trace_session(label):
                time.sleep(max(seconds, 0.0))
            return
        from ..observability import trace as spantrace

        logging.getLogger("kmlserver_tpu.serving").info(
            "profile capture open: dir=%s seconds=%g",
            os.path.join(profile_dir() or "", label), seconds,
        )
        anchors: list[tuple[int, int]] = []
        probes: list[list[int]] = []
        # compiled on every device before the session opens: no compile
        # lands inside the capture
        probe = spantrace.ClockProbe()
        recorder.capture_begin()
        try:
            with trace_session(label):
                deadline = time.monotonic() + max(seconds, 0.0)
                anchors.append(spantrace.emit_clock_anchor())
                probes += probe()
                while True:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    time.sleep(min(1.0, left))
                    anchors.append(spantrace.emit_clock_anchor())
                    probes += probe()
        finally:
            traces = recorder.capture_end()
        _write_capture_spans(label, seconds, anchors, traces, probes)

    thread = threading.Thread(
        target=run, daemon=True, name="kmls-profile-capture"
    )
    thread.start()
    return thread


def _write_capture_spans(
    label: str, seconds: float, anchors: list, traces: list[dict],
    probes: list,
) -> None:
    """``kmls_spans.jsonl`` beside the capture's ``.xplane.pb`` (the
    session's own directory where the profiler wrote none): a header
    line with the anchors' ``perf_counter_ns`` pairs and the clock
    probes' ``[device id, before, after]`` (``device_probes``), then one
    line per request trace and per batch trace, spans with absolute
    ``perf_counter`` nanoseconds. Then the one log line a reader finds
    the capture by."""
    import glob
    import json
    import logging

    from ..io.artifacts import atomic_write_text
    from ..observability.trace import SPANS_FILENAME

    session_dir = os.path.join(profile_dir() or "", label)
    dumps = glob.glob(
        os.path.join(session_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    target = (
        os.path.dirname(max(dumps, key=os.path.getmtime))
        if dumps else session_dir
    )
    requests = sum(1 for t in traces if t["kind"] == "request")
    spans = sum(len(t["spans"]) for t in traces)
    header = {
        "kind": "header", "version": 1, "label": label,
        "seconds": seconds, "clock": "perf_counter_ns",
        "anchors": [list(pair) for pair in anchors],
        "device_probes": probes,
        "requests": requests, "batches": len(traces) - requests,
        "spans": spans,
    }
    lines = [json.dumps(header)] + [json.dumps(t) for t in traces]
    logger = logging.getLogger("kmlserver_tpu.serving")
    try:
        atomic_write_text(
            os.path.join(target, SPANS_FILENAME), "\n".join(lines) + "\n",
            durable=False,
        )
    except OSError:
        # the profiler's own dump stands; only the host's spans are lost
        logger.exception("profile capture: %s not written", SPANS_FILENAME)
        return
    logger.info(
        "profile capture closed: dir=%s requests=%d batches=%d spans=%d",
        target, requests, len(traces) - requests, spans,
    )


class PhaseTimer:
    """Named phase timings with device-sync fencing.

    >>> t = PhaseTimer()
    >>> with t.phase("pair_counts", counts):   # fences on `counts`
    ...     counts = pair_counts(x)
    """

    def __init__(self) -> None:
        self.phases: dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str, *fence: Any) -> Iterator[None]:
        """Time the enclosed block under ``name``. Any ``fence`` values given
        at entry are block_until_ready'd FIRST so queued prior device work
        isn't billed to this phase; the block's own device outputs should be
        fenced by the block itself (or be host work)."""
        for f in fence:
            jax.block_until_ready(f)
        with jax.profiler.TraceAnnotation(f"kmls:{name}"):
            t0 = time.perf_counter()
            yield
            self.phases[name] = self.phases.get(name, 0.0) + (
                time.perf_counter() - t0
            )

    def report(self) -> str:
        """One log line, reference-log style."""
        return format_phases(self.phases)


def format_phases(phases: dict[str, float]) -> str:
    parts = ", ".join(f"{k} {v:.3f}s" for k, v in phases.items())
    return f"phase timings: {parts}" if parts else "phase timings: (none)"
