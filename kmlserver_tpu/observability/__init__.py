"""Observability layer: per-request span tracing with tail-based
retention (``trace``, ISSUE 9), runtime-health collection — event-loop
lag — feeding the admission ladder (``runtime``),
mining-side textfile telemetry (``jobmetrics``), device-truth cost
attribution — per-kernel MFU/roofline, memory and compile telemetry
(``costmodel``, ISSUE 12) — and multi-window SLO burn rates (``slo``).
Serving metrics exposition itself stays in ``serving/metrics.py``;
everything here joins its ``METRIC_REGISTRY``."""

from __future__ import annotations

from .costmodel import KERNEL_COST_SPECS, CostModel
from .runtime import LoopLagMonitor
from .slo import SloTracker
from .trace import SpanRecorder, TraceContext

__all__ = [
    "CostModel",
    "KERNEL_COST_SPECS",
    "LoopLagMonitor",
    "SloTracker",
    "SpanRecorder",
    "TraceContext",
]
