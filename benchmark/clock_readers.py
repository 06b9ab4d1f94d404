"""Per-layer readers that place each device plane by the clock skew the
program measured, where ``spans.causal_shift`` only bounds it from below.

While a capture is open the server's capture thread runs, after each clock
anchor, a one-element program named ``kmls_clock_probe`` on every local
device, and writes ``[device id, perf_counter_ns before the call,
perf_counter_ns after block_until_ready]`` for each into the span file's
header (``device_probes``). The probe's execution on that device's plane
lies between the two readings, so with the anchors' ``ClockMap`` putting
the readings on the capture's host plane, a probe that ran from ``d0`` to
``d1`` on the plane bounds the plane's shift (what is added to the plane's
times to put them on the host plane's clock) to
``[clock(before) - d0, clock(after) - d1]``. A plane's shift is the
intersection of its probes' intervals, placed at its midpoint (where the
intersection is empty, the probes disagree by more than they can be off:
the midpoint of the two nearest bounds, flagged in the printout).

- ``read_idle_measured``: chip-idle time under the union of the spans
  ``spans`` of the batch traces, with every device plane moved by its own
  measured shift; ``stat`` ``ms_per_batch`` divides by the batch traces
  that have one of those spans inside the anchors' window. Returns None
  where the span file has no ``device_probes`` (a program without the
  probe: the metric is left out, not read by ``causal_shift``), where the
  capture has no device plane (the CPU smoke), or where a plane's probes
  are not found on it. It prints, once: each plane's shift interval and width
  beside ``causal_shift``'s value, and the chip-idle time under
  ``handoff`` (the hop from ``dispatch`` to ``finish()``, which no reader
  claims) against the ``unclaimed`` bucket.
"""

from __future__ import annotations

import glob
import os
import re

from . import spans

PROBE_MODULE = re.compile(r"kmls_clock_probe")
# a probe's execution is looked for this near its host readings, and probes
# this close run at one anchor: the skew is milliseconds (causal_shift
# searches as far), the anchors a second apart
GROUP_NS = 50e6
PLANE_ORDINAL = re.compile(r"^/device:[^:]+:(\d+)$")


def read_planes(xplane: str) -> tuple[dict[int, float], dict[str, list[tuple[float, float, str]]]]:
    """→ (anchor number → its start in the capture, each device plane's
    ``XLA Modules`` events as (start, end, name) by plane name), in
    nanoseconds."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    seen: dict[int, float] = {}
    planes: dict[str, list[tuple[float, float, str]]] = {}
    for plane in ProfileData.from_file(xplane).planes:
        device = plane.name.startswith("/device:") and "CUSTOM" not in plane.name.upper()
        for line in plane.lines:
            if device:
                if line.name == "XLA Modules":
                    planes.setdefault(plane.name, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events if e.duration_ns > 0
                    )
                continue
            for e in line.events:
                if e.name.startswith(spans.ANCHOR_PREFIX):
                    seen[int(e.name[len(spans.ANCHOR_PREFIX):])] = e.start_ns
    return seen, planes


def plane_device(name: str) -> int | None:
    """The device id a plane shows (``/device:TPU:2`` → 2), or None."""
    m = PLANE_ORDINAL.match(name)
    return int(m.group(1)) if m else None


def _rounds(probes: list) -> list[list]:
    """One device's probes, oldest first, split where the capture thread
    moved on to the next anchor: the probes it ran back to back at one
    anchor form a group."""
    groups: list[list] = []
    for probe in sorted(probes, key=lambda p: p[1]):
        if groups and probe[1] - groups[-1][-1][2] <= GROUP_NS:
            groups[-1].append(probe)
        else:
            groups.append([probe])
    return groups


def plane_shift(probes: list, events: list[tuple[float, float, str]], clock) -> dict | None:
    """One plane's probes (``[device, before, after]`` of its device) and
    its events → {lo, hi, shift, width, matched, narrowest}, in ns, or None
    where none of the probes is found on the plane. A group of probes run
    back to back is paired in order with the probe programs that ran within
    ``GROUP_NS`` of it; a group whose count the plane does not show (an
    execution the profiler lost) bounds nothing."""
    runs = sorted((a, b) for a, b, name in events if PROBE_MODULE.search(name))
    bounds = []
    for group in _rounds(probes):
        first, last = clock(group[0][1]), clock(group[-1][2])
        ran = [r for r in runs if first - GROUP_NS <= r[0] <= last + GROUP_NS]
        if len(ran) == len(group):
            bounds += [
                (clock(before) - d0, clock(after) - d1)
                for (_, before, after), (d0, d1) in zip(group, ran)
            ]
    if not bounds:
        return None
    lo, hi = max(b[0] for b in bounds), min(b[1] for b in bounds)
    return {
        "lo": lo, "hi": hi, "shift": (lo + hi) / 2.0, "width": hi - lo,
        "matched": len(bounds), "narrowest": min(b[1] - b[0] for b in bounds),
    }


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def idle_within(covered: list[tuple[float, float]], busy: list[tuple[float, float]]) -> float:
    """Nanoseconds of the union of ``covered`` during which no interval of
    ``busy`` runs."""
    covered, busy = _merge(covered), _merge(busy)
    total, j = 0.0, 0
    for s, e in covered:
        total += e - s
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < e:
            total -= min(e, busy[k][1]) - max(s, busy[k][0])
            k += 1
    return total


def span_intervals(traces: list[dict], names: set[str], clock, window) -> tuple[list, int]:
    """→ (the named spans' intervals on the capture's clock, clipped to
    ``window``; how many traces have one inside it)."""
    w0, w1 = window
    out, n = [], 0
    for trace in traces:
        inside = [
            (max(w0, clock(s["t_start_ns"])), min(w1, clock(s["t_end_ns"])))
            for s in trace["spans"] if s["name"] in names
        ]
        inside = [(a, b) for a, b in inside if b > a]
        out += inside
        n += bool(inside)
    return out, n


def measure(path: str, header: dict, requests: list[dict], batches: list[dict], ctx: dict) -> dict | None:
    """The capture beside the span file with each plane at its measured
    shift → {shifts (by plane), busy (the placed programs' intervals),
    clock, window, handoff_idle_s, unclaimed_s}, or None. Prints what it
    read, once a run (``read_idle_measured`` memoizes it)."""
    probes = header.get("device_probes")
    if not probes or not header.get("anchors"):
        return None
    captures = glob.glob(os.path.join(os.path.dirname(path), "*.xplane.pb"))
    if not captures:
        return None
    seen, planes = read_planes(max(captures, key=os.path.getmtime))
    if not planes:
        spans.log(f"[clock] {path}: no device plane in the capture; no skew is measured")
        return None
    try:
        clock = spans.ClockMap(header["anchors"], seen)
    except ValueError as exc:
        spans.log(f"[clock] {path}: {exc}")
        return None
    shifts = {
        name: plane_shift([p for p in probes if p[0] == plane_device(name)], events, clock)
        for name, events in planes.items()
    }
    missed = sorted(name for name, s in shifts.items() if s is None)
    if missed:
        # a plane placed by guesswork would undo what the probes measure
        spans.log(f"[clock] {path}: no probe of the header found on {', '.join(missed)}")
        return None
    modules = [
        (a + shifts[name]["shift"], b + shifts[name]["shift"], ev)
        for name, events in planes.items() for a, b, ev in events
    ]
    busy = [(a, b) for a, b, _ in modules]
    window = (clock.t0, clock.t1)
    # what read_idle reads: the planes moved by causal_shift alone
    causal = spans._memo(ctx, "idle", lambda: spans.analyse(path, header, requests, batches))
    causal_ns = causal["clock"]["device_shift_ns"] if causal else None
    for name, s in sorted(shifts.items()):
        state = "" if s["width"] >= 0 else " (EMPTY: the probes disagree; midpoint taken)"
        beside = "none" if causal_ns is None else f"{causal_ns:.0f} ns"
        spans.log(
            f"[clock] {name}: {s['matched']} probes, shift [{s['lo']:.0f}, {s['hi']:.0f}] ns, "
            f"width {s['width']:.0f} ns{state} (narrowest probe {s['narrowest']:.0f} ns); "
            f"causal_shift {beside}"
        )
    placed = spans.attribute(requests, batches, modules, clock, window)
    handoff, n_handoff = span_intervals(batches, {"handoff"}, clock, window)
    handoff_s = idle_within(handoff, busy) / 1e9
    unclaimed = placed["idle_by_bucket_s"]["unclaimed"]
    spans.log(
        f"[clock] idle under handoff {handoff_s:.6f} s ({n_handoff} batches); the unclaimed bucket "
        f"{unclaimed:.6f} s with the planes at their measured shift"
        + (f", {causal['idle_by_bucket_s']['unclaimed']:.6f} s at causal_shift" if causal else "")
    )
    return {"shifts": shifts, "busy": busy, "clock": clock, "window": window,
            "handoff_idle_s": handoff_s, "unclaimed_s": unclaimed}


def read_idle_measured(reader: dict, ctx: dict) -> float | None:
    path = spans.find_spans(ctx)
    if path is None:
        return None
    header, requests, batches = spans._memo(ctx, "file", lambda: spans.load(path))
    found = spans._memo(ctx, "measured", lambda: measure(path, header, requests, batches, ctx))
    if not found:
        return None
    if reader["stat"] != "ms_per_batch":
        raise ValueError(f"unknown measured-idle stat {reader['stat']!r}")
    names = set(reader["spans"])
    covered, n = span_intervals(batches, names, found["clock"], found["window"])
    if not n:
        return None
    idle_ns = idle_within(covered, found["busy"])
    spans.log(f"[clock] idle under {'|'.join(sorted(names))}: {idle_ns / 1e9:.6f} s over {n} "
              f"batches, {idle_ns / 1e6 / n:.4f} ms a batch")
    return idle_ns / 1e6 / n
