"""Per-layer readers over the program's own spans: which host stage each
idle second of the chip belongs to.

While a ``/debug/profile`` capture is open the server traces every request
and every batch, and when it closes writes ``kmls_spans.jsonl`` beside the
``.xplane.pb`` and logs ``profile capture closed: dir=<dir> ...``. That line
(in ``ctx["log_in_window"]``) is how these readers find the capture. The
server logs it after the profiler has written its own file, so a harness
that read the log the moment that file settled may not have it: the span
file is then looked for under the session directory that ``profile capture
open: dir=<dir>``, logged before the capture started, names. A reader that
finds no span file returns None, and the metric is left out of the line.

- ``read_span``: the span file alone. ``trace`` is ``request`` or ``batch``,
  ``spans`` the span names; ``stat`` is ``mean_ms`` (mean over the traces
  that have one of the spans, of their summed durations), ``self_mean_ms``
  (the same of their self times: duration less what child spans cover) or
  ``attr_mean`` (mean of the attribute ``attr`` over the named spans).
- ``read_idle``: the span file and the capture's device planes. The spans
  are on ``perf_counter``'s clock; the capture's host plane holds the clock
  anchors the server emitted (annotations named ``kmls/clock:<ns>``), and
  the header their ``perf_counter_ns`` pairs, so a line through the first
  and last anchor maps one clock onto the other (their change of offset is
  the drift). The anchors are host events, and the device planes' clock is
  not quite the host plane's: on a v5e capture the programs read 1.6-4 ms
  EARLIER than the calls that dispatched them. ``causal_shift`` therefore
  moves the device planes later by the smallest amount at which the most
  serving programs lie inside a batch's ``dispatch`` start .. last ``fetch``
  end (a program cannot start before it was dispatched, nor end after it was
  fetched); it is a lower bound of the true skew, and is printed.
  Between the first and the last anchor, every instant at which
  no ``XLA Modules`` event runs on any device plane is idle, and is put
  down to one ``bucket``: ``awaiting`` (no request open), else the layer of
  the open span that stands first in ``PRECEDENCE`` (stages ahead of the
  device before stages behind it, a span before the span around it), else
  ``unclaimed`` (inside a batch, under none of its children). ``stat`` is
  ``pct_of_idle``, ``pct_of_open_idle`` (of the idle time with a request
  open) or ``ms_per_request``. Without a device plane (the CPU smoke) it
  returns None. It prints the table it read from to standard error, once.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
import time

from . import trace as trace_mod


SPANS_FILENAME = "kmls_spans.jsonl"
ANCHOR_PREFIX = "kmls/clock:"
CLOSED_LINE = re.compile(r"profile capture closed: dir=(\S+) ")
OPEN_LINE = re.compile(r"profile capture open: dir=(\S+) ")
# the capture thread's last word: the spans are written, or could not be
DONE_LINE = re.compile(r"profile capture( closed: dir=|: \S+ not written)")

# which open span an idle instant is put down to, first match first: what
# stands between a request and the chip (nearest the chip first), then what
# follows the chip's work, then the spans around them
PRECEDENCE = (
    "dispatch", "stage", "queue", "admit", "cache", "parse",
    "fetch_rules", "fetch_embed", "compose", "resolve", "respond", "write",
    "batch", "request",
)
BUCKET_OF = {
    "parse": "front_end", "cache": "front_end", "respond": "front_end",
    "write": "front_end", "request": "front_end",
    "admit": "batcher", "queue": "batcher", "resolve": "batcher",
    "stage": "engine", "dispatch": "engine", "fetch_rules": "engine",
    "fetch_embed": "engine", "compose": "engine",
    "batch": "unclaimed",
}
BUCKETS = ("awaiting", "front_end", "batcher", "engine", "unclaimed")
# the programs a batch dispatches: each execution has to start inside some
# batch's dispatch..fetch interval
SERVE_MODULES = re.compile(r"recommend_batch|embed_topk")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---- the span file ----

def _memo(ctx: dict, key: str, make):
    """Several metrics read one capture: what was read rides the run's
    context, so it is read once a run."""
    cache = ctx.setdefault("_spans", {})
    if key not in cache:
        cache[key] = make()
    return cache[key]


def find_spans(ctx: dict) -> str | None:
    return _memo(ctx, "path", lambda: _find_spans(ctx))


def _find_spans(ctx: dict) -> str | None:
    text = ctx.get("log_in_window") or ""
    closed, opened = CLOSED_LINE.findall(text), OPEN_LINE.findall(text)
    if closed and os.path.isfile(os.path.join(closed[-1], SPANS_FILENAME)):
        return os.path.join(closed[-1], SPANS_FILENAME)
    if opened:
        files = glob.glob(os.path.join(opened[-1], "plugins", "profile", "*", SPANS_FILENAME))
        files += glob.glob(os.path.join(opened[-1], SPANS_FILENAME))
        if files:
            return max(files, key=os.path.getmtime)
    return None


def load(path: str) -> tuple[dict, list[dict], list[dict]]:
    """→ (header, request traces, batch traces)."""
    with open(path, encoding="utf-8") as fh:
        lines = [json.loads(line) for line in fh if line.strip()]
    header = lines[0] if lines and lines[0].get("kind") == "header" else {}
    traces = [t for t in lines if t.get("kind") != "header"]
    return (
        header,
        [t for t in traces if t.get("kind") == "request"],
        [t for t in traces if t.get("kind") == "batch"],
    )


def self_ms(trace: dict, span: dict) -> float:
    """A span's duration less the part of it that its child spans cover."""
    start = span["start_ms"]
    end = start + span["duration_ms"]
    children = [
        (max(start, c["start_ms"]), min(end, c["start_ms"] + c["duration_ms"]))
        for c in trace["spans"] if c.get("parent") == span["id"]
    ]
    return span["duration_ms"] - trace_mod._union([(s, e) for s, e in children if e > s])


def read_span(reader: dict, ctx: dict) -> float | None:
    path = find_spans(ctx)
    if path is None:
        return None
    _, requests, batches = _memo(ctx, "file", lambda: load(path))
    traces = requests if reader["trace"] == "request" else batches
    names, stat = set(reader["spans"]), reader["stat"]
    values = []
    for trace in traces:
        spans = [s for s in trace["spans"] if s["name"] in names]
        if not spans:
            continue
        if stat == "mean_ms":
            values.append(sum(s["duration_ms"] for s in spans))
        elif stat == "self_mean_ms":
            values.append(sum(self_ms(trace, s) for s in spans))
        elif stat == "attr_mean":
            values += [
                float(s["attrs"][reader["attr"]]) for s in spans
                if reader["attr"] in s.get("attrs", {})
            ]
        else:
            raise ValueError(f"unknown span stat {stat!r}")
    return sum(values) / len(values) if values else None


# ---- the two clocks ----

class ClockMap:
    """``perf_counter_ns`` → the capture's nanoseconds, by the anchors.
    ``anchors`` are the header's ``[named, opened]`` pairs, ``seen`` maps
    an anchor's named number to its annotation's start in the capture. The
    annotation opened between the two readings, so their midpoint stands
    for it; the line goes through the first and the last anchor seen."""

    def __init__(self, anchors: list, seen: dict[int, float]):
        pairs = sorted(
            ((named + opened) / 2.0, float(seen[named]))
            for named, opened in anchors if named in seen
        )
        if not pairs:
            raise ValueError("no clock anchor of the span file is in the capture")
        self.n = len(pairs)
        (self.p0, self.t0), (self.p1, self.t1) = pairs[0], pairs[-1]
        self.offset_ns = self.t0 - self.p0
        self.drift_ns = (self.t1 - self.p1) - self.offset_ns
        self.slope = (self.t1 - self.t0) / (self.p1 - self.p0) if self.p1 > self.p0 else 1.0
        self.slack_ns = max((opened - named) / 2.0 for named, opened in anchors)

    def __call__(self, perf_ns: float) -> float:
        return self.t0 + (perf_ns - self.p0) * self.slope


def read_capture(xplane: str) -> tuple[dict[int, float], list[tuple[float, float, str]]]:
    """→ (anchor number → its start in the capture, the device planes'
    ``XLA Modules`` events as (start, end, name)), all in nanoseconds."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    seen: dict[int, float] = {}
    modules: list[tuple[float, float, str]] = []
    for plane in ProfileData.from_file(xplane).planes:
        device = plane.name.startswith("/device:") and "CUSTOM" not in plane.name.upper()
        for line in plane.lines:
            if device:
                if line.name == "XLA Modules":
                    modules += [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events if e.duration_ns > 0
                    ]
                continue
            for e in line.events:
                name = e.name
                if name.startswith(ANCHOR_PREFIX):
                    seen[int(name[len(ANCHOR_PREFIX):])] = e.start_ns
    return seen, modules


# ---- attribution ----

def batch_intervals(batches: list[dict], to_capture) -> list[tuple[float, float]]:
    """Each batch's ``dispatch`` start .. last ``fetch`` end on the
    capture's clock, ascending: where its programs can have run."""
    out = []
    for trace in batches:
        at = {s["name"]: s for s in trace["spans"]}
        if "dispatch" in at:
            last = at.get("fetch_embed") or at.get("fetch_rules") or at["dispatch"]
            out.append((to_capture(at["dispatch"]["t_start_ns"]), to_capture(last["t_end_ns"])))
    return sorted(out)


def _room(intervals: list[tuple[float, float]], start: float, end: float) -> float | None:
    """How far [start, end] could still move right inside one of the
    (ascending, possibly overlapping: the pipeline is four deep) intervals
    that hold it; None where none does."""
    i = bisect.bisect_right(intervals, (start, float("inf")))
    rooms = [b - end for a, b in intervals[max(0, i - 8):i] if a <= start and end <= b]
    return max(rooms) if rooms else None


def causal_shift(
    programs: list[tuple[float, float]], intervals: list[tuple[float, float]],
    limit_ns: float = 50e6,
) -> tuple[float, int, float]:
    """→ (the smallest shift >= 0 of the device planes at which the most
    ``programs`` lie inside one of ``intervals``, how many then do, and how
    much further the planes could move before one of those leaves its
    interval: the true skew lies within that room). The candidates are 0
    and each program's distance to the next interval's start, up to
    ``limit_ns``."""
    starts = [a for a, _ in intervals]
    candidates = {0.0}
    for start, _ in programs:
        i = bisect.bisect_left(starts, start)
        if i < len(starts) and starts[i] - start <= limit_ns:
            candidates.add(starts[i] - start)

    def rooms(shift: float) -> list[float]:
        found = (_room(intervals, a + shift, b + shift) for a, b in programs)
        return [r for r in found if r is not None]

    best = max(candidates, key=lambda shift: (len(rooms(shift)), -shift))
    inside = rooms(best)
    return best, len(inside), min(inside, default=0.0)


def attribute(
    requests: list[dict], batches: list[dict], modules: list[tuple[float, float, str]],
    to_capture, window: tuple[float, float],
) -> dict:
    """Sweep the window once: → idle seconds by span name and by bucket,
    the busy seconds, the requests in the window, and how many serving
    programs started inside a batch's dispatch..fetch interval."""
    w0, w1 = window
    events: list[tuple[float, int, str]] = []

    def add(start: float, end: float, name: str) -> None:
        start, end = max(start, w0), min(end, w1)
        if end > start:
            events.append((start, 1, name))
            events.append((end, -1, name))

    for start, end, _ in modules:
        add(start, end, "_busy")
    n_requests = 0
    for trace in requests + batches:
        for s in trace["spans"]:
            add(to_capture(s["t_start_ns"]), to_capture(s["t_end_ns"]), s["name"])
        root = trace["spans"][0]
        if trace["kind"] == "request":
            n_requests += int(
                to_capture(root["t_end_ns"]) > w0 and to_capture(root["t_start_ns"]) < w1
            )
    events.sort()
    open_now: dict[str, int] = {}
    idle_by_span: dict[str, float] = {}
    busy_ns, at = 0.0, w0
    for t, step, name in events:
        if t > at:
            if open_now.get("_busy", 0) > 0:
                busy_ns += t - at
            else:
                holder = next((n for n in PRECEDENCE if open_now.get(n, 0) > 0), None)
                if holder is None:
                    others = [n for n, c in open_now.items() if c > 0 and n != "_busy"]
                    holder = sorted(others)[0] if others else "awaiting"
                idle_by_span[holder] = idle_by_span.get(holder, 0.0) + (t - at)
            at = t
        open_now[name] = open_now.get(name, 0) + step
    if w1 > at and not open_now.get("_busy", 0):
        idle_by_span["awaiting"] = idle_by_span.get("awaiting", 0.0) + (w1 - at)
    idle_by_bucket = dict.fromkeys(BUCKETS, 0.0)
    for name, ns in idle_by_span.items():
        bucket = "awaiting" if name == "awaiting" else BUCKET_OF.get(name, "unclaimed")
        idle_by_bucket[bucket] += ns / 1e9
    intervals = batch_intervals(batches, to_capture)
    serving = [(s, e) for s, e, name in modules if SERVE_MODULES.search(name) and w0 <= s < w1]
    started_inside = sum(_room(intervals, s, s) is not None for s, _ in serving)
    return {
        "window_s": (w1 - w0) / 1e9, "busy_s": busy_ns / 1e9,
        "idle_s": sum(idle_by_bucket.values()),
        "idle_by_span_s": {k: v / 1e9 for k, v in idle_by_span.items()},
        "idle_by_bucket_s": idle_by_bucket, "requests": n_requests,
        "serving_programs": len(serving), "started_inside_a_batch": started_inside,
    }


def analyse(path: str, header: dict, requests: list[dict], batches: list[dict]) -> dict | None:
    """The capture beside the span file, attributed → the numbers, or None
    where it has no device plane. Prints what it read."""
    captures = glob.glob(os.path.join(os.path.dirname(path), "*.xplane.pb"))
    if not captures or not header.get("anchors"):
        return None
    t_read = time.monotonic()
    seen, modules = read_capture(max(captures, key=os.path.getmtime))
    t_read = time.monotonic() - t_read
    if not modules:
        log(f"[spans] {path}: the capture has no XLA Modules event on a device plane")
        return None
    try:
        clock = ClockMap(header["anchors"], seen)
    except ValueError as exc:
        log(f"[spans] {path}: {exc}")
        return None
    # programs that began before the first anchor were dispatched by a batch
    # formed before the capture traced any: they bound nothing
    shift, _, room = causal_shift(
        [(a, b) for a, b, name in modules
         if SERVE_MODULES.search(name) and clock.t0 <= a < clock.t1],
        batch_intervals(batches, clock),
    )
    modules = [(a + shift, b + shift, name) for a, b, name in modules]
    out = attribute(requests, batches, modules, clock, (clock.t0, clock.t1))
    out["clock"] = {
        "anchors": clock.n, "offset_ns": clock.offset_ns, "drift_ns": clock.drift_ns,
        "slack_ns": clock.slack_ns, "device_shift_ns": shift, "device_shift_room_ns": room,
    }
    log(f"[spans] {path}: {len(requests)} request traces, {len(batches)} batch traces; "
        f"{clock.n} anchors, offset {clock.offset_ns:.0f} ns, drift {clock.drift_ns:.0f} ns over "
        f"{out['window_s']:.3f} s (an anchor is good to {clock.slack_ns:.0f} ns); capture read in "
        f"{t_read:.1f} s")
    log(f"[spans] device planes moved {shift:.0f} ns later: the least that puts the most serving "
        f"programs inside the batch that dispatched them ({room:.0f} ns more would push one out)")
    log(f"[spans] window {out['window_s']:.3f} s: busy {out['busy_s']:.3f} s, idle "
        f"{out['idle_s']:.3f} s; {out['requests']} requests; {out['started_inside_a_batch']} of "
        f"{out['serving_programs']} serving programs started inside a batch's dispatch..fetch")
    for name, s in sorted(out["idle_by_span_s"].items(), key=lambda kv: -kv[1]):
        log(f"[spans]   idle under {name:<12} {s:10.6f} s")
    log("[spans] idle by bucket: " + ", ".join(
        f"{b} {out['idle_by_bucket_s'][b]:.6f} s" for b in BUCKETS))
    return out


def read_idle(reader: dict, ctx: dict) -> float | None:
    path = find_spans(ctx)
    if path is None:
        return None
    found = _memo(ctx, "idle", lambda: analyse(path, *_memo(ctx, "file", lambda: load(path))))
    if not found:
        return None
    idle = found["idle_by_bucket_s"]
    seconds, stat = idle[reader["bucket"]], reader["stat"]
    if stat == "ms_per_request":
        return 1e3 * seconds / found["requests"] if found["requests"] else None
    base = found["idle_s"] if stat == "pct_of_idle" else found["idle_s"] - idle["awaiting"]
    if stat not in ("pct_of_idle", "pct_of_open_idle"):
        raise ValueError(f"unknown idle stat {stat!r}")
    return 100.0 * seconds / base if base > 0 else None
