"""Reduce a profiler capture (``.xplane.pb``) to device busy time, module
times, the top device operations and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` only, after the server has exited
(nothing here starts a backend). Device planes are those named
``/device:...``; their ``XLA Modules`` line holds one event per executed
program (named by the XLA module, e.g. ``jit__recommend_batch_impl(...)``)
and their ``XLA Ops`` line one per operation. A capture with no such events
on a device plane is an :class:`EmptyCapture`, whatever else it holds. Only
the CPU smoke test (``allow_host=True``) reads a CPU backend's capture, which
has no device plane: there the ``ThunkExecutor::Execute`` events of the PjRt
client's threads stand for program executions, un-named, so busy time can be
read and module metrics cannot.
"""

from __future__ import annotations

import glob
import os
import re


class EmptyCapture(RuntimeError):
    pass


def find_capture(profile_dir: str) -> str | None:
    files = glob.glob(os.path.join(profile_dir, "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _union(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def _base(name: str) -> str:
    return re.sub(r"\(.*$", "", name).strip()


def _top(seconds_by_name: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:n]]


def reduce(path: str, allow_host: bool = False) -> dict:
    """→ {"busy_s", "window_s", "modules": {name: [seconds...]},
    "device_ops": [[name, s]], "idle_gaps": [[name, s]]}. Raises
    :class:`EmptyCapture` where no device plane holds an operation; with
    ``allow_host`` (the smoke test) a CPU client's thunks stand in."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    device = [p for p in planes if p.name.startswith("/device:") and "CUSTOM" not in p.name.upper()]
    per_device = []
    modules: dict[str, list[float]] = {}
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    t_min, t_max = float("inf"), float("-inf")

    def note(events, named: bool):
        nonlocal t_min, t_max
        spans = []
        for name, s, d in events:
            if d <= 0:
                continue
            spans.append((s, s + d, name))
            t_min, t_max = min(t_min, s), max(t_max, s + d)
            if named:
                modules.setdefault(_base(name), []).append(d / 1e9)
        spans.sort()
        for (s0, e0, n0), (s1, _, n1) in zip(spans, spans[1:]):
            if s1 > e0:
                key = f"after {_base(n0)} before {_base(n1)}"
                gaps[key] = gaps.get(key, 0.0) + (s1 - e0) / 1e9
        return [(s, e) for s, e, _ in spans]

    for plane in device:
        lines = {line.name: line for line in plane.lines}
        mod_line, op_line = lines.get("XLA Modules"), lines.get("XLA Ops")
        busy_spans = []
        if mod_line is not None:
            busy_spans = note(
                [(e.name, e.start_ns, e.duration_ns) for e in mod_line.events], named=True
            )
        if op_line is not None:
            op_spans = []
            for e in op_line.events:
                if e.duration_ns > 0:
                    ops[e.name] = ops.get(e.name, 0.0) + e.duration_ns / 1e9
                    op_spans.append((e.start_ns, e.start_ns + e.duration_ns))
            if mod_line is None:
                busy_spans = op_spans
        if busy_spans:
            per_device.append(_union(busy_spans) / 1e9)
    if not per_device and allow_host:
        for plane in planes:
            for line in plane.lines:
                if "XLAPjRtCpuClient" not in line.name:
                    continue
                events = [
                    (e.name, e.start_ns, e.duration_ns) for e in line.events
                    if e.name == "ThunkExecutor::Execute"
                ]
                spans = note(events, named=False)
                if spans:
                    per_device.append(_union(spans) / 1e9)
        per_device = [sum(per_device)] if per_device else []
    if not per_device or t_max <= t_min:
        raise EmptyCapture(f"no device operation in the capture {path}")
    return {
        "busy_s": sum(per_device) / len(per_device),
        "window_s": (t_max - t_min) / 1e9,
        "modules": modules,
        "device_ops": _top(ops or {k: sum(v) for k, v in modules.items()}),
        "idle_gaps": _top(gaps),
    }


def describe(path: str, per_line: int = 4) -> str:
    """Planes, lines and a few events of a capture: look at one by hand
    before trusting a reduction (``python3 -m benchmark.trace <file>``)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            names: dict[str, int] = {}
            for e in events:
                names[_base(e.name)] = names.get(_base(e.name), 0) + 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:per_line]
            out.append(f"  LINE {line.name!r}: {len(events)} events; most common {common}")
            for e in events[:per_line]:
                out.append(f"    {e.name[:100]!r} start_ns={e.start_ns} duration_ns={e.duration_ns}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_capture(target)
    print(describe(target))
    print({k: v for k, v in reduce(target, allow_host=True).items() if k != "modules"})
