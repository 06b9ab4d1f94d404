"""Per-layer metric readers. A metric's file names one under ``reader.kind``
as ``module:function`` (these are ``benchmark.readers:read_<kind>``; a later
metric may bring a module of its own), called with the metric's ``reader``
and the run's context.

A reader that finds nothing to read returns None and the metric is left out
of the line; none ever returns 0 for a share of a roofline.

- ``prom``: the server's ``/metrics``, scraped at the window's start and
  end. ``stat`` is ``level`` (value at the end), ``delta`` (end - start,
  summed over the matching label sets) or ``ratio`` (sum of ``numer`` deltas
  over sum of ``denom`` deltas). ``times`` scales; ``divide_by_config``
  divides by one of the configuration's sizes.
- ``log``: lines of the server's log written inside the window that match
  ``pattern``, counted.
- ``sum``: the sum of the readers under ``of`` (missing parts count 0; all
  missing → None).
- ``trace``: the device trace; ``module`` is a regular expression on the XLA
  module's name, ``reduce`` is ``mean_ms``, ``total_ms`` or ``count``.
- ``roofline``: the least time the chip could take for what was asked of
  the module while the trace was open (``count`` names the function that counts the
  operation, ``benchmark.counts:<name>``) over the module's total time in the trace, in percent.
- ``harness``: a clock the harness keeps itself (``field``).
"""

from __future__ import annotations

import re

from . import counts, manifest


def parse_prom(text: str) -> dict[tuple[str, frozenset], float]:
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^([A-Za-z_:][\w:]*)(\{[^}]*\})? (\S+)$", line)
        if not m:
            continue
        labels = frozenset(re.findall(r'(\w+)="([^"]*)"', m.group(2) or ""))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            pass
    return out


def _select(scrape: dict, spec: dict) -> list[float]:
    want = set((spec.get("labels") or {}).items())
    return [
        v for (name, labels), v in scrape.items()
        if name == spec["series"] and want <= set(labels)
    ]


def _delta(ctx, spec: dict) -> float | None:
    end = _select(ctx["prom_end"], spec)
    if not end:
        return None
    return sum(end) - sum(_select(ctx["prom_start"], spec))


def read_prom(reader: dict, ctx: dict) -> float | None:
    stat = reader["stat"]
    if stat == "level":
        found = _select(ctx["prom_end"], reader)
        value = found[0] if found else None
    elif stat == "delta":
        value = _delta(ctx, reader)
    elif stat == "ratio":
        numer = [_delta(ctx, s) for s in reader["numer"]]
        denom = [_delta(ctx, s) for s in reader["denom"]]
        if any(x is None for x in numer + denom) or sum(denom) <= 0:
            return None
        value = sum(numer) / sum(denom)
    else:
        raise ValueError(f"unknown prom stat {stat!r}")
    if value is None:
        return None
    value *= float(reader.get("times", 1.0))
    if "divide_by_config" in reader:
        value /= float(ctx["config"][reader["divide_by_config"]])
    return value


def read_log(reader: dict, ctx: dict) -> float | None:
    return float(len(re.findall(reader["pattern"], ctx["log_in_window"], re.M)))


def read_sum(reader: dict, ctx: dict) -> float | None:
    parts = [read(r, ctx) for r in reader["of"]]
    if all(p is None for p in parts):
        return None
    return float(sum(p or 0.0 for p in parts))


def _module_times(reader: dict, ctx: dict) -> list[float]:
    trace = ctx.get("trace")
    if not trace:
        return []
    pattern = re.compile(reader["module"])
    return [s for name, runs in trace["modules"].items() if pattern.search(name) for s in runs]


def read_trace(reader: dict, ctx: dict) -> float | None:
    times = _module_times(reader, ctx)
    if not times:
        return None
    how = reader["reduce"]
    if how == "mean_ms":
        return 1e3 * sum(times) / len(times)
    if how == "total_ms":
        return 1e3 * sum(times)
    if how == "count":
        return float(len(times))
    raise ValueError(f"unknown trace reduction {how!r}")


def read_roofline(reader: dict, ctx: dict) -> float | None:
    times = _module_times(reader, ctx)
    lens = ctx.get("traced_seed_lens")
    if not times or not lens:
        return None
    peaks = ctx["peaks"].get(ctx["device_kind"])
    if peaks is None:
        raise SystemExit(f"no peaks for device kind {ctx['device_kind']!r} in peaks.json")
    flops, bytes_ = manifest.resolve(reader["count"])(ctx["config"], lens, len(times))
    least, _ = counts.least_seconds(flops, bytes_, peaks, reader.get("matmul_dtype", "bfloat16"))
    return 100.0 * least / sum(times)


def read_harness(reader: dict, ctx: dict) -> float | None:
    return ctx["harness"].get(reader["field"])


def read(reader: dict, ctx: dict) -> float | None:
    return manifest.resolve(reader["kind"])(reader, ctx)
