"""``benchmark/spans.py`` against a small hand-written ``kmls_spans.jsonl``
and synthetic device intervals, and the toy smoke with the new metrics.

The worked case (milliseconds on ``perf_counter``'s clock; the capture's
clock is 1,000 ms ahead): two requests share one batch, the device runs the
rule program 17.5-24 and the embedding program 24.5-32, the anchors stand at
0.001 and 100.001. Every idle stretch is listed where ``EXPECTED_MS`` is built.
"""

import json
import os
import sys

import pytest
from conftest import ROOT, run_cell

sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark import spans  # noqa: E402

MS = 1_000_000  # ns
OFFSET_NS = 1_000 * MS


def trace(kind: str, trace_id: str, root: tuple, children: list, attrs=None) -> dict:
    """A trace as the server writes it, from (name, start_ms, end_ms[, attrs])."""
    t0 = root[0]
    rows = [(kind, *root)] + children
    out = []
    for i, (name, start, end, *rest) in enumerate(rows):
        span = {
            "id": i, "parent": None if i == 0 else 0, "name": name,
            "start_ms": start - t0, "duration_ms": end - start,
            "t_start_ns": int(start * MS), "t_end_ns": int(end * MS),
        }
        if rest:
            span["attrs"] = rest[0]
        out.append(span)
    return {"kind": kind, "trace_id": trace_id, "status": "ok", "duration_ms": root[1] - t0,
            "attrs": attrs or {}, "spans": out}


REQUEST_A = trace("request", "a", (10, 40), [
    ("parse", 10, 11), ("cache", 11, 12), ("admit", 12, 13),
    ("queue", 13, 15, {"batch": 2, "slot_wait_ms": 0.5}),
    ("batch", 15, 36, {"batch_id": 1, "replica": 0}), ("respond", 36, 38), ("write", 38, 39),
])
REQUEST_B = trace("request", "b", (12, 42), [
    ("parse", 12, 13), ("cache", 13, 13.5), ("admit", 13.5, 14),
    ("queue", 14, 15, {"batch": 2, "slot_wait_ms": 0.0}),
    ("batch", 15, 36, {"batch_id": 1, "replica": 0}), ("respond", 38, 40), ("write", 40, 41),
])
BATCH = trace("batch", "batch-1", (15, 36.5), [
    ("stage", 15.5, 17), ("dispatch", 17, 18), ("fetch_rules", 19, 25),
    ("fetch_embed", 25, 33), ("compose", 33, 35), ("resolve", 35, 36.5),
], {"batch_id": 1, "requests": 2, "rows": 2, "length": 8, "seeds_real": 9})
ANCHORS = [[0, 2_000], [100 * MS, 100 * MS + 2_000]]  # [named, opened]
MODULES = [
    (OFFSET_NS + 17.5 * MS, OFFSET_NS + 24 * MS, "jit__recommend_batch_impl(123)"),
    (OFFSET_NS + 24.5 * MS, OFFSET_NS + 32 * MS, "jit__embed_topk_impl(456)"),
]
# the anchors' annotations opened at the midpoint of their two readings
SEEN = {0: OFFSET_NS + 1_000, 100 * MS: OFFSET_NS + 100 * MS + 1_000}

EXPECTED_MS = {
    # 0.001-10 and 42-100.001: no request open
    "awaiting": 10 + 58,
    # parse 10-11, cache 11-12 (A alone); A's respond 36.5-38 once the batch
    # has resolved; respond 38-40 (B's, over A's write and A's uncovered
    # tail); B's write 40-41; B's uncovered tail 41-42
    "front_end": 1 + 1 + 1.5 + 2 + 1 + 1,
    # 12-13 A's admit over B's parse; 13-15 a queue open; resolve 35-36.5
    # (over A's respond from 36)
    "batcher": 1 + 2 + 1.5,
    # stage 15.5-17, dispatch 17-17.5, fetch_rules 24-24.5 (between the two
    # programs), fetch_embed 32-33, compose 33-35
    "engine": 1.5 + 0.5 + 0.5 + 1 + 2,
    # 15-15.5: the batch is formed and the engine has not been entered
    "unclaimed": 0.5,
}


@pytest.fixture
def capture(tmp_path, monkeypatch):
    """A capture directory with the worked span file, an (empty) xplane
    beside it whose content ``read_capture`` is made to return, and the log
    line that names it → the readers' context."""
    with open(tmp_path / spans.SPANS_FILENAME, "w", encoding="utf-8") as fh:
        header = {"kind": "header", "version": 1, "anchors": ANCHORS, "requests": 2, "batches": 1}
        for line in (header, REQUEST_A, BATCH, REQUEST_B):
            fh.write(json.dumps(line) + "\n")
    (tmp_path / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(spans, "read_capture", lambda path: (SEEN, MODULES))
    return {"log_in_window": (
        "2026-09-30 12:00:00,000 INFO kmlserver_tpu.serving: profile capture closed: "
        f"dir={tmp_path} requests=2 batches=1 spans=23"
    )}


def test_anchor_mapping_offset_and_drift():
    clock = spans.ClockMap(ANCHORS, SEEN)
    assert clock.n == 2 and clock.offset_ns == OFFSET_NS and clock.drift_ns == 0
    assert clock.slack_ns == 1_000
    assert clock(15 * MS) == OFFSET_NS + 15 * MS
    # the capture's clock gains 50 us on perf_counter over the 100 ms
    drifting = spans.ClockMap(ANCHORS, {0: SEEN[0], 100 * MS: SEEN[100 * MS] + 50_000})
    assert drifting.drift_ns == 50_000
    assert drifting(1_000) == OFFSET_NS + 1_000
    assert drifting(50 * MS + 1_500) == pytest.approx(OFFSET_NS + 50 * MS + 1_500 + 25_000, abs=1)
    # an anchor the capture lost does not count; none at all is an error
    assert spans.ClockMap(ANCHORS, {0: SEEN[0]}).n == 1
    with pytest.raises(ValueError):
        spans.ClockMap(ANCHORS, {})


def test_self_time_is_duration_less_what_children_cover():
    root = REQUEST_A["spans"][0]
    assert spans.self_ms(REQUEST_A, root) == pytest.approx(1.0)  # 39-40 alone
    assert spans.self_ms(REQUEST_B, REQUEST_B["spans"][0]) == pytest.approx(3.0)  # 36-38, 41-42
    assert spans.self_ms(BATCH, BATCH["spans"][0]) == pytest.approx(1.5)  # 15-15.5, 18-19
    assert spans.self_ms(REQUEST_A, REQUEST_A["spans"][1]) == pytest.approx(1.0)  # a leaf
    # overlapping children are covered once
    t = trace("batch", "x", (0, 10), [("a", 1, 6), ("b", 4, 8)])
    assert spans.self_ms(t, t["spans"][0]) == pytest.approx(3.0)


@pytest.mark.parametrize("reader,value", [
    ({"trace": "request", "spans": ["parse", "respond", "write"], "stat": "self_mean_ms"}, 4.0),
    ({"trace": "batch", "spans": ["stage"], "stat": "mean_ms"}, 1.5),
    ({"trace": "batch", "spans": ["compose"], "stat": "mean_ms"}, 2.0),
    ({"trace": "batch", "spans": ["resolve"], "stat": "mean_ms"}, 1.5),
    ({"trace": "request", "spans": ["queue"], "stat": "attr_mean", "attr": "slot_wait_ms"}, 0.25),
    ({"trace": "batch", "spans": ["no_such_span"], "stat": "mean_ms"}, None),
])
def test_read_span_on_the_worked_file(capture, reader, value):
    got = spans.read_span(reader, capture)
    assert got is None if value is None else got == pytest.approx(value)


def test_read_idle_attributes_the_worked_case(capture, capsys):
    found = {
        bucket: spans.read_idle({"bucket": bucket, "stat": "ms_per_request"}, capture)
        for bucket in spans.BUCKETS
    }
    for bucket, ms in EXPECTED_MS.items():
        assert found[bucket] == pytest.approx(ms / 2), bucket  # two requests
    idle_ms = sum(EXPECTED_MS.values())
    assert idle_ms == pytest.approx(100 - 6.5 - 7.5)  # the window less the two programs
    assert spans.read_idle({"bucket": "awaiting", "stat": "pct_of_idle"}, capture) == (
        pytest.approx(100 * 68 / idle_ms)
    )
    assert spans.read_idle({"bucket": "unclaimed", "stat": "pct_of_open_idle"}, capture) == (
        pytest.approx(100 * 0.5 / (idle_ms - 68))
    )
    analysis = capture["_spans"]["idle"]
    assert analysis["busy_s"] == pytest.approx(0.014) and analysis["requests"] == 2
    assert (analysis["serving_programs"], analysis["started_inside_a_batch"]) == (2, 2)
    by_span = analysis["idle_by_span_s"]
    assert by_span["admit"] == pytest.approx(0.001)  # A's admit outranks B's parse
    assert by_span["resolve"] == pytest.approx(0.0015)
    assert by_span["batch"] == pytest.approx(0.0005)
    # read once, printed once: idle seconds by span, largest first, and the clock
    err = capsys.readouterr().err
    assert err.count("idle by bucket") == 1
    rows = [line.split()[3] for line in err.splitlines() if "idle under" in line]
    assert rows[:2] == ["awaiting", "respond"] and set(rows) == set(by_span)
    assert "2 anchors, offset 1000000000 ns, drift 0 ns" in err


def test_device_planes_that_read_early_are_moved_by_the_least_causal_shift(capture, monkeypatch):
    """On the chip the device planes' clock reads milliseconds ahead of the
    host plane's (programs 'start' before they were dispatched). The anchors
    cannot see that; causality bounds it."""
    early = [(a - 2 * MS, b - 2 * MS, name) for a, b, name in MODULES]
    intervals = spans.batch_intervals([BATCH], spans.ClockMap(ANCHORS, SEEN))
    assert intervals == [(OFFSET_NS + 17 * MS, OFFSET_NS + 33 * MS)]
    programs = [(a, b) for a, b, _ in early]
    # the rule program read 15.5; its batch's dispatch began at 17: 1.5 ms is
    # the least shift (the true 2 ms cannot be known), and both then fit
    # (the embedding program then ends at 31.5, a millisecond and a half
    # before its fetch does: that is the room the true skew lies in)
    assert spans.causal_shift(programs, intervals) == (1.5 * MS, 2, 1.5 * MS)
    assert spans.causal_shift([(a, b) for a, b, _ in MODULES], intervals) == (0.0, 2, 1.0 * MS)
    # nothing fits anywhere: no shift is invented
    assert spans.causal_shift([(0.0, 1.0)], intervals) == (0.0, 0, 0.0)
    monkeypatch.setattr(spans, "read_capture", lambda path: (SEEN, early))
    spans.read_idle({"bucket": "engine", "stat": "ms_per_request"}, capture)
    analysis = capture["_spans"]["idle"]
    assert analysis["clock"]["device_shift_ns"] == 1.5 * MS
    assert analysis["clock"]["device_shift_room_ns"] == 1.5 * MS
    assert (analysis["serving_programs"], analysis["started_inside_a_batch"]) == (2, 2)
    assert analysis["busy_s"] == pytest.approx(0.014)
    # the programs now sit 0.5 ms earlier than they ran: dispatch loses its
    # idle half millisecond and fetch_embed gains it
    assert "dispatch" not in analysis["idle_by_span_s"]
    assert analysis["idle_by_span_s"]["fetch_embed"] == pytest.approx(0.0015)
    assert analysis["idle_by_bucket_s"]["engine"] == pytest.approx(0.0055)


def test_a_program_outside_every_batch_is_counted_as_such(capture, monkeypatch):
    stray = (OFFSET_NS + 60 * MS, OFFSET_NS + 61 * MS, "jit__embed_topk_impl(789)")
    monkeypatch.setattr(spans, "read_capture", lambda path: (SEEN, MODULES + [stray]))
    spans.read_idle({"bucket": "awaiting", "stat": "pct_of_idle"}, capture)
    analysis = capture["_spans"]["idle"]
    assert (analysis["serving_programs"], analysis["started_inside_a_batch"]) == (3, 2)
    assert analysis["idle_by_bucket_s"]["awaiting"] == pytest.approx(0.067)


def test_nothing_to_read_gives_none_never_zero(monkeypatch, capture):
    reader = {"trace": "batch", "spans": ["stage"], "stat": "mean_ms"}
    idle = {"bucket": "engine", "stat": "ms_per_request"}
    for ctx in ({"log_in_window": ""}, {"log_in_window": None},
                {"log_in_window": "profile capture closed: dir=/no/such/dir requests=0 "}):
        assert spans.read_span(reader, ctx) is None
        assert spans.read_idle(idle, ctx) is None
    # a span file with no device plane beside it (the CPU smoke): spans yes, idle no
    monkeypatch.setattr(spans, "read_capture", lambda path: (SEEN, []))
    assert spans.read_span(reader, capture) == pytest.approx(1.5)
    assert spans.read_idle(idle, capture) is None
    # and with no anchor of the file in the capture, nothing is aligned by guessing
    monkeypatch.setattr(spans, "read_capture", lambda path: ({}, MODULES))
    assert spans.read_idle(idle, dict(capture, _spans={})) is None


def test_the_span_file_is_found_by_the_open_line_alone(capture, tmp_path):
    """The server logs the closing line after the profiler has written its
    file: a harness that read the log a moment too early still finds the
    spans, under the session directory the opening line named."""
    session = tmp_path / "profile" / "serve-capture-1"
    nest = session / "plugins" / "profile" / "2026_09_30_12_00_00"
    nest.mkdir(parents=True)
    os.rename(tmp_path / spans.SPANS_FILENAME, nest / spans.SPANS_FILENAME)
    reader = {"trace": "batch", "spans": ["stage"], "stat": "mean_ms"}
    early = f"... INFO kmlserver_tpu.serving: profile capture open: dir={session} seconds=20"
    assert spans.read_span(reader, {"log_in_window": early}) == pytest.approx(1.5)
    assert spans.read_span(reader, {"log_in_window": early.replace(str(session), "/no/such")}) is None


NEW_WITHOUT_A_DEVICE_PLANE = {
    "parse_respond_ms", "stage_ms", "compose_ms", "resolve_ms", "slot_wait_ms", "padding_pct",
    "batch_requests_mean", "unwarmed_in_window",
}
NEW_WITH_ONE = {
    "idle_awaiting_request_pct", "idle_front_end_ms", "idle_batcher_ms", "idle_engine_ms",
    "idle_unclaimed_pct",
}


def test_every_new_metric_has_its_file_and_reader():
    per_layer = {m["name"]: m for m in manifest.benchmark()["per_layer"]}
    for name in NEW_WITHOUT_A_DEVICE_PLANE | NEW_WITH_ONE:
        with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as fh:
            spec = json.load(fh)
        assert spec["name"] == name and per_layer[name]["layer"] == spec["layer"]
        assert spec["source"] == ("device_trace" if name in NEW_WITH_ONE else "program_counter")
        manifest.resolve(spec["reader"]["kind"])


def test_smoke_traced_run_prints_the_span_and_counter_metrics():
    """The toy run on the CPU: its capture has no device plane, so the idle
    metrics are left out; every other new metric is on the line."""
    result, stderr = run_cell(manifest.cells()[0], trace=1, seed=2147484222)
    got = result["metrics"]
    assert NEW_WITHOUT_A_DEVICE_PLANE <= set(got), sorted(got)
    assert not NEW_WITH_ONE & set(got)
    assert "has no XLA Modules event on a device plane" in stderr
    assert got["unwarmed_in_window"]["value"] == 0
    assert 1.0 <= got["batch_requests_mean"]["value"] <= 32.0
    assert 0.0 <= got["padding_pct"]["value"] < 100.0
    assert got["slot_wait_ms"]["value"] >= 0.0
    for name in ("parse_respond_ms", "stage_ms", "compose_ms", "resolve_ms"):
        assert got[name]["value"] > 0.0, name
