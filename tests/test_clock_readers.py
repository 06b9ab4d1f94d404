"""``benchmark/clock_readers.py`` on a hand-written span file and synthetic
device planes: the skew each plane's clock probes measure, chip-idle time
under the fetch spans with every plane placed by it, and None where the
program wrote no probe.

The worked case (milliseconds on ``perf_counter``'s clock; the capture's
host plane is 1,000 ms ahead): one batch, ``dispatch`` 17-18, ``handoff``
18-19, ``fetch_rules`` 19-25, ``fetch_embed`` 25-33. Plane 0 reads 2 ms
early: its programs ran 18.5-24 and 24.5-32 on the host's clock. Plane 1
reads 3 ms early and ran nothing but its probes. Probes at 1 ms and 99 ms.
"""

import json

import pytest

from benchmark import clock_readers, spans

MS = 1_000_000  # ns
OFFSET_NS = 1_000 * MS
SKEW_MS = {0: 2.0, 1: 3.0}  # how early each plane reads


def _span(i, parent, name, start, end):
    return {
        "id": i, "parent": parent, "name": name, "start_ms": start - 15,
        "duration_ms": end - start, "t_start_ns": int(start * MS), "t_end_ns": int(end * MS),
    }


BATCH = {"kind": "batch", "trace_id": "batch-1", "status": "ok", "duration_ms": 21.5,
         "attrs": {"batch_id": 1}, "spans": [
             _span(0, None, "batch", 15, 36.5), _span(1, 0, "stage", 15.5, 17),
             _span(2, 1, "fill_rules", 15.6, 16.2), _span(3, 1, "put_rules", 16.3, 16.9),
             _span(4, 0, "dispatch", 17, 18), _span(5, 4, "enqueue_rules", 17.1, 17.8),
             _span(6, 0, "handoff", 18, 19), _span(7, 0, "fetch_rules", 19, 25),
             _span(8, 0, "fetch_embed", 25, 33), _span(9, 0, "compose", 33, 35),
             _span(10, 0, "resolve", 35, 36.5),
         ]}
ANCHORS = [[0, 2_000], [100 * MS, 100 * MS + 2_000]]
SEEN = {0: OFFSET_NS + 1_000, 100 * MS: OFFSET_NS + 100 * MS + 1_000}
# (device, before, after, ran from, ran to), host milliseconds: each
# probe's bounds on its plane's shift are [before - from, after - to] plus
# the skew, so plane 0 is held to [1.9, 2.1] and [1.95, 2.05] ms
PROBES = [
    (0, 1.0, 1.3, 1.1, 1.2), (1, 1.3, 1.5, 1.35, 1.45),
    (0, 99.0, 99.25, 99.05, 99.2), (1, 99.25, 99.4, 99.3, 99.32),
]


def plane(device: int, programs: list) -> list:
    early = SKEW_MS[device] * MS
    return [(OFFSET_NS + a * MS - early, OFFSET_NS + b * MS - early, name) for a, b, name in programs]


def probe_runs(device: int) -> list:
    return [(a, b, "jit_kmls_clock_probe(7)") for d, _, _, a, b in PROBES if d == device]


PLANES = {
    "/device:TPU:0": plane(0, [(18.5, 24, "jit__recommend_batch_impl(1)"),
                               (24.5, 32, "jit__embed_topk_impl(2)")] + probe_runs(0)),
    "/device:TPU:1": plane(1, probe_runs(1)),
}
HEADER = {"kind": "header", "version": 1, "anchors": ANCHORS,
          "device_probes": [[d, int(b * MS), int(a * MS)] for d, b, a, _, _ in PROBES]}


@pytest.fixture
def capture(tmp_path, monkeypatch):
    def make(header=HEADER, planes=PLANES):
        with open(tmp_path / spans.SPANS_FILENAME, "w", encoding="utf-8") as fh:
            for line in (header, BATCH):
                fh.write(json.dumps(line) + "\n")
        (tmp_path / "host.xplane.pb").write_bytes(b"")
        monkeypatch.setattr(clock_readers, "read_planes", lambda path: (SEEN, planes))
        flat = [e for events in planes.values() for e in events]
        monkeypatch.setattr(spans, "read_capture", lambda path: (SEEN, flat))
        return {"log_in_window": (
            "2026-10-18 12:00:00,000 INFO kmlserver_tpu.serving: profile capture closed: "
            f"dir={tmp_path} requests=0 batches=1 spans=11"
        )}
    return make


READER = {"spans": ["fetch_rules", "fetch_embed"], "stat": "ms_per_batch"}


@pytest.mark.parametrize("device,lo,hi", [(0, 1.95, 2.05), (1, 2.95, 3.05)])
def test_each_planes_probes_bound_its_shift_from_both_sides(device, lo, hi):
    clock = spans.ClockMap(ANCHORS, SEEN)
    mine = [p for p in HEADER["device_probes"] if p[0] == device]
    got = clock_readers.plane_shift(mine, PLANES[f"/device:TPU:{device}"], clock)
    assert got["matched"] == 2
    assert got["lo"] == pytest.approx(lo * MS, abs=2)
    assert got["hi"] == pytest.approx(hi * MS, abs=2)
    assert got["shift"] == pytest.approx(SKEW_MS[device] * MS, abs=2)
    assert got["width"] == pytest.approx(0.1 * MS, abs=4)
    # a probe nobody ran on the plane bounds nothing
    assert clock_readers.plane_shift(mine, [], clock) is None


def test_idle_under_fetch_with_each_plane_at_its_measured_shift(capture, capsys):
    """Placed 2 ms later, plane 0's programs leave the chip idle 24-24.5
    (between them) and 32-33 (before ``fetch_embed`` ends): 1.5 ms."""
    ctx = capture()
    assert clock_readers.read_idle_measured(READER, ctx) == pytest.approx(1.5, abs=1e-5)
    found = ctx["_spans"]["measured"]
    # handoff 18-18.5 is idle, and so is the batch's own 15-15.5: the
    # unclaimed bucket holds both, handoff half of it
    assert found["handoff_idle_s"] == pytest.approx(0.0005, abs=1e-8)
    assert found["unclaimed_s"] == pytest.approx(0.001, abs=1e-8)
    err = capsys.readouterr().err
    assert "[clock] /device:TPU:0: 2 probes, shift [1950000, 2050000] ns, width 100000 ns" in err
    assert "[clock] /device:TPU:1: 2 probes" in err
    assert "causal_shift" in err and "idle under handoff 0.000500 s (1 batches)" in err
    # read once, printed once
    assert clock_readers.read_idle_measured({**READER, "spans": ["handoff"]}, ctx) == (
        pytest.approx(0.5, abs=1e-5)
    )
    assert capsys.readouterr().err.count("[clock] /device:") == 0


def test_causal_shift_reads_at_or_below_the_measured_skew(capture):
    """The rule program started 1.5 ms after ``dispatch`` began: the
    least causal shift is 0.5 ms, under the 2 ms the probes measure."""
    ctx = capture()
    clock_readers.read_idle_measured(READER, ctx)
    assert ctx["_spans"]["idle"]["clock"]["device_shift_ns"] == pytest.approx(0.5 * MS, abs=2)
    assert ctx["_spans"]["measured"]["shifts"]["/device:TPU:0"]["lo"] >= 0.5 * MS


def test_no_probes_in_the_header_gives_none(capture):
    """A server that writes no probe (one older than the probe): the
    metric is left out, not read by causal_shift."""
    header = {k: v for k, v in HEADER.items() if k != "device_probes"}
    assert clock_readers.read_idle_measured(READER, capture(header=header)) is None


def test_no_device_plane_gives_none(capture, capsys):
    assert clock_readers.read_idle_measured(READER, capture(planes={})) is None
    assert "no device plane in the capture" in capsys.readouterr().err


@pytest.mark.parametrize("lost", [["/device:TPU:0", "/device:TPU:1"], ["/device:TPU:1"]])
def test_a_plane_whose_probes_are_not_found_gives_none(capture, capsys, lost):
    """Every plane is placed by its own probes, or the metric is left out."""
    planes = {
        name: [e for e in events if name not in lost or "probe" not in e[2]]
        for name, events in PLANES.items()
    }
    assert clock_readers.read_idle_measured(READER, capture(planes=planes)) is None
    assert f"no probe of the header found on {', '.join(lost)}" in capsys.readouterr().err


def test_no_span_file_gives_none():
    assert clock_readers.read_idle_measured(READER, {"log_in_window": ""}) is None


@pytest.mark.parametrize("name,device", [
    ("/device:TPU:0", 0), ("/device:TPU:3", 3), ("/device:TPU:0 SparseCore", None),
    ("/host:CPU", None),
])
def test_a_plane_names_the_device_it_shows(name, device):
    assert clock_readers.plane_device(name) == device


@pytest.mark.parametrize("covered,busy,idle", [
    ([(0, 10)], [], 10),
    ([(0, 10)], [(2, 3), (5, 20)], 4),
    ([(0, 4), (2, 6), (8, 9)], [(3, 8)], 3 + 1),
    ([(0, 10)], [(-5, 1), (1, 2), (9, 12)], 7),
])
def test_idle_within_subtracts_the_busy_union(covered, busy, idle):
    assert clock_readers.idle_within(covered, busy) == pytest.approx(idle)


def test_probes_run_back_to_back_are_paired_in_order():
    """Four probes at one anchor, 1.5 ms apart, on a plane 3.4 ms early:
    each execution lies nearer the next probe's host reading than its own,
    so only the order pairs them. A group the plane shows one execution
    short of bounds nothing."""
    clock = spans.ClockMap(ANCHORS, SEEN)
    skew, probes, runs = 3.4, [], []
    for k in range(4):
        before = 10.0 + 1.5 * k
        probes.append([0, int(before * MS), int((before + 1.2) * MS)])
        start = before + 0.5 - skew  # launched 0.5 ms after the call
        runs.append((OFFSET_NS + start * MS, OFFSET_NS + (start + 0.001) * MS, "jit_kmls_clock_probe(1)"))
    got = clock_readers.plane_shift(probes, runs, clock)
    assert got["matched"] == 4
    # each probe: [before - start, after - end] = [skew - 0.5, skew + 0.699]
    assert got["lo"] == pytest.approx((skew - 0.5) * MS, abs=2)
    assert got["hi"] == pytest.approx((skew + 0.699) * MS, abs=2)
    assert clock_readers.plane_shift(probes, runs[1:], clock) is None
