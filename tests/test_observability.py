"""ISSUE 9 observability layer: span tracing with tail-based retention,
trace propagation through both HTTP front ends, fixed-bucket latency
histograms pinned against the reservoirs, exposition validity (one TYPE
per name, valid charset, no NaN), the scrape-never-blocks-observe
reservoir contract, the event-loop-lag admission fold and the executor
hop that close the PR 8 blind spot, and the mining job_metrics.prom
textfile.
"""

import bisect
import dataclasses
import inspect
import json
import math
import os
import random
import re
import threading
import time
import urllib.request

import pytest

from kmlserver_tpu import faults
from kmlserver_tpu.config import MiningConfig, ServingConfig  # noqa: F401
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.observability import LoopLagMonitor, SpanRecorder
from kmlserver_tpu.observability.jobmetrics import (
    JOB_METRICS_FILENAME,
    JobMetrics,
)
from kmlserver_tpu.serving.app import RecommendApp, serve
from kmlserver_tpu.serving.batcher import (
    AdmissionController,
    AsyncMicroBatcher,
    DeadlineExceeded,
    Overloaded,
    OverloadDegraded,
)
from kmlserver_tpu.serving.metrics import (
    LATENCY_BUCKETS_S,
    METRIC_REGISTRY,
    LatencyHistogram,
    LatencyReservoir,
    ServingMetrics,
)

from .test_batching import _rule_seeds
from .test_serving import mined_pvc  # noqa: F401  (fixture re-export)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _post(app, songs, trace_header=None):
    return app.handle(
        "POST", "/api/recommend/", json.dumps({"songs": songs}).encode(),
        trace_header=trace_header,
    )


def _traces_of(app):
    status, _, payload = app.handle("GET", "/debug/traces", None)
    assert status == 200
    return json.loads(payload)


# ---------------------------------------------------------------------------
# exposition validity (satellite): parse Prometheus text strictly
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? (\S+)$"
)


def parse_exposition(text: str) -> tuple[dict[str, str], list[str]]:
    """Strictly parse Prometheus text format → (name -> type, sample
    names). Asserts: unique TYPE per name, valid name charset, valid
    non-NaN sample values, and every sample covered by a TYPE line
    (histogram `_bucket`/`_sum`/`_count` children map to their base)."""
    types: dict[str, str] = {}
    samples: list[str] = []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            assert len(parts) == 4, f"malformed TYPE line: {line!r}"
            name, mtype = parts[2], parts[3]
            assert _NAME_RE.match(name), name
            assert name not in types, f"duplicate # TYPE for {name}"
            assert mtype in ("counter", "gauge", "summary", "histogram"), line
            types[name] = mtype
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        value = float(m.group(3))  # raises on garbage
        assert not math.isnan(value), f"NaN sample: {line!r}"
        samples.append(m.group(1))
    for name in samples:
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            stripped = name[: -len(suffix)] if name.endswith(suffix) else ""
            if stripped and types.get(stripped) == "histogram":
                base = stripped
        assert base in types, f"sample {name} has no # TYPE line"
    return types, samples


class TestExpositionValidity:
    def test_live_metrics_output_is_valid_and_registry_backed(
        self, mined_pvc
    ):
        """The full /metrics output of a serving app that has seen
        traffic parses strictly AND agrees with METRIC_REGISTRY: every
        rendered series is declared with the exact type it renders as."""
        cfg, _, _ = mined_pvc
        app = RecommendApp(dataclasses.replace(cfg, trace_sample=0.5))
        assert app.engine.load()
        seeds = _rule_seeds(cfg)
        for s in seeds[:3]:
            status, _, _ = _post(app, [s])
            assert status == 200
        _post(app, ["no-such-track-anywhere"])
        status, _, payload = app.handle("GET", "/metrics", None)
        assert status == 200
        types, samples = parse_exposition(payload.decode())
        for name, mtype in types.items():
            assert name in METRIC_REGISTRY, (
                f"{name} rendered but not in METRIC_REGISTRY"
            )
            declared = METRIC_REGISTRY[name].split(":", 1)[0]
            assert mtype == declared, (name, mtype, declared)
        # the new surfaces are actually present
        for required in (
            "kmls_queue_wait_seconds", "kmls_device_seconds",
            "kmls_e2e_seconds", "kmls_loop_lag_ms",
            "kmls_traces_began_total",
        ):
            assert required in types, required

    def test_robustness_key_colliding_with_static_series_dedupes(self):
        """Satellite: a robustness dict key that collides with a
        statically rendered series must not emit a second # TYPE line
        (invalid exposition) — the static rendering wins, the colliding
        dynamic entry is dropped whole."""
        metrics = ServingMetrics()
        text = metrics.render(
            7, True,
            robustness={
                "degraded_total": 999,
                "utilization": 0.25,
                # collides with a lifecycle series rendered AFTER the
                # robustness block — dedupe must look ahead, not just
                # at lines already emitted
                "reloads_total": 888,
            },
        )
        for series, static_sample in (
            ("kmls_degraded_total", "kmls_degraded_total 0"),
            ("kmls_reloads_total", "kmls_reloads_total 7"),
        ):
            type_lines = [
                line for line in text.splitlines()
                if line.startswith(f"# TYPE {series} ")
            ]
            assert len(type_lines) == 1, series
            sample_lines = [
                line for line in text.splitlines()
                if line.startswith(f"{series} ")
            ]
            # one sample, and it is the static one, not the impostor
            assert sample_lines == [static_sample]
        # the non-colliding dynamic key still renders
        assert "kmls_utilization 0.25" in text
        parse_exposition(text)

    def test_job_metrics_textfile_is_valid_and_mining_scoped(self, tmp_path):
        jm = JobMetrics(str(tmp_path))
        jm.phase_done("encode", 1.25)
        jm.phase_done("mine", 4.5, resumed=True)
        jm.set_dataset(rows=100, playlists=40, tracks=16)
        jm.note_artifact("rules", __file__)
        jm.finish(True, rule_generation_s=4.5, fencing_token=2)
        types, _ = parse_exposition(jm.render())
        for name, mtype in types.items():
            declared_type, _, scope = METRIC_REGISTRY[name].partition(":")
            assert mtype == declared_type, name
            assert scope == "mining", (
                f"{name} rendered by the mining textfile but "
                f"registered {scope!r}"
            )

    def test_job_metrics_refuses_unregistered_series(self, tmp_path, monkeypatch):
        """The textfile writer looks every name up in METRIC_REGISTRY at
        render time — an unregistered series is a KeyError, not silent
        drift."""
        jm = JobMetrics(str(tmp_path))
        jm.finish(True)
        monkeypatch.delitem(METRIC_REGISTRY, "kmls_job_success")
        with pytest.raises(KeyError):
            jm.render()


# ---------------------------------------------------------------------------
# fixed-bucket histograms (tentpole a)
# ---------------------------------------------------------------------------


class TestLatencyHistogram:
    def test_render_shape_and_cumulative_buckets(self):
        hist = LatencyHistogram()
        for v in (0.0004, 0.002, 0.002, 0.03, 20.0):
            hist.observe(v)
        lines = hist.render("kmls_e2e_seconds")
        assert lines[0] == "# TYPE kmls_e2e_seconds histogram"
        buckets = [
            line for line in lines if line.startswith("kmls_e2e_seconds_bucket")
        ]
        # one line per finite bucket + the +Inf band
        assert len(buckets) == len(LATENCY_BUCKETS_S) + 1
        counts = [int(line.rsplit(" ", 1)[1]) for line in buckets]
        assert counts == sorted(counts), "bucket counts must be cumulative"
        assert buckets[-1] == 'kmls_e2e_seconds_bucket{le="+Inf"} 5'
        assert "kmls_e2e_seconds_count 5" in lines
        # the 20 s observation lands only in +Inf
        assert counts[-2] == 4

    def test_bucket_counters_sum_across_replicas(self):
        """The fleet-aggregation property reservoirs lack: two pods'
        bucket counters added elementwise ARE the fleet histogram."""
        rng = random.Random(5)
        pod_a, pod_b, fleet = (
            LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
        )
        for _ in range(500):
            v = rng.lognormvariate(-6.0, 1.2)
            pod = pod_a if rng.random() < 0.5 else pod_b
            pod.observe(v)
            fleet.observe(v)
        counts_a, sum_a, n_a = pod_a.snapshot()
        counts_b, sum_b, n_b = pod_b.snapshot()
        counts_f, sum_f, n_f = fleet.snapshot()
        assert [a + b for a, b in zip(counts_a, counts_b)] == counts_f
        assert n_a + n_b == n_f
        assert sum_a + sum_b == pytest.approx(sum_f)

    @pytest.mark.parametrize("q", [0.50, 0.95, 0.99, 0.999])
    def test_histogram_quantiles_pinned_against_reservoir(self, q):
        """Tentpole test: histogram-derived quantiles agree with the
        reservoir's exact quantiles to within the winning bucket — the
        resolution the fixed buckets promise."""
        rng = random.Random(11)
        reservoir = LatencyReservoir()
        hist = LatencyHistogram()
        for _ in range(4000):
            # latency-shaped: lognormal body + a heavy tail excursion
            v = rng.lognormvariate(-6.2, 1.0)
            if rng.random() < 0.01:
                v += rng.uniform(0.05, 0.8)
            reservoir.observe(v)
            hist.observe(v)
        (exact,) = reservoir.percentiles(q)
        derived = hist.quantile(q)
        idx = bisect.bisect_left(LATENCY_BUCKETS_S, exact)
        lo = LATENCY_BUCKETS_S[idx - 1] if idx > 0 else 0.0
        hi = (
            LATENCY_BUCKETS_S[idx]
            if idx < len(LATENCY_BUCKETS_S)
            else LATENCY_BUCKETS_S[-1]
        )
        assert lo * 0.999 <= derived <= hi * 1.001, (q, exact, derived)

    def test_metrics_reset_windows_reservoirs_not_histograms(self, mined_pvc):
        """/metrics/reset clears the reservoirs (bench windowing) but the
        histograms are counters — scrape-delta semantics survive."""
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()
        for s in _rule_seeds(cfg)[:2]:
            _post(app, [s])
        _, _, before_count = app.metrics.e2e_hist.snapshot()
        assert before_count > 0
        status, _, _ = app.handle(
            "POST", "/metrics/reset", None, client_host="127.0.0.1"
        )
        assert status == 200
        _, _, after_count = app.metrics.e2e_hist.snapshot()
        assert after_count == before_count
        assert app.metrics.e2e.percentiles(0.5) == [0.0]


# ---------------------------------------------------------------------------
# reservoir scrape-under-load (satellite)
# ---------------------------------------------------------------------------


class _GateValue:
    """A comparable whose FIRST comparison blocks until released —
    planted in the reservoir so a concurrent percentiles() call is
    provably inside its sort when observe() runs."""

    sorting = threading.Event()
    release = threading.Event()

    def __init__(self, v: float):
        self.v = v

    def __lt__(self, other):
        _GateValue.sorting.set()
        assert _GateValue.release.wait(timeout=10.0)
        return self.v < other.v


class TestReservoirScrapeUnderLoad:
    def test_observe_never_blocked_by_concurrent_scrape(self):
        """Satellite: percentiles() copies under the lock and sorts
        OUTSIDE it. With a scraper deterministically frozen mid-sort,
        observe() must still complete immediately — under the old
        sort-under-lock code this observe blocked until the sort
        finished."""
        _GateValue.sorting.clear()
        _GateValue.release.clear()
        reservoir = LatencyReservoir()
        for i in range(64):
            reservoir.observe(_GateValue(float(i)))

        result: list = []
        scraper = threading.Thread(
            target=lambda: result.append(reservoir.percentiles(0.5)),
            daemon=True,
        )
        scraper.start()
        assert _GateValue.sorting.wait(timeout=10.0)
        # the scraper is now blocked inside live.sort(); the observe
        # lock must be free
        t0 = time.perf_counter()
        reservoir.observe(0.001)
        observe_s = time.perf_counter() - t0
        assert not _GateValue.release.is_set()
        _GateValue.release.set()
        scraper.join(timeout=10.0)
        assert not scraper.is_alive() and result
        assert observe_s < 0.5, (
            f"observe() took {observe_s:.3f}s while a scrape was sorting "
            "— the sort is back under the observe lock"
        )


# ---------------------------------------------------------------------------
# span recorder: tail-based retention + zero-cost-off (tentpole)
# ---------------------------------------------------------------------------


class TestSpanRecorder:
    def _ctx(self, rec, header=None):
        trace = rec.begin(header)
        assert trace is not None
        return trace

    def test_disabled_recorder_does_nothing(self):
        rec = SpanRecorder(sample=0.0)
        assert not rec.enabled
        assert rec.begin("abc") is None
        assert rec.began == 0
        payload = rec.debug_payload()
        assert payload["enabled"] is False and payload["traces"] == []

    def test_header_parsing_and_charset_guard(self):
        rec = SpanRecorder(sample=1.0, rng=random.Random(0))
        t = self._ctx(rec, "req-01:parent-9")
        assert t.trace_id == "req-01" and t.parent_id == "parent-9"
        # hostile bytes never reach output: invalid charset → fresh id
        t = self._ctx(rec, 'x" }\n<script>:<b>')
        assert re.fullmatch(r"[0-9a-f]{16}", t.trace_id)
        assert t.parent_id is None
        # an invalid trace id with a clean parent keeps just the parent
        t = self._ctx(rec, 'x" }:p')
        assert re.fullmatch(r"[0-9a-f]{16}", t.trace_id)
        assert t.parent_id == "p"
        # over-long ids rejected the same way
        t = self._ctx(rec, "a" * 65)
        assert re.fullmatch(r"[0-9a-f]{16}", t.trace_id)

    def test_non_ok_always_retained_regardless_of_sample(self):
        rec = SpanRecorder(sample=1e-9, slow_n=0, rng=random.Random(1))
        for status in ("shed", "degraded", "error") * 20:
            assert rec.finish(self._ctx(rec), status, 0.001)
        assert rec.retained() == 60

    def test_slowest_n_retained_and_bar_rises(self):
        rec = SpanRecorder(sample=1e-9, slow_n=4, rng=random.Random(2))
        kept = [
            rec.finish(self._ctx(rec), "ok", d)
            for d in (0.010, 0.020, 0.030, 0.040)
        ]
        assert all(kept)  # heap not full: everything is slowest-N
        assert not rec.finish(self._ctx(rec), "ok", 0.005)  # under the bar
        assert rec.finish(self._ctx(rec), "ok", 0.050)  # new tail entrant
        assert not rec.finish(self._ctx(rec), "ok", 0.012)  # bar rose to 20ms

    def test_baseline_sampling_is_probabilistic(self):
        rec = SpanRecorder(sample=0.5, slow_n=0, rng=random.Random(3))
        # identical durations so slowest-N can't interfere (slow_n=0)
        kept = sum(
            rec.finish(self._ctx(rec), "ok", 0.001) for _ in range(400)
        )
        assert 120 < kept < 280  # ~200 at p=0.5, seeded rng

    def test_ring_capacity_bounds_the_buffer(self):
        rec = SpanRecorder(sample=1.0, capacity=8, rng=random.Random(4))
        for i in range(50):
            t = self._ctx(rec)
            t.annotate("i", i)
            rec.finish(t, "shed", 0.001)
        assert rec.retained() == 8
        payload = rec.debug_payload()
        assert [t["attrs"]["i"] for t in payload["traces"]] == list(
            range(42, 50)
        )  # oldest evicted, oldest-first order

    def test_span_and_annotation_round_trip_to_json(self):
        rec = SpanRecorder(sample=1.0, rng=random.Random(5))
        t = self._ctx(rec, "rt-1")
        t0 = t.t0
        queue_id = t.span("queue", t0, t0 + 0.002, {"batch": 3})
        batch_id = t.span(
            "batch", t0 + 0.002, t0 + 0.004, {"replica": 0, "batch_id": 7}
        )
        t.annotate("admission", "degrade")
        rec.finish(t, "degraded", 0.005)
        (trace,) = rec.debug_payload()["traces"]
        json.dumps(trace)  # JSON-clean
        assert trace["trace_id"] == "rt-1"
        assert trace["status"] == "degraded"
        assert trace["attrs"]["admission"] == "degrade"
        # the root comes first and is the trace itself; ids are small
        # integers local to the trace, and every other span names a parent
        assert [s["name"] for s in trace["spans"]] == [
            "request", "queue", "batch",
        ]
        root, queue, batch = trace["spans"]
        assert (root["id"], root["parent"]) == (0, None)
        assert root["duration_ms"] == trace["duration_ms"] == 5.0
        assert (queue["id"], queue["parent"]) == (queue_id, 0)
        assert (batch["id"], batch["parent"]) == (batch_id, 0)
        assert len({root["id"], queue_id, batch_id}) == 3
        assert queue["attrs"] == {"batch": 3}
        assert queue["duration_ms"] == pytest.approx(2.0, abs=0.1)


class TestZeroCostWhenDisabled:
    def test_began_counter_never_moves_with_tracing_off(self, mined_pvc):
        """Acceptance: KMLS_TRACE_SAMPLE=0 (the default) adds zero
        hot-path work — the compile-counter-style proof: real requests
        (even carrying a trace header) never construct a context, never
        generate an id, never touch the recorder."""
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()
        assert app.cfg.trace_sample == 0.0 and not app.recorder.enabled
        for s in _rule_seeds(cfg)[:3]:
            status, headers, _ = _post(app, [s], trace_header="want-a-trace")
            assert status == 200
            assert "X-KMLS-Trace" not in headers
        assert app.recorder.began == 0
        # the batch trace's counter too: the batcher had the recorder
        # and dispatched three batches without opening one
        assert app.batcher.recorder is app.recorder
        assert app.recorder.batches_began == 0
        assert app.recorder.retained_total == 0
        status, _, payload = app.handle("GET", "/metrics", None)
        text = payload.decode()
        assert "kmls_traces_began_total 0" in text
        assert "kmls_trace_buffer_entries 0" in text
        payload = _traces_of(app)
        assert payload["enabled"] is False and payload["traces"] == []


# ---------------------------------------------------------------------------
# trace propagation through both front ends (satellite)
# ---------------------------------------------------------------------------


def _assert_traced_breakdown(doc: dict, trace_id: str, parent_id=None):
    by_id = {t["trace_id"]: t for t in doc["traces"]}
    assert trace_id in by_id, sorted(by_id)
    trace = by_id[trace_id]
    assert trace["parent_id"] == parent_id
    assert trace["status"] == "ok"
    names = [s["name"] for s in trace["spans"]]
    assert names[0] == "request"
    for required in ("parse", "admit", "queue", "batch", "respond"):
        assert required in names, names
    assert "device" not in names and "compose" not in names
    # the root's children tile the request (the batch's own inside is
    # the batch trace's, looked up by batch_id, not copied in here)
    span_sum = sum(
        s["duration_ms"] for s in trace["spans"] if s["parent"] == 0
    )
    e2e = trace["duration_ms"]
    # spans must fit inside the request and account for most of it; the
    # uncovered remainder is validation + completion handoff (bounded
    # generously for noisy CI hosts)
    assert span_sum <= e2e * 1.05 + 0.5, (span_sum, e2e)
    assert e2e - span_sum < 80.0, (span_sum, e2e)
    for span in trace["spans"]:
        assert span["duration_ms"] >= 0.0
        assert -0.1 <= span["start_ms"] <= e2e + 0.1
    return trace


class TestTracePropagationThreaded:
    def test_injected_id_rides_to_debug_traces(self, mined_pvc):
        """Satellite: a request with an injected X-KMLS-Trace id through
        the real threaded HTTP server appears in /debug/traces with
        queue/device/compose spans that sum to ~its e2e latency, and the
        response echoes the id."""
        cfg, _, _ = mined_pvc
        app = RecommendApp(dataclasses.replace(cfg, trace_sample=1.0))
        assert app.engine.load()
        server = serve(app, port=0)
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()
        try:
            seeds = _rule_seeds(cfg)[:2]
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/recommend/",
                data=json.dumps({"songs": seeds}).encode(),
                headers={
                    "Content-Type": "application/json",
                    "X-KMLS-Trace": "threaded-cli-1:bench-run-7",
                },
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert resp.status == 200
                assert resp.headers["X-KMLS-Trace"] == "threaded-cli-1"
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/traces", timeout=10
            ) as resp:
                doc = json.loads(resp.read())
            trace = _assert_traced_breakdown(
                doc, "threaded-cli-1", parent_id="bench-run-7"
            )
            # batcher path annotated its dispatch, and names the batch's
            # own trace, which holds the engine's spans
            batch = next(
                s for s in trace["spans"] if s["name"] == "batch"
            )
            assert "replica" in batch["attrs"]
            by_batch = {
                b["attrs"]["batch_id"]: b for b in doc["batches"]
            }
            inside = [
                s["name"]
                for s in by_batch[batch["attrs"]["batch_id"]]["spans"]
            ]
            assert inside[0] == "batch"
            for required in ("stage", "fetch_rules", "compose", "resolve"):
                assert required in inside, inside
        finally:
            server.shutdown()

    def test_cache_hit_trace_marks_cached_no_device_span(self, mined_pvc):
        cfg, _, _ = mined_pvc
        app = RecommendApp(dataclasses.replace(cfg, trace_sample=1.0))
        assert app.engine.load()
        seeds = _rule_seeds(cfg)[:1]
        assert _post(app, seeds, trace_header="warm-1")[0] == 200
        status, headers, _ = _post(app, seeds, trace_header="hit-1")
        assert status == 200 and headers.get("X-KMLS-Cache") == "hit"
        assert headers["X-KMLS-Trace"] == "hit-1"
        by_id = {t["trace_id"]: t for t in _traces_of(app)["traces"]}
        hit = by_id["hit-1"]
        assert hit["attrs"].get("cached") is True
        names = [s["name"] for s in hit["spans"]]
        assert "batch" not in names and "queue" not in names
        assert "cache" in names and "respond" in names


class TestTracePropagationAsync:
    @pytest.fixture
    def served(self, mined_pvc):
        import asyncio
        from kmlserver_tpu.serving.aioserver import run_async

        cfg, _, _ = mined_pvc
        app = RecommendApp(
            dataclasses.replace(cfg, trace_sample=1.0), defer_batcher=True
        )
        app.engine.load()
        port_box: list[int] = []
        ready = threading.Event()

        def runner():
            asyncio.run(
                run_async(
                    app, 0,
                    ready=lambda p: (port_box.append(p), ready.set()),
                )
            )

        threading.Thread(target=runner, daemon=True).start()
        assert ready.wait(timeout=30)
        return app, port_box[0]

    def test_injected_id_rides_to_debug_traces(self, served):
        import http.client

        app, port = served
        seeds = _rule_seeds(app.cfg)[:2]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request(
            "POST", "/api/recommend/",
            body=json.dumps({"songs": seeds}).encode(),
            headers={
                "Content-Type": "application/json",
                "X-KMLS-Trace": "aio-cli-1",
            },
        )
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 200
        assert resp.headers["X-KMLS-Trace"] == "aio-cli-1"
        conn.request("GET", "/debug/traces")
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        assert resp.status == 200
        _assert_traced_breakdown(doc, "aio-cli-1")
        # the loop-lag drift tick is armed on the serving loop
        assert app.loop_lag is not None
        deadline = time.time() + 10
        while app.loop_lag.ticks == 0 and time.time() < deadline:
            time.sleep(0.05)
        assert app.loop_lag.ticks > 0


# ---------------------------------------------------------------------------
# tail-based retention under failure (acceptance)
# ---------------------------------------------------------------------------


class _LadderScriptBatcher:
    """Replays the admission ladder deterministically: each recommend()
    raises the scripted outcome — exactly what the real batcher raises
    under a burst (shed / overload-degrade) or a stalled kernel
    (deadline)."""

    def __init__(self, script):
        self._script = list(script)

    def submit(self, seeds, deadline=None, trace=None):  # hasattr probe
        raise NotImplementedError

    def recommend(self, seeds, deadline=None, trace=None, timeout=None):
        exc = self._script.pop(0)
        if exc is not None:
            raise exc
        return [f"rec-for-{seeds[0]}"], "rules"


class TestTailRetentionUnderChaos:
    def test_every_shed_degraded_deadline_trace_retained(self, mined_pvc):
        """Acceptance: with a vanishingly small baseline sample, every
        shed, overload-degraded, and deadline-exceeded request is still
        retained in /debug/traces, with the ladder decision recorded in
        a span attribute."""
        cfg, _, _ = mined_pvc
        app = RecommendApp(
            dataclasses.replace(
                cfg, trace_sample=1e-9, cache_enabled=False,
            ),
            defer_batcher=True,
        )
        assert app.engine.load()
        app.recorder.slow_n = 0  # isolate the always-keep rule
        app.batcher = _LadderScriptBatcher([
            Overloaded(1.4, 105.0),
            OverloadDegraded(0.9),
            DeadlineExceeded("deadline exhausted in queue"),
            None,
        ])
        outcomes = []
        for i in range(4):
            status, headers, _ = _post(
                app, [f"seed-{i}"], trace_header=f"chaos-{i}"
            )
            outcomes.append((status, headers.get("X-KMLS-Degraded")))
        assert outcomes[0] == (429, None)
        assert outcomes[1] == (200, "overload")
        assert outcomes[2] == (200, "deadline")
        assert outcomes[3] == (200, None)

        by_id = {t["trace_id"]: t for t in _traces_of(app)["traces"]}
        shed = by_id["chaos-0"]
        assert shed["status"] == "shed"
        assert shed["attrs"]["admission"] == "shed"
        assert shed["attrs"]["retry_after_s"] == pytest.approx(1.4)
        degraded = by_id["chaos-1"]
        assert degraded["status"] == "degraded"
        assert degraded["attrs"]["admission"] == "degrade"
        assert degraded["attrs"]["reason"] == "overload"
        deadline = by_id["chaos-2"]
        assert deadline["status"] == "degraded"
        assert deadline["attrs"]["reason"] == "deadline"
        # the OK request at sample≈0 with slow_n=0 is NOT retained — the
        # tail policy kept exactly the interesting three
        assert "chaos-3" not in by_id
        assert app.recorder.retained_total == 3

    def test_real_kernel_stall_deadline_trace_retained(self, mined_pvc):
        """The PR 3 kernel-delay repro with tracing on: the degraded
        answer's trace lands in the buffer with reason=deadline."""
        cfg, _, _ = mined_pvc
        app = RecommendApp(
            dataclasses.replace(
                cfg, request_deadline_ms=80.0, trace_sample=1e-9,
            )
        )
        assert app.engine.load()
        app.recorder.slow_n = 0
        seeds = app.engine.bundle.vocab[:2]
        faults.inject("replica.kernel", replica=0, delay_s=0.5, times=-1)
        status, headers, _ = _post(app, seeds, trace_header="stall-1")
        assert status == 200
        assert headers.get("X-KMLS-Degraded") == "deadline"
        assert headers["X-KMLS-Trace"] == "stall-1"
        by_id = {t["trace_id"]: t for t in _traces_of(app)["traces"]}
        assert by_id["stall-1"]["status"] == "degraded"
        assert by_id["stall-1"]["attrs"]["reason"] == "deadline"
        faults.clear()
        time.sleep(0.6)  # let the stalled batch drain


# ---------------------------------------------------------------------------
# runtime health: loop-lag collector + admission fold (tentpole b)
# ---------------------------------------------------------------------------


class TestLoopLagMonitor:
    def test_peak_hold_and_decay(self):
        mon = LoopLagMonitor(half_life_s=1.0)
        now = 100.0
        mon.note(0.2, now=now)
        assert mon.lag_s(now=now) == pytest.approx(0.2)
        # a smaller stall does not dilute the held peak
        mon.note(0.01, now=now + 0.1)
        assert mon.lag_s(now=now + 0.1) > 0.15
        # one half-life later the estimate has halved
        assert mon.lag_s(now=now + 1.0) == pytest.approx(0.1, rel=0.05)
        # a larger stall replaces the decayed peak immediately
        mon.note(0.5, now=now + 2.0)
        assert mon.lag_s(now=now + 2.0) == pytest.approx(0.5)
        mon.note(0.0, now=now + 2.1)  # no-op
        assert mon.lag_s(now=now + 2.1) < 0.5

    def test_drift_tick_sees_a_blocked_loop(self):
        import asyncio

        mon = LoopLagMonitor(interval_s=0.01, half_life_s=5.0)

        async def scenario():
            mon.start_on_loop(asyncio.get_running_loop())
            await asyncio.sleep(0.05)  # let ticks establish a baseline
            time.sleep(0.15)  # block the LOOP (deliberately not await)
            await asyncio.sleep(0.05)  # the overdue tick runs and notes
            return mon.lag_s()

        lag = asyncio.run(scenario())
        assert mon.ticks > 0
        assert lag > 0.05, f"drift tick missed a 150ms loop stall ({lag})"

    def test_thread_driver_is_reentry_safe(self):
        mon = LoopLagMonitor(interval_s=0.01)
        before = {
            t for t in threading.enumerate() if t.name == "kmls-loop-lag"
        }
        first = mon.start_thread()
        # the daemon thread is immortal — a second call must hand back
        # the existing driver, not spawn a tick-double-counting twin
        assert first is not None and mon.start_thread() is first
        spawned = {
            t for t in threading.enumerate() if t.name == "kmls-loop-lag"
        } - before
        assert spawned == {first}

    def test_admission_pressure_folds_lag_as_wait_floor(self):
        mon = LoopLagMonitor(half_life_s=10.0)
        ctl = AdmissionController(budget_s=0.1, lag_source=mon.lag_s)
        assert ctl.pressure(0.0) == pytest.approx(0.0, abs=1e-6)
        mon.note(0.3)
        # 0.3s stall over a 0.1s budget: pressure 3.0 — past the hard
        # ratio, exactly like a 3x-budget queue projection
        assert ctl.pressure(0.0) > 1.5
        decision, pressure = ctl.decide(0.0)
        assert decision == "shed" and pressure > 1.5
        # identical controller without the fold stays blind
        blind = AdmissionController(budget_s=0.1)
        assert blind.decide(0.0)[0] == "admit"


class _StallEngine:
    """The PR 8 repro engine: an injected delay fired at the real fault
    site name inside ``finish()``. Carries the two fallback hooks the
    degraded response path reads."""

    cache_value = "fake-model-date"

    def recommend_many_async(self, seed_sets):
        def finish():
            faults.fire("replica.kernel", replica=0)
            return [([f"rec-{s[0]}"], "rules") for s in seed_sets]

        return finish

    def static_recommendation(self, songs, deadline=None):
        return ["popular-1", "popular-2"]


class TestInlinePathBlindSpotClosed:
    """The PR 8 blind spot was a kernel computed ON the event loop: a
    stall there hid the backlog from the queue projection. Every batch
    now takes the executor hop, so a stalled ``finish()`` leaves the
    loop free and the in-flight batch's age IS the projection."""

    STALL_S = 0.6

    def test_kernel_stall_escalates_ladder_no_5xx(self, tmp_path):
        """Acceptance: the PR 8 repro — an injected kernel delay —
        escalates the admission ladder instead of answering everything
        late: requests that arrive during the stall degrade/shed
        (200+header / 429), and nothing is a 5xx."""
        import asyncio

        cfg = ServingConfig(
            base_dir=str(tmp_path), shed_queue_budget_ms=50.0,
            cache_enabled=False, trace_sample=1.0,
        )
        app = RecommendApp.__new__(RecommendApp)  # no artifacts needed
        app.cfg = cfg
        app.recorder = SpanRecorder(sample=1.0, rng=random.Random(9))
        app.loop_lag = LoopLagMonitor(half_life_s=0.4)
        app.cache = None
        app.metrics = ServingMetrics()
        app.engine = _StallEngine()  # the fallback the degrade rung answers from
        faults.inject(
            "replica.kernel", replica=0, delay_s=self.STALL_S, times=1
        )

        async def scenario():
            app.batcher = AsyncMicroBatcher(
                _StallEngine(), max_size=4, window_ms=1.0,
                shed_queue_budget_ms=50.0, lag_monitor=app.loop_lag,
            )
            body = json.dumps({"songs": ["warm"]}).encode()
            response, warm, t0_warm, trace_warm = app.submit_recommend(body)
            assert response is None
            # finish() stalls on the executor; the loop keeps running,
            # and the in-flight batch ages past the 50 ms budget
            await asyncio.sleep(0.15)
            assert not warm.done()
            statuses, waiting = [], []
            for i in range(6):
                body = json.dumps({"songs": [f"s{i}"]}).encode()
                response, future, t0, trace = app.submit_recommend(body)
                if future is not None:
                    waiting.append((future, t0, trace))
                else:
                    statuses.append(response)
            for future, t0, trace in [(warm, t0_warm, trace_warm)] + waiting:
                await future
                statuses.append(app.finish_recommend(future, t0, trace=trace))
            return [
                (response[0], response[1].get("X-KMLS-Degraded"))
                for response in statuses
            ]

        statuses = asyncio.run(scenario())
        assert len(statuses) == 7
        assert all(code < 500 for code, _ in statuses), statuses
        escalated = [
            (code, why) for code, why in statuses
            if code == 429 or why == "overload"
        ]
        assert escalated, f"ladder never engaged: {statuses}"
        # the ladder decisions are traced (tail retention keeps them all)
        retained = {
            (t["status"], t["attrs"].get("admission"))
            for t in app.recorder.debug_payload()["traces"]
        }
        assert ("shed", "shed") in retained or (
            "degraded", "degrade") in retained

    def test_escalation_needs_no_lag_monitor_and_ends_with_the_stall(self):
        """The executor hop is what closes the gap: with no lag monitor
        at all, requests arriving during the stall are refused by the
        queue projection alone, and once the stalled batch has landed
        everything is admitted again."""
        import asyncio

        from kmlserver_tpu.serving.batcher import Overloaded, OverloadDegraded

        faults.inject(
            "replica.kernel", replica=0, delay_s=self.STALL_S, times=1
        )

        async def scenario():
            batcher = AsyncMicroBatcher(
                _StallEngine(), max_size=4, window_ms=1.0,
                shed_queue_budget_ms=50.0,
            )
            warm = batcher.submit(["warm"])
            await asyncio.sleep(0.15)
            assert not warm.done()
            refused = 0
            for i in range(4):
                try:
                    await batcher.submit([f"during-{i}"])
                except (Overloaded, OverloadDegraded):
                    refused += 1
            await warm
            # past the admission controller's memory of the stall
            await asyncio.sleep(1.0)
            results = []
            for i in range(4):
                results.append(await batcher.submit([f"s{i}"]))
            return refused, results

        refused, results = asyncio.run(scenario())
        assert refused > 0
        assert len(results) == 4  # everything admitted again


# ---------------------------------------------------------------------------
# mining-side telemetry (tentpole c)
# ---------------------------------------------------------------------------


def _mining_pvc(base, **overrides) -> MiningConfig:
    import numpy as np

    from kmlserver_tpu.data.csv import write_tracks_csv

    from .oracle import random_baskets
    from .test_pipeline import table_with_metadata

    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir, exist_ok=True)
    rng = np.random.default_rng(7)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds1.csv"),
        table_with_metadata(
            random_baskets(rng, n_playlists=40, n_tracks=16, mean_len=5)
        ),
    )
    return MiningConfig(
        base_dir=base, datasets_dir=ds_dir, min_support=0.1, **overrides
    )


class TestJobMetricsTextfile:
    def test_successful_run_writes_complete_telemetry(self, tmp_path):
        cfg = _mining_pvc(str(tmp_path))
        summary = run_mining_job(cfg)
        path = os.path.join(cfg.pickles_dir, JOB_METRICS_FILENAME)
        assert os.path.exists(path)
        with open(path) as fh:
            text = fh.read()
        types, samples = parse_exposition(text)
        for name in types:
            assert METRIC_REGISTRY[name].endswith(":mining"), name
        assert "kmls_job_success 1" in text
        assert f"kmls_job_fencing_token {summary.fencing_token}" in text
        for phase in ("encode", "mine", "rules"):
            assert f'kmls_job_phase_duration_seconds{{phase="{phase}"}}' in text
            assert f'kmls_job_phase_resumed{{phase="{phase}"}} 0' in text
        assert "kmls_job_playlists 40" in text
        assert "kmls_job_tracks 16" in text
        # published artifact sizes, nonzero
        artifact_lines = [
            line for line in text.splitlines()
            if line.startswith("kmls_job_artifact_bytes")
        ]
        assert artifact_lines
        assert all(int(line.rsplit(" ", 1)[1]) > 0 for line in artifact_lines)
        # deliberately NOT part of the publication manifest (mid-run
        # rewrites would read as torn publications)
        from kmlserver_tpu.io import artifacts

        manifest = artifacts.load_manifest(cfg.pickles_dir)
        assert JOB_METRICS_FILENAME not in manifest.get("files", {})

    def test_preempted_run_leaves_partial_then_resume_reports_skips(
        self, tmp_path
    ):
        """A job killed after the mine phase leaves success=0 telemetry
        for the phases it DID finish; the resumed job reports those
        phases with resumed=1 and the ORIGINAL compute duration from the
        checkpoint's span annotation."""
        cfg = _mining_pvc(str(tmp_path))
        path = os.path.join(cfg.pickles_dir, JOB_METRICS_FILENAME)
        faults.inject("mine.crash.mine", times=1)
        with pytest.raises(faults.FaultInjected):
            run_mining_job(cfg)
        faults.clear()
        with open(path) as fh:
            interrupted = fh.read()
        parse_exposition(interrupted)
        assert "kmls_job_success 0" in interrupted
        assert 'kmls_job_phase_duration_seconds{phase="mine"}' in interrupted
        assert "kmls_job_last_success_timestamp_seconds" not in interrupted
        mine_duration = float(next(
            line.rsplit(" ", 1)[1]
            for line in interrupted.splitlines()
            if line.startswith('kmls_job_phase_duration_seconds{phase="mine"}')
        ))
        assert mine_duration > 0.0

        run_mining_job(cfg)
        with open(path) as fh:
            resumed = fh.read()
        parse_exposition(resumed)
        assert "kmls_job_success 1" in resumed
        assert 'kmls_job_phase_resumed{phase="encode"} 1' in resumed
        assert 'kmls_job_phase_resumed{phase="mine"} 1' in resumed
        # rules was never checkpointed before the crash: computed fresh
        assert 'kmls_job_phase_resumed{phase="rules"} 0' in resumed
        resumed_duration = float(next(
            line.rsplit(" ", 1)[1]
            for line in resumed.splitlines()
            if line.startswith('kmls_job_phase_duration_seconds{phase="mine"}')
        ))
        # the resumed entry reports the original compute, not the
        # (near-zero) checkpoint-load time
        assert resumed_duration == pytest.approx(mine_duration, rel=0.01)

    def test_success_telemetry_failure_cannot_fail_a_published_run(
        self, tmp_path, monkeypatch, capsys
    ):
        """Registry drift (KeyError from render) at the SUCCESS-path
        finish must not abort a job whose publication already succeeded
        — the abort handler would rewrite the telemetry as success=0 and
        the exit-code contract would report a phantom failure. The job
        completes, the token is published, and the lease is released."""
        cfg = _mining_pvc(str(tmp_path))

        def drifted_finish(self, success, **kw):
            raise KeyError("kmls_job_not_registered")

        monkeypatch.setattr(JobMetrics, "finish", drifted_finish)
        summary = run_mining_job(cfg)
        assert summary.token  # published: invalidation token rewritten
        assert "success telemetry skipped" in capsys.readouterr().out
        # the success path still releases the lease (released marker,
        # token retained); a masked abort would have left it live for
        # the TTL
        with open(os.path.join(cfg.pickles_dir, "publish.lease.json")) as fh:
            assert json.load(fh)["released"] is True

    def test_knob_disables_the_writer(self, tmp_path):
        cfg = _mining_pvc(str(tmp_path), job_metrics=False)
        run_mining_job(cfg)
        assert not os.path.exists(
            os.path.join(cfg.pickles_dir, JOB_METRICS_FILENAME)
        )

    def test_writes_are_atomic(self, tmp_path, monkeypatch):
        """Every rewrite goes through the atomic tmp+replace path — the
        same invariant kmls-verify enforces statically."""
        from kmlserver_tpu.io import artifacts

        calls = []
        real = artifacts.atomic_write_text

        def spy(path, text, **kwargs):
            calls.append(path)
            return real(path, text, **kwargs)

        monkeypatch.setattr(artifacts, "atomic_write_text", spy)
        jm = JobMetrics(str(tmp_path))
        jm.phase_done("encode", 0.5)
        jm.finish(True)
        assert len(calls) == 2
        assert all(c.endswith(JOB_METRICS_FILENAME) for c in calls)

    def test_write_failure_is_best_effort_never_raises(
        self, tmp_path, monkeypatch, caplog
    ):
        """A transient PVC error on the telemetry file must never fail the
        run — especially finish(True), which runs AFTER publication. Only
        OSError is survivable: a registry KeyError (drift) still raises."""
        from kmlserver_tpu.io import artifacts

        def boom(path, text, **kwargs):
            raise OSError(28, "No space left on device")

        monkeypatch.setattr(artifacts, "atomic_write_text", boom)
        jm = JobMetrics(str(tmp_path))
        with caplog.at_level("WARNING", logger="kmlserver_tpu.mining"):
            jm.phase_done("mine", 1.5)
            jm.finish(True)
        assert not os.path.exists(os.path.join(str(tmp_path), JOB_METRICS_FILENAME))
        assert any("job_metrics" in r.message for r in caplog.records)
        # drift protection is NOT best-effort: unregistered series raises
        jm.dataset = {"kmls_job_not_registered": 1}
        with pytest.raises(KeyError):
            jm.write()


# ---------------------------------------------------------------------------
# device-truth cost attribution (ISSUE 12)
# ---------------------------------------------------------------------------

from kmlserver_tpu.observability import costmodel as costmodel_mod  # noqa: E402
from kmlserver_tpu.observability.costmodel import (  # noqa: E402
    KERNEL_COST_SPECS,
    CompileWatcher,
    CostModel,
    classify_roofline,
    phase_cost,
)
from kmlserver_tpu.observability.slo import SLOS, WINDOWS, SloTracker  # noqa: E402

_GENERIC_DIMS = dict(
    b=8, l=4, k_max=16, v=100, k_best=10, shards=2, p=50, r=8, iters=3,
    rows=5,
)


class TestCostSpecs:
    def test_every_spec_yields_positive_cost(self):
        for name, spec in KERNEL_COST_SPECS.items():
            flops = spec.flops(_GENERIC_DIMS)
            moved = spec.bytes_moved(_GENERIC_DIMS)
            assert flops > 0, name
            assert moved > 0, name

    def test_phase_cost_matches_spec_and_rejects_unknown(self):
        flops, moved = phase_cost("support_count", p=50, v=100)
        assert flops == 2.0 * 50 * 100 * 100
        assert moved == 2.0 * 50 * 100 + 100 * 100 * 4.0
        with pytest.raises(KeyError):
            phase_cost("no_such_kernel", p=1)

    def test_flops_scale_with_the_dominant_dim(self):
        """Leading-order sanity: doubling the contraction dim doubles
        (or quadruples, for the quadratic terms) the analytic work."""
        base, _ = phase_cost("als_sweep", p=100, v=50, r=8, iters=2)
        double_p, _ = phase_cost("als_sweep", p=200, v=50, r=8, iters=2)
        assert double_p > 1.8 * base
        sc_base, _ = phase_cost("support_count", p=100, v=50)
        sc_double_v, _ = phase_cost("support_count", p=100, v=100)
        assert sc_double_v > 3.5 * sc_base  # quadratic in v

    def test_roofline_classification(self):
        # intensity 100 flops/byte vs ridge 10 → compute-bound
        assert classify_roofline(1e6, 1e4, 1e12, 1e11) == "compute"
        # intensity 0.1 vs ridge 10 → bandwidth-bound
        assert classify_roofline(1e3, 1e4, 1e12, 1e11) == "bandwidth"


class TestCostModelUnit:
    def _cm(self):
        return CostModel(peak_flops=1e12, peak_bytes_s=1e11)

    def test_observation_accumulates_and_derives_rates(self):
        cm = self._cm()
        cm.observe_kernel("support_count", 0.5, p=1000, v=200)
        cm.observe_kernel("support_count", 0.5, p=1000, v=200)
        stats = cm.kernel_stats()["support_count"]
        assert stats["dispatches"] == 2
        assert stats["device_s"] == pytest.approx(1.0)
        expect_flops = 2 * (2.0 * 1000 * 200 * 200)
        assert stats["flops"] == pytest.approx(expect_flops)
        assert stats["flops_per_s"] == pytest.approx(expect_flops / 1.0)
        assert 0.0 < stats["mfu"] <= 1.0
        assert stats["roofline"] in ("compute", "bandwidth")

    def test_mfu_is_capped_at_one(self):
        cm = CostModel(peak_flops=1.0, peak_bytes_s=1.0)  # absurdly low
        cm.observe_kernel("support_count", 0.001, p=10_000, v=1000)
        assert cm.kernel_stats()["support_count"]["mfu"] == 1.0

    def test_unspecced_kernel_is_counted_not_fatal(self):
        """A drifted kernel name must never 500 the serving path: the
        dispatch is recorded with zero flops and counted loudly (the
        costspec checker catches the drift statically in CI)."""
        cm = self._cm()
        cm.observe_kernel("kernel_from_the_future", 0.1, b=1)
        assert cm.unspecced == {"kernel_from_the_future": 1}
        stats = cm.kernel_stats()["kernel_from_the_future"]
        assert stats["flops"] == 0.0 and stats["device_s"] > 0
        text = "\n".join(cm.render_lines())
        assert "kmls_costmodel_unspecced_total 1" in text

    def test_compile_watcher_counts_growth_only_after_publish(self):
        class FakeJit:
            def __init__(self):
                self.size = 3  # pre-existing compiles: never billed

            def _cache_size(self):
                return self.size

        fn = FakeJit()
        watcher = CompileWatcher()
        watcher.watch("serve_rules", fn)
        fn.size += 2  # warmup compiles during publication
        watcher.mark_published()
        assert watcher.compiles() == {"serve_rules": 0}
        fn.size += 1  # a compile ON the serving path
        assert watcher.compiles() == {"serve_rules": 1}
        # a re-publication: note_prepublish banks the live compile (the
        # counter stays monotonic), then the new warmup is absorbed
        watcher.note_prepublish()
        fn.size += 4  # the re-publication's warmup
        watcher.mark_published()
        assert watcher.compiles() == {"serve_rules": 1}
        fn.size += 2  # serving-path compiles against the new generation
        assert watcher.compiles() == {"serve_rules": 3}

    def test_note_publish_headroom_accounting(self):
        cm = self._cm()
        cm.note_publish(
            {"rule_ids": 600, "rule_confs": 600}, budget_bytes=1000,
            n_shards=4, watermark_bytes=77,
        )
        assert cm.per_device_tensor_bytes() == 300
        assert cm.headroom_bytes() == 700
        text = "\n".join(cm.render_lines())
        assert 'kmls_model_tensor_bytes{artifact="rule_ids"} 600' in text
        assert "kmls_device_budget_bytes 1000" in text
        assert "kmls_device_headroom_bytes 700" in text
        assert "kmls_publish_watermark_bytes 77" in text

    def test_peak_resolution_env_override(self, monkeypatch):
        monkeypatch.setenv("KMLS_PEAK_FLOPS", "5e13")
        monkeypatch.setenv("KMLS_PEAK_BYTES_PER_S", "2e12")
        flops, bw, source = costmodel_mod.resolve_peaks()
        assert flops == 5e13 and bw == 2e12 and source == "env"

    def test_partial_peak_override_names_both_origins(self, monkeypatch):
        """One knob set, one from the table: the provenance label must
        say so — 'env' alone would claim a calibration nobody did."""
        monkeypatch.setenv("KMLS_PEAK_FLOPS", "5e13")
        monkeypatch.delenv("KMLS_PEAK_BYTES_PER_S", raising=False)
        flops, bw, source = costmodel_mod.resolve_peaks()
        assert flops == 5e13 and bw > 0
        assert source.startswith("env+auto"), source
        cm = CostModel(peak_flops=5e13)
        assert cm.peak_source.startswith("explicit+"), cm.peak_source
        assert cm.peak_bytes_s > 0

    def test_unknown_accelerator_kind_raises(self, monkeypatch):
        """A device the table does not know must not be judged against
        the CPU row; both env knobs together are the only way through."""
        import types

        monkeypatch.delenv("KMLS_PEAK_FLOPS", raising=False)
        monkeypatch.delenv("KMLS_PEAK_BYTES_PER_S", raising=False)
        chip = types.SimpleNamespace(platform="npu", device_kind="Mystery 9")
        with pytest.raises(ValueError, match="Mystery 9"):
            costmodel_mod.resolve_peaks(chip)
        v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        assert costmodel_mod.resolve_peaks(v5e)[:2] == (197e12, 819e9)
        monkeypatch.setenv("KMLS_PEAK_FLOPS", "5e13")
        with pytest.raises(ValueError):
            costmodel_mod.resolve_peaks(chip)  # one knob is not a peak pair
        monkeypatch.setenv("KMLS_PEAK_BYTES_PER_S", "2e12")
        assert costmodel_mod.resolve_peaks(chip) == (5e13, 2e12, "env")


class TestCostAttributionLive:
    """The tentpole, end to end on the real serving stack: jitted serve
    kernel + cost model + /metrics exposition."""

    def _app(self, cfg, **over):
        app = RecommendApp(
            dataclasses.replace(
                cfg, cache_enabled=False, **over
            )
        )
        assert app.engine.load()
        return app

    def test_mfu_roofline_and_zero_compiles_on_replayed_traffic(
        self, mined_pvc
    ):
        cfg, _, _ = mined_pvc
        app = self._app(cfg)
        seeds = _rule_seeds(cfg)
        for s in seeds[:12]:
            status, _, _ = _post(app, [s])
            assert status == 200
        cm = app.engine.cost_model
        summary = cm.summary()
        serve = summary["kernels"]["serve_rules"]
        assert serve["dispatches"] > 0
        assert serve["device_s"] > 0
        assert 0.0 < serve["mfu"] <= 1.0
        assert serve["roofline"] in ("compute", "bandwidth")
        # the live zero-compiles-post-publish invariant
        assert summary["compiles_post_publish"].get("serve_rules") == 0
        assert summary["unspecced"] == {}
        # memory accounting: the layout decision's inputs are exported
        assert summary["tensor_bytes"]["rule_ids"] > 0
        assert summary["budget_bytes"] == cfg.device_budget_bytes
        status, _, payload = app.handle("GET", "/metrics", None)
        types, _ = parse_exposition(payload.decode())
        for required in (
            "kmls_kernel_device_seconds", "kmls_kernel_dispatches_total",
            "kmls_mfu", "kmls_kernel_compute_bound", "kmls_compiles_total",
            "kmls_model_tensor_bytes", "kmls_device_headroom_bytes",
            "kmls_costmodel_observations_total",
        ):
            assert required in types, required
        for name, mtype in types.items():
            assert METRIC_REGISTRY[name].split(":", 1)[0] == mtype, name

    def test_cost_device_seconds_agree_with_pr9_histogram(self, mined_pvc):
        """Satellite pin: the cost model's per-kernel fenced device
        seconds and the PR 9 kmls_device_seconds histogram measure the
        same dispatches with the same fence semantics — on a sequential
        replay (every batch is one request) their totals must agree to
        within the batcher's extra span (staging fill before dispatch,
        compose after fence). Wide bounds: this pins the RELATIONSHIP,
        not this host's scheduler."""
        cfg, _, _ = mined_pvc
        app = self._app(cfg)
        seeds = _rule_seeds(cfg)
        for _ in range(3):
            for s in seeds[:8]:
                status, _, _ = _post(app, [s])
                assert status == 200
        cost_s = app.engine.cost_model.kernel_stats()["serve_rules"][
            "device_s"
        ]
        _, hist_sum, hist_n = app.metrics.device_hist.snapshot()
        assert hist_n > 0 and cost_s > 0
        # the engine's fence closes BEFORE the batcher's (conversion vs
        # finish-return + compose), so cost_s <= hist_sum modulo clock
        # jitter; and it must be the same order of magnitude
        assert cost_s <= hist_sum * 1.25 + 0.005, (cost_s, hist_sum)
        assert cost_s >= hist_sum * 0.05 - 0.005, (cost_s, hist_sum)

    def test_embed_kernel_observed_when_hybrid_active(self, tmp_path):
        from kmlserver_tpu.data.csv import write_tracks_csv
        from kmlserver_tpu.data.synthetic import synthetic_table

        ds_dir = tmp_path / "datasets"
        ds_dir.mkdir()
        write_tracks_csv(
            str(ds_dir / "2023_spotify_ds1.csv"),
            synthetic_table(
                n_playlists=80, n_tracks=60, target_rows=2400, seed=11
            ),
        )
        mcfg = MiningConfig(
            base_dir=str(tmp_path), datasets_dir=str(ds_dir),
            min_support=0.05, embed_enabled=True, als_rank=8, als_iters=2,
        )
        run_mining_job(mcfg)
        cfg = dataclasses.replace(
            ServingConfig.from_env(None), base_dir=str(tmp_path),
            cache_enabled=False,
        )
        app = RecommendApp(cfg)
        assert app.engine.load()
        assert app.engine.embedding_active
        for s in app.engine.bundle.vocab[:6]:
            status, _, _ = _post(app, [s])
            assert status == 200
        stats = app.engine.cost_model.kernel_stats()
        assert stats["embed_topk"]["dispatches"] > 0
        assert 0.0 < stats["embed_topk"]["mfu"] <= 1.0
        compiles = app.engine.cost_model.compiles_post_publish()
        assert compiles.get("embed_topk") == 0


class TestCostModelZeroCostWhenDisabled:
    def test_observation_counter_never_moves_with_costmodel_off(
        self, mined_pvc
    ):
        """Began-counter discipline (the ISSUE 12 acceptance proof): with
        KMLS_COSTMODEL=0 the engine holds no CostModel, and real traffic
        must not move the module-level observation counter — nor render
        any cost series."""
        cfg, _, _ = mined_pvc
        app = RecommendApp(
            dataclasses.replace(
                cfg, cache_enabled=False, costmodel_enabled=False
            )
        )
        assert app.engine.load()
        assert app.engine.cost_model is None
        before = costmodel_mod.OBSERVATIONS_TOTAL
        for s in _rule_seeds(cfg)[:6]:
            status, _, _ = _post(app, [s])
            assert status == 200
        assert costmodel_mod.OBSERVATIONS_TOTAL == before
        status, _, payload = app.handle("GET", "/metrics", None)
        text = payload.decode()
        assert "kmls_mfu" not in text
        assert "kmls_kernel_device_seconds" not in text
        parse_exposition(text)


# ---------------------------------------------------------------------------
# SLO burn rates (ISSUE 12)
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


class TestSloTracker:
    def _tracker(self, metrics, clock, **over):
        kwargs = dict(
            p99_target_ms=25.0, error_budget=0.001, degrade_budget=0.01,
            fast_window_s=300.0, slow_window_s=3600.0, clock=clock,
        )
        kwargs.update(over)
        return SloTracker(metrics, **kwargs)

    def test_idle_pod_burns_nothing(self):
        clock = _FakeClock()
        slo = self._tracker(ServingMetrics(), clock)
        rates = slo.burn_rates()
        for s in SLOS:
            for w in WINDOWS:
                assert rates[s][w] == 0.0

    def test_error_burst_burns_fast_then_slow_remembers(self):
        clock = _FakeClock()
        metrics = ServingMetrics()
        slo = self._tracker(metrics, clock)
        slo.burn_rates()  # baseline sample at t=1000, all zeros
        for _ in range(990):
            metrics.record("rules", 0.001)
        for _ in range(10):
            metrics.record_error()
        clock.t += 60
        rates = slo.burn_rates()
        # 10 bad / 1000 attempts = 1% over a 0.1% budget → burn ~10x
        assert rates["availability"]["fast"] == pytest.approx(10.0, rel=0.05)
        assert rates["availability"]["slow"] == pytest.approx(10.0, rel=0.05)
        # the burst stops; past the fast window the fast burn clears
        # while the slow window still remembers it
        for step in range(6):
            clock.t += 60
            slo.burn_rates()  # periodic scrape keeps samples flowing
        clock.t += 60  # now > 300s past the errors
        rates = slo.burn_rates()
        assert rates["availability"]["fast"] == 0.0
        assert rates["availability"]["slow"] > 1.0

    def test_latency_burn_reads_the_e2e_histogram(self):
        clock = _FakeClock()
        metrics = ServingMetrics()
        slo = self._tracker(metrics, clock)
        slo.burn_rates()
        # 100 requests, 5 of them slower than the 25 ms target → 5% bad
        # over the 1% budget → burn 5
        for _ in range(95):
            metrics.record_attribution(0.0, 0.001, 0.002)
        for _ in range(5):
            metrics.record_attribution(0.0, 0.04, 0.05)
        clock.t += 60
        rates = slo.burn_rates()
        assert rates["latency_p99"]["fast"] == pytest.approx(5.0, rel=0.05)

    def test_degraded_answers_burn_the_quality_budget(self):
        clock = _FakeClock()
        metrics = ServingMetrics()
        slo = self._tracker(metrics, clock)
        slo.burn_rates()
        for _ in range(96):
            metrics.record("rules", 0.001)
        for _ in range(4):
            metrics.record_degraded("overload")
            metrics.record("fallback", 0.001)
        clock.t += 60
        rates = slo.burn_rates()
        # 4 degraded / 100 attempts over a 1% budget → burn ~4
        assert rates["quality"]["fast"] == pytest.approx(4.0, rel=0.05)

    def test_latency_target_snaps_up_to_a_bucket_boundary(self):
        slo = self._tracker(
            ServingMetrics(), _FakeClock(), p99_target_ms=30.0
        )
        assert slo.latency_boundary_s == 0.05  # next boundary above 30ms

    def test_render_always_emits_all_six_series(self):
        slo = self._tracker(ServingMetrics(), _FakeClock())
        lines = slo.render_lines()
        assert lines[0] == "# TYPE kmls_slo_burn_rate gauge"
        assert len(lines) == 1 + len(SLOS) * len(WINDOWS)
        for s in SLOS:
            for w in WINDOWS:
                assert any(
                    line.startswith(
                        f'kmls_slo_burn_rate{{slo="{s}",window="{w}"}}'
                    )
                    for line in lines
                ), (s, w)

    def test_debug_endpoint_payload_shape(self, mined_pvc):
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()
        status, _, payload = app.handle("GET", "/debug/slo", None)
        assert status == 200
        body = json.loads(payload)
        assert set(body["burn_rates"]) == set(SLOS)
        assert body["targets"]["latency_p99"]["target_ms"] == cfg.slo_p99_ms
        assert body["windows_s"]["fast"] == cfg.slo_fast_window_s


# ---------------------------------------------------------------------------
# shared loopback guard (ISSUE 12 satellite) — one helper, four endpoints
# ---------------------------------------------------------------------------


class TestLoopbackGuard:
    ENDPOINTS = (
        ("POST", "/metrics/reset"),
        ("GET", "/debug/traces"),
        ("GET", "/debug/slo"),
        ("GET", "/debug/profile?seconds=1"),
    )

    @pytest.fixture()
    def app(self, mined_pvc):
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()
        return app

    @pytest.mark.parametrize("method,path", ENDPOINTS)
    def test_non_loopback_client_gets_403(self, app, method, path):
        status, _, payload = app.handle(
            method, path, None, client_host="10.1.2.3"
        )
        assert status == 403
        assert b"localhost only" in payload

    @pytest.mark.parametrize("method,path", ENDPOINTS)
    @pytest.mark.parametrize(
        "host", [None, "127.0.0.1", "::1", "::ffff:127.0.0.1"]
    )
    def test_loopback_forms_pass_the_guard(self, app, method, path, host):
        status, _, _ = app.handle(method, path, None, client_host=host)
        assert status != 403

    def test_helper_is_the_single_copy(self):
        from kmlserver_tpu.serving.app import is_loopback_host

        assert is_loopback_host(None)
        assert is_loopback_host("127.0.0.1")
        assert is_loopback_host("::1")
        assert is_loopback_host("::ffff:127.0.0.1")
        assert not is_loopback_host("192.168.0.7")
        assert not is_loopback_host("::ffff:192.168.0.7")


# ---------------------------------------------------------------------------
# per-artifact freshness age (ISSUE 12 satellite)
# ---------------------------------------------------------------------------


class TestArtifactAges:
    def test_readyz_and_gauge_report_ages(self, mined_pvc):
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()
        status, _, payload = app.handle("GET", "/readyz", None)
        assert status == 200
        body = json.loads(payload)
        ages = body["artifact_age_seconds"]
        for artifact in ("rules", "popularity", "delta-chain"):
            assert artifact in ages, ages
            assert ages[artifact] >= 0.0
        # no embeddings published → no embeddings age (absent, not 0 —
        # a zero would claim freshness for an artifact that isn't there)
        assert "embeddings" not in ages
        status, _, payload = app.handle("GET", "/metrics", None)
        text = payload.decode()
        assert 'kmls_artifact_age_seconds{artifact="rules"}' in text
        assert 'kmls_artifact_age_seconds{artifact="popularity"}' in text

    def test_ages_empty_before_first_load(self, tmp_path):
        cfg = dataclasses.replace(
            ServingConfig.from_env(None), base_dir=str(tmp_path)
        )
        app = RecommendApp(cfg)
        assert app.engine.artifact_ages() == {}
        status, _, payload = app.handle("GET", "/metrics", None)
        assert b"kmls_artifact_age_seconds" not in payload

    def test_delta_chain_age_equals_rules_until_a_delta_applies(
        self, mined_pvc
    ):
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()
        ages = app.engine.artifact_ages()
        assert ages["delta-chain"] == pytest.approx(ages["rules"], abs=0.5)


# ---------------------------------------------------------------------------
# on-demand profile capture (ISSUE 12)
# ---------------------------------------------------------------------------


class TestDebugProfile:
    def test_refused_without_profile_dir(self, mined_pvc, monkeypatch):
        monkeypatch.delenv("KMLS_PROFILE_DIR", raising=False)
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        status, _, payload = app.handle(
            "GET", "/debug/profile?seconds=1", None
        )
        assert status == 409
        assert b"KMLS_PROFILE_DIR" in payload

    def test_capture_runs_and_dumps_a_trace(
        self, mined_pvc, monkeypatch, tmp_path
    ):
        cfg, _, _ = mined_pvc
        target = tmp_path / "profiles"
        target.mkdir()
        monkeypatch.setenv("KMLS_PROFILE_DIR", str(target))
        app = RecommendApp(cfg)
        assert app.engine.load()
        status, _, payload = app.handle(
            "GET", "/debug/profile?seconds=0.1", None
        )
        assert status == 202, payload
        body = json.loads(payload)
        assert body["status"] == "capturing"
        assert body["seconds"] == pytest.approx(0.1)
        # a second capture while one runs is refused
        status2, _, payload2 = app.handle(
            "GET", "/debug/profile?seconds=0.1", None
        )
        assert status2 == 409 or not app._profile_thread.is_alive()
        app._profile_thread.join(timeout=30)
        assert not app._profile_thread.is_alive()
        assert os.path.isdir(body["dir"])

    def test_bad_seconds_is_422(self, mined_pvc, monkeypatch, tmp_path):
        monkeypatch.setenv("KMLS_PROFILE_DIR", str(tmp_path))
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        status, _, _ = app.handle(
            "GET", "/debug/profile?seconds=banana", None
        )
        assert status == 422


class TestJobPhaseCostTelemetry:
    """ISSUE 12: per-phase analytic FLOPs/bytes attribution in the
    mining textfile — same formulas as the serving MFU."""

    def test_phase_cost_series_render_valid_and_mining_scoped(
        self, tmp_path
    ):
        jm = JobMetrics(str(tmp_path))
        jm.phase_done("mine", 2.0)
        flops, moved = phase_cost("support_count", p=2246, v=2171)
        jm.note_phase_cost("mine", flops, moved)
        jm.finish(True)
        text = jm.render()
        types, samples = parse_exposition(text)
        assert types["kmls_job_phase_flops"] == "gauge"
        assert types["kmls_job_phase_bytes_moved"] == "gauge"
        assert 'kmls_job_phase_flops{phase="mine"}' in text
        for name in ("kmls_job_phase_flops", "kmls_job_phase_bytes_moved"):
            declared_type, _, scope = METRIC_REGISTRY[name].partition(":")
            assert types[name] == declared_type and scope == "mining"

    def test_real_mining_run_attributes_the_mine_phase(self, tmp_path):
        from kmlserver_tpu.data.csv import write_tracks_csv
        from kmlserver_tpu.data.synthetic import synthetic_table

        ds_dir = tmp_path / "datasets"
        ds_dir.mkdir()
        write_tracks_csv(
            str(ds_dir / "2023_spotify_ds1.csv"),
            synthetic_table(
                n_playlists=60, n_tracks=50, target_rows=1500, seed=5
            ),
        )
        run_mining_job(
            MiningConfig(
                base_dir=str(tmp_path), datasets_dir=str(ds_dir),
                min_support=0.05,
            )
        )
        prom = (tmp_path / "pickles" / JOB_METRICS_FILENAME).read_text()
        parse_exposition(prom)
        assert 'kmls_job_phase_flops{phase="mine"}' in prom
        assert 'kmls_job_phase_bytes_moved{phase="mine"}' in prom
        # the attributed work is positive and plausibly 2·p·v² shaped
        for line in prom.splitlines():
            if line.startswith('kmls_job_phase_flops{phase="mine"}'):
                assert float(line.rsplit(" ", 1)[1]) > 0


# ---------------------------------------------------------------------------
# span tree, batch traces, capture mode, dispatch counters (ISSUE 26)
# ---------------------------------------------------------------------------

REQUEST_SPANS = {
    "request", "parse", "cache", "admit", "queue", "batch", "respond",
}
# what a rules-only batch records (a hybrid one adds fetch_embed and
# fill_embed, put_embed, enqueue_embed at the front of stage)
BATCH_SPANS = {
    "batch", "stage", "fill_rules", "put_rules", "dispatch",
    "enqueue_rules", "handoff", "fetch_rules", "compose", "resolve",
}
# the children of stage and dispatch: recorded with TraceContext.child
# under their parent's reserved id, never laps
CHILD_SPANS = {
    "fill_rules": "stage", "put_rules": "stage", "enqueue_rules": "dispatch",
    "fill_embed": "stage", "put_embed": "stage", "enqueue_embed": "stage",
}


def _serve_async(app) -> int:
    """Run ``app`` behind the asyncio transport on a daemon thread → the
    bound port."""
    import asyncio

    from kmlserver_tpu.serving.aioserver import run_async

    port_box: list[int] = []
    ready = threading.Event()

    def runner():
        asyncio.run(run_async(
            app, 0, ready=lambda p: (port_box.append(p), ready.set()),
        ))

    threading.Thread(target=runner, daemon=True).start()
    assert ready.wait(timeout=30)
    return port_box[0]


def _http_post(port: int, songs, trace_id=None):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=15)
    headers = {"Content-Type": "application/json"}
    if trace_id:
        headers["X-KMLS-Trace"] = trace_id
    try:
        conn.request(
            "POST", "/api/recommend/",
            body=json.dumps({"songs": songs}).encode(), headers=headers,
        )
        resp = conn.getresponse()
        resp.read()
        return resp.status, dict(resp.getheaders())
    finally:
        conn.close()


def _assert_tree(trace: dict) -> None:
    """Ids unique, one root first, every other span names a parent that
    exists, and every span's interval lies inside its parent's."""
    json.loads(json.dumps(trace))
    spans = trace["spans"]
    by_id = {s["id"]: s for s in spans}
    assert len(by_id) == len(spans)
    root = spans[0]
    assert (root["id"], root["parent"]) == (0, None)
    assert root["name"] == trace["kind"]
    assert root["duration_ms"] == trace["duration_ms"]
    slack = 2e-4  # start_ms / duration_ms are each rounded to 1e-4 ms
    for span in spans[1:]:
        parent = by_id[span["parent"]]
        assert span["start_ms"] >= parent["start_ms"] - slack, span
        assert (
            span["start_ms"] + span["duration_ms"]
            <= parent["start_ms"] + parent["duration_ms"] + slack
        ), (span, parent)


class _GatedEngine:
    """A fake engine whose finish() waits for a gate, so a test decides
    when the pipeline frees; takes the batch trace like the real one."""

    cache_value = "fake-model-date"

    def __init__(self):
        self.gate = threading.Event()
        self.batches: list[int] = []

    def recommend_many_async(self, seed_sets, trace=None):
        self.batches.append(len(seed_sets))
        if trace is not None:
            trace.skip()
            stage, t = trace.reserve(), time.perf_counter()
            trace.child("fill_rules", stage, t)
            trace.child("put_rules", stage, time.perf_counter())
            trace.lap("stage", span_id=stage)
            dispatch = trace.reserve()
            trace.child("enqueue_rules", dispatch, time.perf_counter())
            trace.lap("dispatch", span_id=dispatch)

        def finish():
            if trace is not None:
                trace.lap("handoff")
            assert self.gate.wait(timeout=10)
            if trace is not None:
                trace.lap("fetch_rules")
                trace.lap("compose")
            return [([f"rec-{s[0]}"], "rules") for s in seed_sets]

        return finish

    def static_recommendation(self, songs, deadline=None):
        return ["popular-1"]


class TestSpanTree:
    def test_lap_and_skip_tile_without_overlap(self):
        rec = SpanRecorder(sample=1.0, rng=random.Random(11))
        t0 = time.perf_counter()
        bt = rec.begin_batch(t0, requests=2, replica=0)
        assert bt.kind == "batch" and bt.attrs["batch_id"] == 1
        bt.skip()
        first = bt.lap("stage")
        second = bt.lap("dispatch")
        time.sleep(0.002)
        bt.skip()  # the hop between dispatch and finish is nobody's
        third = bt.lap("compose")
        rec.finish_batch(bt)
        assert [first, second, third] == [1, 2, 3]
        (doc,) = rec.debug_payload()["batches"]
        _assert_tree(doc)
        stage, dispatch, compose = doc["spans"][1:]
        assert dispatch["start_ms"] == pytest.approx(
            stage["start_ms"] + stage["duration_ms"], abs=2e-4
        )
        gap = compose["start_ms"] - (
            dispatch["start_ms"] + dispatch["duration_ms"]
        )
        assert gap >= 1.9  # the skipped 2 ms
        assert rec.begin_batch(t0).attrs["batch_id"] == 2

    @pytest.mark.parametrize("transport", ["threaded", "async"])
    def test_both_batchers_record_the_same_span_names(
        self, mined_pvc, transport
    ):
        """Satellite: the two batchers share one span helper, so the same
        traffic yields the same names; only ``write`` is the asyncio
        transport's own. Ids and parents round-trip to JSON and every
        span lies inside its parent."""
        cfg, _, _ = mined_pvc
        cfg = dataclasses.replace(cfg, trace_sample=1.0)
        seeds = _rule_seeds(cfg)[:2]
        if transport == "threaded":
            app = RecommendApp(cfg)
            assert app.engine.load()
            status, headers, _ = _post(app, seeds, trace_header="same-1")
            assert status == 200 and headers["X-KMLS-Trace"] == "same-1"
            want = REQUEST_SPANS
        else:
            app = RecommendApp(cfg, defer_batcher=True)
            assert app.engine.load()
            status, headers = _http_post(
                _serve_async(app), seeds, trace_id="same-1"
            )
            assert status == 200 and headers["X-KMLS-Trace"] == "same-1"
            want = REQUEST_SPANS | {"write"}
        deadline = time.time() + 5
        while time.time() < deadline:  # the write closes the trace
            doc = app.recorder.debug_payload()
            if doc["traces"]:
                break
            time.sleep(0.01)
        (trace,) = doc["traces"]
        assert {s["name"] for s in trace["spans"]} == want
        _assert_tree(trace)
        (batch,) = doc["batches"]
        assert {s["name"] for s in batch["spans"]} == BATCH_SPANS
        _assert_tree(batch)
        link = next(s for s in trace["spans"] if s["name"] == "batch")
        assert link["attrs"]["batch_id"] == batch["attrs"]["batch_id"]
        assert batch["attrs"]["requests"] == 1
        assert batch["attrs"]["seeds_real"] == len(seeds)
        queue = next(s for s in trace["spans"] if s["name"] == "queue")
        assert queue["attrs"]["slot_wait_ms"] == 0.0
        admit = next(s for s in trace["spans"] if s["name"] == "admit")
        assert admit["attrs"]["decision"] == "admit"

    def test_jitted_variant_records_fetch_spans_where_it_blocks(
        self, mined_pvc
    ):
        cfg, _, _ = mined_pvc
        from kmlserver_tpu.serving.engine import RecommendEngine

        engine = RecommendEngine(cfg)
        assert engine.load()
        rec = SpanRecorder(sample=1.0, rng=random.Random(12))
        bt = rec.begin_batch(time.perf_counter(), requests=3, replica=0)
        seeds = _rule_seeds(cfg)
        results = engine.recommend_many_async(
            [seeds[:1], seeds[:2], seeds[:3]], trace=bt
        )()
        assert len(results) == 3
        rec.finish_batch(bt)
        (doc,) = rec.debug_payload()["batches"]
        assert [s["name"] for s in doc["spans"]] == [
            "batch", "stage", "fill_rules", "put_rules", "dispatch",
            "enqueue_rules", "handoff", "fetch_rules", "compose",
        ]
        _assert_tree(doc)
        # three requests of 1, 2 and 3 seeds land in the (4, 8) bucket
        assert doc["attrs"]["rows"] == 4 and doc["attrs"]["length"] == 8
        assert doc["attrs"]["seeds_real"] == 6

    def test_hybrid_batch_keeps_the_span_names_the_benchmark_reads(
        self, tmp_path
    ):
        """ISSUE 32: with every result's copy started at dispatch, a
        traced hybrid batch still records exactly
        ``stage``, ``dispatch``, ``fetch_rules``, ``fetch_embed``,
        ``compose``: each side of the hop to the completion thread tiles
        without a gap, and the slot counters count the rule array."""
        from .test_embedding import (
            _cold_and_hot_seeds, _make_pvc, _serving_app,
        )

        run_mining_job(_make_pvc(str(tmp_path)))
        engine = _serving_app(str(tmp_path)).engine
        assert engine.embedding_active
        cold, hot = _cold_and_hot_seeds(engine)
        rec = SpanRecorder(sample=1.0, rng=random.Random(32))
        bt = rec.begin_batch(time.perf_counter(), requests=3, replica=0)
        real0, padded0 = engine.seed_slots_real, engine.seed_slots_padded
        # the cold seed is in the embedding array alone: 3 rule seeds
        results = engine.recommend_many_async(
            [[hot], [cold], [hot, cold, hot]], trace=bt
        )()
        assert [src for _songs, src in results] == ["hybrid", "embed", "hybrid"]
        rec.finish_batch(bt)
        (doc,) = rec.debug_payload()["batches"]
        names = [s["name"] for s in doc["spans"]]
        assert names == [
            "batch", "stage", "fill_embed", "put_embed", "enqueue_embed",
            "fill_rules", "put_rules", "dispatch", "enqueue_rules",
            "handoff", "fetch_embed", "fetch_rules", "compose",
        ]
        _assert_tree(doc)
        span = {s["name"]: s for s in doc["spans"]}
        slack = 2e-4  # start_ms / duration_ms are each rounded to 1e-4 ms

        def end(name):
            return span[name]["start_ms"] + span[name]["duration_ms"]

        for before, after in (
            ("stage", "dispatch"), ("dispatch", "handoff"),
            ("handoff", "fetch_embed"), ("fetch_embed", "fetch_rules"),
            ("fetch_rules", "compose"),
        ):
            assert abs(span[after]["start_ms"] - end(before)) <= slack
        assert span["fetch_embed"]["start_ms"] >= end("dispatch") - slack
        assert doc["attrs"]["rows"] == 4 and doc["attrs"]["length"] == 8
        assert doc["attrs"]["seeds_real"] == 3
        assert engine.seed_slots_real - real0 == 3
        assert engine.seed_slots_padded - padded0 == 4 * 8 - 3

    def test_every_engine_span_name_has_a_bucket_in_the_benchmark(
        self, mined_pvc
    ):
        """A span name that ``benchmark/spans.py`` does not know leaves
        its idle time unclaimed (PR 31 read 5.7% there): whatever
        ``recommend_many_async`` records is a key of
        ``BUCKET_OF``, and stands in ``PRECEDENCE``."""
        from benchmark import spans as bench_spans
        from kmlserver_tpu.serving.engine import RecommendEngine

        source = inspect.getsource(RecommendEngine)
        laps = set(re.findall(r'trace\.lap\("(\w+)"', source))
        assert {"stage", "dispatch", "handoff", "fetch_rules",
                "fetch_embed", "compose"} <= laps
        # handoff is the one lap the readers do not claim: idle time under
        # it stays the batch's ("unclaimed")
        assert "handoff" not in bench_spans.PRECEDENCE
        assert "handoff" not in bench_spans.BUCKET_OF
        laps.discard("handoff")
        assert laps <= set(bench_spans.BUCKET_OF)
        assert laps <= set(bench_spans.PRECEDENCE)
        # a child lies inside its parent, which claims its idle time
        children = set(re.findall(r'trace\.child\(\s*"(\w+)"', source))
        assert children == set(CHILD_SPANS)
        assert set(CHILD_SPANS.values()) <= laps
        # and what a live batch records is among them
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        rec = SpanRecorder(sample=1.0, rng=random.Random(5))
        bt = rec.begin_batch(time.perf_counter(), requests=1, replica=0)
        engine.recommend_many_async([_rule_seeds(cfg)[:2]], trace=bt)()
        rec.finish_batch(bt)
        (doc,) = rec.debug_payload()["batches"]
        assert {s["name"] for s in doc["spans"][1:]} <= (
            laps | children | {"handoff"}
        )

    def test_three_requests_one_batch_trace_three_batch_spans(self):
        """Satellite: requests that share a dispatch share one batch
        trace, named by each member's ``batch`` span; the wait for a
        pipeline slot shows as ``slot_wait_ms``."""
        import asyncio

        rec = SpanRecorder(sample=1.0, rng=random.Random(13))
        engine = _GatedEngine()
        metrics = ServingMetrics()

        async def scenario():
            batcher = AsyncMicroBatcher(
                engine, max_size=3, window_ms=1.0, max_inflight=1,
                metrics=metrics, recorder=rec,
            )
            loop = asyncio.get_running_loop()
            first = rec.begin("lead")
            lead = batcher.submit(["lead"], trace=first)  # fills the pipe
            traces = [rec.begin(f"m{i}") for i in range(3)]
            futures = [
                batcher.submit([f"m{i}"], trace=t)
                for i, t in enumerate(traces)
            ]
            await asyncio.sleep(0.03)  # the three wait for the slot
            assert engine.batches == [1]
            loop.call_later(0.0, engine.gate.set)
            await asyncio.gather(lead, *futures)
            return [first] + traces

        traces = asyncio.run(scenario())
        assert engine.batches == [1, 3]
        for t in traces:
            rec.finish(t, "ok", time.perf_counter() - t.t0)
        doc = rec.debug_payload()
        assert doc["batches_began"] == 2 and len(doc["batches"]) == 2
        shared = doc["batches"][1]
        assert shared["attrs"]["requests"] == 3
        assert {s["name"] for s in shared["spans"]} == BATCH_SPANS
        members = [t for t in doc["traces"] if t["trace_id"] != "lead"]
        assert len(members) == 3
        for member in members:
            link = next(s for s in member["spans"] if s["name"] == "batch")
            assert link["attrs"]["batch_id"] == shared["attrs"]["batch_id"]
            queue = next(s for s in member["spans"] if s["name"] == "queue")
            assert queue["attrs"]["batch"] == 3
            # nearly all of the ~30 ms queue was the wait for the slot
            assert 20.0 < queue["attrs"]["slot_wait_ms"] <= (
                queue["duration_ms"] + 1e-3
            )
        # the batch-size histogram saw the same two dispatches
        counts, total, n = metrics.batch_size_hist.snapshot()
        assert (n, total) == (2, 4.0)

    def test_admit_span_carries_the_ladders_decision(self):
        import asyncio

        rec = SpanRecorder(sample=1.0, rng=random.Random(14))

        async def scenario():
            batcher = AsyncMicroBatcher(
                _GatedEngine(), shed_queue_budget_ms=50.0, recorder=rec,
            )
            batcher._admission.decide = lambda projected: ("shed", 2.0)
            trace = rec.begin("shed-1")
            with pytest.raises(Overloaded):
                batcher.submit(["s"], trace=trace)
            return trace

        trace = asyncio.run(scenario())
        (admit,) = [s for s in trace.spans if s[2] == "admit"]
        assert admit[5] == {"decision": "shed"}
        assert rec.batches_began == 0  # nothing was dispatched

    def test_a_failed_dispatch_closes_its_batch_trace_as_error(self):
        import asyncio

        class Broken(_GatedEngine):
            def recommend_many_async(self, seed_sets, trace=None):
                raise RuntimeError("dispatch refused")

        rec = SpanRecorder(sample=1.0, rng=random.Random(15))

        async def scenario():
            batcher = AsyncMicroBatcher(Broken(), recorder=rec)
            with pytest.raises(RuntimeError):
                await batcher.submit(["s"], trace=rec.begin("x"))

        asyncio.run(scenario())
        (doc,) = rec.debug_payload()["batches"]
        assert doc["status"] == "error"

    def test_deferred_trace_ends_where_the_transport_says(self, mined_pvc):
        """The asyncio transport owns a trace's end: no response builder
        finishes it, and ``trace_written`` adds the ``write`` span and
        closes the root over it."""
        import asyncio

        cfg, _, _ = mined_pvc
        app = RecommendApp(
            dataclasses.replace(cfg, trace_sample=1.0), defer_batcher=True
        )
        assert app.engine.load()
        body = json.dumps({"songs": _rule_seeds(cfg)[:1]}).encode()

        async def scenario():
            app.batcher = AsyncMicroBatcher(
                app.engine, metrics=app.metrics, recorder=app.recorder
            )
            t_received = time.perf_counter()
            response, future, t0, trace = app.submit_recommend(
                body, "defer-1", None, t_received
            )
            assert response is None and trace.deferred
            await future
            response = app.finish_recommend(future, t0, trace=trace)
            assert response[0] == 200
            assert response[1]["X-KMLS-Trace"] == "defer-1"
            # built, stamped, not closed: the write has not happened
            assert trace.status == "ok" and not trace.finished
            assert app.recorder.retained() == 0
            t_w = time.perf_counter()
            app.trace_written(trace, t_w, t_w + 0.001)
            return t_received, t_w

        t_received, t_w = asyncio.run(scenario())
        (doc,) = app.recorder.debug_payload()["traces"]
        assert doc["spans"][-1]["name"] == "write"
        assert doc["duration_ms"] == pytest.approx(
            (t_w + 0.001 - t_received) * 1e3, abs=1e-3
        )
        _assert_tree(doc)


class TestZeroCostAcrossTheNewSites:
    def test_no_trace_object_at_any_new_site_async_transport(self, mined_pvc):
        """Acceptance: with no capture open and KMLS_TRACE_SAMPLE=0 the
        parse, admit, queue, batch, stage..resolve, respond and write
        sites create nothing — neither a request trace nor a batch
        trace — through the asyncio transport and the loop-native
        batcher (the threaded pair is pinned above)."""
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg, defer_batcher=True)
        assert app.engine.load()
        assert not app.recorder.enabled and not app.recorder.active
        port = _serve_async(app)
        for i, seed in enumerate(_rule_seeds(cfg)[:3]):
            status, headers = _http_post(port, [seed], trace_id=f"want-{i}")
            assert status == 200
            assert not any(k.lower() == "x-kmls-trace" for k in headers)
        assert app.batcher.recorder is app.recorder
        assert app.recorder.began == 0
        assert app.recorder.batches_began == 0
        assert app.recorder.retained_total == 0
        # the always-on counters moved all the same
        assert app.metrics.batch_size_hist.snapshot()[2] == 3
        assert app.engine.seed_slots_real == 3


class TestCaptureMode:
    def test_capture_traces_everything_whatever_the_sample_says(self):
        rec = SpanRecorder(sample=0.0)
        assert not rec.active and rec.begin() is None
        rec.capture_begin()
        assert rec.active and not rec.enabled
        t = rec.begin("cap-1")
        t.span("queue", t.t0, t.t0 + 0.001)
        assert rec.finish(t, "ok", 0.002) is False  # the ring stays shut
        bt = rec.begin_batch(t.t0, requests=1, replica=0)
        rec.finish_batch(bt)
        traces = rec.capture_end()
        assert not rec.active and rec.begin() is None
        assert [d["kind"] for d in traces] == ["request", "batch"]
        span = traces[0]["spans"][1]
        # absolute perf_counter nanoseconds, the anchors' clock
        assert span["t_start_ns"] == int(t.t0 * 1e9)
        assert span["t_end_ns"] - span["t_start_ns"] == pytest.approx(
            1e6, abs=2
        )
        assert rec.retained() == 0 and rec.debug_payload()["batches"] == []
        assert rec.capture_end() == []  # closed: nothing accumulates

    def test_profile_capture_switches_it_and_leaves_the_span_file(
        self, mined_pvc, monkeypatch, tmp_path, caplog
    ):
        """Satellite: ``start_capture`` turns capture mode on and off,
        and leaves ``kmls_spans.jsonl`` beside the ``.xplane.pb`` with a
        header, the anchors and one line per trace (CPU backend)."""
        import glob
        import logging

        from kmlserver_tpu.observability.trace import SPANS_FILENAME

        cfg, _, _ = mined_pvc
        target = tmp_path / "profiles"
        target.mkdir()
        monkeypatch.setenv("KMLS_PROFILE_DIR", str(target))
        app = RecommendApp(cfg)  # KMLS_TRACE_SAMPLE=0
        assert app.engine.load()
        assert not app.recorder.active
        caplog.set_level(logging.INFO, logger="kmlserver_tpu.serving")
        status, _, payload = app.handle(
            "GET", "/debug/profile?seconds=1.2", None
        )
        assert status == 202, payload
        deadline = time.time() + 10
        while not app.recorder.active and time.time() < deadline:
            time.sleep(0.005)
        assert app.recorder.active and not app.recorder.enabled
        seeds = _rule_seeds(cfg)
        for i in range(3):
            status, headers, _ = _post(app, [seeds[i]], trace_header=f"c{i}")
            assert status == 200 and headers["X-KMLS-Trace"] == f"c{i}"
        app._profile_thread.join(timeout=60)
        assert not app._profile_thread.is_alive()
        assert not app.recorder.active
        assert _post(app, [seeds[3]])[0] == 200
        assert app.recorder.began == 3  # nothing after the capture closed
        (xplane,) = glob.glob(str(
            target / "*" / "plugins" / "profile" / "*" / "*.xplane.pb"
        ))
        path = os.path.join(os.path.dirname(xplane), SPANS_FILENAME)
        with open(path) as fh:
            lines = [json.loads(line) for line in fh]
        header, traces = lines[0], lines[1:]
        assert header["kind"] == "header" and header["version"] == 1
        assert header["requests"] == 3 and header["batches"] == 3
        assert header["spans"] == sum(len(t["spans"]) for t in traces)
        # right after the start, once a second, right before the stop
        assert len(header["anchors"]) >= 3
        named = [a[0] for a in header["anchors"]]
        assert named == sorted(named)
        assert all(0 <= opened - ns < 1e9 for ns, opened in header["anchors"])
        assert 1.2e9 <= named[-1] - named[0] < 10e9  # the capture's length
        # after each anchor, the clock probe on every local device
        import jax

        from kmlserver_tpu.observability.trace import ClockProbe

        ids = [d.id for d in jax.local_devices()]
        assert [p[0] for p in header["device_probes"]] == (
            ids * ClockProbe.rounds * len(named)
        )
        assert [t["kind"] for t in traces].count("request") == 3
        for t in traces:
            _assert_tree(t)
            assert named[0] - 1e9 < t["spans"][0]["t_start_ns"] < named[-1] + 1e9
        assert (
            f"profile capture closed: dir={os.path.dirname(xplane)} "
            "requests=3 batches=3" in caplog.text
        )
        session = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(xplane)
        )))
        assert f"profile capture open: dir={session} seconds=1.2" in caplog.text
        # the anchors are in the capture's host plane, by name
        from jax.profiler import ProfileData

        found = [
            e.name
            for plane in ProfileData.from_file(xplane).planes
            for line in plane.lines
            for e in line.events
            if e.name.startswith("kmls/clock:")
        ]
        assert sorted(found) == [f"kmls/clock:{ns}" for ns in named]


def _hybrid_engine(tmp_path):
    """A published hybrid generation's engine and a batch of three that
    reaches both families (one seed the rules do not know)."""
    from .test_embedding import _cold_and_hot_seeds, _make_pvc, _serving_app

    run_mining_job(_make_pvc(str(tmp_path)))
    engine = _serving_app(str(tmp_path)).engine
    assert engine.embedding_active
    cold, hot = _cold_and_hot_seeds(engine)
    return engine, [[hot], [cold], [hot, cold, hot]]


def _raw_spans(trace) -> dict[str, tuple]:
    """name → (id, parent, t_start, t_end, attrs) of a live trace, on
    perf_counter's clock, unrounded."""
    return {
        name: (span_id, parent, t0, t1, attrs)
        for span_id, parent, name, t0, t1, attrs in trace.spans
    }


@pytest.fixture(scope="module")
def hybrid_batch(tmp_path_factory):
    """One traced hybrid batch through the real engine, with the moments
    ``_note_staged`` was entered and left → (its raw spans, those two
    moments, the trace)."""
    engine, sets = _hybrid_engine(tmp_path_factory.mktemp("hybrid-spans"))
    noted = []
    real = engine._note_staged

    def note(*args):
        noted.append(time.perf_counter())
        real(*args)
        noted.append(time.perf_counter())

    engine._note_staged = note
    rec = SpanRecorder(sample=1.0, rng=random.Random(44))
    bt = rec.begin_batch(time.perf_counter(), requests=3, replica=0)
    engine.recommend_many_async(sets, trace=bt)()
    rec.finish_batch(bt)
    return _raw_spans(bt), noted, bt


class TestHostPathSpans:
    """The children of ``stage`` and ``dispatch`` and the ``handoff`` lap:
    each names its parent and lies inside it, and the laps around them
    keep their endpoints."""

    @pytest.mark.parametrize("child,parent", sorted(CHILD_SPANS.items()))
    def test_each_child_names_its_parent_and_lies_inside_it(
        self, hybrid_batch, child, parent
    ):
        spans, _, _ = hybrid_batch
        cid, cparent, c0, c1, _ = spans[child]
        pid, pparent, p0, p1, _ = spans[parent]
        assert (cparent, pparent) == (pid, 0)
        assert cid > pid  # ids follow the order spans begin in
        assert p0 <= c0 <= c1 <= p1

    def test_children_sum_to_no_more_than_their_parent(self, hybrid_batch):
        spans, _, _ = hybrid_batch

        def length(name):
            return spans[name][3] - spans[name][2]

        assert sum(length(n) for n in (
            "fill_embed", "put_embed", "enqueue_embed", "fill_rules",
            "put_rules",
        )) <= length("stage")
        assert length("enqueue_rules") <= length("dispatch")
        # in the order the work is done: the embedding family first
        for order in (
            ["fill_embed", "put_embed", "enqueue_embed", "fill_rules",
             "put_rules"],
            ["enqueue_rules"],
        ):
            for before, after in zip(order, order[1:]):
                assert spans[before][3] <= spans[after][2], (before, after)

    def test_stage_still_ends_in_note_staged_after_put_rules(self, hybrid_batch):
        spans, (entered, left), _ = hybrid_batch
        stage_end = spans["stage"][3]
        assert entered <= stage_end <= left
        assert spans["put_rules"][3] <= entered

    def test_the_laps_still_tile_across_the_new_handoff(self, hybrid_batch):
        """``dispatch`` ends where ``handoff`` starts and the first
        pick-up, ``fetch_embed`` in a hybrid batch, starts where
        ``handoff`` ends, to the nanosecond: the laps tile the batch, and
        the hop to ``finish()`` is one of them."""
        spans, _, bt = hybrid_batch
        assert spans["handoff"][1] == 0  # a child of the batch
        assert spans["handoff"][2] == spans["dispatch"][3]
        assert spans["fetch_embed"][2] == spans["handoff"][3]
        assert spans["dispatch"][2] == spans["stage"][3]
        assert spans["fetch_rules"][2] == spans["fetch_embed"][3]
        assert spans["compose"][2] == spans["fetch_rules"][3]
        assert bt.cursor == spans["compose"][3]

    @pytest.mark.parametrize("transport", ["threaded", "async"])
    def test_handoff_in_both_batchers(self, mined_pvc, transport):
        """Both batchers hand a batch's ``finish()`` to another thread;
        the engine names that hop in either, from the end of ``dispatch``
        to the start of ``fetch_rules``."""
        cfg, _, _ = mined_pvc
        cfg = dataclasses.replace(cfg, trace_sample=1.0)
        seeds = _rule_seeds(cfg)[:2]
        if transport == "threaded":
            app = RecommendApp(cfg)
            assert app.engine.load()
            assert _post(app, seeds)[0] == 200
        else:
            app = RecommendApp(cfg, defer_batcher=True)
            assert app.engine.load()
            assert _http_post(_serve_async(app), seeds)[0] == 200
        deadline = time.time() + 5
        while time.time() < deadline and not app.recorder.debug_payload()["batches"]:
            time.sleep(0.01)
        (batch,) = app.recorder.debug_payload()["batches"]
        _assert_tree(batch)
        at = {s["name"]: s for s in batch["spans"]}
        handoff = at["handoff"]
        assert handoff["parent"] == 0 and handoff["duration_ms"] >= 0.0

        def end(span):
            return span["start_ms"] + span["duration_ms"]

        assert handoff["start_ms"] == pytest.approx(end(at["dispatch"]), abs=2e-4)
        assert at["fetch_rules"]["start_ms"] == pytest.approx(end(handoff), abs=2e-4)

    def test_tracing_off_records_nothing_and_runs_no_probe(
        self, mined_pvc, monkeypatch
    ):
        """Off means one is-None check at each new site: no context, no
        reserved id, no child, no probe."""
        from kmlserver_tpu.observability import trace as trace_mod

        def refuse(*args, **kwargs):
            raise AssertionError("a tracing call ran with tracing off")

        for name in ("reserve", "child", "lap", "span"):
            monkeypatch.setattr(trace_mod.TraceContext, name, refuse)
        monkeypatch.setattr(trace_mod, "ClockProbe", refuse)
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()
        assert not app.recorder.active
        for seed in _rule_seeds(cfg)[:3]:
            assert _post(app, [seed])[0] == 200
        assert app.recorder.began == 0
        assert app.recorder.batches_began == 0
        assert app.engine.seed_slots_real == 3


_PROBE_CAPTURE = r"""
import contextlib, json, sys, time
from kmlserver_tpu.utils.virtualcpu import force_virtual_cpu
force_virtual_cpu(4)
import jax
from jax._src.dispatch import BACKEND_COMPILE_EVENT
from kmlserver_tpu.observability import SpanRecorder
from kmlserver_tpu.utils import profiling

compiles, session = [], []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: compiles.append(time.perf_counter_ns())
    if event == BACKEND_COMPILE_EVENT else None
)
real_session = profiling.trace_session

@contextlib.contextmanager
def watched(label):
    with real_session(label):
        session.append(time.perf_counter_ns())
        yield
        session.append(time.perf_counter_ns())

profiling.trace_session = watched
thread = profiling.start_capture("probe", float(sys.argv[1]), recorder=SpanRecorder())
thread.join(timeout=120)
assert not thread.is_alive()
print(json.dumps({"compiles": compiles, "session": session,
                  "devices": [d.id for d in jax.local_devices()]}))
"""


class TestClockProbe:
    def test_probe_runs_on_every_device_and_counts_its_bounds(self):
        from kmlserver_tpu.observability.trace import ClockProbe

        probe = ClockProbe()
        first, second = probe(), probe()
        import jax

        ids = [d.id for d in jax.local_devices()]
        for run in (first, second):
            # every device in turn, the rounds one after the other
            assert [p[0] for p in run] == ids * ClockProbe.rounds
            assert all(before < after for _, before, after in run)
            assert all(a[2] <= b[1] for a, b in zip(run, run[1:]))
        assert first[-1][2] <= second[0][1]
        # one executable a device, compiled when the probe was built
        assert probe._run._cache_size() == len(ids)

    def test_header_carries_device_probes_on_four_devices_without_compiling(
        self, tmp_path
    ):
        """A CPU capture with four virtual devices: ``device_probes`` holds
        one probe a device at every anchor, each inside the session, and
        no compile lands inside the session (the probe was compiled before
        it opened)."""
        import subprocess
        import sys

        from kmlserver_tpu.observability.trace import SPANS_FILENAME

        env = dict(os.environ, KMLS_PROFILE_DIR=str(tmp_path))
        proc = subprocess.run(
            [sys.executable, "-c", _PROBE_CAPTURE, "1.5"],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=env, capture_output=True, text=True, timeout=240,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["devices"] == [0, 1, 2, 3]
        opened, closed = out["session"]
        assert not [t for t in out["compiles"] if opened <= t <= closed]
        (path,) = [
            os.path.join(d, SPANS_FILENAME) for d, _, files in os.walk(tmp_path)
            if SPANS_FILENAME in files
        ]
        with open(path) as fh:
            header = json.loads(fh.readline())
        assert header["version"] == 1 and len(header["anchors"]) >= 3
        from kmlserver_tpu.observability.trace import ClockProbe

        probes = header["device_probes"]
        per_anchor = 4 * ClockProbe.rounds
        assert len(probes) == per_anchor * len(header["anchors"])
        for i, (named, _) in enumerate(header["anchors"]):
            mine = probes[per_anchor * i: per_anchor * (i + 1)]
            assert [p[0] for p in mine] == [0, 1, 2, 3] * ClockProbe.rounds
            assert all(named < before < after for _, before, after in mine)
            assert all(opened <= p[1] and p[2] <= closed for p in mine)


class TestDispatchCounters:
    def test_seed_slots_count_exactly_rows_times_length(self, mined_pvc):
        """``kmls_seed_slots_total``: real + padded is the staged array's
        size, dispatch by dispatch, on a hand-built batch (the shape
        is a bucket)."""
        from kmlserver_tpu.serving.engine import RecommendEngine

        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        seeds = _rule_seeds(cfg)
        real0, padded0 = engine.seed_slots_real, engine.seed_slots_padded
        # 3 requests -> 4 rows; longest 9 seeds (3 known) -> length 32
        batch = [seeds[:1], seeds[:3] + ["nobody-knows-me"] * 6, seeds[:2]]
        engine.recommend_many_async(batch)()
        assert engine.seed_slots_real - real0 == 6
        assert engine.seed_slots_padded - padded0 == 4 * 32 - 6
        engine.recommend_many_async([seeds[:1]])()  # (1, 1): no padding
        assert engine.seed_slots_real - real0 == 7
        assert engine.seed_slots_padded - padded0 == 4 * 32 - 6

    def test_new_series_render_valid_and_registry_backed(self, mined_pvc):
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()
        for seed in _rule_seeds(cfg)[:2]:
            assert _post(app, [seed, "nobody-knows-me"])[0] == 200
        text = app.handle("GET", "/metrics", None)[2].decode()
        types, _ = parse_exposition(text)
        for name, mtype in (
            ("kmls_batch_size", "histogram"),
            ("kmls_seed_slots_total", "counter"),
            ("kmls_unwarmed_dispatches_total", "counter"),
        ):
            assert types[name] == mtype
            assert METRIC_REGISTRY[name] == f"{mtype}:serving"
        # two batches of one request: two seeds (one known) in the
        # (1, 8) bucket each
        assert 'kmls_seed_slots_total{kind="real"} 2' in text
        assert 'kmls_seed_slots_total{kind="padded"} 14' in text
        assert "kmls_batch_size_count 2" in text
        assert "kmls_batch_size_sum 2.000000" in text
        assert "kmls_unwarmed_dispatches_total 0" in text
        # and the help text says what the interval called "device" is
        assert "# HELP kmls_device_ms batch formed to batch resolved" in text

    def test_batch_size_buckets_are_cumulative(self):
        m = ServingMetrics()
        for n in (1, 1, 2, 3, 8, 32, 40):
            m.record_batch_size(n)
        lines = m.batch_size_hist.render("kmls_batch_size")
        got = {
            line.split(" ")[0]: int(line.split(" ")[1])
            for line in lines if "_bucket" in line
        }
        assert got == {
            'kmls_batch_size_bucket{le="1"}': 2,
            'kmls_batch_size_bucket{le="2"}': 3,
            'kmls_batch_size_bucket{le="4"}': 4,
            'kmls_batch_size_bucket{le="8"}': 5,
            'kmls_batch_size_bucket{le="16"}': 5,
            'kmls_batch_size_bucket{le="32"}': 6,
            'kmls_batch_size_bucket{le="+Inf"}': 7,
        }
        assert "kmls_batch_size_sum 87.000000" in lines
        assert "kmls_batch_size_count 7" in lines

    def test_unwarmed_dispatches_total_moves_on_an_unwarmed_shape(
        self, mined_pvc
    ):
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()
        seeds = _rule_seeds(cfg)
        app.engine.recommend_many_async([seeds[:1]])()
        assert "kmls_unwarmed_dispatches_total 0" in (
            app.handle("GET", "/metrics", None)[2].decode()
        )
        # 33 rows is past batch_max_size: rounded up to 64, never warmed
        app.engine.recommend_many_async([seeds[:1]] * 33)()
        assert app.engine.unwarmed_dispatches == 1
        assert "kmls_unwarmed_dispatches_total 1" in (
            app.handle("GET", "/metrics", None)[2].decode()
        )
