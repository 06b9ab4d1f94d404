"""The tail-latency serving layer: shape-bucketed pre-warm (no compile on
the serving path), adaptive deadline-aware batching, load shedding (429 +
Retry-After), fresh-staging-array exactness, and queue/device latency
attribution."""

import dataclasses
import json
import threading
import time

import numpy as np
import pytest

from kmlserver_tpu.config import ServingConfig
from kmlserver_tpu.io import artifacts
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.batcher import (
    AdmissionController,
    MicroBatcher,
    Overloaded,
    OverloadDegraded,
)
from kmlserver_tpu.serving.engine import RecommendEngine
from kmlserver_tpu.serving.metrics import ServingMetrics
from kmlserver_tpu.serving.replay import replay, sample_seed_sets

from .test_serving import mined_pvc  # noqa: F401  (fixture re-export)


def _rule_seeds(cfg) -> list[str]:
    rules_dict = artifacts.load_pickle(
        f"{cfg.base_dir}/pickles/{cfg.recommendations_file}"
    )
    return [s for s, row in rules_dict.items() if row]


class TestBucketedCompilation:
    def test_batch_bucket_math(self, tmp_path):
        engine = RecommendEngine(
            ServingConfig(base_dir=str(tmp_path), batch_max_size=32)
        )
        assert engine._batch_buckets() == [1, 2, 4, 8, 16, 32]
        assert engine._bucket_batch(1) == 1
        assert engine._bucket_batch(3) == 4
        assert engine._bucket_batch(17) == 32
        assert engine._bucket_batch(32) == 32
        # oversized (direct recommend_many callers only): multiples of cap
        assert engine._bucket_batch(33) == 64
        assert engine._bucket_batch(65) == 96
        # a non-power-of-two cap is always its own bucket
        engine24 = RecommendEngine(
            ServingConfig(base_dir=str(tmp_path), batch_max_size=24)
        )
        assert engine24._batch_buckets() == [1, 2, 4, 8, 16, 24]
        assert engine24._bucket_batch(20) == 24

    def test_prewarm_covers_every_bucket_no_compile_when_serving(
        self, mined_pvc
    ):
        """Acceptance: after the engine reports ready, no jit compilation
        happens on the serving path — proven by the jitted kernel's compile
        cache not growing AND the engine's unwarmed-dispatch counter
        staying zero across every batch size a request can produce."""
        from kmlserver_tpu.ops import serve as serve_ops

        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        bundle = engine.bundle
        for batch in engine._batch_buckets():
            for length in engine._len_buckets():
                assert (batch, length) in bundle.warmed_shapes
        seeds = _rule_seeds(cfg)
        counter = getattr(serve_ops.recommend_batch, "_cache_size", None)
        n0 = counter() if counter else None
        for b in (1, 2, 3, 5, 8, 13, 27, 32):
            results = engine.recommend_many(
                [[seeds[i % len(seeds)]] for i in range(b)]
            )
            assert len(results) == b
        engine.recommend(seeds[:2])
        engine.recommend(["totally-unknown"])  # answered by the fallback
        assert engine.unwarmed_dispatches == 0
        if counter:
            assert counter() == n0, "a serving request compiled a kernel"

    def test_unwarmed_shape_is_counted_not_silent(self, mined_pvc):
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        seeds = _rule_seeds(cfg)
        # an oversized direct batch (> batch_max_size) has no warmed bucket
        engine.recommend_many([[seeds[0]]] * (cfg.batch_max_size + 1))
        assert engine.unwarmed_dispatches == 1


class TestStagingReuse:
    """What staging-buffer reuse once had to defend, now guarded by the
    rule that every dispatch fills a fresh host array."""

    def test_overlapping_same_shape_dispatches_stay_exact(self, mined_pvc):
        """Two in-flight batches of the SAME padded shape each stage an
        array of their own. Results must match the per-request oracle —
        were a host array shared and the device transfer aliased to it,
        batch 1 would answer with batch 2's seeds."""
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        seeds = _rule_seeds(cfg)
        assert len(seeds) >= 4
        sets_a = [[seeds[0]], [seeds[1]]]
        sets_b = [[seeds[2]], [seeds[3]]]
        expected = {s: engine.recommend([s]) for s in seeds[:4]}
        finish_a = engine.recommend_many_async(sets_a)
        finish_b = engine.recommend_many_async(sets_b)  # same (2, L) bucket
        for seed_sets, finish in ((sets_a, finish_a), (sets_b, finish_b)):
            for (got, source), (seed,) in zip(finish(), seed_sets):
                assert set(got) == set(expected[seed][0])
                assert source == expected[seed][1]

    def test_fallback_rows_survive_buffer_refill(self, mined_pvc):
        # the known-row mask belongs to its own dispatch — an all-unknown
        # row must still fall back correctly with another dispatch in
        # between
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        seeds = _rule_seeds(cfg)
        f1 = engine.recommend_many_async([["unknown-x"], [seeds[0]]])
        f2 = engine.recommend_many_async([[seeds[1]], [seeds[2]]])
        r1, r2 = f1(), f2()
        assert r1[0][1] == "fallback"
        assert r1[1][1] in ("rules", "empty")
        assert all(src in ("rules", "empty") for _, src in r2)


class TestAdaptiveWindow:
    class _InstantEngine:
        def recommend_many_async(self, seed_sets):
            def finish():
                return [(list(s), "rules") for s in seed_sets]

            return finish

    def test_window_tracks_arrival_rate(self):
        b = MicroBatcher(
            self._InstantEngine(), max_size=32, window_ms=10.0,
            adaptive=True, window_min_ms=1.0,
        )
        from kmlserver_tpu.serving.batcher import _Pending
        from concurrent.futures import Future

        now = time.perf_counter()
        batch = [_Pending(["x"], Future(), now)]
        # no arrivals observed yet: fall back to the fixed ceiling
        assert b._busy_window_s(batch, now) == pytest.approx(0.010)
        # sparse traffic (10 ms mean gap): filling 31 slots needs ~310 ms
        # — clamped to the ceiling, same as the fixed window
        b._arrivals.clear()
        b._arrivals.extend(i * 0.010 for i in range(10))
        assert b._arrival_gap_s() == pytest.approx(0.010)
        assert b._busy_window_s(batch, now) == pytest.approx(0.010)
        # dense traffic (0.1 ms mean gap): a nearly-full batch stops
        # waiting at the floor instead of burning the ceiling on one
        # straggler
        b._arrivals.clear()
        b._arrivals.extend(i * 0.0001 for i in range(10))
        nearly_full = batch + [
            _Pending(["y"], Future(), now) for _ in range(30)
        ]
        assert b._busy_window_s(nearly_full, now) == pytest.approx(0.001)

    def test_window_capped_by_shed_budget_deadline(self):
        b = MicroBatcher(
            self._InstantEngine(), max_size=32, window_ms=10.0,
            adaptive=True, window_min_ms=1.0, shed_queue_budget_ms=50.0,
        )
        from kmlserver_tpu.serving.batcher import _Pending
        from concurrent.futures import Future

        now = time.perf_counter()
        # the batch leader has already waited 45 of its 50 ms budget: the
        # window must shrink to the 5 ms remaining, ceiling notwithstanding
        leader = _Pending(["x"], Future(), now - 0.045)
        got = b._busy_window_s([leader], now)
        assert got == pytest.approx(0.005, abs=0.001)
        # budget exhausted → no wait at all
        overdue = _Pending(["x"], Future(), now - 0.100)
        assert b._busy_window_s([overdue], now) == 0.0

    def test_tail_bounded_under_poisson_load(self, mined_pvc):
        """Seeded Poisson arrivals through the full engine + batcher: the
        p99/p50 ratio stays bounded (the r05 replay showed 5.4x with the
        fixed window + single 32-wide kernel shape)."""
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        metrics = ServingMetrics()
        batcher = MicroBatcher(
            engine, max_size=cfg.batch_max_size, window_ms=2.0,
            max_inflight=4, adaptive=True, metrics=metrics,
        )
        payloads = sample_seed_sets(engine.bundle.vocab, 1200, rng_seed=9)
        report = replay(
            lambda seeds: batcher.recommend(seeds)[1], payloads, qps=600.0
        )
        assert report.n_errors == 0
        assert sum(report.by_source.values()) == 1200
        if report.offered_qps < 0.8 * 600.0:
            # the thread-per-request loadgen couldn't sustain the target —
            # the HOST is degraded, and a tail measured through a degraded
            # harness asserts nothing about the batcher
            pytest.skip(
                f"loadgen degraded ({report.offered_qps:.0f} of 600 QPS "
                "offered); host too noisy for a tail assertion"
            )
        # generous bounds (CI hosts are noisy); the bench pins the tight
        # 3x/25ms acceptance on a quiet host
        assert report.p99_ms <= max(6.0 * report.p50_ms, 30.0), (
            f"tail blowup: p50 {report.p50_ms:.2f}ms "
            f"p99 {report.p99_ms:.2f}ms"
        )
        # attribution flowed through: every completed request observed
        n99 = metrics.queue_wait.percentiles(0.99)[0]
        assert metrics.e2e.percentiles(0.5)[0] > 0
        assert np.isfinite(n99)


class TestLoadShedding:
    class _SlowEngine:
        """Every batch takes a fixed 50 ms on the 'device'."""

        def recommend_many_async(self, seed_sets):
            def finish():
                time.sleep(0.05)
                return [(list(s), "rules") for s in seed_sets]

            return finish

    def test_sheds_before_queue_wait_budget_breached(self):
        budget_ms = 120.0
        metrics = ServingMetrics()
        batcher = MicroBatcher(
            self._SlowEngine(), max_size=4, window_ms=1.0, max_inflight=1,
            shed_queue_budget_ms=budget_ms, metrics=metrics,
        )
        # one sequential request first: the projection needs device-time
        # evidence (a fully cold controller deliberately never sheds, and
        # its first-batch learning window would admit a deep queue)
        batcher.recommend(["warm"], timeout=10.0)
        outcomes = {"ok": 0, "shed": 0, "degraded": 0, "other": 0}
        lock = threading.Lock()

        def worker(i):
            try:
                batcher.recommend([f"s{i}"], timeout=30.0)
                key = "ok"
            except Overloaded as exc:
                # Retry-After carries bounded jitter: base 1s ± 50%
                assert 0.5 <= exc.retry_after_s <= 1.5
                key = "shed"
            except OverloadDegraded:
                # the ladder rung before any 429: the app layer answers
                # these from the popularity fallback with HTTP 200
                key = "degraded"
            except Exception:
                key = "other"
            with lock:
                outcomes[key] += 1

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(150)
        ]
        for t in threads:
            t.start()
            time.sleep(0.001)  # sustained pressure, not one instant burst
        for t in threads:
            t.join()
        assert outcomes["other"] == 0
        assert outcomes["shed"] > 0, "overload never shed"
        assert outcomes["ok"] > 0, "shedding rejected everything"
        # this workload drives pressure well past the budget, so the
        # degrade band must have fired on the way up
        assert outcomes["degraded"] > 0, "ladder never degraded"
        assert batcher.shed_total == outcomes["shed"]
        assert metrics.shed_total == outcomes["shed"]
        assert batcher.degrade_total == outcomes["degraded"]
        # the point of shedding: ADMITTED requests keep a bounded queue
        # wait. Unshed, 150 requests at 4-per-50ms mean the last admitted
        # would wait ~1.9 s; with the budget the observed p99 stays within
        # a couple of service times of it.
        (qw_p99,) = metrics.queue_wait.percentiles(0.99)
        assert qw_p99 * 1e3 <= budget_ms + 150.0, (
            f"admitted queue wait p99 {qw_p99 * 1e3:.0f}ms far exceeds "
            f"the {budget_ms:.0f}ms budget"
        )

    def test_cold_batcher_never_sheds(self):
        # no device-time evidence yet → no shedding, however long the queue
        batcher = MicroBatcher(
            self._SlowEngine(), max_size=4, window_ms=1.0,
            shed_queue_budget_ms=1e-6,
        )
        assert batcher.projected_queue_wait_s() == 0.0
        got, _ = batcher.recommend(["x"])
        assert got == ["x"]

    def test_app_returns_429_with_retry_after(self, tmp_path):
        app = RecommendApp(ServingConfig(base_dir=str(tmp_path)))

        class SheddingBatcher:
            def recommend(self, seeds, timeout=30.0):
                raise Overloaded(
                    retry_after_s=1.3, projected_wait_ms=500.0
                )

        app.batcher = SheddingBatcher()
        status, headers, payload = app.handle(
            "POST", "/api/recommend/", json.dumps({"songs": ["x"]}).encode()
        )
        assert status == 429
        # RFC 9110 delay-seconds: integer ONLY (a decimal crashes
        # urllib3's Retry.parse_retry_after); the batcher's sub-second
        # jitter survives as a ceil onto adjacent whole seconds
        assert headers["Retry-After"] == "2"
        body = json.loads(payload)
        assert "overloaded" in body["detail"]

    def test_app_degrades_overload_band_to_fallback(self, tmp_path):
        """The ladder rung before any 429: OverloadDegraded from the
        batcher answers 200 + X-KMLS-Degraded: overload from the
        popularity fallback, and the degraded counter moves."""
        from kmlserver_tpu.config import MiningConfig
        from kmlserver_tpu.data.csv import write_tracks_csv
        from kmlserver_tpu.mining.pipeline import run_mining_job

        from .oracle import random_baskets
        from .test_ops import table_from_baskets

        rng = np.random.default_rng(21)
        ds_dir = tmp_path / "datasets"
        ds_dir.mkdir()
        write_tracks_csv(
            str(ds_dir / "2023_spotify_ds1.csv"),
            table_from_baskets(
                random_baskets(rng, n_playlists=40, n_tracks=12, mean_len=5)
            ),
        )
        run_mining_job(MiningConfig(
            base_dir=str(tmp_path), datasets_dir=str(ds_dir),
            min_support=0.05, k_max_consequents=16,
            top_tracks_save_percentile=0.5,
        ))
        app = RecommendApp(ServingConfig(
            base_dir=str(tmp_path), polling_wait_in_minutes=60.0,
        ))
        assert app.engine.load()

        class DegradingBatcher:
            def submit(self, seeds, deadline=None):
                raise OverloadDegraded(0.8)

            def recommend(self, seeds, timeout=30.0, deadline=None):
                raise OverloadDegraded(0.8)

        app.batcher = DegradingBatcher()
        status, headers, payload = app.handle(
            "POST", "/api/recommend/", json.dumps({"songs": ["x"]}).encode()
        )
        assert status == 200
        assert headers.get("X-KMLS-Degraded") == "overload"
        assert json.loads(payload)["songs"]
        assert app.metrics.degraded_by_reason.get("overload", 0) == 1


class TestAdmissionController:
    """Unit coverage for the pressure ladder, the Retry-After jitter
    bounds, and the queue-wait EWMA's time decay."""

    def test_bands_admit_degrade_shed(self):
        ctrl = AdmissionController(
            1.0, soft_ratio=0.5, hard_ratio=2.0, rng=__import__(
                "random").Random(7),
        )
        decision, pressure = ctrl.decide(0.2)  # below soft
        assert decision == "admit" and pressure == 0.2
        assert ctrl.decide(5.0)[0] == "shed"   # past hard
        # mid-degrade band: over many draws, a MIX of admit and degrade,
        # never a shed
        mid = [ctrl.decide(0.75)[0] for _ in range(400)]
        assert set(mid) == {"admit", "degrade"}
        # between budget and hard: shed and degrade mix, never full admit
        upper = [ctrl.decide(1.5)[0] for _ in range(400)]
        assert set(upper) == {"degrade", "shed"}
        # probability ramps: deeper into the band sheds more often
        deep = [ctrl.decide(1.9)[0] for _ in range(400)]
        assert deep.count("shed") > upper.count("shed")

    def test_legacy_cliff_ratios(self):
        # soft=hard=1.0 reproduces the pre-controller cliff exactly
        ctrl = AdmissionController(1.0, soft_ratio=1.0, hard_ratio=1.0)
        assert ctrl.decide(0.999)[0] == "admit"
        assert ctrl.decide(1.0)[0] == "shed"

    def test_retry_after_jitter_bounded_and_varied(self):
        ctrl = AdmissionController(
            1.0, retry_after_s=1.0, retry_jitter=0.5,
            rng=__import__("random").Random(3),
        )
        draws = [ctrl.retry_after_jittered_s() for _ in range(200)]
        assert all(0.5 <= d <= 1.5 for d in draws)
        assert len({round(d, 3) for d in draws}) > 50, "jitter is constant"
        # jitter off restores the constant hint
        flat = AdmissionController(1.0, retry_after_s=2.0, retry_jitter=0.0)
        assert flat.retry_after_jittered_s() == 2.0

    def test_queue_wait_ewma_decays_after_burst(self):
        ctrl = AdmissionController(0.1, soft_ratio=0.5, hard_ratio=1.5)
        t0 = 100.0
        ctrl.note_queue_wait(0.5, now=t0)  # 5x the budget: hard overload
        assert ctrl.pressure(0.0, now=t0) > 1.5
        # with no new completions, time alone brings pressure back down
        # (half-life = max(budget, 0.25s))
        assert ctrl.pressure(0.0, now=t0 + 2.0) < ctrl.pressure(0.0, now=t0)
        assert ctrl.pressure(0.0, now=t0 + 30.0) < 0.05

    def test_pressure_zero_with_shedding_off(self):
        ctrl = AdmissionController(0.0)
        ctrl.note_queue_wait(10.0, now=1.0)
        assert ctrl.pressure(10.0, now=1.0) == 0.0

    def test_utilization_signal_rises_with_inflight(self):
        """The HPA signal: 0 idle, >0 with a batch in flight, and queue
        pressure lifts it past occupancy alone."""
        release = threading.Event()

        class GateEngine:
            def recommend_many_async(self, seed_sets):
                def finish():
                    release.wait(timeout=10.0)
                    return [(list(s), "rules") for s in seed_sets]

                return finish

        batcher = MicroBatcher(
            GateEngine(), max_size=2, window_ms=1.0, max_inflight=2,
            shed_queue_budget_ms=100.0,
        )
        assert batcher.utilization() == 0.0
        fut = batcher.submit(["x"])
        deadline = time.perf_counter() + 2.0
        while batcher.utilization() == 0.0 and time.perf_counter() < deadline:
            time.sleep(0.005)
        busy = batcher.utilization()
        assert busy > 0.0
        release.set()
        fut.result(timeout=5.0)


class TestAttributionMetrics:
    def test_metrics_render_attribution_summaries(self):
        m = ServingMetrics()
        m.record_attribution(
            queue_wait_s=0.002, device_s=0.004, e2e_s=0.006
        )
        m.record_shed()
        text = m.render(reload_counter=1, finished_loading=True)
        assert 'kmls_queue_wait_ms{quantile="0.99"} 2.0000' in text
        assert 'kmls_device_ms{quantile="0.5"} 4.0000' in text
        assert 'kmls_e2e_ms{quantile="0.999"} 6.0000' in text
        assert "kmls_requests_shed_total 1" in text

    def test_reset_clears_attribution_too(self):
        m = ServingMetrics()
        m.record("rules", 0.001)
        m.record_attribution(0.001, 0.002, 0.003)
        assert m.reset_latency() == 1
        text = m.render(reload_counter=0, finished_loading=True)
        assert 'kmls_queue_wait_ms{quantile="0.99"} 0.0000' in text
        assert "kmls_requests_total 1" in text  # counters stay cumulative

    def test_batcher_threads_timestamps_through(self, mined_pvc):
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        metrics = ServingMetrics()
        batcher = MicroBatcher(
            engine, max_size=8, window_ms=5.0, metrics=metrics
        )
        seeds = _rule_seeds(cfg)
        for s in seeds[:6]:
            batcher.recommend([s])
        (e2e50,) = metrics.e2e.percentiles(0.5)
        (dv50,) = metrics.device.percentiles(0.5)
        (qw50,) = metrics.queue_wait.percentiles(0.5)
        assert e2e50 > 0 and dv50 > 0
        assert qw50 >= 0
        # e2e ⊇ device ⊇ (most of) the pipeline: sanity ordering
        assert e2e50 >= dv50


class TestLoopbackNormalization:
    def test_ipv6_mapped_loopback_accepted(self, tmp_path):
        app = RecommendApp(ServingConfig(base_dir=str(tmp_path)))
        assert app.handle(
            "POST", "/metrics/reset", b"", client_host="::ffff:127.0.0.1"
        )[0] == 200
        assert app.handle(
            "POST", "/metrics/reset", b"", client_host="::1"
        )[0] == 200

    def test_mapped_non_loopback_still_rejected(self, tmp_path):
        app = RecommendApp(ServingConfig(base_dir=str(tmp_path)))
        assert app.handle(
            "POST", "/metrics/reset", b"", client_host="::ffff:10.2.3.4"
        )[0] == 403
        # 'localhost' never appears as a client_address value — dropped
        # from the allowlist
        assert app.handle(
            "POST", "/metrics/reset", b"", client_host="localhost"
        )[0] == 403


class TestAsyncMicroBatcher:
    class _InstantEngine:
        def __init__(self):
            self.batch_sizes = []

        def recommend_many_async(self, seed_sets):
            self.batch_sizes.append(len(seed_sets))

            def finish():
                return [(list(s), "rules") for s in seed_sets]

            return finish

    def test_results_and_batching(self):
        import asyncio
        from kmlserver_tpu.serving.batcher import AsyncMicroBatcher

        async def scenario():
            engine = self._InstantEngine()
            metrics = ServingMetrics()
            batcher = AsyncMicroBatcher(
                engine, max_size=8, window_ms=20.0, metrics=metrics
            )
            futures = [batcher.submit([f"s{i}"]) for i in range(8)]
            # the leader dispatches immediately (the pipeline is idle);
            # the rest coalesce into the scheduled window flush
            results = [await f for f in futures]
            assert [g for g, _ in results] == [[f"s{i}"] for i in range(8)]
            assert metrics.e2e.percentiles(0.5)[0] >= 0
            return engine

        engine = asyncio.run(scenario())
        assert sum(engine.batch_sizes) == 8
        assert len(engine.batch_sizes) <= 3, engine.batch_sizes
        assert max(engine.batch_sizes) >= 6  # aggregation actually happened

    def test_sparse_traffic_dispatches_immediately(self):
        import asyncio
        from kmlserver_tpu.serving.batcher import AsyncMicroBatcher

        async def scenario():
            engine = self._InstantEngine()
            batcher = AsyncMicroBatcher(engine, max_size=8, window_ms=400.0)
            t0 = time.perf_counter()
            got, _ = await batcher.submit(["lone"])
            dt = time.perf_counter() - t0
            assert got == ["lone"]
            assert dt < 0.2, f"lone request waited {dt:.3f}s"

        asyncio.run(scenario())

    def test_shedding_raises_overloaded(self):
        import asyncio
        from kmlserver_tpu.serving.batcher import AsyncMicroBatcher

        class SlowEngine:
            def recommend_many_async(self, seed_sets):
                def finish():
                    time.sleep(0.05)
                    return [(list(s), "rules") for s in seed_sets]

                return finish

        async def scenario():
            metrics = ServingMetrics()
            batcher = AsyncMicroBatcher(
                SlowEngine(), max_size=2, window_ms=1.0, max_inflight=1,
                shed_queue_budget_ms=60.0, metrics=metrics,
            )
            await batcher.submit(["warm"])  # teach the device-time EWMA
            futures = []
            sheds = degrades = 0
            for i in range(40):
                try:
                    futures.append(batcher.submit([f"s{i}"]))
                except Overloaded as exc:
                    # Retry-After carries bounded jitter: base 1s ± 50%
                    assert 0.5 <= exc.retry_after_s <= 1.5
                    sheds += 1
                except OverloadDegraded:
                    degrades += 1
            for f in futures:
                await f
            assert sheds > 0
            assert batcher.shed_total == sheds == metrics.shed_total
            assert batcher.degrade_total == degrades

        asyncio.run(scenario())

    def test_executor_path_matches_engine(self, mined_pvc):
        """Device-path (executor) flow end to end against the real
        engine, results exact vs the sync oracle."""
        import asyncio
        from kmlserver_tpu.serving.batcher import AsyncMicroBatcher

        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        seeds = _rule_seeds(cfg)[:4]
        expected = {s: engine.recommend([s]) for s in seeds}

        async def scenario():
            batcher = AsyncMicroBatcher(engine, max_size=4, window_ms=5.0)
            futures = [batcher.submit([s]) for s in seeds]
            return [await f for f in futures]

        for (got, source), s in zip(asyncio.run(scenario()), seeds):
            assert set(got) == set(expected[s][0])
            assert source == expected[s][1]


class TestAsyncTransport:
    @pytest.fixture
    def served(self, mined_pvc):
        """The real aioserver on an ephemeral port, loop in a daemon
        thread (signal handlers are skipped off the main thread)."""
        import asyncio
        from kmlserver_tpu.serving.aioserver import run_async

        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg, defer_batcher=True)
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

    def test_recommend_roundtrip_and_routes(self, served):
        import http.client

        app, port = served
        seeds = _rule_seeds(app.cfg)[:2]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request(
            "POST", "/api/recommend/",
            body=json.dumps({"songs": seeds}).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        data = json.loads(resp.read())
        assert resp.status == 200
        assert set(data) == {"songs", "model_date", "version"}
        single, _ = app.engine.recommend(seeds)
        assert set(data["songs"]) == set(single)
        for path, want in (
            ("/healthz", 200), ("/readyz", 200), ("/metrics", 200),
            ("/nope", 404),
        ):
            conn.request("GET", path)
            r = conn.getresponse()
            r.read()
            assert r.status == want, path
        conn.request(
            "POST", "/api/recommend/",
            body=json.dumps({"songs": []}).encode(),
        )
        r = conn.getresponse()
        r.read()
        assert r.status == 400

    def test_batcherless_mode_stays_responsive(self, mined_pvc):
        """KMLS_BATCH_WINDOW_MS=0 under the async transport: the blocking
        engine call must run off-loop — health probes stay live while a
        recommendation is in flight."""
        import asyncio
        import http.client
        from kmlserver_tpu.serving.aioserver import run_async

        cfg, _, _ = mined_pvc
        app = RecommendApp(
            dataclasses.replace(cfg, batch_window_ms=0.0), defer_batcher=True
        )
        app.engine.load()
        assert app.batcher is None
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
        port = port_box[0]
        seeds = _rule_seeds(app.cfg)[:2]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request(
            "POST", "/api/recommend/",
            body=json.dumps({"songs": seeds}).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        data = json.loads(resp.read())
        assert resp.status == 200 and data["songs"]
        conn.request("GET", "/healthz")
        r = conn.getresponse()
        r.read()
        assert r.status == 200

    def test_pipelined_requests_answered_in_order(self, served):
        import socket

        app, port = served
        seeds = _rule_seeds(app.cfg)
        bodies = [json.dumps({"songs": [s]}).encode() for s in seeds[:3]]
        raw = b"".join(
            b"POST /api/recommend/ HTTP/1.1\r\nHost: t\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(b)).encode() + b"\r\n\r\n" + b
            for b in bodies
        )
        with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
            s.sendall(raw)
            buf = b""
            responses = []
            while len(responses) < 3:
                chunk = s.recv(65536)
                assert chunk, "connection closed early"
                buf += chunk
                while True:
                    end = buf.find(b"\r\n\r\n")
                    if end < 0:
                        break
                    head = buf[:end]
                    clen = int(
                        [ln for ln in head.lower().split(b"\r\n")
                         if ln.startswith(b"content-length")][0].split(b":")[1]
                    )
                    if len(buf) < end + 4 + clen:
                        break
                    responses.append(
                        (int(head.split(b" ", 2)[1]),
                         buf[end + 4: end + 4 + clen])
                    )
                    buf = buf[end + 4 + clen:]
        for (status, body), seed in zip(responses, seeds[:3]):
            assert status == 200
            got = json.loads(body)["songs"]
            single, _ = app.engine.recommend([seed])
            assert set(got) == set(single)
