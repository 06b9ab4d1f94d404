"""Multi-device data-parallel serving on the virtual 8-device CPU
platform (conftest pins it): replica construction, per-replica warmup
with zero serving-path compiles, answer identity across replicas, and
the least-loaded dispatcher actually spreading concurrent batches over
the device fleet."""

import dataclasses
import threading

import jax
import pytest

from kmlserver_tpu.serving.batcher import MicroBatcher
from kmlserver_tpu.serving.engine import RecommendEngine
from kmlserver_tpu.serving.metrics import ServingMetrics

from .test_batching import _rule_seeds
from .test_serving import mined_pvc  # noqa: F401  (fixture re-export)


def _multi_cfg(cfg, n_devices=8):
    """Device-kernel path across n replicas, with small shape buckets so
    the per-replica warmup stays cheap (3 batch x 2 length buckets)."""
    return dataclasses.replace(
        cfg, serve_devices=n_devices,
        batch_max_size=4, max_seed_tracks=8,
    )


class TestReplicaSet:
    def test_one_replica_per_device_all_warmed(self, mined_pvc):
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(_multi_cfg(cfg))
        assert engine.load()
        assert len(engine.replicas) == 8 == engine.n_replicas
        devices = {b.device for b in engine.replicas}
        assert len(devices) == 8  # distinct devices, not 8 aliases
        assert set(jax.local_devices()[:8]) == devices
        for bundle in engine.replicas:
            for batch in engine._batch_buckets():
                for length in engine._len_buckets():
                    assert (batch, length) in bundle.warmed_shapes
        # shared host state is shared, not copied
        assert all(
            b.index is engine.replicas[0].index for b in engine.replicas
        )

    def test_cpu_backend_defaults_to_one_replica(self, mined_pvc):
        # serve_devices=0 (auto) on a CPU backend: one replica, exactly
        # the pre-multi-device behavior (virtual devices share host cores)
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        assert engine.n_replicas == 1

    def test_replicas_answer_identically(self, mined_pvc):
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(_multi_cfg(cfg))
        assert engine.load()
        seeds = _rule_seeds(cfg)
        sets = [[seeds[0]], [seeds[1], seeds[2]], ["unknown-zz"]]
        oracle = engine.recommend_many_async(sets, replica=0)()
        for idx in range(1, engine.n_replicas):
            assert engine.recommend_many_async(sets, replica=idx)() == oracle

    def test_no_compile_on_any_replica_after_publish(self, mined_pvc):
        """Acceptance: the compile counter stays flat while every replica
        serves every warmed batch shape — publishing warmed ALL devices,
        not just the primary."""
        from kmlserver_tpu.ops import serve as serve_ops

        cfg, _, _ = mined_pvc
        engine = RecommendEngine(_multi_cfg(cfg))
        assert engine.load()
        seeds = _rule_seeds(cfg)
        counter = getattr(serve_ops.recommend_batch, "_cache_size", None)
        n0 = counter() if counter else None
        for idx in range(engine.n_replicas):
            for b in (1, 2, 3, 4):
                results = engine.recommend_many_async(
                    [[seeds[i % len(seeds)]] for i in range(b)], replica=idx
                )()
                assert len(results) == b
        assert engine.unwarmed_dispatches == 0
        if counter:
            assert counter() == n0, "a replica dispatch compiled a kernel"

    def test_epoch_increments_per_publication(self, mined_pvc):
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.bundle_epoch == 0
        assert engine.load()
        assert engine.bundle_epoch == 1
        assert all(b.epoch == 1 for b in engine.replicas)


class TestLeastLoadedDispatch:
    class _SlowEngine:
        """Fixed service time per batch + per-replica dispatch counts —
        slow enough that concurrent batches MUST fan out to hit the
        throughput the test drives."""

        def __init__(self, n_replicas=8, service_s=0.02):
            self.n_replicas = n_replicas
            self.service_s = service_s
            self.dispatch_counts = [0] * n_replicas
            self._lock = threading.Lock()

        def recommend_many_async(self, seed_sets, replica=None):
            import time as time_mod

            idx = 0 if replica is None else replica
            with self._lock:
                self.dispatch_counts[idx] += 1

            def finish():
                time_mod.sleep(self.service_s)
                return [(list(s), "rules") for s in seed_sets]

            return finish

    def test_concurrent_batches_spread_across_replicas(self):
        engine = self._SlowEngine()
        batcher = MicroBatcher(
            engine, max_size=1, window_ms=0.5, max_inflight=2,
        )
        threads = [
            threading.Thread(
                target=lambda i=i: batcher.recommend([f"s{i}"], timeout=30)
            )
            for i in range(48)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        active = sum(1 for c in engine.dispatch_counts if c > 0)
        assert active >= 4, engine.dispatch_counts
        assert sum(engine.dispatch_counts) == 48

    def test_real_engine_fleet_spreads_under_load(self, mined_pvc):
        """Acceptance: with 8 virtual CPU devices, per-device dispatch
        counts show at least 4 devices doing work under concurrent
        batched traffic through the real kernel."""
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(_multi_cfg(cfg))
        assert engine.load()
        metrics = ServingMetrics()
        batcher = MicroBatcher(
            engine, max_size=4, window_ms=1.0, max_inflight=2,
            metrics=metrics,
        )
        seeds = _rule_seeds(cfg)
        errors = []

        def client(i):
            try:
                for j in range(6):
                    batcher.recommend([seeds[(i + j) % len(seeds)]], timeout=30)
            except Exception as exc:  # pragma: no cover - diagnostic
                errors.append(exc)

        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(24)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        active = sum(1 for c in engine.dispatch_counts if c > 0)
        assert active >= 4, engine.dispatch_counts

    def test_async_batcher_spreads_too(self):
        import asyncio

        from kmlserver_tpu.serving.batcher import AsyncMicroBatcher

        engine = self._SlowEngine(service_s=0.01)

        async def scenario():
            batcher = AsyncMicroBatcher(
                engine, max_size=1, window_ms=0.5, max_inflight=2
            )
            futures = [batcher.submit([f"s{i}"]) for i in range(32)]
            await asyncio.gather(*futures)

        asyncio.run(scenario())
        active = sum(1 for c in engine.dispatch_counts if c > 0)
        assert active >= 4, engine.dispatch_counts

    def test_shed_projection_scales_with_replica_count(self):
        # same queue state, 8x the devices → 1/8th the projected wait
        single = MicroBatcher(
            self._SlowEngine(n_replicas=1), max_size=4, window_ms=1.0
        )
        fleet = MicroBatcher(
            self._SlowEngine(n_replicas=8), max_size=4, window_ms=1.0
        )
        for b in (single, fleet):
            b._device_s_ewma = 0.1
            with b._n_lock:
                b._inflight_by_replica[0] = 4
        w1 = single.projected_queue_wait_s()
        w8 = fleet.projected_queue_wait_s()
        assert w1 == pytest.approx(0.4)
        assert w8 == pytest.approx(w1 / 8)
