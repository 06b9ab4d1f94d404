"""Chaos suite: every recovery path fired deterministically through the
fault-injection harness (kmlserver_tpu/faults.py).

The acceptance bar (ISSUE 3): with fault injection active — corrupt
artifact at reload, a replica killed under load, a kernel delayed past
the deadline — the server returns ZERO 5xx: requests are served from the
last-good bundle, re-dispatched to healthy replicas, or degraded with
``X-KMLS-Degraded``; every recovery event lands in /metrics.

All tests here carry the ``chaos`` marker (a dedicated CI job runs
``-m chaos``); they are fast enough to ride tier-1 too."""

import dataclasses
import json
import threading
import time

import pytest

from kmlserver_tpu import faults
from kmlserver_tpu.config import MiningConfig
from kmlserver_tpu.io import artifacts, registry
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.batcher import (
    DeadlineExceeded,
    MicroBatcher,
    NoHealthyReplicas,
)
from kmlserver_tpu.serving.engine import RecommendEngine
from kmlserver_tpu.serving.metrics import ServingMetrics

from .test_serving import mined_pvc  # noqa: F401  (fixture re-export)

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _invalidate(cfg) -> None:
    registry.append_history_and_invalidate(
        MiningConfig(base_dir=cfg.base_dir), 1, "chaos-ds"
    )


def _post(app, songs):
    return app.handle(
        "POST", "/api/recommend/", json.dumps({"songs": songs}).encode()
    )


def _artifact_paths(cfg):
    pickles = f"{cfg.base_dir}/pickles"
    rec = f"{pickles}/{cfg.recommendations_file}"
    return {
        "pickles": pickles,
        "best": f"{pickles}/{cfg.best_tracks_file}",
        "rec": rec,
        "npz": artifacts.tensor_artifact_path(rec),
    }


class TestReloadFaults:
    def test_failed_reload_does_not_swallow_token(self, mined_pvc):
        """THE regression test for the reference's documented bug: a
        failed reload must not consume the invalidation token as a read
        side effect — the very next poll must see the data as still
        stale and retry (and succeed once the fault clears)."""
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        token_before = engine.cache_value
        _invalidate(cfg)
        faults.inject("engine.load", times=1)
        engine.reload_if_required()  # this reload fails (injected)
        assert engine.cache_value == token_before  # token NOT consumed
        assert engine.finished_loading  # last-good still serving
        assert engine.reload_failures == 1
        assert engine.is_data_stale()  # the staleness signal survived
        engine._backoff_until = 0.0  # collapse the backoff for the test
        engine.reload_if_required()  # next poll retries...
        assert engine.cache_value != token_before  # ...and succeeds
        assert engine.consecutive_reload_failures == 0

    def test_env_knob_arms_reload_fault(self, mined_pvc, monkeypatch):
        cfg, _, _ = mined_pvc
        monkeypatch.setenv("KMLS_FAULT_RELOAD_FAIL", "1")
        faults.load_env(force=True)
        engine = RecommendEngine(cfg)
        assert engine.load() is False  # injected failure
        assert engine.load()  # fault spent; next attempt succeeds

    def test_failed_reload_backs_off_exponentially(self, mined_pvc):
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(
            dataclasses.replace(cfg, reload_backoff_base_s=30.0)
        )
        assert engine.load()
        _invalidate(cfg)
        faults.inject("engine.load", times=5)
        engine.reload_if_required()
        assert engine.consecutive_reload_failures == 1
        assert engine._backoff_until > time.monotonic()
        # backoff gates the POLL path: the next nudge is a no-op, the
        # armed fault is not consumed
        engine.reload_if_required()
        assert engine.consecutive_reload_failures == 1


class TestTornArtifacts:
    """Satellite: truncated pickle, truncated npz, checksum-mismatched
    manifest, mid-os.replace torn read — each leaves the engine serving
    the prior bundle with zero 5xx responses."""

    def _assert_survives(self, app, cfg, corrupt):
        assert app.engine.load()
        good_bundle = app.engine.bundle
        seeds = app.engine.bundle.vocab[:2]
        corrupt()
        _invalidate(cfg)
        assert app.engine.is_data_stale()
        assert app.engine.load() is False  # fail-soft
        assert app.engine.bundle is good_bundle  # last-good serving
        for _ in range(5):
            status, _, _ = _post(app, seeds)
            assert status == 200
        # readyz: ready-but-flagged, never 503 (a bad artifact on the
        # shared PVC must not readiness-fail the whole fleet)
        status, _, payload = app.handle("GET", "/readyz", None)
        assert status == 200
        assert json.loads(payload)["status"] == "degraded"

    def test_truncated_pickle_keeps_last_good(self, mined_pvc):
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        paths = _artifact_paths(cfg)

        def corrupt():
            faults.truncate_file(paths["rec"], keep_fraction=0.4)
            faults.truncate_file(paths["npz"], keep_fraction=0.4)

        self._assert_survives(app, cfg, corrupt)

    def test_truncated_npz_falls_back_to_pickle_via_manifest(self, mined_pvc):
        """A torn npz beside an intact pickle: the manifest flags the npz
        BEFORE np.load ever touches it, and the reload still lands off
        the pickle."""
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(cfg)
        assert engine.load()
        paths = _artifact_paths(cfg)
        faults.truncate_file(paths["npz"], keep_fraction=0.3)
        _invalidate(cfg)
        assert engine.load()  # pickle path carries the reload
        assert engine.consecutive_reload_failures == 0

    def test_checksum_mismatch_detected_by_manifest(self, mined_pvc):
        """Same-size bit-rot: only the manifest's sha256 can catch a
        flipped byte (pickle.load may happily parse garbage values)."""
        cfg, _, _ = mined_pvc
        paths = _artifact_paths(cfg)
        assert artifacts.verify_files(
            paths["pickles"], [cfg.recommendations_file]
        ) == []
        faults.flip_byte(paths["rec"])
        bad = artifacts.verify_files(paths["pickles"], [cfg.recommendations_file])
        assert bad == [paths["rec"]]
        app = RecommendApp(cfg)
        # no intact prior bundle exists, but the engine must still
        # fail-soft (503 readiness, no crash), not publish corrupt bytes
        assert app.engine.load() is False
        assert app.handle("GET", "/readyz", None)[0] == 503

    def test_mid_replace_torn_read_simulation(self, mined_pvc):
        """A reader catching the artifact mid-(non-atomic)-rewrite: half
        the NEW bytes over the old file, manifest still describing the
        old generation — the engine must hold the last-good bundle."""
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        paths = _artifact_paths(cfg)

        def corrupt():
            with open(paths["rec"], "rb") as fh:
                new_bytes = fh.read()
            torn = new_bytes[: len(new_bytes) // 2]
            with open(paths["rec"], "wb") as fh:
                fh.write(torn)
            faults.truncate_file(paths["npz"], keep_fraction=0.5)

        self._assert_survives(app, cfg, corrupt)

    def test_manifestless_writer_retires_stale_manifest(self, mined_pvc):
        """Either-side-PVC interop: a manifest-less writer (the reference's
        job, or KMLS_WRITE_MANIFEST=0) rewrites the artifacts + token over
        a PVC that still carries THIS miner's old manifest. The stale
        manifest is generation-gated by its token stamp — it must step
        aside, not condemn (and eventually quarantine) the fresh bytes."""
        cfg, _, mining_cfg = mined_pvc
        engine = RecommendEngine(
            dataclasses.replace(cfg, quarantine_after_failures=1)
        )
        assert engine.load()
        from kmlserver_tpu.mining.pipeline import run_mining_job

        # different support → different rule bytes under the old manifest
        run_mining_job(dataclasses.replace(
            mining_cfg, write_manifest=False, min_support=0.15
        ))
        assert artifacts.load_manifest(f"{cfg.base_dir}/pickles") is not None
        assert engine.is_data_stale()
        assert engine.load()  # fresh generation loads, no integrity abort
        assert engine.consecutive_reload_failures == 0
        assert engine.artifact_quarantines == 0

    def test_quarantine_after_repeated_failures_then_recovery(
        self, mined_pvc, tmp_path
    ):
        cfg, _, mining_cfg = mined_pvc
        engine = RecommendEngine(
            dataclasses.replace(
                cfg, quarantine_after_failures=2, reload_backoff_base_s=0.0
            )
        )
        assert engine.load()
        paths = _artifact_paths(cfg)
        faults.truncate_file(paths["rec"], keep_fraction=0.3)
        faults.truncate_file(paths["npz"], keep_fraction=0.3)
        _invalidate(cfg)
        assert engine.load() is False  # strike 1: no quarantine yet
        assert engine.artifact_quarantines == 0
        assert engine.load() is False  # strike 2: quarantined
        assert engine.artifact_quarantines >= 1
        import os

        qdir = os.path.join(paths["pickles"], artifacts.QUARANTINE_DIRNAME)
        assert os.path.isdir(qdir) and os.listdir(qdir)
        assert not os.path.exists(paths["rec"])  # bad bytes moved aside
        # the next mining run writes fresh artifacts + manifest and the
        # engine recovers on its own
        run_index_bump = registry.get_next_run_index(
            mining_cfg, registry.get_dataset_list(mining_cfg, persist=False)
        )
        assert run_index_bump >= 1
        from kmlserver_tpu.mining.pipeline import run_mining_job

        run_mining_job(mining_cfg)
        engine._backoff_until = 0.0
        engine.reload_if_required()
        assert engine.consecutive_reload_failures == 0
        assert engine.recommend(engine.bundle.vocab[:1])[1] in (
            "rules", "empty", "fallback",
        )


class _FlakyReplicaEngine:
    """Two-replica fake: replica `bad` fails at finish() until healed."""

    n_replicas = 2

    def __init__(self, bad: int = 1):
        self.bad = bad
        self.healed = False
        self.calls_by_replica = {0: 0, 1: 0}

    def recommend_many_async(self, seed_sets, replica=None):
        idx = replica or 0
        self.calls_by_replica[idx] = self.calls_by_replica.get(idx, 0) + 1

        def finish():
            if idx == self.bad and not self.healed:
                raise RuntimeError(f"replica {idx} kernel died")
            return [(list(s), "rules") for s in seed_sets]

        return finish


class TestReplicaEjection:
    def test_sick_replica_ejected_requests_redispatched(self):
        engine = _FlakyReplicaEngine(bad=1)
        metrics = ServingMetrics()
        batcher = MicroBatcher(
            engine, max_size=2, window_ms=1.0, eject_threshold=2,
            probe_interval_s=30.0, redispatch_max=2, metrics=metrics,
        )
        # sequential requests alternate replicas (ties rotate); every
        # request that lands on the sick replica re-dispatches to the
        # healthy one and still succeeds
        for i in range(12):
            recs, source = batcher.recommend([f"s{i}"], timeout=10.0)
            assert recs == [f"s{i}"] and source == "rules"
        assert batcher.ejected_replicas() == [1]
        assert batcher.eject_total == 1
        assert batcher.redispatch_total >= 2
        assert metrics.replica_ejections_total == 1
        assert metrics.redispatch_total == batcher.redispatch_total
        # post-ejection traffic goes only to the healthy replica
        calls_before = dict(engine.calls_by_replica)
        for i in range(4):
            batcher.recommend([f"t{i}"], timeout=10.0)
        assert engine.calls_by_replica[1] == calls_before[1]

    def test_probe_readmits_healed_replica(self):
        engine = _FlakyReplicaEngine(bad=1)
        metrics = ServingMetrics()
        batcher = MicroBatcher(
            engine, max_size=2, window_ms=1.0, eject_threshold=1,
            probe_interval_s=0.15, redispatch_max=2, metrics=metrics,
        )
        for i in range(6):
            batcher.recommend([f"s{i}"], timeout=10.0)
        assert batcher.ejected_replicas() == [1]
        # heal, wait out the probe interval: the next request may BE the
        # probe (half-open trial) and must succeed either way
        engine.healed = True
        time.sleep(0.2)
        for i in range(8):
            batcher.recommend([f"p{i}"], timeout=10.0)
            if not batcher.ejected_replicas():
                break
            time.sleep(0.1)
        assert batcher.ejected_replicas() == []
        assert batcher.readmit_total == 1
        assert metrics.replica_readmissions_total == 1

    def test_total_replica_loss_raises_no_healthy(self):
        class DeadEngine:
            n_replicas = 1

            def recommend_many_async(self, seed_sets, replica=None):
                def finish():
                    raise RuntimeError("dead")

                return finish

        batcher = MicroBatcher(
            DeadEngine(), max_size=2, window_ms=1.0, eject_threshold=2,
            probe_interval_s=60.0,
        )
        # the lone replica dies; first failures propagate the raw error
        for i in range(2):
            with pytest.raises(RuntimeError):
                batcher.recommend([f"s{i}"], timeout=10.0)
        # breaker open + no probe due → NoHealthyReplicas at admission
        with pytest.raises(NoHealthyReplicas):
            batcher.recommend(["x"], timeout=10.0)

    def test_async_batcher_ejects_and_readmits(self):
        import asyncio

        from kmlserver_tpu.serving.batcher import AsyncMicroBatcher

        async def scenario():
            engine = _FlakyReplicaEngine(bad=1)
            metrics = ServingMetrics()
            batcher = AsyncMicroBatcher(
                engine, max_size=2, window_ms=1.0, eject_threshold=2,
                probe_interval_s=0.15, redispatch_max=2, metrics=metrics,
            )
            for i in range(12):
                recs, source = await batcher.submit([f"s{i}"])
                assert recs == [f"s{i}"] and source == "rules"
            assert batcher.ejected_replicas() == [1]
            assert batcher.redispatch_total >= 2
            engine.healed = True
            await asyncio.sleep(0.2)
            for i in range(8):
                await batcher.submit([f"p{i}"])
                if not batcher.ejected_replicas():
                    break
                await asyncio.sleep(0.1)
            assert batcher.ejected_replicas() == []
            assert batcher.readmit_total == 1

        asyncio.run(scenario())


class TestShedCapacityProjection:
    """ISSUE 8 satellite regression: shed capacity must discount
    DEGRADED (mid-failure-run) and HALF-OPEN (probing) replicas, not
    just ejected ones — the old projection counted a replica at full
    capacity right up to the batch that tripped its breaker, and the
    idle fast path dispatched real traffic windowless onto a replica
    still being auditioned by a re-admission probe."""

    class _TwoReplicaEngine:
        n_replicas = 2

        def recommend_many_async(self, seed_sets, replica=None):
            def finish():
                return [(list(s), "rules") for s in seed_sets]

            return finish

    def _batcher(self):
        return MicroBatcher(
            self._TwoReplicaEngine(), max_size=4, window_ms=1.0,
            eject_threshold=3, probe_interval_s=30.0,
        )

    def test_mid_failure_run_replica_discounted(self):
        batcher = self._batcher()
        # two batches in flight, 100ms device EWMA: with both replicas
        # trusted the projected wait is one device-time per replica
        batcher._device_s_ewma = 0.1
        batcher._inflight_by_replica = {0: 1, 1: 1}
        assert batcher.projected_queue_wait_s() == pytest.approx(0.1)
        # replica 1 takes ONE failure — breaker not yet tripped (threshold
        # 3), but it is mid-incident: capacity must halve NOW, before the
        # ejection, doubling the projection
        batcher._consec_failures[1] = 1
        assert batcher._n_effective_locked(2) == 1
        assert batcher._n_healthy_locked(2) == 2  # loss semantics unchanged
        assert batcher.projected_queue_wait_s() == pytest.approx(0.2)

    def test_half_open_probe_replica_discounted(self):
        batcher = self._batcher()
        batcher._device_s_ewma = 0.1
        batcher._inflight_by_replica = {0: 1, 1: 1}
        # replica 1 ejected and now under a half-open probe: one trial
        # batch is out, but a replica being auditioned is NOT capacity
        batcher._ejected[1] = time.perf_counter()
        batcher._probing.add(1)
        assert batcher._n_effective_locked(2) == 1
        assert batcher.projected_queue_wait_s() == pytest.approx(0.2)

    def test_async_twin_mirrors_effective_capacity(self):
        from kmlserver_tpu.serving.batcher import AsyncMicroBatcher

        batcher = AsyncMicroBatcher(
            self._TwoReplicaEngine(), max_size=4, window_ms=1.0,
            eject_threshold=3, probe_interval_s=30.0,
        )
        assert batcher._n_effective(2) == 2
        batcher._consec_failures[1] = 2
        assert batcher._n_effective(2) == 1
        batcher._consec_failures[1] = 0
        batcher._ejected[1] = time.perf_counter()
        batcher._probing.add(1)
        assert batcher._n_effective(2) == 1


class TestEpochFlipStampede:
    """ISSUE 8 satellite: the hot-key flip at an epoch boundary — every
    hot cache key invalidates at once mid-burst (a bundle republication
    moves the epoch, so no old-epoch key can ever match again). The
    epoch-keyed cache + singleflight must collapse the resulting miss
    wave onto ONE batcher slot per epoch generation, not stampede the
    device with one dispatch per request."""

    class _CountingEngine:
        n_replicas = 1
        bundle_epoch = 1
        cache_value = "tok-1"

        def __init__(self):
            self.dispatched_requests = 0
            self.dispatch_calls = 0

        def recommend_many_async(self, seed_sets, replica=None):
            self.dispatch_calls += 1
            self.dispatched_requests += len(seed_sets)

            def finish():
                # slow enough that a whole request wave overlaps one
                # in-flight answer — the window a stampede would exploit
                time.sleep(0.08)
                return [(list(s), "rules") for s in seed_sets]

            return finish

    def test_hot_key_invalidation_does_not_stampede_batcher(self, tmp_path):
        from kmlserver_tpu.config import ServingConfig

        engine = self._CountingEngine()
        app = RecommendApp(
            ServingConfig(base_dir=str(tmp_path)), engine=engine
        )
        assert app.cache is not None and app.batcher is not None
        hot = ["hot-a", "hot-b"]
        results: list = []
        lock = threading.Lock()

        def ask():
            recs, source, cached = app.recommend_direct(list(hot))
            with lock:
                results.append((recs, source))

        # wave 1: 24 concurrent identical requests under epoch 1
        wave1 = [threading.Thread(target=ask) for _ in range(24)]
        for t in wave1:
            t.start()
        time.sleep(0.03)  # mid-flight of wave 1's single batch
        # THE FLIP: the bundle republishes — epoch moves, every hot key
        # is now unreachable (exactly what engine.load() does after a
        # successful swap)
        engine.bundle_epoch = 2
        engine.cache_value = "tok-2"
        wave2 = [threading.Thread(target=ask) for _ in range(24)]
        for t in wave2:
            t.start()
        for t in wave1 + wave2:
            t.join(timeout=10.0)
        assert len(results) == 48
        assert all(recs == hot for recs, _ in results)
        # the stampede bound: one singleflight leader per epoch
        # generation (plus at most a couple of stragglers that raced the
        # flip itself) — NOT one dispatch per request
        assert engine.dispatched_requests <= 6, (
            f"{engine.dispatched_requests} requests reached the batcher "
            "for 48 identical asks across one epoch flip"
        )
        assert app.cache.singleflight_joins >= 40
        # post-flip steady state: the new-epoch answer is cached
        _, _, cached = app.recommend_direct(list(hot))
        assert cached


class TestDeadlineDegradation:
    def test_kernel_delay_past_deadline_degrades_not_500(self, mined_pvc):
        """Acceptance: a kernel delayed past the request deadline yields
        200 + X-KMLS-Degraded (fallback answer), never a 5xx."""
        cfg, _, _ = mined_pvc
        app = RecommendApp(
            dataclasses.replace(cfg, request_deadline_ms=80.0)
        )
        assert app.engine.load()
        seeds = app.engine.bundle.vocab[:2]
        faults.inject(
            "replica.kernel", replica=0, delay_s=0.5, times=-1
        )
        t0 = time.perf_counter()
        status, headers, payload = _post(app, seeds)
        elapsed_ms = (time.perf_counter() - t0) * 1e3
        assert status == 200
        assert headers.get("X-KMLS-Degraded") == "deadline"
        assert json.loads(payload)["songs"]  # fallback answer, not empty
        # the degraded answer arrives near the budget, not after the full
        # injected stall (generous bound: noisy CI hosts)
        assert elapsed_ms < 450.0
        assert app.metrics.degraded_by_reason.get("deadline", 0) == 1
        faults.clear()
        # let the stalled batch drain (a new identical request would
        # singleflight-join it and rightly degrade again); once it lands,
        # the same request serves rules, un-degraded
        time.sleep(0.6)
        status, headers, _ = _post(app, seeds)
        assert status == 200 and "X-KMLS-Degraded" not in headers

    def test_replica_loss_degrades_with_header_and_readyz(self, mined_pvc):
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()

        class DeadBatcher:
            def submit(self, seeds, deadline=None):
                raise NoHealthyReplicas("all ejected")

            def recommend(self, seeds, timeout=30.0, deadline=None):
                raise NoHealthyReplicas("all ejected")

            def ejected_replicas(self):
                return [0]

        app.batcher = DeadBatcher()
        seeds = app.engine.bundle.vocab[:2]
        status, headers, payload = _post(app, seeds)
        assert status == 200
        assert headers.get("X-KMLS-Degraded") == "replica-loss"
        assert json.loads(payload)["songs"]
        status, _, payload = app.handle("GET", "/readyz", None)
        body = json.loads(payload)
        assert status == 200 and body["status"] == "degraded"
        assert any("ejected" in r for r in body["reasons"])

    def test_queue_expiry_uses_deadline_exceeded(self):
        class StallEngine:
            n_replicas = 1

            def recommend_many_async(self, seed_sets, replica=None):
                def finish():
                    time.sleep(0.3)
                    return [(list(s), "rules") for s in seed_sets]

                return finish

        batcher = MicroBatcher(
            StallEngine(), max_size=1, window_ms=1.0, max_inflight=1
        )
        deadline = time.perf_counter() + 0.05
        with pytest.raises(DeadlineExceeded):
            batcher.recommend(["x"], deadline=deadline)


class TestRecoveryMetrics:
    def test_all_recovery_counters_in_metrics(self, mined_pvc):
        cfg, _, _ = mined_pvc
        app = RecommendApp(cfg)
        assert app.engine.load()
        text = app.handle("GET", "/metrics", None)[2].decode()
        for series in (
            "kmls_degraded_total",
            "kmls_replica_ejections_total",
            "kmls_replica_readmissions_total",
            "kmls_redispatch_total",
            "kmls_artifact_quarantines_total",
            "kmls_reload_failures_total",
            "kmls_reload_consecutive_failures",
            "kmls_replicas_ejected",
        ):
            assert series in text, series

    def test_degraded_and_failure_counters_move(self, mined_pvc):
        cfg, _, _ = mined_pvc
        app = RecommendApp(
            dataclasses.replace(cfg, request_deadline_ms=50.0)
        )
        assert app.engine.load()
        faults.inject("replica.kernel", replica=0, delay_s=0.4, times=-1)
        _post(app, app.engine.bundle.vocab[:1])
        faults.clear()
        faults.inject("engine.load", times=1)
        _invalidate(cfg)
        app.engine.load()
        text = app.handle("GET", "/metrics", None)[2].decode()
        assert 'kmls_degraded_by_reason{reason="deadline"} 1' in text
        assert "kmls_reload_failures_total 1" in text


class TestZero5xxUnderCompoundChaos:
    def test_replica_kill_plus_corrupt_reload_zero_5xx(self, mined_pvc):
        """The headline acceptance: two replicas serving under load, one
        killed mid-run AND a corrupt artifact landing on the PVC — every
        request answers 200 (rules, re-dispatched, last-good, or
        degraded) and the recovery counters move."""
        cfg, _, _ = mined_pvc
        cfg = dataclasses.replace(
            cfg, serve_devices=2,
            request_deadline_ms=2000.0, replica_eject_threshold=2,
            replica_probe_interval_s=30.0,
        )
        app = RecommendApp(cfg)
        assert app.engine.load()
        assert app.engine.n_replicas == 2
        vocab = app.engine.bundle.vocab
        paths = _artifact_paths(cfg)
        statuses: list[int] = []
        for i in range(60):
            if i == 15:
                # kill replica 1 mid-run (permanent until cleared)
                faults.inject(
                    "replica.kernel", replica=1, times=-1
                )
            if i == 30:
                # corrupt the artifacts + signal staleness: the poll-path
                # reload must fail soft while serving continues
                faults.truncate_file(paths["rec"], keep_fraction=0.3)
                faults.truncate_file(paths["npz"], keep_fraction=0.3)
                _invalidate(cfg)
                assert app.engine.load() is False
            # cache off the table: distinct seeds every request, so every
            # request exercises the batcher/replica path
            status, headers, _ = _post(app, [vocab[i % len(vocab)], f"u{i}"])
            statuses.append(status)
        assert all(s == 200 for s in statuses), statuses
        assert app.batcher.ejected_replicas() == [1]
        text = app.handle("GET", "/metrics", None)[2].decode()
        assert "kmls_replica_ejections_total 1" in text
        assert "kmls_reload_failures_total 1" in text
        status, _, payload = app.handle("GET", "/readyz", None)
        assert status == 200
        assert json.loads(payload)["status"] == "degraded"
