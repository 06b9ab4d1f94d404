"""Continuous freshness (ISSUE 10): incremental delta mining, in-place
serving application, selective cache invalidation, and the fleet ring.

The load-bearing contract is BIT-IDENTITY: base ∘ delta chain must equal
a full re-mine of the final dataset — tensors and answers — at the
replicated AND vocab-sharded layouts. Everything else (chaos, caching,
affinity) hangs off that guarantee.
"""

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from kmlserver_tpu import faults
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import TrackTable, write_tracks_csv
from kmlserver_tpu.freshness import delta as delta_mod
from kmlserver_tpu.freshness.ring import (
    RendezvousRing,
    fleet_multiplier,
    seeds_key,
    simulate_fleet,
)
from kmlserver_tpu.io import artifacts
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.cache import RecommendCache
from kmlserver_tpu.serving.engine import RecommendEngine

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fixtures: an append-only dataset with a delta-armed base generation
# ---------------------------------------------------------------------------


def _write_csv(path, pids, names):
    write_tracks_csv(
        str(path),
        TrackTable(
            pid=np.asarray(pids, dtype=np.int64),
            track_name=np.asarray(names, dtype=object),
        ),
    )


def _base_rows(rng, n_playlists=80, n_tracks=30, mean_len=5):
    names = [f"s{i:03d}" for i in range(n_tracks)]
    weights = 1.0 / (1.0 + np.arange(n_tracks) ** 1.2)
    weights /= weights.sum()
    pids, tracks = [], []
    for p in range(n_playlists):
        size = min(max(1, rng.poisson(mean_len)), n_tracks)
        for t in rng.choice(n_tracks, size=size, replace=False, p=weights):
            pids.append(p)
            tracks.append(names[int(t)])
    return pids, tracks


def _append_rows(csv_path, rows):
    """Append (pid, name) rows the way a feed would — raw CSV lines."""
    with open(csv_path, "a") as fh:
        for pid, name in rows:
            fh.write(f"{pid},{name}\n")


@pytest.fixture
def delta_pvc(tmp_path, rng):
    """A PVC with one delta-armed full publication; → (mining_cfg,
    serving_cfg, csv_path)."""
    ds_dir = tmp_path / "datasets"
    ds_dir.mkdir()
    csv_path = str(ds_dir / "2023_spotify_ds1.csv")
    pids, tracks = _base_rows(rng)
    _write_csv(csv_path, pids, tracks)
    # 0.04: min_count_for stays at 4 from 80 playlists up to 100, so
    # small appended-playlist deltas do NOT shift the global threshold —
    # the selective-invalidation tests rely on the touched set being
    # exactly the appended names, not a threshold-band recount.
    mining_cfg = MiningConfig(
        base_dir=str(tmp_path), datasets_dir=str(ds_dir), min_support=0.04,
        delta_enabled=True,
    )
    run_mining_job(mining_cfg)
    serving_cfg = ServingConfig(
        base_dir=str(tmp_path), pickle_dir="pickles/", delta_enabled=True,
        polling_wait_in_minutes=0.001,
    )
    return mining_cfg, serving_cfg, csv_path


def _fresh_full_remine(tmp_path, csv_path, mining_cfg, layout="replicated"):
    """Full re-mine of the CURRENT csv bytes in a pristine dir → engine."""
    import shutil

    base2 = tmp_path / f"full_{layout}"
    ds2 = base2 / "datasets"
    ds2.mkdir(parents=True)
    shutil.copy(csv_path, str(ds2 / os.path.basename(csv_path)))
    cfg2 = dataclasses.replace(
        mining_cfg, base_dir=str(base2), datasets_dir=str(ds2),
        delta_enabled=False, model_layout=layout,
    )
    run_mining_job(cfg2)
    engine = RecommendEngine(
        ServingConfig(
            base_dir=str(base2), pickle_dir="pickles/",
            model_layout=layout,
        )
    )
    assert engine.load()
    return engine


def _assert_bundles_identical(a, b):
    assert a.vocab == b.vocab
    assert np.array_equal(np.asarray(a.rule_ids), np.asarray(b.rule_ids))
    assert np.array_equal(np.asarray(a.rule_confs), np.asarray(b.rule_confs))
    assert np.array_equal(np.asarray(a.known_mask), np.asarray(b.known_mask))


# ---------------------------------------------------------------------------
# bit-identity: base ∘ delta chain == full re-mine
# ---------------------------------------------------------------------------


class TestDeltaBitIdentity:
    def test_delta_chain_equals_full_remine(self, tmp_path, rng, delta_pvc):
        """Two successive append→delta cycles, applied in place, must
        leave serving bit-identical to a pristine full re-mine — tensors
        AND answers (the acceptance pin)."""
        mining_cfg, serving_cfg, csv_path = delta_pvc
        engine = RecommendEngine(serving_cfg)
        assert engine.load()

        # cycle 1: extend existing playlists + add new ones + a new name
        _append_rows(csv_path, [(3, "s000"), (3, "zz_new"), (81, "s001"),
                                (81, "s002"), (81, "zz_new")])
        s1 = run_mining_job(mining_cfg)
        assert s1.delta_seq == 1
        assert engine.apply_pending_deltas() == 1
        assert engine.delta_seq == 1

        # cycle 2: another append on top of the rolled-forward base
        _append_rows(csv_path, [(82, "s000"), (82, "s001"), (82, "s003"),
                                (83, "s004"), (83, "zz_new")])
        s2 = run_mining_job(mining_cfg)
        assert s2.delta_seq == 2
        assert engine.apply_pending_deltas() == 1
        assert engine.delta_seq == 2
        assert engine.delta_applied_total == 2

        full = _fresh_full_remine(tmp_path, csv_path, mining_cfg)
        _assert_bundles_identical(engine.bundle, full.bundle)
        for seeds in (["s000"], ["s001", "s002"], ["zz_new"],
                      ["s003", "s004", "s005"], ["__unknown__"]):
            assert engine.recommend(seeds) == full.recommend(seeds)

    def test_delta_chain_sparse_recount_equals_full_remine(
        self, tmp_path, rng, delta_pvc
    ):
        """ISSUE 13: the delta recount routed through the SPARSE family
        (KMLS_COUNT_PATH=sparse → parallel/support.restricted_pair_counts
        takes the event-expansion twin) must keep base ∘ chain
        bit-identical to a full re-mine — tensors AND answers. The
        count-path knob is dispatch, not semantics, so the delta stays
        ELIGIBLE across the flip (same config fingerprint)."""
        mining_cfg, serving_cfg, csv_path = delta_pvc
        sparse_cfg = dataclasses.replace(mining_cfg, count_path="sparse")
        engine = RecommendEngine(serving_cfg)
        assert engine.load()

        _append_rows(csv_path, [(3, "s000"), (3, "zz_new"), (81, "s001"),
                                (81, "s002"), (81, "zz_new")])
        s1 = run_mining_job(sparse_cfg)
        assert s1.delta_seq == 1
        assert engine.apply_pending_deltas() == 1

        _append_rows(csv_path, [(82, "s000"), (82, "s001"), (82, "s003"),
                                (83, "s004"), (83, "zz_new")])
        s2 = run_mining_job(sparse_cfg)
        assert s2.delta_seq == 2
        assert engine.apply_pending_deltas() == 1

        # the full re-mine deliberately keeps the DEFAULT dispatch — the
        # identity must hold across families, not just within one
        full = _fresh_full_remine(tmp_path, csv_path, mining_cfg)
        _assert_bundles_identical(engine.bundle, full.bundle)
        for seeds in (["s000"], ["s001", "s002"], ["zz_new"],
                      ["s003", "s004"], ["__unknown__"]):
            assert engine.recommend(seeds) == full.recommend(seeds)

    def test_delta_with_pruning_and_tombstones(self, tmp_path, rng):
        """Apriori pruning active (vocab > threshold): a marginal track
        at exactly min_count drops out when appended playlists raise the
        threshold — the tombstone path — and the result still equals the
        full re-mine."""
        ds_dir = tmp_path / "datasets"
        ds_dir.mkdir()
        csv_path = str(ds_dir / "2023_spotify_ds1.csv")
        pids, tracks = _base_rows(rng, n_playlists=60, n_tracks=24)
        # "marginal" appears in exactly 3 playlists: min_count at 60
        # playlists / 0.05 = 3, so it is frequent in the base ...
        for p in (0, 1, 2):
            pids.append(p)
            tracks.append("marginal")
        _write_csv(csv_path, pids, tracks)
        mining_cfg = MiningConfig(
            base_dir=str(tmp_path), datasets_dir=str(ds_dir),
            min_support=0.05, delta_enabled=True, prune_vocab_threshold=8,
        )
        run_mining_job(mining_cfg)
        engine = RecommendEngine(
            ServingConfig(
                base_dir=str(tmp_path), pickle_dir="pickles/",
                delta_enabled=True,
            )
        )
        assert engine.load()
        assert "marginal" in engine.bundle.vocab

        # ... and 21 appended playlists push min_count to 5: "marginal"
        # leaves the pruned vocabulary (tombstone)
        _append_rows(
            csv_path,
            [(100 + i, f"s{i % 6:03d}") for i in range(21)]
            + [(100 + i, "s006") for i in range(21)],
        )
        s = run_mining_job(mining_cfg)
        assert s.delta_seq == 1
        state = artifacts.read_delta_state(mining_cfg.pickles_dir)
        assert state["entries"][0]["n_tombstones"] >= 1
        assert engine.apply_pending_deltas() == 1
        assert "marginal" not in engine.bundle.vocab

        full = _fresh_full_remine(tmp_path, csv_path, mining_cfg)
        _assert_bundles_identical(engine.bundle, full.bundle)
        assert engine.recommend(["marginal"]) == full.recommend(["marginal"])

    @pytest.mark.slow
    def test_delta_bit_identity_sharded_layout(self, tmp_path, rng):
        """The vocab-sharded layout: mining recounts through the mesh
        path and serving applies the delta into a SHARDED bundle —
        answers still bit-identical to the replicated full re-mine."""
        ds_dir = tmp_path / "datasets"
        ds_dir.mkdir()
        csv_path = str(ds_dir / "2023_spotify_ds1.csv")
        pids, tracks = _base_rows(rng, n_playlists=70, n_tracks=26)
        _write_csv(csv_path, pids, tracks)
        mining_cfg = MiningConfig(
            base_dir=str(tmp_path), datasets_dir=str(ds_dir),
            min_support=0.05, delta_enabled=True, model_layout="sharded",
        )
        run_mining_job(mining_cfg)
        engine = RecommendEngine(
            ServingConfig(
                base_dir=str(tmp_path), pickle_dir="pickles/",
                delta_enabled=True, model_layout="sharded",
                serve_devices=4,
            )
        )
        assert engine.load()
        assert engine.n_shards > 1

        _append_rows(csv_path, [(71, "s000"), (71, "s001"), (71, "zz_new"),
                                (72, "s002"), (72, "s003")])
        s = run_mining_job(mining_cfg)
        assert s.delta_seq == 1
        assert engine.apply_pending_deltas() == 1
        assert engine.n_shards > 1  # the patched bundle stays sharded

        full = _fresh_full_remine(tmp_path, csv_path, mining_cfg)
        for seeds in (["s000"], ["s001", "s002", "s003"], ["zz_new"]):
            assert engine.recommend(seeds) == full.recommend(seeds)

    def test_restricted_emission_matches_full_emission(self, rng):
        """emit_rule_rows_np on selected rows == the full emission's same
        rows (threshold, diagonal, top-k tie order). The third outputs
        differ by design: the full path returns row_valid_counts (rule
        overflow bookkeeping); the restricted path returns the diagonal
        item supports the confidence filter needs."""
        from kmlserver_tpu.ops.rules import emit_rule_tensors_np

        v = 17
        counts = rng.integers(0, 12, size=(v, v))
        counts = (counts + counts.T).astype(np.int64)
        np.fill_diagonal(counts, rng.integers(1, 15, size=v))
        full_ids, full_counts, _ = emit_rule_tensors_np(
            counts, min_count=4, k_max=6
        )
        rows = np.asarray([0, 3, 9, 16], dtype=np.int64)
        r_ids, r_counts, r_items = delta_mod.emit_rule_rows_np(
            counts[rows], rows, min_count=4, k_max=6
        )
        assert np.array_equal(r_ids, full_ids[rows])
        assert np.array_equal(r_counts, full_counts[rows])
        assert np.array_equal(r_items, np.diagonal(counts)[rows])


# ---------------------------------------------------------------------------
# eligibility + chain discipline: the delta path must never approximate
# ---------------------------------------------------------------------------


class TestDeltaEligibility:
    def test_unchanged_dataset_is_a_noop(self, delta_pvc):
        mining_cfg, _, _ = delta_pvc
        s = run_mining_job(mining_cfg)
        assert s.delta_seq is None
        assert s.artifact_paths == {}
        assert artifacts.read_delta_state(mining_cfg.pickles_dir) is None

    def test_rewritten_prefix_falls_back_to_full_mine(self, delta_pvc):
        """A rewritten byte in the base prefix breaks append-only: the
        run must full-re-mine (token rewrite), never publish a delta."""
        mining_cfg, serving_cfg, csv_path = delta_pvc
        with open(csv_path, "r+b") as fh:
            data = fh.read()
            # overwrite a track-name byte (keeps the CSV parseable — the
            # fallback full mine must succeed on the rewritten file)
            fh.seek(data.index(b",s0") + 1)
            fh.write(b"X")
        s = run_mining_job(mining_cfg)
        assert s.delta_seq is None
        assert "recommendations" in s.artifact_paths  # full publication
        assert artifacts.read_delta_state(mining_cfg.pickles_dir) is None

    def test_config_drift_falls_back_to_full_mine(self, delta_pvc):
        mining_cfg, _, csv_path = delta_pvc
        _append_rows(csv_path, [(90, "s000"), (90, "s001")])
        drifted = dataclasses.replace(mining_cfg, min_support=0.1)
        s = run_mining_job(drifted)
        assert s.delta_seq is None
        assert "recommendations" in s.artifact_paths

    def test_chain_cap_forces_full_remine(self, delta_pvc):
        mining_cfg, _, csv_path = delta_pvc
        capped = dataclasses.replace(mining_cfg, delta_max_chain=1)
        _append_rows(csv_path, [(91, "s000"), (91, "s001")])
        assert run_mining_job(capped).delta_seq == 1
        _append_rows(csv_path, [(92, "s002"), (92, "s003")])
        s = run_mining_job(capped)
        assert s.delta_seq is None  # cap hit → full re-mine
        assert "recommendations" in s.artifact_paths
        # the full publication retires the old chain
        assert artifacts.read_delta_state(mining_cfg.pickles_dir) is None

    def test_full_publication_retires_chain_and_rearms(self, delta_pvc):
        """After a delta, a full re-mine (e.g. nightly) supersedes the
        chain; the NEXT append goes through a fresh delta at seq 1."""
        mining_cfg, _, csv_path = delta_pvc
        _append_rows(csv_path, [(93, "s000"), (93, "s004")])
        assert run_mining_job(mining_cfg).delta_seq == 1
        run_mining_job(dataclasses.replace(mining_cfg, delta_enabled=False))
        assert artifacts.read_delta_state(mining_cfg.pickles_dir) is None
        # base state is stale (token moved): next delta-enabled run
        # full-mines and re-arms ...
        _append_rows(csv_path, [(94, "s001"), (94, "s005")])
        s = run_mining_job(mining_cfg)
        assert s.delta_seq is None
        # ... and the one after that is incremental again
        _append_rows(csv_path, [(95, "s002"), (95, "s006")])
        assert run_mining_job(mining_cfg).delta_seq == 1

    def test_delta_job_respects_live_lease(self, delta_pvc):
        """A live writer's lease blocks the delta publication exactly
        like a full one (zombie fencing rides the same protocol)."""
        mining_cfg, _, csv_path = delta_pvc
        _append_rows(csv_path, [(96, "s000"), (96, "s001")])
        lease = artifacts.PublicationLease.acquire(
            mining_cfg.pickles_dir, ttl_s=30.0
        )
        try:
            with pytest.raises(artifacts.LeaseHeldError):
                delta_mod.run_delta_job(mining_cfg)
        finally:
            lease.release()
        assert artifacts.read_delta_state(mining_cfg.pickles_dir) is None


# ---------------------------------------------------------------------------
# chaos: torn / wrong-base / injected-fault deltas — base keeps serving
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestDeltaChaos:
    def _applied_delta_setup(self, delta_pvc, corrupt):
        """Publish one delta, run ``corrupt`` before serving sees it,
        then drive the POLLING path; → (engine, answers_before)."""
        mining_cfg, serving_cfg, csv_path = delta_pvc
        engine = RecommendEngine(serving_cfg)
        assert engine.load()
        before = engine.recommend(["s000", "s001"])
        _append_rows(csv_path, [(97, "s000"), (97, "s001"), (97, "s002")])
        assert run_mining_job(mining_cfg).delta_seq == 1
        corrupt(mining_cfg)
        engine.reload_if_required()
        return engine, before

    def test_torn_delta_rejected_base_keeps_serving(self, delta_pvc):
        def corrupt(cfg):
            faults.flip_byte(
                os.path.join(
                    cfg.pickles_dir, artifacts.delta_bundle_filename(1)
                ),
                offset=100,
            )

        engine, before = self._applied_delta_setup(delta_pvc, corrupt)
        assert engine.delta_seq == 0
        assert engine.delta_rejected_total == 1
        assert engine.delta_applied_total == 0
        assert "sha256" in (engine.last_delta_error or "")
        # the base generation answers exactly as before — never a 5xx,
        # never a half-applied bundle
        assert engine.recommend(["s000", "s001"]) == before
        # the polling path backs off instead of busy-hashing the poison
        assert engine._delta_backoff_until > time.monotonic() - 1.0

    def test_wrong_base_delta_is_inert(self, delta_pvc):
        """A chain bound to another generation (zombie leftovers) must
        not patch this one."""

        def corrupt(cfg):
            state = artifacts.read_delta_state(cfg.pickles_dir)
            artifacts.write_delta_state(
                cfg.pickles_dir, "1999-01-01 00:00:00.000000",
                state["base_npz_sha256"], state["entries"],
            )

        engine, before = self._applied_delta_setup(delta_pvc, corrupt)
        assert engine.delta_seq == 0
        assert engine.delta_applied_total == 0
        assert engine.recommend(["s000", "s001"]) == before

    def test_chain_gap_rejected(self, delta_pvc):
        def corrupt(cfg):
            state = artifacts.read_delta_state(cfg.pickles_dir)
            entry = dict(state["entries"][0], seq=2)
            artifacts.write_delta_state(
                cfg.pickles_dir, state["base_token"],
                state["base_npz_sha256"], [entry],
            )

        engine, before = self._applied_delta_setup(delta_pvc, corrupt)
        assert engine.delta_seq == 0
        assert engine.delta_rejected_total == 1
        assert "chain gap" in engine.last_delta_error
        assert engine.recommend(["s000", "s001"]) == before

    def test_injected_delta_fault_then_recovery(self, delta_pvc, monkeypatch):
        """KMLS_FAULT_DELTA_CORRUPT=1 rejects exactly one apply (the
        chaos knob the CI job arms); the next direct apply goes through
        and lands the SAME bundle — rejection is never destructive."""
        monkeypatch.setenv("KMLS_FAULT_DELTA_CORRUPT", "1")
        faults.load_env(force=True)
        try:
            def corrupt(cfg):
                pass

            engine, before = self._applied_delta_setup(delta_pvc, corrupt)
            assert engine.delta_seq == 0
            assert engine.delta_rejected_total == 1
            assert engine.recommend(["s000", "s001"]) == before
            # fault exhausted: a direct apply (operator nudge / next poll
            # past the backoff) applies the identical bundle
            assert engine.apply_pending_deltas() == 1
            assert engine.delta_seq == 1
            assert engine.delta_applied_total == 1
        finally:
            monkeypatch.delenv("KMLS_FAULT_DELTA_CORRUPT")
            faults.load_env(force=True)

    def test_freshness_lag_tracks_applied_generation(self, delta_pvc):
        mining_cfg, serving_cfg, csv_path = delta_pvc
        engine = RecommendEngine(serving_cfg)
        assert engine.load()
        lag0 = engine.freshness_lag_s()
        assert lag0 >= 0.0
        _append_rows(csv_path, [(98, "s000"), (98, "s003")])
        assert run_mining_job(mining_cfg).delta_seq == 1
        assert engine.apply_pending_deltas() == 1
        # the applied delta is newer than the base publication
        assert engine.freshness_lag_s() <= lag0 + 5.0


# ---------------------------------------------------------------------------
# selective cache invalidation: poison test + hit-ratio preservation
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestSelectiveInvalidation:
    def test_make_key_generation_component(self):
        cache = RecommendCache()
        k0 = cache.make_key(7, ["a", "b"], 128)
        assert k0 == (7, 0, ("a", "b"))
        assert cache.invalidate_seeds({"b"}) == 0  # nothing stored yet
        assert cache.make_key(7, ["a", "b"], 128) == (7, 1, ("a", "b"))
        assert cache.make_key(7, ["a", "c"], 128) == (7, 0, ("a", "c"))

    def test_stale_entry_unreachable_and_deleted(self):
        cache = RecommendCache()
        hot = cache.make_key(1, ["x", "y"], 128)
        cold = cache.make_key(1, ["p", "q"], 128)
        cache.put(hot, (["r1"], "rules"))
        cache.put(cold, (["r2"], "rules"))
        dropped = cache.invalidate_seeds({"y"})
        assert dropped == 1
        assert cache.invalidated_keys == 1
        assert cache.selective_invalidations == 1
        # the touched key is unconstructable AND its entry is gone
        assert cache.get(hot) is None
        assert cache.make_key(1, ["x", "y"], 128) != hot
        # the untouched entry survives, still reachable via make_key
        assert cache.get(cache.make_key(1, ["p", "q"], 128)) == (
            ["r2"], "rules",
        )

    def test_inflight_pre_delta_leader_cannot_poison(self):
        """The singleflight race the generation component exists for: a
        leader computing under the PRE-delta key completes AFTER the
        invalidation — its stored answer must be unreachable to every
        post-delta lookup."""
        from concurrent.futures import Future

        cache = RecommendCache()
        old_key = cache.make_key(3, ["a", "b"], 128)
        fut = Future()
        got, joined = cache.join_or_lead(old_key, lambda: fut)
        assert not joined
        cache.invalidate_seeds({"a"})
        fut.set_result((["stale"], "rules"))
        cache.put(old_key, (["stale"], "rules"))  # the late store
        # post-delta lookups build a DIFFERENT key: the stale entry is
        # dead weight, never an answer
        assert cache.make_key(3, ["a", "b"], 128) != old_key
        assert cache.get(cache.make_key(3, ["a", "b"], 128)) is None

    def test_app_poison_and_hot_key_survival(self, tmp_path, rng, delta_pvc):
        """The satellite pin, end to end through the app: after a delta
        touching seed X, a request for X can never serve the pre-delta
        answer, while untouched hot keys keep their ENTRIES (hits resume
        without recompute — the hit ratio the wholesale epoch bump would
        have destroyed)."""
        mining_cfg, serving_cfg, csv_path = delta_pvc
        cfg = dataclasses.replace(
            serving_cfg, cache_enabled=True, cache_max_entries=256,
        )
        app = RecommendApp(cfg)
        assert app.engine.load()

        def ask(seeds):
            status, headers, payload = app.handle(
                "POST", "/api/recommend/",
                json.dumps({"songs": seeds}).encode(),
            )
            assert status == 200, status
            return json.loads(payload)["songs"], headers

        touched_seed = ["s000"]
        hot_seed = ["s010", "s011"]
        ask(touched_seed)
        ask(hot_seed)
        _, h = ask(hot_seed)
        assert h.get("X-KMLS-Cache") == "hit"
        entries_before = len(app.cache._lru)
        epoch_before = app.engine.bundle_epoch

        # delta built to touch s000's row: s000 gains co-occurrences
        _append_rows(
            csv_path,
            [(200 + i, "s000") for i in range(6)]
            + [(200 + i, "s001") for i in range(6)],
        )
        assert run_mining_job(mining_cfg).delta_seq == 1
        assert app.engine.apply_pending_deltas() == 1
        # no epoch bump: invalidation was selective, not wholesale
        assert app.engine.bundle_epoch == epoch_before
        assert app.cache.selective_invalidations == 1

        # poison check: the touched seed's answer equals a cache-bypassed
        # recompute from the patched tensors (never the pre-delta entry)
        fresh = app.engine.recommend(touched_seed)[0]
        got, headers = ask(touched_seed)
        assert headers.get("X-KMLS-Cache") != "hit"
        assert got == fresh

        # survival check: the untouched hot key kept its entry — the
        # next request is a HIT with zero recompute
        hits_before = app.cache.hits
        _, h = ask(hot_seed)
        assert h.get("X-KMLS-Cache") == "hit"
        assert app.cache.hits == hits_before + 1
        assert len(app.cache._lru) >= entries_before - len(
            delta_mod.touched_names(
                artifacts.load_delta_bundle(
                    os.path.join(
                        mining_cfg.pickles_dir,
                        artifacts.delta_bundle_filename(1),
                    )
                )
            )
        ) - 1

    def test_full_reload_still_invalidates_wholesale(self, delta_pvc):
        """A full republication keeps the epoch-bump contract: every
        pre-swap entry is unreachable (generation salting must not
        weaken the original mechanism)."""
        mining_cfg, serving_cfg, csv_path = delta_pvc
        cfg = dataclasses.replace(
            serving_cfg, cache_enabled=True, cache_max_entries=64,
        )
        app = RecommendApp(cfg)
        assert app.engine.load()
        epoch0 = app.engine.bundle_epoch
        key0 = app._cache_key(["s000"])
        run_mining_job(dataclasses.replace(mining_cfg, delta_enabled=False))
        assert app.engine.load()
        assert app.engine.bundle_epoch == epoch0 + 1
        assert app._cache_key(["s000"]) != key0


# ---------------------------------------------------------------------------
# fleet ring: rendezvous hashing + the simulated 3-replica topology
# ---------------------------------------------------------------------------


class TestRendezvousRing:
    def test_owner_is_deterministic_and_total(self):
        ring = RendezvousRing(["pod-0", "pod-1", "pod-2"])
        keys = [f"k{i}" for i in range(300)]
        owners = [ring.owner(k) for k in keys]
        assert owners == [ring.owner(k) for k in keys]
        assert set(owners) == {"pod-0", "pod-1", "pod-2"}

    def test_peer_removal_only_remaps_its_keys(self):
        """THE rendezvous property (why not a modulo ring): removing one
        peer re-maps only the keys it owned."""
        full = RendezvousRing(["pod-0", "pod-1", "pod-2"])
        reduced = RendezvousRing(["pod-0", "pod-2"])
        for i in range(500):
            key = f"key-{i}"
            before = full.owner(key)
            after = reduced.owner(key)
            if before != "pod-1":
                assert after == before
            else:
                assert after in ("pod-0", "pod-2")

    def test_seeds_key_matches_cache_canonicalization(self):
        assert seeds_key(["b", "a", "a"]) == seeds_key(["a", "b", "a"])
        assert seeds_key(["a"]) != seeds_key(["a", "a"])

    def test_affinity_beats_roundrobin_on_zipf_stream(self, rng):
        """The decision number: on a head-heavy stream over bounded
        caches, affinity routing's fleet hit ratio must beat
        round-robin's (each replica otherwise re-computes the head)."""
        pool = [f"key-{i}" for i in range(64)]
        ranks = np.arange(1, len(pool) + 1, dtype=np.float64)
        p = ranks ** -1.1
        p /= p.sum()
        keys = [pool[int(i)] for i in rng.choice(len(pool), 4000, p=p)]
        res = fleet_multiplier(keys, n_replicas=3, capacity=16)
        assert res["affinity_hit_ratio"] > res["baseline_hit_ratio"]
        assert res["multiplier"] > 1.0

    def test_simulate_fleet_policies(self):
        keys = ["a"] * 10
        # one hot key: affinity serves 9/10 from one replica's cache;
        # round-robin over 3 replicas still hits after each warms
        assert simulate_fleet(keys, 3, 8, "affinity") == pytest.approx(0.9)
        with pytest.raises(ValueError):
            simulate_fleet(keys, 3, 8, "bogus")

    def test_app_affinity_counters(self, delta_pvc):
        """KMLS_CACHE_AFFINITY=1: the app counts ring-local vs ring-remote
        on the shared request path (counters only, no routing)."""
        _, serving_cfg, _ = delta_pvc
        cfg = dataclasses.replace(
            serving_cfg,
            cache_affinity=True,
            cache_affinity_peers="pod-a,pod-b,pod-c",
            cache_affinity_self="pod-a",
        )
        app = RecommendApp(cfg)
        assert app.engine.load()
        for i in range(40):
            app.handle(
                "POST", "/api/recommend/",
                json.dumps({"songs": [f"s{i % 12:03d}"]}).encode(),
            )
        total = app.affinity_local_total + app.affinity_remote_total
        assert total == 40
        assert 0 < app.affinity_local_total < 40


class TestOwnerAwareServing:
    """Fleet cache ROUTING identity (ISSUE 15): with KMLS_FLEET_PEERS
    armed, a request this replica does not own is answered locally —
    mis-routed traffic degrades gracefully, never fails — but stamps
    ``X-KMLS-Cache-Owner`` and counts non-owned MISSES as
    ``kmls_cache_misrouted_total``, so routing drift at the ingress/
    client is observable per pod."""

    def _fleet_app(self, delta_pvc, self_name="pod-a"):
        _, serving_cfg, _ = delta_pvc
        cfg = dataclasses.replace(
            serving_cfg,
            fleet_self=self_name,
            fleet_peers="pod-a,pod-b,pod-c",
        )
        app = RecommendApp(cfg)
        assert app.engine.load()
        assert app.fleet_routing
        return app

    def _seed_sets_by_ownership(self, app, n=60):
        owned, foreign = [], []
        for i in range(n):
            seeds = [f"s{i % 12:03d}", f"probe-{i}"]
            owner = app.ring.owner(seeds_key(seeds))
            (owned if owner == app._ring_self else foreign).append(seeds)
        assert owned and foreign  # 3 peers: both sides populated
        return owned, foreign

    def _post(self, app, seeds):
        return app.handle(
            "POST", "/api/recommend/",
            json.dumps({"songs": seeds}).encode(),
        )

    def test_foreign_keys_stamp_owner_and_count_misses(self, delta_pvc):
        app = self._fleet_app(delta_pvc)
        owned, foreign = self._seed_sets_by_ownership(app)
        seeds = foreign[0]
        status, headers, _ = self._post(app, seeds)
        assert status == 200  # answered locally: degrade, never fail
        assert headers["X-KMLS-Cache-Owner"] == app.ring.owner(
            seeds_key(seeds)
        )
        assert app.misrouted_total == 1
        # the hit repeats the stamp (the drift observable) but does NOT
        # re-count: a hit did no duplicate device work
        status, headers, _ = self._post(app, seeds)
        assert status == 200
        assert headers.get("X-KMLS-Cache") == "hit"
        assert headers["X-KMLS-Cache-Owner"] == app.ring.owner(
            seeds_key(seeds)
        )
        assert app.misrouted_total == 1

    def test_owned_keys_never_stamp(self, delta_pvc):
        app = self._fleet_app(delta_pvc)
        owned, _ = self._seed_sets_by_ownership(app)
        for seeds in owned[:5]:
            status, headers, _ = self._post(app, seeds)
            assert status == 200
            assert "X-KMLS-Cache-Owner" not in headers
        assert app.misrouted_total == 0

    def test_fleet_identity_arms_affinity_counters_too(self, delta_pvc):
        app = self._fleet_app(delta_pvc)
        owned, foreign = self._seed_sets_by_ownership(app)
        for seeds in owned[:3]:
            self._post(app, seeds)
        for seeds in foreign[:4]:
            self._post(app, seeds)
        assert app.affinity_local_total == 3
        assert app.affinity_remote_total == 4

    def test_metrics_carry_misrouted_and_fleet_peers(self, delta_pvc):
        app = self._fleet_app(delta_pvc)
        _, foreign = self._seed_sets_by_ownership(app)
        self._post(app, foreign[0])
        _, _, body = app.handle("GET", "/metrics", b"")
        text = body.decode()
        assert "kmls_cache_misrouted_total 1" in text
        assert "kmls_fleet_peers 3" in text

    def test_unarmed_app_has_no_owner_surface(self, delta_pvc):
        _, serving_cfg, _ = delta_pvc
        app = RecommendApp(serving_cfg)
        assert app.engine.load()
        assert not app.fleet_routing
        status, headers, _ = self._post(app, ["s000"])
        assert status == 200
        assert "X-KMLS-Cache-Owner" not in headers
        _, _, body = app.handle("GET", "/metrics", b"")
        text = body.decode()
        assert "kmls_cache_misrouted_total 0" in text
        assert "kmls_fleet_peers 0" in text

    def test_degraded_answers_still_stamp_owner(self, delta_pvc):
        """Mis-routed traffic must degrade gracefully, never fail: even
        an answer that fell back to the popularity ranking carries the
        owner stamp (and counts — it did local work the owner's cache
        may already hold)."""
        _, serving_cfg, _ = delta_pvc
        cfg = dataclasses.replace(
            serving_cfg,
            fleet_self="pod-a",
            fleet_peers="pod-a,pod-b,pod-c",
            request_deadline_ms=0.000001,  # everything degrades
        )
        app = RecommendApp(cfg)
        assert app.engine.load()
        _, foreign = TestOwnerAwareServing._seed_sets_by_ownership(
            self, app
        )
        status, headers, _ = self._post(app, foreign[0])
        assert status == 200
        assert headers.get("X-KMLS-Degraded")
        assert "X-KMLS-Cache-Owner" in headers
        assert app.misrouted_total == 1


# ---------------------------------------------------------------------------
# /debug/traces loopback restriction + the tracejoin smoke
# ---------------------------------------------------------------------------


class TestTraceSurface:
    def _traced_app(self, delta_pvc):
        _, serving_cfg, _ = delta_pvc
        cfg = dataclasses.replace(serving_cfg, trace_sample=1.0)
        app = RecommendApp(cfg)
        assert app.engine.load()
        return app

    def test_debug_traces_loopback_only(self, delta_pvc):
        """Retained traces carry request payloads: fleet-scrapeable they
        are not — same policy (and v4/v6-mapped forms) as /metrics/reset."""
        app = self._traced_app(delta_pvc)
        for host in ("127.0.0.1", "::1", "::ffff:127.0.0.1"):
            status, _, _ = app.handle(
                "GET", "/debug/traces", b"", client_host=host
            )
            assert status == 200, host
        for host in ("10.2.3.4", "::ffff:10.2.3.4", "192.168.0.9"):
            status, _, _ = app.handle(
                "GET", "/debug/traces", b"", client_host=host
            )
            assert status == 403, host
        # in-process (no transport) keeps working — tests and tooling
        assert app.handle("GET", "/debug/traces", b"")[0] == 200

    def test_tracejoin_cli_merges_timelines(self, tmp_path, delta_pvc):
        """The CI smoke: replay-shaped client records + a real
        /debug/traces payload → one joined timeline per request."""
        app = self._traced_app(delta_pvc)
        records = []
        for i in range(5):
            t0 = time.time()
            status, headers, _ = app.handle(
                "POST", "/api/recommend/",
                json.dumps({"songs": [f"s{i:03d}"]}).encode(),
            )
            assert status == 200
            tid = headers.get("X-KMLS-Trace")
            assert tid
            records.append({
                "trace_id": tid,
                "client_send_unix": round(t0, 6),
                "client_recv_unix": round(time.time(), 6),
                "client_rtt_ms": round((time.time() - t0) * 1e3, 4),
                "status": status,
            })
        client_path = tmp_path / "client.jsonl"
        client_path.write_text(
            "".join(json.dumps(r) + "\n" for r in records)
        )
        _, _, payload = app.handle("GET", "/debug/traces", b"")
        traces_path = tmp_path / "traces.json"
        traces_path.write_text(payload.decode())

        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO_ROOT, "scripts", "kmls_tracejoin.py"),
             "--client", str(client_path), "--traces", str(traces_path)],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 0, proc.stderr
        lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        assert len(lines) == 5
        joined = [json.loads(ln) for ln in lines]
        for row in joined:
            assert row["server"] is not None
            assert row["client"]["rtt_ms"] >= 0.0
            assert "client_overhead_ms" in row
            assert {s["name"] for s in row["server"]["spans"]}
        assert "5/5" in proc.stderr

    def test_client_trace_log_bounded_and_written(self, tmp_path):
        from kmlserver_tpu.serving.replay import ClientTraceLog

        log = ClientTraceLog(capacity=2)
        log.record("aaaa", 1.0, 1.001)
        log.record("bbbb", 2.0, 2.002, status=429)
        log.record("cccc", 3.0, 3.003)  # over capacity → dropped
        log.record("", 4.0, 4.004)  # no id → ignored
        assert log.dropped == 1
        path = tmp_path / "log.jsonl"
        assert log.write_jsonl(str(path)) == 2
        rows = [json.loads(ln) for ln in path.read_text().splitlines()]
        assert rows[0]["trace_id"] == "aaaa"
        assert rows[1]["status"] == 429
        assert rows[0]["client_rtt_ms"] == pytest.approx(1.0, abs=0.01)


# ---------------------------------------------------------------------------
# serving exposition + the poll loop
# ---------------------------------------------------------------------------


class TestFreshnessExposition:
    def test_metrics_carry_delta_and_affinity_series(self, delta_pvc):
        mining_cfg, serving_cfg, csv_path = delta_pvc
        app = RecommendApp(serving_cfg)
        assert app.engine.load()
        _append_rows(csv_path, [(99, "s000"), (99, "s002")])
        assert run_mining_job(mining_cfg).delta_seq == 1
        assert app.engine.apply_pending_deltas() == 1
        _, _, payload = app.handle("GET", "/metrics", b"")
        text = payload.decode()
        assert "kmls_delta_applied_total 1" in text
        assert "kmls_delta_rejected_total 0" in text
        assert "kmls_delta_seq 1" in text
        assert "kmls_freshness_lag_seconds" in text
        assert "kmls_cache_selective_invalidations_total" in text
        assert "kmls_cache_invalidated_keys_total" in text
        assert "kmls_cache_affinity_local_total" in text
        assert "kmls_cache_affinity_remote_total" in text

    def test_poll_loop_applies_delta_without_token_rewrite(self, delta_pvc):
        """The production path: the poller notices the chain while the
        token (and epoch) stay put — freshness without a reload."""
        mining_cfg, serving_cfg, csv_path = delta_pvc
        engine = RecommendEngine(serving_cfg)
        assert engine.load()
        epoch0 = engine.bundle_epoch
        reloads0 = engine.reload_counter
        _append_rows(csv_path, [(101, "s000"), (101, "s005")])
        assert run_mining_job(mining_cfg).delta_seq == 1
        assert not engine.is_data_stale()
        engine.reload_if_required()
        assert engine.delta_seq == 1
        assert engine.bundle_epoch == epoch0
        assert engine.reload_counter == reloads0
