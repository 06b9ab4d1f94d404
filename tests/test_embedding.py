"""Second model family (ISSUE 6): ALS embedding training, the embedding
artifact, and hybrid rule∪embedding serving.

Coverage map:

- trainer: determinism, factor geometry (co-occurring tracks closer than
  non-co-occurring ones), normalization;
- artifact: save/load round trip, strict validation of corrupt shapes;
- :class:`EmbeddingModel`: fit / load / recommend parity with the kernel;
- pipeline: the ``embed`` phase publishes a manifested artifact, retires
  a stale one when disabled, and resumes bit-identically (the
  kill-at-every-phase matrix rides tests/test_mining_chaos.py, which
  mines with the embed phase ON);
- serving: hybrid answers are deterministic across replicas and cache
  epochs, a cold-start seed (zero rules) answers from the embedding
  space instead of the popularity fallback, response headers are
  unchanged, and the hot path stays compile-free after publish;
- chaos (marker ``chaos``): a torn/corrupt/fault-injected
  ``embeddings.npz`` degrades to rules-only — reload still succeeds,
  requests still answer, never a 5xx.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from kmlserver_tpu import faults
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.io import artifacts
from kmlserver_tpu.mining.als import normalize_factors, train_embeddings
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.mining.vocab import Baskets, Vocab
from kmlserver_tpu.models import EmbeddingModel
from kmlserver_tpu.serving.app import RecommendApp

from .oracle import random_baskets
from .test_pipeline import table_with_metadata


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def baskets_from_lists(lists: list[list[str]]) -> Baskets:
    names = sorted({t for basket in lists for t in basket})
    vocab = Vocab(names=names, index={n: i for i, n in enumerate(names)})
    rows, ids = [], []
    for p, basket in enumerate(lists):
        for t in set(basket):
            rows.append(p)
            ids.append(vocab.index[t])
    return Baskets(
        playlist_rows=np.asarray(rows, dtype=np.int32),
        track_ids=np.asarray(ids, dtype=np.int32),
        n_playlists=len(lists),
        vocab=vocab,
    )


def _make_pvc(base, *, embed=True, n_playlists=60, n_tracks=24, seed=0):
    """A fake PVC with one dataset; min_support high enough that a good
    fraction of the vocabulary has ZERO rules — the cold-start half of
    every hybrid test."""
    rng = np.random.default_rng(seed)
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir, exist_ok=True)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds1.csv"),
        table_with_metadata(random_baskets(
            rng, n_playlists=n_playlists, n_tracks=n_tracks, mean_len=5
        )),
    )
    return MiningConfig(
        base_dir=base, datasets_dir=ds_dir, min_support=0.15,
        k_max_consequents=32, top_tracks_save_percentile=0.25,
        embed_enabled=embed, als_rank=8, als_iters=4,
    )


def _serving_app(base, **over) -> RecommendApp:
    cfg = dataclasses.replace(ServingConfig(), base_dir=base, **over)
    app = RecommendApp(cfg)
    assert app.engine.load()
    return app


def _cold_and_hot_seeds(engine) -> tuple[str, str]:
    """→ (a seed with zero rules but an embedding row, a rule-known seed)."""
    bundle = engine.bundle
    known = {bundle.vocab[i] for i in range(len(bundle.vocab))
             if bundle.known_mask[i]}
    cold = [n for n in bundle.emb_vocab if n not in known]
    assert cold, "fixture must leave some tracks below min_support"
    return cold[0], sorted(known)[0]


class TestTrainer:
    def test_deterministic_and_normalized(self, tiny_baskets):
        bk = baskets_from_lists(tiny_baskets)
        cfg = MiningConfig(als_rank=4, als_iters=6, als_reg=0.05)
        a = train_embeddings(bk, cfg)
        b = train_embeddings(bk, cfg)
        assert np.array_equal(a["item_factors"], b["item_factors"])
        assert a["item_factors"].shape == (bk.n_tracks, 4)
        assert a["item_factors"].dtype == np.float32
        norms = np.linalg.norm(a["item_factors"], axis=1)
        np.testing.assert_allclose(norms, 1.0, atol=1e-5)

    def test_cooccurrence_shapes_similarity(self, tiny_baskets):
        """(t0, t1) co-occur in 3 of 5 playlists; (t0, t3) in 1 — the
        learned geometry must reflect that ordering."""
        bk = baskets_from_lists(tiny_baskets)
        f = train_embeddings(
            bk, MiningConfig(als_rank=4, als_iters=10, als_reg=0.05)
        )["item_factors"]
        idx = bk.vocab.index
        sim = f @ f.T
        assert sim[idx["t0"], idx["t1"]] > sim[idx["t0"], idx["t3"]]

    def test_hyperparameters_change_factors(self, tiny_baskets):
        bk = baskets_from_lists(tiny_baskets)
        a = train_embeddings(bk, MiningConfig(als_rank=4, als_iters=4))
        b = train_embeddings(bk, MiningConfig(als_rank=4, als_iters=8))
        assert not np.array_equal(a["item_factors"], b["item_factors"])

    def test_normalize_factors_guards_zero_rows(self):
        out = normalize_factors(np.zeros((2, 3), dtype=np.float32))
        assert np.isfinite(out).all()

    def test_hbm_guard_skips_training_deterministically(self, tiny_baskets):
        """A dense interaction matrix past the HBM budget must skip the
        phase (rules-only generation) instead of OOMing after the mine."""
        bk = baskets_from_lists(tiny_baskets)
        cfg = MiningConfig(als_rank=4, als_iters=2, hbm_budget_bytes=16)
        res = train_embeddings(bk, cfg)
        assert res["item_factors"] is None
        assert "exceeds hbm_budget_bytes" in res["skipped"]


class TestArtifact:
    def test_round_trip(self, tmp_path, tiny_baskets):
        bk = baskets_from_lists(tiny_baskets)
        res = train_embeddings(bk, MiningConfig(als_rank=4, als_iters=4))
        path = str(tmp_path / "embeddings.npz")
        artifacts.save_embeddings(
            path, vocab=bk.vocab.names, item_factors=res["item_factors"],
            rank=4, iters=4, reg=0.1, final_loss=res["final_loss"],
        )
        loaded = artifacts.load_embeddings(path)
        assert loaded["vocab"] == bk.vocab.names
        assert np.array_equal(loaded["item_factors"], res["item_factors"])
        assert loaded["rank"] == 4

    def test_save_rejects_shape_mismatch(self, tmp_path):
        with pytest.raises(ValueError):
            artifacts.save_embeddings(
                str(tmp_path / "e.npz"), vocab=["a", "b"],
                item_factors=np.zeros((3, 2), dtype=np.float32),
                rank=2, iters=1, reg=0.1,
            )

    def test_load_rejects_vocab_mismatch_and_nonfinite(self, tmp_path):
        path = str(tmp_path / "e.npz")
        artifacts.save_embeddings(
            path, vocab=["a", "b"],
            item_factors=np.full((2, 2), np.nan, dtype=np.float32),
            rank=2, iters=1, reg=0.1,
        )
        with pytest.raises(ValueError):
            artifacts.load_embeddings(path)

    def test_load_rejects_torn_file(self, tmp_path):
        path = str(tmp_path / "e.npz")
        artifacts.save_embeddings(
            path, vocab=["a", "b"],
            item_factors=np.eye(2, dtype=np.float32),
            rank=2, iters=1, reg=0.1,
        )
        faults.truncate_file(path, keep_fraction=0.4)
        with pytest.raises(Exception):
            artifacts.load_embeddings(path)


class TestEmbeddingModel:
    def test_fit_recommend_excludes_seeds(self, tiny_baskets):
        bk = baskets_from_lists(tiny_baskets)
        model = EmbeddingModel.fit(
            bk, MiningConfig(als_rank=4, als_iters=8)
        )
        recs = model.recommend([["t0"]], k_best=3)[0]
        assert recs and "t0" not in recs

    def test_load_matches_fit(self, tmp_path, tiny_baskets):
        bk = baskets_from_lists(tiny_baskets)
        res = train_embeddings(bk, MiningConfig(als_rank=4, als_iters=8))
        path = str(tmp_path / "embeddings.npz")
        artifacts.save_embeddings(
            path, vocab=bk.vocab.names, item_factors=res["item_factors"],
            rank=4, iters=8, reg=0.1,
        )
        fit = EmbeddingModel.fit(bk, MiningConfig(als_rank=4, als_iters=8))
        loaded = EmbeddingModel.load(path)
        seeds = [["t0", "t2"], ["t3"]]
        assert fit.recommend(seeds) == loaded.recommend(seeds)

    def test_unknown_seeds_give_empty(self, tiny_baskets):
        bk = baskets_from_lists(tiny_baskets)
        model = EmbeddingModel.fit(bk, MiningConfig(als_rank=4, als_iters=4))
        assert model.recommend([["nope"]], k_best=3) == [[]]


class TestPipelinePublication:
    def test_embed_phase_publishes_manifested_artifact(self, tmp_path):
        cfg = _make_pvc(str(tmp_path))
        summary = run_mining_job(cfg)
        assert summary.als_train_s is not None and summary.als_train_s > 0
        emb_path = summary.artifact_paths["embeddings"]
        assert os.path.basename(emb_path) == artifacts.EMBEDDINGS_FILENAME
        manifest = artifacts.load_manifest(cfg.pickles_dir)
        entry = manifest["files"][artifacts.EMBEDDINGS_FILENAME]
        assert entry == artifacts.file_digest(emb_path)
        loaded = artifacts.load_embeddings(emb_path)
        assert loaded["rank"] == cfg.als_rank

    def test_disabled_run_retires_stale_embeddings(self, tmp_path):
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        emb_path = artifacts.embeddings_artifact_path(cfg.pickles_dir)
        assert os.path.exists(emb_path)
        summary = run_mining_job(dataclasses.replace(cfg, embed_enabled=False))
        assert summary.als_train_s is None
        assert not os.path.exists(emb_path)
        manifest = artifacts.load_manifest(cfg.pickles_dir)
        assert artifacts.EMBEDDINGS_FILENAME not in manifest["files"]

    def test_hbm_guard_publishes_rules_only_generation(self, tmp_path):
        cfg = dataclasses.replace(_make_pvc(str(tmp_path)), hbm_budget_bytes=16)
        summary = run_mining_job(cfg)
        assert summary.als_train_s is None
        assert "embeddings" not in summary.artifact_paths
        assert not os.path.exists(
            artifacts.embeddings_artifact_path(cfg.pickles_dir)
        )
        app = _serving_app(str(tmp_path))
        assert not app.engine.embedding_active
        assert not app.engine.embedding_degraded  # absent ≠ degraded

    def test_crash_after_embed_resumes_bit_identical(self, tmp_path):
        """Kill right after the embed checkpoint; the restart resumes all
        four phases and publishes a byte-identical embeddings.npz (the
        manifest sha256 is the proof)."""
        ref_cfg = _make_pvc(str(tmp_path / "ref"))
        run_mining_job(ref_cfg)
        ref_manifest = artifacts.load_manifest(ref_cfg.pickles_dir)["files"]

        cfg = _make_pvc(str(tmp_path / "int"))
        faults.inject("mine.crash.embed", times=1)
        with pytest.raises(faults.FaultInjected):
            run_mining_job(cfg)
        faults.clear()
        summary = run_mining_job(cfg)
        assert summary.resumed_phases == ("encode", "mine", "rules", "embed")
        manifest = artifacts.load_manifest(cfg.pickles_dir)["files"]
        assert manifest == ref_manifest


class TestHybridServing:
    def test_cold_start_seed_answers_from_embeddings(self, tmp_path):
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        app = _serving_app(str(tmp_path))
        cold, _hot = _cold_and_hot_seeds(app.engine)
        songs, source = app.engine.recommend([cold])
        assert source == "embed"
        assert songs and cold not in songs

    def test_hot_seed_blends_and_zero_compiles(self, tmp_path):
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        app = _serving_app(str(tmp_path))
        _cold, hot = _cold_and_hot_seeds(app.engine)
        songs, source = app.engine.recommend([hot])
        assert source == "hybrid" and songs
        # batched path through the app/batcher/cache stack
        body = json.dumps({"songs": [hot]}).encode()
        status, headers, payload = app.handle("POST", "/api/recommend/", body)
        assert status == 200
        assert json.loads(payload)["songs"] == songs
        assert "X-KMLS-Cache" not in headers
        status, headers, payload = app.handle("POST", "/api/recommend/", body)
        assert status == 200 and headers.get("X-KMLS-Cache") == "hit"
        assert "X-KMLS-Degraded" not in headers
        assert app.engine.unwarmed_dispatches == 0

    def test_mode_rules_reproduces_legacy_answers(self, tmp_path):
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        hybrid_app = _serving_app(str(tmp_path))
        rules_app = _serving_app(str(tmp_path), hybrid_mode="rules")
        assert not rules_app.engine.embedding_active
        _cold, hot = _cold_and_hot_seeds(hybrid_app.engine)
        songs, source = rules_app.engine.recommend([hot])
        assert source == "rules" and songs

    def test_mode_embed_serves_embedding_topk(self, tmp_path):
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        app = _serving_app(str(tmp_path), hybrid_mode="embed")
        _cold, hot = _cold_and_hot_seeds(app.engine)
        songs, source = app.engine.recommend([hot])
        assert source == "embed" and songs

    def test_invalid_hybrid_mode_env_falls_back_to_rules(self, monkeypatch):
        """A typo in KMLS_HYBRID_MODE must never silently enable the
        hybrid merge — unrecognized values pin rules-only (fail-safe)."""
        monkeypatch.setenv("KMLS_HYBRID_MODE", "rule")  # typo
        assert ServingConfig.from_env(dotenv_path=None).hybrid_mode == "rules"
        monkeypatch.setenv("KMLS_HYBRID_MODE", "BLEND")  # case-insensitive
        assert ServingConfig.from_env(dotenv_path=None).hybrid_mode == "blend"
        monkeypatch.delenv("KMLS_HYBRID_MODE")
        assert ServingConfig.from_env(dotenv_path=None).hybrid_mode == "blend"

    def test_blend_weight_bounds(self, tmp_path):
        """w=0 ranks like rules-only for rule-covered candidates; w=1
        like embed-only — the knob's documented endpoints."""
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        _cold, hot = _cold_and_hot_seeds(_serving_app(str(tmp_path)).engine)
        w1 = _serving_app(str(tmp_path), hybrid_blend_weight=1.0)
        embed_only = _serving_app(str(tmp_path), hybrid_mode="embed")
        assert (
            w1.engine.recommend([hot])[0]
            == embed_only.engine.recommend([hot])[0]
        )

    def test_identity_across_replicas(self, tmp_path):
        """Every replica composes the identical hybrid answer — the
        least-loaded dispatcher may route a request anywhere."""
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        app = _serving_app(
            str(tmp_path), serve_devices=2
        )
        engine = app.engine
        assert engine.n_replicas >= 2
        cold, hot = _cold_and_hot_seeds(engine)
        for seeds in ([hot], [cold], [hot, cold]):
            answers = {
                tuple(r)
                for replica in range(engine.n_replicas)
                for r, _src in engine.recommend_many_async(
                    [seeds], replica=replica
                )()
            }
            assert len(answers) == 1, f"replicas disagree on {seeds}"
        assert engine.unwarmed_dispatches == 0

    def test_identity_across_cache_epochs(self, tmp_path):
        """Re-publishing identical artifacts bumps the epoch (cache
        invalidated wholesale) and the recomputed answer is identical."""
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        app = _serving_app(str(tmp_path))
        cold, hot = _cold_and_hot_seeds(app.engine)
        before = {
            s: app.recommend_direct([s])[0] for s in (hot, cold)
        }
        epoch_before = app.engine.bundle_epoch
        # same dataset re-mined: new token, same rule/embedding bytes
        run_mining_job(cfg)
        assert app.engine.load()
        assert app.engine.bundle_epoch == epoch_before + 1
        for seed, songs in before.items():
            recs, _source, cached = app.recommend_direct([seed])
            assert not cached  # old epoch's entries are unreachable
            assert recs == songs


@pytest.mark.chaos
class TestEmbeddingChaos:
    """The second writer's failure surface: a bad embeddings.npz costs
    answer QUALITY (rules-only), never the reload and never a 5xx."""

    def _request(self, app, seeds):
        return app.handle(
            "POST", "/api/recommend/", json.dumps({"songs": seeds}).encode()
        )

    def test_torn_artifact_degrades_to_rules_only(self, tmp_path):
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        emb_path = artifacts.embeddings_artifact_path(cfg.pickles_dir)
        faults.truncate_file(emb_path, keep_fraction=0.5)
        app = _serving_app(str(tmp_path))  # reload still succeeds
        engine = app.engine
        assert not engine.embedding_active
        assert engine.embedding_load_failures == 1
        assert engine.embedding_degraded
        cold, hot = None, None
        bundle = engine.bundle
        known = {bundle.vocab[i] for i in range(len(bundle.vocab))
                 if bundle.known_mask[i]}
        hot = sorted(known)[0]
        cold = next(n for n in bundle.vocab if n not in known)
        status, headers, _ = self._request(app, [hot])
        assert status == 200 and "X-KMLS-Degraded" not in headers
        # the cold seed falls back to popularity — degraded quality, not 5xx
        status, _headers, payload = self._request(app, [cold])
        assert status == 200
        # /readyz flags the dark second model, but stays 200 (ready)
        status, _h, body = app.handle("GET", "/readyz", None)
        assert status == 200
        assert "embedding artifact unusable" in str(json.loads(body))

    def test_fault_knob_arms_rules_only_degradation(self, tmp_path, monkeypatch):
        """KMLS_FAULT_EMBED_CORRUPT=1 (site embed.artifact) fails exactly
        one embedding load; the next reload recovers the hybrid path."""
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        monkeypatch.setenv("KMLS_FAULT_EMBED_CORRUPT", "1")
        faults.load_env(force=True)
        app = _serving_app(str(tmp_path))
        assert not app.engine.embedding_active
        assert app.engine.embedding_load_failures == 1
        # fault exhausted: re-publication (new token) reloads embeddings
        run_mining_job(cfg)
        assert app.engine.load()
        assert app.engine.embedding_active
        assert not app.engine.embedding_degraded

    def test_checksum_mismatch_skips_embeddings_not_reload(self, tmp_path):
        """Flip a byte WITHOUT breaking npz structure: the manifest gate
        catches it before parse, embeddings are skipped, rules serve."""
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        emb_path = artifacts.embeddings_artifact_path(cfg.pickles_dir)
        faults.flip_byte(emb_path)
        app = _serving_app(str(tmp_path))
        assert app.engine.finished_loading
        assert not app.engine.embedding_active
        assert app.engine.embedding_degraded

    def test_vanished_artifact_mid_load_is_absent_not_degraded(
        self, tmp_path, monkeypatch
    ):
        """exists() passes but the open races a writer retiring the file
        (an embed-disabled publication removes it before the token
        rewrite): rules-only WITHOUT the degraded flag or a failure count."""
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        real_load = artifacts.load_embeddings

        def vanish(path, **kwargs):
            raise FileNotFoundError(path)

        monkeypatch.setattr(
            "kmlserver_tpu.io.artifacts.load_embeddings", vanish
        )
        app = _serving_app(str(tmp_path))
        monkeypatch.setattr(
            "kmlserver_tpu.io.artifacts.load_embeddings", real_load
        )
        assert app.engine.finished_loading
        assert not app.engine.embedding_active
        assert not app.engine.embedding_degraded
        assert app.engine.embedding_load_failures == 0

    def test_all_unknown_seeds_skip_the_embed_dispatch(self, tmp_path):
        """A request with no embed-known seed must not pay the full-vocab
        kernel: _dispatch_embed declines and the legacy path answers."""
        cfg = _make_pvc(str(tmp_path))
        run_mining_job(cfg)
        engine = _serving_app(str(tmp_path)).engine
        assert engine.embedding_active
        assert engine._dispatch_embed(
            engine.bundle, [["definitely-not-a-track"]], 1, 1
        ) is None
        songs, source = engine.recommend(["definitely-not-a-track"])
        assert source == "fallback"

    def test_absent_artifact_is_not_degraded(self, tmp_path):
        """No embeddings published (embed phase off) = plain rules-only
        serving: no failure counters, no degraded flag, no readyz reason."""
        cfg = _make_pvc(str(tmp_path), embed=False)
        run_mining_job(cfg)
        app = _serving_app(str(tmp_path))
        assert not app.engine.embedding_active
        assert app.engine.embedding_load_failures == 0
        assert not app.engine.embedding_degraded
        status, _h, body = app.handle("GET", "/readyz", None)
        assert status == 200 and json.loads(body)["status"] == "ready"


# ---------------------------------------------------------------------------
# result copies started at dispatch (ISSUE 32): the batched device path
# against the single-request path, and the mechanism itself
# ---------------------------------------------------------------------------

HYBRID_MODES = ("blend", "rules", "embed")
ROW_KINDS = ("rule_only", "embed_only", "neither", "over_long", "empty")


@pytest.fixture(scope="module")
def hybrid_pvc(tmp_path_factory):
    base = str(tmp_path_factory.mktemp("hybrid_pvc"))
    run_mining_job(_make_pvc(base))
    return base


@pytest.fixture(scope="module")
def device_engines(hybrid_pvc):
    """One jitted-path engine per hybrid mode on one mined PVC, and the
    batch every case below dispatches: a row for each of ``ROW_KINDS``.
    The rule-only row's seed is taken out of the embedding index (the
    live factors may be older than the rules, after a delta), so it is
    known to the rule family alone."""
    engines = {
        mode: _serving_app(
            hybrid_pvc, hybrid_mode=mode
        ).engine
        for mode in HYBRID_MODES
    }
    cold, hot = _cold_and_hot_seeds(engines["blend"])
    bundle = engines["blend"].bundle
    hots = sorted(
        bundle.vocab[i] for i in range(len(bundle.vocab))
        if bundle.known_mask[i]
    )
    rule_only = hots[-1]
    assert rule_only != hot
    for engine in engines.values():
        for replica in engine.replicas:
            if replica.emb_index is not None:
                replica.emb_index = {
                    k: v for k, v in replica.emb_index.items()
                    if k != rule_only
                }
    cap = engines["blend"].cfg.max_seed_tracks
    rows = {
        "rule_only": [rule_only],
        "embed_only": [cold],
        "neither": ["definitely-not-a-track"],
        # longer than the widest bucket: both paths cut it at the cap,
        # which drops the last two names
        "over_long": [hot] * (cap - 1) + [hots[1], hots[2], cold],
        "empty": [],
    }
    assert len(rows["over_long"]) > cap
    return engines, rows


class TestBatchedMatchesSingle:
    @pytest.mark.parametrize("kind", ROW_KINDS)
    @pytest.mark.parametrize("mode", HYBRID_MODES)
    def test_row_for_row_what_recommend_gives(self, device_engines, mode, kind):
        """``recommend_many_async(...)()`` answers each row of a mixed
        batch with the songs and the source that ``recommend()`` gives
        the same seed set alone, whatever the mode and whichever family
        knows the row; the row is also checked as a batch of its own
        (where a row no family knows dispatches one program, or none)."""
        engines, rows = device_engines
        engine = engines[mode]
        expected = engine.recommend(rows[kind])
        batch = [rows[k] for k in ROW_KINDS]
        got = engine.recommend_many_async(batch)()
        assert len(got) == len(batch)
        assert got[ROW_KINDS.index(kind)] == expected
        assert engine.recommend_many_async([rows[kind]])() == [expected]
        assert engine.unwarmed_dispatches == 0

    def test_the_rows_are_what_their_names_say(self, device_engines):
        engines, rows = device_engines
        sources = {
            kind: engines["blend"].recommend(rows[kind])[1] for kind in rows
        }
        assert sources == {
            "rule_only": "rules", "embed_only": "embed",
            "neither": "fallback", "over_long": "hybrid",
            "empty": "fallback",
        }


class _Watched:
    """Stands in for a device result: says when its copy to the host was
    started and when it was picked up, and is the array otherwise."""

    def __init__(self, arr, name, log):
        self.arr, self.name, self.log = arr, name, log

    def copy_to_host_async(self):
        self.log.append(("copy", self.name))
        self.arr.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.name))
        return np.asarray(self.arr)


class TestCopiesStartAtDispatch:
    """The mechanism, on the CPU backend: every result's copy to the host
    is started inside dispatch, and ``finish()`` picks the rule pair up
    before the embedding pair."""

    @staticmethod
    def _watch(engine, monkeypatch):
        from kmlserver_tpu.serving import engine as engine_mod

        log: list[tuple[str, str]] = []
        real_kernel, real_embed = engine._kernel, engine_mod.embed_topk

        def kernel(*args):
            ids, confs = real_kernel(*args)
            return (
                _Watched(ids, "rule_ids", log),
                _Watched(confs, "rule_confs", log),
            )

        def embed(*args, **kwargs):
            ids, sims = real_embed(*args, **kwargs)
            return (
                _Watched(ids, "emb_ids", log),
                _Watched(sims, "emb_sims", log),
            )

        monkeypatch.setattr(engine, "_kernel", kernel)
        monkeypatch.setattr(engine_mod, "embed_topk", embed)
        return log

    def test_hybrid_batch_four_copies_picked_up_in_program_order(
        self, device_engines, monkeypatch
    ):
        engines, rows = device_engines
        engine = engines["blend"]
        batch = [rows["over_long"], rows["embed_only"], rows["rule_only"]]
        expected = engine.recommend_many_async(batch)()  # unwatched, warm
        log = self._watch(engine, monkeypatch)
        finish = engine.recommend_many_async(batch)
        # dispatch is over: every result is already on its way to the
        # host, and none has been waited for
        assert log == [
            ("copy", "rule_ids"), ("copy", "rule_confs"),
            ("copy", "emb_ids"), ("copy", "emb_sims"),
        ]
        del log[:]
        assert finish() == expected
        assert log == [
            ("fetch", "rule_ids"), ("fetch", "rule_confs"),
            ("fetch", "emb_ids"), ("fetch", "emb_sims"),
        ]

    @pytest.mark.parametrize("case", ["rules_mode", "no_embed_known_seed"])
    def test_rules_only_batch_starts_the_rule_pair_alone(
        self, device_engines, monkeypatch, case
    ):
        """The embedding dispatch is skipped where the code can see it
        has nothing to answer: the operator pinned rules-only, or no row
        of the batch has an embed-known seed. Two copies start, not four."""
        engines, rows = device_engines
        engine = engines["rules" if case == "rules_mode" else "blend"]
        batch = [rows["rule_only"], rows["neither"], rows["empty"]]
        if case == "rules_mode":
            batch.append(rows["over_long"])
        expected = engine.recommend_many_async(batch)()
        log = self._watch(engine, monkeypatch)
        finish = engine.recommend_many_async(batch)
        assert log == [("copy", "rule_ids"), ("copy", "rule_confs")]
        assert finish() == expected
        assert log[2:] == [("fetch", "rule_ids"), ("fetch", "rule_confs")]
