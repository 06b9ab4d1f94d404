"""Model-parallel layout (ISSUE 7): vocab-sharded rule & support tensors.

Layout-equivalence coverage, on the virtual 8-device CPU mesh:

- kernel: the sharded lookup (per-shard gather/top-k + cross-device
  max-merge of the partials) is BIT-identical to the replicated kernel,
  ties and padding included, and every rule program to a numpy
  reference of the dense semantics;
- serving: a sharded engine answers bit-identically to a replicated one
  across publications (epochs), presents as one replica, never compiles
  after publish on ANY warmed bucket, and exposes per-shard dispatch
  counters;
- layout resolution: ``auto`` shards exactly when the measured tensor
  bytes exceed the per-device budget (and never on one device);
- mining: the vocab-sharded count→emit path produces rule tensors (and
  the expanded pickle dict) bit-identical to the dense/native path, and
  a catalog-scale chaos case proves sharded mine→crash→resume publishes
  bit-identical artifacts (marker ``chaos``);
- ALS: the mesh-sharded item half-sweep matches the single-device
  factors to float tolerance, is run-to-run deterministic, and the
  layout's presence in the checkpoint fingerprint keeps cross-layout
  resumes impossible.
"""

import dataclasses
import os

import jax
import numpy as np
import pytest

from kmlserver_tpu import faults
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.io import registry
from kmlserver_tpu.mining import checkpoint as ckpt_mod
from kmlserver_tpu.mining.miner import mine
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.ops.serve import (
    merge_partial_topk,
    recommend_batch,
    shard_partial_topk,
    sharded_recommend_fn,
)
from kmlserver_tpu.parallel.layout import resolve_layout, validate_layout
from kmlserver_tpu.parallel.mesh import make_mesh
from kmlserver_tpu.serving.engine import RecommendEngine

from .test_serving import mined_pvc  # noqa: F401  (fixture re-export)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _random_rule_tensors(rng, v, k, levels=None):
    """Random padded rule tensors with deliberate confidence TIES (the
    tie order is half the bit-identity contract)."""
    rule_ids = np.full((v, k), -1, np.int32)
    rule_confs = np.zeros((v, k), np.float32)
    # quantized confidences: collisions guaranteed
    if levels is None:
        levels = np.linspace(0.1, 1.0, 7).astype(np.float32)
    for i in range(v):
        n = int(rng.integers(0, k + 1))
        ids = rng.choice(v, size=n, replace=False).astype(np.int32)
        confs = np.sort(rng.choice(levels, size=n))[::-1]
        rule_ids[i, :n] = ids
        rule_confs[i, :n] = confs
    return rule_ids, rule_confs


def _shard_tensors(mesh, rule_ids, rule_confs):
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape["shard"]
    v, k = rule_ids.shape
    v_pad = ((v + n - 1) // n) * n
    ids = np.full((v_pad, k), -1, np.int32)
    confs = np.zeros((v_pad, k), np.float32)
    ids[:v] = rule_ids
    confs[:v] = rule_confs
    spec = NamedSharding(mesh, P("shard", None))
    return jax.device_put(ids, spec), jax.device_put(confs, spec)


class TestShardedKernel:
    @pytest.mark.parametrize("n_shards", [2, 4, 8])
    def test_bit_identical_to_replicated(self, rng, n_shards):
        from jax.sharding import Mesh

        v, k, k_best = 53, 7, 10
        rule_ids, rule_confs = _random_rule_tensors(rng, v, k)
        seeds = rng.integers(-1, v, size=(6, 4)).astype(np.int32)
        ref = recommend_batch(
            jax.numpy.asarray(rule_ids), jax.numpy.asarray(rule_confs),
            jax.numpy.asarray(seeds), k_best=k_best,
        )
        mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("shard",))
        ids_sh, confs_sh = _shard_tensors(mesh, rule_ids, rule_confs)
        got = sharded_recommend_fn(mesh, k_best)(ids_sh, confs_sh, seeds)
        np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(got[0]))
        np.testing.assert_array_equal(np.asarray(ref[1]), np.asarray(got[1]))

    def test_tiny_vocab_under_k_best(self, rng):
        # V < k_best AND V < v_pad: the static-pad columns must match
        from jax.sharding import Mesh

        v, k, k_best = 5, 3, 10
        rule_ids, rule_confs = _random_rule_tensors(rng, v, k)
        seeds = np.array([[0, 4, -1]], np.int32)
        ref = recommend_batch(
            jax.numpy.asarray(rule_ids), jax.numpy.asarray(rule_confs),
            jax.numpy.asarray(seeds), k_best=k_best,
        )
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("shard",))
        ids_sh, confs_sh = _shard_tensors(mesh, rule_ids, rule_confs)
        got = sharded_recommend_fn(mesh, k_best)(ids_sh, confs_sh, seeds)
        np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(got[0]))
        np.testing.assert_array_equal(np.asarray(ref[1]), np.asarray(got[1]))


def _dense_reference(cand_ids, cand_confs, v, k_best):
    """The dense lookup's semantics in numpy, independent of the kernels:
    scatter-max the live lanes (id ≥ 0, conf > 0) into V+1 columns, dead
    ones into the spill column V; a stable sort of the V columns by
    (-conf, id); the first ``k_best``, -1 where conf ≤ 0, padded to
    ``k_best`` columns when V < k_best."""
    b = cand_ids.shape[0]
    top_ids = np.full((b, k_best), -1, np.int32)
    top_confs = np.zeros((b, k_best), np.float32)
    for row in range(b):
        live = (cand_ids[row] >= 0) & (cand_confs[row] > 0)
        scores = np.zeros(v + 1, np.float32)
        np.maximum.at(
            scores, np.where(live, cand_ids[row], v),
            np.where(live, cand_confs[row], np.float32(0)),
        )
        scores = scores[:v]
        order = np.argsort(-scores, kind="stable")[:k_best]
        top_ids[row, : len(order)] = np.where(scores[order] > 0, order, -1)
        top_confs[row, : len(order)] = scores[order]
    return top_ids, top_confs


_LEVELS = np.array([0.25, 0.5, 0.75, 1.0], np.float32)


def _shard_partials(rng, n_shards, batch, v, k_best):
    """(S, B, k_best) partials as the shards hand them to the merge: each
    a deduplicated top-k of a shard, ids drawn from a pool of 2·k_best so
    that ids repeat across shards, at quantized confs so that they repeat
    at equal and at different confs and different ids tie, dead lanes
    -1 / 0. Row 0 is written by hand (5 distinct ids, fewer than k_best,
    an id repeated at an equal and one at a different conf); the last of
    three or more rows is all dead; row 1 carries a rank the serve mesh
    dropped (ids 0, conf -inf)."""
    ids = np.full((n_shards, batch, k_best), -1, np.int32)
    confs = np.zeros((n_shards, batch, k_best), np.float32)
    pool = min(v, 2 * k_best)
    for shard in range(n_shards):
        for row in range(batch):
            n = int(rng.integers(0, min(pool, k_best) + 1))
            lane_ids = rng.choice(pool, size=n, replace=False)
            lane_confs = rng.choice(_LEVELS, size=n)
            order = np.lexsort((lane_ids, -lane_confs))
            ids[shard, row, :n] = lane_ids[order]
            confs[shard, row, :n] = lane_confs[order]
    ids[:, 0], confs[:, 0] = -1, 0.0
    ids[0, 0, :4], confs[0, 0, :4] = [4, 2, 6, 1], [0.75, 0.5, 0.5, 0.25]
    ids[1, 0, :3], confs[1, 0, :3] = [2, 0, 4], [0.5, 0.5, 0.25]
    if batch >= 2:
        ids[-1, 1], confs[-1, 1] = 0, -np.inf
    if batch >= 3:
        ids[:, -1], confs[:, -1] = -1, 0.0
    return ids, confs


def _lookup_case(rng, batch, v):
    """Rule tensors and a seed batch for the lookup programs. V = 7 is
    under ``k_best``; V = 300 draws 5 seeds a request over rows of 6
    lanes at 7 conf levels; V = 3000 draws 128 seeds over rows of 256,
    N = 32,768 lanes a request, at 4,096 levels. Rule rows 0-2 are
    written by hand and request 0 asks for them: id 4 from three seeds
    at 0.75, 0.25 and 0.75, id 2 from two at 0.5, ids 0, 2 and 6 tied at
    0.5, five distinct live ids in all. The last of three or more
    requests has every lane dead."""
    k_max, length, levels = 6, 5, None
    if v == 3000:
        k_max, length = 256, 128
        levels = (np.arange(1, 4097) / 4096).astype(np.float32)
    rule_ids, rule_confs = _random_rule_tensors(rng, v, min(v, k_max), levels)
    for row, (ids, confs) in enumerate([
        ([4, 2, 6, 1], [0.75, 0.5, 0.5, 0.25]),
        ([2, 0, 4], [0.5, 0.5, 0.25]),
        ([4], [0.75]),
    ]):
        rule_ids[row], rule_confs[row] = -1, 0.0
        rule_ids[row, : len(ids)], rule_confs[row, : len(ids)] = ids, confs
    seeds = rng.integers(-1, v, size=(batch, length)).astype(np.int32)
    seeds[0] = -1
    seeds[0, :4] = [0, 1, 2, 0]
    if batch >= 3:
        seeds[-1] = -1
    return rule_ids, rule_confs, seeds


def _candidates(rule_ids, rule_confs, seeds, lo=0, hi=None):
    """The (B, L·K_max) lanes the lookup ranks, from seeds in [lo, hi)."""
    hi = rule_ids.shape[0] if hi is None else hi
    in_range = (seeds >= lo) & (seeds < hi)
    rows = np.where(in_range, seeds, 0)
    valid = in_range[..., None] & (rule_ids[rows] >= 0)
    batch = seeds.shape[0]
    return (
        np.where(valid, rule_ids[rows], -1).reshape(batch, -1),
        np.where(valid, rule_confs[rows], 0).reshape(batch, -1),
    )


class TestMergeAgainstDenseReference:
    """Every rule program is held bit for bit to a numpy reference of the
    dense semantics: the cross-shard merge alone (its S·k_best partial
    lanes), the replicated kernel, one shard's partial at every ``lo``,
    and the whole sharded lookup. Each ranks its candidate lanes among
    themselves, so the reference is the one independent judge. The
    replicated kernel has no shards: ``n_shards`` draws its data."""

    @pytest.mark.parametrize("v", [7, 300, 3000])
    @pytest.mark.parametrize("batch", [1, 3, 32])
    @pytest.mark.parametrize("n_shards", [2, 4, 8])
    @pytest.mark.parametrize(
        "kernel", ["merge", "sharded", "replicated", "partial"]
    )
    def test_bit_identical_to_dense_reference(
        self, kernel, n_shards, batch, v
    ):
        from jax.sharding import Mesh

        k_best = 10
        rng = np.random.default_rng([n_shards, batch, v])
        if kernel == "merge":
            ids, confs = _shard_partials(rng, n_shards, batch, v, k_best)
            got = merge_partial_topk(ids, confs, k_best=k_best)
            cand_ids = np.swapaxes(ids, 0, 1).reshape(batch, -1)
            cand_confs = np.swapaxes(confs, 0, 1).reshape(batch, -1)
        else:
            rule_ids, rule_confs, seeds = _lookup_case(rng, batch, v)
            cand_ids, cand_confs = _candidates(rule_ids, rule_confs, seeds)
        if kernel == "sharded":
            mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("shard",))
            ids_sh, confs_sh = _shard_tensors(mesh, rule_ids, rule_confs)
            got = sharded_recommend_fn(mesh, k_best)(ids_sh, confs_sh, seeds)
        elif kernel == "replicated":
            got = recommend_batch(rule_ids, rule_confs, seeds, k_best=k_best)
        elif kernel == "partial":
            v_loc = -(-v // n_shards)
            pad = ((0, v_loc * n_shards - v), (0, 0))
            ids_pad = np.pad(rule_ids, pad, constant_values=-1)
            confs_pad = np.pad(rule_confs, pad)
            for lo in range(0, v, v_loc):
                part = shard_partial_topk(
                    ids_pad[lo : lo + v_loc], confs_pad[lo : lo + v_loc],
                    seeds, np.int32(lo), v=v_loc * n_shards, k_best=k_best,
                )
                ref = _dense_reference(
                    *_candidates(rule_ids, rule_confs, seeds, lo, lo + v_loc),
                    v, k_best,
                )
                np.testing.assert_array_equal(np.asarray(part[0]), ref[0])
                np.testing.assert_array_equal(np.asarray(part[1]), ref[1])
                assert part[1].dtype == np.float32
            got = None
        ref = _dense_reference(cand_ids, cand_confs, v, k_best)
        if got is not None:
            np.testing.assert_array_equal(np.asarray(got[0]), ref[0])
            np.testing.assert_array_equal(np.asarray(got[1]), ref[1])
            assert got[1].dtype == np.float32
        # the hand-written request: max-merged repeats, then (conf, id)
        np.testing.assert_array_equal(ref[0][0, :6], [4, 0, 2, 6, 1, -1])
        if batch == 32 and v == 300:
            # somewhere in the batch the k-th and the next id tie
            wider = _dense_reference(cand_ids, cand_confs, v, k_best + 1)[1]
            cut = wider[:, k_best]
            assert ((cut > 0) & (cut == wider[:, k_best - 1])).any()
        if batch >= 3:
            assert (ref[0][-1] == -1).all()


class TestLayoutResolution:
    def test_explicit_spellings(self):
        assert resolve_layout("replicated", 10**12, 1, 8) == "replicated"
        assert resolve_layout("sharded", 1, 10**12, 8) == "sharded"
        # one device: nothing to shard across, whatever the knob says
        assert resolve_layout("sharded", 10**12, 1, 1) == "replicated"

    def test_auto_measures_bytes_vs_budget(self):
        assert resolve_layout("auto", 100, 1000, 8) == "replicated"
        assert resolve_layout("auto", 1001, 1000, 8) == "sharded"
        # budget 0 disables the trigger entirely
        assert resolve_layout("auto", 10**12, 0, 8) == "replicated"

    def test_typo_fails_safe_to_replicated(self):
        assert validate_layout("shard-it-all") == "replicated"
        assert resolve_layout("shard-it-all", 10**12, 1, 8) == "replicated"


def _sharded_cfg(cfg, **kw):
    return dataclasses.replace(
        cfg, model_layout="sharded", serve_devices=4,
        batch_max_size=4, max_seed_tracks=8, **kw,
    )


def _replicated_cfg(cfg, **kw):
    return dataclasses.replace(
        cfg, serve_devices=1,
        batch_max_size=4, max_seed_tracks=8, **kw,
    )


def _known_seeds(bundle):
    return [s for s in bundle.vocab if bundle.known_mask[bundle.index[s]]]


class TestShardedServing:
    def test_answers_identical_across_layouts_and_epochs(self, mined_pvc):
        cfg, _, mining_cfg = mined_pvc
        rep = RecommendEngine(_replicated_cfg(cfg))
        shd = RecommendEngine(_sharded_cfg(cfg))
        assert rep.load() and shd.load()
        assert shd.model_layout == "sharded"
        assert rep.model_layout == "replicated"
        assert shd.n_replicas == 1  # one logical replica to the batcher
        seeds = _known_seeds(shd.bundle)
        sets = [
            [seeds[0]], [seeds[1], seeds[2]], ["unknown-zz"],
            seeds[:4], ["loner"],
        ]
        assert rep.recommend_many_async(sets)() == \
            shd.recommend_many_async(sets)()
        assert rep.recommend(seeds[0:2]) == shd.recommend(seeds[0:2])
        # a new publication (epoch bump) must stay answer-identical too
        registry.append_history_and_invalidate(mining_cfg, 1, "ds1")
        assert rep.load() and shd.load()
        assert shd.bundle_epoch == 2 == rep.bundle_epoch
        assert rep.recommend_many_async(sets)() == \
            shd.recommend_many_async(sets)()

    def test_zero_compile_after_publish_on_every_sharded_bucket(
        self, mined_pvc
    ):
        """Acceptance: every (batch, length) bucket was compiled for the
        sharded kernel at publication — dispatching all of them moves
        neither the jit cache nor the unwarmed-dispatch counter."""
        cfg, _, _ = mined_pvc
        engine = RecommendEngine(_sharded_cfg(cfg))
        assert engine.load()
        bundle = engine.bundle
        for batch in engine._batch_buckets():
            for length in engine._len_buckets():
                assert (batch, length) in bundle.warmed_shapes
        counter = getattr(bundle.shard_kernel, "_cache_size", None)
        n0 = counter() if counter else None
        seeds = _known_seeds(bundle)
        for b in (1, 2, 3, 4):
            results = engine.recommend_many_async(
                [[seeds[i % len(seeds)]] for i in range(b)]
            )()
            assert len(results) == b
        assert engine.unwarmed_dispatches == 0
        if counter:
            assert counter() == n0, "a sharded dispatch compiled a kernel"

    def test_auto_layout_shards_only_past_the_budget(self, mined_pvc):
        cfg, _, _ = mined_pvc
        # tiny budget: the ds tensors measure over it → sharded
        tight = RecommendEngine(dataclasses.replace(
            cfg, model_layout="auto", device_budget_bytes=64,
            serve_devices=4, batch_max_size=4, max_seed_tracks=8,
        ))
        assert tight.load()
        assert tight.bundle.layout == "sharded"
        assert tight.n_shards == 4
        # roomy budget: replicated, exactly the legacy layout
        roomy = RecommendEngine(dataclasses.replace(
            cfg, model_layout="auto", device_budget_bytes=1 << 40,
            serve_devices=4,
            batch_max_size=4, max_seed_tracks=8,
        ))
        assert roomy.load()
        assert roomy.bundle.layout == "replicated"
        assert len(roomy.replicas) == 4

    def test_hybrid_embeddings_ride_the_sharded_layout(self, tmp_path):
        """Second-model-family interop: with embeddings published, a
        sharded engine still answers identically to a replicated one
        (only the RULE tensors span the mesh; the embed kernel keeps its
        default placement) and neither kernel compiles post-publish."""
        from kmlserver_tpu.data.csv import write_tracks_csv
        from kmlserver_tpu.ops import embed as embed_ops

        from .oracle import random_baskets
        from .test_pipeline import table_with_metadata

        rng = np.random.default_rng(2)
        ds_dir = os.path.join(str(tmp_path), "datasets")
        os.makedirs(ds_dir)
        write_tracks_csv(
            os.path.join(ds_dir, "2023_spotify_ds1.csv"),
            table_with_metadata(random_baskets(
                rng, n_playlists=60, n_tracks=24, mean_len=5
            )),
        )
        run_mining_job(MiningConfig(
            base_dir=str(tmp_path), datasets_dir=ds_dir, min_support=0.12,
            k_max_consequents=16, top_tracks_save_percentile=0.3,
            embed_enabled=True, als_rank=8, als_iters=3,
        ))
        cfg = ServingConfig(base_dir=str(tmp_path), k_best_tracks=5)
        rep = RecommendEngine(_replicated_cfg(cfg))
        shd = RecommendEngine(_sharded_cfg(cfg))
        assert rep.load() and shd.load()
        assert shd.embedding_active and shd.bundle.layout == "sharded"
        counter = getattr(embed_ops.embed_topk, "_cache_size", None)
        n0 = counter() if counter else None
        bundle = shd.bundle
        cold = [
            n for n in bundle.emb_vocab
            if n not in bundle.index or not bundle.known_mask[bundle.index[n]]
        ]
        sets = [
            _known_seeds(bundle)[:2], ["unknown-zz"],
            (cold[:1] or [bundle.emb_vocab[0]]),
        ]
        assert rep.recommend_many_async(sets)() == \
            shd.recommend_many_async(sets)()
        assert shd.unwarmed_dispatches == 0
        if counter:
            assert counter() == n0, "embed kernel compiled post-publish"

    def test_shard_dispatch_counters_rendered(self, mined_pvc):
        from kmlserver_tpu.serving.metrics import ServingMetrics

        cfg, _, _ = mined_pvc
        engine = RecommendEngine(_sharded_cfg(cfg))
        assert engine.load()
        seeds = _known_seeds(engine.bundle)
        engine.recommend_many_async([[s] for s in seeds[:4]])()
        counts = engine.shard_dispatch_counts
        assert len(counts) == 4 and sum(counts) >= 4
        text = ServingMetrics().render(
            engine.reload_counter, True, shard_counts=counts
        )
        assert 'kmls_shard_dispatch_total{shard="0"}' in text


def _mesh_tp(n):
    return make_mesh((1, n), devices=jax.devices()[:n])


class TestShardedMining:
    def _baskets(self, seed=9, n_playlists=300, n_tracks=220):
        from kmlserver_tpu.data.synthetic import synthetic_table
        from kmlserver_tpu.mining.vocab import build_baskets

        return build_baskets(synthetic_table(
            n_playlists=n_playlists, n_tracks=n_tracks,
            target_rows=n_playlists * 18, seed=seed,
        ))

    def test_vocab_sharded_mine_bit_identical_to_dense(self):
        baskets = self._baskets()
        cfg = MiningConfig(
            min_support=0.01, k_max_consequents=24,
            prune_vocab_threshold=10_000,
        )
        dense = mine(baskets, cfg)
        sharded = mine(
            baskets, dataclasses.replace(cfg, model_layout="sharded")
        )
        assert sharded.count_path == "sharded-vocab-gspmd"
        for field in (
            "rule_ids", "rule_counts", "rule_confs", "item_counts",
            "row_valid_counts",
        ):
            np.testing.assert_array_equal(
                getattr(dense.tensors, field),
                getattr(sharded.tensors, field),
                err_msg=field,
            )
        assert dense.tensors.to_rules_dict(dense.vocab_names) == \
            sharded.tensors.to_rules_dict(sharded.vocab_names)

    @pytest.mark.parametrize("impl", ["allgather", "ring"])
    def test_explicit_impls_agree(self, impl):
        from kmlserver_tpu.ops import support
        from kmlserver_tpu.parallel.support import sharded_rule_tensors

        baskets = self._baskets(seed=3, n_playlists=120, n_tracks=90)
        cfg = MiningConfig(min_support=0.02, prune_vocab_threshold=10_000)
        dense = mine(baskets, cfg)
        min_count = support.min_count_for(0.02, baskets.n_playlists)
        # a dp×tp mesh: playlists AND vocab both sharded
        emitted = sharded_rule_tensors(
            baskets, make_mesh((2, 4)), min_count, 256, impl=impl,
        )
        np.testing.assert_array_equal(dense.tensors.rule_ids, emitted[0])
        np.testing.assert_array_equal(dense.tensors.rule_counts, emitted[1])
        np.testing.assert_array_equal(dense.tensors.item_counts, emitted[3])

    def test_explicit_vocab_mesh_respected(self):
        baskets = self._baskets(seed=4, n_playlists=100, n_tracks=60)
        cfg = MiningConfig(
            min_support=0.02, model_layout="sharded",
            sharded_impl="allgather", prune_vocab_threshold=10_000,
        )
        got = mine(
            baskets, cfg,
            mesh=make_mesh((2, 2), devices=jax.devices()[:4]),
        )
        assert got.count_path == "sharded-vocab-allgather"

    def test_fingerprint_differs_across_layouts_and_topologies(
        self, tmp_path, monkeypatch
    ):
        ds = tmp_path / "ds.csv"
        ds.write_text("playlist_pid,track_name,artist_name,track_uri\n")
        cfg = MiningConfig(base_dir=str(tmp_path))
        a = ckpt_mod.compute_fingerprint(cfg, str(ds), 1)
        sharded_cfg = dataclasses.replace(cfg, model_layout="sharded")
        b = ckpt_mod.compute_fingerprint(sharded_cfg, str(ds), 1)
        assert a != b  # a checkpoint can never resume across layouts
        # ... nor across shard TOPOLOGIES (the sharded ALS psum order
        # follows the mesh): a rescaled gang must re-mine
        monkeypatch.setattr(jax, "devices", lambda: list(range(4)))
        c = ckpt_mod.compute_fingerprint(sharded_cfg, str(ds), 1)
        assert c != b
        # the replicated default stays topology-INVARIANT (a TPU↔CPU
        # restart with a different device count must keep resuming)
        assert ckpt_mod.compute_fingerprint(cfg, str(ds), 1) == a


class TestShardedALS:
    def _baskets(self):
        from kmlserver_tpu.data.synthetic import synthetic_table
        from kmlserver_tpu.mining.vocab import build_baskets

        return build_baskets(synthetic_table(
            n_playlists=90, n_tracks=45, target_rows=1400, seed=7
        ))

    def test_sharded_half_sweep_matches_dense_factors(self):
        from kmlserver_tpu.mining.als import train_embeddings

        baskets = self._baskets()
        cfg = MiningConfig(embed_enabled=True, als_rank=8, als_iters=4)
        dense = train_embeddings(baskets, cfg)
        sharded = train_embeddings(
            baskets, dataclasses.replace(cfg, model_layout="sharded"),
            mesh=_mesh_tp(4),
        )
        assert dense["shards"] == 1 and sharded["shards"] == 4
        assert sharded["item_factors"].shape == dense["item_factors"].shape
        # collective reduction order ≠ single-matmul order: float-equal,
        # not bit-equal — which is exactly why model_layout fingerprints
        np.testing.assert_allclose(
            sharded["item_factors"], dense["item_factors"],
            rtol=2e-4, atol=2e-5,
        )
        assert sharded["final_loss"] == pytest.approx(
            dense["final_loss"], rel=1e-4
        )

    def test_sharded_training_is_deterministic(self):
        from kmlserver_tpu.mining.als import train_embeddings

        baskets = self._baskets()
        cfg = MiningConfig(
            embed_enabled=True, als_rank=8, als_iters=3,
            model_layout="sharded",
        )
        one = train_embeddings(baskets, cfg, mesh=_mesh_tp(4))
        two = train_embeddings(baskets, cfg, mesh=_mesh_tp(4))
        np.testing.assert_array_equal(
            one["item_factors"], two["item_factors"]
        )

    def test_auto_layout_trains_what_one_device_would_skip(self):
        from kmlserver_tpu.mining.als import train_embeddings

        baskets = self._baskets()
        p, v = baskets.n_playlists, baskets.n_tracks
        # budget sized between the single-device and the 4-shard slab:
        # one device must SKIP, the sharded auto layout must TRAIN
        budget = 3 * p * v
        cfg = MiningConfig(
            embed_enabled=True, als_rank=4, als_iters=2,
            model_layout="auto", hbm_budget_bytes=budget,
        )
        alone = train_embeddings(baskets, cfg)
        assert alone["item_factors"] is None  # HBM guard skipped it
        meshed = train_embeddings(baskets, cfg, mesh=_mesh_tp(4))
        assert meshed["item_factors"] is not None
        assert meshed["shards"] == 4


def _artifact_bytes(cfg) -> dict[str, bytes]:
    out = {}
    for name in (cfg.recommendations_file, cfg.best_tracks_file):
        with open(os.path.join(cfg.pickles_dir, name), "rb") as fh:
            out[name] = fh.read()
    return out


@pytest.mark.chaos
class TestShardedMineResume:
    def _make_pvc(self, base, rng_seed=0):
        from .oracle import random_baskets
        from .test_pipeline import table_with_metadata
        from kmlserver_tpu.data.csv import write_tracks_csv

        rng = np.random.default_rng(rng_seed)
        ds_dir = os.path.join(base, "datasets")
        os.makedirs(ds_dir, exist_ok=True)
        write_tracks_csv(
            os.path.join(ds_dir, "2023_spotify_ds1.csv"),
            table_with_metadata(random_baskets(
                rng, n_playlists=50, n_tracks=20, mean_len=5
            )),
        )
        return MiningConfig(
            base_dir=base, datasets_dir=ds_dir, min_support=0.08,
            k_max_consequents=32, top_tracks_save_percentile=0.25,
            model_layout="sharded", prune_vocab_threshold=10_000,
            # the sharded ALS rides the same mesh through the crash too
            embed_enabled=True, als_rank=8, als_iters=3,
        )

    def test_sharded_mine_crash_resume_bit_identical(self, tmp_path):
        """ISSUE 7 chaos acceptance: a vocab-sharded mine killed right
        after the mine phase's checkpoint resumes to bit-identical
        artifacts (embeddings included — the sharded ALS factors are in
        the manifest's sha256s)."""
        from kmlserver_tpu.io import artifacts

        ref_cfg = self._make_pvc(str(tmp_path / "ref"))
        run_mining_job(ref_cfg)
        ref_bytes = _artifact_bytes(ref_cfg)
        ref_manifest = artifacts.load_manifest(ref_cfg.pickles_dir)["files"]

        cfg = self._make_pvc(str(tmp_path / "int"))
        faults.inject("mine.crash.mine", times=1)
        with pytest.raises(faults.FaultInjected):
            run_mining_job(cfg)
        faults.clear()
        summary = run_mining_job(cfg)
        assert summary.resumed_phases == ("encode", "mine")
        assert _artifact_bytes(cfg) == ref_bytes
        assert artifacts.load_manifest(cfg.pickles_dir)["files"] == \
            ref_manifest
