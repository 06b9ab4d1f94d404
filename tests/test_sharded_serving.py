"""The vocabulary-sharded deployment, judged by the plain reference.

A seeded random catalog at a small size is published through the
program's own publication path (``benchmark.generators.catalog``), loaded
by an engine configured from the environment (``KMLS_MODEL_LAYOUT=sharded``
on four host devices, rules only), and every answer served through the
engine's normal path is held to ``benchmark.reference``: numpy over the
generator's arrays, which knows nothing of shards. Ids and order up to
ties, confidences bitwise.
"""

import numpy as np
import pytest

from benchmark import reference
from benchmark.generators import catalog
from kmlserver_tpu.config import ServingConfig
from kmlserver_tpu.serving.engine import RecommendEngine
from kmlserver_tpu.serving.metrics import ServingMetrics

K_BEST = 10
MAX_SEEDS = 128
PARAMS = {
    "k_max": 16, "n_playlists": 1000000, "head_count": 400,
    "zipf_exponent": 1.0, "zipf_shift": 5.0, "fill_divisor": 4,
    "top_conf_lo": 0.05, "top_conf_hi": 0.9, "slot_decay": 0.7,
    "slot_jitter": 0.15, "min_confidence": 0.04, "min_support": 2e-06,
    "confidence_mode": "confidence", "name_prefix": "t", "name_digits": 7,
    "popular_tracks_kept": 30, "embedding_rank": 0,
}
# min_support 2e-6 of 1,000,000 playlists: a track counted 2 or 3 times is a
# rule key with an empty row (fill = count // 4), one counted once is no key
# (tracks, seed): the first two vocabularies do not divide by four, so the
# last shard holds padding rows; the third does, and is placed as loaded
CATALOGS = [(2999, 11), (1201, 2147483659), (1600, 7)]


@pytest.fixture(scope="module", params=CATALOGS, ids=lambda c: f"v{c[0]}-seed{c[1]}")
def served(request, tmp_path_factory):
    """→ (catalog, reference, engine, captured confidences) of one
    published generation under the sharded layout."""
    n_tracks, seed = request.param
    params = dict(PARAMS, n_tracks=n_tracks)
    cat = catalog.build(params, seed)
    base = str(tmp_path_factory.mktemp(f"gen{n_tracks}"))
    catalog.publish(cat, params, base, log=lambda msg: None)
    mp = pytest.MonkeyPatch()
    mp.setenv("BASE_DIR", base)
    mp.setenv("KMLS_MODEL_LAYOUT", "sharded")
    mp.setenv("KMLS_SERVE_DEVICES", "4")
    mp.setenv("KMLS_HYBRID_MODE", "rules")
    try:
        engine = RecommendEngine(ServingConfig.from_env(dotenv_path=None))
    finally:
        mp.undo()
    assert engine.load()
    assert engine.model_layout == "sharded" and engine.n_shards == 4
    # the confidences a batch's answers were composed from, as the engine
    # picked them up from the device
    picked = []
    compose = engine._compose_answer

    def spy(bundle, seeds, rule_known, ids_row, confs_row, emb_row):
        picked.append((np.array(ids_row), np.array(confs_row)))
        return compose(bundle, seeds, rule_known, ids_row, confs_row, emb_row)

    engine._compose_answer = spy
    ref = reference.Reference(
        cat, {"k_best": K_BEST, "blend_weight": 0.0}, MAX_SEEDS
    )
    return cat, ref, engine, picked


def serve_and_judge(served, seed_sets):
    """Serve ``seed_sets`` (track ids) as one batch and hold every answer
    to the reference → the answers' sources."""
    cat, ref, engine, picked = served
    del picked[:]
    answers = engine.recommend_many_async(
        [[cat.names[i] for i in s] for s in seed_sets]
    )()
    assert engine.unwarmed_dispatches == 0
    assert len(picked) == len(seed_sets)
    for seeds, (songs, source), (ids_row, confs_row) in zip(seed_sets, answers, picked):
        ids, confs = ref.rule_scores(np.asarray(seeds, dtype=np.int64))
        want = np.sort(confs)[::-1][:K_BEST]
        if source == "fallback":  # popular tracks: no seed is a rule key
            assert not cat.known[list(seeds)].any() and not confs_row.any()
            continue
        got_ids = [cat.name_to_id[s] for s in songs]
        assert got_ids == [int(i) for i in ids_row if i >= 0]
        assert len(set(got_ids)) == len(got_ids) == len(want)
        # the confidences bitwise, in descending order
        assert confs_row[: len(want)].view(np.int32).tolist() == \
            want.view(np.int32).tolist()
        assert not confs_row[len(want):].any()
        # each served track holds the confidence of its rank: the order
        # up to ties
        conf_of = dict(zip(ids.tolist(), confs))
        assert [conf_of[i] for i in got_ids] == want.tolist()
    return [source for _, source in answers]


def with_row(cat):
    return np.flatnonzero(cat.live > 0)


def test_seed_sets_across_the_shards(served):
    cat = served[0]
    rng = np.random.default_rng(5)
    rows = with_row(cat)
    sets = [rng.choice(rows, size=n, replace=False).tolist() for n in (1, 5, 10, 25, 100)]
    assert set(serve_and_judge(served, sets)) == {"rules"}


@pytest.mark.parametrize("shard", [0, 3])
def test_seed_sets_that_fall_in_one_shard(served, shard):
    cat, _, engine, _ = served
    size = engine.bundle.shard_size
    rows = with_row(cat)
    inside = rows[(rows >= shard * size) & (rows < (shard + 1) * size)]
    before = list(engine.shard_dispatch_counts) or [0] * 4
    sets = [inside[:3].tolist(), inside[3:20].tolist(), inside[-1:].tolist()]
    assert set(serve_and_judge(served, sets)) == {"rules"}
    delta = [a - b for a, b in zip(engine.shard_dispatch_counts, before)]
    assert delta[shard] == sum(len(s) for s in sets)
    assert sum(delta) == delta[shard]


def test_seeds_with_no_rule_row(served):
    cat = served[0]
    bare = np.flatnonzero((cat.live == 0) & cat.known)
    assert len(bare) > 8
    unknown = np.flatnonzero(~cat.known)
    assert len(unknown) > 2
    # rule keys whose rows are empty: an empty answer, not a fallback; beside
    # a seed with a row they, and tracks that are no rule key, add nothing;
    # tracks that are no key alone: the fallback
    sets = [bare[:1].tolist(), bare[1:8].tolist(),
            [int(with_row(cat)[0]), *bare[8:12].tolist(), int(unknown[0])],
            unknown[1:3].tolist()]
    assert serve_and_judge(served, sets) == ["empty", "empty", "rules", "fallback"]


def test_a_batch_mixing_seed_lengths_1_and_128(served):
    cat = served[0]
    rng = np.random.default_rng(7)
    pool = np.arange(len(cat.names))
    sets = []
    for n in (1, 128, 1, 128, 128, 1):
        s = rng.choice(pool, size=n, replace=False).tolist()
        s[0] = int(rng.choice(with_row(cat)))  # no set falls back
        sets.append(list(dict.fromkeys(s)))
    sources = serve_and_judge(served, sets)
    assert sources.count("rules") >= 3 and "fallback" not in sources


def test_placement_gauges_and_module_name(served):
    cat, _, engine, _ = served
    bundle = engine.bundle
    seconds, resident = engine.shard_placement()
    rows = -(-len(cat.names) // 4)
    assert bundle.shard_size == rows and bundle.rule_ids.shape[0] == 4 * rows
    assert seconds > 0
    assert resident == (rows * PARAMS["k_max"] * 8,) * 4
    text = ServingMetrics().render(
        engine.reload_counter, True,
        shard_counts=engine.shard_dispatch_counts,
        shard_placement=engine.shard_placement(),
    )
    assert f"kmls_shard_place_seconds {seconds:.6f}" in text
    for shard in range(4):
        assert f'kmls_shard_resident_bytes{{shard="{shard}"}} {resident[0]}' in text
    # the device trace finds the sharded lookup under the replicated one's
    # name: benchmark/metrics/rules_kernel_ms.json looks for "recommend_batch"
    seeds = np.full((1, 1), -1, np.int32)
    lowered = bundle.shard_kernel.lower(bundle.rule_ids, bundle.rule_confs, seeds)
    assert "module @jit__recommend_batch_sharded " in lowered.as_text()


def test_no_placement_series_outside_the_sharded_layout():
    text = ServingMetrics().render(0, True)
    assert "kmls_shard_place_seconds" not in text
    assert "kmls_shard_resident_bytes" not in text


@pytest.mark.parametrize("mode", ["confidence", "support"])
def test_confidences_by_blocks_equal_the_whole_tables(mode):
    """``derive_confs`` walks the table a block of rows at a time; element
    for element it is the whole table's float64 division, then float32."""
    from kmlserver_tpu.ops import rules

    rng = np.random.default_rng(3)
    rows = 2 * rules._CONF_BLOCK_ROWS + 77  # three blocks, the last ragged
    item_counts = rng.integers(0, 50000, rows).astype(np.int32)
    rule_counts = (rng.random((rows, 8)) * item_counts[:, None]).astype(np.int32)
    got = rules.derive_confs(rule_counts, item_counts, 1000000, mode)
    if mode == "support":
        want = (rule_counts.astype(np.float64) / 1000000).astype(np.float32)
    else:
        denom = np.maximum(item_counts, 1)[:, None].astype(np.float64)
        want = (rule_counts / denom).astype(np.float32)
    assert got.dtype == np.float32 and got.shape == want.shape
    assert got.view(np.int32).tolist() == want.view(np.int32).tolist()


def test_a_rule_without_a_count_is_found_in_any_block(tmp_path):
    """The stripped-artifact check reads the tables a block of rows at a
    time too: a rule with a zero count in the last rows still refuses the
    load, and the artifact loads back what was saved."""
    from kmlserver_tpu.io import artifacts

    rows, k = (1 << 14) + 9, 4
    rule_ids = np.full((rows, k), -1, np.int32)
    rule_counts = np.zeros((rows, k), np.int32)
    rule_ids[5, 0], rule_counts[5, 0] = 7, 3
    rule_ids[rows - 2, 0], rule_counts[rows - 2, 0] = 1, 2
    saved = dict(
        vocab=[f"t{i}" for i in range(rows)], rule_ids=rule_ids,
        rule_counts=rule_counts, item_counts=np.full(rows, 4, np.int32),
        n_playlists=100, min_support=0.01, mode="confidence",
    )
    path = str(tmp_path / "rules.npz")
    artifacts.save_rule_tensors(path, **saved)
    loaded = artifacts.load_rule_tensors(path)
    assert np.array_equal(loaded["rule_ids"], rule_ids)
    assert loaded["rule_confs"][rows - 2, 0] == np.float32(0.5)
    rule_counts[rows - 2, 0] = 0  # a rule that lost its count
    artifacts.save_rule_tensors(path, **saved)
    with pytest.raises(ValueError, match="zero counts"):
        artifacts.load_rule_tensors(path)
