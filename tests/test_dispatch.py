"""The engine's one dispatch path (ISSUE 35): ``recommend`` is a batch
of one, the jitted batch path answers what the brute-force oracle
answers at every warmed bucket, and the local and mesh layouts run the
same stage → dispatch → fetch → compose skeleton — same spans, same
attributes, one ``replica.kernel`` fault hook a batch."""

import dataclasses
import glob
import json
import os
import time

import numpy as np
import pytest

from kmlserver_tpu import faults
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.io import artifacts
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.observability.trace import SpanRecorder
from kmlserver_tpu.serving.engine import RecommendEngine

from .oracle import random_baskets, reference_recommend
from .test_mesh import _gang_ports, _shutdown
from .test_mesh import gang_pair, mesh_pvc  # noqa: F401  (fixture re-export)
from .test_pipeline import table_with_metadata

MAX_SEEDS = 8  # max_seed_tracks of the engines below: length buckets {1, 8}
LAYOUTS = ("replicated", "sharded")
MODES = ("rules", "embed", "blend")
SEED_SETS = ("all_known", "some_unknown", "none_known", "over_cap")


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _mine(base_dir: str, rng, **kw) -> str:
    from kmlserver_tpu.data.csv import write_tracks_csv

    ds_dir = os.path.join(base_dir, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds1.csv"),
        table_with_metadata(random_baskets(
            rng, n_playlists=60, n_tracks=24, mean_len=5
        )),
    )
    run_mining_job(MiningConfig(
        base_dir=base_dir, datasets_dir=ds_dir, min_support=0.12,
        k_max_consequents=16, top_tracks_save_percentile=0.3, **kw,
    ))
    return base_dir


@pytest.fixture(scope="module")
def hybrid_pvc(tmp_path_factory) -> str:
    """ONE mined PVC with both model families published."""
    return _mine(
        str(tmp_path_factory.mktemp("dispatch-hybrid")),
        np.random.default_rng(2),
        embed_enabled=True, als_rank=8, als_iters=3,
    )


@pytest.fixture(scope="module")
def hybrid_engines(hybrid_pvc):
    """(layout, hybrid_mode) → a loaded engine over the hybrid PVC; built
    on first use, kept for the module (a load warms every bucket of both
    kernels)."""
    base = hybrid_pvc
    engines: dict = {}

    def get(layout: str, mode: str) -> RecommendEngine:
        if (layout, mode) not in engines:
            engine = RecommendEngine(ServingConfig(
                base_dir=base, k_best_tracks=5, hybrid_mode=mode,
                model_layout=layout,
                serve_devices=4 if layout == "sharded" else 1,
                batch_max_size=4, max_seed_tracks=MAX_SEEDS,
            ))
            assert engine.load()
            assert engine.model_layout == layout
            assert engine.embedding_active == (mode != "rules")
            engines[(layout, mode)] = engine
        return engines[(layout, mode)]

    return get


def _seed_set(bundle, kind: str) -> list[str]:
    known = [s for s in bundle.vocab if bundle.known_mask[bundle.index[s]]]
    if kind == "all_known":
        return known[:3]
    if kind == "some_unknown":
        # one name no family knows, and (where the catalog has one) a
        # cold-start name only the embedding vocabulary knows
        cold = [
            n for n in bundle.emb_vocab or ()
            if n not in bundle.index or not bundle.known_mask[bundle.index[n]]
        ]
        return [known[0], "unknown-zz"] + cold[:1] + [known[1]]
    if kind == "none_known":
        return ["unknown-a", "unknown-b"]
    assert kind == "over_cap"
    # more seeds than max_seed_tracks, the first unknown: the known ones
    # past the cap must be cut the same way on every path
    seeds = ["unknown-zz"] + (known * 3)[: MAX_SEEDS + 4]
    assert len(seeds) > MAX_SEEDS
    return seeds


class TestRecommendIsABatchOfOne:
    @pytest.mark.parametrize("kind", SEED_SETS)
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_single_equals_batch_row(self, hybrid_engines, layout, mode, kind):
        """``recommend(s)`` is ``recommend_many([s])[0]``, answers and
        sources — and both are the row ``s`` gets inside a mixed batch,
        whose (rows, length) bucket differs: padding slots are inert."""
        engine = hybrid_engines(layout, mode)
        seeds = _seed_set(engine.bundle, kind)
        single = engine.recommend(seeds)
        assert single == engine.recommend_many([seeds])[0]
        mixed = [_seed_set(engine.bundle, k) for k in SEED_SETS]
        assert engine.recommend_many(mixed)[SEED_SETS.index(kind)] == single
        songs, source = single
        if kind == "none_known":
            assert source == "fallback"
            assert songs == engine.static_recommendation(seeds)
        elif mode == "rules":
            assert source in ("rules", "empty")
        elif mode == "embed":
            assert source == "embed"
        else:
            assert source == "hybrid"
        assert engine.unwarmed_dispatches == 0


@pytest.fixture(scope="module")
def rules_engine(tmp_path_factory):
    """A default-config engine (every default bucket warmed) over a
    rules-only PVC, with the published rules dict the oracle reads."""
    base = _mine(
        str(tmp_path_factory.mktemp("dispatch-rules")),
        np.random.default_rng(5),
    )
    cfg = ServingConfig(base_dir=base, k_best_tracks=5)
    engine = RecommendEngine(cfg)
    assert engine.load()
    rules = artifacts.load_pickle(
        os.path.join(cfg.pickles_dir, cfg.recommendations_file)
    )
    return engine, rules


# the default bucket grid (config.py: batch_max_size 32, max_seed_tracks
# 128), spelled out so that each bucket is its own case
BATCH_BUCKETS = (1, 2, 4, 8, 16, 32)
LEN_BUCKETS = (1, 8, 32, 128)


class TestBatchPathAgainstOracle:
    def test_grid_is_the_warmed_grid(self, rules_engine):
        engine, _ = rules_engine
        assert tuple(engine._batch_buckets()) == BATCH_BUCKETS
        assert tuple(engine._len_buckets()) == LEN_BUCKETS
        assert engine.bundle.warmed_shapes == {
            (b, n) for b in BATCH_BUCKETS for n in LEN_BUCKETS
        }

    @pytest.mark.parametrize("length", LEN_BUCKETS)
    @pytest.mark.parametrize("batch", BATCH_BUCKETS)
    def test_bucket_matches_brute_force(self, rules_engine, batch, length):
        """A full batch at this (batch, length) bucket through the jitted
        kernel answers what the dict max-merge answers: the same
        confidences in the same order (ties may order names either
        way), every name above the cut present."""
        engine, rules = rules_engine
        k = engine.cfg.k_best_tracks
        names = sorted(rules) + ["unknown-x", "unknown-y"]
        rng = np.random.default_rng(batch * 1000 + length)
        sets = []
        for row in range(batch):
            # the first row is as long as the bucket, so the batch
            # lands in it; the others are any length up to it
            n = length if row == 0 else int(rng.integers(1, length + 1))
            sets.append([names[i] for i in rng.integers(0, len(names), n)])
        staged = engine.seed_slots_real + engine.seed_slots_padded
        got = engine.recommend_many(sets)
        assert (
            engine.seed_slots_real + engine.seed_slots_padded - staged
            == batch * length
        )
        assert engine.unwarmed_dispatches == 0
        for seeds, (songs, source) in zip(sets, got):
            known = [s for s in seeds if s in rules]
            if not known:
                assert source == "fallback"
                assert songs == engine.static_recommendation(seeds)
                continue
            merged = dict(reference_recommend(rules, known, 10**6))
            expected = reference_recommend(rules, known, k)
            assert source == ("rules" if expected else "empty")
            assert len(set(songs)) == len(songs) == len(expected)
            assert [np.float32(merged[n]) for n in songs] == [
                np.float32(c) for _, c in expected
            ]
            if expected:
                cut = np.float32(expected[-1][1])
                assert {
                    n for n, c in merged.items() if np.float32(c) > cut
                } <= set(songs)


def _span_shape(trace) -> list[tuple[str, tuple]]:
    """(name, attribute keys) of each span, in the order they began (by
    id: a reserved parent is recorded after its children)."""
    return [
        (name, tuple(sorted(attrs or ())))
        for _id, _parent, name, _t0, _t1, attrs in sorted(trace.spans)
    ]


# a rules-only batch's spans in the order they begin; a hybrid one
# launches the embedding family first, at the front of stage, and picks
# its pair up first
RULE_BATCH = [
    "stage", "fill_rules", "put_rules", "dispatch", "enqueue_rules",
    "handoff", "fetch_rules", "compose",
]
HYBRID_BATCH = [
    "stage", "fill_embed", "put_embed", "enqueue_embed", "fill_rules",
    "put_rules", "dispatch", "enqueue_rules", "handoff", "fetch_embed",
    "fetch_rules", "compose",
]


def _traced_batch(engine, seed_sets, monkeypatch):
    """Run one traced batch → (answers, its trace, how often the
    ``replica.kernel`` fault hook fired)."""
    fired = []
    real_fire = faults.fire

    def counting_fire(site, **kw):
        fired.append(site)
        return real_fire(site, **kw)

    recorder = SpanRecorder(sample=1.0)
    trace = recorder.begin_batch(time.perf_counter(), requests=len(seed_sets))
    with monkeypatch.context() as patched:
        patched.setattr(faults, "fire", counting_fire)
        out = engine.recommend_many_async(seed_sets, trace=trace)()
    return out, trace, fired.count("replica.kernel")


class TestOneSkeletonAcrossLayouts:
    def test_local_and_mesh_record_the_same_spans(
        self, mesh_pvc, gang_pair, monkeypatch  # noqa: F811
    ):
        _, baskets = mesh_pvc
        reference, members = gang_pair
        seed_sets = [baskets[0][:3], ["definitely-not-a-track"], baskets[1][:2]]
        local_out, local_trace, local_fired = _traced_batch(
            reference, seed_sets, monkeypatch
        )
        assert reference.model_layout == "replicated"
        assert [n for n, _ in _span_shape(local_trace)] == RULE_BATCH
        assert local_fired == 1
        for member in members:
            assert member.model_layout == "mesh"
            out, trace, fired = _traced_batch(member, seed_sets, monkeypatch)
            assert out == local_out
            assert _span_shape(trace) == _span_shape(local_trace)
            assert trace.attrs.keys() == local_trace.attrs.keys()
            for key in ("rows", "length", "seeds_real"):
                assert trace.attrs[key] == local_trace.attrs[key]
            assert fired == 1

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_hybrid_batch_adds_fetch_embed(
        self, hybrid_engines, layout, monkeypatch
    ):
        engine = hybrid_engines(layout, "blend")
        sets = [_seed_set(engine.bundle, k) for k in SEED_SETS]
        _, trace, fired = _traced_batch(engine, sets, monkeypatch)
        assert [n for n, _ in _span_shape(trace)] == HYBRID_BATCH
        assert trace.attrs["rows"] == 4 and trace.attrs["length"] == MAX_SEEDS
        assert fired == 1

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_put_spans_count_the_bytes_and_the_devices_they_go_to(
        self, hybrid_engines, layout, monkeypatch
    ):
        """The vocabulary-sharded layout places the rule seeds on every
        device of its mesh; the embedding seeds go to one device."""
        engine = hybrid_engines(layout, "blend")
        sets = [_seed_set(engine.bundle, k) for k in SEED_SETS]
        _, trace, _ = _traced_batch(engine, sets, monkeypatch)
        attrs = {name: a for _id, _parent, name, _t0, _t1, a in trace.spans}
        size = trace.attrs["rows"] * trace.attrs["length"] * 4  # int32 ids
        devices = 4 if layout == "sharded" else 1
        assert attrs["put_rules"] == {"bytes": size, "devices": devices}
        assert attrs["put_embed"] == {"bytes": size, "devices": 1}

    def test_fallback_before_first_load_composes_only(self, tmp_path, monkeypatch):
        engine = RecommendEngine(ServingConfig(base_dir=str(tmp_path)))
        monkeypatch.setattr(engine, "reload_if_required", lambda: None)
        out, trace, fired = _traced_batch(engine, [["a"], ["b"]], monkeypatch)
        assert [src for _, src in out] == ["fallback", "fallback"]
        assert [n for n, _ in _span_shape(trace)] == ["handoff", "compose"]
        assert fired == 0
        assert engine.recommend(["a"]) == out[0]


def _launch_log(engine, seed_sets, monkeypatch) -> list[str]:
    """Run one batch with the calls that launch its programs logged, in
    the order this thread made them: ``put`` a seed transfer, ``rules``
    the rule program's enqueue (``fanout`` the mesh layout's peer fan-out
    before it), ``embed`` the embedding program's, ``copies:<n>`` n
    results' copies to the host started, ``|`` the dispatch returned.
    Other threads (a gang peer serving the fan-out) are left out."""
    import threading

    import jax

    from kmlserver_tpu.ops import serve as serve_ops
    from kmlserver_tpu.serving import engine as engine_mod

    log: list[str] = []
    me = threading.current_thread()

    def logged(name, fn):
        def call(*args, **kw):
            if threading.current_thread() is me:
                log.append(name(*args) if callable(name) else name)
            return fn(*args, **kw)

        return call

    with monkeypatch.context() as patched:
        patched.setattr(jax, "device_put", logged("put", jax.device_put))
        patched.setattr(
            engine_mod, "embed_topk", logged("embed", engine_mod.embed_topk)
        )
        patched.setattr(engine_mod, "_start_host_copies", logged(
            lambda *arrays: f"copies:{len(arrays)}",
            engine_mod._start_host_copies,
        ))
        patched.setattr(engine, "_kernel", logged("rules", engine._kernel))
        for bundle in engine.replicas:
            if bundle.shard_kernel is not None:
                patched.setattr(
                    bundle, "shard_kernel", logged("rules", bundle.shard_kernel)
                )
        patched.setattr(serve_ops, "shard_partial_topk", logged(
            "rules", serve_ops.shard_partial_topk
        ))
        if engine.mesh_coordinator is not None:
            coordinator = engine.mesh_coordinator
            patched.setattr(coordinator, "fetch_partials", logged(
                "fanout", coordinator.fetch_partials
            ))
        finish = engine.recommend_many_async(seed_sets)
        log.append("|")
        assert len(finish()) == len(seed_sets)
    return log


@pytest.fixture
def hybrid_gang(hybrid_pvc):
    """Two gang members (the mesh layout) over the hybrid PVC, both
    families published."""
    port = _gang_ports()
    members = []
    try:
        for rank in range(2):
            engine = RecommendEngine(ServingConfig(
                base_dir=hybrid_pvc, k_best_tracks=5, hybrid_mode="blend",
                batch_max_size=4, max_seed_tracks=MAX_SEEDS,
                serve_gang_coordinator=f"127.0.0.1:{port}",
                serve_gang_size=2, serve_gang_rank=rank,
                serve_gang_port=port + rank,
            ))
            members.append(engine)
            assert engine.load()
        yield members
    finally:
        for engine in members:
            _shutdown(engine)


class _PowerClock:
    """``time`` with ``perf_counter`` reading 2**k at its k-th call: the
    difference of two readings names the two calls it spans."""

    def __init__(self):
        self.calls = 0

    def perf_counter(self) -> float:
        self.calls += 1
        return float(2 ** self.calls)

    def __getattr__(self, name):
        return getattr(time, name)


def _ticks(interval: float) -> tuple[int, int]:
    """The calls ``(i, j)`` of a ``_PowerClock`` interval 2**j - 2**i."""
    n = int(interval)
    assert n == interval and n > 0, interval
    i = (n & -n).bit_length() - 1
    return i, i + (n >> i).bit_length()


class TestLaunchOrder:
    """A hybrid batch launches the embedding program first, so the rule
    family's host work runs while the chip scores the catalog; a
    rules-only batch, and the mesh layout's, launch as before."""

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_hybrid_batch_enqueues_the_embedding_before_the_rule_seeds_go(
        self, hybrid_engines, layout, monkeypatch
    ):
        engine = hybrid_engines(layout, "blend")
        sets = [_seed_set(engine.bundle, k) for k in SEED_SETS]
        assert _launch_log(engine, sets, monkeypatch) == [
            "put", "embed", "copies:2", "put", "rules", "copies:2", "|",
        ]

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_rules_only_batch_launches_as_before(
        self, hybrid_engines, layout, monkeypatch
    ):
        engine = hybrid_engines(layout, "rules")
        sets = [_seed_set(engine.bundle, k) for k in SEED_SETS]
        assert _launch_log(engine, sets, monkeypatch) == [
            "put", "rules", "copies:2", "|",
        ]

    def test_mesh_batch_fans_out_to_its_peers_before_the_embedding(
        self, hybrid_gang, monkeypatch
    ):
        member = hybrid_gang[0]
        assert member.model_layout == "mesh" and member.embedding_active
        sets = [_seed_set(member.bundle, k) for k in SEED_SETS]
        assert _launch_log(member, sets, monkeypatch) == [
            "put", "fanout", "rules", "copies:2", "put", "embed", "copies:2",
            "|",
        ]

    @pytest.mark.parametrize("batch", [1, 3])
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_answers_equal_the_rules_first_order(
        self, hybrid_engines, layout, batch, monkeypatch
    ):
        """Launched either way, a batch composes from the same rule ids
        and confidences, the same embedding ids and similarities, into
        the same answers and sources."""
        engine = hybrid_engines(layout, "blend")
        assert engine._embed_goes_first(engine.bundle)
        kinds = ("all_known", "some_unknown", "over_cap")[:batch]
        sets = [_seed_set(engine.bundle, k) for k in kinds]

        def run():
            rows = []
            compose = engine._compose_answer

            def recorded(bundle, seeds, rule_known, ids, confs, emb_row):
                rows.append((rule_known, ids, confs, emb_row))
                return compose(bundle, seeds, rule_known, ids, confs, emb_row)

            with monkeypatch.context() as patched:
                patched.setattr(engine, "_compose_answer", recorded)
                return engine.recommend_many(sets), rows

        answers, rows = run()
        with monkeypatch.context() as patched:
            patched.setattr(engine, "_embed_goes_first", lambda bundle: False)
            want_answers, want_rows = run()
        assert answers == want_answers
        assert {src for _, src in answers} == {"hybrid"}
        assert len(rows) == len(want_rows) == batch
        for got, want in zip(rows, want_rows):
            assert got[0] == want[0]
            np.testing.assert_array_equal(got[1], want[1])
            np.testing.assert_array_equal(got[2], want[2])
            for g, w in zip(got[3], want[3], strict=True):
                np.testing.assert_array_equal(g, w)

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_each_program_is_billed_its_own_interval(
        self, hybrid_engines, layout, monkeypatch
    ):
        """The cost model bills the embedding lookup from its launch to
        its pick-up and the rule lookup from there to its own: one
        interval each, and they do not overlap."""
        from kmlserver_tpu.serving import engine as engine_mod

        engine = hybrid_engines(layout, "blend")
        assert engine.cost_model is not None
        sets = [_seed_set(engine.bundle, k) for k in SEED_SETS]
        billed = []
        with monkeypatch.context() as patched:
            patched.setattr(engine_mod, "time", _PowerClock())
            patched.setattr(
                engine.cost_model, "observe_kernel",
                lambda kernel, device_s, **dims: billed.append(
                    (kernel, device_s)
                ),
            )
            engine.recommend_many(sets)
        rules = "serve_sharded" if layout == "sharded" else "serve_rules"
        assert [kernel for kernel, _ in billed] == ["embed_topk", rules]
        (e0, e1), (r0, r1) = (_ticks(s) for _, s in billed)
        assert e0 < e1 <= r0 < r1


class TestFreshStagingArray:
    def test_host_seed_array_is_never_reused(self, rules_engine):
        """Every dispatch fills an array of its own: two same-shape
        dispatches in flight cannot see each other's seeds."""
        engine, rules = rules_engine
        known = [s for s, row in sorted(rules.items()) if row]
        bundle = engine.bundle
        a, _, _ = engine._stage_seeds(bundle, [[known[0]]], 1, 1)
        b, _, _ = engine._stage_seeds(bundle, [[known[1]]], 1, 1)
        assert a is not b and not np.shares_memory(a, b)
        assert a[0, 0] == bundle.index[known[0]]
        assert b[0, 0] == bundle.index[known[1]]


class TestFinishIsFreedWithItsBatch:
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_finish_names_no_cycle(self, hybrid_engines, layout):
        """A local layout's ``finish`` holds the batch's device results;
        it must go when the batcher drops it, not when the cycle
        collector next runs (on the chip a ``finish`` that named itself
        kept 0.14 MB of results alive; ``PERF.md`` §6, PR 35)."""
        import gc
        import weakref

        engine = hybrid_engines(layout, "blend")
        sets = [_seed_set(engine.bundle, k) for k in SEED_SETS]
        gc.collect()
        gc.disable()
        try:
            finish = engine.recommend_many_async(sets)
            ref = weakref.ref(finish)
            assert len(finish()) == len(sets)
            del finish
            assert ref() is None
        finally:
            gc.enable()


def test_unregistered_kmls_variables_in_benchmark_configs_are_inert(
    tmp_path, monkeypatch
):
    """The benchmark's configuration files (which this tree may not
    edit) still set ``KMLS_NATIVE_SERVE=0``, the knob that selected the
    retired CPU-native serve stack: a ``KMLS_*`` variable no knob
    registers is read nowhere — not by the configuration the server
    builds from its environment, not by any module of the package — and
    no ``kmls-verify`` checker reads those files."""
    from kmlserver_tpu.analysis.core import AnalysisConfig
    from kmlserver_tpu.config import KNOB_REGISTRY

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = {"KMLS_NATIVE_SERVE"}
    for pattern in ("benchmark/configs/*.json",
                    "benchmark/tests/later_pr/configs/*.json"):
        for path in glob.glob(os.path.join(repo, pattern)):
            with open(path) as f:
                server = json.load(f).get("server", {})
            for section in ("env", "smoke_env"):
                names |= {
                    k for k in server.get(section, {}) if k.startswith("KMLS_")
                }
    names -= set(KNOB_REGISTRY)
    assert "KMLS_NATIVE_SERVE" in names  # the registry is one entry shorter
    monkeypatch.setenv("BASE_DIR", str(tmp_path))
    plain = dataclasses.asdict(ServingConfig.from_env())
    for name in names:
        monkeypatch.setenv(name, "0")
    assert dataclasses.asdict(ServingConfig.from_env()) == plain
    for dirpath, _dirs, files in os.walk(os.path.join(repo, "kmlserver_tpu")):
        for fname in files:
            if fname.endswith(".py"):
                with open(os.path.join(dirpath, fname)) as f:
                    source = f.read()
                assert not [n for n in names if n in source], fname
    cfg = AnalysisConfig()
    scanned = (cfg.package_dir, *cfg.extra_code, cfg.tests_dir, cfg.readme,
               *cfg.manifest_files)
    assert not [path for path in scanned if path.startswith("benchmark")]
