"""Tests for the env contract, dotenv parsing, artifact I/O, and the
dataset-registry / history-rotation / invalidation-token state machine
(reference behaviors: machine-learning/main.py:315-411)."""

import os

import numpy as np
import pytest

from kmlserver_tpu.config import BASE_INDEX, MiningConfig, ServingConfig
from kmlserver_tpu.io import artifacts, registry
from kmlserver_tpu.utils.envfile import load_dotenv, parse_env_line


class TestEnvFile:
    def test_parse_basic(self):
        assert parse_env_line("FOO=bar") == ("FOO", "bar")
        assert parse_env_line("export FOO=bar") == ("FOO", "bar")
        assert parse_env_line('FOO="bar baz"') == ("FOO", "bar baz")
        assert parse_env_line("FOO=bar # comment") == ("FOO", "bar")
        assert parse_env_line('FOO="/data/api" # prod path') == ("FOO", "/data/api")
        assert parse_env_line("FOO='x y' # c") == ("FOO", "x y")
        assert parse_env_line("# comment") is None
        assert parse_env_line("") is None
        assert parse_env_line("NOEQUALS") is None

    def test_load_no_override(self, tmp_path, monkeypatch):
        envf = tmp_path / ".env"
        envf.write_text("A=1\nB=2\n")
        monkeypatch.setenv("A", "keep")
        monkeypatch.delenv("B", raising=False)
        load_dotenv(envf)
        assert os.environ["A"] == "keep"
        assert os.environ["B"] == "2"

    def test_load_missing_file(self, tmp_path):
        assert load_dotenv(tmp_path / "nope.env") == {}


class TestConfig:
    def test_mining_env_contract(self, monkeypatch, tmp_path):
        # names bound by kubernetes/job.yaml:24-40 in the reference
        monkeypatch.setenv("BASE_DIR", str(tmp_path))
        monkeypatch.setenv("MIN_SUPPORT", "0.07")
        monkeypatch.setenv("REGEX_FILENAME", "ds*.csv")
        monkeypatch.setenv("TOP_TRACKS_SAVE_PERCENTILE", "0.1")
        cfg = MiningConfig.from_env(dotenv_path=None)
        assert cfg.base_dir == str(tmp_path)
        assert cfg.min_support == 0.07
        assert cfg.regex_filename == "ds*.csv"
        assert cfg.top_tracks_save_percentile == 0.1
        assert cfg.datasets_dir == os.path.join(str(tmp_path), "datasets")
        assert cfg.pickles_dir == os.path.join(str(tmp_path), "pickles")

    def test_serving_env_contract(self, monkeypatch):
        # names bound by kubernetes/deployment.yaml:33-53 in the reference
        monkeypatch.setenv("VERSION", "V9")
        monkeypatch.setenv("K_BEST_TRACKS", "7")
        monkeypatch.setenv("POLLING_WAIT_IN_MINUTES", "1")
        cfg = ServingConfig.from_env(dotenv_path=None)
        assert cfg.version == "V9"
        assert cfg.k_best_tracks == 7
        assert cfg.polling_wait_in_minutes == 1.0

    def test_tpu_rebuild_knob_env_contract(self, monkeypatch):
        # the KMLS_* knobs added by the rebuild must parse from env too
        monkeypatch.setenv("KMLS_NATIVE_PAIR_COUNTS", "0")
        mining = MiningConfig.from_env(dotenv_path=None)
        assert mining.native_cpu_pair_counts is False
        monkeypatch.setenv("KMLS_BATCH_MAX_INFLIGHT", "2")
        serving = ServingConfig.from_env(dotenv_path=None)
        assert serving.batch_max_inflight == 2

    @pytest.fixture
    def jax_config_writes(self, monkeypatch):
        """Record every ``jax.config.update`` instead of applying it, so
        the helper's writes are observable and the pytest process keeps
        its own compile-cache settings."""
        import jax

        writes: list[tuple[str, object]] = []
        monkeypatch.setattr(
            jax.config, "update", lambda name, value: writes.append((name, value))
        )
        return writes

    def test_cache_dir_placed_from_outside_is_left_to_jax(
        self, monkeypatch, tmp_path, jax_config_writes
    ):
        """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself — the helper
        writes no directory of its own, only the one storage threshold."""
        from kmlserver_tpu.utils import jaxcache

        cache = tmp_path / "placed"
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
        assert jaxcache.enable_compilation_cache() == str(cache)
        assert cache.is_dir()
        assert jax_config_writes == [(
            "jax_persistent_cache_min_compile_time_secs",
            jaxcache.MIN_COMPILE_TIME_S,
        )]
        assert jaxcache.child_env()["JAX_COMPILATION_CACHE_DIR"] == str(cache)

    def test_unset_cache_dir_is_one_fixed_in_checkout_path(
        self, monkeypatch, tmp_path, jax_config_writes
    ):
        from kmlserver_tpu.utils import jaxcache

        monkeypatch.setattr(jaxcache, "DEFAULT_CACHE_DIR", str(tmp_path / "c"))
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            assert jaxcache.enable_compilation_cache() == str(tmp_path / "c")
            # exported, so children inherit the same directory
            assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "c")
            assert (
                "jax_compilation_cache_dir", str(tmp_path / "c")
            ) in jax_config_writes
        finally:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

    def test_default_cache_path_is_identical_across_processes(self, monkeypatch):
        """No temp name, pid or time in the path: the directory is part
        of where a cached executable is looked up, so one that moves
        never hits."""
        import subprocess
        import sys

        from kmlserver_tpu.utils import jaxcache

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jaxcache.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        env = {
            k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"
        }
        env["PYTHONPATH"] = repo
        code = (
            "from kmlserver_tpu.utils import jaxcache; "
            "print(jaxcache.cache_dir()); "
            "print(jaxcache.child_env()['JAX_COMPILATION_CACHE_DIR'])"
        )
        seen = {
            subprocess.run(
                [sys.executable, "-c", code], env=env, cwd=cwd, check=True,
                capture_output=True, text=True,
            ).stdout
            for cwd in (repo, os.path.join(repo, "tests"))
        }
        assert seen == {f"{jaxcache.DEFAULT_CACHE_DIR}\n" * 2}

    def test_compilation_cache_failure_is_soft(
        self, monkeypatch, tmp_path, jax_config_writes
    ):
        # a mis-mounted cache path must never take down the job/API…
        from kmlserver_tpu.utils import jaxcache

        blocker = tmp_path / "not-a-dir"
        blocker.write_text("file, not a directory")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(blocker / "cache"))
        assert jaxcache.enable_compilation_cache() is None  # logged, not raised
        assert jax_config_writes == []
        # …while a driver of children (bench.py, chip_smoke.py) gets the error
        with pytest.raises(OSError):
            jaxcache.child_env()

    def test_bitpack_threshold_env_forms(self, monkeypatch):
        # default and "auto" -> HBM-fit dispatch; "none" disables bitpack;
        # an integer keeps the explicit element-count semantic
        assert MiningConfig.from_env(dotenv_path=None).bitpack_threshold_elems == "auto"
        monkeypatch.setenv("KMLS_BITPACK_THRESHOLD_ELEMS", "auto")
        assert MiningConfig.from_env(dotenv_path=None).bitpack_threshold_elems == "auto"
        monkeypatch.setenv("KMLS_BITPACK_THRESHOLD_ELEMS", "none")
        assert MiningConfig.from_env(dotenv_path=None).bitpack_threshold_elems is None
        monkeypatch.setenv("KMLS_BITPACK_THRESHOLD_ELEMS", "123456")
        assert MiningConfig.from_env(dotenv_path=None).bitpack_threshold_elems == 123456
        monkeypatch.setenv("KMLS_HBM_BUDGET_BYTES", str(1 << 30))
        assert MiningConfig.from_env(dotenv_path=None).hbm_budget_bytes == 1 << 30


class TestArtifacts:
    def test_pickle_roundtrip(self, tmp_path):
        path = str(tmp_path / "sub" / "x.pickle")
        obj = {"a": {"b": 0.5}}
        artifacts.save_pickle(obj, path)
        assert artifacts.load_pickle(path) == obj
        # no temp droppings
        assert sorted(os.listdir(tmp_path / "sub")) == ["x.pickle"]

    def test_rule_tensor_roundtrip(self, tmp_path):
        vocab = ["a", "b", "c"]
        rule_ids = np.array([[1, -1], [0, 2], [-1, -1]], dtype=np.int32)
        rule_counts = np.array([[2, 0], [2, 1], [0, 0]], dtype=np.int32)
        # c is frequent-but-partnerless (count 1 >= min_count 1): empty KEY
        item_counts = np.array([3, 2, 1], dtype=np.int32)
        path = str(tmp_path / "r.npz")
        artifacts.save_rule_tensors(
            path, vocab=vocab, rule_ids=rule_ids, rule_counts=rule_counts,
            item_counts=item_counts, n_playlists=4, min_support=0.25,
        )
        loaded = artifacts.load_rule_tensors(path)
        assert loaded["vocab"] == vocab
        np.testing.assert_array_equal(loaded["rule_ids"], rule_ids)
        np.testing.assert_array_equal(loaded["rule_counts"], rule_counts)
        np.testing.assert_allclose(loaded["rule_confs"][0, 0], 0.5)
        assert loaded["n_playlists"] == 4
        # expansion: confidences re-derived in float64, empty keys preserved
        d = artifacts.rules_dict_from_tensors(loaded)
        assert d == {"a": {"b": 0.5}, "b": {"a": 0.5, "c": 0.25}, "c": {}}

    def test_rule_tensor_roundtrip_explicit_confs(self, tmp_path):
        # triple-antecedent merge: confidences carry per-rule denominators
        # and must survive the npz verbatim, not be re-derived from counts
        vocab = ["a", "b", "c"]
        rule_ids = np.array([[1, 2], [0, -1], [-1, -1]], dtype=np.int32)
        rule_counts = np.zeros((3, 2), dtype=np.int32)
        confs64 = np.array([[0.75, 2 / 3], [0.4, 0.0], [0.0, 0.0]])
        item_counts = np.array([3, 2, 2], dtype=np.int32)
        path = str(tmp_path / "rc.npz")
        artifacts.save_rule_tensors(
            path, vocab=vocab, rule_ids=rule_ids, rule_counts=rule_counts,
            item_counts=item_counts, n_playlists=4, min_support=0.25,
            mode="confidence", min_confidence=0.1, rule_confs64=confs64,
        )
        loaded = artifacts.load_rule_tensors(path)
        np.testing.assert_array_equal(loaded["rule_confs64"], confs64)
        np.testing.assert_array_equal(
            loaded["rule_confs"], confs64.astype(np.float32)
        )
        d = artifacts.rules_dict_from_tensors(loaded)
        assert d == {"a": {"b": 0.75, "c": 2 / 3}, "b": {"a": 0.4}, "c": {}}

    def test_zero_count_rules_without_confs64_refused(self, tmp_path):
        # valid rule ids backed by zero counts and no rule_confs64 would
        # re-derive as all-0.0 confidences; the loader must refuse instead
        path = str(tmp_path / "stripped.npz")
        artifacts.save_rule_tensors(
            path, vocab=["a", "b"],
            rule_ids=np.array([[1], [-1]], dtype=np.int32),
            rule_counts=np.zeros((2, 1), dtype=np.int32),
            item_counts=np.array([2, 2], dtype=np.int32),
            n_playlists=4, min_support=0.25, mode="confidence",
        )
        with pytest.raises(ValueError, match="stripped"):
            artifacts.load_rule_tensors(path)

    def test_tensors_from_dict_legacy_pickle(self):
        vocab = ["a", "b", "c"]
        d = {"a": {"zz-not-in-vocab": 0.9, "b": 0.5, "c": 0.4}, "c": {}}
        ids, confs, known = artifacts.tensors_from_rules_dict(d, vocab, k_max=2)
        # unknown consequents must not punch holes or crowd out valid ones
        np.testing.assert_array_equal(ids[0], [1, 2])
        np.testing.assert_allclose(confs[0], [0.5, 0.4])
        # empty-dict keys are still KNOWN seeds (rest_api/app/main.py:235)
        np.testing.assert_array_equal(known, [True, False, True])


def _mk_cfg(tmp_path, n_datasets=3) -> MiningConfig:
    ds_dir = tmp_path / "datasets"
    ds_dir.mkdir(parents=True, exist_ok=True)
    for i in range(1, n_datasets + 1):
        (ds_dir / f"2023_spotify_ds{i}.csv").write_text("pid,track_name\n")
    return MiningConfig(base_dir=str(tmp_path), datasets_dir=str(ds_dir))


class TestRegistry:
    def test_discover_and_persist(self, tmp_path):
        cfg = _mk_cfg(tmp_path)
        datasets = registry.get_dataset_list(cfg)
        assert len(datasets) == 3
        assert all(d.endswith(".csv") for d in datasets)
        # list is persisted and re-read, not re-globbed
        (tmp_path / "datasets" / "2023_spotify_ds9.csv").write_text("x\n")
        assert registry.get_dataset_list(cfg) == datasets

    def test_no_datasets_raises(self, tmp_path):
        cfg = MiningConfig(base_dir=str(tmp_path), datasets_dir=str(tmp_path / "none"))
        with pytest.raises(FileNotFoundError):
            registry.get_dataset_list(cfg)

    def test_rotation_wraparound(self, tmp_path):
        # reference semantics: last index + 1, wrap to BASE_INDEX
        # (machine-learning/main.py:364-392)
        cfg = _mk_cfg(tmp_path, n_datasets=2)
        datasets = registry.get_dataset_list(cfg)
        assert registry.get_next_run_index(cfg, datasets) == BASE_INDEX
        registry.append_history_and_invalidate(cfg, BASE_INDEX, datasets[0])
        assert registry.get_next_run_index(cfg, datasets) == BASE_INDEX + 1
        registry.append_history_and_invalidate(cfg, BASE_INDEX + 1, datasets[1])
        assert registry.get_next_run_index(cfg, datasets) == BASE_INDEX  # wrapped

    def test_token_rewrite(self, tmp_path):
        cfg = _mk_cfg(tmp_path, n_datasets=1)
        datasets = registry.get_dataset_list(cfg)
        token1 = registry.append_history_and_invalidate(cfg, 1, datasets[0], "2026-01-01 00:00:00")
        tok_file = registry.token_path_for(cfg.base_dir, cfg.data_invalidation_file)
        assert artifacts.read_text(tok_file) == token1
        token2 = registry.append_history_and_invalidate(cfg, 2, datasets[0], "2026-01-02 00:00:00")
        assert artifacts.read_text(tok_file) == token2 != token1
        history = registry.read_history(cfg)
        assert [h[1] for h in history] == [1, 2]

    def test_history_format_interop_with_reference(self, tmp_path):
        # a history file written by the REFERENCE job (header + row layout
        # from machine-learning/main.py:394-405) must drive our rotation
        cfg = _mk_cfg(tmp_path, n_datasets=3)
        datasets = registry.get_dataset_list(cfg)
        (tmp_path / "dataset_history.csv").write_text(
            "time,dataset_index,dataset_file\n"
            "2025-01-10 10:30:00,2,/api-data/datasets/2023_spotify_ds2.csv\n"
        )
        assert registry.get_next_run_index(cfg, datasets) == 3
        # and our appended row keeps the reference's column order
        registry.append_history_and_invalidate(cfg, 3, datasets[2], "2025-01-10 11:00:00")
        last = (tmp_path / "dataset_history.csv").read_text().splitlines()[-1]
        assert last.split(",", 2)[0] == "2025-01-10 11:00:00"
        assert last.split(",", 2)[1] == "3"
