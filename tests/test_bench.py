"""Unit tests for bench.py's pure helpers — the artifact-assembly logic
whose bugs would silently corrupt the judged JSON line (the bench itself is
exercised end to end by the driver; these pin the derivations)."""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

_spec = importlib.util.spec_from_file_location(
    "kmls_bench", Path(__file__).resolve().parent.parent / "bench.py"
)
bench = importlib.util.module_from_spec(_spec)
sys.modules.setdefault("kmls_bench", bench)
_spec.loader.exec_module(bench)
# an exported KMLS_BENCH_STATE must never leak banked results into these
# canned tests, so the module-global state is forced inert here; tests
# that exercise banking construct their own BenchState
bench.STATE = bench.BenchState(None)


@pytest.fixture(autouse=True)
def _sidecar_to_tmp(tmp_path, monkeypatch):
    """Every emitter mirrors its full artifact to a sidecar; point it at a
    tmp file so tests never litter the repo root (subprocess-based tests
    inherit the env)."""
    monkeypatch.setenv(
        "KMLS_BENCH_SIDECAR", str(tmp_path / "bench_full.json")
    )


def _full_artifact(tmp_path) -> dict:
    """The COMPLETE artifact a test run produced (the stdout line is the
    compact ≤1,800-char projection; completeness assertions read this)."""
    return json.loads((tmp_path / "bench_full.json").read_text())


class TestMfuKeys:
    MINING_TPU = {
        "median_s": 0.1,
        "matmul_s": 0.001,
        "n_playlists": 2246,
        "n_tracks": 2171,
        "device_kind": "TPU v5e",
        "platform": "tpu",
    }

    def test_closed_form_op_count(self):
        out = bench._mfu_keys(self.MINING_TPU)
        # 2·P·V² ops: V² output cells, P MACs each, 2 ops/MAC
        expected_gops = 2 * 2246 * 2171 * 2171 / 1e9
        assert out["mining_matmul_gops"] == round(expected_gops, 2)
        assert out["mining_matmul_ms"] == 1.0
        assert out["mining_matmul_gops_per_s"] == round(expected_gops / 0.001, 1)

    def test_mfu_pct_only_on_tpu_with_known_peak(self):
        out = bench._mfu_keys(self.MINING_TPU)
        # v5e int8 peak 394 TOPS; achieved = 2.117e13 ops/s → ~5.4%
        assert out["mining_mfu_peak_tops"] == 394.0
        achieved = 2 * 2246 * 2171 * 2171 / 0.001
        assert out["mining_mfu_pct"] == round(100 * achieved / 394e12, 2)

    def test_no_mfu_pct_on_cpu(self):
        cpu = dict(self.MINING_TPU, platform="cpu", device_kind="cpu")
        out = bench._mfu_keys(cpu)
        assert "mining_mfu_pct" not in out
        assert "mining_matmul_gops_per_s" in out  # achieved still labeled

    def test_prefix_separates_cpu_and_tpu_evidence(self):
        out = bench._mfu_keys(self.MINING_TPU, prefix="mining_cpu")
        assert set(out) >= {"mining_cpu_matmul_ms", "mining_cpu_matmul_gops"}
        assert "mining_matmul_ms" not in out

    def test_missing_matmul_is_empty(self):
        assert bench._mfu_keys({"median_s": 1.0}) == {}

    def test_amortized_time_preferred_for_mfu(self):
        # the per-blocked-call time carries the host round trip; the
        # pipelined time is the device rate — MFU must use the latter
        mining = dict(self.MINING_TPU, matmul_amortized_s=0.0001)
        out = bench._mfu_keys(mining)
        achieved = 2 * 2246 * 2171 * 2171 / 0.0001
        assert out["mining_matmul_gops_per_s"] == round(achieved / 1e9, 1)
        assert out["mining_mfu_pct"] == round(100 * achieved / 394e12, 2)
        assert out["mining_matmul_ms"] == 1.0  # blocked time still reported
        assert out["mining_matmul_amortized_ms"] == 0.1


class TestParseLatencyPercentiles:
    def test_parses_rendered_metrics(self):
        # exactly what serving/metrics.py renders
        from kmlserver_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.record("rules", 0.004)
        m.record("fallback", 0.008)
        text = m.render(reload_counter=1, finished_loading=True)
        out = bench._parse_latency_percentiles(text)
        assert set(out) == {"p50_ms", "p95_ms", "p99_ms"}
        assert out["p50_ms"] in (4.0, 8.0)
        assert out["p99_ms"] == 8.0

    def test_empty_on_unrelated_text(self):
        assert bench._parse_latency_percentiles("nope 1\n") == {}


class TestClassify:
    def test_hang_wins(self):
        assert bench._classify("whatever", timed_out=True) == "hang"

    def test_transient_markers(self):
        assert bench._classify("... UNAVAILABLE: pool down", False) == "transient"
        assert bench._classify("Unable to initialize backend", False) == "transient"

    def test_hard_default(self):
        assert bench._classify("TypeError: boom", False) == "hard"


class TestRunPhaseWatchdog:
    def test_init_hang_killed_early_and_retried(self, monkeypatch):
        import time as time_mod

        monkeypatch.setattr(bench, "STARTUP_GRACE_S", 1.5)
        sleeps = []
        monkeypatch.setattr(bench.time, "sleep", lambda s: sleeps.append(s))
        code = "import time\ntime.sleep(30)"  # never prints a device line
        t0 = time_mod.monotonic()
        out = bench._run_phase(
            "watchdog-test", code, [], platform="tpu", timeout=60, attempts=2
        )
        elapsed = time_mod.monotonic() - t0
        assert out is None
        # two ~1.5s grace windows, NOT the 60s phase timeout
        assert elapsed < 20
        assert 30 in sleeps  # the init hang consumed a retry with backoff

    def test_device_line_disarms_watchdog(self, monkeypatch):
        monkeypatch.setattr(bench, "STARTUP_GRACE_S", 1.0)
        code = (
            "import sys, time\n"
            "print('device: tpu (fake)', file=sys.stderr, flush=True)\n"
            "time.sleep(2)\n"  # longer than the grace — must NOT be killed
            "print('{\"ok\": 1}')\n"
        )
        out = bench._run_phase(
            "watchdog-test", code, [], platform="tpu", timeout=30, attempts=1
        )
        assert out == {"ok": 1}

    def test_cpu_phase_needs_no_device_line(self):
        code = "print('{\"ok\": 2}')"
        out = bench._run_phase(
            "cpu-test", code, [], platform="cpu", timeout=30, attempts=1
        )
        assert out == {"ok": 2}

    def test_nonzero_exit_salvages_last_json_checkpoint(self):
        """A phase that checkpoints partial JSON then crashes (config4's
        cold line before a warm pass that dies) must still contribute
        its checkpoint — salvage is not timeout-only."""
        code = (
            "import sys\n"
            "print('{\"partial\": 1}')\n"
            "print('not json trailing output')\n"
            "sys.exit(1)\n"
        )
        out = bench._run_phase(
            "salvage-test", code, [], platform="cpu", timeout=30, attempts=1
        )
        assert out == {"partial": 1}

    def test_salvage_skips_non_dict_json_lines(self):
        """A bare scalar is valid JSON but not a checkpoint (e.g. a line
        truncated by a kill): salvage must skip past it to the last DICT
        — returning a scalar would TypeError in every consumer."""
        code = (
            "import sys\n"
            "print('{\"partial\": 2}')\n"
            "print('42')\n"  # valid JSON, not a checkpoint
            "sys.exit(1)\n"
        )
        out = bench._run_phase(
            "salvage-test", code, [], platform="cpu", timeout=30, attempts=1
        )
        assert out == {"partial": 2}


class TestProbeHistory:
    def test_forced_cpu_history_shape(self):
        prober = bench.TpuProber(probe_timeout_s=1.0)
        prober.history.append({"t_s": 0.0, "outcome": "forced_cpu", "dur_s": 0.0})
        snap = prober.history_snapshot()
        assert snap == [{"t_s": 0.0, "outcome": "forced_cpu", "dur_s": 0.0}]
        snap.append("mutation")  # snapshot is a copy
        assert len(prober.history_snapshot()) == 1

    def test_hung_probe_is_killed_and_recorded(self, monkeypatch):
        prober = bench.TpuProber(probe_timeout_s=1.0)
        monkeypatch.setattr(bench, "_PROBE", "import time; time.sleep(30)")
        assert prober.probe_once() == "hang"
        assert [h["outcome"] for h in prober.history_snapshot()] == ["hang"]


class TestMfuClamp:
    MINING_TPU = dict(TestMfuKeys.MINING_TPU)

    def test_impossible_mfu_flagged_suspect_not_headline(self):
        # r03 shipped mining_mfu_pct: 177.13 — physically impossible; now
        # >100% lands under *_suspect with a reason, never as the MFU key
        mining = dict(self.MINING_TPU, matmul_amortized_s=1e-9)
        out = bench._mfu_keys(mining)
        assert "mining_mfu_pct" not in out
        assert out["mining_mfu_pct_suspect"] > 100.0
        assert "physically impossible" in out["mining_mfu_suspect_reason"]
        assert out["mining_mfu_peak_tops"] == 394.0

    def test_plausible_mfu_unchanged(self):
        out = bench._mfu_keys(dict(self.MINING_TPU, matmul_amortized_s=0.0001))
        assert "mining_mfu_pct_suspect" not in out
        assert 0 < out["mining_mfu_pct"] <= 100

    def test_chain_slope_inputs_travel_with_the_artifact(self):
        mining = dict(
            self.MINING_TPU, chain_n1=16, chain_n2=1016,
            chain_t_short_s=0.1234567891, chain_t_long_s=0.5,
        )
        out = bench._mfu_keys(mining)
        assert out["mining_chain_n1"] == 16
        assert out["mining_chain_n2"] == 1016
        assert out["mining_chain_t_short_s"] == 0.123457  # rounded, auditable
        assert out["mining_chain_t_long_s"] == 0.5


class TestArtifactEmitter:
    def test_silent_before_headline(self, capsys):
        em = bench.ArtifactEmitter()
        em.checkpoint()
        assert capsys.readouterr().out == ""
        assert em.finalize() is False  # never prints a dud line

    def test_checkpoints_supersede_and_dedup(self, capsys):
        em = bench.ArtifactEmitter()
        em.set_headline("cpu", {"median_s": 2.0})  # prints checkpoint 1
        em.extras["popcount_ds2_ms"] = 1.5
        em.checkpoint()  # prints checkpoint 2
        em.checkpoint()  # identical → deduped
        lines = [
            json.loads(ln)
            for ln in capsys.readouterr().out.splitlines()
            if ln.strip()
        ]
        assert len(lines) == 2
        assert all(ln["checkpoint"] is True for ln in lines)
        assert lines[0]["value"] == 2.0
        assert lines[0]["vs_baseline"] == round(20.31 / 2.0, 1)
        assert lines[-1]["popcount_ds2_ms"] == 1.5

    def test_finalize_drops_checkpoint_flag(self, capsys):
        prober = bench.TpuProber(probe_timeout_s=1.0)
        prober.history.append({"t_s": 0.0, "outcome": "forced_cpu", "dur_s": 0.0})
        em = bench.ArtifactEmitter(prober)
        em.set_headline("tpu", {"median_s": 0.5})
        assert em.finalize() is True
        lines = [
            json.loads(ln)
            for ln in capsys.readouterr().out.splitlines()
            if ln.strip()
        ]
        final = lines[-1]
        assert "checkpoint" not in final
        assert final["platform"] == "tpu"
        assert final["probe_history"][0]["outcome"] == "forced_cpu"
        em.checkpoint()  # after finalize: silent
        assert capsys.readouterr().out == ""

    def test_cpu_comparison_keys(self, capsys):
        em = bench.ArtifactEmitter()
        em.set_headline("tpu", {"median_s": 0.8})
        em.set_cpu_comparison({"median_s": 0.1})
        lines = [
            json.loads(ln)
            for ln in capsys.readouterr().out.splitlines()
            if ln.strip()
        ]
        final = lines[-1]
        assert final["mining_cpu_s"] == 0.1
        assert final["best_mining_platform"] == "cpu"
        assert final["vs_baseline_best"] == round(20.31 / 0.1, 1)


class TestTpuSuiteWiring:
    """run_tpu_suite executes only on real hardware — unattended, at round
    end. This pins its key-mapping/checkpoint wiring against canned phase
    results so a src-key typo or a non-dict phase result can't surface for
    the first time on the driver."""

    CANNED = {
        "mining": {
            "median_s": 0.5, "matmul_s": 0.001, "matmul_amortized_s": 0.0005,
            "n_playlists": 2246, "n_tracks": 2171,
            "device_kind": "TPU v5e", "platform": "tpu",
            "count_path": "dense-fused",
            "chain_n1": 16, "chain_n2": 1016,
            "chain_t_short_s": 0.1, "chain_t_long_s": 0.6,
        },
        "popcount": {
            "kernel": "bcast", "popcount_ms": 150.0, "dense_ms": 80.0,
            "words_per_s": 2e10, "popcount_amortized_ms": 120.0,
            "dense_amortized_ms": 7.0, "mxu_ms": 30.0,
            "mxu_amortized_ms": 11.0, "mxu_words_per_s": 2e11,
            "exact": True, "mode": "compiled", "v_pad": 2176, "w_pad": 512,
            "word_ops": 1, "shape": "2246x2171",
        },
        "config4-devicegen": {
            "mine_s": 9.5, "mine_cold_s": 30.0, "gen_device_s": 4.0,
            "rows": 500_000_000, "rows_basis": "expected-model-rows",
            "rows_per_s": 5e7, "frequent_items": 8000, "n_rules": 90000,
            "bitset_gib": 9.5, "workload_model": "bernoulli-zipf",
            "rows_measured": 450_000_000,
        },
        # before "scale": the prefix match must hit the sparse bracket's
        # own canned result, not fall through to the scale one
        "scale-sparse": {
            "identical": True, "headline_identical": True,
            "shape": "1500000x40000", "rows": 6000000,
            "density": 0.0001, "auto_path": "sparse",
            "auto_source": "table", "auto_path_dense_regime": "dense",
            "table_cell": "d0:e3", "sparse_mine_s": 2.53,
            "sparse_rows_per_s": 2367872.0, "count_path": "sparse-hybrid",
            "frequent_items": 39862, "native_mine_s": 18.38,
            "native_rows_per_s": 326448.0,
            "native_count_path": "native-cpu", "speedup_vs_native": 7.27,
            "table_points": 13, "table_cells": 11,
            "sweep_identical": True, "platform": "cpu",
        },
        "scale": {
            "mine_s": 20.0, "rows_per_s": 2.5e6, "frequent_items": 5069,
            "auto_mine_s": 12.0, "auto_path": "dense-fused",
            "auto_rows_per_s": 4e6, "device_resident_mine_s": 3.0,
            "device_resident_path": "bitpack-mxu",
        },
        "sweep": {
            "points": 68, "total_s": 12.0, "emission_total_s": 9.0,
            "setup_plus_count_s": 3.0,
        },
        "serving": {
            "p50_ms": 0.5, "amortized_ms": 0.4,
            "p50_256_ms": 1.2, "amortized_256_ms": 1.0,
        },
        "pallas-tune": {
            "shape": "2246x2171", "best_config": "64x128x512",
            "best_variant": "bcast", "best_ms": 95.0,
            "best_words_per_s": 2.6e10,
            "results": [{"config": "64x128x512", "variant": "bcast",
                         "ms": 95.0, "words_per_s": 2.6e10}],
        },
        "replay10k": {
            "qps": 10000.0, "offered_qps": 10020.0,
            "achieved_qps": 10010.0, "p50_ms": 0.4, "p95_ms": 1.4,
            "p99_ms": 4.9, "errors": 0, "cache_hit_ratio": 0.98,
            "cached_p50_ms": 0.4, "uncached_p50_ms": 2.0, "zipf_s": 1.1,
            "per_device_dispatch": [230, 243], "devices_active": 2,
            "n_replicas": 2, "platform": "cpu",
        },
        "chaos": {
            "qps": 1000.0, "offered_qps": 950.0, "achieved_qps": 948.0,
            "p50_ms": 120.0, "p99_ms": 900.0, "errors": 0, "http_5xx": 0,
            "degraded_answers": 3, "ok_answers": 7997, "redispatched": 4,
            "ejections": 1, "eject_recovery_ms": 250.0, "zipf_s": 1.1,
            "cache_hit_ratio": 0.94, "platform": "cpu",
        },
        "mine-resume": {
            "crash_phase": "mine", "resumed_phases": ["encode", "mine"],
            "full_s": 1.445, "interrupted_s": 1.298, "resume_s": 0.129,
            "saved_pct": 91.068, "identical": True, "platform": "cpu",
        },
        # NB: listed BEFORE "loadshape" — the fakes match phase names by
        # startswith() in insertion order, and "loadshape_pred" shares
        # the shorter prefix
        "loadshape_pred": {
            "qps": 1000.0, "requests": 4000, "platform": "cpu",
            "shapes": {
                "ramp": {
                    "reactive": {
                        "p50_ms": 1.1, "p99_ms": 9.4,
                        "onset_p99_ms": 14.2, "steady_p99_ms": 6.1,
                        "errors": 0, "http_5xx": 0, "shed": 12,
                        "degraded": 30, "ok": 3958,
                        "achieved_qps": 998.0,
                        "forecast_disabled_obs_delta": 0,
                    },
                    "predictive": {
                        "p50_ms": 1.0, "p99_ms": 7.1,
                        "onset_p99_ms": 8.9, "steady_p99_ms": 6.0,
                        "errors": 0, "http_5xx": 0, "shed": 4,
                        "degraded": 11, "ok": 3985,
                        "achieved_qps": 999.0,
                        "forecast_observations": 4000,
                        "prewarm_total": 1,
                    },
                },
                "sine": {
                    "reactive": {
                        "p50_ms": 1.0, "p99_ms": 8.2,
                        "onset_p99_ms": 8.0, "steady_p99_ms": 8.3,
                        "errors": 0, "http_5xx": 0, "shed": 6,
                        "degraded": 14, "ok": 3980,
                        "achieved_qps": 997.0,
                        "forecast_disabled_obs_delta": 0,
                    },
                    "predictive": {
                        "p50_ms": 1.0, "p99_ms": 6.9,
                        "onset_p99_ms": 6.8, "steady_p99_ms": 7.0,
                        "errors": 0, "http_5xx": 0, "shed": 2,
                        "degraded": 5, "ok": 3993,
                        "achieved_qps": 998.0,
                        "forecast_observations": 4000,
                        "prewarm_total": 2,
                    },
                },
                "constant": {
                    "reactive": {
                        "p50_ms": 0.9, "p99_ms": 4.1,
                        "onset_p99_ms": 4.0, "steady_p99_ms": 4.2,
                        "errors": 0, "http_5xx": 0, "shed": 0,
                        "degraded": 0, "ok": 4000,
                        "achieved_qps": 1000.0,
                        "forecast_disabled_obs_delta": 0,
                    },
                    "predictive": {
                        "p50_ms": 0.9, "p99_ms": 4.2,
                        "onset_p99_ms": 4.1, "steady_p99_ms": 4.2,
                        "errors": 0, "http_5xx": 0, "shed": 0,
                        "degraded": 0, "ok": 4000,
                        "achieved_qps": 1000.0,
                        "forecast_observations": 4000,
                        "prewarm_total": 0,
                    },
                },
            },
        },
        "loadshape": {
            "qps": 1000.0, "burst_factor": 10.0, "zipf_s": 1.1,
            "requests": 8000,
            "burst": {
                "offered_qps": 2388.9, "achieved_qps": 2388.9,
                "p50_ms": 0.7, "p99_ms": 4.7, "errors": 0, "http_5xx": 0,
                "shed": 0, "degraded": 0, "ok": 8000,
                "runs_p99_ms": [4.7, 5.1, 9.2],
            },
            "flash": {
                "offered_qps": 1007.0, "achieved_qps": 1007.0,
                "p50_ms": 0.8, "p99_ms": 26.3, "errors": 0, "http_5xx": 0,
                "shed": 2, "degraded": 1, "ok": 3997,
            },
            "epochflip": {
                "offered_qps": 1008.0, "achieved_qps": 1008.0,
                "p50_ms": 1.2, "p99_ms": 32.0, "errors": 0, "http_5xx": 0,
                "shed": 0, "degraded": 0, "ok": 4000,
                "epoch_moved": 1, "singleflight_joins": 5,
            },
            "cache_hit_ratio": 0.983, "utilization_after": 0.01,
            "platform": "cpu",
        },
        "als-hybrid": {
            "als_train_s": 3.2, "als_rank": 32, "als_iters": 8,
            "emb_vocab": 2171, "qps": 1000.0, "achieved_qps": 999.0,
            "p50_ms": 1.2, "p95_ms": 3.0, "p99_ms": 6.5, "errors": 0,
            "cold_start_seeds": 300, "cold_start_hit_frac": 0.99,
            "platform": "cpu",
        },
        "confserve": {
            "qps": 1000.0, "achieved_qps": 1001.0, "p50_ms": 2.0,
            "p95_ms": 4.5, "p99_ms": 9.0, "errors": 0, "rule_keys": 431,
            "max_itemset_len": 3, "confidence_mode": "confidence",
            "platform": "cpu",
        },
        "traceoverhead": {
            "qps": 1000.0, "requests": 6000, "p99_on_ms": 5.1,
            "p99_off_ms": 5.0, "p99_ratio": 1.02, "p50_on_ms": 1.1,
            "p50_off_ms": 1.1, "began_off": 0, "began_on": 60,
            "retained_on": 48, "platform": "cpu",
        },
        "freshness": {
            "qps": 800.0, "achieved_qps": 799.0, "p50_ms": 0.6,
            "p99_ms": 7.4, "errors": 0, "http_5xx": 0,
            "full_path_s": 1.0, "delta_path_s": 0.15,
            "delta_publish_s": 0.13, "publish_to_applied_ms": 14.0,
            "delta_underload_s": 0.2, "speedup": 6.7,
            "delta_applied_total": 4, "delta_rejected_total": 0,
            "freshness_lag_s": 0.9, "cache_hit_ratio": 0.92,
            "cache_hits_after_warm": 2100, "cache_invalidated_keys": 40,
            "cache_selective_invalidations": 4,
            "fleet_affinity_hit_ratio": 0.81,
            "fleet_baseline_hit_ratio": 0.62, "fleet_multiplier": 1.31,
            "platform": "cpu",
        },
        "fleet": {
            "qps": 10500.0, "requests": 42000, "replicas": 3,
            "cache_entries": 512, "zipf_pool": 2304,
            "independent_hit_ratio": 0.642, "routed_hit_ratio": 0.833,
            "independent_hit_ratio_full": 0.648,
            "routed_hit_ratio_full": 0.822,
            "multiplier_achieved": 1.2979, "multiplier_simulated": 1.3528,
            "multiplier_vs_simulated": 0.9594,
            "sim_affinity_hit": 0.864, "sim_roundrobin_hit": 0.638,
            "offered_qps": 10528.0, "achieved_qps": 10528.0,
            "p50_ms": 1.54, "p99_ms": 12.15, "errors": 0, "http_5xx": 0,
            "kill_peer": "replica-2", "rerouted": 60,
            "router_ejections": 1, "router_spills": 6037,
            "owner_stamped": 6037,
            "answered_by": {"replica-0": 16246, "replica-1": 16659,
                            "replica-2": 9095},
            "delta_applied_ok": True, "selective_invalidations": 2,
            "misrouted_total": 7925, "identity_ok": True,
            "platform": "cpu",
        },
        "meshserve": {
            "gang_size": 2, "identical": True, "unwarmed_dispatches": 0,
            "catalog_bytes": 1843200, "host_budget_bytes": 921600,
            "max_catalog_bytes": 1843200, "sharded_p50_ms": 2.1,
            "sharded_p99_ms": 4.4, "mesh_p50_ms": 3.6, "mesh_p99_ms": 7.9,
            "replay_qps": 500.0, "replay_requests": 4000,
            "achieved_qps": 501.0, "replay_p99_ms": 11.2,
            "http_5xx": 0, "errors": 0, "mesh_unavailable": 9,
            "ejections": 1, "failed_shards": {"gang": 1},
            "answered_by": {"gang": 2012, "solo": 1988},
            "platform": "cpu",
        },
        "slowpeer": {
            "qps": 32.0, "requests": 600, "stall_ms": 200,
            "control_p50_ms": 6.1, "control_p99_ms": 260.8,
            "hedged_p50_ms": 5.9, "hedged_p99_ms": 22.4,
            "p99_ratio": 11.63, "hedge_overhead_pct": 4.0,
            "hedges_issued": 12, "hedge_wins": 12, "hedge_losses": 0,
            "hedges_suppressed": 0, "hedge_mismatch": 0,
            "slow_ejections": 1, "deadline_expired": 0,
            "server_deadline_expired": 0, "control_hedges_issued": 0,
            "control_http_5xx": 0, "control_errors": 0,
            "http_5xx": 0, "errors": 0, "identity_ok": True,
            "mesh_requests": 300, "mesh_hedge_wins": 8,
            "mesh_hedge_cancelled": 7, "mesh_straggler_degraded": 8,
            "mesh_expired_on_arrival": 0, "mesh_p99_ms": 1502.0,
            "mesh_http_5xx": 0, "mesh_errors": 0,
            "platform": "cpu",
        },
        "graystore": {
            "qps": 1000.0, "requests": 6000, "stall_ms": 400.0,
            "control_p50_ms": 0.26, "control_p99_ms": 12.2,
            "stalled_p50_ms": 0.24, "stalled_p99_ms": 13.9,
            "p99_ratio": 1.14, "storage_slow": True,
            "readyz_degraded": True, "reload_deferred": True,
            "backoff_bounded": True, "last_good_held": True,
            "enospc_exit": 75, "enospc_exit_resumable": True,
            "enospc_identical": True, "enospc_token_moved": False,
            "torn_parts": 0, "probe_p99_ms": 1.1, "recovered": True,
            "io_retries": 0, "http_5xx": 0, "errors": 0,
            "platform": "cpu",
        },
        "quality": {
            "recall_rules": 0.27, "recall_embed": 0.41,
            "recall_blend": 0.41, "recall_blend_best": 0.43,
            "recall_popularity": 0.11, "mrr_blend": 0.22,
            "coverage_blend": 1.0, "measured_weight": 0.15,
            "weight_roundtrip": True, "eval_playlists": 320,
            "full_job_s": 4.2, "remine_s": 1.2, "compact_s": 0.14,
            "compact_speedup": 8.4, "compact_folded": 2,
            "compact_identical": True, "http_5xx": 0, "errors": 0,
            "p99_ms": 6.1, "platform": "cpu",
        },
        "costattrib": {
            "qps": 800.0, "requests": 4000, "p50_ms": 0.6, "p99_ms": 6.9,
            "mfu": 7.2e-05, "roofline": "bandwidth",
            "flops_per_s": 1.44e7, "bytes_per_s": 5.1e7,
            "device_s": 4.82, "dispatches": 4000, "compiles": 0,
            "obs_off_delta": 0, "peak_flops": 2e11,
            "peak_source": "auto:cpu cpu", "headroom_bytes": 12884000000,
            "platform": "cpu",
        },
    }
    REPLAY = {
        "target_qps": 1000.0, "achieved_qps": 1010.0, "p50_ms": 4.0,
        "p95_ms": 9.0, "p99_ms": 14.0, "n_errors": 0,
        "runs": [{"p50_ms": 4.0, "achieved_qps": 1010.0, "n_errors": 0}],
        "host_load1": 0.5, "warmup_requests": 1000,
        "job_end_to_end_s": 3.5,
        "server_percentiles": {"p50_ms": 2.0, "p95_ms": 5.0, "p99_ms": 8.0},
    }

    def test_every_phase_key_lands_in_the_artifact(
        self, monkeypatch, capsys, tmp_path
    ):
        def fake_run_phase(name, code, argv, **kw):
            for prefix, canned in self.CANNED.items():
                if name.startswith(prefix):
                    return dict(canned)
            raise AssertionError(f"unexpected phase {name!r}")

        monkeypatch.setattr(bench, "_run_phase", fake_run_phase)
        monkeypatch.setattr(
            bench, "replay_phase", lambda platform: dict(self.REPLAY)
        )
        # the suite gates phases on wall-clock headroom; pin it so test
        # ordering / an exported KMLS_BENCH_DEADLINE_S can't skip phases
        monkeypatch.setattr(bench, "_remaining", lambda: 1e9)
        em = bench.ArtifactEmitter()
        mining = bench.run_tpu_suite(em, "/tmp/unused.npz")
        assert mining == self.CANNED["mining"]
        assert em.finalize()
        out = capsys.readouterr().out
        stdout_line = [ln for ln in out.splitlines() if ln.strip()][-1]
        # stdout carries the bounded compact projection with the headline
        # + judged serving keys; completeness is asserted on the sidecar
        assert len(stdout_line) <= bench.COMPACT_LINE_LIMIT
        compact = json.loads(stdout_line)
        assert compact["platform"] == "tpu"
        assert compact["value"] == 0.5
        assert compact["replay_achieved_qps"] == 1010.0
        assert compact["serving_batch32_p50_ms"] == 0.5
        assert compact["full_artifact"].endswith("bench_full.json")
        final = _full_artifact(tmp_path)
        assert final["platform"] == "tpu"
        assert final["value"] == 0.5
        assert final["mining_mfu_pct"] > 0  # amortized path, ≤100
        assert final["mining_chain_n2"] == 1016
        assert final["popcount_ds2_ms"] == 150.0
        assert final["bitpack_mxu_ds2_ms"] == 30.0
        assert final["config4_mine_s"] == 9.5
        assert final["config4_rows_basis"] == "expected-model-rows"
        assert final["scale_1m_x_100k_mine_s"] == 20.0
        assert final["scale_device_resident_mine_s"] == 3.0
        assert final["sweep_points"] == 68
        assert final["serving_batch32_p50_ms"] == 0.5
        assert final["serving_batch256_p50_ms"] == 1.2
        assert final["replay_achieved_qps"] == 1010.0
        assert final["replay_server_p50_ms"] == 2.0
        assert final["replay_runs"] == self.REPLAY["runs"]
        assert final["replay_job_end_to_end_s"] == 3.5
        assert final["popcount_tune_best_config"] == "64x128x512"
        assert final["popcount_tune_best_ms"] == 95.0
        # the 10k-QPS bracket: self-labeled CPU keys, cache + dispatch
        assert final["replay10k_p99_ms"] == 4.9
        assert final["replay10k_cache_hit_ratio"] == 0.98
        assert final["replay10k_devices_active"] == 2
        assert final["replay10k_platform"] == "cpu"
        # the continuous-freshness bracket rides the TPU artifact too
        assert final["freshness_speedup"] == 6.7
        assert final["freshness_http_5xx"] == 0
        assert final["freshness_fleet_multiplier"] == 1.31
        assert final["freshness_platform"] == "cpu"
        # ... and the fleet cache-routing bracket (ISSUE 15)
        assert final["fleet_hit_ratio"] == 0.833
        assert final["fleet_multiplier_achieved"] == 1.2979
        assert final["fleet_multiplier_simulated"] == 1.3528
        assert final["fleet_http_5xx"] == 0
        assert final["fleet_identity_ok"] is True
        assert final["fleet_platform"] == "cpu"
        # ... and the pod-spanning serve-mesh bracket (ISSUE 16)
        assert final["meshserve_identical"] is True
        assert final["meshserve_gang"] == 2
        assert final["meshserve_unwarmed"] == 0
        assert final["meshserve_max_catalog_bytes"] == 1843200
        assert final["meshserve_http_5xx"] == 0
        assert final["meshserve_errors"] == 0
        assert final["meshserve_mesh_unavailable"] == 9
        assert final["meshserve_platform"] == "cpu"
        # ... and the gray-failure slowpeer bracket (ISSUE 18)
        assert final["slowpeer_p99_ratio"] == 11.63
        assert final["slowpeer_hedge_overhead_pct"] == 4.0
        assert final["slowpeer_hedge_mismatch"] == 0
        assert final["slowpeer_control_hedges_issued"] == 0
        assert final["slowpeer_http_5xx"] == 0
        assert final["slowpeer_identity_ok"] is True
        assert final["slowpeer_mesh_hedge_wins"] == 8
        assert final["slowpeer_mesh_straggler_degraded"] == 8
        assert final["slowpeer_platform"] == "cpu"
        # ... and the storage gray-failure bracket (ISSUE 19)
        assert final["graystore_storage_slow"] is True
        assert final["graystore_readyz_degraded"] is True
        assert final["graystore_reload_deferred"] is True
        assert final["graystore_last_good_held"] is True
        assert final["graystore_enospc_exit_resumable"] is True
        assert final["graystore_enospc_identical"] is True
        assert final["graystore_enospc_token_moved"] is False
        assert final["graystore_torn_parts"] == 0
        assert final["graystore_http_5xx"] == 0
        assert final["graystore_platform"] == "cpu"
        # ... and so does the quality-loop bracket (ISSUE 14)
        assert final["quality_recall_blend"] == 0.43
        assert final["quality_weight_roundtrip"] is True
        assert final["quality_compact_identical"] is True
        assert final["quality_http_5xx"] == 0
        assert final["quality_platform"] == "cpu"
        # the supplementary CPU replay lands under cpu_-prefixed keys
        assert final["cpu_replay_achieved_qps"] == 1010.0

    def test_failed_optional_phase_never_aborts_the_suite(self, monkeypatch, capsys):
        def fake_run_phase(name, code, argv, **kw):
            if name.startswith("mining"):
                return dict(self.CANNED["mining"])
            return None  # every optional phase fails

        monkeypatch.setattr(bench, "_run_phase", fake_run_phase)
        monkeypatch.setattr(bench, "replay_phase", lambda platform: None)
        monkeypatch.setattr(bench, "_remaining", lambda: 1e9)
        em = bench.ArtifactEmitter()
        mining = bench.run_tpu_suite(em, "/tmp/unused.npz")
        assert mining == self.CANNED["mining"]
        assert em.finalize()
        out = capsys.readouterr().out
        final = json.loads(
            [ln for ln in out.splitlines() if ln.strip()][-1]
        )
        assert final["value"] == 0.5
        assert "popcount_ds2_ms" not in final


class TestMainPlatform:
    """main() decides the platform once — the chip, or an explicitly
    CPU-labelled run — and a chip run that cannot get or keep the chip
    exits non-zero with no artifact line. Neither turns into the other."""

    @staticmethod
    def _wire(monkeypatch, probe_outcome, tpu_mining=None):
        ran: list[str] = []

        class FakeProber:
            def __init__(self, *a, **kw):
                self.history = []

            def probe_once(self):
                self.history.append(
                    {"t_s": 0.0, "outcome": probe_outcome, "dur_s": 1.0}
                )
                return probe_outcome

            def history_snapshot(self):
                return list(self.history)

        def fake_cpu_suite(em, npz):
            ran.append("cpu")
            em.set_headline("cpu", {"median_s": 0.08, "count_path": "native-cpu"})
            return em.mining

        def fake_tpu_suite(em, npz):
            ran.append("tpu")
            if tpu_mining is not None:
                em.set_headline("tpu", dict(tpu_mining))
            return tpu_mining

        monkeypatch.setattr(bench, "TpuProber", FakeProber)
        monkeypatch.setattr(bench, "run_cpu_suite", fake_cpu_suite)
        monkeypatch.setattr(bench, "run_tpu_suite", fake_tpu_suite)
        monkeypatch.setattr(bench, "run_mining", lambda *a, **kw: None)
        monkeypatch.setattr(bench, "_remaining", lambda: 1e9)
        return ran

    @pytest.mark.parametrize(
        "outcome", ["cpu_only", "hang", "error", "transient_error"]
    )
    def test_no_tpu_and_no_cpu_flag_exits_nonzero(
        self, monkeypatch, capsys, outcome
    ):
        ran = self._wire(monkeypatch, outcome)
        monkeypatch.delenv("KMLS_BENCH_CPU", raising=False)
        assert bench.main() != 0
        assert ran == []  # no suite at all — least of all the CPU one
        assert capsys.readouterr().out.strip() == ""

    def test_chip_run_that_loses_the_chip_exits_nonzero(
        self, monkeypatch, capsys
    ):
        ran = self._wire(monkeypatch, "tpu", tpu_mining=None)
        monkeypatch.delenv("KMLS_BENCH_CPU", raising=False)
        assert bench.main() != 0
        assert ran == ["tpu"]  # no CPU suite after the chip suite failed
        assert capsys.readouterr().out.strip() == ""

    def test_chip_run_reports_the_chip(self, monkeypatch, capsys):
        ran = self._wire(monkeypatch, "tpu", tpu_mining={
            "median_s": 0.4, "platform": "tpu", "device_kind": "TPU v5 lite",
            "count_path": "dense-fused",
        })
        monkeypatch.delenv("KMLS_BENCH_CPU", raising=False)
        assert bench.main() == 0
        assert ran == ["tpu"]
        final = json.loads(
            [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()][-1]
        )
        assert final["platform"] == "tpu" and final["value"] == 0.4

    def test_cpu_flag_is_a_cpu_labelled_run_without_a_probe(
        self, monkeypatch, capsys
    ):
        ran = self._wire(monkeypatch, "tpu")
        monkeypatch.setenv("KMLS_BENCH_CPU", "1")
        assert bench.main() == 0
        assert ran == ["cpu"]
        final = json.loads(
            [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()][-1]
        )
        assert final["platform"] == "cpu"
        assert [h["outcome"] for h in final["probe_history"]] == ["forced_cpu"]


class TestTpuSuiteLock:
    def test_contended_lock_means_no_chip(self, monkeypatch, tmp_path):
        """Two benches, one chip: when another process holds the
        TPU-suite lock past the wait budget, this one reports nothing —
        it neither contends nor replays the holder's bank."""
        import subprocess
        import sys as sys_mod

        state_path = str(tmp_path / "bank.json")
        state = bench.BenchState(state_path)
        state.bank("mining_tpu", dict(TestTpuSuiteWiring.CANNED["mining"]))
        holder = subprocess.Popen(
            [sys_mod.executable, "-c", f"""
import fcntl, sys, time
fd = open({state_path + ".lock"!r}, "w")
fcntl.flock(fd, fcntl.LOCK_EX)
print("held", flush=True)
time.sleep(60)
"""],
            stdout=subprocess.PIPE, text=True,
        )
        try:
            assert holder.stdout.readline().strip() == "held"
            monkeypatch.setattr(bench, "STATE", bench.BenchState(state_path))
            # wait budget: _remaining() - 420 <= 0 → a single try, no hang
            monkeypatch.setattr(bench, "_remaining", lambda: 400.0)
            em = bench.ArtifactEmitter()
            assert bench.run_tpu_suite(em, str(tmp_path / "w.npz")) is None
            assert em.mining is None and em.extras == {}
        finally:
            holder.kill()
            holder.wait()

    def test_uncontended_lock_runs_live_and_releases(
        self, monkeypatch, tmp_path
    ):
        """No contention: the suite takes the lock, runs live, and a
        second acquisition afterwards succeeds (the lock was released)."""
        state_path = str(tmp_path / "bank.json")

        def fake_run_phase(name, code, argv, **kw):
            for prefix, result in TestTpuSuiteWiring.CANNED.items():
                if name.startswith(prefix):
                    return dict(result)
            raise AssertionError(f"unexpected phase {name!r}")

        monkeypatch.setattr(bench, "STATE", bench.BenchState(state_path))
        monkeypatch.setattr(bench, "_run_phase", fake_run_phase)
        monkeypatch.setattr(
            bench, "replay_phase",
            lambda platform: dict(TestTpuSuiteWiring.REPLAY),
        )
        monkeypatch.setattr(bench, "_remaining", lambda: 1e9)
        npz = tmp_path / "w.npz"
        npz.write_bytes(b"x")
        em = bench.ArtifactEmitter()
        assert bench.run_tpu_suite(em, str(npz)) is not None
        lock = bench._acquire_tpu_lock(0)
        assert lock not in (None, "nolock")
        bench._release_tpu_lock(lock)


class TestSigtermFlush:
    def test_sigterm_mid_run_still_yields_parsed_artifact(self, tmp_path):
        """The r03 failure mode, pinned: a driver kill AFTER the headline
        exists but BEFORE the final print must still leave a parseable
        artifact as the last stdout JSON line."""
        import json as json_mod
        import signal
        import subprocess
        import sys as sys_mod
        import time as time_mod

        bench_path = Path(__file__).resolve().parent.parent / "bench.py"
        code = f"""
import importlib.util, sys, time
spec = importlib.util.spec_from_file_location("kmls_bench", {str(bench_path)!r})
bench = importlib.util.module_from_spec(spec)
sys.modules["kmls_bench"] = bench
spec.loader.exec_module(bench)
em = bench.ArtifactEmitter()
bench._install_crash_handlers(em)
em.set_headline("cpu", {{"median_s": 1.5}})
print("READY", file=sys.stderr, flush=True)
time.sleep(60)  # simulates the stuck probe-wait the driver killed in r03
"""
        proc = subprocess.Popen(
            [sys_mod.executable, "-c", code],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            # wait for the headline checkpoint before killing
            line = proc.stderr.readline()
            assert "READY" in line
            proc.send_signal(signal.SIGTERM)
            stdout, _ = proc.communicate(timeout=30)
        finally:
            proc.kill()
        json_lines = [
            json_mod.loads(ln) for ln in stdout.splitlines() if ln.strip()
        ]
        assert json_lines, "no JSON on stdout after SIGTERM"
        last = json_lines[-1]
        assert last["value"] == 1.5
        assert last["metric"] == "fpgrowth_ds2_rule_generation_time"
        assert last["aborted"].startswith("signal ")
        # at least one line was flushed → the kill still counts as clean
        assert proc.returncode == 0

    def test_sigterm_before_any_line_exits_nonzero(self):
        """A driver kill BEFORE the first mining headline
        used to exit 0 with no JSON — a clean-looking rc for a run that
        produced nothing. It must exit 128+signum."""
        import signal
        import subprocess
        import sys as sys_mod

        bench_path = Path(__file__).resolve().parent.parent / "bench.py"
        code = f"""
import importlib.util, sys, time
spec = importlib.util.spec_from_file_location("kmls_bench", {str(bench_path)!r})
bench = importlib.util.module_from_spec(spec)
sys.modules["kmls_bench"] = bench
spec.loader.exec_module(bench)
em = bench.ArtifactEmitter()
bench._install_crash_handlers(em)
print("READY", file=sys.stderr, flush=True)
time.sleep(60)  # no headline ever arrives
"""
        proc = subprocess.Popen(
            [sys_mod.executable, "-c", code],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            assert "READY" in proc.stderr.readline()
            proc.send_signal(signal.SIGTERM)
            stdout, _ = proc.communicate(timeout=30)
        finally:
            proc.kill()
        assert not stdout.strip(), "no artifact line expected"
        assert proc.returncode == 128 + signal.SIGTERM


class TestBenchStateResume:
    """Bounded chip calls must compound: a
    second bench invocation with KMLS_BENCH_STATE set replays every banked
    TPU phase — including the headline mine and its serving-input npz —
    with ZERO live phase runs, even when the deadline gate would normally
    skip the phase."""

    def test_second_window_replays_all_banked_phases(
        self, monkeypatch, tmp_path, capsys
    ):
        state_path = str(tmp_path / "bank.json")
        canned = TestTpuSuiteWiring.CANNED
        replay = TestTpuSuiteWiring.REPLAY

        # ---- window 1: live phases, everything banks ----
        def fake_run_phase(name, code, argv, **kw):
            for prefix, result in canned.items():
                if name.startswith(prefix):
                    return dict(result)
            raise AssertionError(f"unexpected phase {name!r}")

        monkeypatch.setattr(bench, "STATE", bench.BenchState(state_path))
        monkeypatch.setattr(bench, "_run_phase", fake_run_phase)
        monkeypatch.setattr(
            bench, "replay_phase", lambda platform: dict(replay)
        )
        monkeypatch.setattr(bench, "_remaining", lambda: 1e9)
        npz1 = tmp_path / "window1.npz"
        npz1.write_bytes(b"npz-sentinel")  # the mining phase's side output
        em = bench.ArtifactEmitter()
        assert bench.run_tpu_suite(em, str(npz1)) == canned["mining"]
        banked = json.loads(Path(state_path).read_text())["phases"]
        assert set(banked) == {
            "traceoverhead_cpu", "freshness_cpu", "fleet_cpu",
            "costattrib_tpu",
            "mining_tpu", "serving_tpu", "replay_tpu", "popcount_tpu",
            "config4_tpu", "scale_tpu", "sweep_tpu", "popcount_tune_tpu",
            "replay_cpu_supp", "replay10k_cpu", "chaos_cpu",
            "loadshape_cpu", "loadshape_pred_cpu", "mine_resume_cpu",
            "als_hybrid_cpu",
            "confserve_cpu", "scale_sparse_cpu", "quality_cpu",
            "meshserve_cpu", "slowpeer_cpu", "graystore_cpu",
        }
        assert Path(state_path + ".npz").read_bytes() == b"npz-sentinel"
        capsys.readouterr()

        # ---- window 2: any live phase run is a test failure; the gate is
        # pinned shut so only bank replays can fill the artifact ----
        def no_live_runs(*a, **kw):
            raise AssertionError("live phase ran despite a full bank")

        monkeypatch.setattr(bench, "STATE", bench.BenchState(state_path))
        monkeypatch.setattr(bench, "_run_phase", no_live_runs)
        monkeypatch.setattr(bench, "replay_phase", no_live_runs)
        monkeypatch.setattr(bench, "_remaining", lambda: 10.0)
        npz2 = tmp_path / "window2.npz"
        em2 = bench.ArtifactEmitter()
        assert bench.run_tpu_suite(em2, str(npz2)) == canned["mining"]
        assert npz2.read_bytes() == b"npz-sentinel"  # serving input restored
        assert em2.finalize()
        stdout_line = [
            ln for ln in capsys.readouterr().out.splitlines() if ln.strip()
        ][-1]
        assert len(stdout_line) <= bench.COMPACT_LINE_LIMIT
        final = _full_artifact(tmp_path)
        assert final["platform"] == "tpu"
        assert final["value"] == 0.5
        assert final["popcount_ds2_ms"] == 150.0
        assert final["config4_mine_s"] == 9.5
        assert final["scale_1m_x_100k_mine_s"] == 20.0
        assert final["sweep_points"] == 68
        assert final["serving_batch32_p50_ms"] == 0.5
        assert final["replay_achieved_qps"] == 1010.0
        assert final["cpu_replay_achieved_qps"] == 1010.0
        assert final["popcount_tune_best_config"] == "64x128x512"
        # replayed-from-bank phases carry per-phase provenance
        assert final["serving_tpu_from_bank"] is True
        assert final["serving_tpu_bank_age_s"] >= 0
        assert final["replay_tpu_from_bank"] is True
        assert final["mining_tpu_from_bank"] is True

    def test_tune_error_result_is_not_banked(
        self, monkeypatch, tmp_path, capsys
    ):
        """A no-config-succeeded tune is a failure: banking it would
        replay the failure into every later window."""
        state_path = str(tmp_path / "bank.json")

        def fake_run_phase(name, code, argv, **kw):
            if name.startswith("pallas-tune"):
                return {"error": "no config succeeded"}
            for prefix, result in TestTpuSuiteWiring.CANNED.items():
                if name.startswith(prefix):
                    return dict(result)
            raise AssertionError(f"unexpected phase {name!r}")

        monkeypatch.setattr(bench, "STATE", bench.BenchState(state_path))
        monkeypatch.setattr(bench, "_run_phase", fake_run_phase)
        monkeypatch.setattr(
            bench, "replay_phase",
            lambda platform: dict(TestTpuSuiteWiring.REPLAY),
        )
        monkeypatch.setattr(bench, "_remaining", lambda: 1e9)
        em = bench.ArtifactEmitter()
        npz = tmp_path / "w.npz"
        npz.write_bytes(b"x")
        bench.run_tpu_suite(em, str(npz))
        banked = json.loads(Path(state_path).read_text())["phases"]
        assert "popcount_tune_tpu" not in banked
        assert em.finalize()
        final = json.loads(
            [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()][-1]
        )
        assert "popcount_tune_best_config" not in final

    def test_partial_bank_runs_only_missing_phases(
        self, monkeypatch, tmp_path, capsys
    ):
        """A window that died mid-suite leaves a partial bank; the next
        window replays what's banked and runs ONLY the missing phases."""
        state_path = str(tmp_path / "bank.json")
        canned = TestTpuSuiteWiring.CANNED
        state = bench.BenchState(state_path)
        state.bank("mining_tpu", dict(canned["mining"]))
        state.bank("serving_tpu", dict(canned["serving"]))
        npz_src = tmp_path / "bank.json.npz"
        npz_src.write_bytes(b"npz-sentinel")

        live = []

        def fake_run_phase(name, code, argv, **kw):
            live.append(name)
            for prefix, result in canned.items():
                if name.startswith(prefix):
                    return dict(result)
            raise AssertionError(f"unexpected phase {name!r}")

        monkeypatch.setattr(bench, "STATE", bench.BenchState(state_path))
        monkeypatch.setattr(bench, "_run_phase", fake_run_phase)
        monkeypatch.setattr(
            bench, "replay_phase",
            lambda platform: dict(TestTpuSuiteWiring.REPLAY),
        )
        monkeypatch.setattr(bench, "_remaining", lambda: 1e9)
        em = bench.ArtifactEmitter()
        npz = tmp_path / "window.npz"
        assert bench.run_tpu_suite(em, str(npz)) == canned["mining"]
        assert "mining" not in [n.split("-")[0] for n in live]
        assert not any(n.startswith("serving") for n in live)
        assert any(n.startswith("popcount") for n in live)
        # the freshly-run phases banked for the NEXT window
        banked = json.loads(Path(state_path).read_text())["phases"]
        assert "popcount_tpu" in banked and "sweep_tpu" in banked

    def test_bank_without_npz_sidecar_remines(
        self, monkeypatch, tmp_path, capsys
    ):
        """A banked mining result whose npz sidecar is gone must re-mine —
        the serving phase cannot run without its input."""
        state_path = str(tmp_path / "bank.json")
        state = bench.BenchState(state_path)
        state.bank("mining_tpu", dict(TestTpuSuiteWiring.CANNED["mining"]))
        # no .npz sidecar written

        mined = []

        def fake_run_phase(name, code, argv, **kw):
            if name.startswith("mining"):
                mined.append(name)
                return dict(TestTpuSuiteWiring.CANNED["mining"])
            return None

        monkeypatch.setattr(bench, "STATE", bench.BenchState(state_path))
        monkeypatch.setattr(bench, "_run_phase", fake_run_phase)
        monkeypatch.setattr(bench, "replay_phase", lambda platform: None)
        monkeypatch.setattr(bench, "_remaining", lambda: 1e9)
        em = bench.ArtifactEmitter()
        bench.run_tpu_suite(em, str(tmp_path / "w.npz"))
        assert mined, "expected a live re-mine when the npz sidecar is missing"

    def test_resolve_state_path_rules(self, monkeypatch, tmp_path):
        """The env names the bank; unset or empty means none — a bank
        file lying in cwd is never adopted on its own."""
        monkeypatch.setenv("KMLS_BENCH_STATE", "/x/y.json")
        assert bench._resolve_state_path() == "/x/y.json"
        monkeypatch.setenv("KMLS_BENCH_STATE", "")
        assert bench._resolve_state_path() is None
        monkeypatch.delenv("KMLS_BENCH_STATE")
        monkeypatch.chdir(tmp_path)
        (tmp_path / "bench_state_r05_tpu.json").write_text("{}")
        assert bench._resolve_state_path() is None

    def test_stale_phases_dropped_at_load(self, monkeypatch, tmp_path):
        """A bank older than the round length must not leak a previous
        round's measurements into a fresh artifact."""
        path = str(tmp_path / "bank.json")
        state = bench.BenchState(path)
        state.bank("mining_tpu", {"median_s": 0.4})
        state.bank("sweep_tpu", {"points": 68})
        # age one phase past the cap by rewriting its timestamp
        raw = json.loads(Path(path).read_text())
        raw["banked_at"]["mining_tpu"] -= bench.BenchState.MAX_AGE_S + 60
        Path(path).write_text(json.dumps(raw))
        fresh = bench.BenchState(path)
        assert fresh.get("mining_tpu") is None
        assert fresh.get("sweep_tpu") == {"points": 68}

    def test_unset_state_is_a_noop(self, monkeypatch, tmp_path):
        """KMLS_BENCH_STATE unset (every CI/driver-default path): nothing
        is written anywhere and every invocation runs phases live."""
        state = bench.BenchState(None)
        state.bank("mining_tpu", {"median_s": 1.0})
        assert state.get("mining_tpu") is None  # nothing banked anywhere
        assert state.npz_path is None
        assert not list(tmp_path.iterdir())


class TestCompactLine:
    """The final stdout JSON line must stay under the driver's tail window
    (the r05 headline died at 2,112 chars → parsed: null)."""

    def _bloated(self):
        extras = {
            f"optional_phase_{i}_detail": "x" * 60 for i in range(60)
        }
        extras["replay_p50_ms"] = 4.0
        extras["replay_p99_ms"] = 11.0
        extras["replay_errors"] = 0
        extras["replay_queue_wait_p99_ms"] = 3.5
        extras["replay_device_p99_ms"] = 6.0
        return extras

    def test_compact_line_bounded_and_prioritized(self):
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu", **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["value"] == 1.0
        # the judged serving keys outrank the bloat
        assert parsed["replay_queue_wait_p99_ms"] == 3.5
        assert parsed["replay_device_p99_ms"] == 6.0

    def test_compact_line_keeps_replay10k_and_cache_keys(self):
        """The r05 headline was lost at 2,112 chars against a 2,000-char
        tail window; the PR-2 key additions (replay10k_* + cache_*) must
        not regress the ≤1,800 budget, and must outrank filler."""
        r10k = {
            "replay10k_qps": 10000.0,
            "replay10k_achieved_qps": 10021.8,
            "replay10k_p50_ms": 0.403,
            "replay10k_p99_ms": 4.881,
            "replay10k_errors": 0,
            "replay10k_cache_hit_ratio": 0.997,
            "replay10k_cached_p50_ms": 0.402,
            "replay10k_uncached_p50_ms": 2.035,
            "replay10k_devices_active": 8,
            "replay10k_per_device_dispatch": [59, 61, 58, 60, 57, 62, 59, 57],
        }
        for key in r10k:
            if key != "replay10k_per_device_dispatch":
                assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **r10k, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["replay10k_p99_ms"] == 4.881
        assert parsed["replay10k_cache_hit_ratio"] == 0.997
        assert parsed["replay10k_cached_p50_ms"] == 0.402

    def test_record_loadshape_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-8 traffic-shape bracket's judged keys (burst p99 /
        zero 5xx / zero errors, flash + epoch-flip 5xx, the epoch-moved
        proof) must land in the compact line without regressing the
        ≤1,800 budget."""
        canned = {
            "qps": 1000.0, "burst_factor": 10.0, "zipf_s": 1.1,
            "requests": 8000,
            "burst": {
                "offered_qps": 2388.9, "achieved_qps": 2388.9,
                "p50_ms": 0.713, "p99_ms": 4.745, "errors": 0,
                "http_5xx": 0, "shed": 0, "degraded": 0, "ok": 8000,
                "runs_p99_ms": [4.745, 5.1, 9.2],
            },
            "flash": {
                "offered_qps": 1007.6, "achieved_qps": 1007.6,
                "p50_ms": 0.801, "p99_ms": 26.299, "errors": 0,
                "http_5xx": 0, "shed": 3, "degraded": 2, "ok": 3995,
            },
            "epochflip": {
                "offered_qps": 1008.7, "achieved_qps": 1008.7,
                "p50_ms": 1.153, "p99_ms": 32.04, "errors": 0,
                "http_5xx": 0, "shed": 0, "degraded": 0, "ok": 4000,
                "epoch_moved": 1, "singleflight_joins": 5,
            },
            "cache_hit_ratio": 0.983, "utilization_after": 0.01,
            "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_loadshape(result)
        assert result["loadshape_p99_ms"] == 4.745
        assert result["loadshape_errors"] == 0
        assert result["loadshape_http_5xx"] == 0
        assert result["loadshape_flash_http_5xx"] == 0
        assert result["loadshape_flip_http_5xx"] == 0
        assert result["loadshape_flip_epoch_moved"] == 1
        assert result["loadshape_flip_singleflight"] == 5
        assert result["loadshape_burst_factor"] == 10.0
        assert result["loadshape_platform"] == "cpu"
        for key in ("loadshape_p99_ms", "loadshape_errors",
                    "loadshape_http_5xx", "loadshape_shed",
                    "loadshape_degraded", "loadshape_offered_qps",
                    "loadshape_burst_factor", "loadshape_flash_http_5xx",
                    "loadshape_flip_http_5xx",
                    "loadshape_flip_epoch_moved"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["loadshape_p99_ms"] == 4.745
        assert parsed["loadshape_http_5xx"] == 0
        assert parsed["loadshape_flip_epoch_moved"] == 1

    def test_record_loadshape_pred_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-17 predictive A/B bracket's judged keys (ramp/sine
        paired p99 + onset split, zero 5xx, observation evidence) must
        land in the compact line without regressing the ≤1,800 budget."""

        def leg(p99, onset, shed=0, degraded=0, predictive=False):
            out = {
                "p50_ms": 0.7, "p99_ms": p99, "onset_p99_ms": onset,
                "steady_p99_ms": p99, "errors": 0, "http_5xx": 0,
                "shed": shed, "degraded": degraded, "ok": 8000,
                "achieved_qps": 1000.0,
            }
            if predictive:
                out["forecast_observations"] = 8000
                out["prewarm_total"] = 2
            else:
                out["forecast_disabled_obs_delta"] = 0
            return out

        canned = {
            "qps": 1000.0, "requests": 8000, "platform": "cpu",
            "shapes": {
                "ramp": {
                    "reactive": leg(9.4, 14.2, shed=12, degraded=30),
                    "predictive": leg(7.1, 8.9, shed=4, degraded=11,
                                      predictive=True),
                },
                "sine": {
                    "reactive": leg(6.2, 7.0, degraded=8),
                    "predictive": leg(5.8, 6.1, degraded=5,
                                      predictive=True),
                },
                "constant": {
                    "reactive": leg(4.7, 4.8),
                    "predictive": leg(4.8, 4.9, predictive=True),
                },
            },
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_loadshape_pred(result)
        assert result["loadshape_pred_ramp_react_p99_ms"] == 9.4
        assert result["loadshape_pred_ramp_pred_p99_ms"] == 7.1
        assert result["loadshape_pred_ramp_pred_onset_p99_ms"] == 8.9
        assert result["loadshape_pred_sine_pred_p99_ms"] == 5.8
        assert result["loadshape_pred_http_5xx"] == 0
        assert result["loadshape_pred_errors"] == 0
        # the zero-cost proof rides the sidecar: the disabled legs'
        # forecaster observation deltas, asserted 0 inside the phase
        assert result["loadshape_pred_ramp_react_obs_delta"] == 0
        assert result["loadshape_pred_constant_react_obs_delta"] == 0
        assert result["loadshape_pred_ramp_obs"] == 8000
        assert result["loadshape_pred_ramp_pred_shed"] == 4
        for key in ("loadshape_pred_ramp_react_p99_ms",
                    "loadshape_pred_ramp_pred_p99_ms",
                    "loadshape_pred_ramp_react_onset_p99_ms",
                    "loadshape_pred_ramp_pred_onset_p99_ms",
                    "loadshape_pred_sine_react_p99_ms",
                    "loadshape_pred_sine_pred_p99_ms",
                    "loadshape_pred_http_5xx", "loadshape_pred_errors",
                    "loadshape_pred_ramp_obs"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["loadshape_pred_ramp_pred_p99_ms"] == 7.1
        assert parsed["loadshape_pred_http_5xx"] == 0

    def test_record_traceoverhead_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-9 tracing-overhead bracket's judged keys (sampled
        p99 within 5% of disabled, the disabled recorder's began==0
        zero-cost proof) must land in the compact line without
        regressing the ≤1,800 budget."""
        canned = {
            "qps": 1000.0, "requests": 6000,
            "p50_on_ms": 0.412, "p50_off_ms": 0.401,
            "p99_on_ms": 4.981, "p99_off_ms": 4.902,
            "p99_ratio": 1.0161,
            "began_on": 6000, "began_off": 0, "retained_on": 97,
            "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_traceoverhead(result)
        assert result["traceoverhead_p99_ratio"] == 1.0161
        assert result["traceoverhead_began_off"] == 0
        assert result["traceoverhead_retained_on"] == 97
        # only the judged claims ride the compact line (the TPU-suite
        # line is at capacity; on/off/retained detail is sidecar-only)
        for key in ("traceoverhead_p99_ratio", "traceoverhead_began_off"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["traceoverhead_p99_ratio"] == 1.0161
        assert parsed["traceoverhead_began_off"] == 0

    def test_record_freshness_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-10 continuous-freshness bracket's judged keys
        (delta-vs-full speedup ≥ 5x, zero 5xx through the in-place
        apply, the 3-replica fleet hit-ratio multiplier) must land in
        the compact line without regressing the ≤1,800 budget."""
        canned = {
            "qps": 800.0, "achieved_qps": 799.2,
            "p50_ms": 0.6, "p99_ms": 7.4, "errors": 0, "http_5xx": 0,
            "full_path_s": 11.04, "delta_path_s": 1.01,
            "delta_publish_s": 0.97, "publish_to_applied_ms": 12.3,
            "delta_underload_s": 1.22, "speedup": 10.93,
            "delta_applied_total": 2, "delta_rejected_total": 0,
            "freshness_lag_s": 0.8, "cache_hit_ratio": 0.902,
            "cache_hits_after_warm": 2101, "cache_invalidated_keys": 38,
            "cache_selective_invalidations": 2,
            "fleet_affinity_hit_ratio": 0.81,
            "fleet_baseline_hit_ratio": 0.62,
            "fleet_multiplier": 1.306, "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_freshness(result)
        assert result["freshness_speedup"] == 10.93
        assert result["freshness_http_5xx"] == 0
        assert result["freshness_publish_to_applied_ms"] == 12.3
        assert result["freshness_fleet_multiplier"] == 1.306
        assert result["freshness_cache_invalidated_keys"] == 38
        assert result["freshness_platform"] == "cpu"
        # only the judged claims ride the compact line (it sits at its
        # budget; path/cache detail is sidecar-only, like traceoverhead)
        for key in ("freshness_speedup", "freshness_http_5xx",
                    "freshness_errors",
                    "freshness_publish_to_applied_ms",
                    "freshness_fleet_multiplier"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["freshness_speedup"] == 10.93
        assert parsed["freshness_http_5xx"] == 0
        assert parsed["freshness_fleet_multiplier"] == 1.306

    def test_record_fleet_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-15 fleet cache-routing bracket's judged keys
        (routed vs independent fleet hit ratio, multiplier achieved vs
        the PR 10 simulated prediction, p99 + zero 5xx through the
        mid-replay kill/delta, survivor answer identity) must land in
        the compact line without regressing the ≤1,800 budget."""
        canned = {
            "qps": 10500.0, "requests": 42000, "replicas": 3,
            "cache_entries": 512, "zipf_pool": 2304,
            "independent_hit_ratio": 0.412, "routed_hit_ratio": 0.783,
            "independent_hit_ratio_full": 0.418,
            "routed_hit_ratio_full": 0.741,
            "multiplier_achieved": 1.9005, "multiplier_simulated": 1.84,
            "multiplier_vs_simulated": 1.0329,
            "sim_affinity_hit": 0.79, "sim_roundrobin_hit": 0.4293,
            "offered_qps": 10391.0, "achieved_qps": 10380.0,
            "p50_ms": 0.9, "p99_ms": 11.2, "errors": 0, "http_5xx": 0,
            "kill_peer": "replica-2", "rerouted": 311,
            "router_ejections": 1, "router_spills": 5120,
            "owner_stamped": 5100,
            "answered_by": {"replica-0": 20100, "replica-1": 16000,
                            "replica-2": 5900},
            "delta_applied_ok": True, "selective_invalidations": 2,
            "misrouted_total": 4100, "identity_ok": True,
            "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_fleet(result)
        assert result["fleet_hit_ratio"] == 0.783
        assert result["fleet_independent_hit_ratio"] == 0.412
        assert result["fleet_multiplier_achieved"] == 1.9005
        assert result["fleet_multiplier_simulated"] == 1.84
        assert result["fleet_http_5xx"] == 0
        assert result["fleet_identity_ok"] is True
        assert result["fleet_delta_applied_ok"] is True
        assert result["fleet_platform"] == "cpu"
        # only the judged claims ride the compact line (per-peer and
        # router detail is sidecar-only, like the freshness detail)
        for key in ("fleet_hit_ratio", "fleet_independent_hit_ratio",
                    "fleet_multiplier_achieved",
                    "fleet_multiplier_simulated", "fleet_p99_ms",
                    "fleet_http_5xx", "fleet_errors",
                    "fleet_identity_ok"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["fleet_hit_ratio"] == 0.783
        assert parsed["fleet_multiplier_achieved"] == 1.9005
        assert parsed["fleet_http_5xx"] == 0

    def test_record_quality_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-14 quality-loop bracket's judged keys (held-out
        recall per mode, the measured blend weight + its serve-time
        round-trip, compacted-snapshot identity + zero 5xx through the
        mid-replay swap) must land in the compact line without
        regressing the ≤1,800 budget."""
        canned = {
            "recall_rules": 0.2656, "recall_embed": 0.4094,
            "recall_blend": 0.4094, "recall_blend_best": 0.4281,
            "recall_popularity": 0.1125, "mrr_blend": 0.2193,
            "coverage_blend": 1.0, "measured_weight": 0.15,
            "weight_roundtrip": True, "eval_playlists": 320,
            "full_job_s": 4.21, "remine_s": 1.18, "compact_s": 0.14,
            "compact_speedup": 8.43, "compact_folded": 2,
            "compact_identical": True, "http_5xx": 0, "errors": 0,
            "p99_ms": 6.1, "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_quality(result)
        assert result["quality_recall_blend"] == 0.4281
        assert result["quality_recall_rules"] == 0.2656
        assert result["quality_blend_weight"] == 0.15
        assert result["quality_weight_roundtrip"] is True
        assert result["quality_compact_identical"] is True
        assert result["quality_compact_speedup"] == 8.43
        assert result["quality_http_5xx"] == 0
        assert result["quality_platform"] == "cpu"
        # only the judged claims ride the compact line (sweep-curve/
        # MRR/coverage detail is sidecar-only, like the siblings)
        for key in ("quality_recall_blend", "quality_recall_rules",
                    "quality_recall_embed", "quality_blend_weight",
                    "quality_weight_roundtrip",
                    "quality_compact_identical", "quality_compact_s",
                    "quality_compact_speedup", "quality_http_5xx",
                    "quality_errors"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["quality_recall_blend"] == 0.4281
        assert parsed["quality_weight_roundtrip"] is True
        assert parsed["quality_compact_identical"] is True
        assert parsed["quality_http_5xx"] == 0

    def test_record_costattrib_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-12 cost-attribution bracket's judged keys
        (serve-kernel MFU ∈ (0, 1], roofline class, live compiles==0,
        the disabled-mode zero-observation proof) must land in the
        compact line without regressing the ≤1,800 budget."""
        canned = {
            "qps": 800.0, "requests": 4000,
            "p50_ms": 0.62, "p99_ms": 6.91,
            "mfu": 7.2158e-05, "roofline": "bandwidth",
            "flops_per_s": 1.443e7, "bytes_per_s": 5.1e7,
            "device_s": 4.821, "dispatches": 4000,
            "compiles": 0, "obs_off_delta": 0,
            "peak_flops": 2e11, "peak_source": "auto:cpu cpu",
            "headroom_bytes": 12884000000, "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_costattrib(result)
        assert result["costattrib_mfu"] == pytest.approx(7.216e-05)
        assert result["costattrib_roofline"] == "bandwidth"
        assert result["costattrib_compiles"] == 0
        assert result["costattrib_obs_off"] == 0
        assert result["costattrib_platform"] == "cpu"
        # only the judged claims ride the compact line (rate/peak detail
        # is sidecar-only, like the traceoverhead/freshness detail)
        for key in ("costattrib_mfu", "costattrib_roofline",
                    "costattrib_compiles", "costattrib_obs_off"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["costattrib_mfu"] == pytest.approx(7.216e-05)
        assert parsed["costattrib_compiles"] == 0
        assert parsed["costattrib_obs_off"] == 0

    def test_record_scale_sparse_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-13 sparsity bracket's judged keys (≥5x over the
        native record path on the SAME ≥99%-sparse workload, every route
        bit-identical, the auto dispatch resolving from the measured
        table) must land in the compact line without regressing the
        ≤1,800 budget."""
        canned = {
            "identical": True, "headline_identical": True,
            "shape": "1500000x40000", "rows": 6000000,
            "density": 0.0001, "auto_path": "sparse",
            "auto_source": "table", "auto_path_dense_regime": "dense",
            "table_cell": "d0:e3",
            "sparse_mine_s": 2.53, "sparse_rows_per_s": 2367872.0,
            "count_path": "sparse-hybrid", "frequent_items": 39862,
            "native_mine_s": 18.38, "native_rows_per_s": 326448.0,
            "native_count_path": "native-cpu",
            "speedup_vs_native": 7.27,
            "table_points": 13, "table_cells": 11,
            "sweep_identical": True, "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_scale_sparse(result)
        assert result["sparse_speedup_vs_native"] == 7.27
        assert result["sparse_identical"] is True
        assert result["sparse_headline_identical"] is True
        assert result["sparse_auto_path"] == "sparse"
        assert result["sparse_auto_source"] == "table"
        assert result["sparse_count_path"] == "sparse-hybrid"
        # only the judged claims ride the compact line (the TPU-suite
        # line is at capacity; rows/s + shape/table detail is
        # sidecar-only, the freshness/traceoverhead precedent)
        for key in ("sparse_speedup_vs_native", "sparse_identical",
                    "sparse_headline_identical", "sparse_density",
                    "sparse_auto_path", "sparse_auto_source"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["sparse_speedup_vs_native"] == 7.27
        assert parsed["sparse_identical"] is True
        assert parsed["sparse_auto_path"] == "sparse"

    def test_record_mine_resume_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-4 interruption bracket's keys must land in the
        compact line (they are the judged resume evidence) without
        regressing the ≤1,800 budget."""
        canned = {
            "crash_phase": "mine", "resumed_phases": ["encode", "mine"],
            "full_s": 1.445, "interrupted_s": 1.298, "resume_s": 0.129,
            "saved_pct": 91.068, "identical": True, "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_mine_resume(result)
        assert result["mine_resume_phase"] == "mine"
        assert result["mine_resume_saved_pct"] == 91.068
        assert result["mine_resume_identical"] is True
        for key in ("mine_resume_s", "mine_resume_full_s",
                    "mine_resume_saved_pct", "mine_resume_identical",
                    "mine_resume_phase"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["mine_resume_identical"] is True
        assert parsed["mine_resume_saved_pct"] == 91.068

    def test_record_replay10k_emits_bounded_artifact(self, monkeypatch):
        canned = {
            "qps": 10000.0, "offered_qps": 10021.8, "achieved_qps": 10011.2,
            "p50_ms": 0.41, "p95_ms": 1.4, "p99_ms": 4.9, "errors": 0,
            "cache_hit_ratio": 0.98, "cached_p50_ms": 0.4,
            "uncached_p50_ms": 2.1, "zipf_s": 1.1,
            "per_device_dispatch": [10, 11, 9, 12, 10, 9, 11, 10],
            "devices_active": 8, "n_replicas": 8, "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_replay10k(result)
        assert result["replay10k_qps"] == 10000.0
        assert result["replay10k_errors"] == 0
        assert result["replay10k_cache_hit_ratio"] == 0.98
        assert result["replay10k_devices_active"] == 8
        assert result["replay10k_platform"] == "cpu"
        # the full dict + headline still fits the compact budget
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu", **result}
        assert len(bench._compact_line(full)) <= bench.COMPACT_LINE_LIMIT

    def test_record_als_hybrid_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-6 second-model-family bracket's judged keys (ALS
        train time, hybrid p99, cold-start hit fraction) must land in the
        compact line without regressing the ≤1,800 budget."""
        canned = {
            "als_train_s": 3.214, "als_rank": 32, "als_iters": 8,
            "emb_vocab": 2171, "qps": 1000.0, "achieved_qps": 998.7,
            "p50_ms": 1.2, "p95_ms": 3.1, "p99_ms": 6.4, "errors": 0,
            "cold_start_seeds": 312, "cold_start_hit_frac": 0.987,
            "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_als_hybrid(result)
        assert result["als_train_s"] == 3.214
        assert result["hybrid_p99_ms"] == 6.4
        assert result["cold_start_hit_frac"] == 0.987
        assert result["hybrid_platform"] == "cpu"
        for key in ("als_train_s", "hybrid_p50_ms", "hybrid_p99_ms",
                    "hybrid_errors", "cold_start_hit_frac",
                    "cold_start_seeds"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["als_train_s"] == 3.214
        assert parsed["hybrid_p99_ms"] == 6.4
        assert parsed["cold_start_hit_frac"] == 0.987

    def test_record_confserve_emits_bounded_artifact(self, monkeypatch):
        """The confidence-mode serving bracket (carried-over ROADMAP
        item): multi-antecedent rules through the max-merge kernel, keys
        in the compact line under the budget."""
        canned = {
            "qps": 1000.0, "achieved_qps": 1001.3, "p50_ms": 2.1,
            "p95_ms": 4.8, "p99_ms": 9.2, "errors": 0, "rule_keys": 431,
            "max_itemset_len": 3, "confidence_mode": "confidence",
            "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_confserve(result)
        assert result["confserve_p99_ms"] == 9.2
        assert result["confserve_qps"] == 1001.3
        assert result["confserve_rule_keys"] == 431
        for key in ("confserve_p50_ms", "confserve_p99_ms",
                    "confserve_qps", "confserve_errors"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["confserve_p99_ms"] == 9.2
        assert parsed["confserve_p50_ms"] == 2.1

    def test_record_shardserve_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-7 model-parallel serving bracket's judged keys
        (layout identity, zero-compile proof, replicated-vs-sharded
        p50/p99, max servable catalog bytes) must land in the compact
        line without regressing the ≤1,800 budget."""
        canned = {
            "shards": 8, "identical": True, "unwarmed_dispatches": 0,
            "catalog_bytes": 878592, "device_budget_bytes": 439296,
            "max_catalog_bytes": 3514368,
            "replicated_p50_ms": 13.361, "replicated_p99_ms": 29.528,
            "sharded_p50_ms": 72.773, "sharded_p99_ms": 129.957,
            "shard_dispatch_counts": [1, 2, 3, 4, 5, 6, 7, 8],
            "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_shardserve(result)
        assert result["shardserve_identical"] is True
        assert result["shardserve_unwarmed"] == 0
        assert result["shardserve_shards"] == 8
        assert result["shardserve_sharded_p50_ms"] == 72.773
        assert result["shardserve_max_catalog_bytes"] == 3514368
        for key in ("shardserve_sharded_p50_ms", "shardserve_sharded_p99_ms",
                    "shardserve_replicated_p50_ms", "shardserve_identical",
                    "shardserve_shards", "shardserve_unwarmed",
                    "shardserve_max_catalog_bytes"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["shardserve_identical"] is True
        assert parsed["shardserve_sharded_p99_ms"] == 129.957

    def test_record_scale_shard_emits_bounded_artifact(self, monkeypatch):
        """The ISSUE-7 vocab-sharded mining bracket: the sharded
        count→emit path on an input whose dense single-device
        formulation busts the budget, keys under the ≤1,800 line."""
        canned = {
            "mine_s": 13.938, "rows_per_s": 28697.9, "shape": "20000x2000",
            "count_path": "sharded-vocab-gspmd", "shards": 8,
            "dense_single_device_bytes": 72000000,
            "hbm_budget_bytes": 36000000,
            "per_shard_counts_bytes": 2000000,
            "rules_emitted": 5688, "frequent_items": 629, "platform": "cpu",
        }
        monkeypatch.setattr(
            bench, "_run_phase", lambda *a, **k: dict(canned)
        )
        result = {}
        bench._record_scale_shard(result)
        assert result["scale_shard_mine_s"] == 13.938
        assert result["scale_shard_count_path"] == "sharded-vocab-gspmd"
        assert result["scale_shard_dense_bytes"] == 72000000
        for key in ("scale_shard_mine_s", "scale_shard_rows_per_s",
                    "scale_shard_count_path", "scale_shard_shards"):
            assert key in bench._COMPACT_PRIORITY, key
        full = {"metric": "m", "value": 1.0, "unit": "s",
                "vs_baseline": 20.0, "platform": "cpu",
                **result, **self._bloated()}
        line = bench._compact_line(full)
        assert len(line) <= bench.COMPACT_LINE_LIMIT
        parsed = json.loads(line)
        assert parsed["scale_shard_mine_s"] == 13.938
        assert parsed["scale_shard_count_path"] == "sharded-vocab-gspmd"

    def test_emitter_final_line_bounded_with_full_sidecar(
        self, tmp_path, capsys
    ):
        prober = bench.TpuProber(probe_timeout_s=1.0)
        # a probe history long enough to sink the old full-line emission
        for i in range(80):
            prober.history.append(
                {"t_s": float(i), "outcome": "hang", "dur_s": 60.0}
            )
        em = bench.ArtifactEmitter(prober)
        em.extras.update(self._bloated())
        em.set_headline("cpu", {"median_s": 2.0})
        assert em.finalize()
        lines = [
            ln for ln in capsys.readouterr().out.splitlines() if ln.strip()
        ]
        assert all(len(ln) <= bench.COMPACT_LINE_LIMIT for ln in lines)
        final = json.loads(lines[-1])
        assert final is not None and final["value"] == 2.0
        assert "checkpoint" not in final
        # everything — bloat and probe history included — is in the sidecar
        full = _full_artifact(tmp_path)
        assert full["optional_phase_59_detail"] == "x" * 60
        assert len(full["probe_history"]) == 80
        assert final["full_artifact"].endswith("bench_full.json")

    def test_sidecar_disabled_still_bounded(self, monkeypatch, capsys):
        monkeypatch.setenv("KMLS_BENCH_SIDECAR", "")
        em = bench.ArtifactEmitter()
        em.extras.update(self._bloated())
        em.set_headline("cpu", {"median_s": 1.0})
        assert em.finalize()
        lines = [
            ln for ln in capsys.readouterr().out.splitlines() if ln.strip()
        ]
        assert all(len(ln) <= bench.COMPACT_LINE_LIMIT for ln in lines)
        assert "full_artifact" not in json.loads(lines[-1])


class TestReplayAttributionKeys:
    def test_parse_attribution_from_rendered_metrics(self):
        from kmlserver_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.record_attribution(queue_wait_s=0.002, device_s=0.004, e2e_s=0.006)
        text = m.render(reload_counter=1, finished_loading=True)
        out = bench._parse_attribution(text)
        assert out["queue_wait_p99_ms"] == 2.0
        assert out["device_p99_ms"] == 4.0
        assert out["e2e_p999_ms"] == 6.0

    def test_record_replay_emits_split_keys(self):
        replay = dict(TestTpuSuiteWiring.REPLAY)
        replay["server_percentiles"] = {
            "p50_ms": 2.0, "p95_ms": 5.0, "p99_ms": 8.0,
            "attribution": {
                "queue_wait_p50_ms": 0.8, "queue_wait_p99_ms": 3.2,
                "device_p50_ms": 1.1, "device_p99_ms": 4.4,
                "e2e_p999_ms": 9.9,
            },
        }
        result = {}
        # drive _record_replay with a canned replay via a no-bank path
        orig = bench.replay_phase
        bench.replay_phase = lambda platform: replay
        try:
            bench._record_replay(result, "cpu")
        finally:
            bench.replay_phase = orig
        assert result["replay_queue_wait_p99_ms"] == 3.2
        assert result["replay_device_p99_ms"] == 4.4
        assert result["replay_e2e_p999_ms"] == 9.9
        assert result["replay_server_p50_ms"] == 2.0
        # the attribution dict itself must not leak as a server_ key
        assert "replay_server_attribution" not in result


class TestBankMergeAndStaleness:
    def test_merge_prefers_newer_banked_at_regardless_of_origin(
        self, tmp_path
    ):
        """A process must not overwrite a fresher on-disk
        result with the stale copy it merely loaded at startup."""
        path = str(tmp_path / "bank.json")
        import time as time_mod

        now = time_mod.time()
        # process A loads a bank holding an OLD serving result
        state_a = bench.BenchState(None)
        state_a.path = path
        state_a.phases = {"serving_tpu": {"p50_ms": 99.0}}
        state_a.banked_at = {"serving_tpu": now - 600}
        # meanwhile process B banked a FRESHER serving result on disk
        (tmp_path / "bank.json").write_text(json.dumps({
            "version": 2,
            "phases": {"serving_tpu": {"p50_ms": 1.0}},
            "banked_at": {"serving_tpu": now - 5},
        }))
        # A banks an unrelated phase → merge-on-write runs
        state_a.bank("sweep_tpu", {"points": 68})
        disk = json.loads((tmp_path / "bank.json").read_text())
        assert disk["phases"]["serving_tpu"] == {"p50_ms": 1.0}  # B's wins
        assert disk["phases"]["sweep_tpu"] == {"points": 68}

    def test_v1_bank_without_timestamps_is_stale(self, tmp_path):
        """A timestampless (v1) bank must not replay into every
        artifact that names it, forever."""
        path = tmp_path / "bank.json"
        path.write_text(json.dumps({
            "version": 1,
            "phases": {"mining_tpu": {"median_s": 0.4}},
        }))
        state = bench.BenchState(str(path))
        assert state.get("mining_tpu") is None

    def test_banked_replay_stamps_provenance(self, tmp_path):
        state = bench.BenchState(str(tmp_path / "bank.json"))
        state.bank("popcount_tpu", {"popcount_ms": 1.0})
        old_state = bench.STATE
        bench.STATE = state
        try:
            extras = {}
            got = bench._banked(
                "popcount_tpu", lambda: None, extras=extras
            )
        finally:
            bench.STATE = old_state
        assert got == {"popcount_ms": 1.0}
        assert extras["popcount_tpu_from_bank"] is True
        assert extras["popcount_tpu_bank_age_s"] >= 0

    def test_live_run_stamps_nothing(self, tmp_path):
        state = bench.BenchState(str(tmp_path / "bank.json"))
        old_state = bench.STATE
        bench.STATE = state
        try:
            extras = {}
            got = bench._banked(
                "popcount_tpu", lambda: {"popcount_ms": 2.0}, extras=extras
            )
        finally:
            bench.STATE = old_state
        assert got == {"popcount_ms": 2.0}
        assert extras == {}
