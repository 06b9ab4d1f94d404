#!/usr/bin/env python
"""Headline benchmark: FP-Growth rule generation on a ds2-shaped workload.

The reference's published number (BASELINE.md): 20.31 s of rule generation —
mlxtend TransactionEncoder + FP-Growth + Python dict-expansion loops — on
ds2 (240,249 membership rows, 2,246 playlists, 2,171 tracks, min_support
0.05) on a CPU cluster node (relatorio.pdf p.6; timer bracket at
machine-learning/main.py:264,306-308).

This benchmark reproduces the same workload shape synthetically (the real
ds2 CSV is not distributed with the reference repo) and times the SAME
bracket for the TPU path: device one-hot encode + MXU pair-support matmul +
rule-tensor emission + host rule-dict expansion. Median of repeated runs,
compile excluded via warm-up (the reference's 20.31 s excludes Python/lib
import too).

Structure: this parent process never imports jax. Each phase runs in its
OWN subprocess, sequentially — matching deployment (batch job pod vs API
server pod are separate processes) and keeping phases from contending for
the single TPU chip (libtpu is one-process-per-chip on real hardware). All
phases share one persistent JAX compilation cache directory, so on-TPU
compile cost is paid once across the whole bench, not per-subprocess.

The platform is decided ONCE, at the start: one bounded probe child asks
JAX what it has. A TPU → the tpu suite runs on the chip. Anything else → this
exits non-zero, because a chip benchmark that cannot get or keep the chip has
nothing to report. ``KMLS_BENCH_CPU=1`` asks for the cpu suite instead, an
explicitly CPU-labelled run (every key ``*_cpu*``). There is no fallback from
one to the other. The probe (timestamp, outcome, duration) is recorded in the
JSON line as ``probe_history``.

Phases (tpu suite), in order: mining
(headline, + an isolated MXU matmul timing with closed-form op counts →
MFU via the chained-scan slope), serving (batch-32 p50), replay (full
stack at 1k QPS, median of N runs, server-side /metrics percentiles next
to the client-observed ones), popcount (compiled Pallas kernel, counts
asserted equal on-device, words/s emitted), config4-devicegen (TRUE
10M×1M shape, workload born in HBM as a Bernoulli-Zipf bitset), scale
(1M×100k config-4 mechanics through the real host-data pipeline), sweep
(the reference's 68-point support grid, count-once).
Phases (cpu suite): mining, popcount stand-in (interpret mode, small
shape), scale stand-in (20k×5k on an 8-virtual-device mesh), serving,
replay — all keys labeled ``*_cpu*`` — plus replay10k (the 10k-QPS
Zipf-mix in-process bracket through cache → batcher → native kernel;
always CPU-measured and self-labeled, reported as ``replay10k_*`` with
``cache_hit_ratio`` and per-device dispatch counts), chaos (kill a
replica mid-run at 1k QPS, zero-5xx acceptance), loadshape (10x burst
trains / flash crowd / epoch-boundary hot-key flip through the
admission ladder — p99 < 10 ms and zero 5xx through the bursts,
``loadshape_*``), and mine-resume (kill
the mining job after a fixed phase's checkpoint, restart, report
resume-vs-full wall clock + artifact bit-identity, ``mine_resume_*``).

THE ARTIFACT IS UNLOSEABLE. The driver records the LAST parseable JSON
line on this process's stdout; a single end-of-run print is lost whenever
the driver's kill comes first. Three mechanisms guarantee a parsed artifact
from the moment the headline mining number exists:

1. checkpoints — a complete, self-contained artifact line is printed after
   EVERY completed phase (marked ``"checkpoint": true``); later lines
   strictly supersede earlier ones, and only JSON lines ever go to stdout
   (all narrative goes to stderr);
2. SIGTERM/SIGINT/atexit handlers flush the best-so-far line (and kill
   live phase subprocesses) before exiting, so a driver kill at ANY time
   after the first mining result still yields a parsed artifact;
3. the soft deadline defaults to 1200 s — below the driver's observed
   ~1500 s kill.

Final line (checkpoint flag absent):
    {"metric": ..., "value": <median mining seconds>, "unit": "s",
     "vs_baseline": <baseline_s / value>, "platform": "tpu"|"cpu",
     "probe_history": [...], ...}

Extra context (per-run timings, diagnostics) goes to stderr.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

BASELINE_RULE_GEN_S = 20.31  # relatorio.pdf p.6 (BASELINE.md row 1)
MIN_SUPPORT = 0.05
REPEATS = 5

# soft wall-clock budget: optional phases are skipped once exceeded so the
# required JSON line is never lost to a driver-side timeout. 1200 s sits
# well under the ~1500 s at which a driver was seen to kill the run.
DEADLINE_S = 1200.0
_T0 = time.monotonic()


def _deadline_s() -> float:
    # env read at call time, not import time (envread checker): an
    # exported KMLS_BENCH_DEADLINE_S must keep working however late the
    # driver sets it relative to this module's first import
    return float(os.environ.get("KMLS_BENCH_DEADLINE_S", str(DEADLINE_S)))

_CPU_ENV = {"JAX_PLATFORMS": "cpu"}


def _cache_env() -> dict:
    """One compilation cache for every phase subprocess: the second process
    that compiles the same kernel (e.g. serving after mining) loads it
    instead of re-lowering. The same helper — and so the same fixed,
    outside-placeable directory — as the job, the server and
    ``chip_smoke.py``; lazy, because importing this module for its helpers
    must not touch the filesystem."""
    from kmlserver_tpu.utils import jaxcache

    return jaxcache.child_env()

# peak int8 MXU throughput per chip, ops/s (public spec sheets), for the
# MFU denominator — the mining matmul is int8×int8→int32 (ops/support.py
# pair_counts). Matched by substring against jax's device_kind.
_INT8_PEAK_OPS = {
    "v6": 1836e12,
    "v5p": 918e12,
    "v5e": 394e12,  # a.k.a. v5 lite
    "v5lite": 394e12,
    "v4": 275e12,
}

# substrings marking a backend-init failure worth retrying (vs a compute bug)
_TRANSIENT_MARKERS = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "backend setup",
    "Unable to initialize backend",
    "failed to connect",
    "Connection reset",
    "Socket closed",
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _elapsed() -> float:
    return time.monotonic() - _T0


def _remaining() -> float:
    return _deadline_s() - _elapsed()


def _phase_env(platform: str) -> dict:
    env = os.environ.copy()
    env.update(_cache_env())
    if platform == "cpu":
        env.update(_CPU_ENV)
    return env


def _classify(stderr_text: str, timed_out: bool) -> str:
    """'hang' | 'transient' | 'hard' — drives retry + diagnosis wording."""
    if timed_out:
        return "hang"
    if any(m in stderr_text for m in _TRANSIENT_MARKERS):
        return "transient"
    return "hard"


_PROBE = "import jax; d = jax.devices()[0]; print('PROBE', d.platform, d.device_kind)"


class TpuProber:
    """The one bounded question "which platform does JAX have here?", asked
    in a child so this parent never touches the device; the answer and its
    duration travel in the artifact as ``probe_history``."""

    def __init__(self, probe_timeout_s: float | None = None):
        self.probe_timeout_s = probe_timeout_s if probe_timeout_s is not None \
            else float(os.environ.get("KMLS_BENCH_PROBE_TIMEOUT_S", "120"))
        self.history: list[dict] = []  # {"t_s", "outcome", "dur_s"}

    def probe_once(self) -> str:
        """→ 'tpu' | 'cpu_only' | 'hang' | 'transient_error' | 'error';
        appends to history."""
        t_start = _elapsed()
        outcome = "error"
        detail = ""
        # _tracked_popen (not subprocess.run): the crash handlers must be
        # able to kill a probe child that is alive at driver-kill time —
        # an orphan stuck in backend init would keep holding the chip
        proc = _tracked_popen(
            [sys.executable, "-c", _PROBE],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env={**os.environ, **_cache_env()},
        )
        try:
            stdout_text, stderr_text = proc.communicate(
                timeout=self.probe_timeout_s
            )
            if proc.returncode == 0 and "PROBE" in stdout_text:
                kind = stdout_text.strip().split("PROBE", 1)[1].strip()
                detail = kind
                platform = kind.split()[0] if kind else "unknown"
                outcome = "cpu_only" if platform == "cpu" else "tpu"
            else:
                detail = "\n".join(stderr_text.strip().splitlines()[-3:])
                outcome = (
                    "transient_error"
                    if _classify(stderr_text, False) == "transient"
                    else "error"
                )
        except subprocess.TimeoutExpired:
            _kill_tree(proc)
            proc.communicate()
            outcome = "hang"
            detail = f"probe exceeded {self.probe_timeout_s:.0f}s"
        entry = {
            "t_s": round(t_start, 1),
            "outcome": outcome,
            "dur_s": round(_elapsed() - t_start, 1),
        }
        self.history.append(entry)
        log(f"probe @ t={entry['t_s']:.0f}s: {outcome} ({detail.splitlines()[-1] if detail else ''})")
        return outcome

    def history_snapshot(self) -> list[dict]:
        return list(self.history)


# live phase subprocesses, killed by the crash handlers so a driver TERM
# doesn't leave an orphan holding the TPU chip. Reaped entries are pruned
# opportunistically at the next spawn.
_LIVE_PROCS: "set[subprocess.Popen]" = set()


def _tracked_popen(*args, **kwargs) -> subprocess.Popen:
    for p in [p for p in _LIVE_PROCS if p.poll() is not None]:
        _LIVE_PROCS.discard(p)
    # own process group: phases can spawn grandchildren (the tune phase
    # runs one worker subprocess per config), and killing only the direct
    # child would orphan a grandchild holding the TPU
    kwargs.setdefault("start_new_session", True)
    proc = subprocess.Popen(*args, **kwargs)
    _LIVE_PROCS.add(proc)
    return proc


def _kill_tree(proc: subprocess.Popen) -> None:
    """SIGKILL the phase's whole process group, then the direct child as
    a fallback (never raises)."""
    import signal as _signal

    try:
        os.killpg(proc.pid, _signal.SIGKILL)
    except (OSError, PermissionError):
        pass
    try:
        proc.kill()
    except Exception:
        pass


# hard bound on every stdout artifact line: the driver parses the last
# JSON line within a ~2,000-char tail, and a 2,112-char final line was
# once lost to its own key growth. 1,800 leaves margin for a trailing
# newline + partial flushes.
COMPACT_LINE_LIMIT = 1800

# key order for the compact line: identity + headline first, then the
# judged serving-path numbers, then utilization/scale evidence; anything
# that doesn't fit lives only in the sidecar (which always has everything)
_COMPACT_PRIORITY = (
    "metric", "value", "unit", "vs_baseline", "platform",
    "checkpoint", "aborted", "full_artifact",
    "best_mining_s", "best_mining_platform", "vs_baseline_best",
    "mining_cpu_s", "mining_count_path",
    "replay_target_qps", "replay_achieved_qps", "replay_p50_ms",
    "replay_p95_ms", "replay_p99_ms", "replay_errors",
    "replay10k_qps", "replay10k_achieved_qps", "replay10k_p50_ms",
    "replay10k_p99_ms", "replay10k_errors", "replay10k_cache_hit_ratio",
    "replay10k_cached_p50_ms", "replay10k_uncached_p50_ms",
    "replay10k_devices_active",
    "chaos_qps", "chaos_errors", "chaos_http_5xx", "chaos_degraded_answers",
    "chaos_eject_recovery_ms", "chaos_redispatched",
    "loadshape_p99_ms", "loadshape_errors", "loadshape_http_5xx",
    "loadshape_shed", "loadshape_degraded", "loadshape_offered_qps",
    "loadshape_achieved_qps", "loadshape_p50_ms", "loadshape_burst_factor",
    "loadshape_onset_p99_ms", "loadshape_steady_p99_ms",
    "loadshape_flash_p99_ms", "loadshape_flash_http_5xx",
    "loadshape_flip_http_5xx", "loadshape_flip_errors",
    "loadshape_flip_epoch_moved", "loadshape_flip_singleflight",
    "mine_resume_s", "mine_resume_full_s", "mine_resume_saved_pct",
    "mine_resume_identical", "mine_resume_phase",
    "als_train_s", "hybrid_p50_ms", "hybrid_p99_ms", "hybrid_errors",
    "cold_start_hit_frac", "cold_start_seeds",
    "confserve_p50_ms", "confserve_p99_ms", "confserve_qps",
    "confserve_errors",
    "shardserve_sharded_p50_ms", "shardserve_sharded_p99_ms",
    "shardserve_replicated_p50_ms", "shardserve_replicated_p99_ms",
    "shardserve_identical", "shardserve_shards", "shardserve_unwarmed",
    "shardserve_max_catalog_bytes",
    "scale_shard_mine_s", "scale_shard_rows_per_s",
    "scale_shard_count_path", "scale_shard_shards",
    "replay_queue_wait_p99_ms", "replay_device_p99_ms",
    "replay_queue_wait_p50_ms", "replay_device_p50_ms", "replay_e2e_p999_ms",
    "replay_server_p50_ms", "replay_server_p95_ms", "replay_server_p99_ms",
    "serving_batch32_p50_ms", "serving_batch32_amortized_ms",
    "serving_batch256_p50_ms", "serving_batch256_amortized_ms",
    # judged tracing claims (ratio ≤ 1.05, zero-cost began_off == 0),
    # ranked below the TPU serving evidence; on/off/retained detail
    # lives in the sidecar
    "traceoverhead_p99_ratio", "traceoverhead_began_off",
    # judged freshness claims (ISSUE 10): delta vs full-path speedup
    # (≥ 5x), zero 5xx through the in-place apply, and the 3-replica
    # fleet hit-ratio multiplier — ranked with traceoverhead below the
    # TPU serving evidence (CPU-measured by construction); path/cache
    # detail is sidecar-only, the compact line sits at its budget
    "freshness_speedup", "freshness_http_5xx", "freshness_errors",
    "freshness_publish_to_applied_ms", "freshness_fleet_multiplier",
    # judged predictive-serving claims (ISSUE 17): the paired A/B legs'
    # p99 + onset-window p99 for ramp/sine (predictive must be no worse
    # on both and on shed/degrade at equal capacity), zero 5xx across
    # every leg, and the predictive legs' observation evidence — ranked
    # with the other CPU-measured judged brackets below the TPU serving
    # evidence; steady-window, constant-control and per-leg detail is
    # sidecar-only
    "loadshape_pred_ramp_react_p99_ms", "loadshape_pred_ramp_pred_p99_ms",
    "loadshape_pred_ramp_react_onset_p99_ms",
    "loadshape_pred_ramp_pred_onset_p99_ms",
    "loadshape_pred_sine_react_p99_ms", "loadshape_pred_sine_pred_p99_ms",
    "loadshape_pred_ramp_react_shed", "loadshape_pred_ramp_pred_shed",
    "loadshape_pred_http_5xx", "loadshape_pred_errors",
    "loadshape_pred_ramp_obs",
    # judged fleet cache-routing claims (ISSUE 15): routed vs
    # independent fleet hit ratio on 3 REAL server processes, the
    # multiplier achieved vs the PR 10 simulated prediction (≥ 0.9 of
    # it — one canonical ring on both sides), p99 and zero 5xx through
    # a mid-replay replica kill AND delta apply, with survivor answer
    # identity pinned — ranked with the freshness block below the TPU
    # serving evidence (CPU-measured by construction); per-peer and
    # router detail is sidecar-only
    "fleet_hit_ratio", "fleet_independent_hit_ratio",
    "fleet_multiplier_achieved", "fleet_multiplier_simulated",
    "fleet_p99_ms", "fleet_http_5xx", "fleet_errors",
    "fleet_identity_ok",
    # judged serve-mesh claims (ISSUE 16): gang answers bit-identical to
    # the single-process kernels with zero compiles, max servable
    # catalog = per-host budget x gang size, and zero 5xx / zero drops
    # through a mid-replay gang-member SIGKILL (the refusal + ejection
    # counters prove the shard loss actually happened) — ranked with the
    # fleet block below the TPU serving evidence (CPU-measured by
    # construction, the socket transport stands in for GSPMD-over-DCN);
    # per-peer, budget-bytes and replay detail is sidecar-only
    "meshserve_p50_ms", "meshserve_p99_ms", "meshserve_sharded_p50_ms",
    "meshserve_identical", "meshserve_gang", "meshserve_unwarmed",
    "meshserve_max_catalog_bytes", "meshserve_http_5xx",
    "meshserve_errors", "meshserve_mesh_unavailable", "meshserve_ejections",
    # judged gray-failure claims (ISSUE 18): hedged p99 ≥ 5x better than
    # the no-hedge control through a 200 ms alive-but-late stall at
    # equal capacity, hedge overhead ≤ 5% of dispatches, zero 5xx and
    # zero drops on every leg, answers bit-identical whichever copy wins,
    # and the KMLS_HEDGE=0 zero-cost pin (control leg leaves the module
    # hedge counter at exactly 0 under real traffic) — ranked with the
    # fleet/meshserve blocks below the TPU serving evidence (CPU-measured
    # by construction); per-leg latency and mesh-side detail is
    # sidecar-only
    "slowpeer_p99_ratio", "slowpeer_hedged_p99_ms",
    "slowpeer_control_p99_ms", "slowpeer_hedge_overhead_pct",
    "slowpeer_hedge_wins", "slowpeer_hedge_mismatch",
    "slowpeer_http_5xx", "slowpeer_errors", "slowpeer_identity_ok",
    "slowpeer_control_hedges_issued", "slowpeer_mesh_hedge_wins",
    # judged storage gray-failure claims (ISSUE 19): serving p99 unmoved
    # under the 400 ms PVC read stall, conviction flips /readyz to
    # degraded (never unready), the armed reload parks in bounded
    # backoff holding last-good, and the ENOSPC-mid-publish leg pins
    # exit 75 + bit-identity + zero torn temps — ranked with the
    # slowpeer block (CPU-measured by construction); per-leg latency
    # detail is sidecar-only
    "graystore_p99_ratio", "graystore_storage_slow",
    "graystore_readyz_degraded", "graystore_reload_deferred",
    "graystore_last_good_held", "graystore_enospc_exit_resumable",
    "graystore_enospc_identical", "graystore_torn_parts",
    "graystore_http_5xx", "graystore_errors",
    # judged quality-loop claims (ISSUE 14): held-out recall@k per
    # serving mode (blend at the MEASURED optimum vs both pure modes),
    # the measured weight round-tripping report → bundle → serve time,
    # and the compacted snapshot bit-identical to a full re-mine with
    # zero 5xx through the mid-replay swap — ranked with the freshness/
    # costattrib blocks below the TPU serving evidence (CPU-measured by
    # construction); sweep-curve/MRR/coverage detail is sidecar-only
    "quality_recall_blend", "quality_recall_rules", "quality_recall_embed",
    "quality_blend_weight", "quality_weight_roundtrip",
    "quality_compact_identical", "quality_compact_s",
    "quality_compact_speedup", "quality_http_5xx", "quality_errors",
    # judged sparsity-adaptive claims (ISSUE 13): ≥5x over the native
    # record path on the SAME ≥99%-sparse workload (density carries the
    # ≥99% part), every route bit-identical, and the auto dispatch
    # resolving from the measured table — ranked with the freshness/
    # costattrib blocks below the TPU serving evidence (CPU-measured by
    # construction); rows/s, shape and table detail are sidecar-only
    "sparse_speedup_vs_native", "sparse_identical",
    "sparse_headline_identical", "sparse_density",
    "sparse_auto_path", "sparse_auto_source",
    # judged cost-attribution claims (ISSUE 12): serve-kernel MFU +
    # roofline class (CPU-labeled unless the phase ran on the chip),
    # live compiles==0 post-publish, and the
    # disabled-mode zero-observation proof; rate/detail keys are
    # sidecar-only like the traceoverhead/freshness detail
    "costattrib_mfu", "costattrib_roofline", "costattrib_compiles",
    "costattrib_obs_off",
    "mining_mfu_pct", "mining_mfu_peak_tops", "mining_matmul_gops_per_s",
    "config4_mine_s", "config4_rows_per_s", "scale_1m_x_100k_mine_s",
    "popcount_words_per_s", "sweep_points",
    "tpu_suite_from_bank", "tpu_bank_age_s",
)


def _compact_line(full: dict, limit: int = COMPACT_LINE_LIMIT) -> str:
    """Serialize ``full`` into a JSON line guaranteed ≤ ``limit`` chars:
    keys added greedily in priority order (then insertion order) while the
    serialized line still fits. The full dict always reaches the sidecar;
    this bounds only what rides stdout past the driver's tail window."""
    ordered = [k for k in _COMPACT_PRIORITY if k in full]
    seen = set(ordered)
    ordered += [k for k in full if k not in seen]
    out: dict = {}
    line = "{}"
    for key in ordered:
        candidate = json.dumps({**out, key: full[key]})
        if len(candidate) <= limit:
            out[key] = full[key]
            line = candidate
    return line


class ArtifactEmitter:
    """Crash-proof artifact emission.

    Holds the headline mining result + every optional phase's keys
    (``extras``) and prints an artifact line on every :meth:`checkpoint` —
    the driver parses the last JSON line on stdout, so each print strictly
    supersedes the previous one. Stdout lines are the COMPACT projection
    (≤ 1,800 chars — a longer line overruns the driver's tail and is
    lost) with the complete artifact
    mirrored to a sidecar file (``KMLS_BENCH_SIDECAR``, default
    ``bench_full.json``) on every emission. Signal-handler
    emissions (``note`` set) are prefixed with a newline so they land on
    a fresh line even if the signal interrupted the main thread
    mid-write; normal checkpoints don't need it (the emitter is the only
    stdout writer in this process), keeping the captured stream valid
    line-per-record JSONL. Thread-safe (the SIGTERM handler and the main
    thread both emit); RLock because the handler can fire while the main
    thread is mid-checkpoint.
    """

    def __init__(self, prober: TpuProber | None = None):
        self._lock = threading.RLock()
        self.prober = prober
        self.platform: str | None = None
        self.mining: dict | None = None
        self.cpu_mining: dict | None = None
        self.extras: dict = {}
        self._finalized = False
        self._last_printed: str | None = None
        # every stdout line is the COMPACT projection (≤ 1,800 chars so the
        # driver's tail can never lose it); the complete artifact goes to
        # this sidecar on every checkpoint. The default name is
        # per-PROCESS: two invocations can share one cwd (the same
        # topology the bank's merge-on-write exists for), and a fixed
        # shared name would let them clobber each other's artifact while
        # both compact lines point at it. Empty string disables the
        # sidecar (stdout stays compact regardless).
        self.sidecar_path = (
            os.environ.get(
                "KMLS_BENCH_SIDECAR", f"bench_full_{os.getpid()}.json"
            ) or None
        )
        self._sidecar_ok = False

    def _write_sidecar(self, line: dict) -> None:
        if self.sidecar_path is None:
            return
        tmp = self.sidecar_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(line, f, indent=1)
            os.replace(tmp, self.sidecar_path)
            self._sidecar_ok = True
        except OSError as exc:
            # drop the pointer too: advertising full_artifact after a
            # failed write would hand consumers a STALE sidecar missing
            # this checkpoint's keys
            self._sidecar_ok = False
            log(f"sidecar write failed ({exc}); stdout line still emitted")

    def _render(self, line: dict) -> str:
        self._write_sidecar(line)
        if self._sidecar_ok:
            line = {**line, "full_artifact": self.sidecar_path}
        return _compact_line(line)

    def set_headline(self, platform: str, mining: dict) -> None:
        with self._lock:
            self.platform = platform
            self.mining = mining
        self.checkpoint()

    def set_cpu_comparison(self, cpu_mining: dict | None) -> None:
        with self._lock:
            self.cpu_mining = cpu_mining
        self.checkpoint()

    def compose(self, *, checkpoint: bool, note: str | None = None) -> dict | None:
        with self._lock:
            if self.mining is None:
                return None  # nothing judgeable yet — never print a dud line
            line = _headline_keys(self.platform, self.mining, self.cpu_mining)
            line.update(self.extras)
            if self.prober is not None:
                line["probe_history"] = self.prober.history_snapshot()
            if checkpoint:
                line["checkpoint"] = True
            if note:
                line["aborted"] = note
            return line

    def checkpoint(self, note: str | None = None) -> None:
        """Print the best-so-far artifact line (no-op before the headline
        exists or after finalize)."""
        with self._lock:
            if self._finalized:
                return
            line = self.compose(checkpoint=True, note=note)
            if line is None:
                return
            s = self._render(line)
            if s == self._last_printed:
                return
            sys.stdout.write(("\n" if note else "") + s + "\n")
            sys.stdout.flush()
            self._last_printed = s

    def finalize(self) -> bool:
        """Print the final line (checkpoint flag absent). → False when no
        headline was ever captured."""
        with self._lock:
            line = self.compose(checkpoint=False)
            if line is None:
                return False
            sys.stdout.write(self._render(line) + "\n")
            sys.stdout.flush()
            self._finalized = True
            return True

    def ever_printed(self) -> bool:
        """True once ANY artifact line (checkpoint or final) reached
        stdout — the signal handler's exit-code discriminator."""
        with self._lock:
            return self._last_printed is not None or self._finalized


def _install_crash_handlers(emitter: ArtifactEmitter) -> None:
    """SIGTERM/SIGINT/atexit → flush the best-so-far line, kill live phase
    subprocesses, exit. This is the mechanism that makes a driver kill at
    ANY time after the first mining result still yield a parsed artifact."""
    import atexit
    import signal

    def _flush(signum=None, frame=None):
        emitter.checkpoint(
            note=f"signal {signum} at t={_elapsed():.0f}s" if signum else None
        )
        for p in list(_LIVE_PROCS):
            _kill_tree(p)
        if signum is not None:
            sys.stdout.flush()
            sys.stderr.flush()
            # a kill BEFORE the first artifact line must not look like a
            # clean run: rc 0 is reserved for runs that flushed at least
            # one checkpoint
            os._exit(0 if emitter.ever_printed() else 128 + signum)

    atexit.register(_flush)
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _flush)
        except (ValueError, OSError):
            pass  # non-main thread / exotic platform: atexit still covers


class BenchState:
    """Cross-invocation TPU phase bank.

    A chip call is bounded, and the full suite may not fit one: when a bank
    file is named (``KMLS_BENCH_STATE``), every completed TPU-suite phase
    banks its raw result dict there (atomic tmp+rename, the io/artifacts.py
    discipline) and the next invocation on the chip replays banked phases
    into the artifact line — each stamped ``<phase>_from_bank`` with its
    age — instead of re-running them. The mining phase also banks its
    rule-tensor npz (sidecar ``<path>.npz``) so the serving phase still has
    its input when mining itself is skipped. Phases older than
    ``KMLS_BENCH_STATE_MAX_AGE_S`` (default 12 h) are dropped at load so a
    stale bank can't leak into a fresh artifact. No path → no-op. A bank is
    only ever read by a run that holds the chip: without one, main() exits
    before any suite.
    """

    MAX_AGE_S = 43200.0

    def _max_age_s(self) -> float:
        # env read at call time, not import time (envread checker)
        return float(
            os.environ.get("KMLS_BENCH_STATE_MAX_AGE_S", str(self.MAX_AGE_S))
        )

    def __init__(self, path: str | None):
        self.path = path
        self.phases: dict = {}
        self.banked_at: dict = {}
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
                if not isinstance(data, dict) or not isinstance(
                    data.get("phases"), dict
                ):
                    raise ValueError("not a phase-bank object")
                self.phases = dict(data["phases"])
                # every writer stamps banked_at (v2); an entry WITHOUT a
                # numeric timestamp is a legacy v1 bank (or a corrupted
                # one) of unknowable age — treat it as stale, never as
                # fresh: a timestampless entry would otherwise replay
                # into every artifact that names this bank, forever
                meta = data.get("banked_at")
                meta = meta if isinstance(meta, dict) else {}
                self.banked_at = {
                    n: t for n, t in meta.items()
                    if isinstance(t, (int, float))
                }
                now = time.time()
                stale = [
                    n for n in self.phases
                    if self.banked_at.get(n) is None
                    or now - self.banked_at[n] > self._max_age_s()
                ]
                for n in stale:
                    self.phases.pop(n, None)
                    self.banked_at.pop(n, None)
                if stale:
                    log(
                        f"state bank {path}: dropped stale phases "
                        f"{sorted(stale)} (> {self._max_age_s():.0f}s old)"
                    )
                log(
                    f"state bank {path}: resuming with "
                    f"{sorted(self.phases)} already banked"
                )
            except (OSError, ValueError, TypeError) as exc:
                log(f"state bank {path} unreadable ({exc}); starting fresh")
                self.phases = {}
                self.banked_at = {}

    @property
    def npz_path(self) -> str | None:
        return self.path + ".npz" if self.path else None

    def get(self, name: str) -> dict | None:
        return self.phases.get(name)

    def age_s(self, name: str) -> float | None:
        t = self.banked_at.get(name)
        return None if t is None else max(0.0, time.time() - t)

    def bank(self, name: str, result: dict) -> None:
        if self.path is None:
            return
        self.phases[name] = result
        self.banked_at[name] = time.time()
        # merge-on-write: two invocations can name one bank — a blind dump
        # of this process's view would erase phases the other process
        # banked since our load. NEWEST banked_at wins regardless of
        # origin: "own names win" would let a process overwrite a fresher
        # on-disk result with the stale copy it merely loaded at startup.
        # The phase just banked above carries a timestamp of now, so it
        # wins its own name naturally.
        phases, banked_at = dict(self.phases), dict(self.banked_at)
        try:
            with open(self.path) as f:
                disk = json.load(f)
            if isinstance(disk, dict) and isinstance(disk.get("phases"), dict):
                disk_at = disk.get("banked_at")
                disk_at = disk_at if isinstance(disk_at, dict) else {}
                for other, res in disk["phases"].items():
                    disk_t = disk_at.get(other)
                    if not isinstance(disk_t, (int, float)):
                        continue  # timestampless disk entry = stale
                    ours_t = banked_at.get(other)
                    if other not in phases or ours_t is None or disk_t > ours_t:
                        phases[other] = res
                        banked_at[other] = disk_t
        except (OSError, ValueError, TypeError):
            pass  # no readable disk copy to merge — write ours
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump({"version": 2, "phases": phases,
                           "banked_at": banked_at}, f)
            os.replace(tmp, self.path)
        except OSError as exc:
            log(f"state bank write failed ({exc}); {name} not banked")


def _resolve_state_path() -> str | None:
    """``KMLS_BENCH_STATE`` names the bank; unset or empty → no bank."""
    return os.environ.get("KMLS_BENCH_STATE") or None


STATE = BenchState(_resolve_state_path())


def _acquire_tpu_lock(timeout_s: float):
    """One TPU suite at a time per bank: a chip belongs to one process,
    and a second suite started against the same bank would fail or hang
    on it. → an open fd holding the flock, the sentinel "nolock" when no
    bank is configured (nothing to coordinate through), or None when the
    lock stayed held past timeout_s (the caller cannot have the chip)."""
    if STATE.path is None:
        return "nolock"
    import fcntl

    try:
        fd = open(STATE.path + ".lock", "w")
    except OSError as exc:
        # an unwritable bank path was always tolerated (BenchState.bank
        # just logs) — the lock must not be stricter than the bank
        log(f"TPU-suite lock unavailable ({exc}); proceeding unlocked")
        return "nolock"
    deadline = time.monotonic() + max(timeout_s, 0.0)
    while True:
        try:
            fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            return fd
        except BlockingIOError:
            if time.monotonic() >= deadline:
                fd.close()
                return None
            time.sleep(5)
        except OSError as exc:
            # flock itself unsupported (e.g. NFS without lockd): that is
            # not contention — don't burn the deadline or fake a fallback
            fd.close()
            log(f"TPU-suite lock unsupported here ({exc}); proceeding unlocked")
            return "nolock"


def _release_tpu_lock(lock) -> None:
    if lock is None or lock == "nolock":
        return
    import fcntl

    try:
        fcntl.flock(lock, fcntl.LOCK_UN)
    finally:
        lock.close()


def _banked(
    name: str, runner, budget_s: float | None = None,
    extras: dict | None = None,
) -> dict | None:
    """Replay ``name`` from the state bank, or run it live and bank the
    result. A banked phase replays for free — even past the deadline gate;
    a live run happens only with ``budget_s`` of deadline headroom (None =
    no gate, the caller gates).

    A replayed phase stamps ``<name>_from_bank`` / ``<name>_bank_age_s``
    into ``extras`` (the artifact's extra-key dict) so a mixed artifact —
    fresh mining next to hours-old banked phases — says which numbers came
    from which invocation."""
    cached = STATE.get(name)
    if cached is not None:
        log(f"{name}: banked by an earlier invocation — skipping live run")
        if extras is not None:
            extras[f"{name}_from_bank"] = True
            age = STATE.age_s(name)
            if age is not None:
                extras[f"{name}_bank_age_s"] = round(age)
        return dict(cached)
    if budget_s is not None and _remaining() <= budget_s:
        return None
    result = runner()
    if result is not None:
        STATE.bank(name, result)
    return result


_MINING_BENCH = r"""
import json, statistics, sys, time
from functools import partial
import numpy as np
from kmlserver_tpu.config import MiningConfig
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_baskets
from kmlserver_tpu.mining.miner import mine

out_npz, min_support, repeats = sys.argv[1], float(sys.argv[2]), int(sys.argv[3])

import jax
import jax.numpy as jnp
dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)

baskets = synthetic_baskets(**DS2_SHAPE, seed=123)
print(
    f"workload: {len(baskets.playlist_rows)} memberships, "
    f"{baskets.n_playlists} playlists, {baskets.n_tracks} tracks, "
    f"min_support {min_support} (ds2 shape)", file=sys.stderr, flush=True,
)
cfg = MiningConfig(min_support=min_support, k_max_consequents=256)

# warm-up: compile every kernel in the bracket
result = mine(baskets, cfg)
result.tensors.to_rules_dict(result.vocab_names)
print(f"warm-up mine: {result.duration_s:.3f}s (includes compile)",
      file=sys.stderr, flush=True)

times = []
for i in range(repeats):
    t0 = time.perf_counter()
    result = mine(baskets, cfg)
    rules_dict = result.tensors.to_rules_dict(result.vocab_names)
    times.append(time.perf_counter() - t0)
    print(f"run {i}: {times[-1]:.3f}s ({len(rules_dict)} rule keys)",
          file=sys.stderr, flush=True)
print(
    "phase timings (last run): "
    + ", ".join(
        f"{k} {v * 1e3:.0f}ms"
        for k, v in (result.phase_timings or {}).items()
    ),
    file=sys.stderr, flush=True,
)

# isolated MXU pair-count matmul with a closed-form op count — the anchor
# for a utilization (MFU) judgement the full bracket can't provide (it
# includes host-side rule-dict expansion). ops = 2·P·V² (V² output cells,
# P int8 MACs each, 2 ops/MAC), per ops/support.py pair_counts.
from kmlserver_tpu.ops import encode, support
pr, ti = jnp.asarray(baskets.playlist_rows), jnp.asarray(baskets.track_ids)
x = jax.jit(partial(
    encode.onehot_matrix,
    n_playlists=baskets.n_playlists, n_tracks=baskets.n_tracks,
))(pr, ti)
support.pair_counts(x).block_until_ready()  # compile
mm = []
for _ in range(20):
    t0 = time.perf_counter()
    support.pair_counts(x).block_until_ready()
    mm.append(time.perf_counter() - t0)
matmul_s = statistics.median(mm)
# Device-resident chained timing — the honest MFU numerator. N matmuls run
# inside ONE compiled scan, each iteration data-dependent on the last
# (min(counts[0,0], 0) is always 0 at runtime but not provably so at
# compile time, so XLA can neither fold the chain nor overlap iterations),
# and the fetched scalar sums the carry so the host read cannot complete
# before all N iterations have. Timing the scan at two lengths and taking
# the slope cancels the per-call constants — dispatch cost, the host
# round trip, async acknowledgement — that a sub-millisecond matmul
# cannot be separated from otherwise (overlapping dispatches timed as a
# batch once "measured" 177% MFU — physically impossible — and
# per-blocked-call timing is floored by the round trip instead).
if dev.platform == "tpu":
    @partial(jax.jit, static_argnames=("n",))
    def _chained(x0, n):
        def step(carry, _):
            counts = support.pair_counts(carry)
            bump = jnp.minimum(counts[0, 0], 0).astype(carry.dtype)
            return carry + bump, ()
        out, _ = jax.lax.scan(step, x0, None, length=n)
        return jnp.sum(out, dtype=jnp.int32)

    def _timed_chain(n):
        float(jax.device_get(_chained(x, n)))  # compile + warm
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(jax.device_get(_chained(x, n)))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    N1, N2 = 16, 1016
    t_short, t_long = _timed_chain(N1), _timed_chain(N2)
    slope = (t_long - t_short) / (N2 - N1)
    # noise guard: a non-positive slope means the two timings were
    # indistinguishable — fall back to the blocked per-call median
    matmul_amortized_s = slope if slope > 0 else matmul_s
    # the slope's raw inputs travel with the artifact so the MFU number is
    # auditable
    chain_keys = {"chain_n1": N1, "chain_n2": N2,
                  "chain_t_short_s": t_short, "chain_t_long_s": t_long}
    print(f"isolated pair-count matmul: {matmul_s * 1e3:.3f}ms/call "
          f"blocked, {matmul_amortized_s * 1e3:.3f}ms/iter from the "
          f"{N2}-vs-{N1} chained-scan slope "
          f"(t({N1})={t_short:.4f}s, t({N2})={t_long:.4f}s)",
          file=sys.stderr, flush=True)
else:
    # CPU: per-call cost (~1s) dwarfs dispatch overhead; a short async
    # pipeline amortizes what little there is without chained compiles
    N_AMORT = 10
    t0 = time.perf_counter()
    rs = [support.pair_counts(x) for _ in range(N_AMORT)]
    jax.block_until_ready(rs)
    matmul_amortized_s = (time.perf_counter() - t0) / N_AMORT
    chain_keys = {}
    print(f"isolated pair-count matmul: {matmul_s * 1e3:.3f}ms/call "
          f"blocked, {matmul_amortized_s * 1e3:.3f}ms amortized over "
          f"{N_AMORT}", file=sys.stderr, flush=True)

np.savez(out_npz, rule_ids=result.tensors.rule_ids,
         rule_confs=result.tensors.rule_confs)
print(json.dumps({
    "median_s": statistics.median(times),
    "matmul_s": matmul_s,
    "matmul_amortized_s": matmul_amortized_s,
    "n_playlists": baskets.n_playlists,
    "n_tracks": baskets.n_tracks,
    "device_kind": dev.device_kind,
    "platform": dev.platform,
    "count_path": result.count_path,
    **chain_keys,
}))
"""

# popcount kernel evidence. argv: [mode, n_playlists, n_tracks, target_rows]
#   mode "compiled"  — real TPU kernel (interpret=False), ds2 shape
#   mode "interpret" — CPU stand-in (interpret=True), small shape, so a
#     CPU-only round still carries config-4 kernel evidence
# Both assert count equality vs the dense MXU path and report the
# closed-form word-op count (V_pad²·W_pad) → words/s.
_POPCOUNT_BENCH = r"""
import json, statistics, sys, time
import numpy as np
import jax, jax.numpy as jnp
from kmlserver_tpu.data.synthetic import synthetic_baskets
from kmlserver_tpu.ops import encode, support
from kmlserver_tpu.ops import popcount as pc

mode = sys.argv[1]
n_playlists, n_tracks, target_rows = map(int, sys.argv[2:5])
interpret = mode == "interpret"

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind}), mode={mode}",
      file=sys.stderr, flush=True)
baskets = synthetic_baskets(
    n_playlists=n_playlists, n_tracks=n_tracks, target_rows=target_rows,
    seed=123)
pr = jnp.asarray(baskets.playlist_rows)
ti = jnp.asarray(baskets.track_ids)
kw = dict(n_playlists=baskets.n_playlists, n_tracks=baskets.n_tracks)

dense_fn = jax.jit(lambda a, b: support.pair_counts(encode.onehot_matrix(a, b, **kw)))
dense = dense_fn(pr, ti)
dense.block_until_ready()  # warm-up/compile

def med(fn, n=5):
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn().block_until_ready()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e3

def amortized(fn, n=20):
    # pipeline n async dispatches, block once: device throughput without
    # the per-call host<->device round trip
    fn().block_until_ready()
    t0 = time.perf_counter()
    jax.block_until_ready([fn() for _ in range(n)])
    return (time.perf_counter() - t0) / n * 1e3

# closed-form kernel work: every (i, j) output tile row processes W_pad
# words (AND + popcount + accumulate per word) → V_pad² · W_pad word-ops
v_pad, w_pad = pc.padded_shape(baskets.n_tracks, baskets.n_playlists)
word_ops = v_pad * v_pad * w_pad

reps = 2 if interpret else 5

# the production-default bit-packed impl: blocked unpack-matmul on the MXU
# (pure XLA — native on every backend, never interpreted)
mxu_keys = {}
mxu_fn = lambda: pc.popcount_pair_counts(
    baskets.playlist_rows, baskets.track_ids, impl="mxu", **kw)
try:
    res = mxu_fn()
    res.block_until_ready()
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(res))
    print("bitpack[mxu] == dense (compiled): EXACT", file=sys.stderr, flush=True)
    mxu_keys["mxu_ms"] = med(mxu_fn, n=reps)
    mxu_keys["mxu_words_per_s"] = word_ops / (mxu_keys["mxu_ms"] / 1e3)
except Exception as exc:
    print(f"bitpack[mxu] failed: {type(exc).__name__}: "
          f"{(str(exc).splitlines() or [repr(exc)])[0][:300]}", file=sys.stderr, flush=True)

# the Pallas VPU kernel: try each (variant, popcount-impl) config until one
# compiles AND matches the dense counts exactly; report which. (Mosaic
# lowering can't be pre-verified off-hardware.)
chosen = None
for variant, swar in (("bcast", False), ("row", False),
                      ("bcast", True), ("row", True)):
    label = f"{variant}{'-swar' if swar else ''}"
    try:
        res = pc.popcount_pair_counts(
            baskets.playlist_rows, baskets.track_ids, impl="vpu",
            interpret=interpret, variant=variant, swar=swar, **kw)
        res.block_until_ready()
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(res))
        print(f"popcount[{label}] == dense ({mode}): EXACT",
              file=sys.stderr, flush=True)
        chosen = (variant, swar, label)
        break
    except Exception as exc:
        print(f"popcount[{label}] failed: {type(exc).__name__}: "
              f"{(str(exc).splitlines() or [repr(exc)])[0][:300]}", file=sys.stderr, flush=True)
if chosen is None and not mxu_keys:
    print("all bit-packed counting impls failed to compile/run on this backend",
          file=sys.stderr, flush=True)
    sys.exit(1)

dense_ms = med(lambda: dense_fn(pr, ti))
if chosen is not None:
    variant, swar, label = chosen
    pc_fn = lambda: pc.popcount_pair_counts(
        baskets.playlist_rows, baskets.track_ids, impl="vpu",
        interpret=interpret, variant=variant, swar=swar, **kw)
    pc_ms = med(pc_fn, n=reps)
else:
    # VPU kernel unusable here; the MXU impl carries the popcount keys
    label = "mxu"
    pc_fn = mxu_fn
    pc_ms = mxu_keys["mxu_ms"]
out = {
    "dense_ms": dense_ms, "popcount_ms": pc_ms, "exact": True,
    "kernel": label, "mode": mode,
    "v_pad": v_pad, "w_pad": w_pad, "word_ops": word_ops,
    "words_per_s": word_ops / (pc_ms / 1e3),
    "shape": f"{n_playlists}x{n_tracks}",
}
out.update(mxu_keys)
if not interpret:
    # the kernel's true device rate (interpret mode is host-python slow,
    # amortizing it tells nothing)
    pc_amort_ms = amortized(pc_fn)
    dense_amort_ms = amortized(lambda: dense_fn(pr, ti))
    out["popcount_amortized_ms"] = pc_amort_ms
    out["dense_amortized_ms"] = dense_amort_ms
    out["words_per_s"] = word_ops / (pc_amort_ms / 1e3)
    out["words_per_s_blocked"] = word_ops / (pc_ms / 1e3)
    if mxu_keys:
        # when the VPU kernel failed entirely, pc_fn IS mxu_fn and the
        # amortized number above already measured it — don't pay another
        # 20 dispatches for a copy
        out["mxu_amortized_ms"] = (
            amortized(mxu_fn) if chosen is not None else pc_amort_ms
        )
        out["mxu_words_per_s"] = word_ops / (out["mxu_amortized_ms"] / 1e3)
print(json.dumps(out))
"""

_SERVING_BENCH = r"""
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp
from kmlserver_tpu.ops.serve import recommend_batch

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
with np.load(sys.argv[1]) as z:
    rule_ids = jax.device_put(jnp.asarray(z["rule_ids"]))
    rule_confs = jax.device_put(jnp.asarray(z["rule_confs"]))
v = rule_ids.shape[0]
rng = np.random.default_rng(0)
seeds = jnp.asarray(rng.integers(0, v, size=(32, 8), dtype=np.int32))
recommend_batch(rule_ids, rule_confs, seeds, k_best=10)[0].block_until_ready()
lat = []
for _ in range(50):
    t0 = time.perf_counter()
    recommend_batch(rule_ids, rule_confs, seeds, k_best=10)[0].block_until_ready()
    lat.append(time.perf_counter() - t0)
lat.sort()
# pipelined rate: batches/s the device could sustain if requests kept the
# queue full (per-call p50 includes one full host<->device round trip)
t0 = time.perf_counter()
jax.block_until_ready([
    recommend_batch(rule_ids, rule_confs, seeds, k_best=10)[0]
    for _ in range(50)
])
amortized_ms = (time.perf_counter() - t0) / 50 * 1e3
# batch 256: the TPU replay's config (KMLS_BATCH_MAX_SIZE=256 — the
# batcher self-sizes toward this under backpressure); its on-device
# time anchors the throughput claim (256/amortized_s QPS/batch)
seeds256 = jnp.asarray(rng.integers(0, v, size=(256, 8), dtype=np.int32))
recommend_batch(rule_ids, rule_confs, seeds256, k_best=10)[0].block_until_ready()
lat256 = []
for _ in range(20):
    t0 = time.perf_counter()
    recommend_batch(rule_ids, rule_confs, seeds256, k_best=10)[0].block_until_ready()
    lat256.append(time.perf_counter() - t0)
lat256.sort()
t0 = time.perf_counter()
jax.block_until_ready([
    recommend_batch(rule_ids, rule_confs, seeds256, k_best=10)[0]
    for _ in range(20)
])
amortized256_ms = (time.perf_counter() - t0) / 20 * 1e3
print(json.dumps({"p50_ms": lat[len(lat) // 2] * 1e3,
                  "amortized_ms": amortized_ms,
                  "p50_256_ms": lat256[len(lat256) // 2] * 1e3,
                  "amortized_256_ms": amortized256_ms}))
"""

# run scripts/scale_demo.py under _run_phase's retry/diagnosis machinery
# (cwd is the repo root, set by _run_phase)
_SCALE_BENCH = r"""
import runpy, sys
sys.argv = ["scale_demo"] + sys.argv[1:]
runpy.run_path("scripts/scale_demo.py", run_name="__main__")
"""

# BASELINE config 4 (10M×1M) with the workload born in HBM as a
# Bernoulli-Zipf bitset (scripts/config4_tpu.py --device-gen): no host
# generation, no bulk transfer — it fits one bounded chip call
_CONFIG4_BENCH = r"""
import runpy, sys
sys.argv = ["config4_tpu"] + sys.argv[1:]
runpy.run_path("scripts/config4_tpu.py", run_name="__main__")
"""

# the reference's 68-point support sweep (machine-learning/main.py:450-473
# grid) through the count-once harness, on-device
# on-hardware tile sweep for the Pallas VPU kernel:
# scripts/popcount_tune.py runs each (variant, tile) config in its own
# subprocess and prints checkpoint + winner lines. The parent process
# must NOT import jax — holding a live TPU client would wedge every
# worker on a single-tenant chip — so the watchdog's "device:" match is
# satisfied with a sentinel; real backend-hang protection is each
# worker's own --timeout, and the workers' true device lines are relayed
# as they finish.
_TUNE_BENCH = r"""
import runpy, sys
print("device: pending (tune workers own the chip)", file=sys.stderr, flush=True)
sys.argv = ["popcount_tune", "--timeout", "300"] + sys.argv[1:]
runpy.run_path("scripts/popcount_tune.py", run_name="__main__")
"""

_SWEEP_BENCH = r"""
import json, os, sys, tempfile, time
import numpy as np
import jax
from kmlserver_tpu.config import MiningConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.sweep import run_sweep

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
with tempfile.TemporaryDirectory() as base:
    csv = os.path.join(base, "2023_spotify_ds2.csv")
    write_tracks_csv(csv, synthetic_table(**DS2_SHAPE, seed=123))
    cfg = MiningConfig(base_dir=base, datasets_dir=base)
    supports = np.arange(0.03, 0.2, 0.0025)  # the reference grid
    t0 = time.perf_counter()
    records = run_sweep(cfg, supports, dataset=csv)
    total_s = time.perf_counter() - t0
emission_s = sum(r["duration_s"] for r in records)
print(json.dumps({
    "points": len(records),
    "total_s": round(total_s, 3),
    "emission_total_s": round(emission_s, 3),
    "setup_plus_count_s": round(total_s - emission_s, 3),
    "missing_at_min_support": records[0]["missing_songs"],
    "missing_at_max_support": records[-1]["missing_songs"],
    "platform": dev.platform,
}))
"""

_CSV_SETUP = r"""
import sys
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
write_tracks_csv(sys.argv[1], synthetic_table(**DS2_SHAPE, seed=123))
print("{}")
"""

# the 10k-QPS throughput phase: in-process (cache → batcher → engine, the
# same path both HTTP front ends serve) with a Zipf-skewed query mix —
# real playlist-seed traffic repeats its head, which is what the
# epoch-keyed answer cache feeds on. In-process because at 10k QPS an HTTP
# loadgen on this syscall-taxed sandbox measures the loadgen, not the
# server (the 1k replay phase keeps the full-stack HTTP bracket).
_REPLAY10K_BENCH = r"""
import dataclasses, json, os, sys, tempfile
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.replay import replay_pooled, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
qps = float(os.environ.get("KMLS_BENCH_REPLAY10K_QPS", "10000"))
n_req = int(os.environ.get("KMLS_BENCH_REPLAY10K_REQUESTS", "40000"))
zipf_s = float(os.environ.get("KMLS_BENCH_REPLAY10K_ZIPF_S", "1.1"))
with tempfile.TemporaryDirectory(prefix="kmls_replay10k_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    run_mining_job(
        MiningConfig(base_dir=base, datasets_dir=ds_dir, min_support=0.05)
    )
    # shedding off for this bracket: overload must surface as LATENCY
    # (replay_pooled times from the scheduled arrival), not as 429 drops
    # that would void the zero-errors claim while hiding the tail
    cfg = dataclasses.replace(
        ServingConfig.from_env(), base_dir=base,
        batch_max_size=64, shed_queue_budget_ms=0.0,
    )
    app = RecommendApp(cfg)
    assert app.engine.load(), "mined artifacts must load"

    def make_send():
        def send(seeds):
            recs, source, cached = app.recommend_direct(seeds)
            return source, cached
        return send

    vocab = app.engine.bundle.vocab
    payloads = sample_seed_sets(vocab, n_req, rng_seed=11, zipf_s=zipf_s)
    # warm the answer cache + jit/native paths with the same Zipf pool
    # (steady state is what 10k QPS sustains; the measured hit ratio
    # below still comes only from the measured run's own responses)
    replay_pooled(
        make_send, payloads[: min(4000, n_req)], qps=qps / 4, n_workers=16
    )
    # 16 workers, not 64: with a warm cache most requests are dictionary
    # lookups, and on a small host the extra threads only convoy on the
    # GIL — measured here, 64 workers capped the whole phase at ~5.3k
    # QPS while 16 clear the target with headroom
    report = replay_pooled(
        make_send, payloads, qps=qps, n_workers=16, max_queue=8192
    )
    counts = list(app.engine.dispatch_counts)
    print(json.dumps({
        "qps": qps,
        "offered_qps": report.offered_qps,
        "achieved_qps": report.achieved_qps,
        "p50_ms": report.p50_ms,
        "p95_ms": report.p95_ms,
        "p99_ms": report.p99_ms,
        "errors": report.n_errors,
        "cache_hit_ratio": report.cache_hit_ratio,
        "cached_p50_ms": report.cached_p50_ms,
        "uncached_p50_ms": report.uncached_p50_ms,
        "zipf_s": zipf_s,
        "per_device_dispatch": counts,
        "devices_active": sum(1 for c in counts if c > 0),
        "n_replicas": app.engine.n_replicas,
        "platform": dev.platform,
    }))
"""

# the chaos phase: 1k-QPS replay through cache → batcher → two engine
# replicas while one replica is KILLED mid-run (permanent kernel fault via
# kmlserver_tpu/faults.py). Reports recovery time (kill → circuit-breaker
# ejection), degraded-answer count, and — the acceptance bar — zero 5xx /
# zero errors: every request is answered from the surviving replica
# (re-dispatch) or degrades to the popularity fallback. In-process for the
# same reason as replay10k: at QPS scale an HTTP loadgen on this sandbox
# measures the loadgen.
_CHAOS_BENCH = r"""
import dataclasses, json, os, sys, tempfile, threading, time
import jax
from kmlserver_tpu import faults
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.replay import replay_pooled, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
qps = float(os.environ.get("KMLS_BENCH_CHAOS_QPS", "1000"))
n_req = int(os.environ.get("KMLS_BENCH_CHAOS_REQUESTS", "8000"))
zipf_s = float(os.environ.get("KMLS_BENCH_CHAOS_ZIPF_S", "1.1"))
with tempfile.TemporaryDirectory(prefix="kmls_chaos_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    run_mining_job(
        MiningConfig(base_dir=base, datasets_dir=ds_dir, min_support=0.05)
    )
    # two device-path replicas (the native host kernel is single-replica
    # by design); shedding off so overload surfaces as latency, not 429s
    # that would muddy the zero-errors claim; a generous deadline so only
    # a genuine stall degrades, and a probe interval past the run length
    # so the killed replica stays out (recovery time stays well-defined)
    cfg = dataclasses.replace(
        ServingConfig.from_env(), base_dir=base,
        serve_devices=2,
        batch_max_size=64, shed_queue_budget_ms=0.0,
        replica_eject_threshold=3, replica_probe_interval_s=3600.0,
        # >= eject_threshold: a request can be failed at most
        # eject_threshold times by one sick replica before the breaker
        # removes it, so this bound guarantees zero request deaths
        redispatch_max_retries=3,
        request_deadline_ms=2000.0,
    )
    app = RecommendApp(cfg)
    assert app.engine.load(), "mined artifacts must load"
    assert app.engine.n_replicas == 2, "two serving replicas required"
    http_5xx = [0]
    lock = threading.Lock()

    def make_send():
        def send(seeds):
            status, headers, _ = app.handle(
                "POST", "/api/recommend/",
                json.dumps({"songs": seeds}).encode(),
            )
            if status >= 500:
                with lock:
                    http_5xx[0] += 1
                raise RuntimeError(f"HTTP {status}")
            if status != 200:
                raise RuntimeError(f"HTTP {status}")
            return ("degraded" if "X-KMLS-Degraded" in headers else "ok"), None
        return send

    vocab = app.engine.bundle.vocab
    # the same Zipf-skewed mix replay10k uses (real playlist-seed traffic
    # repeats its head): cache hits resolve inline, misses exercise the
    # batcher/replica path — the killed replica is hit by every miss
    payloads = sample_seed_sets(vocab, n_req, rng_seed=7, zipf_s=zipf_s)
    # 32 workers, unlike replay10k's 16: these sends BLOCK on the batch
    # future (device path, near-zero cache hits on distinct seeds), so
    # worker count caps concurrency by Little's law — 16 blocked workers
    # at ~25ms/batch capped the loadgen at ~600 QPS — while 64 threads
    # convoy on the GIL of a small host and made it WORSE (380 QPS)
    replay_pooled(make_send, payloads[:1000], qps=qps / 2, n_workers=32)

    kill_t = [None]
    recovery_ms = [None]

    def killer():
        # kill replica 1 at ~40% through the measured run
        time.sleep((n_req / qps) * 0.4)
        kill_t[0] = time.perf_counter()
        faults.inject("replica.kernel", replica=1, times=-1)
        print("chaos: replica 1 killed", file=sys.stderr, flush=True)
        while time.perf_counter() - kill_t[0] < 30.0:
            if app.batcher.ejected_replicas() == [1]:
                recovery_ms[0] = (time.perf_counter() - kill_t[0]) * 1e3
                print(
                    f"chaos: replica 1 ejected after "
                    f"{recovery_ms[0]:.0f}ms", file=sys.stderr, flush=True,
                )
                return
            time.sleep(0.005)

    kt = threading.Thread(target=killer, daemon=True)
    kt.start()
    report = replay_pooled(
        make_send, payloads, qps=qps, n_workers=32, max_queue=8192
    )
    kt.join(timeout=35.0)
    print(json.dumps({
        "qps": qps,
        "offered_qps": report.offered_qps,
        "achieved_qps": report.achieved_qps,
        "p50_ms": report.p50_ms,
        "p99_ms": report.p99_ms,
        "errors": report.n_errors,
        "http_5xx": http_5xx[0],
        "degraded_answers": report.by_source.get("degraded", 0),
        "ok_answers": report.by_source.get("ok", 0),
        "redispatched": app.batcher.redispatch_total,
        "ejections": app.batcher.eject_total,
        "eject_recovery_ms": recovery_ms[0],
        "zipf_s": zipf_s,
        "cache_hit_ratio": app.cache.hit_ratio() if app.cache else None,
        "platform": dev.platform,
    }))
"""

# the continuous-freshness phase (ISSUE 10): the delta path's whole
# reason to exist is freshness lag — how long after new rows land does
# serving answer from them? Three judged brackets in one in-process run
# (CPU-platform by construction, self-labeled):
#   full path  — a second FULL re-mine + full reload on the ds2 shape:
#                the baseline freshness lag (mine + republish + swap);
#   delta path — append ~2% new rows, run the SAME pipeline entry (it
#                takes the delta route), and measure publish→applied
#                into the live engine through the production poll loop,
#                with a 1k-QPS-class Zipf replay running THROUGH the
#                apply: freshness_speedup = full_path_s / delta_path_s
#                (acceptance: ≥ 5x) and zero 5xx mid-apply;
#   fleet      — the 3-replica effective-hit-ratio multiplier from
#                freshness/ring.py's simulated topology (affinity vs
#                round-robin over the same key stream) — the ROADMAP's
#                measure-before-committing decision number.
# Selective invalidation is judged by the hit ratio: the delta touches a
# handful of vocab rows, so the Zipf head's cache entries must SURVIVE
# the apply (a wholesale epoch bump would re-compute all of them).
_FRESHNESS_BENCH = r"""
import dataclasses, json, os, sys, tempfile, threading, time
import numpy as np
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.replay import replay_pooled, sample_seed_sets
from kmlserver_tpu.freshness.ring import fleet_multiplier, seeds_key

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
qps = float(os.environ.get("KMLS_BENCH_FRESHNESS_QPS", "800"))
n_req = int(os.environ.get("KMLS_BENCH_FRESHNESS_REQUESTS", "6000"))
with tempfile.TemporaryDirectory(prefix="kmls_fresh_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    csv_path = os.path.join(ds_dir, "2023_spotify_ds2.csv")
    write_tracks_csv(csv_path, synthetic_table(**DS2_SHAPE, seed=123))
    mcfg = MiningConfig(
        base_dir=base, datasets_dir=ds_dir, min_support=0.05,
        delta_enabled=True,
    )
    run_mining_job(mcfg)  # base generation (arms the freshness state)
    cfg = dataclasses.replace(
        ServingConfig.from_env(), base_dir=base, delta_enabled=True,
        batch_max_size=64, shed_queue_budget_ms=0.0,
    )
    app = RecommendApp(cfg)
    assert app.engine.load(), "mined artifacts must load"

    # ---- full path baseline: re-mine everything + full reload (warm
    # jit; delta off — with it on, an unchanged dataset is a designed
    # no-op). This is exactly what the pre-delta GitOps posture pays on
    # EVERY sync cadence tick. Median of 3 — single-shot wall clocks on
    # a shared host are noisy enough to swing the speedup ratio 2x
    # (same discipline as loadshape's runs_p99_ms).
    full_runs = []
    for _ in range(3):
        t0 = time.perf_counter()
        run_mining_job(dataclasses.replace(mcfg, delta_enabled=False))
        assert app.engine.is_data_stale(), "full publication must rewrite the token"
        assert app.engine.load(), "full reload must succeed"
        full_runs.append(time.perf_counter() - t0)
    full_path_s = sorted(full_runs)[1]

    # re-arm the freshness base at the CURRENT generation (the baseline
    # run above retired the old base state by rewriting the token): the
    # delta route detects the mismatch and falls through to a full
    # re-mine that saves a fresh base. Untimed — arming, not the race.
    run_mining_job(mcfg)
    assert app.engine.load(), "re-arm reload must succeed"

    # appended rows concentrate on a ~128-track slice of the catalog —
    # the locality real incremental feeds have (uniform appends would
    # touch nearly every vocab column and degenerate the delta into a
    # full recount, which run_delta_job would do correctly but slowly)
    rng = np.random.default_rng(7)
    n_tracks = DS2_SHAPE["n_tracks"]
    def append_rows(first_pid, lo):
        lines = []
        for p in range(24):
            pid = first_pid + p
            for t in lo + rng.integers(0, 128, size=90):
                t = int(t)
                lines.append(
                    f"{pid},Track {t:07d},spotify:track:{t:07d},"
                    f"Artist {t % 997:04d},spotify:artist:{t % 997:04d},"
                    f"Album {t // 12:06d}"
                )
        # plus a brand-new track (vocabulary growth in a delta)
        t = 9_000_000 + first_pid % 1000
        lines.append(
            f"{first_pid},Track {t:07d},spotify:track:{t:07d},"
            f"Artist 0000,spotify:artist:0000,Album 000000"
        )
        with open(csv_path, "a") as fh:
            fh.write("\n".join(lines) + "\n")

    # ---- the production poll loop, 20 ms cadence ----
    stop = [False]
    def poller():
        while not stop[0]:
            app.engine.reload_if_required()
            time.sleep(0.02)
    pt = threading.Thread(target=poller, daemon=True)
    pt.start()

    # ---- idle deltas 1-3 (apples-to-apples with the idle full
    # baseline): append → the SAME pipeline entry takes the delta route
    # → publish → applied into the live engine by the poll loop.
    # Median of 3 cycles, mirroring the baseline's discipline.
    delta_runs, publish_runs, apply_gaps = [], [], []
    for cycle in range(3):
        append_rows(10_000_000 + cycle * 1_000, 96 + cycle * 160)
        t1 = time.perf_counter()
        summary = run_mining_job(mcfg)
        published_s = time.perf_counter() - t1
        assert summary.delta_seq == cycle + 1, (
            f"delta never published: {summary}"
        )
        t2 = time.perf_counter()
        while (
            app.engine.delta_seq < cycle + 1
            and time.perf_counter() - t2 < 30.0
        ):
            time.sleep(0.002)
        assert app.engine.delta_seq == cycle + 1, (
            f"delta {cycle + 1} never applied in serving"
        )
        delta_runs.append(time.perf_counter() - t1)
        publish_runs.append(published_s)
        apply_gaps.append((time.perf_counter() - t2) * 1e3)
    delta_path_s = sorted(delta_runs)[1]
    published_s = sorted(publish_runs)[1]
    publish_to_applied_ms = sorted(apply_gaps)[1]
    n_idle_deltas = 3

    http_5xx = [0]
    lock = threading.Lock()
    def make_send():
        def send(seeds):
            status, headers, _ = app.handle(
                "POST", "/api/recommend/",
                json.dumps({"songs": seeds}).encode(),
            )
            if status >= 500:
                with lock:
                    http_5xx[0] += 1
                raise RuntimeError(f"HTTP {status}")
            if status != 200:
                raise RuntimeError(f"HTTP {status}")
            cached = headers.get("X-KMLS-Cache") == "hit"
            return ("degraded" if "X-KMLS-Degraded" in headers else "ok",
                    cached)
        return send

    vocab = app.engine.bundle.vocab
    payloads = sample_seed_sets(vocab, n_req, rng_seed=11, zipf_s=1.1)
    # warm the Zipf head so the mid-replay apply hits a POPULATED cache —
    # survival of those entries is the selective-invalidation claim
    replay_pooled(make_send, payloads[: min(3000, n_req)],
                  qps=qps, n_workers=16)
    hits_before = app.cache.hits if app.cache else 0

    # ---- final delta, mid-replay: zero 5xx through the in-place apply
    mid_seq = n_idle_deltas + 1
    delta_mid = {}
    def run_delta_mid():
        append_rows(20_000_000, 640)
        t3 = time.perf_counter()
        s_mid = run_mining_job(mcfg)
        delta_mid["seq"] = s_mid.delta_seq
        while (
            app.engine.delta_seq < mid_seq
            and time.perf_counter() - t3 < 30.0
        ):
            time.sleep(0.002)
        delta_mid["applied_s"] = time.perf_counter() - t3
    mid_thread = threading.Thread(target=run_delta_mid, daemon=True)
    events = [(int(n_req * 0.25), mid_thread.start)]
    report = replay_pooled(
        make_send, payloads, qps=qps, n_workers=16, max_queue=8192,
        events=events,
    )
    # the replay can drain before a slow host finishes the mid-replay
    # mine: join the delta (and leave the poller running to apply it)
    # BEFORE asserting, or the assertions race the publication. ident
    # guard: joining a never-started thread (event never fired) raises
    if mid_thread.ident is not None:
        mid_thread.join(timeout=60.0)
    stop[0] = True
    pt.join(timeout=5.0)
    assert delta_mid.get("seq") == mid_seq, (
        f"mid-replay delta never published: {delta_mid}"
    )
    assert app.engine.delta_seq == mid_seq, (
        "mid-replay delta never applied in serving"
    )

    # ---- fleet multiplier: 3-replica simulated topology ----
    keys = [seeds_key(p) for p in payloads]
    fleet = fleet_multiplier(keys, n_replicas=3, capacity=512)

    cache = app.cache
    print(json.dumps({
        "qps": qps,
        "achieved_qps": report.achieved_qps,
        "p50_ms": report.p50_ms,
        "p99_ms": report.p99_ms,
        "errors": report.n_errors,
        "http_5xx": http_5xx[0],
        "full_path_s": full_path_s,
        "delta_path_s": delta_path_s,
        "delta_publish_s": published_s,
        "publish_to_applied_ms": publish_to_applied_ms,
        "delta_underload_s": delta_mid.get("applied_s"),
        "speedup": full_path_s / delta_path_s,
        "delta_applied_total": app.engine.delta_applied_total,
        "delta_rejected_total": app.engine.delta_rejected_total,
        "freshness_lag_s": app.engine.freshness_lag_s(),
        "cache_hit_ratio": cache.hit_ratio() if cache else None,
        "cache_hits_after_warm": (cache.hits - hits_before) if cache else None,
        "cache_invalidated_keys": cache.invalidated_keys if cache else None,
        "cache_selective_invalidations": (
            cache.selective_invalidations if cache else None
        ),
        "fleet_affinity_hit_ratio": fleet["affinity_hit_ratio"],
        "fleet_baseline_hit_ratio": fleet["baseline_hit_ratio"],
        "fleet_multiplier": fleet["multiplier"],
        "platform": dev.platform,
    }))
"""

# the storage gray-failure phase (ISSUE 19): the SAME in-process app the
# freshness bracket uses, with the artifact plane stall/ENOSPC-injected
# through the path-scoped io.* fault sites. Four legs: (1) clean control
# replay; (2) replay with every PVC read stalled 400 ms — serving runs
# from memory so p99 must not move, the reload (armed by a mid-leg
# invalidation) parks in bounded backoff at the read deadline with
# last-good serving, and the token-poll latency EWMA convicts
# storage-slow (/readyz ready-but-degraded); (3) ENOSPC exactly on the
# recommendations write of the next publication — resumable exit
# classification, token unconsumed, last-good BIT-IDENTICAL (sha256),
# no torn temp files, serving probe still 200; (4) clean re-publish
# recovers end-to-end. Zero 5xx across all legs.
_GRAYSTORE_BENCH = r"""
import dataclasses, errno, hashlib, json, os, sys, tempfile, threading, time
import jax
from kmlserver_tpu import faults
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.io import artifacts, iohealth, registry
from kmlserver_tpu.mining.job import EXIT_RESUMABLE, classify_exception
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.replay import replay_pooled, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
qps = float(os.environ.get("KMLS_BENCH_GRAYSTORE_QPS", "1000"))
n_req = int(os.environ.get("KMLS_BENCH_GRAYSTORE_REQUESTS", "6000"))
STALL_MS = 400.0  # > the 250 ms conviction default, < any replay budget
with tempfile.TemporaryDirectory(prefix="kmls_graystore_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    mcfg = MiningConfig(base_dir=base, datasets_dir=ds_dir, min_support=0.05)
    run_mining_job(mcfg)
    cfg = dataclasses.replace(
        ServingConfig.from_env(), base_dir=base, batch_max_size=64,
        shed_queue_budget_ms=0.0, io_read_deadline_s=0.15,
        reload_backoff_base_s=0.5, reload_backoff_max_s=4.0,
    )
    app = RecommendApp(cfg)
    assert app.engine.load(), "mined artifacts must load"
    pickles = os.path.join(base, "pickles")
    rec_path = os.path.join(pickles, mcfg.recommendations_file)

    http_5xx = [0]
    lock = threading.Lock()
    def send(seeds):
        status, headers, _ = app.handle(
            "POST", "/api/recommend/", json.dumps({"songs": seeds}).encode(),
        )
        if status >= 500:
            with lock:
                http_5xx[0] += 1
            raise RuntimeError(f"HTTP {status}")
        if status != 200:
            raise RuntimeError(f"HTTP {status}")
        return ("degraded" if "X-KMLS-Degraded" in headers else "ok", False)

    vocab = app.engine.bundle.vocab
    payloads = sample_seed_sets(vocab, n_req, rng_seed=11, zipf_s=1.1)

    # ---- leg 1: clean control ----
    control = replay_pooled(lambda: send, payloads, qps=qps, n_workers=16,
                            max_queue=8192)

    # ---- leg 2: every PVC read stalls 400 ms ----
    # the production poll loop keeps running (its token reads ARE the
    # conviction evidence); an invalidation mid-stall arms a reload that
    # must fail at the read deadline into backoff, not wedge
    stop = [False]
    def poller():
        while not stop[0]:
            app.engine.reload_if_required()
            time.sleep(0.02)
    pt = threading.Thread(target=poller, daemon=True)
    pt.start()
    token_before = app.engine.cache_value
    registry.append_history_and_invalidate(
        MiningConfig(base_dir=base), 1, "graystore-ds"
    )
    faults.inject("io.read", delay_s=STALL_MS / 1e3, times=-1)
    stalled = replay_pooled(lambda: send, payloads, qps=qps, n_workers=16,
                            max_queue=8192)
    # drive conviction to its sample floor: each pure staleness check IS
    # a stalled 400 ms token poll (production reaches the floor over
    # minutes of polling; the bench compresses that to ~3 s)
    for _ in range(12):
        if iohealth.MONITOR.storage_slow():
            break
        app.engine.is_data_stale()
    storage_slow = iohealth.MONITOR.storage_slow()
    reload_deferred = app.engine.consecutive_reload_failures >= 1
    backoff_bounded = (
        app.engine._backoff_until > 0.0
        and app.engine._backoff_until - time.monotonic() <= 8.0
    )
    last_good_held = (
        app.engine.finished_loading
        and app.engine.cache_value == token_before
    )
    status, _, payload = app.handle("GET", "/readyz", b"")
    readyz = json.loads(payload)
    readyz_degraded = (
        status == 200 and readyz.get("status") == "degraded"
        and "storage-slow" in readyz.get("reasons", ())
    )
    faults.clear()
    iohealth.MONITOR.reset()
    # drain the pending invalidation (loop: the poller may hold the
    # reload lock mid-stall for one last 400 ms read)
    deadline = time.monotonic() + 30.0
    while (
        app.engine.cache_value == token_before
        and time.monotonic() < deadline
    ):
        app.engine._backoff_until = 0.0
        app.engine.reload_if_required()
        time.sleep(0.05)
    assert app.engine.cache_value != token_before, (
        "reload must recover once the stall clears"
    )

    # ---- leg 3: ENOSPC exactly on the recommendations write ----
    with open(rec_path, "rb") as fh:
        sha_before = hashlib.sha256(fh.read()).hexdigest()
    token_path = registry.token_path_for(base, mcfg.data_invalidation_file)
    with open(token_path) as fh:
        disk_token_before = fh.read()
    faults.inject("io.write", kind="enospc", times=1, path="recommendations")
    enospc_exit = None
    try:
        run_mining_job(mcfg)
    except OSError as exc:
        if exc.errno == errno.ENOSPC:
            enospc_exit = classify_exception(exc)
    faults.clear()
    with open(rec_path, "rb") as fh:
        sha_after = hashlib.sha256(fh.read()).hexdigest()
    with open(token_path) as fh:
        disk_token_after = fh.read()
    torn_parts = sum(
        1 for name in os.listdir(pickles)
        if name.startswith(".tmp_") and name.endswith(".part")
    )
    probe = replay_pooled(lambda: send, payloads[:200], qps=qps,
                          n_workers=8, max_queue=8192)

    # ---- leg 4: clean re-publish recovers ----
    token_pre_recover = app.engine.cache_value
    run_mining_job(mcfg)
    deadline = time.monotonic() + 30.0
    while (
        app.engine.cache_value == token_pre_recover
        and time.monotonic() < deadline
    ):
        time.sleep(0.02)
    recovered = app.engine.cache_value != token_pre_recover
    stop[0] = True
    pt.join(timeout=5.0)

    print(json.dumps({
        "qps": qps,
        "requests": n_req,
        "stall_ms": STALL_MS,
        "control_p50_ms": control.p50_ms,
        "control_p99_ms": control.p99_ms,
        "stalled_p50_ms": stalled.p50_ms,
        "stalled_p99_ms": stalled.p99_ms,
        "p99_ratio": stalled.p99_ms / max(control.p99_ms, 1e-9),
        "storage_slow": bool(storage_slow),
        "readyz_degraded": bool(readyz_degraded),
        "reload_deferred": bool(reload_deferred),
        "backoff_bounded": bool(backoff_bounded),
        "last_good_held": bool(last_good_held),
        "enospc_exit": enospc_exit,
        "enospc_exit_resumable": enospc_exit == EXIT_RESUMABLE,
        "enospc_identical": sha_after == sha_before,
        "enospc_token_moved": disk_token_after != disk_token_before,
        "torn_parts": torn_parts,
        "probe_p99_ms": probe.p99_ms,
        "recovered": bool(recovered),
        "io_retries": iohealth.MONITOR.snapshot()["retries"],
        "http_5xx": http_5xx[0],
        "errors": (control.n_errors + stalled.n_errors + probe.n_errors),
        "platform": dev.platform,
    }))
"""

# the fleet cache-routing phase (ISSUE 15): N REAL server processes +
# the client-side consistent-hash router vs the same fleet under
# round-robin (independent caches) — the bracket that falsifies (or
# confirms) the PR 10 SIMULATED fleet multiplier with real sockets.
# Judged claims:
#   multiplier — routed fleet hit ratio >= independent x the simulated
#                multiplier (within 10%), judged on the pre-kill window
#                so the kill's cold remap doesn't blur the comparison;
#                the Zipf pool is sized past one replica's LRU (the
#                regime the tier exists for: no single pod can hold the
#                head, the fleet together can);
#   kill       — one replica SIGKILLed mid-replay: the router ejects it
#                (PR 3 breaker semantics) and spills its keys to their
#                next-highest rendezvous weight — zero 5xx, survivors
#                absorb, owner-stamped (misrouted) responses appear;
#   delta      — a delta publication lands mid-replay: every survivor
#                applies it in place with SELECTIVE per-seed
#                invalidation, and post-run probes pin answer identity
#                across survivors (per-shard invalidation held).
_FLEET_BENCH = r"""
import dataclasses, json, os, pickle, re, subprocess, sys, tempfile
import threading, time, urllib.request
import numpy as np
import jax
from kmlserver_tpu.config import MiningConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.freshness.ring import seeds_key, simulate_fleet
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.replay import replay_fleet_http, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
qps = float(os.environ.get("KMLS_BENCH_FLEET_QPS", "10500"))
n_req = int(os.environ.get("KMLS_BENCH_FLEET_REQUESTS", "42000"))
n_replicas = int(os.environ.get("KMLS_BENCH_FLEET_REPLICAS", "3"))
cache_entries = int(os.environ.get("KMLS_BENCH_FLEET_CACHE", "512"))
# Zipf pool wider than ONE replica's LRU but within the fleet's
# aggregate — the exact regime the routing tier exists for
pool = int(cache_entries * (n_replicas + 1.5))
peers = [f"replica-{i}" for i in range(n_replicas)]
peers_csv = ",".join(peers)

with tempfile.TemporaryDirectory(prefix="kmls_fleet_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    csv_path = os.path.join(ds_dir, "2023_spotify_ds2.csv")
    write_tracks_csv(csv_path, synthetic_table(**DS2_SHAPE, seed=123))
    mcfg = MiningConfig(
        base_dir=base, datasets_dir=ds_dir, min_support=0.05,
        delta_enabled=True,
    )
    run_mining_job(mcfg)  # base generation (arms the freshness state)
    with open(
        os.path.join(base, "pickles", "recommendations.pickle"), "rb"
    ) as fh:
        vocab = sorted(pickle.load(fh).keys())

    # ---- N real server processes, stable identities replica-0..N-1,
    # one shared PVC-shaped base dir — the statefulset.yaml topology
    # mirrored locally by the KMLS_FLEET_* knobs. Everything from the
    # first spawn runs under try/finally: a failed assert/probe must
    # not orphan N jax servers into the rest of the bench run (the
    # parent only killpg's this phase on TIMEOUT, not on nonzero exit,
    # and a retry would double the orphans).
    procs, ports, logs = [], {}, {}
    def _terminate_all():
        for proc in procs:
            try:
                proc.terminate()
            except Exception:
                pass
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
    def start_server(i):
        env = dict(os.environ)
        env.update({
            "BASE_DIR": base, "KMLS_PORT": "0",
            # fast poll so the mid-replay delta publication is applied
            # within ~0.3s on every replica
            "POLLING_WAIT_IN_MINUTES": "0.005",
            "KMLS_DELTA_ENABLED": "1",
            "KMLS_CACHE_MAX_ENTRIES": str(cache_entries),
            "KMLS_SHED_QUEUE_BUDGET_MS": "0",
            "KMLS_FLEET_SELF": peers[i],
            "KMLS_FLEET_PEERS": peers_csv,
        })
        proc = subprocess.Popen(
            [sys.executable, "-m", "kmlserver_tpu.serving.server"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        lines = []
        logs[i] = lines
        def drain():
            for line in proc.stdout:
                lines.append(line.rstrip())
                m = re.search(r"serving on \S+?:(\d+)", line)
                if m and i not in ports:
                    ports[i] = int(m.group(1))
        threading.Thread(target=drain, daemon=True).start()
        return proc

    try:
        for i in range(n_replicas):
            procs.append(start_server(i))
        t_wait = time.time()
        while len(ports) < n_replicas and time.time() - t_wait < 120:
            time.sleep(0.1)
        assert len(ports) == n_replicas, f"servers never reported ports: {ports}"
        urls = {peers[i]: f"http://127.0.0.1:{ports[i]}" for i in range(n_replicas)}
        def wait_ready(url, deadline_s=180):
            t0 = time.time()
            while time.time() - t0 < deadline_s:
                try:
                    with urllib.request.urlopen(url + "/readyz", timeout=5) as r:
                        if r.status == 200:
                            return True
                except Exception:
                    pass
                time.sleep(0.25)
            return False
        for p_name, url in urls.items():
            assert wait_ready(url), f"{p_name} never went ready"
        print(f"fleet up: {urls}", file=sys.stderr, flush=True)

        def scrape(url):
            with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
                text = r.read().decode()
            out = {}
            for line in text.splitlines():
                if line and not line.startswith("#"):
                    parts = line.split()
                    if len(parts) == 2:
                        try:
                            out[parts[0]] = float(parts[1])
                        except ValueError:
                            pass
            return out

        # the judged hit-ratio window ends BEFORE either mid-replay
        # event: the delta's selective invalidations + in-process mining
        # contention and the kill's cold remap all land on the routed
        # leg only, and simulate_fleet models neither — judging the
        # event-free prefix keeps the multiplier comparison apples-to-
        # apples (both legs AND the simulation see the same cold-start-
        # to-warm window); the delta and the kill stay genuinely
        # mid-replay for the zero-5xx claims
        window_end = int(n_req * 0.30)
        delta_at = window_end
        kill_at = int(n_req * 0.60)

        # ---- leg A: the same fleet under round-robin — what N independent
        # epoch-keyed LRUs do today (each replica re-warms the same head).
        # Distinct rng seed from leg B: neither leg may pre-warm the other's
        # keys, so both start cold for their own population, like the
        # simulation does.
        payloads_a = sample_seed_sets(
            vocab, n_req, rng_seed=31, zipf_s=0.9, zipf_pool=pool,
        )
        rep_a, fleet_a = replay_fleet_http(
            urls, payloads_a, qps=qps, policy="roundrobin",
            window_end=window_end,
        )
        print(
            f"independent: hit {fleet_a['window_hit_ratio']:.3f} (window), "
            f"{rep_a.achieved_qps:.0f} QPS, {fleet_a['http_5xx']} 5xx",
            file=sys.stderr, flush=True,
        )
        # misrouted baseline AFTER leg A: round-robin deliberately lands
        # ~ (N-1)/N of traffic off-owner, so the drift counter must be
        # read as a DELTA over the routed leg or the baseline's designed
        # misroutes would masquerade as routing drift
        misrouted_before = {
            i: scrape(urls[peers[i]]).get("kmls_cache_misrouted_total", 0)
            for i in range(n_replicas)
        }

        # ---- leg B: consistent-hash routed, with the kill + the delta
        # landing mid-replay
        payloads_b = sample_seed_sets(
            vocab, n_req, rng_seed=32, zipf_s=0.9, zipf_pool=pool,
        )
        victim = n_replicas - 1
        delta_state = {}
        def run_delta():
            rng = np.random.default_rng(7)
            lines = []
            for p in range(24):
                pid = 30_000_000 + p
                for t in 96 + rng.integers(0, 128, size=90):
                    t = int(t)
                    lines.append(
                        f"{pid},Track {t:07d},spotify:track:{t:07d},"
                        f"Artist {t % 997:04d},spotify:artist:{t % 997:04d},"
                        f"Album {t // 12:06d}"
                    )
            with open(csv_path, "a") as fh:
                fh.write("\n".join(lines) + "\n")
            summary = run_mining_job(mcfg)
            delta_state["seq"] = summary.delta_seq
        delta_thread = threading.Thread(target=run_delta, daemon=True)
        events = [
            (delta_at, delta_thread.start),
            (kill_at, procs[victim].kill),  # SIGKILL: a real crash, no drain
        ]
        rep_b, fleet_b = replay_fleet_http(
            urls, payloads_b, qps=qps, policy="ring",
            window_end=window_end, events=events,
        )
        delta_thread.join(timeout=120)
        assert delta_state.get("seq") == 1, (
            f"mid-replay delta never published: {delta_state}"
        )
        print(
            f"routed: hit {fleet_b['window_hit_ratio']:.3f} (window), "
            f"{rep_b.achieved_qps:.0f} QPS, {fleet_b['http_5xx']} 5xx, "
            f"rerouted {fleet_b['rerouted']}, ejections {fleet_b['ejections']}",
            file=sys.stderr, flush=True,
        )

        # ---- survivors: the delta applied in place on every one, with the
        # SELECTIVE per-seed invalidation (no epoch bump), and answers stay
        # identical across replicas (per-shard invalidation identity)
        survivors = [i for i in range(n_replicas) if i != victim]
        deadline = time.time() + 60
        metrics_by = {}
        for i in survivors:
            while time.time() < deadline:
                m = scrape(urls[peers[i]])
                if m.get("kmls_delta_seq", 0) >= 1:
                    break
                time.sleep(0.25)
            metrics_by[i] = scrape(urls[peers[i]])
        delta_applied_ok = all(
            metrics_by[i].get("kmls_delta_seq", 0) >= 1
            and metrics_by[i].get("kmls_delta_applied_total", 0) >= 1
            and metrics_by[i].get("kmls_delta_rejected_total", 0) == 0
            for i in survivors
        )
        selective = sum(
            metrics_by[i].get("kmls_cache_selective_invalidations_total", 0)
            for i in survivors
        )
        # routed-leg drift only: survivors' counter growth since the leg-A
        # snapshot (all of it comes from the post-kill spill — before the
        # kill, ring routing keeps every key on its owner)
        misrouted = sum(
            metrics_by[i].get("kmls_cache_misrouted_total", 0)
            - misrouted_before[i]
            for i in survivors
        )
        def probe(url, seeds):
            body = json.dumps({"songs": seeds}).encode()
            req = urllib.request.Request(
                url + "/api/recommend/", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                return json.load(r)["songs"]
        probe_sets = payloads_b[:4] + [["Track 0000100"], vocab[:3]]
        # cross-replica identity needs >= 2 survivors to mean anything
        # (one answer compared with itself is vacuously identical):
        # None = not claimable at this replica count, never a pass
        identity_ok = (
            all(
                len({
                    tuple(probe(urls[peers[i]], seeds)) for i in survivors
                }) == 1
                for seeds in probe_sets
            )
            if len(survivors) >= 2
            else None
        )

    finally:
        _terminate_all()

    # ---- the simulated prediction (PR 10) this run falsifies or
    # confirms: SAME ring, SAME capacity, SAME key stream, same window
    keys_b = [seeds_key(p) for p in payloads_b[:window_end]]
    sim_aff = simulate_fleet(keys_b, n_replicas, cache_entries, "affinity")
    sim_rr = simulate_fleet(keys_b, n_replicas, cache_entries, "roundrobin")
    sim_mult = (sim_aff / sim_rr) if sim_rr > 0 else float("inf")
    ach_mult = (
        fleet_b["window_hit_ratio"] / fleet_a["window_hit_ratio"]
        if fleet_a["window_hit_ratio"]
        else float("inf")
    )

    print(json.dumps({
        "qps": qps,
        "requests": n_req,
        "replicas": n_replicas,
        "cache_entries": cache_entries,
        "zipf_pool": pool,
        "independent_hit_ratio": fleet_a["window_hit_ratio"],
        "routed_hit_ratio": fleet_b["window_hit_ratio"],
        "independent_hit_ratio_full": fleet_a["hit_ratio"],
        "routed_hit_ratio_full": fleet_b["hit_ratio"],
        "multiplier_achieved": ach_mult,
        "multiplier_simulated": sim_mult,
        "multiplier_vs_simulated": (
            ach_mult / sim_mult if sim_mult > 0 else float("inf")
        ),
        "sim_affinity_hit": sim_aff,
        "sim_roundrobin_hit": sim_rr,
        "offered_qps": rep_b.offered_qps,
        "achieved_qps": rep_b.achieved_qps,
        "p50_ms": rep_b.p50_ms,
        "p99_ms": rep_b.p99_ms,
        "errors": rep_a.n_errors + rep_b.n_errors,
        "http_5xx": fleet_a["http_5xx"] + fleet_b["http_5xx"],
        "kill_peer": peers[victim],
        "rerouted": fleet_b["rerouted"],
        "router_ejections": fleet_b["ejections"],
        "router_spills": fleet_b["spills"],
        "owner_stamped": fleet_b["owner_stamped"],
        "answered_by": fleet_b["answered_by"],
        "delta_applied_ok": delta_applied_ok,
        "selective_invalidations": selective,
        "misrouted_total": misrouted,
        "identity_ok": identity_ok,
        "platform": dev.platform,
    }))
"""

# the quality-loop phase (ISSUE 14): the first bracket that measures
# whether the ANSWERS are any good, next to all the latency evidence.
# One in-process run (CPU-platform by construction, self-labeled):
#   eval     — a full pipeline run with embed + eval on publishes
#              quality.report.json: held-out basket-completion recall@k
#              / MRR / coverage per serving mode through the production
#              kernels, plus the blend-weight sweep;
#   measured — the sweep's argmax round-trips into serving: an engine
#              under KMLS_HYBRID_BLEND_WEIGHT=measured reads the report
#              and serves that exact weight (weight_roundtrip);
#   compact  — two delta publications grow the chain, then the
#              snapshotting compactor folds base ∘ chain into a new
#              base MID-REPLAY: zero 5xx through the swap, and the
#              compacted npz is bit-identical to a pristine full
#              re-mine of the final CSV (compact_identical) at a
#              fraction of its wall clock (compact_speedup).
_QUALITY_BENCH = r"""
import dataclasses, json, os, shutil, sys, tempfile, threading, time
import numpy as np
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.io import artifacts
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.quality import lifecycle
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.replay import replay_pooled, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
rows = int(os.environ.get("KMLS_BENCH_QUALITY_ROWS", str(DS2_SHAPE["target_rows"])))
scale = rows / DS2_SHAPE["target_rows"]
shape = dict(
    n_playlists=max(int(DS2_SHAPE["n_playlists"] * scale), 200),
    n_tracks=max(int(DS2_SHAPE["n_tracks"] * scale), 150),
    target_rows=rows,
)
n_req = max(800, min(4000, rows // 50))
with tempfile.TemporaryDirectory(prefix="kmls_quality_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    csv_path = os.path.join(ds_dir, "2023_spotify_ds2.csv")
    write_tracks_csv(csv_path, synthetic_table(**shape, seed=123))
    mcfg = MiningConfig(
        base_dir=base, datasets_dir=ds_dir, min_support=0.05,
        delta_enabled=True, embed_enabled=True, als_rank=16, als_iters=5,
        eval_enabled=True, eval_max_playlists=1024,
    )
    t0 = time.perf_counter()
    run_mining_job(mcfg)
    full_job_s = time.perf_counter() - t0  # incl. the eval double-train
    report = artifacts.load_quality_report(mcfg.pickles_dir)
    assert report is not None, "eval phase must publish quality.report.json"
    modes = report["modes"]
    w = report["measured_blend_weight"]

    # measured blend optimum round-trips report -> bundle -> serve time
    cfg = dataclasses.replace(
        ServingConfig.from_env(), base_dir=base, delta_enabled=True,
        hybrid_blend_measured=True, shed_queue_budget_ms=0.0,
        batch_max_size=64,
    )
    app = RecommendApp(cfg)
    assert app.engine.load(), "mined artifacts must load"
    weight_roundtrip = bool(
        w is not None and app.engine.blend_weight == w
        and app.engine.measured_blend_weight == w
    )

    # grow a 2-bundle delta chain (the compaction trigger's shape)
    rng = np.random.default_rng(7)
    n_tracks = shape["n_tracks"]
    def append_rows(first_pid, lo):
        lines = []
        for p in range(16):
            pid = first_pid + p
            for t in lo + rng.integers(0, 96, size=40):
                t = int(t) % n_tracks
                lines.append(
                    f"{pid},Track {t:07d},spotify:track:{t:07d},"
                    f"Artist {t % 997:04d},spotify:artist:{t % 997:04d},"
                    f"Album {t // 12:06d}"
                )
        with open(csv_path, "a") as fh:
            fh.write("\n".join(lines) + "\n")
    for i in range(2):
        append_rows(10_000_000 + i * 1000, 40 + 100 * i)
        s = run_mining_job(mcfg)
        assert s.delta_seq == i + 1, f"delta never published: {s}"

    # control: pristine full re-mine of the final CSV — the identity
    # bar the compacted snapshot is judged against (and the wall clock
    # the compactor avoids paying)
    ctl = os.path.join(base, "ctl")
    ctl_ds = os.path.join(ctl, "datasets")
    os.makedirs(ctl_ds)
    shutil.copy(csv_path, os.path.join(ctl_ds, os.path.basename(csv_path)))
    ctl_cfg = dataclasses.replace(
        mcfg, base_dir=ctl, datasets_dir=ctl_ds,
        delta_enabled=False, eval_enabled=False, embed_enabled=False,
    )
    t1 = time.perf_counter()
    run_mining_job(ctl_cfg)
    remine_s = time.perf_counter() - t1

    # ---- mid-replay compaction through the production poll loop ----
    stop = [False]
    def poller():
        while not stop[0]:
            app.engine.reload_if_required()
            time.sleep(0.02)
    pt = threading.Thread(target=poller, daemon=True)
    pt.start()

    http_5xx = [0]
    lock = threading.Lock()
    def make_send():
        def send(seeds):
            status, headers, _ = app.handle(
                "POST", "/api/recommend/",
                json.dumps({"songs": seeds}).encode(),
            )
            if status >= 500:
                with lock:
                    http_5xx[0] += 1
                raise RuntimeError(f"HTTP {status}")
            if status != 200:
                raise RuntimeError(f"HTTP {status}")
            return ("degraded" if "X-KMLS-Degraded" in headers else "ok",
                    headers.get("X-KMLS-Cache") == "hit")
        return send

    vocab = app.engine.bundle.vocab
    payloads = sample_seed_sets(vocab, n_req, rng_seed=11, zipf_s=1.1)
    compact = {}
    def run_compact():
        t2 = time.perf_counter()
        res = lifecycle.compact_delta_chain(mcfg)
        compact["s"] = time.perf_counter() - t2
        compact["folded"] = res.n_folded
        compact["token"] = res.token
    ct = threading.Thread(target=run_compact, daemon=True)
    events = [(int(n_req * 0.3), ct.start)]
    replay = replay_pooled(
        make_send, payloads, qps=500.0, n_workers=12, max_queue=8192,
        events=events,
    )
    assert replay.n_requests > 0, "replay generated no completed requests"
    if ct.ident is not None:
        ct.join(timeout=120.0)
    # the poller must hot-swap onto the compacted token before teardown
    deadline = time.time() + 30.0
    while (
        app.engine.cache_value != compact.get("token")
        and time.time() < deadline
    ):
        time.sleep(0.01)
    stop[0] = True
    pt.join(timeout=5.0)
    assert compact.get("folded") == 2, f"compaction never ran: {compact}"
    assert app.engine.cache_value == compact["token"], (
        "compacted generation never hot-swapped into serving"
    )

    a = artifacts.load_rule_tensors(artifacts.tensor_artifact_path(
        os.path.join(mcfg.pickles_dir, mcfg.recommendations_file)))
    b = artifacts.load_rule_tensors(artifacts.tensor_artifact_path(
        os.path.join(ctl_cfg.pickles_dir, ctl_cfg.recommendations_file)))
    identical = bool(
        a["vocab"] == b["vocab"]
        and all(
            np.array_equal(a[k], b[k])
            for k in ("rule_ids", "rule_counts", "item_counts")
        )
        and a["n_playlists"] == b["n_playlists"]
    )

    sweep = report.get("sweep") or {}
    print(json.dumps({
        "recall_rules": modes["rules"]["recall_at_k"],
        "recall_embed": modes.get("embed", {}).get("recall_at_k"),
        "recall_blend": modes["blend"]["recall_at_k"],
        "recall_blend_best": sweep.get("best_recall_at_k"),
        "recall_popularity": modes["popularity"]["recall_at_k"],
        "mrr_blend": modes["blend"]["mrr"],
        "coverage_blend": modes["blend"]["coverage"],
        "measured_weight": w,
        "weight_roundtrip": weight_roundtrip,
        "eval_playlists": report["split"]["n_eval_playlists"],
        "full_job_s": full_job_s,
        "remine_s": remine_s,
        "compact_s": compact.get("s"),
        "compact_speedup": (
            remine_s / compact["s"] if compact.get("s") else None
        ),
        "compact_folded": compact.get("folded"),
        "compact_identical": identical,
        "http_5xx": http_5xx[0],
        "errors": replay.n_errors,
        "p99_ms": replay.p99_ms,
        "platform": dev.platform,
    }))
"""

# the traffic-shape phase (ISSUE 8): the PR 1-3 shed/degrade/eject
# machinery exercised under the load shapes production actually has,
# not constant-rate Poisson. Three brackets through the full in-process
# app path (cache → admission ladder → batcher → native kernel),
# statuses counted at the HTTP layer so a 5xx can never hide:
#   burst    — 10x burst trains at Zipf 1.1; the judged claims are
#              p99 < 10 ms, zero 5xx, zero errors straight through the
#              bursts (the cache absorbs the head, admission the tail);
#   flash    — flash crowd: a mid-run window collapses ALL traffic onto
#              a handful of cold seed sets (singleflight's worst case);
#              degradation (X-KMLS-Degraded / jittered 429) is allowed,
#              5xx never;
#   epochflip— hot-key flip pinned to a REAL epoch boundary: a second
#              mining generation is pre-published and the bundle
#              hot-swaps mid-burst, invalidating every hot cache key at
#              once; singleflight must collapse the miss wave (zero
#              5xx, zero errors).
# In-process for the same reason as replay10k: at QPS scale an HTTP
# loadgen on this sandbox measures the loadgen. CPU-platform by
# construction, self-labeled.
_LOADSHAPE_BENCH = r"""
import dataclasses, json, os, sys, tempfile, threading, time
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.replay import (
    flash_crowd_payloads,
    replay_pooled,
    sample_seed_sets,
    shaped_arrivals,
)

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
qps = float(os.environ.get("KMLS_BENCH_LOADSHAPE_QPS", "1000"))
n_req = int(os.environ.get("KMLS_BENCH_LOADSHAPE_REQUESTS", "8000"))
burst = float(os.environ.get("KMLS_BENCH_LOADSHAPE_BURST", "10"))
with tempfile.TemporaryDirectory(prefix="kmls_loadshape_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    mcfg = MiningConfig(base_dir=base, datasets_dir=ds_dir, min_support=0.05)
    run_mining_job(mcfg)
    # admission ladder ON at its production defaults (the whole point of
    # this bracket); generous deadline so only a genuine stall degrades
    cfg = dataclasses.replace(
        ServingConfig.from_env(), base_dir=base,
        batch_max_size=64, request_deadline_ms=2000.0,
    )
    app = RecommendApp(cfg)
    assert app.engine.load(), "mined artifacts must load"
    assert cfg.shed_queue_budget_ms > 0, "admission control must be on"
    http_5xx = [0]
    lock = threading.Lock()
    # pre-encoded request bodies, keyed by seed tuple: the loadgen's job
    # is pacing, not cooking (replay_async_http's rule) — at a 10x burst
    # peak the per-request json.dumps is half a core of GIL work on this
    # host, taxing the very tail being measured
    body_cache = {}

    def _body(seeds):
        key = tuple(seeds)
        body = body_cache.get(key)
        if body is None:
            body = json.dumps({"songs": seeds}).encode()
            body_cache[key] = body
        return body

    # make_send_http — full HTTP accounting (app.handle): statuses
    # counted, a 5xx can never hide. ~0.4 ms of GIL-held json per request
    # on this host, so this sender honestly paces ~1k QPS — the
    # flash/epochflip brackets (whose claims are about 5xx and
    # degradation) use it.
    def make_send_http():
        def send(seeds):
            status, headers, _ = app.handle(
                "POST", "/api/recommend/", _body(seeds),
            )
            if status >= 500:
                with lock:
                    http_5xx[0] += 1
                raise RuntimeError(f"HTTP {status}")
            if status == 429:
                # visible backpressure, tracked per-phase — never a 5xx,
                # and Retry-After carries the jitter
                return ("shed", None) if "Retry-After" in headers else (
                    "shed-nojitter", None)
            if status != 200:
                raise RuntimeError(f"HTTP {status}")
            return (
                "degraded" if "X-KMLS-Degraded" in headers else "ok"
            ), None
        return send

    # exception classes the HTTP layer maps AWAY from 5xx (app.py
    # _degrade_reason + the 429 path) — anything else would be a 500
    from kmlserver_tpu.serving.batcher import (
        DeadlineExceeded, NoHealthyReplicas, Overloaded, OverloadDegraded,
    )

    # make_send_direct — the replay10k sender (app.recommend_direct: the
    # same cache → admission → batcher → kernel path minus the json
    # encode/decode, which at a 10x burst peak measures the LOADGEN's
    # GIL, not the server). Exceptions are classified by the app layer's
    # own mapping: shed/degrade classes are non-5xx outcomes by
    # construction (unit-tested in test_batching/test_chaos); anything
    # else is counted as a would-be 5xx AND an error. The judged
    # p99-under-burst bracket uses this sender.
    def make_send_direct():
        def send(seeds):
            try:
                recs, source, cached = app.recommend_direct(seeds)
            except Overloaded:
                return "shed", None
            except (OverloadDegraded, DeadlineExceeded, NoHealthyReplicas):
                return "degraded", None
            except Exception:
                with lock:
                    http_5xx[0] += 1  # the handle() path would 500 this
                raise
            return "ok", cached
        return send

    vocab = app.engine.bundle.vocab
    payloads = sample_seed_sets(vocab, n_req, rng_seed=17, zipf_s=1.1)
    # warm to STEADY STATE before pacing (replay10k's posture: steady
    # state is what the rate sustains): every distinct payload in the
    # Zipf pool once — the measured bursts then run at the hit ratio a
    # long-lived pod actually has — plus a paced half-rate pass for the
    # jit/native and batcher paths
    warm_send = make_send_http()
    seen = set()
    for p in payloads:
        key = tuple(p)
        if key not in seen:
            seen.add(key)
            warm_send(p)
    replay_pooled(
        make_send_http, payloads[: min(3000, n_req)], qps=qps / 2,
        n_workers=16,
    )

    def phase(name, make_send, pl, arrivals, events=None):
        t5xx0 = http_5xx[0]
        shed0 = app.batcher.shed_total
        rep = replay_pooled(
            make_send, pl, qps=qps, n_workers=16, max_queue=16384,
            arrivals=arrivals, events=events,
        )
        out = {
            "offered_qps": round(rep.offered_qps, 1),
            "achieved_qps": round(rep.achieved_qps, 1),
            "p50_ms": round(rep.p50_ms, 3),
            "p99_ms": round(rep.p99_ms, 3),
            # arrival-windowed split (ISSUE 17): the first-40%-of-
            # schedule tail vs the last-40% tail — on shaped traffic the
            # onset window is where reactive adaptation is still
            # catching up, and a pooled p99 averages that away
            "onset_p99_ms": (
                round(rep.onset_p99_ms, 3)
                if rep.onset_p99_ms is not None else None
            ),
            "steady_p99_ms": (
                round(rep.steady_p99_ms, 3)
                if rep.steady_p99_ms is not None else None
            ),
            "errors": rep.n_errors,
            "http_5xx": http_5xx[0] - t5xx0,
            "shed": app.batcher.shed_total - shed0,
            "degraded": rep.by_source.get("degraded", 0),
            "ok": rep.by_source.get("ok", 0),
        }
        print(f"loadshape/{name}: {out}", file=sys.stderr, flush=True)
        return out

    # --- bracket 1: 10x burst trains (the judged p99-under-burst claim).
    # Median of 3 runs by p99, the same discipline as the 1k replay
    # bracket: this sandbox's CPU shares make any single run's tail
    # hostage to a neighbor, and the claim is about the SERVER, not one
    # lucky or unlucky scheduling window. Error/5xx counts are summed
    # across all runs — a failure in any run must not hide in the median.
    burst_arrivals = shaped_arrivals(n_req, qps, "burst", burst_factor=burst)
    runs = [
        phase(f"burst[{i}]", make_send_direct, payloads, burst_arrivals)
        for i in range(3)
    ]
    burst_res = sorted(runs, key=lambda r: r["p99_ms"])[len(runs) // 2]
    burst_res = dict(burst_res)
    burst_res["errors"] = sum(r["errors"] for r in runs)
    burst_res["http_5xx"] = sum(r["http_5xx"] for r in runs)
    burst_res["runs_p99_ms"] = [r["p99_ms"] for r in runs]

    # --- bracket 2: flash crowd (all traffic onto a cold hot-pool)
    n_flash = max(n_req // 2, 1000)
    flash_pl = flash_crowd_payloads(
        sample_seed_sets(vocab, n_flash, rng_seed=29, zipf_s=1.1),
        window=(0.4, 0.7), hot_pool=4,
    )
    flash_res = phase(
        "flash", make_send_http, flash_pl,
        shaped_arrivals(n_flash, qps, "constant"),
    )

    # --- bracket 3: hot-key flip at a REAL epoch boundary — publish a
    # second mining generation now, hot-swap the bundle mid-burst
    run_mining_job(mcfg)  # same data, new generation + invalidation token
    assert app.engine.is_data_stale()
    n_flip = max(n_req // 2, 1000)
    flip_pl = sample_seed_sets(vocab, n_flip, rng_seed=31, zipf_s=1.1)
    epoch_before = app.engine.bundle_epoch
    sf_before = app.cache.singleflight_joins if app.cache else 0

    flip_threads = []

    def flip():
        # the hot swap runs exactly like the production poller: on its
        # own thread, concurrent with serving — the epoch bump lands
        # mid-burst and every hot cache key invalidates at once
        t = threading.Thread(target=app.engine.load, daemon=True)
        t.start()
        flip_threads.append(t)

    flip_res = phase(
        "epochflip", make_send_http, flip_pl,
        shaped_arrivals(n_flip, qps, "constant"),
        events=[(n_flip // 2, flip)],
    )
    # the swap raced the burst (that's the scenario) but the epoch
    # assertion must not race a reload still pre-warming on a contended
    # host: bound the wait, don't leave it to replay-tail luck
    for t in flip_threads:
        t.join(timeout=120.0)
    flip_res["epoch_moved"] = int(app.engine.bundle_epoch > epoch_before)
    flip_res["singleflight_joins"] = (
        (app.cache.singleflight_joins - sf_before) if app.cache else None
    )

    print(json.dumps({
        "qps": qps,
        "burst_factor": burst,
        "zipf_s": 1.1,
        "requests": n_req,
        "burst": burst_res,
        "flash": flash_res,
        "epochflip": flip_res,
        "cache_hit_ratio": app.cache.hit_ratio() if app.cache else None,
        "utilization_after": round(app.batcher.utilization(), 4),
        "platform": dev.platform,
    }))
"""

# the predictive-serving phase (ISSUE 17): the same shaped-traffic rig as
# the loadshape bracket, run as paired A/B legs at EQUAL capacity — one
# server with the forecaster off (pure reactive, the PR 8 ladder), one
# with KMLS_FORECAST=1 — over the two shapes prediction exists for (ramp,
# sine) plus constant as the control where the forecaster must change
# nothing. Each leg reports pooled p99, the onset/steady arrival-window
# split (onset is where reactive adaptation lags and prediction can
# lead), and the shed/degrade counts; the predictive legs also report the
# forecaster's own counters so a "win" with zero observations reads as
# the measurement artifact it would be.
_LOADSHAPE_PRED_BENCH = r"""
import dataclasses, json, os, sys, tempfile, threading, time
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.batcher import (
    DeadlineExceeded, NoHealthyReplicas, Overloaded, OverloadDegraded,
)
from kmlserver_tpu.serving import forecast as forecast_mod
from kmlserver_tpu.serving.replay import (
    replay_pooled, sample_seed_sets, shaped_arrivals,
)

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
qps = float(os.environ.get("KMLS_BENCH_LOADSHAPE_QPS", "1000"))
n_req = int(os.environ.get("KMLS_BENCH_LOADSHAPE_REQUESTS", "8000"))
with tempfile.TemporaryDirectory(prefix="kmls_loadshape_pred_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    run_mining_job(MiningConfig(base_dir=base, datasets_dir=ds_dir,
                                min_support=0.05))
    # a tight shed budget puts the admission ladder IN PLAY at these
    # shapes: with the 250ms default neither leg ever sheds and the
    # judged shed/degrade comparison is a vacuous 0-0 tie. 30ms is
    # still ~10x the steady-state p99, so a leg sheds only when its
    # batch window lags the arrival rate — exactly the lag the
    # forecaster exists to remove. Applied to BOTH legs: equal capacity.
    base_cfg = dataclasses.replace(
        ServingConfig.from_env(), base_dir=base,
        batch_max_size=64, request_deadline_ms=2000.0,
        shed_queue_budget_ms=30.0,
    )
    assert base_cfg.shed_queue_budget_ms > 0, "admission control must be on"

    def run_leg(shape, predictive, payloads, arrivals):
        # equal capacity by construction: the ONLY config difference
        # between the paired legs is the forecaster knob
        cfg = dataclasses.replace(base_cfg, forecast_enabled=predictive)
        app = RecommendApp(cfg)
        assert app.engine.load(), "mined artifacts must load"
        would_5xx = [0]
        lock = threading.Lock()

        def make_send():
            def send(seeds):
                try:
                    recs, source, cached = app.recommend_direct(seeds)
                except Overloaded:
                    return "shed", None
                except (OverloadDegraded, DeadlineExceeded,
                        NoHealthyReplicas):
                    return "degraded", None
                except Exception:
                    with lock:
                        would_5xx[0] += 1  # handle() would 500 this
                    raise
                return "ok", cached
            return send

        # identical warm discipline both modes: every distinct payload
        # once, then a paced half-rate pass for the jit/batcher paths
        warm = make_send()
        seen = set()
        for p in payloads:
            key = tuple(p)
            if key not in seen:
                seen.add(key)
                warm(p)
        replay_pooled(
            make_send, payloads[: min(3000, n_req)], qps=qps / 2,
            n_workers=16,
        )
        shed0 = app.batcher.shed_total
        obs0 = forecast_mod.OBSERVATIONS_TOTAL
        rep = replay_pooled(
            make_send, payloads, qps=qps, n_workers=16, max_queue=16384,
            arrivals=arrivals,
        )
        out = {
            "p50_ms": round(rep.p50_ms, 3),
            "p99_ms": round(rep.p99_ms, 3),
            "onset_p99_ms": (
                round(rep.onset_p99_ms, 3)
                if rep.onset_p99_ms is not None else None
            ),
            "steady_p99_ms": (
                round(rep.steady_p99_ms, 3)
                if rep.steady_p99_ms is not None else None
            ),
            "errors": rep.n_errors,
            "http_5xx": would_5xx[0],
            "shed": app.batcher.shed_total - shed0,
            "degraded": rep.by_source.get("degraded", 0),
            "ok": rep.by_source.get("ok", 0),
            "achieved_qps": round(rep.achieved_qps, 1),
        }
        if predictive:
            f = app.forecaster
            assert f is not None, "KMLS_FORECAST leg must hold a forecaster"
            out["forecast_observations"] = f.observations
            out["prewarm_total"] = getattr(app.batcher, "prewarm_total", 0)
        else:
            # the zero-cost proof under REAL traffic: a disabled-mode
            # leg must never reach the forecaster (is-None gate)
            delta = forecast_mod.OBSERVATIONS_TOTAL - obs0
            assert delta == 0, f"disabled leg observed {delta} requests"
            out["forecast_disabled_obs_delta"] = delta
        mode = "pred" if predictive else "react"
        print(f"loadshape_pred/{shape}/{mode}: {out}", file=sys.stderr,
              flush=True)
        return out

    # one probe load for the catalog vocab; the measured legs each load
    # their own fresh app
    from kmlserver_tpu.serving.engine import RecommendEngine

    probe = RecommendEngine(base_cfg)
    assert probe.load(), "mined artifacts must load"
    vocab = list(probe.bundle.vocab)
    del probe

    shapes = {}
    rng_seeds = {"ramp": 41, "sine": 43, "constant": 47}
    for shape in ("ramp", "sine", "constant"):
        # fixed per-shape rng: the paired legs replay the SAME payloads
        # on the SAME arrival schedule — the knob is the only variable
        payloads = sample_seed_sets(
            vocab, n_req, rng_seed=rng_seeds[shape], zipf_s=1.1,
        )
        # the ramp climbs to 3x base — past the point where a
        # stale-wide batch window starts costing queue wait, so the
        # tightened shed budget has something to judge
        kw = {"ramp_stop_factor": 3.0} if shape == "ramp" else {}
        arrivals = shaped_arrivals(n_req, qps, shape, **kw)
        shapes[shape] = {
            "reactive": run_leg(shape, False, payloads, arrivals),
            "predictive": run_leg(shape, True, payloads, arrivals),
        }
    print(json.dumps({
        "qps": qps,
        "requests": n_req,
        "shapes": shapes,
        "platform": dev.platform,
    }))
"""

# the mining-interruption phase (ISSUE 4): kill the mining job right after
# a fixed phase's checkpoint lands (the deterministic preemption stand-in,
# KMLS_FAULT_MINE_CRASH_PHASE), restart it, and report resume-vs-full
# wall clock plus bit-identity of the resumed artifacts against an
# uninterrupted run. The full-run timing is taken on a SECOND, warm run so
# jit compilation (paid once per process, amortized to zero by the
# production job's PVC compilation cache) doesn't inflate the savings.
_TRACEOVERHEAD_BENCH = r"""
import dataclasses, json, os, sys, tempfile, time
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.replay import replay_pooled, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
qps = float(os.environ.get("KMLS_BENCH_TRACE_QPS", "1000"))
n_req = int(os.environ.get("KMLS_BENCH_TRACE_REQUESTS", "6000"))
with tempfile.TemporaryDirectory(prefix="kmls_traceov_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    mcfg = MiningConfig(base_dir=base, datasets_dir=ds_dir, min_support=0.05)
    run_mining_job(mcfg)

    # two identical apps, one knob apart: tracing sampled at 0.01 vs
    # disabled. Both are driven through app.handle (the full HTTP path
    # minus the socket) with pre-encoded bodies — the json cost is paid
    # identically on both sides, so the RATIO isolates the trace cost
    # (begin + the request and batch spans + tail retention). The cache
    # is OFF: a Zipf replay warmed through the cache would answer ~all
    # hits and never reach the batcher's per-pending span recording —
    # the dominant trace cost this bracket exists to bound.
    def build(sample):
        cfg = dataclasses.replace(
            ServingConfig.from_env(), base_dir=base,
            batch_max_size=64, trace_sample=sample, cache_enabled=False,
        )
        app = RecommendApp(cfg)
        assert app.engine.load(), "mined artifacts must load"
        return app

    apps = {"on": build(0.01), "off": build(0.0)}
    body_cache = {}

    def body_of(seeds):
        key = tuple(seeds)
        b = body_cache.get(key)
        if b is None:
            b = json.dumps({"songs": seeds}).encode()
            body_cache[key] = b
        return b

    def make_sender(app):
        def make_send():
            def send(seeds):
                status, headers, _ = app.handle(
                    "POST", "/api/recommend/", body_of(seeds),
                )
                if status >= 500:
                    raise RuntimeError(f"HTTP {status}")
                return ("ok" if status == 200 else "other"), None
            return send
        return make_send

    vocab = apps["on"].engine.bundle.vocab
    payloads = sample_seed_sets(vocab, n_req, rng_seed=47, zipf_s=1.1)
    # steady-state warm per app (replay10k posture), then ALTERNATE the
    # measured runs off/on/off/on so neighbor noise on this host drifts
    # across both modes instead of biasing one
    for app in apps.values():
        send = make_sender(app)()
        for p in {tuple(p): p for p in payloads}.values():
            send(list(p))
        replay_pooled(
            make_sender(app), payloads[: min(2000, n_req)], qps=qps / 2,
            n_workers=16,
        )
    p99s = {"on": [], "off": []}
    p50s = {"on": [], "off": []}
    for _ in range(2):
        for mode in ("off", "on"):
            rep = replay_pooled(
                make_sender(apps[mode]), payloads, qps=qps,
                n_workers=16, max_queue=16384,
            )
            assert rep.n_errors == 0, (mode, rep.n_errors)
            p99s[mode].append(rep.p99_ms)
            p50s[mode].append(rep.p50_ms)
            print(
                f"traceoverhead/{mode}: p50 {rep.p50_ms:.3f}ms "
                f"p99 {rep.p99_ms:.3f}ms ({rep.achieved_qps:.0f} qps)",
                file=sys.stderr, flush=True,
            )
    p99_on, p99_off = min(p99s["on"]), min(p99s["off"])
    rec_on, rec_off = apps["on"].recorder, apps["off"].recorder
    # the zero-cost contract: the disabled recorder never began a trace
    assert rec_off.began == 0, rec_off.began
    assert rec_on.began > 0 and rec_on.retained_total > 0
    print(json.dumps({
        "qps": qps,
        "requests": n_req,
        "p50_on_ms": round(min(p50s["on"]), 3),
        "p50_off_ms": round(min(p50s["off"]), 3),
        "p99_on_ms": round(p99_on, 3),
        "p99_off_ms": round(p99_off, 3),
        "p99_ratio": round(p99_on / max(p99_off, 1e-9), 4),
        "began_on": rec_on.began,
        "began_off": rec_off.began,
        "retained_on": rec_on.retained_total,
        "platform": dev.platform,
    }))
"""

# the cost-attribution bracket (ISSUE 12): replay a Zipf mix through the
# JITTED serve kernel (native kernel off — the XLA kernel is the one the
# TPU suite re-runs on chip) with the cost model on, then report the
# device-truth numbers the costmodel layer derives: serve-kernel MFU
# against the backend peak table, the roofline classification, and the
# live compiles-post-publish counter (must be 0 — the invariant that was
# test-only before ISSUE 12). The disabled-mode proof rides along,
# began-counter style: a second app one knob apart (KMLS_COSTMODEL=0)
# sees the same traffic and the module observation counter must not move.
_COSTATTRIB_BENCH = r"""
import dataclasses, json, os, sys, tempfile
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.observability import costmodel
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.replay import replay_pooled, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
qps = float(os.environ.get("KMLS_BENCH_COSTATTRIB_QPS", "800"))
n_req = int(os.environ.get("KMLS_BENCH_COSTATTRIB_REQUESTS", "4000"))
with tempfile.TemporaryDirectory(prefix="kmls_costattrib_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    run_mining_job(
        MiningConfig(base_dir=base, datasets_dir=ds_dir, min_support=0.05)
    )

    def build(enabled):
        cfg = dataclasses.replace(
            ServingConfig.from_env(), base_dir=base,
            cache_enabled=False,
            costmodel_enabled=enabled,
        )
        app = RecommendApp(cfg)
        assert app.engine.load(), "mined artifacts must load"
        return app

    app_on = build(True)
    body_cache = {}

    def body_of(seeds):
        key = tuple(seeds)
        b = body_cache.get(key)
        if b is None:
            b = json.dumps({"songs": seeds}).encode()
            body_cache[key] = b
        return b

    def make_sender(app):
        def make_send():
            def send(seeds):
                status, headers, _ = app.handle(
                    "POST", "/api/recommend/", body_of(seeds),
                )
                if status >= 500:
                    raise RuntimeError(f"HTTP {status}")
                return ("ok" if status == 200 else "other"), None
            return send
        return make_send

    vocab = app_on.engine.bundle.vocab
    payloads = sample_seed_sets(vocab, n_req, rng_seed=29, zipf_s=1.1)
    rep = replay_pooled(
        make_sender(app_on), payloads, qps=qps, n_workers=16,
        max_queue=16384,
    )
    assert rep.n_errors == 0, rep.n_errors
    cm = app_on.engine.cost_model
    summary = cm.summary()
    serve = summary["kernels"]["serve_rules"]
    compiles = sum(summary["compiles_post_publish"].values())
    # the invariant this bracket makes a live headline: zero compiles on
    # the serving path after publication, and MFU honestly in (0, 1]
    assert compiles == 0, summary["compiles_post_publish"]
    assert 0.0 < serve["mfu"] <= 1.0, serve
    assert summary["unspecced"] == {}, summary["unspecced"]

    # disabled-mode zero-cost proof: same traffic, one knob apart — the
    # module observation counter must not move (no CostModel exists)
    app_off = build(False)
    assert app_off.engine.cost_model is None
    obs_before = costmodel.OBSERVATIONS_TOTAL
    rep_off = replay_pooled(
        make_sender(app_off), payloads[: min(1000, n_req)], qps=qps,
        n_workers=16,
    )
    assert rep_off.n_errors == 0, rep_off.n_errors
    obs_off_delta = costmodel.OBSERVATIONS_TOTAL - obs_before
    assert obs_off_delta == 0, obs_off_delta

    print(json.dumps({
        "qps": qps,
        "requests": n_req,
        "p50_ms": round(rep.p50_ms, 3),
        "p99_ms": round(rep.p99_ms, 3),
        "mfu": serve["mfu"],
        "roofline": serve["roofline"],
        "flops_per_s": serve["flops_per_s"],
        "bytes_per_s": serve["bytes_per_s"],
        "device_s": round(serve["device_s"], 4),
        "dispatches": serve["dispatches"],
        "compiles": compiles,
        "obs_off_delta": obs_off_delta,
        "peak_flops": summary["peak_flops"],
        "peak_source": summary["peak_source"],
        "headroom_bytes": summary["headroom_bytes"],
        "platform": dev.platform,
    }))
"""

_MINE_RESUME_BENCH = r"""
import json, os, sys, tempfile, time
import jax
from kmlserver_tpu import faults
from kmlserver_tpu.config import MiningConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
crash_phase = os.environ.get("KMLS_BENCH_RESUME_PHASE", "mine")
with tempfile.TemporaryDirectory(prefix="kmls_resume_") as root:
    def make_base(name):
        base = os.path.join(root, name)
        ds = os.path.join(base, "datasets")
        os.makedirs(ds)
        write_tracks_csv(
            os.path.join(ds, "2023_spotify_ds2.csv"),
            synthetic_table(**DS2_SHAPE, seed=123),
        )
        return MiningConfig(base_dir=base, datasets_dir=ds, min_support=0.05)

    def artifact_bytes(cfg):
        out = {}
        for name in (cfg.recommendations_file, cfg.best_tracks_file):
            with open(os.path.join(cfg.pickles_dir, name), "rb") as fh:
                out[name] = fh.read()
        return out

    # run 1: warmup (pays every jit compile) + the reference bytes
    cfg_warm = make_base("warm")
    run_mining_job(cfg_warm)
    ref = artifact_bytes(cfg_warm)

    # run 2: the timed UNINTERRUPTED baseline, warm
    cfg_full = make_base("full")
    t0 = time.perf_counter()
    run_mining_job(cfg_full)
    full_s = time.perf_counter() - t0

    # run 3: killed right after crash_phase's checkpoint persists
    cfg_int = make_base("interrupted")
    faults.inject(f"mine.crash.{crash_phase}", times=1)
    t0 = time.perf_counter()
    try:
        run_mining_job(cfg_int)
        raise SystemExit(f"crash fault at {crash_phase} never fired")
    except faults.FaultInjected:
        pass
    interrupted_s = time.perf_counter() - t0
    faults.clear()

    # run 4: the restart — resumes from the checkpoint
    t0 = time.perf_counter()
    summary = run_mining_job(cfg_int)
    resume_s = time.perf_counter() - t0

    print(json.dumps({
        "crash_phase": crash_phase,
        "resumed_phases": list(summary.resumed_phases),
        "full_s": full_s,
        "interrupted_s": interrupted_s,
        "resume_s": resume_s,
        "saved_pct": 100.0 * (1.0 - resume_s / full_s) if full_s > 0 else 0.0,
        "identical": artifact_bytes(cfg_int) == ref,
        "platform": dev.platform,
    }))
"""

_REPLAY_CLIENT = r"""
import json, os, pickle, sys
from kmlserver_tpu.serving.replay import (
    ClientTraceLog, replay_async_http, sample_seed_sets,
)

url, qps, n, pickles = sys.argv[1], float(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
# optional 5th arg: JSONL path for echoed X-KMLS-Trace ids + client
# send/recv wall clocks — the client half of scripts/kmls_tracejoin.py
trace_path = sys.argv[5] if len(sys.argv) > 5 else None
# seed vocabulary straight from the artifact pickle — no jax in the client
# (the server owns the TPU; libtpu is one process per chip)
with open(pickles, "rb") as f:
    vocab = sorted(pickle.load(f).keys())
# the single-loop pipelined client (replay_async_http): thread-pool
# loadgens convoy on the GIL and pay ~2 syscall traps per request on this
# sandbox — they melt before the server does and mismeasure it. In-flight
# capacity = n_conns x pipeline; by Little's law a server answering in
# ~0.5 s needs ~500 in flight at 1k QPS, so the conn count scales with
# the env override rather than a fixed 64.
trace_log = ClientTraceLog() if trace_path else None
report = replay_async_http(
    url, sample_seed_sets(vocab, n), qps=qps,
    n_conns=min(int(os.environ.get("KMLS_BENCH_REPLAY_WORKERS", "48")), 128),
    max_queue=int(os.environ.get("KMLS_BENCH_REPLAY_QUEUE", "4096")),
    trace_log=trace_log,
)
out = json.loads(report.to_json())
if trace_log is not None:
    out["trace_records"] = trace_log.write_jsonl(trace_path)
print(json.dumps(out))
"""


# the second-model-family phase (ISSUE 6): ALS embedding training time
# through the real pipeline (embed phase enabled), then hybrid
# rule∪embedding serving — 1k-QPS blend-mode replay p50/p99 through
# cache → batcher → both kernels, plus the cold-start bracket: every
# zero-rule track in the embedding vocabulary is asked as a single seed
# and the hit fraction counts answers served from the embedding space
# (source "embed") instead of the popularity fallback. In-process for the
# same reason as replay10k. CPU-platform by construction, self-labeled.
_ALS_HYBRID_BENCH = r"""
import dataclasses, json, os, sys, tempfile
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.replay import replay_pooled, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
with tempfile.TemporaryDirectory(prefix="kmls_als_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    mcfg = dataclasses.replace(
        MiningConfig.from_env(dotenv_path=None), base_dir=base,
        datasets_dir=ds_dir, min_support=0.05, embed_enabled=True,
    )
    summary = run_mining_job(mcfg)
    cfg = dataclasses.replace(
        ServingConfig.from_env(dotenv_path=None), base_dir=base,
        hybrid_mode="blend", batch_max_size=64, shed_queue_budget_ms=0.0,
    )
    app = RecommendApp(cfg)
    assert app.engine.load(), "mined artifacts must load"
    bundle = app.engine.bundle
    assert bundle.emb_factors is not None, "embedding artifact must attach"

    # cold-start bracket: every embedding-vocab track with ZERO rules
    known = {bundle.vocab[i] for i in range(len(bundle.vocab))
             if bundle.known_mask[i]}
    cold = [n for n in bundle.emb_vocab if n not in known][:512]
    embed_answered = 0
    for name in cold:
        _songs, source, _cached = app.recommend_direct([name])
        if source == "embed":
            embed_answered += 1

    def make_send():
        def send(seeds):
            recs, source, cached = app.recommend_direct(seeds)
            return source, cached
        return send

    payloads = sample_seed_sets(
        bundle.emb_vocab, 8000, rng_seed=11, zipf_s=1.1
    )
    replay_pooled(make_send, payloads[:1000], qps=250, n_workers=8)  # warm
    report = replay_pooled(
        make_send, payloads, qps=1000, n_workers=16, max_queue=4096
    )
    print(json.dumps({
        "als_train_s": round(summary.als_train_s, 3),
        "als_rank": mcfg.als_rank,
        "als_iters": mcfg.als_iters,
        "emb_vocab": len(bundle.emb_vocab),
        "qps": 1000.0,
        "achieved_qps": report.achieved_qps,
        "p50_ms": report.p50_ms,
        "p95_ms": report.p95_ms,
        "p99_ms": report.p99_ms,
        "errors": report.n_errors,
        "cold_start_seeds": len(cold),
        "cold_start_hit_frac": (
            embed_answered / len(cold) if cold else None
        ),
        "platform": dev.platform,
    }))
"""

# confidence-mode serving bracket (carried-over ROADMAP item): mine with
# the dormant slow path's true-confidence semantics + multi-antecedent
# rules (max_itemset_len 3), then replay-grade the SAME max-merge kernel
# those rules serve through (native kernel off so the jitted device
# kernel is the one measured). In-process; CPU-platform by construction.
_CONFSERVE_BENCH = r"""
import dataclasses, json, os, sys, tempfile
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.app import RecommendApp
from kmlserver_tpu.serving.replay import replay_pooled, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
with tempfile.TemporaryDirectory(prefix="kmls_confserve_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    mcfg = dataclasses.replace(
        MiningConfig.from_env(dotenv_path=None), base_dir=base,
        datasets_dir=ds_dir, min_support=0.05,
        confidence_mode="confidence", max_itemset_len=3,
    )
    run_mining_job(mcfg)
    cfg = dataclasses.replace(
        ServingConfig.from_env(dotenv_path=None), base_dir=base,
        batch_max_size=64, shed_queue_budget_ms=0.0,
    )
    app = RecommendApp(cfg)
    assert app.engine.load(), "mined artifacts must load"
    bundle = app.engine.bundle

    def make_send():
        def send(seeds):
            recs, source, cached = app.recommend_direct(seeds)
            return source, cached
        return send

    payloads = sample_seed_sets(bundle.vocab, 8000, rng_seed=7, zipf_s=1.1)
    replay_pooled(make_send, payloads[:1000], qps=250, n_workers=8)  # warm
    report = replay_pooled(
        make_send, payloads, qps=1000, n_workers=16, max_queue=4096
    )
    print(json.dumps({
        "qps": 1000.0,
        "achieved_qps": report.achieved_qps,
        "p50_ms": report.p50_ms,
        "p95_ms": report.p95_ms,
        "p99_ms": report.p99_ms,
        "errors": report.n_errors,
        "rule_keys": int(bundle.known_mask.sum()),
        "max_itemset_len": mcfg.max_itemset_len,
        "confidence_mode": mcfg.confidence_mode,
        "platform": dev.platform,
    }))
"""


# model-parallel serving bracket (ISSUE 7): mine a real catalog, publish
# it under BOTH layouts, and prove the acceptance on the 8-virtual-device
# mesh — auto resolves to sharded because the rule tensors measure over
# the (deliberately tiny) per-device budget, answers are bit-identical to
# the replicated engine, zero compiles post-publish, and the p50/p99 of
# both layouts land in the artifact alongside the max servable catalog
# bytes the mesh buys (budget × shards vs one device's budget).
_SHARDSERVE_BENCH = r"""
import dataclasses, json, os, sys, tempfile, time
import numpy as np
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.engine import RecommendEngine

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
n_devices = len(jax.devices())
assert n_devices >= 4, f"mesh bracket needs >=4 virtual devices, have {n_devices}"
with tempfile.TemporaryDirectory(prefix="kmls_shardserve_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    mcfg = dataclasses.replace(
        MiningConfig.from_env(dotenv_path=None), base_dir=base,
        datasets_dir=ds_dir, min_support=0.05,
    )
    run_mining_job(mcfg)

    common = dict(
        base_dir=base, batch_max_size=32, max_seed_tracks=8,
    )
    rep = RecommendEngine(dataclasses.replace(
        ServingConfig.from_env(dotenv_path=None), serve_devices=1, **common
    ))
    assert rep.load()
    catalog_bytes = int(
        np.asarray(rep.bundle.rule_ids).nbytes
        + np.asarray(rep.bundle.rule_confs).nbytes
    )
    # budget HALF the catalog: one (virtual) device cannot hold a replica,
    # so the auto layout MUST measure its way to sharded
    budget = max(catalog_bytes // 2, 1)
    shd = RecommendEngine(dataclasses.replace(
        ServingConfig.from_env(dotenv_path=None), serve_devices=n_devices,
        model_layout="auto", device_budget_bytes=budget, **common
    ))
    assert shd.load()
    assert shd.bundle.layout == "sharded", shd.bundle.layout
    shards = shd.bundle.n_shards

    bundle = shd.bundle
    rng = np.random.default_rng(0)
    known = [
        s for s in bundle.vocab if bundle.known_mask[bundle.index[s]]
    ]
    sets = [
        list(rng.choice(known, size=int(rng.integers(1, 5)), replace=False))
        for _ in range(32)
    ]
    identical = rep.recommend_many_async(sets)() == \
        shd.recommend_many_async(sets)()

    def bracket(engine, reps=40):
        engine.recommend_many_async(sets)()  # warm the bucket
        lat = []
        for _ in range(reps):
            t0 = time.perf_counter()
            engine.recommend_many_async(sets)()
            lat.append((time.perf_counter() - t0) * 1e3)
        lat.sort()
        return lat[len(lat) // 2], lat[min(int(len(lat) * 0.99), len(lat) - 1)]

    rep_p50, rep_p99 = bracket(rep)
    shd_p50, shd_p99 = bracket(shd)
    print(json.dumps({
        "shards": shards,
        "identical": bool(identical),
        "unwarmed_dispatches": shd.unwarmed_dispatches,
        "catalog_bytes": catalog_bytes,
        "device_budget_bytes": budget,
        "max_catalog_bytes": budget * shards,
        "replicated_p50_ms": round(rep_p50, 3),
        "replicated_p99_ms": round(rep_p99, 3),
        "sharded_p50_ms": round(shd_p50, 3),
        "sharded_p99_ms": round(shd_p99, 3),
        "shard_dispatch_counts": shd.shard_dispatch_counts,
        "platform": dev.platform,
    }))
"""

# the pod-spanning serve-mesh bracket (ISSUE 16): the same over-budget
# catalog served two ways — single-PROCESS sharded (the ISSUE 7 ceiling:
# whatever one host's devices hold) vs a 2-member serve GANG where each
# member holds only its vocab slab and the answer merges over the socket
# mesh transport. Identity leg pins gang answers bit-identical to the
# replicated reference AND the single-process sharded kernel on BOTH
# members with zero compiles post-publish; the chaos leg runs 2 REAL
# gang server processes + 1 solo replica behind the routed replay client
# and SIGKILLs a gang member mid-replay — the gang must degrade exactly
# like a dead replica (503 + X-KMLS-Mesh-Unavailable → whole-gang
# ejection → spill to the solo peer), never as a 5xx or a drop.
_MESHSERVE_BENCH = r"""
import dataclasses, json, os, re, signal, socket, subprocess, sys
import tempfile, threading, time, urllib.request
import numpy as np
import jax
from kmlserver_tpu.config import MiningConfig, ServingConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving.engine import RecommendEngine
from kmlserver_tpu.serving.replay import replay_fleet_http, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
qps = float(os.environ.get("KMLS_BENCH_MESHSERVE_QPS", "500"))
n_req = int(os.environ.get("KMLS_BENCH_MESHSERVE_REQUESTS", "4000"))
GANG = 2
n_devices = len(jax.devices())
assert n_devices >= GANG, f"mesh bracket needs >={GANG} virtual devices"

def gang_ports():
    # a base port where base..base+GANG-1 are all free: bare-host
    # coordinator addressing derives member ports by rank offset
    for base in range(29170, 29970, 10):
        socks = []
        try:
            for r in range(GANG):
                s = socket.socket()
                s.bind(("127.0.0.1", base + r))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free consecutive port pair")

with tempfile.TemporaryDirectory(prefix="kmls_meshserve_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    mcfg = dataclasses.replace(
        MiningConfig.from_env(dotenv_path=None), base_dir=base,
        datasets_dir=ds_dir, min_support=0.05,
    )
    run_mining_job(mcfg)

    common = dict(
        base_dir=base, batch_max_size=32, max_seed_tracks=8,
    )
    rep = RecommendEngine(dataclasses.replace(
        ServingConfig.from_env(dotenv_path=None), serve_devices=1, **common
    ))
    assert rep.load()
    catalog_bytes = int(
        np.asarray(rep.bundle.rule_ids).nbytes
        + np.asarray(rep.bundle.rule_confs).nbytes
    )
    # budget HALF the catalog: neither one virtual device nor one gang
    # member can hold a replica — the single-process comparator must
    # measure its way to sharded, the gang spans the rest over sockets
    budget = max(catalog_bytes // 2, 1)
    shd = RecommendEngine(dataclasses.replace(
        ServingConfig.from_env(dotenv_path=None), serve_devices=n_devices,
        model_layout="auto", device_budget_bytes=budget, **common
    ))
    assert shd.load()
    assert shd.bundle.layout == "sharded", shd.bundle.layout

    mesh_base = gang_ports()
    members = []
    for rank in range(GANG):
        m = RecommendEngine(dataclasses.replace(
            ServingConfig.from_env(dotenv_path=None),
            device_budget_bytes=budget,
            serve_gang_coordinator=f"127.0.0.1:{mesh_base}",
            serve_gang_size=GANG, serve_gang_rank=rank,
            serve_gang_port=mesh_base + rank,
            **common,
        ))
        members.append(m)
    for rank, m in enumerate(members):
        assert m.load(), f"gang rank {rank} failed to load"
        assert m.bundle.layout == "mesh", m.bundle.layout

    bundle = shd.bundle
    rng = np.random.default_rng(0)
    known = [
        s for s in bundle.vocab if bundle.known_mask[bundle.index[s]]
    ]
    sets = [
        list(rng.choice(known, size=int(rng.integers(1, 5)), replace=False))
        for _ in range(32)
    ]
    ref_ans = rep.recommend_many_async(sets)()
    identical = (
        ref_ans == shd.recommend_many_async(sets)()
        and all(ref_ans == m.recommend_many_async(sets)() for m in members)
    )

    def bracket(engine, reps=40):
        engine.recommend_many_async(sets)()  # warm the bucket
        lat = []
        for _ in range(reps):
            t0 = time.perf_counter()
            engine.recommend_many_async(sets)()
            lat.append((time.perf_counter() - t0) * 1e3)
        lat.sort()
        return lat[len(lat) // 2], lat[min(int(len(lat) * 0.99), len(lat) - 1)]

    shd_p50, shd_p99 = bracket(shd)
    mesh_p50, mesh_p99 = bracket(members[0])
    unwarmed = sum(m.unwarmed_dispatches for m in members)
    missing = members[0].mesh_missing_shards()
    assert missing == [], f"gang dark mid-bracket: {missing}"
    for m in members:  # free the mesh ports before the HTTP leg
        if m.mesh_worker is not None:
            m.mesh_worker.stop()
        if m.mesh_coordinator is not None:
            m.mesh_coordinator.close()
    print(
        f"identity leg: identical={identical}, unwarmed={unwarmed}, "
        f"sharded p50 {shd_p50:.2f}ms vs mesh p50 {mesh_p50:.2f}ms",
        file=sys.stderr, flush=True,
    )

    # ---- chaos leg: 2 REAL gang server processes + 1 solo replica.
    # The ring lists the gang ONCE (rank 0's URL is the gang's front
    # door); mid-replay SIGKILL of rank 1 darkens a SHARD, and the
    # routed client must see only 503+X-KMLS-Mesh-Unavailable refusals
    # (ejection + spill to solo), zero 5xx, zero drops.
    http_base = gang_ports()  # fresh pair for the server gang
    procs, ports, logs = {}, {}, {}
    def _terminate_all():
        for proc in procs.values():
            try:
                proc.terminate()
            except Exception:
                pass
        for proc in procs.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
    def start_server(name, gang_rank=None):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # servers don't need the virtual mesh
        env.update({
            "BASE_DIR": base, "KMLS_PORT": "0",
            "KMLS_SHED_QUEUE_BUDGET_MS": "0",
            "KMLS_FLEET_SELF": "gang" if gang_rank is not None else "solo",
            "KMLS_FLEET_PEERS": "gang,solo",
        })
        if gang_rank is not None:
            env.update({
                "KMLS_SERVE_GANG_COORDINATOR": f"127.0.0.1:{http_base}",
                "KMLS_SERVE_GANG_SIZE": str(GANG),
                "KMLS_SERVE_GANG_RANK": str(gang_rank),
                # bare-host addressing: member rank r binds base + r
                "KMLS_SERVE_GANG_PORT": str(http_base + gang_rank),
            })
        proc = subprocess.Popen(
            [sys.executable, "-m", "kmlserver_tpu.serving.server"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        lines = []
        logs[name] = lines
        def drain():
            for line in proc.stdout:
                lines.append(line.rstrip())
                m = re.search(r"serving on \S+?:(\d+)", line)
                if m and name not in ports:
                    ports[name] = int(m.group(1))
        threading.Thread(target=drain, daemon=True).start()
        procs[name] = proc
        return proc

    try:
        for rank in range(GANG):
            start_server(f"gang-{rank}", gang_rank=rank)
        start_server("solo")
        t_wait = time.time()
        while len(ports) < GANG + 1 and time.time() - t_wait < 120:
            time.sleep(0.1)
        assert len(ports) == GANG + 1, f"servers never reported ports: {ports}"
        def wait_ready(url, deadline_s=180):
            t0 = time.time()
            while time.time() - t0 < deadline_s:
                try:
                    with urllib.request.urlopen(url + "/readyz", timeout=5) as r:
                        if r.status == 200:
                            return True
                except Exception:
                    pass
                time.sleep(0.25)
            return False
        urls = {
            name: f"http://127.0.0.1:{port}" for name, port in ports.items()
        }
        for name, url in urls.items():
            assert wait_ready(url), f"{name} never went ready"
        print(f"mesh fleet up: {urls}", file=sys.stderr, flush=True)

        vocab = sorted(known)
        payloads = sample_seed_sets(
            vocab, n_req, rng_seed=61, zipf_s=1.1, zipf_pool=2048,
        )
        kill_at = int(n_req * 0.5)
        victim = procs[f"gang-{GANG - 1}"]
        events = [(kill_at, lambda: victim.send_signal(signal.SIGKILL))]
        # the gang is ONE ring peer, fronted by rank 0
        ring_urls = {"gang": urls["gang-0"], "solo": urls["solo"]}
        rep_http, fleet = replay_fleet_http(
            ring_urls, payloads, qps=qps, policy="ring", events=events,
        )
    finally:
        _terminate_all()

    assert fleet["http_5xx"] == 0, f"5xx through shard loss: {fleet}"
    assert rep_http.n_errors == 0, f"drops through shard loss: {rep_http}"
    assert fleet["mesh_unavailable"] >= 1, f"no mesh refusals seen: {fleet}"
    assert fleet["ejections"] >= 1, f"gang never ejected: {fleet}"
    print(json.dumps({
        "gang_size": GANG,
        "identical": bool(identical),
        "unwarmed_dispatches": unwarmed,
        "catalog_bytes": catalog_bytes,
        "host_budget_bytes": budget,
        "max_catalog_bytes": budget * GANG,
        "sharded_p50_ms": round(shd_p50, 3),
        "sharded_p99_ms": round(shd_p99, 3),
        "mesh_p50_ms": round(mesh_p50, 3),
        "mesh_p99_ms": round(mesh_p99, 3),
        "replay_qps": qps,
        "replay_requests": n_req,
        "achieved_qps": rep_http.achieved_qps,
        "replay_p99_ms": rep_http.p99_ms,
        "http_5xx": fleet["http_5xx"],
        "errors": rep_http.n_errors,
        "mesh_unavailable": fleet["mesh_unavailable"],
        "ejections": fleet["ejections"],
        "failed_shards": fleet["failed_shards"],
        "answered_by": fleet["answered_by"],
        "platform": dev.platform,
    }))
"""

# gray-failure chaos bracket (ISSUE 18): a 200 ms deterministic stall —
# injected via the KMLS_FAULT_*_PEER_DELAY_MS sites, never a kill — on
# one fleet peer and one gang member, with the hedged leg racing the
# no-hedge control at equal capacity. The stalled peer answers every
# request successfully (late), so nothing here ever trips the PR 15/16
# error breakers: only the slow-outlier ladder + hedged dispatch can
# route around it. Judged claims: hedged p99 ≥ 5x better than control,
# hedge overhead (extra dispatches / total) ≤ 5%, zero 5xx and zero
# drops on EVERY leg, bit-identical answers whichever copy wins
# (hedge_mismatch == 0 + post-replay cross-replica probe identity), and
# the in-bench zero-cost pin: the control leg leaves the module
# HEDGES_ISSUED counter at exactly 0 under real traffic.
_SLOWPEER_BENCH = r"""
import json, os, pickle, re, socket, subprocess, sys, tempfile
import threading, time, urllib.request
import jax
from kmlserver_tpu.config import MiningConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table
from kmlserver_tpu.mining.pipeline import run_mining_job
from kmlserver_tpu.serving import replay as replay_mod
from kmlserver_tpu.serving.replay import replay_fleet_http, sample_seed_sets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
# qps sits deliberately UNDER the stalled peer's service capacity
# (n_conns / stall = 20 req/s at 200 ms): the control leg must measure
# the gray-failure tail itself, not an overload collapse on top of it —
# both legs then see the identical, stable fault
qps = float(os.environ.get("KMLS_BENCH_SLOWPEER_QPS", "32"))
n_req = int(os.environ.get("KMLS_BENCH_SLOWPEER_REQUESTS", "600"))
STALL_MS = 200
GANG = 2

with tempfile.TemporaryDirectory(prefix="kmls_slowpeer_") as base:
    ds_dir = os.path.join(base, "datasets")
    os.makedirs(ds_dir)
    write_tracks_csv(
        os.path.join(ds_dir, "2023_spotify_ds2.csv"),
        synthetic_table(**DS2_SHAPE, seed=123),
    )
    run_mining_job(MiningConfig(
        base_dir=base, datasets_dir=ds_dir, min_support=0.05,
    ))
    with open(
        os.path.join(base, "pickles", "recommendations.pickle"), "rb"
    ) as fh:
        vocab = sorted(pickle.load(fh).keys())

    procs, ports, logs = {}, {}, {}
    def _terminate_all():
        for proc in procs.values():
            try:
                proc.terminate()
            except Exception:
                pass
        for proc in procs.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        procs.clear()
        ports.clear()
    def start_server(name, extra_env):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # servers don't need the virtual mesh
        env.update({
            "BASE_DIR": base, "KMLS_PORT": "0",
            "KMLS_SHED_QUEUE_BUDGET_MS": "0",
        })
        env.update(extra_env)
        proc = subprocess.Popen(
            [sys.executable, "-m", "kmlserver_tpu.serving.server"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        lines = logs.setdefault(name, [])
        def drain():
            for line in proc.stdout:
                lines.append(line.rstrip())
                m = re.search(r"serving on \S+?:(\d+)", line)
                if m and name not in ports:
                    ports[name] = int(m.group(1))
        threading.Thread(target=drain, daemon=True).start()
        procs[name] = proc
    def await_up(n):
        t_wait = time.time()
        while len(ports) < n and time.time() - t_wait < 120:
            time.sleep(0.1)
        assert len(ports) == n, f"servers never reported ports: {ports}"
        urls = {name: f"http://127.0.0.1:{p}" for name, p in ports.items()}
        for name, url in urls.items():
            t0 = time.time()
            ready = False
            while time.time() - t0 < 180:
                try:
                    with urllib.request.urlopen(url + "/readyz", timeout=5) as r:
                        if r.status == 200:
                            ready = True
                            break
                except Exception:
                    pass
                time.sleep(0.25)
            assert ready, f"{name} never went ready"
        return urls
    def scrape(url):
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        out = {}
        for line in text.splitlines():
            if line and not line.startswith("#"):
                parts = line.split()
                if len(parts) == 2:
                    try:
                        out[parts[0]] = float(parts[1])
                    except ValueError:
                        pass
        return out
    def probe(url, seeds):
        body = json.dumps({"songs": seeds}).encode()
        req = urllib.request.Request(
            url + "/api/recommend/", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=15) as r:
            return json.load(r)["songs"]

    # ---- fleet pair: replica-1 (sorted fleet index 1) stalls EVERY
    # request STALL_MS via the armed fault site — a pure gray failure,
    # alive and answering for both legs at equal capacity
    fleet_env = {"KMLS_FLEET_PEERS": "replica-0,replica-1"}
    try:
        start_server("replica-0", {**fleet_env, "KMLS_FLEET_SELF": "replica-0"})
        start_server("replica-1", {
            **fleet_env, "KMLS_FLEET_SELF": "replica-1",
            "KMLS_FAULT_FLEET_PEER_DELAY_MS": f"1:{STALL_MS}:-1",
        })
        urls = await_up(2)
        print(f"fleet up: {urls}", file=sys.stderr, flush=True)

        # leg A — no-hedge control: PR 15 routing exactly. The stalled
        # peer owns ~half the keys and error-breaks NOTHING, so its
        # stall compounds down each pipelined connection — the gray-
        # failure tail the spine exists to cut.
        payloads_a = sample_seed_sets(
            vocab, n_req, rng_seed=41, zipf_s=1.1, zipf_pool=1024,
        )
        rep_ctl, fleet_ctl = replay_fleet_http(
            urls, payloads_a, qps=qps, policy="ring",
        )
        # the in-bench zero-cost pin: real traffic, hedging off, the
        # module counter must not have moved
        control_hedges = replay_mod.HEDGES_ISSUED
        print(
            f"control: p50 {rep_ctl.p50_ms:.1f}ms p99 {rep_ctl.p99_ms:.1f}ms, "
            f"{fleet_ctl['http_5xx']} 5xx, {rep_ctl.n_errors} errors, "
            f"hedges {control_hedges}",
            file=sys.stderr, flush=True,
        )

        # leg B — the gray-failure spine armed: slow ladder + hedged
        # dispatch + deadline budgets on every hop, same fleet, same
        # stall, same offered load
        payloads_b = sample_seed_sets(
            vocab, n_req, rng_seed=42, zipf_s=1.1, zipf_pool=1024,
        )
        # deadline 5 s: wide enough that nothing degrades (the digest
        # identity claim compares FULL answers — deadline-degraded
        # bodies are a different, correct answer), tight enough that the
        # budget header rides every hop; probes every 5 s so ejection-
        # probe hedges don't eat the ≤5% overhead budget
        rep_hdg, fleet_hdg = replay_fleet_http(
            urls, payloads_b, qps=qps, policy="ring",
            hedge=True, hedge_delay_ms=20.0, hedge_max_frac=0.5,
            slow_ratio=3.0, deadline_ms=5000.0, probe_interval_s=5.0,
        )
        print(
            f"hedged: p50 {rep_hdg.p50_ms:.1f}ms p99 {rep_hdg.p99_ms:.1f}ms, "
            f"{fleet_hdg['hedges_issued']} hedges "
            f"({fleet_hdg['hedge_wins']} won), "
            f"{fleet_hdg['slow_ejections']} slow ejections, "
            f"{fleet_hdg['http_5xx']} 5xx, {rep_hdg.n_errors} errors",
            file=sys.stderr, flush=True,
        )

        # bit-identity across the hedge winner: the digest check rode
        # every double-answered request (hedge_mismatch), and both
        # replicas must still answer probes identically — the stalled
        # peer is SLOW, never wrong
        probe_sets = payloads_b[:3] + [vocab[:3]]
        identity_ok = all(
            probe(urls["replica-0"], seeds) == probe(urls["replica-1"], seeds)
            for seeds in probe_sets
        )
        expired = scrape(urls["replica-0"]).get(
            "kmls_deadline_expired_total", 0
        ) + scrape(urls["replica-1"]).get("kmls_deadline_expired_total", 0)
    finally:
        _terminate_all()

    assert control_hedges == 0, (
        f"hedges issued with hedging off: {control_hedges}"
    )
    assert fleet_ctl["http_5xx"] == 0 and rep_ctl.n_errors == 0, (
        f"control leg not clean: {fleet_ctl} {rep_ctl}"
    )
    assert fleet_hdg["http_5xx"] == 0 and rep_hdg.n_errors == 0, (
        f"hedged leg not clean: {fleet_hdg} {rep_hdg}"
    )
    assert fleet_hdg["hedge_wins"] >= 1, f"no hedge ever won: {fleet_hdg}"
    assert fleet_hdg["hedge_mismatch"] == 0, (
        f"hedge answered differently from primary: {fleet_hdg}"
    )
    p99_ratio = (
        rep_ctl.p99_ms / rep_hdg.p99_ms if rep_hdg.p99_ms > 0 else float("inf")
    )
    overhead_pct = 100.0 * fleet_hdg["hedges_issued"] / max(1, n_req)

    # ---- gang pair: rank 1 stalls its first partials — the coordinator
    # must merge without the straggler (degraded answers, zero 5xx, the
    # rank never blamed missing), then recover when the stall drains
    def gang_ports():
        for gbase in range(29170, 29970, 10):
            socks = []
            try:
                for r in range(GANG):
                    s = socket.socket()
                    socks.append(s)
                    s.bind(("127.0.0.1", gbase + r))
                return gbase
            except OSError:
                continue
            finally:
                for s in socks:
                    s.close()
        raise RuntimeError("no free consecutive port pair")
    mesh_base = gang_ports()
    n_req_mesh = max(100, n_req // 2)
    logs.clear()
    try:
        for rank in range(GANG):
            env = {
                "KMLS_FLEET_SELF": "gang", "KMLS_FLEET_PEERS": "gang",
                "KMLS_SERVE_GANG_COORDINATOR": f"127.0.0.1:{mesh_base}",
                "KMLS_SERVE_GANG_SIZE": str(GANG),
                "KMLS_SERVE_GANG_RANK": str(rank),
                "KMLS_SERVE_GANG_PORT": str(mesh_base + rank),
                "KMLS_HEDGE": "1",
                "KMLS_HEDGE_DELAY_MS": "20",
                "KMLS_HEDGE_MAX_FRAC": "0.5",
                "KMLS_PEER_SLOW_RATIO": "3.0",
            }
            if rank == 1:
                # a finite stall: rank 1 recovers mid-replay, so the
                # bracket also covers the straggler rejoining the merge
                env["KMLS_FAULT_MESH_PEER_DELAY_MS"] = f"1:{STALL_MS}:12"
            start_server(f"gang-{rank}", env)
        urls = await_up(GANG)
        print(f"gang up: {urls}", file=sys.stderr, flush=True)
        ring_urls = {"gang": urls["gang-0"]}
        payloads_m = sample_seed_sets(
            vocab, n_req_mesh, rng_seed=43, zipf_s=1.1, zipf_pool=1024,
        )
        rep_m, fleet_m = replay_fleet_http(
            ring_urls, payloads_m, qps=qps, policy="ring",
            deadline_ms=1500.0,
        )
        front = scrape(urls["gang-0"])
        stalled = scrape(urls["gang-1"])
    finally:
        _terminate_all()

    assert fleet_m["http_5xx"] == 0 and rep_m.n_errors == 0, (
        f"mesh leg not clean: {fleet_m} {rep_m}"
    )
    mesh_hedge_wins = front.get("kmls_hedge_wins_total", 0)
    mesh_degraded = front.get("kmls_mesh_straggler_degraded_total", 0)
    assert mesh_hedge_wins >= 1, f"coordinator never hedged: {front}"
    assert mesh_degraded >= 1, f"no straggler-degraded answers: {front}"

    print(json.dumps({
        "qps": qps,
        "requests": n_req,
        "stall_ms": STALL_MS,
        "control_p50_ms": rep_ctl.p50_ms,
        "control_p99_ms": rep_ctl.p99_ms,
        "hedged_p50_ms": rep_hdg.p50_ms,
        "hedged_p99_ms": rep_hdg.p99_ms,
        "p99_ratio": p99_ratio,
        "hedge_overhead_pct": overhead_pct,
        "hedges_issued": fleet_hdg["hedges_issued"],
        "hedge_wins": fleet_hdg["hedge_wins"],
        "hedge_losses": fleet_hdg["hedge_losses"],
        "hedges_suppressed": fleet_hdg["hedges_suppressed"],
        "hedge_mismatch": fleet_hdg["hedge_mismatch"],
        "slow_ejections": fleet_hdg["slow_ejections"],
        "deadline_expired": fleet_hdg["deadline_expired"],
        "server_deadline_expired": expired,
        "control_hedges_issued": control_hedges,
        "control_http_5xx": fleet_ctl["http_5xx"],
        "control_errors": rep_ctl.n_errors,
        "http_5xx": fleet_hdg["http_5xx"] + fleet_ctl["http_5xx"]
        + fleet_m["http_5xx"],
        "errors": rep_hdg.n_errors + rep_ctl.n_errors + rep_m.n_errors,
        "identity_ok": bool(identity_ok),
        "mesh_requests": n_req_mesh,
        "mesh_hedge_wins": mesh_hedge_wins,
        "mesh_hedge_cancelled": front.get("kmls_hedge_cancelled_total", 0),
        "mesh_straggler_degraded": mesh_degraded,
        "mesh_expired_on_arrival": stalled.get(
            "kmls_mesh_expired_on_arrival_total", 0
        ),
        "mesh_p99_ms": rep_m.p99_ms,
        "mesh_http_5xx": fleet_m["http_5xx"],
        "mesh_errors": rep_m.n_errors,
        "platform": dev.platform,
    }))
"""

# vocab-sharded mining bracket (ISSUE 7): a basket matrix whose dense
# single-device formulation busts the (deliberately small) HBM budget is
# mined through the sharded count→emit pipeline on a 1x8 vocab mesh —
# counts stay column-sharded, each shard emits its own antecedent rows.
# Bitpack is pinned off so the bracket measures the MODEL-sharded dense
# path, not the bit-packed fallback the budget would otherwise trigger.
_SCALE_SHARD_BENCH = r"""
import dataclasses, json, sys, time
import jax
from kmlserver_tpu.config import MiningConfig
from kmlserver_tpu.data.synthetic import synthetic_table
from kmlserver_tpu.mining.miner import mine
from kmlserver_tpu.mining.vocab import build_baskets

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
n_devices = len(jax.devices())
assert n_devices >= 4, f"mesh bracket needs >=4 virtual devices, have {n_devices}"
P_N, V_N, ROWS = 20000, 2000, 400000
table = synthetic_table(
    n_playlists=P_N, n_tracks=V_N, target_rows=ROWS, seed=11
)
baskets = build_baskets(table)
# dense single-device plan: int8 one-hot + int32 counts + top-k scratch
dense_bytes = P_N * V_N + 8 * V_N * V_N
budget = dense_bytes // 2  # one device cannot hold the dense formulation
cfg = dataclasses.replace(
    MiningConfig.from_env(dotenv_path=None),
    min_support=0.005, k_max_consequents=64,
    model_layout="sharded", bitpack_threshold_elems=None,
    hbm_budget_bytes=budget, prune_vocab_threshold=1 << 30,
)
t0 = time.perf_counter()
result = mine(baskets, cfg)
mine_s = time.perf_counter() - t0
n_rules = int((result.tensors.rule_ids >= 0).sum())
print(json.dumps({
    "mine_s": round(mine_s, 3),
    "rows_per_s": round(ROWS / mine_s, 1),
    "shape": f"{P_N}x{V_N}",
    "count_path": result.count_path,
    "shards": n_devices,
    "dense_single_device_bytes": dense_bytes,
    "hbm_budget_bytes": budget,
    "per_shard_counts_bytes": 4 * V_N * V_N // n_devices,
    "rules_emitted": n_rules,
    "frequent_items": result.tensors.n_frequent_items,
    "platform": dev.platform,
}))
"""

# the sparsity-adaptive bracket (ISSUE 13): the sparse CSR×bitpacked
# hybrid vs the standing scale_cpu_native record-holder ON THE SAME
# ≥99%-sparse workload (same prune, same emission contract, tensors
# asserted bit-identical) — plus a dense/bitpack/sparse identity leg at
# a bounded sub-shape and the density sweep that re-measures and
# re-banks the dispatch lookup table the auto path consults.
_SCALE_SPARSE_BENCH = r"""
import dataclasses, json, os, socket, sys, time
import numpy as np
import jax
from kmlserver_tpu.config import MiningConfig
from kmlserver_tpu.data.synthetic import synthetic_baskets
from kmlserver_tpu.mining import dispatch as dispatch_mod
from kmlserver_tpu.mining.miner import mine
from kmlserver_tpu.mining.sweep import run_density_sweep

dev = jax.devices()[0]
print(f"device: {dev.platform} ({dev.device_kind})", file=sys.stderr, flush=True)
P_N = int(os.environ.get("KMLS_BENCH_SPARSE_PLAYLISTS", "1500000"))
V_N = int(os.environ.get("KMLS_BENCH_SPARSE_TRACKS", "40000"))
ROWS = int(os.environ.get("KMLS_BENCH_SPARSE_ROWS", "6000000"))
out = {}

def same_tensors(a, b):
    return bool(
        np.array_equal(a.rule_ids, b.rule_ids)
        and np.array_equal(a.rule_counts, b.rule_counts)
        and np.array_equal(a.item_counts, b.item_counts)
    )

# ---- identity leg: all four routes on a bounded sub-shape (small
# enough that the forced DENSE leg stays cheap on a 2-core CI runner) --
small = synthetic_baskets(
    n_playlists=8000, n_tracks=1200, target_rows=80000, seed=13
)
base_cfg = dataclasses.replace(
    MiningConfig.from_env(dotenv_path=None),
    min_support=0.001, k_max_consequents=64,
)
legs = {}
for name, kw in (
    ("sparse", dict(count_path="sparse")),
    ("dense", dict(count_path="dense", native_cpu_pair_counts=False)),
    ("bitpack", dict(count_path="bitpack")),
    ("native", dict(count_path="dense")),
):
    legs[name] = mine(small, dataclasses.replace(base_cfg, **kw)).tensors
out["identical"] = all(
    same_tensors(legs["sparse"], t) for t in legs.values()
)
print(json.dumps(out), flush=True)  # checkpoint

# ---- the headline: sparse vs the native record path, SAME workload ----
baskets = synthetic_baskets(
    n_playlists=P_N, n_tracks=V_N, target_rows=ROWS, seed=7
)
rows = len(baskets.playlist_rows)
cfg = dataclasses.replace(
    MiningConfig.from_env(dotenv_path=None),
    min_support=8.0 / P_N, k_max_consequents=64,
)
plan = dispatch_mod.plan_count_path(
    cfg, P_N, V_N, rows, backend=jax.default_backend(), baskets=baskets
)
# control probe: a dense-regime workload (5% density, toy size) must
# keep resolving to the dense family — the dispatch smoke pins both
# directions of the decision
plan_dense = dispatch_mod.plan_count_path(
    base_cfg, 4000, 1000, 200000, backend=jax.default_backend()
)
out.update({
    "shape": f"{P_N}x{V_N}",
    "rows": rows,
    "density": round(rows / (P_N * float(V_N)), 8),
    "auto_path": plan.path,
    "auto_source": plan.source,
    "auto_path_dense_regime": plan_dense.path,
    "table_cell": plan.cell,
})
r_sparse = mine(baskets, dataclasses.replace(cfg, count_path="sparse"))
out["sparse_mine_s"] = round(r_sparse.duration_s, 3)
out["sparse_rows_per_s"] = round(rows / r_sparse.duration_s, 1)
out["count_path"] = r_sparse.count_path
out["frequent_items"] = r_sparse.tensors.n_frequent_items
out["platform"] = dev.platform
print(json.dumps(out), flush=True)  # checkpoint before the slow leg
r_native = mine(baskets, dataclasses.replace(cfg, count_path="dense"))
out["native_mine_s"] = round(r_native.duration_s, 3)
out["native_rows_per_s"] = round(rows / r_native.duration_s, 1)
out["native_count_path"] = r_native.count_path
out["speedup_vs_native"] = round(
    r_native.duration_s / r_sparse.duration_s, 2
)
out["headline_identical"] = same_tensors(
    r_sparse.tensors, r_native.tensors
)
print(json.dumps(out), flush=True)  # checkpoint before the sweep

# ---- density axis: re-measure + re-bank the dispatch lookup table ----
records = run_density_sweep(
    max_rows=min(4_000_000, max(ROWS // 2, 20000))
)
table = dispatch_mod.table_from_records(
    records, jax.default_backend(),
    measured_on=f"{socket.gethostname()}/{dev.device_kind}",
    banked_at=time.time(),
    base=dispatch_mod.load_table(),
)
dispatch_mod.save_table(dispatch_mod.builtin_table_path(), table)
out["table_points"] = len(records)
out["table_cells"] = len(
    table["backends"][jax.default_backend()]["cells"]
)
out["sweep_identical"] = all(r["identical"] for r in records)
print(json.dumps(out))
"""


# every phase script prints "device: ..." to stderr right after backend
# init; on TPU, not seeing it within this grace period means the backend
# init hung (another process still holds the chip, say) — kill early
# instead of burning the phase's full timeout on a process that will
# never start computing. No shorter than the prober's timeout: a backend
# the prober certified must not have phases killed under a shorter fuse.
STARTUP_GRACE_S = 240.0


def _startup_grace_s() -> float:
    # env read at call time, not import time (envread checker)
    return float(
        os.environ.get("KMLS_BENCH_STARTUP_GRACE_S", str(STARTUP_GRACE_S))
    )


def _salvage_checkpoint(
    stdout_parts: list[str], name: str, reason: str
) -> dict | None:
    """Last parseable JSON DICT on a phase's stdout (phases checkpoint
    complete dicts; a bare scalar — e.g. a line truncated by a kill — must
    not be returned, callers assume dict). The ONE copy of this parse for
    the success, timeout, and crash paths."""
    stdout = "".join(stdout_parts)
    skipped = 0
    for line in reversed(stdout.strip().splitlines()):
        try:
            salvaged = json.loads(line)
        except ValueError:
            skipped += 1
            continue
        if isinstance(salvaged, dict):
            if reason:
                log(f"{name} phase {reason} but a checkpoint was salvaged")
            elif skipped:
                # clean exit but the LAST line wasn't the result: say so —
                # an earlier checkpoint may be missing later keys
                log(
                    f"{name} phase: result taken {skipped} line(s) above "
                    "an unparseable stdout tail"
                )
            return salvaged
        skipped += 1
    return None


def _run_phase(
    name: str,
    code: str,
    argv: list[str],
    *,
    platform: str,
    timeout: float = 1800,
    attempts: int = 2,
    extra_env: dict | None = None,
) -> dict | None:
    """Run one bench phase in its own process with transient-failure
    retries and (on TPU) a backend-init watchdog; → parsed result JSON
    (last stdout line) or None (logged)."""
    env = _phase_env(platform)
    if extra_env:
        env.update(extra_env)
    for attempt in range(1, attempts + 1):
        proc = _tracked_popen(
            [sys.executable, "-c", code, *argv],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        stderr_lines: list[str] = []
        stdout_parts: list[str] = []
        started = threading.Event()

        def _drain_err() -> None:
            for line in proc.stderr:  # type: ignore[union-attr]
                stderr_lines.append(line.rstrip())
                log(f"[{name}] {line.rstrip()}")
                if "device:" in line:
                    started.set()

        def _drain_out() -> None:
            stdout_parts.append(proc.stdout.read())  # type: ignore[union-attr]

        t_err = threading.Thread(target=_drain_err, daemon=True)
        t_out = threading.Thread(target=_drain_out, daemon=True)
        t_err.start()
        t_out.start()

        timed_out = False
        t_phase = time.monotonic()
        if platform == "tpu":
            # never arm a grace longer than the phase's own budget, and
            # count grace time AGAINST that budget below — otherwise a
            # short-deadline phase could overrun the bench deadline by
            # grace+timeout and cost the whole JSON artifact
            grace = min(_startup_grace_s(), timeout)
            t_end = t_phase + grace
            # poll alongside the wait: a phase that crashes at import never
            # prints a device line and must not idle out the full grace
            while (
                not started.is_set()
                and proc.poll() is None
                and time.monotonic() < t_end
            ):
                started.wait(timeout=2.0)
            if not started.is_set() and proc.poll() is None:
                log(
                    f"{name} phase: no device line within "
                    f"{grace:.0f}s — backend init hang; killing "
                    "early instead of burning the phase timeout"
                )
                _kill_tree(proc)
                proc.wait()
                t_err.join(timeout=10)
                t_out.join(timeout=10)
                # unlike a full-timeout hang (which already burned the whole
                # phase budget), the early kill only cost the grace period —
                # a chip held by a dying predecessor frees up, so this IS
                # worth a retry (when the deadline still has room for one)
                if attempt < attempts and _remaining() > grace + 60:
                    log(
                        f"{name} phase init hang (attempt {attempt}/"
                        f"{attempts}); retrying in 30s"
                    )
                    time.sleep(30)
                    continue
                return None
        if not timed_out:
            try:
                proc.wait(timeout=max(timeout - (time.monotonic() - t_phase), 5.0))
            except subprocess.TimeoutExpired:
                _kill_tree(proc)
                timed_out = True
                log(f"{name} phase timed out after {timeout:.0f}s (backend hang?)")
        proc.wait()
        t_err.join(timeout=10)
        t_out.join(timeout=10)
        stderr_text = "\n".join(stderr_lines)
        if timed_out:
            # no retry (a hang already burned budget once) — but salvage
            # the last checkpoint JSON the phase printed before the kill
            # (scale_demo checkpoints after every completed section)
            return _salvage_checkpoint(stdout_parts, name, "timed out")
        if proc.returncode == 0:
            result = _salvage_checkpoint(stdout_parts, name, "")
            if result is None:
                log(f"{name} phase produced no parseable result dict")
            return result
        kind = _classify(stderr_text, timed_out=False)
        if kind == "transient" and attempt < attempts:
            log(
                f"{name} phase hit a transient backend error "
                f"(attempt {attempt}/{attempts}); retrying in 30s"
            )
            time.sleep(30)
            continue
        log(
            f"{name} phase failed (exit {proc.returncode}): "
            + (
                "TPU unreachable (backend init error)"
                if kind == "transient"
                else f"compute failed on {platform}"
            )
        )
        # salvage like the timeout path: a phase that checkpointed partial
        # JSON before crashing (config4's cold line, scale_demo's section
        # lines) still contributes — the unloseable-artifact rule applies
        # to phase results too, not only the top-level line
        return _salvage_checkpoint(stdout_parts, name, "failed")
    return None


def _wait_ready(url: str, deadline_s: float) -> bool:
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        try:
            with urllib.request.urlopen(url + "/readyz", timeout=5) as resp:
                if resp.status == 200:
                    return True
        except Exception:
            pass
        time.sleep(1.0)
    return False


def _parse_latency_percentiles(metrics_text: str) -> dict:
    """Prometheus text → {"p50_ms": ..., ...} (empty if absent)."""
    out = {}
    for q, key in (("0.5", "p50_ms"), ("0.95", "p95_ms"), ("0.99", "p99_ms")):
        m = re.search(
            r'kmls_request_latency_seconds\{quantile="%s"\} ([0-9.eE+-]+)' % q,
            metrics_text,
        )
        if m:
            out[key] = float(m.group(1)) * 1e3
    return out


def _parse_attribution(metrics_text: str) -> dict:
    """Queue-vs-device attribution summaries (serving/metrics.py renders
    them in milliseconds) → {"queue_wait_p99_ms": ..., ...} (empty if
    absent — an old server simply doesn't carry the split)."""
    out = {}
    for metric, label in (
        ("kmls_queue_wait_ms", "queue_wait"),
        ("kmls_device_ms", "device"),
        ("kmls_e2e_ms", "e2e"),
    ):
        for q, suffix in (
            ("0.5", "p50_ms"), ("0.99", "p99_ms"), ("0.999", "p999_ms")
        ):
            m = re.search(
                r'%s\{quantile="%s"\} ([0-9.eE+-]+)' % (metric, q),
                metrics_text,
            )
            if m:
                out[f"{label}_{suffix}"] = float(m.group(1))
    return out


def _scrape_server_percentiles(url: str) -> dict | None:
    """Read the server's own latency percentiles from /metrics
    (serving/metrics.py renders them) → {"p50_ms": ..., ...} or None,
    plus the queue-vs-device attribution under an "attribution" subkey.
    Recording these NEXT TO the client-observed replay numbers separates
    server time from harness queueing."""
    try:
        with urllib.request.urlopen(url + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
    except Exception as exc:
        log(f"[replay] /metrics scrape failed: {type(exc).__name__}: {exc}")
        return None
    pcts = _parse_latency_percentiles(text)
    if not pcts:
        return None
    attribution = _parse_attribution(text)
    if attribution:
        pcts["attribution"] = attribution
    return pcts


def _reset_server_metrics(url: str) -> bool:
    """POST /metrics/reset (loopback-guarded, serving/app.py): start a
    fresh latency window so the next scrape covers exactly one replay run."""
    try:
        req = urllib.request.Request(
            url + "/metrics/reset", data=b"", method="POST"
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status == 200
    except Exception as exc:
        log(f"[replay] /metrics/reset failed: {type(exc).__name__}: {exc}")
        return False


def replay_phase(platform: str) -> dict | None:
    """Full-stack serving measurement: mining job → PVC artifacts → real
    HTTP server (own process, owns the chip) → open-loop 1k-QPS replay."""
    qps = float(os.environ.get("KMLS_BENCH_REPLAY_QPS", "1000"))
    n_req = int(os.environ.get("KMLS_BENCH_REPLAY_REQUESTS", "8000"))
    with tempfile.TemporaryDirectory(prefix="kmls_bench_pvc_") as base:
        ds_dir = os.path.join(base, "datasets")
        os.makedirs(ds_dir)
        csv_path = os.path.join(ds_dir, "2023_spotify_ds2.csv")
        if _run_phase(
            "replay-setup", _CSV_SETUP, [csv_path], platform="cpu", timeout=300
        ) is None:
            return None
        job_env = {"BASE_DIR": base, "DATASETS_DIR": ds_dir,
                   "MIN_SUPPORT": str(MIN_SUPPORT)}
        env = _phase_env(platform)
        env.update(job_env)
        log(f"[replay] running the real mining job on {platform}...")
        job_timeout = min(900.0, max(_remaining(), 60.0))
        t_job = time.monotonic()
        try:
            job = subprocess.run(
                [sys.executable, "-m", "kmlserver_tpu.mining.job"],
                capture_output=True, text=True, timeout=job_timeout, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
        except subprocess.TimeoutExpired:
            log(f"replay skipped: mining job hung past {job_timeout:.0f}s")
            return None
        # the container-shaped end-to-end bracket (process start → pickles
        # on the PVC, interpreter + backend init included) — BASELINE.md's
        # "ML job end-to-end ≈ 1 min" row
        job_end_to_end_s = round(time.monotonic() - t_job, 2)
        log(f"[replay] mining job end-to-end: {job_end_to_end_s:.2f}s "
            "(reference: ~60s, relatorio.pdf p.3)")
        if job.returncode != 0:
            for line in job.stdout.splitlines()[-10:]:
                log(f"[replay-job] {line}")
            for line in job.stderr.splitlines()[-10:]:
                log(f"[replay-job] {line}")
            log(f"replay skipped: mining job failed (exit {job.returncode})")
            return None

        srv_env = _phase_env(platform)
        srv_env.update({"BASE_DIR": base, "KMLS_PORT": "0",
                        "POLLING_WAIT_IN_MINUTES": "1",
                        # arm span tracing at the overhead-bracket-proven
                        # sample so the final run's echoed ids can be
                        # JOINed against /debug/traces (ISSUE 9
                        # remainder); traceoverhead pins p99 ≤ 1.05x at
                        # this setting every round, and the per-run
                        # summaries keep the raw numbers honest
                        "KMLS_TRACE_SAMPLE": "0.01"})
        if platform == "tpu":
            # large batches, deep pipeline: a dispatch pays a fixed
            # host<->device cost, throughput is capped at batch size over
            # that cost, and larger batches amortize it — the batcher's
            # backpressure then self-sizes batches to match the arrival
            # rate (a blocked dispatch grows the next batch). These values
            # were chosen for a link far slower than a local chip's and
            # have not been re-tuned on one (ROADMAP queue 3 item 1); pods
            # keep the default batch-32 low-latency config.
            srv_env.update({
                "KMLS_BATCH_MAX_SIZE": "256",
                "KMLS_BATCH_WINDOW_MS": "20",
                "KMLS_BATCH_MAX_INFLIGHT": "8",
            })
        server = _tracked_popen(
            [sys.executable, "-m", "kmlserver_tpu.serving.server"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=srv_env, cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        srv_lines: list[str] = []
        port_found = threading.Event()
        port_holder: list[int] = []

        def _drain() -> None:
            for line in server.stdout:  # type: ignore[union-attr]
                srv_lines.append(line.rstrip())
                m = re.search(r"serving on \S+?:(\d+)", line)
                if m and not port_found.is_set():
                    port_holder.append(int(m.group(1)))
                    port_found.set()

        t = threading.Thread(target=_drain, daemon=True)
        t.start()
        try:
            if not port_found.wait(timeout=120) or not port_holder:
                log("replay skipped: server never reported its port")
                for line in srv_lines[-10:]:
                    log(f"[replay-server] {line}")
                return None
            url = f"http://127.0.0.1:{port_holder[0]}"
            # jit warmup happens on first load; gate on readiness
            if not _wait_ready(url, deadline_s=min(300.0, max(_remaining(), 30.0))):
                log("replay skipped: server /readyz never went 200")
                for line in srv_lines[-10:]:
                    log(f"[replay-server] {line}")
                return None
            # median-of-N with an explicit warmup
            load1 = os.getloadavg()[0] if hasattr(os, "getloadavg") else -1.0
            n_warm = int(os.environ.get("KMLS_BENCH_REPLAY_WARMUP", "1000"))
            n_runs = int(os.environ.get("KMLS_BENCH_REPLAY_RUNS", "3"))
            log(
                f"[replay] server ready at {url}; host load1 {load1:.2f}; "
                f"warmup {n_warm} requests, then {n_runs}x{n_req} at "
                f"{qps:.0f} QPS"
            )
            pickles = os.path.join(base, "pickles", "recommendations.pickle")
            client_env = None
            if platform == "tpu":
                # Little's law: at ~0.5 s per answer 1k QPS needs ~500
                # in flight; size the client above that so the CLIENT
                # never caps what the batched server can absorb
                client_env = {"KMLS_BENCH_REPLAY_WORKERS": "768",
                              "KMLS_BENCH_REPLAY_QUEUE": "4096"}
            if n_warm > 0:
                _run_phase(
                    "replay-warmup", _REPLAY_CLIENT,
                    [url, str(qps), str(n_warm), pickles],
                    platform="cpu", timeout=300, extra_env=client_env,
                )
            runs: list[dict] = []
            # per-run server windows: reset the latency reservoir before
            # every run so the /metrics percentiles cover exactly the
            # requests that run's client percentiles cover
            window_clean = _reset_server_metrics(url)
            any_reset = window_clean
            for i in range(n_runs):
                if runs and _remaining() < 120:
                    log(
                        f"[replay] deadline headroom gone after run {i}; "
                        f"reporting the median of {len(runs)}"
                    )
                    break
                r = _run_phase(
                    "replay-client", _REPLAY_CLIENT,
                    [url, str(qps), str(n_req), pickles,
                     os.path.join(base, "trace_client.jsonl")],
                    platform="cpu", timeout=600, extra_env=client_env,
                )
                if r is not None:
                    log(
                        f"[replay] run {i}: p50 {r['p50_ms']:.2f}ms, "
                        f"{r['achieved_qps']:.0f} QPS, {r['n_errors']} errors"
                    )
                    if window_clean:
                        pcts = _scrape_server_percentiles(url)
                        if pcts:
                            r["server_percentiles"] = pcts
                    runs.append(r)
                window_clean = _reset_server_metrics(url)
                any_reset = any_reset or window_clean
            if not runs:
                return None
            run_summaries = []  # chronological, travels with the artifact
            for r in runs:
                s = {"p50_ms": round(r["p50_ms"], 3),
                     "achieved_qps": round(r["achieved_qps"], 1),
                     "n_errors": r["n_errors"]}
                if "server_percentiles" in r:
                    s["server_p50_ms"] = round(
                        r["server_percentiles"]["p50_ms"], 3
                    )
                run_summaries.append(s)
            report = sorted(runs, key=lambda r: r["p50_ms"])[len(runs) // 2]
            report["runs"] = run_summaries
            # trace JOIN (ISSUE 9 remainder): the last run's client
            # records vs the server's retained spans, merged by
            # scripts/kmls_tracejoin.py — proves the end-to-end id
            # propagation + join tooling against a REAL HTTP stack
            client_jsonl = os.path.join(base, "trace_client.jsonl")
            if os.path.exists(client_jsonl):
                try:
                    traces_path = os.path.join(base, "debug_traces.json")
                    with urllib.request.urlopen(
                        url + "/debug/traces", timeout=10
                    ) as resp:
                        with open(traces_path, "wb") as fh:
                            fh.write(resp.read())
                    join = subprocess.run(
                        [sys.executable,
                         os.path.join("scripts", "kmls_tracejoin.py"),
                         "--client", client_jsonl, "--traces", traces_path],
                        capture_output=True, text=True, timeout=60,
                        cwd=os.path.dirname(os.path.abspath(__file__)),
                    )
                    joined = len(
                        [ln for ln in join.stdout.splitlines() if ln.strip()]
                    )
                    report["trace_joined"] = joined
                    report["trace_sample"] = 0.01
                    log(
                        f"[replay] tracejoin: {joined} per-request "
                        "timelines merged (client send/recv x server "
                        "spans)"
                    )
                except Exception as exc:
                    log(f"[replay] tracejoin skipped: {exc!r}")
            report["host_load1"] = round(load1, 2)
            report["warmup_requests"] = n_warm
            report["job_end_to_end_s"] = job_end_to_end_s
            if "server_percentiles" in report:
                report["server_percentiles_basis"] = (
                    "per-run window: reservoir reset before each run; "
                    "covers the same requests as the reported client run"
                )
            elif not any_reset:
                # reset endpoint unavailable (old server) — fall back to
                # the cumulative scrape, honestly labeled. Guarded on NO
                # reset ever succeeding: after a successful reset the
                # reservoir no longer holds the cumulative window, and a
                # scrape would fabricate near-zero percentiles under a
                # false label; honest absence beats that.
                server_pcts = _scrape_server_percentiles(url)
                if server_pcts:
                    report["server_percentiles"] = server_pcts
                    report["server_percentiles_note"] = (
                        "cumulative over warmup + all replay runs"
                    )
            return report
        finally:
            server.terminate()
            try:
                server.wait(timeout=10)
            except subprocess.TimeoutExpired:
                _kill_tree(server)


def _mfu_keys(mining: dict, prefix: str = "mining") -> dict:
    """Utilization accounting from the isolated matmul timing: closed-form op count vs measured time vs chip peak.
    MFU uses the amortized (pipelined) time when available — per-blocked-call
    time is floored by the host<->device round trip, which measures the
    link, not the chip."""
    out: dict = {}
    if "matmul_s" not in mining:
        return out
    p, v = mining["n_playlists"], mining["n_tracks"]
    ops = 2.0 * p * v * v  # V² output cells × P MACs × 2 ops/MAC
    mfu_time = mining.get("matmul_amortized_s", mining["matmul_s"])
    achieved = ops / mfu_time
    out[f"{prefix}_matmul_ms"] = round(mining["matmul_s"] * 1e3, 4)
    if "matmul_amortized_s" in mining:
        out[f"{prefix}_matmul_amortized_ms"] = round(
            mining["matmul_amortized_s"] * 1e3, 4
        )
    out[f"{prefix}_matmul_gops"] = round(ops / 1e9, 2)
    out[f"{prefix}_matmul_gops_per_s"] = round(achieved / 1e9, 1)
    for key in ("chain_n1", "chain_n2", "chain_t_short_s", "chain_t_long_s"):
        if key in mining:
            out[f"{prefix}_{key}"] = (
                round(mining[key], 6) if isinstance(mining[key], float)
                else mining[key]
            )
    kind = mining.get("device_kind", "").lower().replace(" ", "")
    for marker, peak in _INT8_PEAK_OPS.items():
        if marker in kind and mining.get("platform") == "tpu":
            mfu = 100.0 * achieved / peak
            if mfu <= 100.0:
                out[f"{prefix}_mfu_pct"] = round(mfu, 2)
            else:
                # physically impossible — the timing understates device
                # time (overlapped dispatches timed as a batch do this).
                # Flag at emission, never as a headline MFU.
                out[f"{prefix}_mfu_pct_suspect"] = round(mfu, 2)
                out[f"{prefix}_mfu_suspect_reason"] = (
                    ">100% MFU is physically impossible: the matmul timing "
                    "understates device time (overlapped dispatch/ack "
                    "artifacts); see the *_chain_* keys for the raw "
                    "slope inputs"
                )
            out[f"{prefix}_mfu_peak_tops"] = round(peak / 1e12, 1)
            break
    return out


def _headline_keys(
    platform: str, mining: dict, cpu_mining: dict | None = None
) -> dict:
    """The artifact's headline block: metric/value/vs_baseline + MFU
    accounting + (when the TPU took the headline over a CPU run) the CPU
    comparison keys. Pure — the ONE assembly used by every checkpoint and
    the final line, so partial and final artifacts can never disagree."""
    median_s = mining["median_s"]
    line = {
        "metric": "fpgrowth_ds2_rule_generation_time",
        "value": round(median_s, 4),
        "unit": "s",
        "vs_baseline": round(BASELINE_RULE_GEN_S / median_s, 1),
        "platform": platform,
    }
    line.update(_mfu_keys(mining))
    if mining.get("count_path"):
        line["mining_count_path"] = mining["count_path"]
    if cpu_mining is not None and cpu_mining is not mining:
        # the TPU suite took the headline; keep the CPU evidence too,
        # under unambiguous keys. At the ds2 shape the TPU bracket is
        # mostly host<->device round trips, so the native CPU path can be
        # FASTER — surface the best measured number explicitly rather
        # than burying it.
        line["mining_cpu_s"] = round(cpu_mining["median_s"], 4)
        line.update(_mfu_keys(cpu_mining, prefix="mining_cpu"))
        best_s = min(median_s, cpu_mining["median_s"])
        line["best_mining_s"] = round(best_s, 4)
        line["best_mining_platform"] = "tpu" if best_s == median_s else "cpu"
        line["vs_baseline_best"] = round(BASELINE_RULE_GEN_S / best_s, 1)
    return line


def run_mining(
    platform: str,
    npz_path: str,
    attempts: int | None = None,
    timeout: float | None = None,
) -> dict | None:
    """The headline phase keeps a 300 s floor even near the deadline (a
    bench with no mining number is worthless); OPTIONAL callers must pass
    a deadline-respecting timeout instead."""
    mining = _run_phase(
        "mining", _MINING_BENCH, [npz_path, str(MIN_SUPPORT), str(REPEATS)],
        platform=platform,
        attempts=attempts if attempts is not None
        else (3 if platform == "tpu" else 2),
        timeout=timeout if timeout is not None
        else min(1800, max(_remaining(), 300)),
    )
    return mining


def run_tpu_suite(em: ArtifactEmitter, npz_path: str) -> dict | None:
    """The on-chip phases. → the TPU mining result (or None if mining
    failed); optional phases fill the emitter's extras as deadline headroom
    allows, checkpointing the artifact line after each.

    Serialized per bank: if another bench holds the lock past the wait
    budget, this one cannot have the chip and reports no result."""
    lock = _acquire_tpu_lock(min(max(_remaining() - 420, 0.0), 600.0))
    if lock is None:
        log("another bench holds the TPU-suite lock — the chip is not ours")
        return None
    try:
        return _run_tpu_suite_inner(em, npz_path)
    finally:
        _release_tpu_lock(lock)


def _run_tpu_suite_inner(em: ArtifactEmitter, npz_path: str) -> dict | None:
    result = em.extras
    banked_mining = STATE.get("mining_tpu")
    mining = None
    if (
        banked_mining is not None
        and STATE.npz_path
        and os.path.exists(STATE.npz_path)
    ):
        # both the result AND the serving input survive across
        # invocations; a bank without its npz sidecar re-mines (serving
        # needs the npz)
        try:
            shutil.copyfile(STATE.npz_path, npz_path)
            log("mining_tpu: banked by an earlier invocation — skipping live run")
            mining = dict(banked_mining)
            result["mining_tpu_from_bank"] = True
            age = STATE.age_s("mining_tpu")
            if age is not None:
                result["mining_tpu_bank_age_s"] = round(age)
        except OSError as exc:
            log(f"state bank npz restore failed ({exc}); re-mining live")
    if mining is None:
        mining = run_mining("tpu", npz_path)
        if mining is not None:
            STATE.bank("mining_tpu", mining)
            if STATE.npz_path:
                try:
                    shutil.copyfile(npz_path, STATE.npz_path)
                except OSError as exc:
                    log(f"state bank npz copy failed ({exc})")
    if mining is None:
        return None
    em.set_headline("tpu", mining)

    # serving + replay directly after the headline: config 5 is a judged
    # BASELINE target and a chip call is bounded — the supporting
    # phases (popcount/scale/config4/sweep) run after. A banked phase
    # replays even past the deadline gate (replaying is free; budgets gate
    # only live runs, inside _banked).
    _record_serving(result, npz_path, "tpu", bank="serving_tpu", budget_s=120)
    em.checkpoint()

    _record_replay(result, "tpu", bank="replay_tpu", budget_s=300)
    em.checkpoint()

    popcount = _banked("popcount_tpu", lambda: _run_phase(
        "popcount", _POPCOUNT_BENCH,
        ["compiled", "2246", "2171", "240249"],
        platform="tpu", timeout=min(900, _remaining()),
    ), budget_s=240, extras=result)
    if popcount is not None:
        log(
            f"popcount kernel [{popcount['kernel']}] (compiled TPU, "
            f"ds2 shape): {popcount['popcount_ms']:.2f}ms/call vs dense "
            f"MXU {popcount['dense_ms']:.2f}ms, exact match, "
            f"{popcount['words_per_s'] / 1e9:.2f} Gwords/s amortized"
        )
        result["popcount_ds2_ms"] = round(popcount["popcount_ms"], 3)
        result["dense_pair_ds2_ms"] = round(popcount["dense_ms"], 3)
        result["popcount_kernel"] = popcount["kernel"]
        result["popcount_words_per_s"] = round(popcount["words_per_s"])
        for key in ("popcount_amortized_ms", "dense_amortized_ms"):
            if key in popcount:
                result[key.replace("_ms", "_ds2_ms")] = round(
                    popcount[key], 3
                )
        # the MXU unpack-matmul impl (production default for the
        # bit-packed path), measured next to the VPU kernel
        for src, dst in (("mxu_ms", "bitpack_mxu_ds2_ms"),
                         ("mxu_amortized_ms", "bitpack_mxu_amortized_ds2_ms"),
                         ("mxu_words_per_s", "bitpack_mxu_words_per_s")):
            if src in popcount:
                result[dst] = round(popcount[src], 3)
    em.checkpoint()

    # TRUE config-4 shape (10M playlists × 1M tracks) on the single
    # chip, workload generated in HBM (Bernoulli-Zipf bitset — zero
    # host generation or transfer); compare CONFIG4_CPU_r03.json's
    # 77.8 s one-core bracket
    config4 = _banked("config4_tpu", lambda: _run_phase(
        "config4-devicegen", _CONFIG4_BENCH, ["--device-gen"],
        platform="tpu", timeout=min(900, _remaining()),
    ), budget_s=300, extras=result)
    if config4 is not None:
        for src, dst in (
            ("mine_s", "config4_mine_s"),
            ("mine_cold_s", "config4_mine_cold_s"),
            ("gen_device_s", "config4_gen_device_s"),
            ("rows", "config4_rows"),
            ("rows_basis", "config4_rows_basis"),
            ("rows_per_s", "config4_rows_per_s"),
            ("frequent_items", "config4_frequent_items"),
            ("n_rules", "config4_n_rules"),
            ("bitset_gib", "config4_bitset_gib"),
            ("workload_model", "config4_workload_model"),
            ("rows_measured", "config4_rows_measured"),
        ):
            if src in config4:
                result[dst] = config4[src]
    em.checkpoint()

    # config-4 scale mechanics on real HBM: 1M playlists x 100k vocab
    # through Apriori prune + the bit-packed popcount path
    scale = _banked("scale_tpu", lambda: _run_phase(
        "scale", _SCALE_BENCH,
        ["--playlists", "1000000", "--tracks", "100000",
         "--rows", "50000000", "--min-support", "0.001"],
        platform="tpu", timeout=min(900, _remaining()),
    ), budget_s=300, extras=result)
    if scale is not None:
        result["scale_1m_x_100k_mine_s"] = scale["mine_s"]
        result["scale_rows_per_s"] = scale["rows_per_s"]
        result["scale_frequent_items"] = scale["frequent_items"]
        # auto dispatch (warm) + device-resident timings: the HBM-fit
        # dense path and the transfer-free on-chip bracket, labeled
        for src, dst in (
            ("auto_mine_s", "scale_auto_mine_s"),
            ("auto_path", "scale_auto_path"),
            ("auto_rows_per_s", "scale_auto_rows_per_s"),
            ("device_resident_mine_s", "scale_device_resident_mine_s"),
            ("device_resident_path", "scale_device_resident_path"),
        ):
            if src in scale:
                result[dst] = scale[src]
    em.checkpoint()

    # the reference's full 68-point support sweep, count-once, on-chip
    sweep = _banked("sweep_tpu", lambda: _run_phase(
        "sweep", _SWEEP_BENCH, [], platform="tpu",
        timeout=min(600, _remaining()),
    ), budget_s=180, extras=result)
    if sweep is not None:
        result["sweep_points"] = sweep["points"]
        result["sweep_total_s"] = sweep["total_s"]
        result["sweep_emission_total_s"] = sweep["emission_total_s"]
        result["sweep_setup_plus_count_s"] = sweep["setup_plus_count_s"]
    em.checkpoint()

    # on-hardware Pallas tile tune: pins the kernel's
    # tile defaults from measurement instead of guesswork, and settles
    # VPU-vs-MXU with same-bitset numbers (the popcount phase above
    # carries the MXU twin). Named "pallas-tune" — NOT "popcount-..." —
    # so result salvage/log greps can't confuse it with the kernel phase.
    def _tune_runner() -> dict | None:
        r = _run_phase(
            "pallas-tune", _TUNE_BENCH, [],
            platform="tpu", timeout=min(900, _remaining()),
        )
        # a no-config-succeeded error is a failure, not a result — banking
        # it would replay the failure into every later invocation
        return None if r is None or "error" in r else r

    tune = _banked(
        "popcount_tune_tpu", _tune_runner, budget_s=240, extras=result
    )
    if tune is not None:
        for src, dst in (
            ("best_config", "popcount_tune_best_config"),
            ("best_variant", "popcount_tune_best_variant"),
            ("best_ms", "popcount_tune_best_ms"),
            ("best_words_per_s", "popcount_tune_best_words_per_s"),
            ("results", "popcount_tune_results"),
            ("partial", "popcount_tune_partial"),
        ):
            if src in tune:
                result[dst] = tune[src]
    em.checkpoint()

    # supplementary CPU replay: the host stack (native mining + host
    # kernels) under the same traffic, as the framework-overhead
    # reference — recorded under cpu_-prefixed keys, never under the
    # chip's.
    cpu_replay: dict = {}
    _record_replay(cpu_replay, "cpu", bank="replay_cpu_supp", budget_s=300)
    for key, val in cpu_replay.items():
        result[f"cpu_{key}"] = val
    em.checkpoint()

    # the 10k-QPS Zipf throughput bracket is CPU-measured by construction
    # (self-labeled keys)
    _record_replay10k(result, bank="replay10k_cpu", budget_s=240)
    em.checkpoint()

    # the kill-a-replica chaos bracket is CPU-measured by construction
    # too (self-labeled keys)
    _record_chaos(result, bank="chaos_cpu", budget_s=200)
    em.checkpoint()

    # the traffic-shape bracket (ISSUE 8): CPU-measured by construction
    _record_loadshape(result, bank="loadshape_cpu", budget_s=200)
    em.checkpoint()

    # predictive-serving A/B bracket (ISSUE 17): CPU-measured by
    # construction — forecaster on vs off at equal capacity over
    # ramp/sine/constant
    _record_loadshape_pred(
        result, bank="loadshape_pred_cpu", budget_s=240
    )
    em.checkpoint()

    # mining-interruption bracket: CPU-measured by construction as well
    _record_mine_resume(result, bank="mine_resume_cpu", budget_s=150)
    em.checkpoint()

    # second-model-family + confidence-mode brackets: CPU-measured by
    # construction (self-labeled keys)
    _record_als_hybrid(result, bank="als_hybrid_cpu", budget_s=240)
    em.checkpoint()
    _record_confserve(result, bank="confserve_cpu", budget_s=200)
    em.checkpoint()

    # tracing-overhead micro-bracket (ISSUE 9): CPU-measured by
    # construction (self-labeled keys) — the ≤1.05 p99 claim must ride
    # the TPU artifact too, same as every sibling bracket above
    _record_traceoverhead(result, bank="traceoverhead_cpu", budget_s=150)
    em.checkpoint()

    # continuous-freshness bracket (ISSUE 10): CPU-measured by
    # construction — the ≥5x delta speedup / zero-5xx / fleet-multiplier
    # acceptance evidence must ride the TPU artifact too
    _record_freshness(result, bank="freshness_cpu", budget_s=200)
    em.checkpoint()

    # fleet cache-routing bracket (ISSUE 15): CPU-measured by
    # construction (real local server processes) — the routed-vs-
    # independent multiplier + kill/delta zero-5xx evidence must ride
    # the TPU artifact too
    _record_fleet(result, bank="fleet_cpu", budget_s=240)
    em.checkpoint()

    # pod-spanning serve-mesh bracket (ISSUE 16): CPU-measured by
    # construction (socket transport stands in for GSPMD-over-DCN) —
    # the gang-vs-sharded identity + shard-loss zero-5xx evidence must
    # ride the TPU artifact too
    _record_meshserve(result, bank="meshserve_cpu", budget_s=240)
    em.checkpoint()

    # gray-failure chaos bracket (ISSUE 18): CPU-measured by
    # construction (real local server processes under an injected
    # stall) — the hedged-vs-control tail + zero-5xx/zero-drop evidence
    # must ride the TPU artifact too
    _record_slowpeer(result, bank="slowpeer_cpu", budget_s=240)
    em.checkpoint()

    # storage gray-failure bracket (ISSUE 19): CPU-measured by
    # construction (tmpfs artifact dir + injected IO faults) — the
    # zero-5xx / p99-unmoved / torn-free ENOSPC evidence must ride the
    # TPU artifact too
    _record_graystore(result, bank="graystore_cpu", budget_s=200)
    em.checkpoint()

    # quality-loop bracket (ISSUE 14): CPU-measured by construction —
    # the held-out recall / measured-weight / compaction-identity
    # evidence must ride the TPU artifact too
    _record_quality(result, bank="quality_cpu", budget_s=240)
    em.checkpoint()

    # sparsity-adaptive bracket (ISSUE 13): CPU-measured by construction
    # (the native comparison IS a CPU kernel) — the ≥5x-at-≥99%-sparsity
    # and bit-identity evidence must ride the TPU artifact too
    _record_scale_sparse(result, bank="scale_sparse_cpu", budget_s=240)
    em.checkpoint()

    # cost-attribution bracket (ISSUE 12): unlike the CPU-by-construction
    # siblings above, this phase runs ON the chip (platform="tpu" → the
    # phase subprocess sees the TPU), so it measures serve-kernel MFU
    # against the real chip's peak. Banked under its own TPU key; the
    # CPU suite's run of it is labeled as such.
    _record_costattrib(
        result, bank="costattrib_tpu", budget_s=150, platform="tpu"
    )
    em.checkpoint()
    return mining


def run_cpu_suite(em: ArtifactEmitter, npz_path: str) -> dict | None:
    """Everything that doesn't need the chip, including CPU-labeled
    stand-ins for the config-4 popcount/scale evidence."""
    result = em.extras
    mining = run_mining("cpu", npz_path)
    if mining is None:
        return None
    em.set_headline("cpu", mining)

    # serving + replay FIRST: config 5 is a judged BASELINE target; the
    # scale/popcount stand-ins are supporting evidence and run after
    if _remaining() > 120:
        _record_serving(result, npz_path, "cpu")
        em.checkpoint()

    if _remaining() > 240:
        _record_replay(result, "cpu")
        em.checkpoint()

    if _remaining() > 180:
        # the 10k-QPS Zipf throughput bracket: cache + batcher + native
        # kernel in-process (PR 2's tentpole acceptance)
        _record_replay10k(result)
        em.checkpoint()

    if _remaining() > 150:
        # kill-a-replica fault-tolerance bracket (PR 3's acceptance):
        # zero 5xx while a replica dies under 1k QPS
        _record_chaos(result)
        em.checkpoint()

    if _remaining() > 150:
        # traffic-shape bracket (ISSUE 8): 10x burst trains / flash
        # crowd / epoch-boundary hot-key flip through the admission
        # ladder — p99 < 10 ms and zero 5xx through the bursts
        _record_loadshape(result)
        em.checkpoint()

    if _remaining() > 240:
        # predictive-serving A/B bracket (ISSUE 17): forecaster on vs
        # off at equal capacity — predictive no worse on p99 AND
        # shed/degrade for ramp + sine, constant the unchanged control
        _record_loadshape_pred(result)
        em.checkpoint()

    if _remaining() > 120:
        # tracing-overhead micro-bracket (ISSUE 9): sampled tracing p99
        # within 5% of disabled; disabled recorder allocates nothing
        _record_traceoverhead(result)
        em.checkpoint()

    if _remaining() > 200:
        # continuous-freshness bracket (ISSUE 10): delta publish→applied
        # vs full re-mine + republish, zero 5xx through the in-place
        # apply, hot cache surviving selectively, fleet multiplier
        _record_freshness(result)
        em.checkpoint()

    if _remaining() > 240:
        # fleet cache-routing bracket (ISSUE 15): 3 real server
        # processes, routed vs independent hit ratio, zero 5xx through
        # a mid-replay replica kill + delta apply
        _record_fleet(result)
        em.checkpoint()

    if _remaining() > 120:
        # cost-attribution bracket (ISSUE 12): serve-kernel MFU +
        # roofline class + live compiles==0 + disabled-mode zero-cost
        _record_costattrib(result)
        em.checkpoint()

    if _remaining() > 240:
        # quality-loop bracket (ISSUE 14): held-out recall@k per mode,
        # measured blend optimum round-trip, compacted-snapshot
        # identity + zero 5xx through the mid-replay swap
        _record_quality(result)
        em.checkpoint()

    if _remaining() > 120:
        # mining-interruption bracket (ISSUE 4): kill-at-phase, resume,
        # bit-identical artifacts + wall-clock savings
        _record_mine_resume(result)
        em.checkpoint()

    if _remaining() > 200:
        # second model family (ISSUE 6): ALS train time, hybrid blend
        # replay p50/p99, cold-start hit fraction
        _record_als_hybrid(result)
        em.checkpoint()

    if _remaining() > 150:
        # confidence-mode serving bracket: multi-antecedent rules through
        # the jitted max-merge kernel (carried-over ROADMAP item)
        _record_confserve(result)
        em.checkpoint()

    if _remaining() > 200:
        # model-parallel serving (ISSUE 7): auto layout shards a catalog
        # that exceeds one (virtual) device's budget, answers stay
        # bit-identical to replicated, zero compiles post-publish
        _record_shardserve(result)
        em.checkpoint()

    if _remaining() > 240:
        # pod-spanning serve mesh (ISSUE 16): a 2-member gang over the
        # socket transport vs single-process sharded on the same
        # over-budget catalog, + the mid-replay gang-member SIGKILL
        _record_meshserve(result)
        em.checkpoint()

    if _remaining() > 240:
        # gray-failure spine (ISSUE 18): a 200 ms alive-but-late stall
        # on one fleet peer and one gang member, hedged leg vs no-hedge
        # control at equal capacity
        _record_slowpeer(result)
        em.checkpoint()

    if _remaining() > 200:
        # storage gray-failure spine (ISSUE 19): a 400 ms PVC read stall
        # under replay (degraded-not-unready, reload parked in backoff)
        # + ENOSPC landing exactly on the recommendations write
        _record_graystore(result)
        em.checkpoint()

    if _remaining() > 240:
        # vocab-sharded mining (ISSUE 7): the sharded count→emit path on
        # an input whose dense formulation busts the per-device budget
        _record_scale_shard(result)
        em.checkpoint()

    if _remaining() > 180:
        # interpret-mode Pallas popcount at a small shape: proves the
        # kernel path exists + counts match, labeled honestly as interpret
        popcount = _run_phase(
            "popcount-interpret", _POPCOUNT_BENCH,
            ["interpret", "2048", "512", "40000"],
            platform="cpu", timeout=min(600, _remaining()),
        )
        if popcount is not None:
            result["popcount_cpu_interpret_ms"] = round(popcount["popcount_ms"], 1)
            result["popcount_cpu_interpret_shape"] = popcount["shape"]
            result["popcount_cpu_interpret_exact"] = popcount["exact"]
            result["popcount_cpu_interpret_kernel"] = popcount["kernel"]
            if "mxu_ms" in popcount:
                # the MXU unpack-matmul impl is pure XLA: on CPU it runs
                # COMPILED (not interpreted) — real kernel evidence even
                # in a chipless round
                result["bitpack_mxu_cpu_compiled_ms"] = round(
                    popcount["mxu_ms"], 1
                )
        em.checkpoint()

    if _remaining() > 240:
        # config-4 mechanics on an 8-virtual-device dp mesh (sharded
        # bitpack path + psum), bounded shape
        scale = _run_phase(
            "scale-cpu", _SCALE_BENCH,
            ["--playlists", "20000", "--tracks", "5000",
             "--rows", "400000", "--min-support", "0.01", "--mesh", "8x1"],
            platform="cpu", timeout=min(600, _remaining()),
            extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        )
        if scale is not None:
            result["scale_cpu_mesh8_mine_s"] = scale["mine_s"]
            result["scale_cpu_mesh8_rows_per_s"] = scale["rows_per_s"]
            result["scale_cpu_mesh8_frequent_items"] = scale["frequent_items"]
            result["scale_cpu_mesh8_shape"] = "20000x5000"
            if "auto_mine_s" in scale:
                result["scale_cpu_mesh8_auto_mine_s"] = scale["auto_mine_s"]
                result["scale_cpu_mesh8_auto_path"] = scale["auto_path"]
        em.checkpoint()

    if _remaining() > 180:
        # half-million-playlist mine through the NATIVE fallback (Apriori
        # prune → C++ bitpack scatter → tiled POPCNT counts): real
        # large-scale evidence that doesn't need the chip at all
        # --require-native: without the native library this shape would
        # fall through to a ~25 GB dense one-hot on XLA:CPU — fail fast
        # and keep the budget for the serving/replay phases instead
        scale_n = _run_phase(
            "scale-cpu-native", _SCALE_BENCH,
            ["--playlists", "500000", "--tracks", "50000",
             "--rows", "25000000", "--min-support", "0.002",
             "--require-native"],
            platform="cpu", timeout=min(600, _remaining()),
        )
        if scale_n is not None:
            result["scale_cpu_native_mine_s"] = scale_n["mine_s"]
            result["scale_cpu_native_rows_per_s"] = scale_n["rows_per_s"]
            result["scale_cpu_native_frequent_items"] = scale_n["frequent_items"]
            result["scale_cpu_native_shape"] = "500000x50000"
            if "auto_mine_s" in scale_n:
                result["scale_cpu_native_auto_mine_s"] = scale_n["auto_mine_s"]
                result["scale_cpu_native_auto_path"] = scale_n["auto_path"]
        em.checkpoint()

    if _remaining() > 240:
        # sparsity-adaptive bracket (ISSUE 13): sparse-vs-native on one
        # ≥99%-sparse workload + identity leg + dispatch-table re-bank
        _record_scale_sparse(result)
        em.checkpoint()
    return mining


def _record_serving(
    result: dict, npz_path: str, platform: str,
    bank: str | None = None, budget_s: float | None = None,
) -> None:
    def _run() -> dict | None:
        return _run_phase(
            "serving", _SERVING_BENCH, [npz_path], platform=platform,
            timeout=min(900, _remaining()),
        )

    serving = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if serving is None:
        return
    p50 = serving["p50_ms"]
    log(
        f"serving ({platform}): batch-32 recommend p50 {p50:.3f}ms/call, "
        f"{serving['amortized_ms']:.3f}ms amortized"
    )
    result["serving_batch32_p50_ms"] = round(p50, 3)
    result["serving_batch32_amortized_ms"] = round(serving["amortized_ms"], 3)
    if "p50_256_ms" in serving:
        result["serving_batch256_p50_ms"] = round(serving["p50_256_ms"], 3)
        result["serving_batch256_amortized_ms"] = round(
            serving["amortized_256_ms"], 3
        )


def _record_replay(
    result: dict, platform: str,
    bank: str | None = None, budget_s: float | None = None,
) -> None:
    def _run() -> dict | None:
        try:
            return replay_phase(platform)
        except Exception as exc:
            # the replay stack is optional evidence; the headline mining
            # number in hand must reach stdout no matter what breaks here
            log(f"replay phase crashed ({type(exc).__name__}: {exc}); skipping")
            return None

    replay = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if replay is None:
        return
    log(
        f"replay @ {replay['target_qps']:.0f} QPS: "
        f"p50 {replay['p50_ms']:.2f}ms p95 {replay['p95_ms']:.2f}ms "
        f"p99 {replay['p99_ms']:.2f}ms, achieved "
        f"{replay['achieved_qps']:.0f} QPS "
        f"({replay['n_errors']} errors/drops)"
    )
    result.update(
        replay_target_qps=replay["target_qps"],
        replay_achieved_qps=round(replay["achieved_qps"], 1),
        replay_p50_ms=round(replay["p50_ms"], 3),
        replay_p95_ms=round(replay["p95_ms"], 3),
        replay_p99_ms=round(replay["p99_ms"], 3),
        replay_errors=replay["n_errors"],
    )
    # median-of-N provenance: every run's summary + host conditions, so a
    # single replay number is auditable instead of luck-dependent
    for src, dst in (("runs", "replay_runs"),
                     ("host_load1", "replay_host_load1"),
                     ("warmup_requests", "replay_warmup_requests"),
                     # replay_ prefix: rides the takeover relabeling, so a
                     # CPU-measured job bracket can never masquerade as TPU
                     ("job_end_to_end_s", "replay_job_end_to_end_s"),
                     ("server_percentiles_basis", "replay_server_basis"),
                     ("server_percentiles_note", "replay_server_note"),
                     # trace JOIN evidence (ISSUE 9 remainder): client
                     # records carrying echoed X-KMLS-Trace ids, and the
                     # per-request timelines kmls_tracejoin.py merged
                     ("trace_records", "replay_trace_records"),
                     ("trace_joined", "replay_trace_joined"),
                     ("trace_sample", "replay_trace_sample")):
        if src in replay:
            result[dst] = replay[src]
    server_pcts = replay.get("server_percentiles")
    if server_pcts:
        gap = replay["p50_ms"] - server_pcts.get("p50_ms", 0.0)
        log(
            f"replay server-side (from /metrics): "
            f"p50 {server_pcts.get('p50_ms', float('nan')):.2f}ms "
            f"(client-server p50 gap {gap:.2f}ms = harness queueing + HTTP)"
        )
        attribution = server_pcts.get("attribution") or {}
        for key, val in server_pcts.items():
            if key != "attribution":
                result[f"replay_server_{key}"] = round(val, 3)
        # the queue-vs-device split: WHERE the server-side tail lives
        # (replay_queue_wait_p99_ms vs replay_device_p99_ms), so the next
        # round optimizes the right stage instead of guessing
        for key, val in attribution.items():
            result[f"replay_{key}"] = round(val, 3)
        if "queue_wait_p99_ms" in attribution and "device_p99_ms" in attribution:
            log(
                f"replay attribution: queue-wait p99 "
                f"{attribution['queue_wait_p99_ms']:.2f}ms vs device p99 "
                f"{attribution['device_p99_ms']:.2f}ms"
            )


def _record_chaos(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The kill-a-replica chaos bracket: 1k-QPS in-process replay with
    one of two replicas killed mid-run. CPU-platform by construction
    (same rationale and self-labeling as replay10k); the judged claims
    are chaos_errors == 0 and chaos_http_5xx == 0 with a bounded
    chaos_eject_recovery_ms."""

    def _run() -> dict | None:
        return _run_phase(
            "chaos", _CHAOS_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
            # two virtual CPU devices: the kill-a-replica story needs a
            # second replica to survive on (a bare CPU host has 1 device)
            extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
        )

    chaos = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if chaos is None:
        return
    rec_ms = chaos.get("eject_recovery_ms")
    log(
        f"chaos @ {chaos['qps']:.0f} QPS, replica killed mid-run: "
        f"{chaos['errors']} errors, {chaos['http_5xx']} HTTP 5xx, "
        f"{chaos['degraded_answers']} degraded answers, "
        f"{chaos['redispatched']} re-dispatched, ejection in "
        f"{rec_ms:.0f}ms" if rec_ms is not None else
        f"chaos @ {chaos['qps']:.0f} QPS: replica never ejected (!)"
    )
    for src, dst in (
        ("qps", "chaos_qps"),
        ("achieved_qps", "chaos_achieved_qps"),
        ("p50_ms", "chaos_p50_ms"),
        ("p99_ms", "chaos_p99_ms"),
        ("errors", "chaos_errors"),
        ("http_5xx", "chaos_http_5xx"),
        ("degraded_answers", "chaos_degraded_answers"),
        ("ok_answers", "chaos_ok_answers"),
        ("redispatched", "chaos_redispatched"),
        ("ejections", "chaos_ejections"),
        ("eject_recovery_ms", "chaos_eject_recovery_ms"),
        ("zipf_s", "chaos_zipf_s"),
        ("cache_hit_ratio", "chaos_cache_hit_ratio"),
        ("platform", "chaos_platform"),
    ):
        if src in chaos and chaos[src] is not None:
            val = chaos[src]
            result[dst] = round(val, 3) if isinstance(val, float) else val


def _record_loadshape(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The traffic-shape bracket (ISSUE 8): burst trains, flash crowd,
    and a hot-key flip at a real epoch boundary through the full
    admission-ladder path. The judged claims are loadshape_p99_ms < 10
    with loadshape_errors == loadshape_http_5xx == 0 through the 10x
    bursts, and zero 5xx on the flash/epochflip brackets (degradation
    and jittered 429s allowed there — that IS the ladder working).
    CPU-platform by construction, self-labeled."""

    def _run() -> dict | None:
        return _run_phase(
            "loadshape", _LOADSHAPE_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    b, fl, fp = res["burst"], res["flash"], res["epochflip"]
    log(
        f"loadshape @ {res['qps']:.0f} QPS base, {res['burst_factor']:.0f}x "
        f"bursts: p99 {b['p99_ms']:.2f}ms, {b['errors']} errors, "
        f"{b['http_5xx']} 5xx, {b['shed']} shed, {b['degraded']} degraded; "
        f"flash p99 {fl['p99_ms']:.2f}ms ({fl['http_5xx']} 5xx); epoch-flip "
        f"{fp['http_5xx']} 5xx, epoch_moved={fp.get('epoch_moved')}"
    )
    flat = {
        "loadshape_qps": res["qps"],
        "loadshape_burst_factor": res["burst_factor"],
        "loadshape_offered_qps": b["offered_qps"],
        "loadshape_achieved_qps": b["achieved_qps"],
        "loadshape_p50_ms": b["p50_ms"],
        "loadshape_p99_ms": b["p99_ms"],
        "loadshape_onset_p99_ms": b.get("onset_p99_ms"),
        "loadshape_steady_p99_ms": b.get("steady_p99_ms"),
        "loadshape_errors": b["errors"],
        "loadshape_http_5xx": b["http_5xx"],
        "loadshape_shed": b["shed"],
        "loadshape_degraded": b["degraded"],
        "loadshape_flash_p99_ms": fl["p99_ms"],
        "loadshape_flash_http_5xx": fl["http_5xx"],
        "loadshape_flash_shed": fl["shed"],
        "loadshape_flash_degraded": fl["degraded"],
        "loadshape_flip_p99_ms": fp["p99_ms"],
        "loadshape_flip_errors": fp["errors"],
        "loadshape_flip_http_5xx": fp["http_5xx"],
        "loadshape_flip_epoch_moved": fp.get("epoch_moved"),
        "loadshape_flip_singleflight": fp.get("singleflight_joins"),
        "loadshape_cache_hit_ratio": res.get("cache_hit_ratio"),
        "loadshape_platform": res["platform"],
    }
    for key, val in flat.items():
        if val is not None:
            result[key] = round(val, 3) if isinstance(val, float) else val


def _record_loadshape_pred(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The predictive-serving bracket (ISSUE 17): paired A/B legs at
    equal capacity — forecaster off vs KMLS_FORECAST=1 — over ramp and
    sine (where prediction can lead the cliff) plus constant (the
    control, where it must change nothing). The judged claims: the
    predictive leg no worse than reactive on BOTH pooled p99 and
    shed+degrade count for ramp and sine, zero 5xx on every leg, and the
    predictive legs' forecaster observation counts > 0 (a win with no
    observations would be a measurement artifact). CPU-platform by
    construction, self-labeled."""

    def _run() -> dict | None:
        return _run_phase(
            "loadshape_pred", _LOADSHAPE_PRED_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    shapes = res.get("shapes")
    if not shapes:
        return
    total_5xx = sum(
        leg["http_5xx"] for pair in shapes.values() for leg in pair.values()
    )
    total_errors = sum(
        leg["errors"] for pair in shapes.values() for leg in pair.values()
    )
    for s in ("ramp", "sine"):
        if s not in shapes:
            continue
        react, pred = shapes[s]["reactive"], shapes[s]["predictive"]
        log(
            f"loadshape_pred/{s}: p99 react {react['p99_ms']:.2f}ms → pred "
            f"{pred['p99_ms']:.2f}ms (onset {react.get('onset_p99_ms')} → "
            f"{pred.get('onset_p99_ms')}); shed+degraded "
            f"{react['shed'] + react['degraded']} → "
            f"{pred['shed'] + pred['degraded']}; "
            f"{pred.get('forecast_observations', 0)} observations"
        )
    flat = {"loadshape_pred_http_5xx": total_5xx,
            "loadshape_pred_errors": total_errors,
            "loadshape_pred_qps": res["qps"],
            "loadshape_pred_platform": res["platform"]}
    for s, pair in shapes.items():
        for mode, tag in (("reactive", "react"), ("predictive", "pred")):
            leg = pair[mode]
            prefix = f"loadshape_pred_{s}_{tag}"
            flat[f"{prefix}_p99_ms"] = leg["p99_ms"]
            flat[f"{prefix}_onset_p99_ms"] = leg.get("onset_p99_ms")
            flat[f"{prefix}_steady_p99_ms"] = leg.get("steady_p99_ms")
            flat[f"{prefix}_shed"] = leg["shed"]
            flat[f"{prefix}_degraded"] = leg["degraded"]
            if tag == "react":
                # the zero-cost proof under real traffic: the disabled
                # leg's forecaster observation delta, asserted 0 in-phase
                flat[f"{prefix}_obs_delta"] = leg.get(
                    "forecast_disabled_obs_delta"
                )
        flat[f"loadshape_pred_{s}_obs"] = pair["predictive"].get(
            "forecast_observations"
        )
    for key, val in flat.items():
        if val is not None:
            result[key] = round(val, 3) if isinstance(val, float) else val


def _record_freshness(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The continuous-freshness bracket (ISSUE 10): full re-mine +
    republish vs incremental delta publish→applied-in-serving on the ds2
    shape, with a Zipf replay running through the in-place apply. Judged
    claims: freshness_speedup ≥ 5, freshness_http_5xx == 0 mid-apply,
    and the hot cache surviving the delta (selective invalidation —
    freshness_cache_invalidated_keys stays a sliver of the entry count).
    freshness_fleet_multiplier is the 3-replica affinity decision number.
    CPU-platform by construction, self-labeled."""

    def _run() -> dict | None:
        return _run_phase(
            "freshness", _FRESHNESS_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"freshness: full path {res['full_path_s']:.2f}s vs delta "
        f"{res['delta_path_s']:.2f}s ({res['speedup']:.1f}x), "
        f"publish→applied {res['publish_to_applied_ms']:.0f}ms, "
        f"{res['http_5xx']} 5xx mid-apply, "
        f"{res['cache_invalidated_keys']} keys selectively invalidated, "
        f"fleet multiplier {res['fleet_multiplier']:.2f}x"
    )
    for src, dst in (
        ("full_path_s", "freshness_full_path_s"),
        ("delta_path_s", "freshness_delta_path_s"),
        ("delta_publish_s", "freshness_delta_publish_s"),
        ("publish_to_applied_ms", "freshness_publish_to_applied_ms"),
        ("speedup", "freshness_speedup"),
        ("errors", "freshness_errors"),
        ("http_5xx", "freshness_http_5xx"),
        ("p99_ms", "freshness_p99_ms"),
        ("delta_applied_total", "freshness_delta_applied"),
        ("delta_rejected_total", "freshness_delta_rejected"),
        ("cache_hit_ratio", "freshness_cache_hit_ratio"),
        ("cache_invalidated_keys", "freshness_cache_invalidated_keys"),
        ("fleet_affinity_hit_ratio", "freshness_fleet_affinity_hit"),
        ("fleet_baseline_hit_ratio", "freshness_fleet_baseline_hit"),
        ("fleet_multiplier", "freshness_fleet_multiplier"),
        ("platform", "freshness_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 3) if isinstance(val, float) else val


def _record_fleet(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The fleet cache-routing bracket (ISSUE 15): 3 real server
    processes + routed replay vs the same fleet under round-robin, on a
    Zipf pool wider than one replica's LRU. Judged claims:
    fleet_hit_ratio ≥ fleet_independent_hit_ratio ×
    fleet_multiplier_simulated within 10% (the PR 10 simulation,
    falsified or confirmed with real sockets — one canonical ring on
    both sides), fleet_http_5xx == 0 through BOTH a mid-replay replica
    SIGKILL (router ejects + spills, survivors absorb) and a mid-replay
    delta apply (selective per-seed invalidation held per shard —
    fleet_identity_ok pins survivor answer identity). CPU-platform by
    construction, self-labeled."""

    def _run() -> dict | None:
        return _run_phase(
            "fleet", _FLEET_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"fleet @ {res['achieved_qps']:.0f}/{res['qps']:.0f} QPS x "
        f"{res['replicas']} replicas: routed hit "
        f"{res['routed_hit_ratio']:.3f} vs independent "
        f"{res['independent_hit_ratio']:.3f} = "
        f"{res['multiplier_achieved']:.2f}x (simulated "
        f"{res['multiplier_simulated']:.2f}x), p99 {res['p99_ms']:.2f}ms, "
        f"{res['http_5xx']} 5xx through kill+delta, "
        f"{res['rerouted']} rerouted, identity_ok={res['identity_ok']}"
    )
    for src, dst in (
        ("routed_hit_ratio", "fleet_hit_ratio"),
        ("independent_hit_ratio", "fleet_independent_hit_ratio"),
        ("multiplier_achieved", "fleet_multiplier_achieved"),
        ("multiplier_simulated", "fleet_multiplier_simulated"),
        ("multiplier_vs_simulated", "fleet_multiplier_vs_simulated"),
        ("achieved_qps", "fleet_achieved_qps"),
        ("offered_qps", "fleet_offered_qps"),
        ("p50_ms", "fleet_p50_ms"),
        ("p99_ms", "fleet_p99_ms"),
        ("errors", "fleet_errors"),
        ("http_5xx", "fleet_http_5xx"),
        ("replicas", "fleet_replicas"),
        ("cache_entries", "fleet_cache_entries"),
        ("zipf_pool", "fleet_zipf_pool"),
        ("rerouted", "fleet_rerouted"),
        ("router_ejections", "fleet_router_ejections"),
        ("owner_stamped", "fleet_owner_stamped"),
        ("misrouted_total", "fleet_misrouted_total"),
        ("delta_applied_ok", "fleet_delta_applied_ok"),
        ("selective_invalidations", "fleet_selective_invalidations"),
        ("identity_ok", "fleet_identity_ok"),
        ("platform", "fleet_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 4) if isinstance(val, float) else val


def _record_quality(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The quality-loop bracket (ISSUE 14): held-out ranking quality per
    serving mode next to the latency evidence for the first time. Judged
    claims: quality_recall_blend (the sweep's measured optimum) vs the
    pure-mode recalls, quality_weight_roundtrip (the published optimum
    IS what KMLS_HYBRID_BLEND_WEIGHT=measured serves),
    quality_compact_identical (compacted snapshot == pristine full
    re-mine of the final CSV, tensors exact) and quality_http_5xx == 0
    through the mid-replay compaction swap. CPU-platform by
    construction, self-labeled."""

    def _run() -> dict | None:
        return _run_phase(
            "quality", _QUALITY_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"quality: recall@k rules {res['recall_rules']:.3f} / embed "
        f"{res['recall_embed'] if res['recall_embed'] is not None else 'n/a'}"
        f" / blend@measured {res['recall_blend_best']}, measured w="
        f"{res['measured_weight']} (roundtrip {res['weight_roundtrip']}), "
        f"compaction {res['compact_s']:.2f}s vs re-mine "
        f"{res['remine_s']:.2f}s (identical={res['compact_identical']}), "
        f"{res['http_5xx']} 5xx mid-swap"
    )
    for src, dst in (
        ("recall_rules", "quality_recall_rules"),
        ("recall_embed", "quality_recall_embed"),
        ("recall_blend_best", "quality_recall_blend"),
        ("recall_popularity", "quality_recall_popularity"),
        ("mrr_blend", "quality_mrr_blend"),
        ("coverage_blend", "quality_coverage_blend"),
        ("measured_weight", "quality_blend_weight"),
        ("weight_roundtrip", "quality_weight_roundtrip"),
        ("eval_playlists", "quality_eval_playlists"),
        ("compact_s", "quality_compact_s"),
        ("compact_speedup", "quality_compact_speedup"),
        ("compact_identical", "quality_compact_identical"),
        ("remine_s", "quality_remine_s"),
        ("http_5xx", "quality_http_5xx"),
        ("errors", "quality_errors"),
        ("p99_ms", "quality_p99_ms"),
        ("platform", "quality_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 4) if isinstance(val, float) else val


def _record_traceoverhead(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The tracing-overhead micro-bracket (ISSUE 9): the same 1k-QPS
    Zipf constant replay through two apps one knob apart —
    KMLS_TRACE_SAMPLE=0.01 vs tracing disabled — alternated so host
    noise drifts across both modes. Judged claims: p99_ratio ≤ 1.05
    (sampled tracing inside 5% of disabled) and began_off == 0 (the
    disabled recorder allocated NOTHING — the zero-cost contract the
    compact line carries as traceoverhead_began_off)."""

    def _run() -> dict | None:
        return _run_phase(
            "traceoverhead", _TRACEOVERHEAD_BENCH, [], platform="cpu",
            timeout=min(480, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"traceoverhead @ {res['qps']:.0f} QPS: p99 on {res['p99_on_ms']:.2f}ms "
        f"vs off {res['p99_off_ms']:.2f}ms (ratio {res['p99_ratio']:.3f}); "
        f"began off={res['began_off']} on={res['began_on']}, "
        f"retained {res['retained_on']}"
    )
    for key in (
        "p99_on_ms", "p99_off_ms", "p99_ratio", "p50_on_ms", "p50_off_ms",
        "began_off", "retained_on",
    ):
        result[f"traceoverhead_{key}"] = res[key]


def _record_costattrib(
    result: dict, bank: str | None = None, budget_s: float | None = None,
    platform: str = "cpu",
) -> None:
    """The cost-attribution bracket (ISSUE 12): a Zipf replay through
    the JITTED serve kernel with the cost model on. Judged claims:
    costattrib_mfu ∈ (0, 1] (device-truth serve-kernel MFU against the
    backend peak table — the TPU suite runs this with platform="tpu"
    so the phase subprocess actually sees the chip),
    costattrib_roofline (compute vs bandwidth bound),
    costattrib_compiles == 0 (the zero-compiles-post-publish invariant
    measured LIVE), and costattrib_obs_off == 0 (the disabled cost
    model did literally nothing — began-counter style)."""

    def _run() -> dict | None:
        return _run_phase(
            "costattrib", _COSTATTRIB_BENCH, [], platform=platform,
            timeout=min(480, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"costattrib @ {res['qps']:.0f} QPS: serve-kernel MFU "
        f"{res['mfu']:.2e} ({res['roofline']}-bound, "
        f"{res['flops_per_s']:.3g} FLOP/s vs peak {res['peak_flops']:.3g} "
        f"[{res['peak_source']}]), {res['dispatches']} dispatches over "
        f"{res['device_s']:.2f}s device time, compiles={res['compiles']}, "
        f"disabled-mode observations={res['obs_off_delta']}"
    )
    for src, dst in (
        ("mfu", "costattrib_mfu"),
        ("roofline", "costattrib_roofline"),
        ("compiles", "costattrib_compiles"),
        ("obs_off_delta", "costattrib_obs_off"),
        ("flops_per_s", "costattrib_flops_per_s"),
        ("bytes_per_s", "costattrib_bytes_per_s"),
        ("device_s", "costattrib_device_s"),
        ("dispatches", "costattrib_dispatches"),
        ("p99_ms", "costattrib_p99_ms"),
        ("peak_source", "costattrib_peak_source"),
        ("platform", "costattrib_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = (
                float(f"{val:.4g}") if isinstance(val, float) else val
            )


def _record_mine_resume(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The mining-interruption bracket (ISSUE 4's satellite): kill the
    mining job right after a fixed phase's checkpoint, restart, and report
    resume-vs-full-recompute wall clock. The judged claims are
    mine_resume_identical == True (bit-identical artifacts after resume)
    and mine_resume_saved_pct > 0 (the checkpoint actually pays)."""
    def _run():
        return _run_phase(
            "mine-resume", _MINE_RESUME_BENCH, [], platform="cpu",
            timeout=min(600, max(_remaining(), 60)),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"mine-resume (killed after {res['crash_phase']!r}): full "
        f"{res['full_s']:.2f}s vs resume {res['resume_s']:.2f}s "
        f"({res['saved_pct']:.0f}% saved), bit-identical: {res['identical']}"
    )
    for src, dst in (
        ("crash_phase", "mine_resume_phase"),
        ("full_s", "mine_resume_full_s"),
        ("resume_s", "mine_resume_s"),
        ("saved_pct", "mine_resume_saved_pct"),
        ("identical", "mine_resume_identical"),
        ("platform", "mine_resume_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 3) if isinstance(val, float) else val


def _record_replay10k(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The 10k-QPS in-process throughput bracket (cache → batcher →
    engine, Zipf-skewed mix). Always CPU-platform — the native host
    kernel owns the CPU hot path and an HTTP loadgen can't honestly pace
    10k QPS on this sandbox — so the keys carry their own platform label
    and are never relabeled by a TPU takeover."""

    def _run() -> dict | None:
        return _run_phase(
            "replay10k", _REPLAY10K_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    r10k = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if r10k is None:
        return
    log(
        f"replay10k @ {r10k['qps']:.0f} QPS (zipf {r10k['zipf_s']}): "
        f"p50 {r10k['p50_ms']:.2f}ms p99 {r10k['p99_ms']:.2f}ms, achieved "
        f"{r10k['achieved_qps']:.0f} QPS, {r10k['errors']} errors, "
        f"cache hit ratio {r10k.get('cache_hit_ratio') or 0:.2f}"
    )
    for src, dst in (
        ("qps", "replay10k_qps"),
        ("offered_qps", "replay10k_offered_qps"),
        ("achieved_qps", "replay10k_achieved_qps"),
        ("p50_ms", "replay10k_p50_ms"),
        ("p95_ms", "replay10k_p95_ms"),
        ("p99_ms", "replay10k_p99_ms"),
        ("errors", "replay10k_errors"),
        ("cache_hit_ratio", "replay10k_cache_hit_ratio"),
        ("cached_p50_ms", "replay10k_cached_p50_ms"),
        ("uncached_p50_ms", "replay10k_uncached_p50_ms"),
        ("zipf_s", "replay10k_zipf_s"),
        ("per_device_dispatch", "replay10k_per_device_dispatch"),
        ("devices_active", "replay10k_devices_active"),
        ("n_replicas", "replay10k_n_replicas"),
        ("platform", "replay10k_platform"),
    ):
        if src in r10k and r10k[src] is not None:
            val = r10k[src]
            result[dst] = round(val, 3) if isinstance(val, float) else val


def _record_als_hybrid(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The second-model-family bracket (ISSUE 6): ALS training time
    through the real pipeline's embed phase, hybrid blend-mode replay
    p50/p99, and the cold-start hit fraction (zero-rule seeds answered
    from the embedding space, not the popularity fallback). CPU-platform
    by construction, self-labeled — never relabeled by a TPU takeover."""

    def _run() -> dict | None:
        return _run_phase(
            "als-hybrid", _ALS_HYBRID_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    frac = res.get("cold_start_hit_frac")
    log(
        f"als-hybrid: ALS train {res['als_train_s']:.2f}s (rank "
        f"{res['als_rank']}), blend replay p50 {res['p50_ms']:.2f}ms "
        f"p99 {res['p99_ms']:.2f}ms @ {res['achieved_qps']:.0f} QPS, "
        f"cold-start hit "
        f"{frac:.2%}" if frac is not None else
        "als-hybrid: no cold-start seeds in this workload (!)"
    )
    for src, dst in (
        ("als_train_s", "als_train_s"),
        ("als_rank", "als_rank"),
        ("als_iters", "als_iters"),
        ("emb_vocab", "als_emb_vocab"),
        ("achieved_qps", "hybrid_achieved_qps"),
        ("p50_ms", "hybrid_p50_ms"),
        ("p95_ms", "hybrid_p95_ms"),
        ("p99_ms", "hybrid_p99_ms"),
        ("errors", "hybrid_errors"),
        ("cold_start_seeds", "cold_start_seeds"),
        ("cold_start_hit_frac", "cold_start_hit_frac"),
        ("platform", "hybrid_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 4) if isinstance(val, float) else val


def _record_confserve(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """Confidence-mode serving bracket (carried-over ROADMAP item):
    multi-antecedent true-confidence rules replayed through the jitted
    max-merge kernel. CPU-platform by construction, self-labeled."""

    def _run() -> dict | None:
        return _run_phase(
            "confserve", _CONFSERVE_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"confserve (confidence mode, itemsets ≤{res['max_itemset_len']}): "
        f"p50 {res['p50_ms']:.2f}ms p99 {res['p99_ms']:.2f}ms @ "
        f"{res['achieved_qps']:.0f} QPS, {res['errors']} errors, "
        f"{res['rule_keys']} rule keys"
    )
    for src, dst in (
        ("achieved_qps", "confserve_qps"),
        ("p50_ms", "confserve_p50_ms"),
        ("p95_ms", "confserve_p95_ms"),
        ("p99_ms", "confserve_p99_ms"),
        ("errors", "confserve_errors"),
        ("rule_keys", "confserve_rule_keys"),
        ("max_itemset_len", "confserve_max_itemset_len"),
        ("platform", "confserve_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 3) if isinstance(val, float) else val


def _record_shardserve(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The model-parallel serving bracket (ISSUE 7): a catalog whose
    rule tensors exceed the per-device budget serves SHARDED (auto
    layout), bit-identical to replicated, zero compiles post-publish;
    replicated-vs-sharded p50/p99 and the max servable catalog bytes
    land in the artifact. CPU-platform by construction (virtual 8-device
    mesh), self-labeled."""

    def _run() -> dict | None:
        return _run_phase(
            "shardserve", _SHARDSERVE_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
            extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"shardserve: {res['shards']} shards, identical="
        f"{res['identical']}, unwarmed={res['unwarmed_dispatches']}, "
        f"replicated p50 {res['replicated_p50_ms']:.2f}ms vs sharded "
        f"p50 {res['sharded_p50_ms']:.2f}ms (batch bracket), max catalog "
        f"{res['max_catalog_bytes'] / 1e6:.1f} MB across the mesh"
    )
    for src, dst in (
        ("shards", "shardserve_shards"),
        ("identical", "shardserve_identical"),
        ("unwarmed_dispatches", "shardserve_unwarmed"),
        ("catalog_bytes", "shardserve_catalog_bytes"),
        ("device_budget_bytes", "shardserve_device_budget_bytes"),
        ("max_catalog_bytes", "shardserve_max_catalog_bytes"),
        ("replicated_p50_ms", "shardserve_replicated_p50_ms"),
        ("replicated_p99_ms", "shardserve_replicated_p99_ms"),
        ("sharded_p50_ms", "shardserve_sharded_p50_ms"),
        ("sharded_p99_ms", "shardserve_sharded_p99_ms"),
        ("platform", "shardserve_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 3) if isinstance(val, float) else val


def _record_meshserve(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The pod-spanning serve-mesh bracket (ISSUE 16): a 2-member gang
    (each holding only its vocab slab, merging over the socket mesh
    transport) serves the SAME over-budget catalog as the single-process
    sharded kernel — answers pinned bit-identical to replicated AND
    sharded on BOTH members, zero compiles post-publish, max servable
    catalog = per-host budget x gang size. The chaos leg SIGKILLs a
    gang member mid-replay behind the routed client: zero 5xx, zero
    drops, whole-gang ejection with the dark shard blamed. CPU-platform
    by construction (socket transport), self-labeled."""

    def _run() -> dict | None:
        return _run_phase(
            "meshserve", _MESHSERVE_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
            extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"meshserve: gang of {res['gang_size']}, identical="
        f"{res['identical']}, unwarmed={res['unwarmed_dispatches']}, "
        f"sharded p50 {res['sharded_p50_ms']:.2f}ms vs mesh p50 "
        f"{res['mesh_p50_ms']:.2f}ms, max catalog "
        f"{res['max_catalog_bytes'] / 1e6:.1f} MB across the gang; chaos "
        f"leg {res['http_5xx']} 5xx / {res['errors']} drops through a "
        f"gang-member SIGKILL ({res['mesh_unavailable']} mesh refusals, "
        f"{res['ejections']} ejections)"
    )
    for src, dst in (
        ("gang_size", "meshserve_gang"),
        ("identical", "meshserve_identical"),
        ("unwarmed_dispatches", "meshserve_unwarmed"),
        ("catalog_bytes", "meshserve_catalog_bytes"),
        ("host_budget_bytes", "meshserve_host_budget_bytes"),
        ("max_catalog_bytes", "meshserve_max_catalog_bytes"),
        ("sharded_p50_ms", "meshserve_sharded_p50_ms"),
        ("sharded_p99_ms", "meshserve_sharded_p99_ms"),
        ("mesh_p50_ms", "meshserve_p50_ms"),
        ("mesh_p99_ms", "meshserve_p99_ms"),
        ("achieved_qps", "meshserve_achieved_qps"),
        ("replay_p99_ms", "meshserve_replay_p99_ms"),
        ("http_5xx", "meshserve_http_5xx"),
        ("errors", "meshserve_errors"),
        ("mesh_unavailable", "meshserve_mesh_unavailable"),
        ("ejections", "meshserve_ejections"),
        ("platform", "meshserve_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 3) if isinstance(val, float) else val


def _record_slowpeer(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The gray-failure chaos bracket (ISSUE 18): a 200 ms deterministic
    stall on one fleet peer and one gang member — alive, answering,
    LATE, so no error breaker ever fires — with the hedged leg racing
    the no-hedge control at equal capacity. Judged claims: hedged p99
    ≥ 5x better than the control, hedge overhead (extra dispatches /
    total) ≤ 5%, zero 5xx and zero drops on every leg, answers
    bit-identical whichever copy wins (hedge_mismatch == 0 plus the
    post-replay cross-replica probe identity), and the in-bench
    zero-cost pin — the control leg leaves replay.HEDGES_ISSUED at
    exactly 0 under real traffic. CPU-platform by construction (real
    local server processes), self-labeled."""

    def _run() -> dict | None:
        return _run_phase(
            "slowpeer", _SLOWPEER_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"slowpeer: control p99 {res['control_p99_ms']:.0f}ms vs hedged "
        f"p99 {res['hedged_p99_ms']:.0f}ms ({res['p99_ratio']:.1f}x) "
        f"through a {res['stall_ms']}ms gray stall — "
        f"{res['hedges_issued']} hedges ({res['hedge_overhead_pct']:.1f}% "
        f"overhead, {res['hedge_wins']} won), {res['slow_ejections']} slow "
        f"ejections, {res['http_5xx']} 5xx / {res['errors']} drops across "
        f"all legs, identity_ok={res['identity_ok']}, control hedges "
        f"{res['control_hedges_issued']}; mesh leg {res['mesh_hedge_wins']} "
        f"coordinator hedge wins, {res['mesh_straggler_degraded']} "
        f"straggler-degraded merges"
    )
    for src, dst in (
        ("qps", "slowpeer_qps"),
        ("requests", "slowpeer_requests"),
        ("stall_ms", "slowpeer_stall_ms"),
        ("control_p50_ms", "slowpeer_control_p50_ms"),
        ("control_p99_ms", "slowpeer_control_p99_ms"),
        ("hedged_p50_ms", "slowpeer_hedged_p50_ms"),
        ("hedged_p99_ms", "slowpeer_hedged_p99_ms"),
        ("p99_ratio", "slowpeer_p99_ratio"),
        ("hedge_overhead_pct", "slowpeer_hedge_overhead_pct"),
        ("hedges_issued", "slowpeer_hedges_issued"),
        ("hedge_wins", "slowpeer_hedge_wins"),
        ("hedge_losses", "slowpeer_hedge_losses"),
        ("hedges_suppressed", "slowpeer_hedges_suppressed"),
        ("hedge_mismatch", "slowpeer_hedge_mismatch"),
        ("slow_ejections", "slowpeer_slow_ejections"),
        ("deadline_expired", "slowpeer_deadline_expired"),
        ("server_deadline_expired", "slowpeer_server_deadline_expired"),
        ("control_hedges_issued", "slowpeer_control_hedges_issued"),
        ("http_5xx", "slowpeer_http_5xx"),
        ("errors", "slowpeer_errors"),
        ("identity_ok", "slowpeer_identity_ok"),
        ("mesh_hedge_wins", "slowpeer_mesh_hedge_wins"),
        ("mesh_hedge_cancelled", "slowpeer_mesh_hedge_cancelled"),
        ("mesh_straggler_degraded", "slowpeer_mesh_straggler_degraded"),
        ("mesh_expired_on_arrival", "slowpeer_mesh_expired_on_arrival"),
        ("mesh_p99_ms", "slowpeer_mesh_p99_ms"),
        ("mesh_http_5xx", "slowpeer_mesh_http_5xx"),
        ("mesh_errors", "slowpeer_mesh_errors"),
        ("platform", "slowpeer_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 3) if isinstance(val, float) else val


def _record_graystore(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The storage gray-failure bracket (ISSUE 19): the shared artifact
    volume goes gray — every PVC read stalls 400 ms under a 1k-QPS
    replay, then ENOSPC lands exactly on the recommendations write of a
    full publication. Judged claims: zero 5xx on every leg, serving p99
    unmoved by the stall (the hot path never touches the volume), slow-IO
    conviction flips /readyz to ready-but-degraded reason storage-slow,
    the armed reload parks in bounded backoff holding last-good (and
    recovers once the stall clears), and the ENOSPC publication aborts
    resumable (exit 75) with the last-good bytes bit-identical, the
    token unmoved, and zero torn temp files on the volume. CPU-platform
    by construction (tmpfs-backed artifact dir + injected faults),
    self-labeled."""

    def _run() -> dict | None:
        return _run_phase(
            "graystore", _GRAYSTORE_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"graystore: serving p99 {res['control_p99_ms']:.1f}ms clean vs "
        f"{res['stalled_p99_ms']:.1f}ms under a {res['stall_ms']:.0f}ms "
        f"PVC read stall ({res['p99_ratio']:.2f}x), "
        f"storage_slow={res['storage_slow']}, "
        f"readyz_degraded={res['readyz_degraded']}, reload deferred="
        f"{res['reload_deferred']} (backoff bounded={res['backoff_bounded']}, "
        f"last-good held={res['last_good_held']}); ENOSPC mid-publish: "
        f"exit {res['enospc_exit']} (resumable={res['enospc_exit_resumable']}), "
        f"identical={res['enospc_identical']}, "
        f"token_moved={res['enospc_token_moved']}, "
        f"{res['torn_parts']} torn temps, recovered={res['recovered']}; "
        f"{res['http_5xx']} 5xx / {res['errors']} drops across all legs"
    )
    for src in (
        "qps", "requests", "stall_ms", "control_p50_ms", "control_p99_ms",
        "stalled_p50_ms", "stalled_p99_ms", "p99_ratio", "storage_slow",
        "readyz_degraded", "reload_deferred", "backoff_bounded",
        "last_good_held", "enospc_exit", "enospc_exit_resumable",
        "enospc_identical", "enospc_token_moved", "torn_parts",
        "probe_p99_ms", "recovered", "io_retries", "http_5xx", "errors",
        "platform",
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result["graystore_" + src] = (
                round(val, 3) if isinstance(val, float) else val
            )


def _record_scale_shard(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The vocab-sharded mining bracket (ISSUE 7): a basket matrix whose
    dense single-device formulation busts the HBM budget mines through
    the sharded count→emit pipeline on the 1x8 vocab mesh."""

    def _run() -> dict | None:
        return _run_phase(
            "scale-shard", _SCALE_SHARD_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
            extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    log(
        f"scale-shard: {res['shape']} mined in {res['mine_s']:.1f}s "
        f"({res['rows_per_s']:.0f} rows/s) via {res['count_path']} — "
        f"dense needs {res['dense_single_device_bytes'] / 1e6:.0f} MB on "
        f"one device (budget {res['hbm_budget_bytes'] / 1e6:.0f} MB); "
        f"per-shard counts {res['per_shard_counts_bytes'] / 1e6:.1f} MB"
    )
    for src, dst in (
        ("mine_s", "scale_shard_mine_s"),
        ("rows_per_s", "scale_shard_rows_per_s"),
        ("shape", "scale_shard_shape"),
        ("count_path", "scale_shard_count_path"),
        ("shards", "scale_shard_shards"),
        ("dense_single_device_bytes", "scale_shard_dense_bytes"),
        ("hbm_budget_bytes", "scale_shard_budget_bytes"),
        ("rules_emitted", "scale_shard_rules"),
        ("frequent_items", "scale_shard_frequent_items"),
        ("platform", "scale_shard_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 3) if isinstance(val, float) else val


def _record_scale_sparse(
    result: dict, bank: str | None = None, budget_s: float | None = None,
) -> None:
    """The sparsity-adaptive bracket (ISSUE 13): at ≥99% sparsity the
    sparse CSR×bitpacked hybrid must beat the standing
    ``scale_cpu_native`` record path ≥5x ON THE SAME workload with
    bit-identical tensors, the dense/bitpack/sparse identity leg must
    agree, and the density sweep re-banks the measured dispatch table
    the auto path consults."""

    def _run() -> dict | None:
        return _run_phase(
            "scale-sparse", _SCALE_SPARSE_BENCH, [], platform="cpu",
            timeout=min(600, _remaining()),
        )

    res = _banked(bank, _run, budget_s, extras=result) if bank else _run()
    if res is None:
        return
    if "speedup_vs_native" in res:
        log(
            f"scale-sparse: {res['shape']} at density {res['density']:.6f} "
            f"mined in {res['sparse_mine_s']:.2f}s "
            f"({res['sparse_rows_per_s']:.0f} rows/s, "
            f"{res['count_path']}) vs {res['native_mine_s']:.2f}s "
            f"{res['native_count_path']} — {res['speedup_vs_native']:.1f}x, "
            f"identical={res.get('headline_identical')}; auto dispatch "
            f"-> {res['auto_path']} ({res['auto_source']})"
        )
    for src, dst in (
        ("sparse_mine_s", "sparse_mine_s"),
        ("sparse_rows_per_s", "sparse_rows_per_s"),
        ("native_mine_s", "sparse_native_mine_s"),
        ("native_rows_per_s", "sparse_native_rows_per_s"),
        ("speedup_vs_native", "sparse_speedup_vs_native"),
        ("identical", "sparse_identical"),
        ("headline_identical", "sparse_headline_identical"),
        ("density", "sparse_density"),
        ("shape", "sparse_shape"),
        ("count_path", "sparse_count_path"),
        ("auto_path", "sparse_auto_path"),
        ("auto_source", "sparse_auto_source"),
        ("table_cells", "sparse_table_cells"),
        ("sweep_identical", "sparse_sweep_identical"),
        ("frequent_items", "sparse_frequent_items"),
        ("platform", "sparse_platform"),
    ):
        if src in res and res[src] is not None:
            val = res[src]
            result[dst] = round(val, 3) if isinstance(val, float) else val


def main() -> int:
    prober = TpuProber()
    em = ArtifactEmitter(prober)
    _install_crash_handlers(em)
    result = em.extras
    # the platform is decided here, once: the chip, or an explicitly
    # CPU-labelled run. Neither ever turns into the other.
    cpu_run = os.environ.get("KMLS_BENCH_CPU") == "1"
    if cpu_run:
        log("KMLS_BENCH_CPU=1: a CPU-labelled run, no chip asked for")
        prober.history.append({"t_s": 0.0, "outcome": "forced_cpu", "dur_s": 0.0})
    else:
        log("probing TPU backend (bounded)...")
        outcome = prober.probe_once()
        if outcome != "tpu":
            log(
                f"FATAL: no TPU (probe: {outcome}). A chip run that cannot "
                "get the chip reports nothing; set KMLS_BENCH_CPU=1 for a "
                "CPU-labelled run"
            )
            return 1

    with tempfile.NamedTemporaryFile(suffix=".npz") as f:
        if cpu_run:
            mining = run_cpu_suite(em, f.name)
        else:
            mining = run_tpu_suite(em, f.name)
            if mining is not None:
                # cheap CPU comparison point (native POPCNT path), under
                # its own cpu-labelled keys next to the on-chip headline —
                # optional, so its timeout respects the deadline (the
                # already-measured TPU headline must not be lost to a
                # harness kill past DEADLINE_S)
                cpu_cmp = _banked("mining_cpu_cmp", lambda: run_mining(
                    "cpu", f.name, attempts=1,
                    timeout=min(600, max(_remaining() - 30, 60)),
                ), budget_s=180, extras=result)
                if cpu_cmp is not None:
                    em.set_cpu_comparison(cpu_cmp)

    if mining is None:
        log(
            "FATAL: the mining bench produced no number on "
            + ("the cpu" if cpu_run else "the chip")
        )
        return 1

    return 0 if em.finalize() else 1


if __name__ == "__main__":
    sys.exit(main())
