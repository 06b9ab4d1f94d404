"""Typed configuration over the reference's env-var contract.

The reference configures both workloads purely through environment variables
(reference: machine-learning/main.py:17-49, rest_api/app/main.py:31-50), bound
in-cluster by the manifests (reference: kubernetes/job.yaml:24-40,
kubernetes/deployment.yaml:33-53). The variable NAMES and defaults here are
that contract and must not drift — the Kubernetes layer depends on them.

On top, the TPU rebuild adds its own knobs under a ``KMLS_`` prefix (mesh
shape, rule-row capacity, confidence semantics, server port); these have safe
defaults and are absent from the reference.
"""

from __future__ import annotations

import dataclasses
import os

from .utils.envfile import load_dotenv


def _getenv_int(name: str, default: int) -> int:
    raw = os.getenv(name)
    return int(raw) if raw not in (None, "") else default


def _getenv_float(name: str, default: float) -> float:
    raw = os.getenv(name)
    return float(raw) if raw not in (None, "") else default


def _getenv_bool(name: str, default: bool) -> bool:
    raw = os.getenv(name)
    if raw in (None, ""):
        return default
    return raw.strip().lower() in ("1", "true", "yes", "on")


def _getenv_hybrid_mode() -> str:
    """``KMLS_HYBRID_MODE``: one of ``rules``/``embed``/``blend``
    (case-insensitive). An unrecognized value falls back to ``rules`` —
    the FAIL-SAFE direction: a typo while trying to pin the legacy path
    must never silently enable the hybrid merge — with a loud warning."""
    raw = os.getenv("KMLS_HYBRID_MODE")
    if raw in (None, ""):
        return "blend"
    word = raw.strip().lower()
    if word in ("rules", "embed", "blend"):
        return word
    import logging

    logging.getLogger("kmlserver_tpu.serving").warning(
        "KMLS_HYBRID_MODE=%r is not one of rules/embed/blend; "
        "serving rules-only", raw,
    )
    return "rules"


def _getenv_blend_weight() -> tuple[float, bool]:
    """``KMLS_HYBRID_BLEND_WEIGHT``: a float, or ``measured`` — serve
    the blend optimum the quality loop published in
    ``quality.report.json`` (ISSUE 14). → ``(weight, measured)``; the
    explicit float always wins over a report, and anything unparseable
    fails SAFE to the default weight with a loud warning (a typo while
    opting into measurement must not silently pin a wrong float)."""
    raw = os.getenv("KMLS_HYBRID_BLEND_WEIGHT")
    if raw in (None, ""):
        return 0.5, False
    word = raw.strip().lower()
    if word == "measured":
        return 0.5, True
    try:
        return float(raw), False
    except ValueError:
        import logging

        logging.getLogger("kmlserver_tpu.serving").warning(
            "KMLS_HYBRID_BLEND_WEIGHT=%r is neither a float nor "
            "'measured'; using the default 0.5", raw,
        )
        return 0.5, False


def _getenv_model_layout() -> str:
    """``KMLS_MODEL_LAYOUT``: ``replicated`` (default), ``sharded``, or
    ``auto`` (shard when measured tensor bytes exceed
    ``KMLS_DEVICE_BUDGET_BYTES``). Validation — including the fail-safe
    fallback to ``replicated`` on a typo — lives in ONE place:
    ``parallel.layout.validate_layout`` (both workloads resolve through
    it, so the knob can never mean different things to the two sides)."""
    from .parallel.layout import validate_layout

    return validate_layout(os.getenv("KMLS_MODEL_LAYOUT", "replicated"))


def _getenv_gang_rank() -> int:
    """``KMLS_SERVE_GANG_RANK``: explicit rank, falling back to the same
    identity recipe as the mining bootstrap (``JOB_COMPLETION_INDEX``,
    then the hostname's trailing StatefulSet ordinal —
    ``parallel.distributed.gang_rank_fallback`` is the canonical copy;
    this mirror keeps config import-light)."""
    raw = os.getenv("KMLS_SERVE_GANG_RANK")
    if raw not in (None, ""):
        return int(raw)
    idx = os.getenv("JOB_COMPLETION_INDEX")
    if idx is not None and idx.isdigit():
        return int(idx)
    import socket

    _, _, ordinal = socket.gethostname().rpartition("-")
    return int(ordinal) if ordinal.isdigit() else 0


def _getenv_bitpack_threshold() -> int | str | None:
    """``KMLS_BITPACK_THRESHOLD_ELEMS``: "auto" (HBM-fit dispatch, the
    default), "none"/"never" (dense always), or an explicit element count."""
    raw = os.getenv("KMLS_BITPACK_THRESHOLD_ELEMS")
    if raw in (None, ""):
        return "auto"
    word = raw.strip().lower()
    if word == "auto":
        return "auto"
    if word in ("none", "never"):
        return None
    return int(raw)


# ---------------------------------------------------------------------------
# The env-knob registry — THE declaration point for every KMLS_* knob.
#
# kmls-verify's `knobs` checker (kmlserver_tpu/analysis/registries.py)
# enforces, in CI: every knob read anywhere in the code is declared here;
# every entry here is still read somewhere (no dead docs); every entry has a
# README row; and runtime scopes are bound or documented in the Kubernetes
# manifest that deploys them. Scopes:
#
#   "serving" — read by the API pod           (kubernetes/deployment.yaml)
#   "mining"  — read by the batch mining job  (kubernetes/job*.yaml)
#   "both"    — read by both workloads        (all three manifests)
#   "tool"    — bench/sweep/dev harness only  (never shipped in manifests)
#   "fault"   — fault injection (faults.py)   (chaos tests must exercise it)
#
# Adding a knob = add the os.getenv read, an entry here, and a README row
# (+ a manifest line for runtime scopes) — or CI's verify job rejects the
# diff, naming exactly what is missing.
# ---------------------------------------------------------------------------
KNOB_REGISTRY: dict[str, str] = {
    # --- serving: request path / transport ---
    "KMLS_PORT": "serving",
    "KMLS_HTTP_IMPL": "serving",
    "KMLS_MAX_SEED_TRACKS": "serving",
    "KMLS_BATCH_WINDOW_MS": "serving",
    "KMLS_BATCH_MAX_SIZE": "serving",
    "KMLS_BATCH_ADAPTIVE": "serving",
    "KMLS_BATCH_WINDOW_MIN_MS": "serving",
    "KMLS_BATCH_MAX_INFLIGHT": "serving",
    "KMLS_SHED_QUEUE_BUDGET_MS": "serving",
    "KMLS_SHED_RETRY_AFTER_S": "serving",
    # adaptive admission ladder (ISSUE 8): degrade band start, hard-shed
    # band end, and the bounded Retry-After jitter fraction
    "KMLS_SHED_SOFT_RATIO": "serving",
    "KMLS_SHED_HARD_RATIO": "serving",
    "KMLS_SHED_RETRY_JITTER": "serving",
    "KMLS_SERVE_DEVICES": "serving",
    "KMLS_CACHE_ENABLED": "serving",
    "KMLS_CACHE_MAX_ENTRIES": "serving",
    "KMLS_PREFER_TENSOR_ARTIFACT": "serving",
    "KMLS_DRAIN_SETTLE_S": "serving",
    "KMLS_GIL_SWITCH_S": "serving",
    # --- serving: fault tolerance ---
    "KMLS_VERIFY_MANIFEST": "serving",
    "KMLS_QUARANTINE_AFTER_FAILURES": "serving",
    "KMLS_RELOAD_BACKOFF_BASE_S": "serving",
    "KMLS_RELOAD_BACKOFF_MAX_S": "serving",
    "KMLS_REPLICA_EJECT_THRESHOLD": "serving",
    "KMLS_REPLICA_PROBE_INTERVAL_S": "serving",
    "KMLS_REDISPATCH_MAX_RETRIES": "serving",
    "KMLS_REQUEST_DEADLINE_MS": "serving",
    "KMLS_FALLBACK_BUDGET_MS": "serving",
    # --- serving: hybrid rule∪embedding merge (second model family) ---
    "KMLS_HYBRID_MODE": "serving",
    "KMLS_HYBRID_BLEND_WEIGHT": "serving",
    # --- serving: quality loop (ISSUE 14) ---
    # per-artifact staleness bound: any served artifact older than this
    # flags /readyz ready-but-degraded and sets kmls_artifact_stale
    # (0 = disabled — age gauges stay observability-only)
    "KMLS_ARTIFACT_MAX_AGE_S": "serving",
    # --- serving: fleet cache affinity (ISSUE 10) ---
    # rendezvous-hash request affinity (freshness/ring.py): count how much
    # real traffic an affinity router would keep ring-local before
    # committing to one (or to a shared external cache tier)
    "KMLS_CACHE_AFFINITY": "serving",
    "KMLS_CACHE_AFFINITY_PEERS": "serving",
    "KMLS_CACHE_AFFINITY_SELF": "serving",
    # --- serving: fleet cache routing (ISSUE 15) ---
    # stable replica identity for the routing tier (kubernetes/
    # statefulset.yaml binds SELF from the pod name; PEERS lists the
    # StatefulSet ordinals). Setting PEERS arms owner-aware serving:
    # the ring (same rendezvous implementation the router and
    # simulate_fleet use), X-KMLS-Cache-Owner stamping on non-owned
    # answers, and the kmls_cache_misrouted_total drift counter.
    "KMLS_FLEET_SELF": "serving",
    "KMLS_FLEET_PEERS": "serving",
    # --- serving: pod-spanning serve mesh (ISSUE 16) ---
    # gang bootstrap mirroring the mining job's KMLS_PROCESS_ID recipe
    # (kubernetes/serve-gang.yaml binds RANK from the StatefulSet pod
    # index): COORDINATOR is rank 0's partial-fetch address, SIZE the
    # gang width (== spec.replicas), PORT the base partial-protocol
    # port. SIZE > 1 arms the "mesh" layout: each member holds only its
    # vocab slab yet the gang presents ONE logical replica (and one
    # ring peer) to the dispatcher.
    "KMLS_SERVE_GANG_COORDINATOR": "serving",
    "KMLS_SERVE_GANG_SIZE": "serving",
    "KMLS_SERVE_GANG_RANK": "serving",
    "KMLS_SERVE_GANG_PORT": "serving",
    # --- serving: gray-failure spine (ISSUE 18) ---
    # hedged dispatch master switch (0 = off, the proven-zero-cost
    # default: no hedge state allocated, module hedge counters pinned 0)
    "KMLS_HEDGE": "serving",
    # slow-outlier ladder: eject a peer whose EWMA latency exceeds
    # RATIO × the healthy-peer median (0 disables the ladder; slowness
    # then never ejects, only hedging absorbs it)
    "KMLS_PEER_SLOW_RATIO": "serving",
    # hedge trigger floor in ms — the adaptive per-peer delay (tracked
    # latency ~p95) never fires earlier than this
    "KMLS_HEDGE_DELAY_MS": "serving",
    # amplification bound: hedges may add at most this fraction of extra
    # dispatches (token bucket earning FRAC per primary dispatch);
    # exhausted budget falls back to plain waiting
    "KMLS_HEDGE_MAX_FRAC": "serving",
    # --- serving: storage gray-failure spine (ISSUE 19) ---
    # slow-IO conviction threshold: any artifact-plane op whose latency
    # EWMA crosses this flips /readyz ready-but-degraded with reason
    # storage-slow (kmls_storage_slow gauge); clears at half (hysteresis)
    "KMLS_IO_SLOW_MS": "serving",
    # deadline on reload-path artifact reads: a hung NFS read parks the
    # reload in the normal failure backoff with last-good still serving
    # instead of wedging the reload thread (0 = no deadline)
    "KMLS_IO_READ_DEADLINE_S": "serving",
    # --- serving: observability (ISSUE 9) ---
    # span tracing: baseline sample rate for OK traces (0 = tracing off —
    # the zero-hot-path-cost default; shed/degraded/slowest-N traces are
    # ALWAYS retained once tracing is on), ring capacity, slowest-N size
    "KMLS_TRACE_SAMPLE": "serving",
    "KMLS_TRACE_BUFFER": "serving",
    "KMLS_TRACE_SLOW_N": "serving",
    # event-loop-lag collector: peak-hold decay half-life (0 disables the
    # collector AND its admission-pressure fold)
    "KMLS_LOOP_LAG_HALF_LIFE_S": "serving",
    # --- serving: device-truth cost attribution + SLOs (ISSUE 12) ---
    # per-kernel MFU/roofline + memory/compile telemetry (0 disables the
    # cost model entirely — proven zero-cost, observation-counter style)
    "KMLS_COSTMODEL": "serving",
    # peak FLOP/s and HBM bytes/s the MFU/roofline math measures against
    # (default: auto from the device kind — observability/costmodel.py's
    # peak table; the TPU window pins the exact chip)
    "KMLS_PEAK_FLOPS": "serving",
    "KMLS_PEAK_BYTES_PER_S": "serving",
    # SLO layer (observability/slo.py): latency target, error/degrade
    # budgets, and the fast/slow burn-rate windows — observability only,
    # the PR 8 admission ladder stays the actuator
    "KMLS_SLO_P99_MS": "serving",
    "KMLS_SLO_ERROR_BUDGET": "serving",
    "KMLS_SLO_DEGRADE_BUDGET": "serving",
    "KMLS_SLO_FAST_WINDOW_S": "serving",
    "KMLS_SLO_SLOW_WINDOW_S": "serving",
    # --- serving: predictive serving (ISSUE 17) ---
    # online traffic forecaster (serving/forecast.py): arrival-rate +
    # request-mix EWMAs with trend, feeding three actuators — batch-
    # window pre-widening/shape pre-touch, a bounded HPA-lead term in
    # kmls_utilization, and owner-targeted post-delta cache pre-fetch.
    # 0 (default) leaves the hook None — proven zero-cost, observation-
    # counter style like KMLS_COSTMODEL.
    "KMLS_FORECAST": "serving",
    "KMLS_FORECAST_HORIZON_S": "serving",
    "KMLS_FORECAST_WINDOW_S": "serving",
    "KMLS_FORECAST_ALPHA": "serving",
    "KMLS_FORECAST_UTIL_CAP": "serving",
    "KMLS_FORECAST_RAMP_RATIO": "serving",
    "KMLS_FORECAST_PREFETCH_TOP_N": "serving",
    # --- mining: semantics / device dispatch ---
    "KMLS_MAX_ITEMSET_LEN": "mining",
    "KMLS_K_MAX_CONSEQUENTS": "mining",
    "KMLS_CONFIDENCE_MODE": "mining",
    "KMLS_MIN_CONFIDENCE": "mining",
    "KMLS_MESH_SHAPE": "mining",
    "KMLS_BITPACK_THRESHOLD_ELEMS": "mining",
    "KMLS_BITPACK_IMPL": "mining",
    # sparsity-adaptive dispatch (ISSUE 13): pin a count family
    # (dense/bitpack/sparse; anything else fails safe to the measured
    # auto), point at an alternative measured dispatch table, and set
    # the hybrid's long-basket split point
    "KMLS_COUNT_PATH": "mining",
    "KMLS_DISPATCH_TABLE": "mining",
    "KMLS_SPARSE_LONG_BASKET": "mining",
    "KMLS_HBM_BUDGET_BYTES": "mining",
    "KMLS_SHARDED_IMPL": "mining",
    "KMLS_PRUNE_VOCAB_THRESHOLD": "mining",
    "KMLS_WRITE_TENSOR_ARTIFACT": "mining",
    "KMLS_WRITE_MANIFEST": "mining",
    "KMLS_REFERENCE_RACE_COMPAT": "mining",
    "KMLS_NATIVE_PAIR_COUNTS": "mining",
    "KMLS_NATIVE_PAIR_METHOD": "mining",
    "KMLS_NATIVE_THREADS": "mining",
    "KMLS_POPCOUNT_VARIANT": "mining",
    "KMLS_POPCOUNT_SWAR": "mining",
    "KMLS_POPCOUNT_TILE_I": "mining",
    "KMLS_POPCOUNT_TILE_J": "mining",
    "KMLS_POPCOUNT_WORD_CHUNK": "mining",
    # jax.profiler trace dumps: the mining PhaseTimer sessions AND the
    # serving /debug/profile?seconds=N capture endpoint (ISSUE 12) —
    # unset (the default) disables both, so production pods can never
    # be profiled by accident
    "KMLS_PROFILE_DIR": "both",
    # --- mining: ALS embedding phase (second model family) ---
    "KMLS_EMBED_ENABLED": "mining",
    "KMLS_ALS_RANK": "mining",
    "KMLS_ALS_ITERS": "mining",
    "KMLS_ALS_REG": "mining",
    # sparse ALS storage (ISSUE 13): auto = compressed interaction matrix
    # exactly when the dense one busts the HBM guard; always/never pin it
    "KMLS_ALS_SPARSE": "mining",
    # --- mining: telemetry (ISSUE 9) ---
    # write pickles/job_metrics.prom (textfile-exporter format) as phases
    # complete, so a fleet's Prometheus sees mining progress
    "KMLS_JOB_METRICS": "mining",
    # --- mining: preemption-proofing / multi-host ---
    "KMLS_CKPT_ENABLED": "mining",
    "KMLS_CKPT_DIR": "mining",
    "KMLS_CKPT_QUARANTINE_AFTER": "mining",
    "KMLS_LEASE_ENABLED": "mining",
    "KMLS_LEASE_TTL_S": "mining",
    "KMLS_LEASE_HEARTBEAT_S": "mining",
    # --- mining: storage gray-failure spine (ISSUE 19) ---
    # ENOSPC ladder floor: publication preflight requires
    # max(last-manifest bytes, this) free on the artifact volume,
    # reclaims (quarantine + orphaned temp files) when short, then
    # exits resumable (75) rather than starting a write it can't finish
    "KMLS_DISK_MIN_FREE_BYTES": "mining",
    # transient-EIO retry ladder for artifact-plane writes: attempt
    # count and exponential-backoff base (ENOSPC and fsync failures
    # never retry — see io/artifacts.py)
    "KMLS_IO_RETRIES": "mining",
    "KMLS_IO_RETRY_BASE_MS": "mining",
    # lease heartbeat self-fence: a heartbeat write stalling past this
    # fraction of the TTL means the writer can't prove it still holds
    # the lease (hung mount) — it marks itself lost and aborts resumable
    "KMLS_LEASE_STALL_FRACTION": "mining",
    "KMLS_RANK_TIMEOUT_S": "mining",
    "KMLS_RANK_HEARTBEAT_S": "mining",
    "KMLS_COLLECTIVE_TIMEOUT_S": "mining",
    "KMLS_COORDINATOR_ADDRESS": "mining",
    "KMLS_NUM_PROCESSES": "mining",
    "KMLS_PROCESS_ID": "mining",
    # --- mining: continuous freshness (ISSUE 10) ---
    # cap on the delta chain length before the pipeline forces a full
    # re-mine (accumulated patch cost + chain-replay cost at cold start)
    "KMLS_DELTA_MAX_CHAIN": "mining",
    # --- mining: quality loop (ISSUE 14) ---
    # snapshotting compactor: fold a delta chain of this length into a
    # new base bundle WITHOUT a full re-mine (0 = disabled; keep below
    # KMLS_DELTA_MAX_CHAIN so compaction fires before the hard cap)
    "KMLS_DELTA_COMPACT_AFTER": "mining",
    # offline ranking evaluation (quality/eval.py): run the optional
    # checkpointed `eval` phase after `embed` — held-out basket
    # completion scored through the production kernels, published as
    # quality.report.json via the manifest + lease path
    "KMLS_EVAL_ENABLED": "mining",
    # leave-n-out per playlist, recall@k depth, and the deterministic
    # cap on evaluated playlists (bounds eval cost at scale; 0 = all)
    "KMLS_EVAL_HOLDOUT_N": "mining",
    "KMLS_EVAL_K": "mining",
    "KMLS_EVAL_MAX_PLAYLISTS": "mining",
    # --- both workloads ---
    "KMLS_NATIVE": "both",
    # continuous freshness (ISSUE 10): mining publishes incremental
    # delta-<seq>.bundle artifacts between full re-mines; serving applies
    # them in place (engine.apply_pending_deltas) with selective cache
    # invalidation instead of a full reload
    "KMLS_DELTA_ENABLED": "both",
    # model layout: replicated per-device tensors vs vocab-sharded across
    # the mesh — read by the serving engine (rule/embedding tensors) and
    # the mining dispatch (one-hot / support counting / ALS half-sweep)
    "KMLS_MODEL_LAYOUT": "both",
    "KMLS_DEVICE_BUDGET_BYTES": "both",
    # --- bench / sweep / dev harness ---
    "KMLS_BENCH_CPU": "tool",
    "KMLS_BENCH_DEADLINE_S": "tool",
    "KMLS_BENCH_SIDECAR": "tool",
    "KMLS_BENCH_STATE": "tool",
    "KMLS_BENCH_STATE_MAX_AGE_S": "tool",
    "KMLS_BENCH_STARTUP_GRACE_S": "tool",
    "KMLS_BENCH_PROBE_TIMEOUT_S": "tool",
    "KMLS_BENCH_REPLAY_QPS": "tool",
    "KMLS_BENCH_REPLAY_REQUESTS": "tool",
    "KMLS_BENCH_REPLAY_RUNS": "tool",
    "KMLS_BENCH_REPLAY_WARMUP": "tool",
    "KMLS_BENCH_REPLAY_WORKERS": "tool",
    "KMLS_BENCH_REPLAY_QUEUE": "tool",
    "KMLS_BENCH_REPLAY10K_QPS": "tool",
    "KMLS_BENCH_REPLAY10K_REQUESTS": "tool",
    "KMLS_BENCH_REPLAY10K_ZIPF_S": "tool",
    "KMLS_BENCH_CHAOS_QPS": "tool",
    "KMLS_BENCH_CHAOS_REQUESTS": "tool",
    "KMLS_BENCH_CHAOS_ZIPF_S": "tool",
    "KMLS_BENCH_RESUME_PHASE": "tool",
    # traffic-shape replay (ISSUE 8): shape selector for the replay CLI
    # and the loadshape bench bracket's base rate / volume / burst factor
    "KMLS_REPLAY_SHAPE": "tool",
    "KMLS_BENCH_LOADSHAPE_QPS": "tool",
    "KMLS_BENCH_LOADSHAPE_REQUESTS": "tool",
    "KMLS_BENCH_LOADSHAPE_BURST": "tool",
    # tracing-overhead micro-phase (ISSUE 9): base rate / volume for the
    # sampled-vs-disabled p99 comparison bracket
    "KMLS_BENCH_TRACE_QPS": "tool",
    "KMLS_BENCH_TRACE_REQUESTS": "tool",
    # cost-attribution phase (ISSUE 12): rate / volume for the
    # serve-kernel MFU + roofline + compiles==0 bracket
    "KMLS_BENCH_COSTATTRIB_QPS": "tool",
    "KMLS_BENCH_COSTATTRIB_REQUESTS": "tool",
    # continuous-freshness phase (ISSUE 10): request rate/volume for the
    # mid-delta zero-5xx replay bracket
    "KMLS_BENCH_FRESHNESS_QPS": "tool",
    "KMLS_BENCH_FRESHNESS_REQUESTS": "tool",
    # fleet cache-routing phase (ISSUE 15): aggregate rate / volume /
    # replica count / per-replica LRU entries for the multi-process
    # routed-vs-independent bracket (the CI smoke shrinks all four)
    "KMLS_BENCH_FLEET_QPS": "tool",
    "KMLS_BENCH_FLEET_REQUESTS": "tool",
    "KMLS_BENCH_FLEET_REPLICAS": "tool",
    "KMLS_BENCH_FLEET_CACHE": "tool",
    # serve-mesh phase (ISSUE 16): rate / volume for the 2-process-gang
    # vs single-process-sharded identity + chaos bracket (CI smoke
    # shrinks both)
    "KMLS_BENCH_MESHSERVE_QPS": "tool",
    "KMLS_BENCH_MESHSERVE_REQUESTS": "tool",
    # gray-failure phase (ISSUE 18): rate / volume for the slowpeer
    # bracket's hedged-vs-control legs (CI smoke shrinks both)
    "KMLS_BENCH_SLOWPEER_QPS": "tool",
    "KMLS_BENCH_SLOWPEER_REQUESTS": "tool",
    # storage gray-failure phase (ISSUE 19): rate / volume for the
    # graystore bracket's stall-injected artifact-plane replay legs
    # (CI smoke shrinks both)
    "KMLS_BENCH_GRAYSTORE_QPS": "tool",
    "KMLS_BENCH_GRAYSTORE_REQUESTS": "tool",
    # quality-loop phase (ISSUE 14): membership-row volume of the eval/
    # compaction bracket's synthetic workload (CI smoke shrinks it)
    "KMLS_BENCH_QUALITY_ROWS": "tool",
    # sparsity-adaptive phase (ISSUE 13): the ≥99%-sparse headline
    # workload's shape (CI smoke shrinks it)
    "KMLS_BENCH_SPARSE_PLAYLISTS": "tool",
    "KMLS_BENCH_SPARSE_TRACKS": "tool",
    "KMLS_BENCH_SPARSE_ROWS": "tool",
    "KMLS_SWEEP_START": "tool",
    "KMLS_SWEEP_STOP": "tool",
    "KMLS_SWEEP_STEP": "tool",
    # --- fault injection (faults.py switchboard) ---
    "KMLS_FAULT_RELOAD_FAIL": "fault",
    "KMLS_FAULT_REPLICA_FAIL": "fault",
    "KMLS_FAULT_REPLICA_DELAY_MS": "fault",
    "KMLS_FAULT_MINE_CRASH_PHASE": "fault",
    "KMLS_FAULT_CKPT_CORRUPT": "fault",
    "KMLS_FAULT_RANK_DEAD": "fault",
    "KMLS_FAULT_EMBED_CORRUPT": "fault",
    "KMLS_FAULT_DELTA_CORRUPT": "fault",
    "KMLS_FAULT_MESH_PEER_DELAY_MS": "fault",
    "KMLS_FAULT_FLEET_PEER_DELAY_MS": "fault",
    # storage plane (ISSUE 19): path-scoped faults consumed inside
    # io/artifacts.py's single writer/reader (faults.take_io)
    "KMLS_FAULT_IO_WRITE": "fault",
    "KMLS_FAULT_IO_WRITE_STALL_MS": "fault",
    "KMLS_FAULT_IO_READ": "fault",
    "KMLS_FAULT_IO_READ_STALL_MS": "fault",
    "KMLS_FAULT_IO_FSYNC": "fault",
}

# Columns dropped from the raw CSV before any processing
# (reference: machine-learning/main.py:42).
DROP_COLUMNS = ("duration_ms",)

# First dataset index in the rotation scheme (reference: machine-learning/main.py:46).
BASE_INDEX = 1


@dataclasses.dataclass(frozen=True)
class MiningConfig:
    """Batch mining job config (reference: machine-learning/main.py:17-49,
    kubernetes/job.yaml:24-40)."""

    base_dir: str = "./api-data"
    datasets_dir: str = ""
    regex_filename: str = "2023_spotify_ds*.csv"
    min_support: float = 0.05
    pickles_folder: str = "pickles"
    recommendations_file: str = "recommendations.pickle"
    best_tracks_file: str = "best_tracks.pickle"
    data_invalidation_file: str = "last_execution.txt"
    top_tracks_save_percentile: float = 0.03
    artists_mapping_file: str = "artistsMapping.pickle"
    repeated_tracks_file: str = "trackNameToRepeatedUris.pickle"
    track_info_file: str = "trackIdsToInfo.pickle"
    datasets_list_file: str = "datasets_list.txt"
    dataset_history_file: str = "dataset_history.csv"
    sample_ratio: float = 1.0

    # --- TPU-rebuild knobs (not in the reference) ---
    # Max itemset length the miner enumerates. 2 reproduces the reference
    # fast path's OUTPUT exactly (see ops/support.py dominance note); 3/4 add
    # the itemset census + true-confidence rules.
    max_itemset_len: int = 2
    # Padded per-antecedent rule-row capacity (consequents kept per song).
    k_max_consequents: int = 256
    # "support" = reference fast-path semantics (itemset support stored as the
    # confidence, symmetric rules — machine-learning/main.py:284-296);
    # "confidence" = the dormant slow path's true asymmetric confidence
    # (machine-learning/main.py:224-260).
    confidence_mode: str = "support"
    # Minimum confidence when confidence_mode == "confidence"
    # (reference slow path hardcodes 0.04 — machine-learning/main.py:226-227).
    min_confidence: float = 0.04
    # Device-mesh shape for sharded mining: "auto", "1x1", "dpxtp" e.g.
    # "4x1", or "hybrid"/"hybrid:tpN" (DCN×ICI layout for multi-host — tp
    # pinned to intra-host devices). "auto" picks hybrid automatically when
    # the multi-host runtime is active (KMLS_COORDINATOR_ADDRESS set).
    mesh_shape: str = "auto"
    # When to use the bit-packed popcount path instead of the dense int8
    # MXU matmul (single-device AND sharded: over a mesh this selects the
    # dp-sharded popcount slabs). "auto" (default) dispatches on estimated
    # HBM footprint: dense whenever the pruned one-hot + count matrix fit
    # ``hbm_budget_bytes`` — the MXU matmul beats the VPU popcount kernel
    # by an order of magnitude whenever it fits, so element count alone is
    # the wrong dispatch key (r03: 1M×100k pruned to 5k items is 5 GiB
    # dense — easily resident — yet an element threshold routed it to the
    # slow kernel). An int forces the old explicit element threshold;
    # None disables bitpack entirely.
    bitpack_threshold_elems: int | str | None = "auto"
    # HBM the mining job may plan against for the auto dispatch. Default
    # leaves ~4 GiB of a v5e's 16 GiB for XLA workspace/fusion copies.
    hbm_budget_bytes: int = 12 * (1 << 30)
    # Sparsity-adaptive dispatch (mining/dispatch.py): "auto" (default)
    # resolves dense/bitpack/sparse from the MEASURED per-backend lookup
    # table (bench-banked; legacy heuristic when no cell matches);
    # "dense"/"bitpack"/"sparse" pin a family; any other spelling fails
    # SAFE to auto with a loud warning.
    count_path: str = "auto"
    # Alternative measured dispatch table (JSON; see
    # mining/dispatch_table.json for the banked shape). Empty = the
    # packaged bench-banked table.
    dispatch_table: str = ""
    # Baskets longer than this leave the sparse path's CSR pair
    # expansion for the gathered bitpacked/dense sub-count (the
    # quadratic-per-basket guard). 0 = the ops/sparse.py default (256).
    sparse_long_basket: int = 0
    # Sharded dense pair-count implementation: "gspmd" (annotate + let XLA
    # partition), "allgather" (explicit shard_map), "ring" (ppermute
    # neighbor exchange; lowest peak memory).
    sharded_impl: str = "gspmd"
    # Model layout (parallel/layout.py — shared with the serving side):
    # "replicated" keeps the legacy single-device-shaped mining compute;
    # "sharded" lays the one-hot, the support counts, the rule emission,
    # and the ALS item half-sweep out along the vocab axis of the mesh
    # (a 1xN vocab-major mesh is built automatically when none is given),
    # so the encode/mine phases accept inputs whose dense replicated
    # formulation cannot fit one device; "auto" engages the sharded path
    # only when the configured mesh already spans the vocab axis.
    model_layout: str = "replicated"
    # Per-device byte budget the LAYOUT decision measures against (the
    # serving engine's auto trigger; distinct from hbm_budget_bytes,
    # which routes the bitpack-vs-dense COUNTING dispatch). 0 = fall
    # back to hbm_budget_bytes.
    device_budget_bytes: int = 0
    # Above this vocabulary size, prune infrequent items (exact, by the
    # Apriori property) before pair counting — the path that makes the
    # 1M-track configs feasible (a dense 1M x 1M count matrix is 4 TB).
    # Low by default: pruning is exact and pays at EVERY scale — it shrinks
    # the matmul, the emission, and the rule-tensor fetch, e.g. ds2's 2171
    # rows -> its 429 frequent items. The threshold only spares tiny vocabularies the
    # (trivial) host bincount.
    prune_vocab_threshold: int = 512
    # Write the tensor-native artifact (rules npz) alongside the pickles.
    write_tensor_artifact: bool = True
    # Write the integrity manifest (artifacts.manifest.json: size + sha256
    # per artifact) after each artifact set — the serving engine validates
    # against it before publishing a bundle, so a torn/corrupt artifact is
    # caught before it can poison a reload.
    write_manifest: bool = True
    # On a CPU backend (no TPU reachable), count pair supports with the
    # native bit-packed POPCNT kernel (native/kmls_popcount.cpp) instead of
    # XLA:CPU's int8 matmul — exact, ~40x faster on the dominant phase.
    # Ignored on TPU; falls back automatically when the .so can't build.
    native_cpu_pair_counts: bool = True

    # --- second model family: ALS embedding phase (mining/als.py) ---
    # Optional `embed` pipeline phase after `rules`: train ALS item
    # embeddings over the playlist×track matrix and publish embeddings.npz
    # through the same manifest + lease-fenced path as the rule tensors.
    # Off by default — the reference pipeline has no embedding model, and
    # the serving side degrades to rules-only when the artifact is absent.
    embed_enabled: bool = False
    # Factorization rank (embedding dimension).
    als_rank: int = 32
    # Alternating sweeps (users then items per sweep).
    als_iters: int = 8
    # L2 regularization λ on both factor matrices.
    als_reg: float = 0.1
    # Interaction-matrix storage for the ALS half-sweeps (mining/als.py):
    # "auto" = dense while the dense f32 matrix fits the HBM guard,
    # compressed (indices-only, nnz-proportional) exactly when it does
    # not — the case that previously SKIPPED the embed phase; "always" /
    # "never" pin it. Sparse factors are float-different from dense ones
    # (accumulation order), so this knob joins the checkpoint
    # fingerprint like model_layout did.
    als_sparse: str = "auto"

    # --- continuous freshness (ISSUE 10) ---
    # Incremental delta mining: after a full publication the pipeline
    # saves a freshness base state (encode membership + published rule
    # tensors + dataset byte-prefix fingerprint); a later run finds the
    # dataset grew append-only and publishes a delta-<seq>.bundle (changed
    # rule rows + tombstones, base-sha256-bound) through the lease path
    # instead of re-mining everything. Off by default — the reference has
    # no incremental posture, and serving ignores chains unless its own
    # KMLS_DELTA_ENABLED is set.
    delta_enabled: bool = False
    # Chain cap: at this many unapplied-on-top-of-base deltas the next
    # run full-re-mines instead (bounds cold-start chain replay and
    # accumulated patch drift surface). 0 = unlimited.
    delta_max_chain: int = 16

    # --- quality loop (ISSUE 14) ---
    # Snapshotting compactor (quality/lifecycle.py): once the delta
    # chain reaches this length, fold base ∘ chain into a new base
    # bundle WITHOUT a full re-mine — the canonical delta application
    # makes the fold bit-identical to the chain it replaces. 0 disables
    # (KMLS_DELTA_MAX_CHAIN stays the hard full-re-mine backstop; keep
    # this below it so the cheap snapshot fires first).
    delta_compact_after: int = 0
    # Offline ranking evaluation (quality/eval.py): run the optional
    # checkpointed `eval` phase after `embed` — deterministic held-out
    # basket-completion recall@k / MRR / coverage per serving mode
    # through the production kernels, plus the blend-weight sweep —
    # published as quality.report.json through the manifest+lease path.
    # Off by default: eval re-trains both model families on the train
    # split, roughly doubling job compute.
    eval_enabled: bool = False
    # Tracks held out per playlist (playlists shorter than holdout+2
    # are not evaluated — something must remain to seed with).
    eval_holdout_n: int = 1
    # recall@k depth — matches serving's K_BEST_TRACKS default.
    eval_k: int = 10
    # Deterministic cap on evaluated playlists (hash-selected, not a
    # prefix slice); bounds eval cost at scale. 0 = evaluate all.
    eval_max_playlists: int = 2048

    # --- mining telemetry (ISSUE 9) ---
    # Write per-phase progress/duration/bytes counters to
    # pickles/job_metrics.prom (node-exporter textfile-collector format)
    # through the atomic-write path, rewritten as each phase completes —
    # a preempted job leaves the telemetry of the phases it DID finish,
    # and a resumed job reports the compute it skipped.
    job_metrics: bool = True

    # --- preemption-proofing knobs (checkpoint / lease / watchdog) ---
    # Phase-level checkpointing: after each expensive phase (encode, mine,
    # rules) the writer rank persists an atomic, sha256-manifested
    # checkpoint keyed by a config+dataset fingerprint, so a preempted/
    # evicted job resumes from the last completed phase instead of
    # recomputing everything. Retired automatically after a successful
    # publication (the next rotation run starts fresh).
    checkpoint_enabled: bool = True
    # Checkpoint directory; empty = <base_dir>/mining_checkpoint (on the
    # PVC, so a replacement pod sees its predecessor's progress).
    checkpoint_dir: str = ""
    # A checkpoint whose bytes verify but fail to UNPICKLE this many
    # consecutive loads is quarantined (pickles-style quarantine dir) and
    # recomputed — one torn read must not cost a good checkpoint, but a
    # poison one must not wedge every restart. 0 disables quarantining.
    checkpoint_quarantine_after: int = 2
    # Lease-fenced publication: the rank-0 writer takes a heartbeat lease
    # (pickles/publish.lease.json) with a monotonically-increasing fencing
    # token before mining and re-validates it before every publication
    # step — a zombie job superseded by an ArgoCD Replace cannot tear
    # artifacts a newer run already published.
    lease_enabled: bool = True
    # A lease whose heartbeat is older than this is expired (its writer
    # died) and can be taken over by the next job.
    lease_ttl_s: float = 60.0
    # Heartbeat period; 0 = ttl/3.
    lease_heartbeat_interval_s: float = 0.0
    # Dead-rank watchdog (multi-host jobs only): every rank heartbeats a
    # shared file every rank_heartbeat_interval_s; a peer silent for
    # rank_timeout_s turns the would-be forever-hang into a bounded-time
    # abort with the resumable EXIT_RANK_DEAD code (mining/job.py).
    # 0 disables.
    rank_timeout_s: float = 300.0
    rank_heartbeat_interval_s: float = 5.0
    # Deadline for one guarded COLLECTIVE section (the mine). Separate
    # from — and much larger than — rank_timeout_s: the guard brackets
    # real compute, and a legitimately long mine must not read as a hang
    # (a shared timeout would livelock every restart into the same
    # too-long recompute). Keep below the Job's activeDeadlineSeconds;
    # 0 = 6 × rank_timeout_s.
    collective_timeout_s: float = 1800.0
    # Storage gray-failure spine (ISSUE 19): operator floor for the
    # publication free-space preflight — publication requires
    # max(estimated artifact bytes, this) free, reclaims, then exits
    # resumable. 0 disables the preflight.
    disk_min_free_bytes: int = 64 * (1 << 20)
    # Lease heartbeat self-fence threshold as a fraction of the TTL
    # (0 disables self-fencing).
    lease_stall_fraction: float = 0.5

    @property
    def pickles_dir(self) -> str:
        return os.path.join(self.base_dir, self.pickles_folder)

    @property
    def checkpoint_path(self) -> str:
        return self.checkpoint_dir or os.path.join(
            self.base_dir, "mining_checkpoint"
        )

    @staticmethod
    def from_env(dotenv_path: str | None = ".env") -> "MiningConfig":
        if dotenv_path:
            load_dotenv(dotenv_path)
        base_dir = os.getenv("BASE_DIR", "./api-data")
        return MiningConfig(
            base_dir=base_dir,
            datasets_dir=os.getenv("DATASETS_DIR", os.path.join(base_dir, "datasets")),
            regex_filename=os.getenv("REGEX_FILENAME", "2023_spotify_ds*.csv"),
            min_support=_getenv_float("MIN_SUPPORT", 0.05),
            pickles_folder=os.getenv("PICKLES_FOLDER", "pickles"),
            recommendations_file=os.getenv("RECOMMENDATIONS_FILE", "recommendations.pickle"),
            best_tracks_file=os.getenv("BEST_TRACKS_FILE", "best_tracks.pickle"),
            data_invalidation_file=os.getenv("DATA_INVALIDATION_FILE", "last_execution.txt"),
            top_tracks_save_percentile=_getenv_float("TOP_TRACKS_SAVE_PERCENTILE", 0.03),
            artists_mapping_file=os.getenv("ARTISTS_MAPPING_FILE", "artistsMapping.pickle"),
            repeated_tracks_file=os.getenv("REPEATED_TRACKS_FILE", "trackNameToRepeatedUris.pickle"),
            track_info_file=os.getenv("TRACK_INFO_FILE", "trackIdsToInfo.pickle"),
            datasets_list_file=os.getenv("DATASETS_LIST_FILE", "datasets_list.txt"),
            dataset_history_file=os.getenv("DATASET_HISTORY_FILE", "dataset_history.csv"),
            sample_ratio=_getenv_float("SAMPLE_RATIO", 1.0),
            max_itemset_len=_getenv_int("KMLS_MAX_ITEMSET_LEN", 2),
            k_max_consequents=_getenv_int("KMLS_K_MAX_CONSEQUENTS", 256),
            confidence_mode=os.getenv("KMLS_CONFIDENCE_MODE", "support"),
            min_confidence=_getenv_float("KMLS_MIN_CONFIDENCE", 0.04),
            mesh_shape=os.getenv("KMLS_MESH_SHAPE", "auto"),
            bitpack_threshold_elems=_getenv_bitpack_threshold(),
            count_path=os.getenv("KMLS_COUNT_PATH", "auto"),
            dispatch_table=os.getenv("KMLS_DISPATCH_TABLE", ""),
            sparse_long_basket=_getenv_int("KMLS_SPARSE_LONG_BASKET", 0),
            hbm_budget_bytes=_getenv_int("KMLS_HBM_BUDGET_BYTES", 12 * (1 << 30)),
            sharded_impl=os.getenv("KMLS_SHARDED_IMPL", "gspmd"),
            model_layout=_getenv_model_layout(),
            device_budget_bytes=_getenv_int("KMLS_DEVICE_BUDGET_BYTES", 0),
            prune_vocab_threshold=_getenv_int("KMLS_PRUNE_VOCAB_THRESHOLD", 512),
            write_tensor_artifact=_getenv_bool("KMLS_WRITE_TENSOR_ARTIFACT", True),
            write_manifest=_getenv_bool("KMLS_WRITE_MANIFEST", True),
            native_cpu_pair_counts=_getenv_bool("KMLS_NATIVE_PAIR_COUNTS", True),
            embed_enabled=_getenv_bool("KMLS_EMBED_ENABLED", False),
            als_rank=_getenv_int("KMLS_ALS_RANK", 32),
            als_iters=_getenv_int("KMLS_ALS_ITERS", 8),
            als_reg=_getenv_float("KMLS_ALS_REG", 0.1),
            als_sparse=os.getenv("KMLS_ALS_SPARSE", "auto"),
            delta_enabled=_getenv_bool("KMLS_DELTA_ENABLED", False),
            delta_max_chain=_getenv_int("KMLS_DELTA_MAX_CHAIN", 16),
            delta_compact_after=_getenv_int("KMLS_DELTA_COMPACT_AFTER", 0),
            eval_enabled=_getenv_bool("KMLS_EVAL_ENABLED", False),
            eval_holdout_n=_getenv_int("KMLS_EVAL_HOLDOUT_N", 1),
            eval_k=_getenv_int("KMLS_EVAL_K", 10),
            eval_max_playlists=_getenv_int("KMLS_EVAL_MAX_PLAYLISTS", 2048),
            job_metrics=_getenv_bool("KMLS_JOB_METRICS", True),
            checkpoint_enabled=_getenv_bool("KMLS_CKPT_ENABLED", True),
            checkpoint_dir=os.getenv("KMLS_CKPT_DIR", ""),
            checkpoint_quarantine_after=_getenv_int(
                "KMLS_CKPT_QUARANTINE_AFTER", 2
            ),
            lease_enabled=_getenv_bool("KMLS_LEASE_ENABLED", True),
            lease_ttl_s=_getenv_float("KMLS_LEASE_TTL_S", 60.0),
            lease_heartbeat_interval_s=_getenv_float(
                "KMLS_LEASE_HEARTBEAT_S", 0.0
            ),
            rank_timeout_s=_getenv_float("KMLS_RANK_TIMEOUT_S", 300.0),
            rank_heartbeat_interval_s=_getenv_float(
                "KMLS_RANK_HEARTBEAT_S", 5.0
            ),
            collective_timeout_s=_getenv_float(
                "KMLS_COLLECTIVE_TIMEOUT_S", 1800.0
            ),
            disk_min_free_bytes=_getenv_int(
                "KMLS_DISK_MIN_FREE_BYTES", 64 * (1 << 20)
            ),
            lease_stall_fraction=_getenv_float(
                "KMLS_LEASE_STALL_FRACTION", 0.5
            ),
        )


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Online API config (reference: rest_api/app/main.py:31-50,
    kubernetes/deployment.yaml:33-53)."""

    version: str = "V1.1"
    base_dir: str = "./api-data/"
    pickle_dir: str = "pickles/"
    app_path_from_root: str = "/app"
    recommendations_file: str = "recommendations.pickle"
    best_tracks_file: str = "best_tracks.pickle"
    data_invalidation_file: str = "last_execution.txt"
    k_best_tracks: int = 10
    polling_wait_in_minutes: float = 5.0

    # --- TPU-rebuild knobs ---
    port: int = 80
    # Max seed songs per request the jitted kernel is specialized for;
    # requests are bucketed to powers of two up to this bound.
    max_seed_tracks: int = 128
    # Micro-batching window for aggregating concurrent requests into one
    # device call (milliseconds); 0 disables batching. With the adaptive
    # controller on, this is the window CEILING — the controller sizes the
    # actual wait from the observed arrival rate and the shed budget.
    batch_window_ms: float = 2.0
    batch_max_size: int = 32
    # Adaptive deadline-aware window: size the collection wait from the
    # arrival-gap EWMA (time to fill the batch at the current rate) instead
    # of always burning the full fixed window. Off = fixed window.
    batch_adaptive_window: bool = True
    # Floor for the adaptive window (milliseconds). Not lower: closed-loop
    # clients arrive in bursts (a completed batch releases its waiters at
    # once), and a near-zero floor splits each wave into undersized
    # batches, each paying the fixed per-dispatch cost.
    batch_window_min_ms: float = 1.0
    # Load shedding: when the EFFECTIVE queue wait for a new request
    # (max of the instantaneous projection and the measured queue-wait
    # EWMA) exceeds this budget (milliseconds), the request is shed with
    # HTTP 429 + Retry-After instead of rotting in the queue
    # (backpressure made visible, not a silent p99 cliff). 0 disables
    # admission control entirely.
    shed_queue_budget_ms: float = 250.0
    # Retry-After hint (seconds) returned with a 429 shed — the BASE
    # value; the controller jitters it (see shed_retry_jitter).
    shed_retry_after_s: float = 1.0
    # Adaptive admission ladder (ISSUE 8): pressure = effective queue
    # wait / budget. Below soft_ratio every request is admitted at full
    # quality; between soft_ratio and 1.0 a rising fraction of cache
    # MISSES degrades to the popularity fallback (200 + X-KMLS-Degraded:
    # overload — hits are untouched); between 1.0 and hard_ratio a
    # rising fraction sheds (429) and the rest degrades; past hard_ratio
    # everything sheds. soft_ratio=1 + hard_ratio=1 restores the legacy
    # cliff-at-the-budget behavior.
    shed_soft_ratio: float = 0.6
    shed_hard_ratio: float = 1.5
    # Bounded Retry-After jitter: the 429 header carries a value uniform
    # on base*(1 ± this fraction). A constant Retry-After re-synchronizes
    # every shed client into one retry wave exactly one hint later — the
    # storm the shed was supposed to absorb. 0 restores the constant.
    shed_retry_jitter: float = 0.5
    # Device-call pipeline depth PER REPLICA: batches dispatched but not yet
    # completed. >1 overlaps the next batch's dispatch and host staging
    # with the previous batch's device time and result transfer. The
    # aggregate pipeline bound is this times the number of serving
    # replicas.
    batch_max_inflight: int = 4
    # Serving replicas, one per local device: 0 = auto (every local device
    # on accelerator backends; 1 on CPU, where virtual devices share the
    # same host cores and extra replicas only multiply warmup
    # compiles). N > 0 pins min(N, local device count) replicas — e.g.
    # KMLS_SERVE_DEVICES=8 on an 8-virtual-device CPU host exercises the
    # full data-parallel dispatch tier without hardware.
    serve_devices: int = 0
    # Model layout for the published serving tensors (parallel/layout.py,
    # shared with the mining side): "replicated" = one full rule-tensor
    # copy per serving device (PR 2's data-parallel replicas, the
    # default); "sharded" = ONE logical model vocab-sharded across every
    # serving device via NamedSharding — per-device HBM holds V/S rule
    # rows, so the servable catalog scales with the mesh; "auto" measures
    # the loaded tensor bytes against device_budget_bytes and shards only
    # when a replica would not fit. Sharded layout serves through the
    # jitted sharded kernel and presents as one replica to the
    # dispatcher.
    model_layout: str = "replicated"
    # Per-device byte budget the auto layout measures rule+confidence
    # tensor bytes against. 0 disables the auto trigger (auto then always
    # resolves to replicated).
    device_budget_bytes: int = 12 * (1 << 30)
    # Epoch-keyed recommendation cache in front of the batcher: answers are
    # keyed by (bundle epoch, canonicalized seed set), so a bundle hot-swap
    # invalidates the whole cache for free (the epoch moves, old keys can
    # never match again). 0 entries — or KMLS_CACHE_ENABLED=0 — disables.
    cache_enabled: bool = True
    cache_max_entries: int = 8192
    # Prefer the tensor-native npz artifact over the pickle when present.
    prefer_tensor_artifact: bool = True

    # --- robustness knobs (fault-tolerance layer) ---
    # Validate artifacts against the mining job's integrity manifest
    # (artifacts.manifest.json) before publishing a bundle; a mismatched
    # best/recommendations pickle aborts the reload (last-good keeps
    # serving), a mismatched npz falls back to the pickle. No manifest on
    # the PVC (older miner, or the reference's) = no validation.
    verify_manifest: bool = True
    # Move an artifact that keeps failing to load/verify into
    # pickles/quarantine/ after this many CONSECUTIVE failed reloads (a
    # single mid-update mismatch resolves itself next poll and must not
    # cost a good file). 0 disables quarantining.
    quarantine_after_failures: int = 2
    # Exponential backoff between FAILED reload attempts (corrupt
    # artifacts, not merely-missing ones): base doubles per consecutive
    # failure up to max. Keeps a poison artifact from turning the poller
    # into a checksum-hashing busy loop; the invalidation token is never
    # consumed, so the retry ladder always ends in a reload of whatever
    # the miner writes next.
    reload_backoff_base_s: float = 0.5
    reload_backoff_max_s: float = 30.0
    # Storage gray-failure spine (ISSUE 19): deadline on reload-path
    # artifact reads — a hung NFS read fails the reload into the normal
    # backoff ladder above (last-good keeps serving) instead of wedging
    # the reload thread forever. 0 disables the deadline.
    io_read_deadline_s: float = 0.0
    # Per-replica consecutive-failure circuit breaker in the batchers:
    # after this many consecutive batch failures a replica is EJECTED from
    # the least-loaded dispatcher (its in-flight requests re-dispatch to
    # healthy replicas) and probed for re-admission every
    # replica_probe_interval_s. 0 disables ejection.
    replica_eject_threshold: int = 3
    replica_probe_interval_s: float = 5.0
    # Bounded re-dispatch: how many times one request may be re-queued
    # after a batch failure before the failure propagates (and the HTTP
    # layer degrades it). Keep >= replica_eject_threshold: a sick replica
    # fails at most eject_threshold batches before the breaker takes it
    # out, so a request that can retry that many times is GUARANTEED to
    # outlive any single-replica failure burst.
    redispatch_max_retries: int = 3
    # Per-request deadline budget (milliseconds), propagated cache →
    # batcher → device: on exhaustion the request degrades to the
    # popularity-fallback answer with an X-KMLS-Degraded header instead
    # of queueing forever or 500ing. 0 disables deadlines.
    request_deadline_ms: float = 0.0
    # Latency budget for the degraded popularity-fallback answer itself:
    # past the request deadline the sampler is skipped for a head slice
    # of the popularity ranking (cheapest possible answer).
    fallback_budget_ms: float = 50.0

    # --- continuous freshness (ISSUE 10) ---
    # Apply delta bundles published between full re-mines: the poll loop
    # checks the delta chain alongside the invalidation token and patches
    # the live per-device tensors in place (epoch advances to a
    # (base, delta_seq) pair; the answer cache invalidates selectively).
    # Off by default; a full token rewrite always behaves as before.
    delta_enabled: bool = False
    # Rendezvous-hash request affinity (freshness/ring.py): when on, the
    # app counts ring-local vs ring-remote requests over the peer set so
    # operators can measure the affinity win before routing on it.
    cache_affinity: bool = False
    # Comma-separated replica identities (headless-Service pod DNS names);
    # this replica's own identity (default: hostname) is added if absent.
    cache_affinity_peers: str = ""
    cache_affinity_self: str = ""

    # --- fleet cache routing (ISSUE 15) ---
    # Stable replica identity for the ROUTING tier (the acted-on twin of
    # the measurement knobs above): a non-empty fleet_peers arms
    # owner-aware serving — the app builds the canonical rendezvous ring
    # over these identities, answers every request locally (mis-routed
    # traffic degrades gracefully, never fails), stamps
    # X-KMLS-Cache-Owner on answers this replica does not own, and
    # counts non-owned misses as kmls_cache_misrouted_total so routing
    # drift at the ingress/client is observable. Under the StatefulSet
    # recipe (kubernetes/statefulset.yaml) fleet_self is the pod's own
    # stable ordinal name; empty falls back to the hostname, which IS
    # that name in-cluster.
    fleet_self: str = ""
    fleet_peers: str = ""

    # --- pod-spanning serve mesh (ISSUE 16) ---
    # Gang bootstrap mirroring the mining job's KMLS_PROCESS_ID recipe:
    # serve_gang_size > 1 arms the "mesh" layout — engine.load() on each
    # gang member holds only its own vocab slab (rows
    # [rank·slab, (rank+1)·slab)), serves per-slab top-k partials to its
    # peers over the partial-fetch protocol (serving/mesh.py), and
    # merges all slabs' partials exactly like the single-process sharded
    # kernel's all_gather + max-merge — the gang presents ONE logical
    # replica to the dispatcher and ONE ring member to the FleetRouter.
    # coordinator is rank 0's partial-fetch address ("host:port"; the
    # k8s recipe points it at the headless-Service ordinal-0 DNS name,
    # the CPU simulation at 127.0.0.1 with per-rank ports base+rank);
    # rank falls back to the hostname's trailing ordinal (the
    # StatefulSet pod identity), mirroring JOB_COMPLETION_INDEX.
    serve_gang_coordinator: str = ""
    serve_gang_size: int = 1
    serve_gang_rank: int = 0
    serve_gang_port: int = 8477

    # --- gray-failure spine (ISSUE 18) ---
    # Hedged dispatch master switch. False (default) is the proven-
    # zero-cost path: no hedge bookkeeping allocated, the module hedge
    # counters stay pinned at 0, and the PR 8 admission ladder has
    # structurally no hedge input (hedges are client/coordinator-side —
    # they never enter the admission queue as a new class of work).
    hedge_enabled: bool = False
    # Slow-outlier ladder: eject a peer whose EWMA latency exceeds
    # ratio × the healthy-peer median (FleetRouter.mark_latency /
    # MeshCoordinator rank tracking). 0 disables the ladder.
    peer_slow_ratio: float = 0.0
    # Hedge trigger floor (ms): the adaptive per-peer delay — tracked
    # latency ~p95 — never fires earlier than this, so a cold router
    # can't hedge on noise.
    hedge_delay_ms: float = 30.0
    # Amplification bound: a token bucket earns this fraction per
    # primary dispatch and each hedge spends one token — extra
    # dispatches are structurally ≤ this fraction of total. An empty
    # bucket means plain waiting, never an unbounded retry storm.
    hedge_max_frac: float = 0.05

    # --- observability (ISSUE 9): span tracing + runtime health ---
    # Baseline retention probability for OK traces once tracing is on.
    # 0 (default) disables tracing entirely: no trace context, no id
    # generation, no per-request allocation anywhere on the hot path
    # (the SpanRecorder's `began` counter proves it, compile-counter
    # style). With any sample > 0, retention is TAIL-BASED: every shed/
    # degraded/deadline-exceeded/error trace and the slowest-N OK traces
    # are always kept; this knob only rates the representative baseline.
    trace_sample: float = 0.0
    # Ring capacity of retained traces served at GET /debug/traces.
    trace_buffer: int = 512
    # How many slowest-OK traces the tail-based policy always retains.
    trace_slow_n: int = 32
    # Event-loop-lag collector (closes the PR 8 inline-path blind spot):
    # peak-hold decay half-life for the stall estimate exported as
    # kmls_loop_lag_ms and folded into AdmissionController pressure.
    # 0 disables the collector and the pressure fold.
    loop_lag_half_life_s: float = 1.0

    # --- device-truth cost attribution + SLOs (ISSUE 12) ---
    # Per-kernel cost attribution (observability/costmodel.py): fenced
    # device seconds × analytic FLOPs/bytes specs → achieved rates, MFU
    # vs the backend peak, roofline class, live compile counter, and
    # the publish-time memory accounting — all at /metrics. Off = the
    # engine holds no cost model at all (one is-None check per batch;
    # the module observation counter proves zero work, test-pinned).
    costmodel_enabled: bool = True
    # SLO burn rates (observability/slo.py, /debug/slo +
    # kmls_slo_burn_rate): the p99-latency target (snapped up to the
    # nearest histogram bucket boundary), the availability (errors +
    # sheds) and quality (degraded answers) budgets as bad-event
    # fractions, and the fast/slow alerting windows.
    slo_p99_ms: float = 25.0
    slo_error_budget: float = 0.001
    slo_degrade_budget: float = 0.01
    slo_fast_window_s: float = 300.0
    slo_slow_window_s: float = 3600.0

    # --- predictive serving (ISSUE 17, serving/forecast.py) ---
    # Online arrival-rate + request-mix forecaster feeding the three
    # predictive actuators (batch-window pre-widening + shape pre-touch,
    # the bounded HPA-lead term in kmls_utilization, owner-targeted
    # post-delta cache pre-fetch). Off (default) = the app holds no
    # forecaster at all: every call site is one is-None check, and the
    # module observation counter proves zero work (test-pinned, the
    # KMLS_COSTMODEL pattern). A wrong forecast can only over-provision
    # — the admission ladder never reads it, so shedding can never start
    # earlier than reactive.
    forecast_enabled: bool = False
    # How far ahead the rate prediction looks: predicted = level +
    # trend·horizon. Matches the scale-out lead the HPA can actually
    # use (its scaleUp stabilization window is 15 s; the batcher's
    # actuators work at sub-second scale from the same prediction).
    forecast_horizon_s: float = 2.0
    # Width of the arrival-count windows the level/trend EWMAs smooth
    # over; silent windows fold in as zeros so the forecast decays in
    # real time after a burst.
    forecast_window_s: float = 0.5
    # Smoothing factor for the rate level (the trend term uses 0.3,
    # fixed — one knob tunes responsiveness, the pair stays stable).
    forecast_alpha: float = 0.35
    # Ceiling on the forecast CONTRIBUTION to kmls_utilization: the
    # lead term is clamped to [reactive, this cap], so prediction alone
    # can drive the HPA to the cap but only measured overload reports
    # past it.
    forecast_util_cap: float = 1.0
    # Growth ratio (predicted/current rate) that arms the pre-widen/
    # pre-touch actuators; below it the batcher behaves exactly
    # reactively.
    forecast_ramp_ratio: float = 1.2
    # How many predicted-hot seed sets the post-delta pre-fetch
    # re-materializes (owner-owned, invalidation-cold sets only).
    forecast_prefetch_top_n: int = 8

    # --- second model family: hybrid rule∪embedding serving ---
    # How the two model families combine when an embedding artifact is
    # published: "rules" ignores embeddings entirely (the legacy path),
    # "embed" serves embedding top-k (rules only when the seeds are
    # unknown to the embedding vocab), "blend" unions both candidate
    # lists with blended scores. With no embedding artifact on the PVC —
    # or one that fails validation — every mode serves rules-only.
    hybrid_mode: str = "blend"
    # Weight of the EMBEDDING similarity in blend mode: blended score =
    # (1 - w)·rule_confidence + w·cosine_similarity. 0 ranks like
    # rules-only (embeddings still backfill rule-less candidates),
    # 1 like embed-only.
    hybrid_blend_weight: float = 0.5
    # KMLS_HYBRID_BLEND_WEIGHT=measured (ISSUE 14): serve the blend
    # optimum the quality loop's held-out sweep published in
    # quality.report.json. An explicit float wins (measured stays
    # False); an absent/unusable report fails safe to the default
    # weight above, with a warning at load.
    hybrid_blend_measured: bool = False
    # Per-artifact staleness bound (ISSUE 14): when any served artifact
    # (rules/delta-chain/embeddings/popularity) is older than this many
    # seconds, /readyz reports ready-but-degraded with the stale
    # artifact named and kmls_artifact_stale{artifact} flips to 1 — an
    # aging embeddings.npz becomes visible before it misleads.
    # 0 disables (the age gauges stay observability-only).
    artifact_max_age_s: float = 0.0

    @property
    def pickles_dir(self) -> str:
        return os.path.join(self.base_dir, self.pickle_dir)

    @staticmethod
    def from_env(dotenv_path: str | None = ".env") -> "ServingConfig":
        if dotenv_path:
            load_dotenv(dotenv_path)
        base_dir = os.getenv("BASE_DIR", "./api-data/")
        _blend_weight, _blend_measured = _getenv_blend_weight()
        return ServingConfig(
            version=os.getenv("VERSION", "V1.1"),
            base_dir=base_dir,
            pickle_dir=os.getenv("PICKLE_DIR", "pickles/"),
            app_path_from_root=os.getenv("APP_PATH_FROM_ROOT", "/app"),
            recommendations_file=os.getenv("RECOMMENDATIONS_FILE", "recommendations.pickle"),
            best_tracks_file=os.getenv("BEST_TRACKS_FILE", "best_tracks.pickle"),
            data_invalidation_file=os.getenv("DATA_INVALIDATION_FILE", "last_execution.txt"),
            k_best_tracks=_getenv_int("K_BEST_TRACKS", 10),
            polling_wait_in_minutes=_getenv_float("POLLING_WAIT_IN_MINUTES", 5.0),
            port=_getenv_int("KMLS_PORT", 80),
            max_seed_tracks=_getenv_int("KMLS_MAX_SEED_TRACKS", 128),
            batch_window_ms=_getenv_float("KMLS_BATCH_WINDOW_MS", 2.0),
            batch_max_size=_getenv_int("KMLS_BATCH_MAX_SIZE", 32),
            batch_adaptive_window=_getenv_bool("KMLS_BATCH_ADAPTIVE", True),
            batch_window_min_ms=_getenv_float("KMLS_BATCH_WINDOW_MIN_MS", 1.0),
            shed_queue_budget_ms=_getenv_float("KMLS_SHED_QUEUE_BUDGET_MS", 250.0),
            shed_retry_after_s=_getenv_float("KMLS_SHED_RETRY_AFTER_S", 1.0),
            shed_soft_ratio=_getenv_float("KMLS_SHED_SOFT_RATIO", 0.6),
            shed_hard_ratio=_getenv_float("KMLS_SHED_HARD_RATIO", 1.5),
            shed_retry_jitter=_getenv_float("KMLS_SHED_RETRY_JITTER", 0.5),
            batch_max_inflight=_getenv_int("KMLS_BATCH_MAX_INFLIGHT", 4),
            serve_devices=_getenv_int("KMLS_SERVE_DEVICES", 0),
            model_layout=_getenv_model_layout(),
            device_budget_bytes=_getenv_int(
                "KMLS_DEVICE_BUDGET_BYTES", 12 * (1 << 30)
            ),
            cache_enabled=_getenv_bool("KMLS_CACHE_ENABLED", True),
            cache_max_entries=_getenv_int("KMLS_CACHE_MAX_ENTRIES", 8192),
            prefer_tensor_artifact=_getenv_bool("KMLS_PREFER_TENSOR_ARTIFACT", True),
            verify_manifest=_getenv_bool("KMLS_VERIFY_MANIFEST", True),
            quarantine_after_failures=_getenv_int(
                "KMLS_QUARANTINE_AFTER_FAILURES", 2
            ),
            reload_backoff_base_s=_getenv_float("KMLS_RELOAD_BACKOFF_BASE_S", 0.5),
            reload_backoff_max_s=_getenv_float("KMLS_RELOAD_BACKOFF_MAX_S", 30.0),
            io_read_deadline_s=_getenv_float("KMLS_IO_READ_DEADLINE_S", 0.0),
            replica_eject_threshold=_getenv_int("KMLS_REPLICA_EJECT_THRESHOLD", 3),
            replica_probe_interval_s=_getenv_float(
                "KMLS_REPLICA_PROBE_INTERVAL_S", 5.0
            ),
            redispatch_max_retries=_getenv_int("KMLS_REDISPATCH_MAX_RETRIES", 3),
            request_deadline_ms=_getenv_float("KMLS_REQUEST_DEADLINE_MS", 0.0),
            fallback_budget_ms=_getenv_float("KMLS_FALLBACK_BUDGET_MS", 50.0),
            hybrid_mode=_getenv_hybrid_mode(),
            hybrid_blend_weight=_blend_weight,
            hybrid_blend_measured=_blend_measured,
            artifact_max_age_s=_getenv_float("KMLS_ARTIFACT_MAX_AGE_S", 0.0),
            delta_enabled=_getenv_bool("KMLS_DELTA_ENABLED", False),
            cache_affinity=_getenv_bool("KMLS_CACHE_AFFINITY", False),
            cache_affinity_peers=os.getenv("KMLS_CACHE_AFFINITY_PEERS", ""),
            cache_affinity_self=os.getenv("KMLS_CACHE_AFFINITY_SELF", ""),
            fleet_self=os.getenv("KMLS_FLEET_SELF", ""),
            fleet_peers=os.getenv("KMLS_FLEET_PEERS", ""),
            serve_gang_coordinator=os.getenv(
                "KMLS_SERVE_GANG_COORDINATOR", ""
            ),
            serve_gang_size=_getenv_int("KMLS_SERVE_GANG_SIZE", 1),
            serve_gang_rank=_getenv_gang_rank(),
            serve_gang_port=_getenv_int("KMLS_SERVE_GANG_PORT", 8477),
            hedge_enabled=_getenv_bool("KMLS_HEDGE", False),
            peer_slow_ratio=_getenv_float("KMLS_PEER_SLOW_RATIO", 0.0),
            hedge_delay_ms=_getenv_float("KMLS_HEDGE_DELAY_MS", 30.0),
            hedge_max_frac=_getenv_float("KMLS_HEDGE_MAX_FRAC", 0.05),
            trace_sample=_getenv_float("KMLS_TRACE_SAMPLE", 0.0),
            trace_buffer=_getenv_int("KMLS_TRACE_BUFFER", 512),
            trace_slow_n=_getenv_int("KMLS_TRACE_SLOW_N", 32),
            loop_lag_half_life_s=_getenv_float(
                "KMLS_LOOP_LAG_HALF_LIFE_S", 1.0
            ),
            costmodel_enabled=_getenv_bool("KMLS_COSTMODEL", True),
            slo_p99_ms=_getenv_float("KMLS_SLO_P99_MS", 25.0),
            slo_error_budget=_getenv_float("KMLS_SLO_ERROR_BUDGET", 0.001),
            slo_degrade_budget=_getenv_float(
                "KMLS_SLO_DEGRADE_BUDGET", 0.01
            ),
            slo_fast_window_s=_getenv_float("KMLS_SLO_FAST_WINDOW_S", 300.0),
            slo_slow_window_s=_getenv_float(
                "KMLS_SLO_SLOW_WINDOW_S", 3600.0
            ),
            forecast_enabled=_getenv_bool("KMLS_FORECAST", False),
            forecast_horizon_s=_getenv_float("KMLS_FORECAST_HORIZON_S", 2.0),
            forecast_window_s=_getenv_float("KMLS_FORECAST_WINDOW_S", 0.5),
            forecast_alpha=_getenv_float("KMLS_FORECAST_ALPHA", 0.35),
            forecast_util_cap=_getenv_float("KMLS_FORECAST_UTIL_CAP", 1.0),
            forecast_ramp_ratio=_getenv_float(
                "KMLS_FORECAST_RAMP_RATIO", 1.2
            ),
            forecast_prefetch_top_n=_getenv_int(
                "KMLS_FORECAST_PREFETCH_TOP_N", 8
            ),
        )
