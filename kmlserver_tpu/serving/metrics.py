"""Serving observability — the counters the reference lacks.

The reference's only observability is log lines and one in-memory
``reload_counter`` (rest_api/app/main.py:18-29,120,143; SURVEY.md §5 calls
out the absence of a metrics endpoint). This adds latency/QPS counters with
bounded reservoirs so the p50-at-QPS target is measurable, exposed in
Prometheus text format at ``GET /metrics`` — including the queue-vs-device
latency attribution the micro-batcher threads through
(``kmls_queue_wait_ms`` / ``kmls_device_ms`` / ``kmls_e2e_ms``, quantiles
up to p999), which is what lets a replay harness say WHERE a tail lives
instead of only that one exists.
"""

from __future__ import annotations

import bisect
import threading
import time

# every summary rendered below carries these quantiles; p999 needs the
# larger reservoir to mean anything (16384 samples → ~16 above p999)
_QUANTILES = (0.50, 0.95, 0.99, 0.999)

# ---------------------------------------------------------------------------
# The metric-series registry — THE declaration point for every exported
# Prometheus series, serving (`GET /metrics`) and mining (the
# `pickles/job_metrics.prom` textfile) alike. Values are "<type>:<scope>"
# with type ∈ counter/gauge/summary/histogram and scope ∈ serving/mining.
#
# kmls-verify's `metrics` checker (kmlserver_tpu/analysis/metricsreg.py)
# enforces, in CI: every series name spelled in the exposition modules
# (this file, observability/jobmetrics.py, and the app's dynamically
# rendered robustness keys) is declared here with a valid type+scope and
# has a README row; and the inverse — a registry entry nothing renders is
# an orphan. The mining textfile writer additionally looks its names up
# HERE at render time, so the two exposition surfaces can never drift
# from one declaration the way KNOB_REGISTRY keeps env knobs honest.
# Adding a series = render it, add an entry here, and a README table row
# — or CI's verify job rejects the diff, naming exactly what is missing.
# ---------------------------------------------------------------------------
METRIC_REGISTRY: dict[str, str] = {
    # --- serving: request counters ---
    "kmls_requests_total": "counter:serving",
    "kmls_request_errors_total": "counter:serving",
    "kmls_requests_shed_total": "counter:serving",
    "kmls_requests_by_source": "counter:serving",
    # --- serving: latency (reservoir summaries for bench windowing,
    # fixed-bucket histograms for fleet aggregation — see ISSUE 9) ---
    "kmls_request_latency_seconds": "summary:serving",
    "kmls_queue_wait_ms": "summary:serving",
    "kmls_device_ms": "summary:serving",
    "kmls_e2e_ms": "summary:serving",
    "kmls_queue_wait_seconds": "histogram:serving",
    "kmls_device_seconds": "histogram:serving",
    "kmls_e2e_seconds": "histogram:serving",
    # --- serving: recommendation cache ---
    "kmls_cache_hits_total": "counter:serving",
    "kmls_cache_misses_total": "counter:serving",
    "kmls_cache_evictions_total": "counter:serving",
    "kmls_cache_singleflight_joins_total": "counter:serving",
    "kmls_cache_entries": "gauge:serving",
    "kmls_cache_hit_ratio": "gauge:serving",
    # selective invalidation (continuous freshness, ISSUE 10): delta
    # applies bump per-seed-name generations instead of the epoch —
    # invalidation events and the entries each walk deleted
    "kmls_cache_selective_invalidations_total": "counter:serving",
    "kmls_cache_invalidated_keys_total": "counter:serving",
    # fleet cache affinity (freshness/ring.py): would a rendezvous-hash
    # router have kept this request on THIS replica? The decision data
    # for affinity routing vs a shared external cache tier.
    "kmls_cache_affinity_local_total": "counter:serving",
    "kmls_cache_affinity_remote_total": "counter:serving",
    # fleet cache routing (ISSUE 15): with KMLS_FLEET_PEERS armed, a
    # non-owned miss answered locally is routing DRIFT at the ingress/
    # client — the counter a dashboard alerts on when the consistent-
    # hash tier stops keeping keys on their owners — plus the configured
    # routing-ring size (0 = tier unarmed)
    "kmls_cache_misrouted_total": "counter:serving",
    "kmls_fleet_peers": "gauge:serving",
    # --- serving: dispatch / layout ---
    "kmls_device_dispatch_total": "counter:serving",
    # what a dispatch carried (ISSUE 26), counted where the work
    # happens: requests per dispatched batch (buckets 1..32); seed slots
    # staged for the rule lookup by kind (real = a known seed's id,
    # padded = the -1 fill up to the shape bucket; the real series is
    # also the per-batch sum of seed lengths a roofline join needs); and
    # dispatches whose shape was never warmed (a compile on the serving
    # path — must stay 0)
    "kmls_batch_size": "histogram:serving",
    "kmls_seed_slots_total": "counter:serving",
    "kmls_unwarmed_dispatches_total": "counter:serving",
    "kmls_shard_dispatch_total": "counter:serving",
    "kmls_model_shards": "gauge:serving",
    # sharded layout only: what placing the rule shards took at the last
    # publication (the sharded device_puts until resident), and the rule
    # bytes each device holds
    "kmls_shard_place_seconds": "gauge:serving",
    "kmls_shard_resident_bytes": "gauge:serving",
    # pod-spanning serve mesh (ISSUE 16): gang shard health by state
    # (serving/missing) — rendered only on gang members, so the series
    # existing at all says "this pod is a mesh member", and
    # {state="missing"} > 0 is the alert that a vocab slab is dark
    # (the same condition /readyz names as serve_mesh_shard_missing:<r>)
    "kmls_serve_mesh_shards": "gauge:serving",
    # --- serving: fault tolerance / overload ---
    "kmls_degraded_total": "counter:serving",
    "kmls_degraded_by_reason": "counter:serving",
    "kmls_replica_ejections_total": "counter:serving",
    "kmls_replica_readmissions_total": "counter:serving",
    "kmls_redispatch_total": "counter:serving",
    "kmls_artifact_quarantines_total": "counter:serving",
    "kmls_reload_failures_total": "counter:serving",
    "kmls_reload_consecutive_failures": "gauge:serving",
    "kmls_embedding_active": "gauge:serving",
    "kmls_embedding_load_failures_total": "counter:serving",
    "kmls_replicas_ejected": "gauge:serving",
    "kmls_utilization": "gauge:serving",
    "kmls_admission_degrade_total": "counter:serving",
    # --- serving: gray-failure spine (ISSUE 18) ---
    # deadline propagation: requests whose forwarded
    # X-KMLS-Deadline-Budget arrived already spent (answered degraded,
    # counted as wasted-work — distinct from slow-compute "deadline"
    # degrades), and the mesh-worker twin (partial frames shed before
    # compute because their budget field was ≤ 0 on arrival)
    "kmls_deadline_expired_total": "counter:serving",
    "kmls_mesh_expired_on_arrival_total": "counter:serving",
    # hedged mesh dispatch (KMLS_HEDGE): straggler outcomes — won
    # (merged without the late rank), lost (it landed in the grace
    # re-check; token refunded), cancelled (hedge budget exhausted →
    # plain waiting). All pinned 0 with the knob off (zero-cost proof).
    "kmls_hedge_wins_total": "counter:serving",
    "kmls_hedge_losses_total": "counter:serving",
    "kmls_hedge_cancelled_total": "counter:serving",
    # slow-outlier ladder (KMLS_PEER_SLOW_RATIO): gang ranks ejected
    # for EWMA latency over ratio×healthy-median, re-admissions after
    # recovery, and how many ranks are slow-marked right now
    "kmls_peer_slow_ejections_total": "counter:serving",
    "kmls_peer_slow_readmissions_total": "counter:serving",
    "kmls_peer_slow": "gauge:serving",
    # merges answered without a straggler slab's candidates (each one
    # also counts kmls_degraded_total{reason="mesh-straggler"})
    "kmls_mesh_straggler_degraded_total": "counter:serving",
    # --- serving: storage gray-failure spine (ISSUE 19) ---
    # artifact-plane IO health (io/iohealth.py, fed by io/artifacts.py):
    # per-operation latency EWMA {op ∈ token_poll/read/write/fsync},
    # errors by (op, errno), transient-EIO retries, free bytes on the
    # artifact volume, and the storage-slow conviction behind the
    # /readyz "storage-slow" degraded reason
    "kmls_io_latency_seconds": "gauge:serving",
    "kmls_io_errors_total": "counter:serving",
    "kmls_io_retries_total": "counter:serving",
    "kmls_disk_free_bytes": "gauge:serving",
    "kmls_storage_slow": "gauge:serving",
    # --- serving: continuous freshness (ISSUE 10) ---
    # delta bundles applied in place vs rejected (torn/wrong-base/
    # injected), the chain position serving ((base, delta_seq) epoch
    # pair), and the age of the newest APPLIED generation — the
    # freshness-lag number the delta path exists to shrink
    "kmls_delta_applied_total": "counter:serving",
    "kmls_delta_rejected_total": "counter:serving",
    "kmls_delta_seq": "gauge:serving",
    "kmls_freshness_lag_seconds": "gauge:serving",
    # --- serving: quality loop (ISSUE 14) ---
    # published delta-chain length for the serving generation — the
    # compaction trigger (KMLS_DELTA_COMPACT_AFTER), observable before
    # the compactor acts on it
    "kmls_delta_chain_length": "gauge:serving",
    # the EFFECTIVE hybrid blend weight: the measured optimum when
    # KMLS_HYBRID_BLEND_WEIGHT=measured published one, else the knob —
    # dashboards see which weight actually ranks answers
    "kmls_hybrid_blend_weight": "gauge:serving",
    # per-artifact staleness flag: 1 when the artifact's age exceeds
    # KMLS_ARTIFACT_MAX_AGE_S (always 0 with the bound disabled) — the
    # alertable twin of kmls_artifact_age_seconds
    "kmls_artifact_stale": "gauge:serving",
    # --- serving: observability (ISSUE 9) ---
    # peak-hold event-loop/scheduler stall estimate, decayed — the
    # runtime-health signal the admission ladder also folds in
    "kmls_loop_lag_ms": "gauge:serving",
    "kmls_traces_began_total": "counter:serving",
    "kmls_traces_retained_total": "counter:serving",
    "kmls_trace_buffer_entries": "gauge:serving",
    # --- serving: device-truth cost attribution (ISSUE 12) ---
    # per-kernel fenced device time + analytic FLOPs/bytes → achieved
    # rates, MFU vs the backend peak table, and the roofline class
    # (1 = compute-bound); rendered by observability/costmodel.py
    "kmls_kernel_device_seconds": "counter:serving",
    "kmls_kernel_dispatches_total": "counter:serving",
    "kmls_kernel_flops_per_second": "gauge:serving",
    "kmls_kernel_bytes_per_second": "gauge:serving",
    "kmls_mfu": "gauge:serving",
    "kmls_kernel_compute_bound": "gauge:serving",
    # jit-cache growth after publication — the LIVE form of the
    # zero-compiles-post-publish invariant (was test-only before)
    "kmls_compiles_total": "counter:serving",
    # cost-model bookkeeping: total observations (the zero-cost proof
    # counter — 0 with KMLS_COSTMODEL=0) and dispatches naming a kernel
    # with no registered spec (the costspec checker's runtime shadow)
    "kmls_costmodel_observations_total": "counter:serving",
    "kmls_costmodel_unspecced_total": "counter:serving",
    # memory telemetry: live memory_stats() gauges where the backend
    # provides them, plus the analytic per-artifact tensor residency
    # the layout.py auto decision measures — budget, headroom, and the
    # publish-time bytes-in-use watermark
    "kmls_device_bytes_in_use": "gauge:serving",
    "kmls_device_bytes_limit": "gauge:serving",
    "kmls_model_tensor_bytes": "gauge:serving",
    "kmls_device_budget_bytes": "gauge:serving",
    "kmls_device_headroom_bytes": "gauge:serving",
    "kmls_publish_watermark_bytes": "gauge:serving",
    # --- serving: predictive serving (ISSUE 17, serving/forecast.py) ---
    # online traffic forecaster: smoothed current arrival rate, the
    # horizon prediction, their ratio (the ramp signal), the zero-cost
    # proof counter (0 with KMLS_FORECAST=0 — test-pinned, costmodel
    # style), the actuator counters (owner-targeted cache pre-fetches
    # led, shape-bucket pre-touches dispatched), and the bounded
    # forecast term actually folded into kmls_utilization — rendered
    # through the robustness dict only while the forecaster is armed
    "kmls_forecast_rate": "gauge:serving",
    "kmls_forecast_predicted_rate": "gauge:serving",
    "kmls_forecast_ratio": "gauge:serving",
    "kmls_forecast_observations_total": "counter:serving",
    "kmls_forecast_prefetch_total": "counter:serving",
    "kmls_forecast_prewarm_total": "counter:serving",
    "kmls_utilization_forecast": "gauge:serving",
    # --- serving: SLO burn rates (ISSUE 12, observability/slo.py) ---
    # multi-window budget-consumption rates (slo ∈ latency_p99/
    # availability/quality, window ∈ fast/slow); observability only —
    # the admission ladder stays the actuator
    "kmls_slo_burn_rate": "gauge:serving",
    # per-artifact freshness age (ISSUE 12 satellite): seconds since
    # each served artifact's publication (rules/delta-chain/embeddings/
    # popularity) — the staleness bound /readyz also reports
    "kmls_artifact_age_seconds": "gauge:serving",
    # --- serving: lifecycle ---
    "kmls_reloads_total": "counter:serving",
    "kmls_finished_loading": "gauge:serving",
    "kmls_uptime_seconds": "gauge:serving",
    # --- mining: the job_metrics.prom textfile (observability/
    # jobmetrics.py — node-exporter textfile-collector format; gauges
    # because a batch job's file restarts from scratch every run, so
    # counter delta semantics would lie across runs) ---
    "kmls_job_phase_duration_seconds": "gauge:mining",
    "kmls_job_phase_resumed": "gauge:mining",
    "kmls_job_rows": "gauge:mining",
    "kmls_job_playlists": "gauge:mining",
    "kmls_job_tracks": "gauge:mining",
    "kmls_job_artifact_bytes": "gauge:mining",
    "kmls_job_rule_generation_seconds": "gauge:mining",
    "kmls_job_fencing_token": "gauge:mining",
    "kmls_job_duration_seconds": "gauge:mining",
    "kmls_job_success": "gauge:mining",
    "kmls_job_last_success_timestamp_seconds": "gauge:mining",
    # per-phase analytic cost attribution (ISSUE 12): the same
    # costmodel.phase_cost formulas the serving side uses, evaluated on
    # the mined shape — what the phase's dominant kernel moved/computed
    "kmls_job_phase_flops": "gauge:mining",
    "kmls_job_phase_bytes_moved": "gauge:mining",
    # sparsity-adaptive dispatch (ISSUE 13): which pair-count family the
    # measured dispatcher chose for this generation, labeled
    # {path, source} — value is always 1 (an info-style gauge)
    "kmls_job_count_path": "gauge:mining",
}

# The autoscaling signal (ISSUE 8): the gauge kubernetes/hpa.yaml scales
# the API fleet on, derived by the batcher from its queue/device latency
# attribution (max of pipeline occupancy and admission queue pressure;
# 1.0 = at capacity, shedding begins above it). With KMLS_FORECAST=1 a
# bounded predictive lead term joins the max (ISSUE 17): the reactive
# value scaled by the forecast growth ratio, clamped so it can raise
# the signal ahead of a ramp but never lower it and never exceed
# KMLS_FORECAST_UTIL_CAP on prediction alone. The app exposes it
# through the robustness-state dict (serving/app.py _robustness_state,
# key "utilization" → rendered with the kmls_ prefix below);
# tests/test_deploy.py pins the HPA manifest to THIS name so the metric
# the adapter queries can never drift from the one the server exports.
UTILIZATION_SERIES = "kmls_utilization"


class LatencyReservoir:
    """Fixed-size ring of recent latencies; cheap percentile reads."""

    def __init__(self, size: int = 16384):
        self._buf = [0.0] * size
        self._n = 0
        self._lock = threading.Lock()

    def observe(self, seconds: float) -> None:
        with self._lock:
            self._buf[self._n % len(self._buf)] = seconds
            self._n += 1

    def percentiles(self, *qs: float) -> list[float]:
        # COPY under the lock, sort OUTSIDE it (ISSUE 9 satellite): the
        # sort is O(n log n) over up to 16384 floats — holding the observe
        # lock through it would stall every request thread mid-record on
        # each scrape. The slice is a snapshot; a concurrent observe
        # racing the copy costs at most one sample's visibility.
        with self._lock:
            live = self._buf[: min(self._n, len(self._buf))]
        if not live:
            return [0.0 for _ in qs]
        live.sort()
        return [live[min(int(q * len(live)), len(live) - 1)] for q in qs]

    def reset(self) -> int:
        """Empty the ring → number of observations discarded."""
        with self._lock:
            n = self._n
            self._n = 0
        return n


# default latency buckets (seconds): sub-ms resolution where the serving
# p50 lives (0.4–5 ms on the CPU replay record), decade coverage out to
# the deadline/backoff regime. Shared across every replica of a fleet —
# fixed buckets are the whole point: per-pod `_bucket` counters SUM
# across replicas, which per-pod reservoir quantiles never can.
LATENCY_BUCKETS_S = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)
# requests per dispatched batch: the engine's batch buckets up to the
# default batch_max_size (a larger cap lands in +Inf)
BATCH_SIZE_BUCKETS = (1, 2, 4, 8, 16, 32)


class LatencyHistogram:
    """Fixed-bucket Prometheus histogram (`_bucket`/`_sum`/`_count`).

    The reservoirs above answer "what is THIS pod's p99 right now"
    (bench windowing — they reset per run); this histogram answers the
    fleet question: bucket counters are cumulative and additive across
    replicas, so `histogram_quantile(0.99, sum(rate(..._bucket[5m])) by
    (le))` is the aggregation the ROADMAP's millions-of-users fleet
    needs and reservoir quantiles mathematically cannot provide.
    Deliberately NOT reset by the bench's `/metrics/reset` — counters
    keep scrape-delta semantics."""

    def __init__(self, buckets: tuple[float, ...] = LATENCY_BUCKETS_S):
        self.buckets = tuple(buckets)
        # counts[i] = observations <= buckets[i]; counts[-1] = +Inf band
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, seconds: float) -> None:
        idx = bisect.bisect_left(self.buckets, seconds)
        with self._lock:
            self._counts[idx] += 1
            self._sum += seconds
            self._count += 1

    def snapshot(self) -> tuple[list[int], float, int]:
        with self._lock:
            return list(self._counts), self._sum, self._count

    def quantile(self, q: float) -> float:
        """Bucket-derived quantile (histogram_quantile semantics: linear
        interpolation inside the winning bucket; the +Inf band answers
        its finite lower edge). Used by the test pinning histogram
        quantiles against reservoir quantiles — and by nothing on any
        hot path."""
        counts, _total_sum, n = self.snapshot()
        if n == 0:
            return 0.0
        target = q * n
        cum = 0
        for i, c in enumerate(counts):
            if c == 0:
                cum += c
                continue
            if cum + c >= target:
                lo = self.buckets[i - 1] if i > 0 else 0.0
                hi = self.buckets[i] if i < len(self.buckets) else lo
                frac = (target - cum) / c
                return lo + frac * (hi - lo)
            cum += c
        return self.buckets[-1]

    def render(self, name: str) -> list[str]:
        counts, total_sum, n = self.snapshot()
        lines = [f"# TYPE {name} histogram"]
        cum = 0
        for bound, count in zip(self.buckets, counts):
            cum += count
            lines.append(f'{name}_bucket{{le="{bound:g}"}} {cum}')
        lines += [
            f'{name}_bucket{{le="+Inf"}} {n}',
            f"{name}_sum {total_sum:.6f}",
            f"{name}_count {n}",
        ]
        return lines


class ServingMetrics:
    def __init__(self):
        self.started_at = time.time()
        self.requests_total = 0
        # "embed"/"hybrid" are the second model family's sources — present
        # from the start so dashboards can rely on the series existing
        self.requests_by_source = {
            "rules": 0, "embed": 0, "hybrid": 0, "fallback": 0, "empty": 0,
        }
        self.errors_total = 0
        self.shed_total = 0
        # fault-tolerance counters: degraded answers by reason (deadline
        # exhaustion vs total replica loss), plus the batcher's circuit-
        # breaker events — every recovery event is visible, not just logged
        self.degraded_by_reason: dict[str, int] = {}
        self.replica_ejections_total = 0
        self.replica_readmissions_total = 0
        self.redispatch_total = 0
        self.latency = LatencyReservoir()
        # per-request latency attribution from the micro-batcher:
        # queue_wait = enqueue→batch formed; "device" = batch formed →
        # batch resolved on the HOST's clock, so it holds staging,
        # dispatch, the device's work, both fetches and the compose of
        # every member (the span tree's `batch`; the name is kept for the
        # dashboards and the admission ladder that read it); e2e =
        # enqueue→done
        self.queue_wait = LatencyReservoir()
        self.device = LatencyReservoir()
        self.e2e = LatencyReservoir()
        # the same attributions as fixed-bucket histograms: reservoirs
        # window per-pod bench runs, histograms aggregate across a fleet
        self.queue_wait_hist = LatencyHistogram()
        self.device_hist = LatencyHistogram()
        self.e2e_hist = LatencyHistogram()
        # requests per dispatched batch (the same fixed-bucket counter
        # histogram, over a count instead of seconds)
        self.batch_size_hist = LatencyHistogram(BATCH_SIZE_BUCKETS)
        self._lock = threading.Lock()

    def record(self, source: str, seconds: float) -> None:
        with self._lock:
            self.requests_total += 1
            self.requests_by_source[source] = self.requests_by_source.get(source, 0) + 1
        self.latency.observe(seconds)

    def record_error(self) -> None:
        with self._lock:
            self.errors_total += 1

    def record_shed(self) -> None:
        with self._lock:
            self.shed_total += 1

    def record_degraded(self, reason: str) -> None:
        """A request answered from the popularity fallback with an
        X-KMLS-Degraded header instead of an error."""
        with self._lock:
            self.degraded_by_reason[reason] = (
                self.degraded_by_reason.get(reason, 0) + 1
            )

    def record_replica_ejected(self) -> None:
        with self._lock:
            self.replica_ejections_total += 1

    def record_replica_readmitted(self) -> None:
        with self._lock:
            self.replica_readmissions_total += 1

    def record_redispatch(self, n: int = 1) -> None:
        with self._lock:
            self.redispatch_total += n

    def record_batch_size(self, n_requests: int) -> None:
        """One dispatched batch of ``n_requests`` (the batchers call it
        where the engine call returned)."""
        self.batch_size_hist.observe(n_requests)

    def record_attribution(
        self, queue_wait_s: float, device_s: float, e2e_s: float
    ) -> None:
        self.queue_wait.observe(queue_wait_s)
        self.device.observe(device_s)
        self.e2e.observe(e2e_s)
        self.queue_wait_hist.observe(queue_wait_s)
        self.device_hist.observe(device_s)
        self.e2e_hist.observe(e2e_s)

    def reset_latency(self) -> int:
        """Clear the latency + attribution reservoirs (→ request-latency
        observations discarded).

        Lets a measurement harness window the percentiles to one replay
        run. The Prometheus counters stay cumulative —
        resetting counters would break scrape-delta semantics — and the
        attribution HISTOGRAMS stay with the counters: their buckets ARE
        counters (fleet aggregation depends on scrape deltas), so only
        the reservoirs window."""
        n = self.latency.reset()
        self.queue_wait.reset()
        self.device.reset()
        self.e2e.reset()
        return n

    @staticmethod
    def _summary_ms(name: str, reservoir: LatencyReservoir) -> list[str]:
        values = reservoir.percentiles(*_QUANTILES)
        lines = [f"# TYPE {name} summary"]
        for q, val in zip(_QUANTILES, values):
            label = f"{q:g}"
            lines.append(f'{name}{{quantile="{label}"}} {val * 1e3:.4f}')
        return lines

    def render(
        self, reload_counter: int, finished_loading: bool,
        cache=None, dispatch_counts=None, robustness=None,
        shard_counts=None, cost=None, slo=None, artifact_ages=None,
        artifact_stale=None, mesh_shards=None, io=None, seed_slots=None,
        shard_placement=None,
    ) -> str:
        """Prometheus text. ``cache`` (a serving.cache.RecommendCache),
        ``dispatch_counts`` (the engine's per-replica dispatch counters),
        ``robustness`` (a flat dict of engine/batcher recovery-state
        values — names ending in ``_total`` render as counters, the rest
        as gauges, all under a ``kmls_`` prefix), ``shard_counts``
        (per-vocab-shard seed-hit counters, present only under the
        sharded model layout), ``cost`` (an observability.costmodel
        .CostModel — per-kernel MFU/roofline + memory/compile
        telemetry), ``slo`` (an observability.slo.SloTracker) and
        ``artifact_ages`` (artifact name → seconds since publication),
        ``seed_slots`` (the engine's ``(real, padded)`` staged-slot
        counters) and ``shard_placement`` (the engine's
        ``shard_placement()``, None outside the sharded layout) are
        optional — deployments without them render exactly
        the old exposition."""
        p50, p95, p99 = self.latency.percentiles(0.50, 0.95, 0.99)
        uptime = time.time() - self.started_at
        lines = [
            "# TYPE kmls_requests_total counter",
            f"kmls_requests_total {self.requests_total}",
            "# TYPE kmls_request_errors_total counter",
            f"kmls_request_errors_total {self.errors_total}",
            "# TYPE kmls_requests_shed_total counter",
            f"kmls_requests_shed_total {self.shed_total}",
            "# TYPE kmls_requests_by_source counter",
        ]
        for source, count in sorted(self.requests_by_source.items()):
            lines.append(f'kmls_requests_by_source{{source="{source}"}} {count}')
        lines += [
            "# TYPE kmls_request_latency_seconds summary",
            f'kmls_request_latency_seconds{{quantile="0.5"}} {p50:.6f}',
            f'kmls_request_latency_seconds{{quantile="0.95"}} {p95:.6f}',
            f'kmls_request_latency_seconds{{quantile="0.99"}} {p99:.6f}',
        ]
        # batcher attribution summaries, milliseconds (absent→all-zero is
        # fine: an unbatched deployment simply never observes into them)
        lines += self._summary_ms("kmls_queue_wait_ms", self.queue_wait)
        lines.append(
            "# HELP kmls_device_ms batch formed to batch resolved, host "
            "clock: staging, dispatch, device, fetch, compose"
        )
        lines += self._summary_ms("kmls_device_ms", self.device)
        lines += self._summary_ms("kmls_e2e_ms", self.e2e)
        # the same attributions as fixed-bucket histograms (seconds):
        # `_bucket` counters sum across replicas, so the fleet's
        # histogram_quantile works where per-pod reservoir quantiles
        # cannot aggregate (ISSUE 9)
        lines += self.queue_wait_hist.render("kmls_queue_wait_seconds")
        lines.append(
            "# HELP kmls_device_seconds batch formed to batch resolved, "
            "host clock: staging, dispatch, device, fetch, compose"
        )
        lines += self.device_hist.render("kmls_device_seconds")
        lines += self.e2e_hist.render("kmls_e2e_seconds")
        lines += self.batch_size_hist.render("kmls_batch_size")
        if cache is not None:
            # epoch-keyed recommendation cache: hit/miss/evict counters +
            # the hit-ratio gauge the 10k-QPS claim is judged on
            lines += [
                "# TYPE kmls_cache_hits_total counter",
                f"kmls_cache_hits_total {cache.hits}",
                "# TYPE kmls_cache_misses_total counter",
                f"kmls_cache_misses_total {cache.misses}",
                "# TYPE kmls_cache_evictions_total counter",
                f"kmls_cache_evictions_total {cache.evictions}",
                "# TYPE kmls_cache_singleflight_joins_total counter",
                f"kmls_cache_singleflight_joins_total {cache.singleflight_joins}",
                "# TYPE kmls_cache_entries gauge",
                f"kmls_cache_entries {len(cache)}",
                "# TYPE kmls_cache_hit_ratio gauge",
                f"kmls_cache_hit_ratio {cache.hit_ratio():.4f}",
                # selective invalidation (continuous freshness): delta
                # applies invalidate only touched seed keys — events and
                # entries deleted, vs the for-free wholesale epoch bump
                "# TYPE kmls_cache_selective_invalidations_total counter",
                "kmls_cache_selective_invalidations_total "
                f"{getattr(cache, 'selective_invalidations', 0)}",
                "# TYPE kmls_cache_invalidated_keys_total counter",
                "kmls_cache_invalidated_keys_total "
                f"{getattr(cache, 'invalidated_keys', 0)}",
            ]
        if dispatch_counts:
            # per-replica device dispatch counters: the evidence that the
            # data-parallel dispatcher actually spreads work
            lines.append("# TYPE kmls_device_dispatch_total counter")
            lines += [
                f'kmls_device_dispatch_total{{device="{i}"}} {count}'
                for i, count in enumerate(dispatch_counts)
            ]
        if seed_slots is not None:
            # what the staged seed arrays held: padded / (real + padded)
            # is the share of the lookup's slots that were padding
            lines += [
                "# TYPE kmls_seed_slots_total counter",
                f'kmls_seed_slots_total{{kind="real"}} {seed_slots[0]}',
                f'kmls_seed_slots_total{{kind="padded"}} {seed_slots[1]}',
            ]
        if shard_counts:
            # sharded model layout: seed ids dispatched per vocab shard —
            # the load-balance evidence for the model-parallel lookup
            # (which shard's rule rows the traffic actually hits)
            lines.append("# TYPE kmls_shard_dispatch_total counter")
            lines += [
                f'kmls_shard_dispatch_total{{shard="{i}"}} {count}'
                for i, count in enumerate(shard_counts)
            ]
        if shard_placement is not None:
            # sharded model layout: the publication's placement time and
            # what each device holds of the rule tensors
            place_seconds, resident = shard_placement
            lines += [
                "# TYPE kmls_shard_place_seconds gauge",
                f"kmls_shard_place_seconds {place_seconds:.6f}",
                "# TYPE kmls_shard_resident_bytes gauge",
            ]
            lines += [
                f'kmls_shard_resident_bytes{{shard="{i}"}} {nbytes}'
                for i, nbytes in enumerate(resident)
            ]
        if mesh_shards:
            # pod-spanning serve mesh (ISSUE 16): shard health by state —
            # {state="serving"} + {state="missing"} always sums to the
            # gang size, so either series alone places this pod's gang
            # health; rendered only when the app passes a gang snapshot
            # (non-mesh deployments keep the exact old exposition)
            lines.append("# TYPE kmls_serve_mesh_shards gauge")
            lines += [
                f'kmls_serve_mesh_shards{{state="{state}"}} {count}'
                for state, count in sorted(mesh_shards.items())
            ]
        # fault-tolerance exposition: degraded answers by reason + the
        # circuit breaker's eject/readmit/redispatch counters — always
        # present (zero-valued when nothing ever degraded), so dashboards
        # and the chaos bench can rely on the series existing
        with self._lock:
            degraded = dict(self.degraded_by_reason)
            ejections = self.replica_ejections_total
            readmissions = self.replica_readmissions_total
            redispatches = self.redispatch_total
        lines += [
            "# TYPE kmls_degraded_total counter",
            f"kmls_degraded_total {sum(degraded.values())}",
            "# TYPE kmls_degraded_by_reason counter",
        ]
        lines += [
            f'kmls_degraded_by_reason{{reason="{reason}"}} {count}'
            for reason, count in sorted(degraded.items())
        ]
        lines += [
            "# TYPE kmls_replica_ejections_total counter",
            f"kmls_replica_ejections_total {ejections}",
            "# TYPE kmls_replica_readmissions_total counter",
            f"kmls_replica_readmissions_total {readmissions}",
            "# TYPE kmls_redispatch_total counter",
            f"kmls_redispatch_total {redispatches}",
        ]
        lines += [
            "# TYPE kmls_reloads_total counter",
            f"kmls_reloads_total {reload_counter}",
            "# TYPE kmls_finished_loading gauge",
            f"kmls_finished_loading {int(finished_loading)}",
            "# TYPE kmls_uptime_seconds gauge",
            f"kmls_uptime_seconds {uptime:.1f}",
        ]
        if cost is not None:
            # device-truth cost attribution (ISSUE 12): per-kernel
            # device seconds / achieved rates / MFU / roofline class,
            # the live compile counter, and the memory accounting —
            # rendered by the cost model itself (one exposition site)
            lines += cost.render_lines()
        if slo is not None:
            # multi-window SLO burn rates (observability only — the
            # admission ladder stays the actuator)
            lines += slo.render_lines()
        if artifact_ages:
            # per-artifact freshness age: seconds since each served
            # artifact's publication (the /readyz staleness bound)
            lines.append("# TYPE kmls_artifact_age_seconds gauge")
            lines += [
                f'kmls_artifact_age_seconds{{artifact="{name}"}} '
                f"{artifact_ages[name]:.3f}"
                for name in sorted(artifact_ages)
            ]
        if artifact_stale:
            # the alertable staleness flag (ISSUE 14): 1 = the artifact
            # is over KMLS_ARTIFACT_MAX_AGE_S (and /readyz says so too);
            # rendered wherever ages are, all-0 with the bound disabled
            lines.append("# TYPE kmls_artifact_stale gauge")
            lines += [
                f'kmls_artifact_stale{{artifact="{name}"}} '
                f"{int(artifact_stale[name])}"
                for name in sorted(artifact_stale)
            ]
        if io is not None:
            # storage gray-failure spine (ISSUE 19): the IO-health
            # monitor's snapshot (io/iohealth.py). Latency EWMAs are
            # gauges (not summaries — they carry the conviction math's
            # exact inputs), errors are labeled by the errno a real bad
            # mount would return, and kmls_storage_slow is the 0/1
            # conviction behind /readyz's "storage-slow" reason.
            lines.append("# TYPE kmls_io_latency_seconds gauge")
            lines += [
                f'kmls_io_latency_seconds{{op="{op}"}} {ewma:.6f}'
                for op, ewma in sorted(io.get("latency_s", {}).items())
            ]
            lines.append("# TYPE kmls_io_errors_total counter")
            lines += [
                f'kmls_io_errors_total{{op="{op}",errno="{err}"}} {count}'
                for (op, err), count in sorted(io.get("errors", {}).items())
            ]
            lines += [
                "# TYPE kmls_io_retries_total counter",
                f"kmls_io_retries_total {int(io.get('retries', 0))}",
                "# TYPE kmls_storage_slow gauge",
                f"kmls_storage_slow {int(bool(io.get('storage_slow')))}",
            ]
            free = io.get("disk_free_bytes")
            if free is not None:
                lines += [
                    "# TYPE kmls_disk_free_bytes gauge",
                    f"kmls_disk_free_bytes {int(free)}",
                ]
        if robustness:
            # dedupe by series name (ISSUE 9 satellite): a robustness key
            # colliding with a statically rendered series (e.g. a
            # `degraded_total` entry vs kmls_degraded_total above) must
            # not emit a second `# TYPE` line — duplicate TYPE for one
            # name is invalid exposition and breaks strict scrapers. The
            # static rendering wins; the colliding dynamic entry is
            # dropped whole (its VALUE would be a second unlabeled sample
            # of the same series, equally invalid). The dynamic block
            # renders LAST so this set covers every static series.
            typed = {
                line.split(" ", 3)[2]
                for line in lines
                if line.startswith("# TYPE ")
            }
            for name, value in robustness.items():
                full = f"kmls_{name}"
                if full in typed:
                    continue
                typed.add(full)
                mtype = "counter" if name.endswith("_total") else "gauge"
                lines += [
                    f"# TYPE {full} {mtype}",
                    f"{full} {value}",
                ]
        return "\n".join(lines) + "\n"
