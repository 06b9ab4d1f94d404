"""The REST serving app — the reference's FastAPI surface rebuilt on the
stdlib (this image ships no web framework), same routes, same schemas:

- ``POST /api/recommend/`` (reference: rest_api/app/main.py:176-187):
  body ``{"songs": [...]}`` → ``{"songs": [...], "model_date": <token>,
  "version": <VERSION>}``; empty song list → 400; malformed body → 422
  (FastAPI's validation status).
- ``GET /`` (reference: :190-203): HTML test client with a seed sample.
- ``GET /test`` (reference: :150-153): 307 redirect to the docs.
- ``GET /docs`` + ``GET /openapi.json``: interactive-docs equivalent with
  the reference's three canned request examples (:158-174) — rendered
  without external CDN assets (this environment is egress-free).
- ``GET /healthz`` / ``GET /readyz``: liveness + fail-soft readiness — the
  fix for the reference's documented crash-loop-on-empty-PVC (its report
  risk #2; SURVEY.md §5): the pod comes up, readiness holds traffic until
  the first artifacts land.
- ``GET /metrics``: Prometheus text (absent in the reference; SURVEY.md §5).

The app core is transport-independent (``handle()`` maps a request tuple to
a response tuple) with a thin ``ThreadingHTTPServer`` adapter — testable
in-process, multi-threaded under load, no framework dependency.
"""

from __future__ import annotations

import json
import logging
import math
import os
import random
import threading
import time
from concurrent.futures import TimeoutError as FuturesTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from .. import faults
from ..config import ServingConfig
from ..io import iohealth
from ..observability import LoopLagMonitor, SloTracker, SpanRecorder
from .batcher import (
    DeadlineExceeded,
    NoHealthyReplicas,
    Overloaded,
    OverloadDegraded,
)
from .cache import RecommendCache
from .engine import RecommendEngine
from .mesh import MeshShardUnavailable
from .metrics import ServingMetrics

logger = logging.getLogger("kmlserver_tpu.serving")

_TEMPLATE_PATH = os.path.join(os.path.dirname(__file__), "templates", "client.html")

# The reference documents three canned request examples in its OpenAPI
# metadata (rest_api/app/main.py:158-174): typical seeds, uncommon seeds,
# and seeds absent from the rules (exercising the static fallback).
CANNED_EXAMPLES = {
    "normal": {
        "summary": "Typical seed songs",
        "value": {"songs": ["Yesterday", "Bohemian Rhapsody"]},
    },
    "uncommon": {
        "summary": "Uncommon seed songs (sparse rules)",
        "value": {"songs": ["Some Deep Cut B-Side"]},
    },
    "absent": {
        "summary": "Songs absent from the rules (static fallback)",
        "value": {"songs": ["Definitely Not A Real Song 123"]},
    },
}

Response = tuple[int, dict[str, str], bytes]


def is_loopback_host(client_host: str | None) -> bool:
    """THE loopback guard (ISSUE 12 satellite — one copy, four
    endpoints: ``/metrics/reset``, ``/debug/traces``, ``/debug/slo``,
    ``/debug/profile``). ``None`` is a direct in-process call (tests,
    embedding harnesses) — inherently local. A dual-stack server reports
    IPv4 loopback in IPv6-mapped form (``::ffff:127.0.0.1``): normalize
    before the check."""
    if client_host is None:
        return True
    host = client_host.removeprefix("::ffff:")
    return host in ("127.0.0.1", "::1")


def _json_response(status: int, obj) -> Response:
    body = json.dumps(obj).encode("utf-8")
    return status, {"Content-Type": "application/json"}, body


def _html_response(status: int, html: str) -> Response:
    return status, {"Content-Type": "text/html; charset=utf-8"}, html.encode("utf-8")


class RecommendApp:
    """Transport-independent app core."""

    # class-level defaults so hand-assembled test apps (``__new__`` +
    # attribute injection, no ``__init__``) keep working as the surface
    # grows — the affinity/routing layer is default-off anyway
    ring = None
    _ring_self = ""
    fleet_routing = False
    affinity_local_total = 0
    affinity_remote_total = 0
    misrouted_total = 0
    slo = None
    _profile_thread = None
    _profile_lock = threading.Lock()
    # predictive serving (ISSUE 17): default-off — a hand-assembled app
    # without __init__ behaves exactly reactively
    forecaster = None
    forecast_prefetch_total = 0
    # gray-failure spine (ISSUE 18): requests whose forwarded
    # X-KMLS-Deadline-Budget arrived already spent (wasted work a
    # downstream hop sheds, distinct from slow-compute "deadline"
    # degrades), and this replica's sorted-fleet index for the
    # fleet.peer stall fault site (None = fleet tier unarmed)
    deadline_expired_total = 0
    _fleet_index = None

    def __init__(
        self, cfg: ServingConfig, engine: RecommendEngine | None = None,
        *, defer_batcher: bool = False,
    ):
        self.cfg = cfg
        self.engine = engine or RecommendEngine(cfg)
        self.metrics = ServingMetrics()
        self.batcher = None
        # per-request span tracing (ISSUE 9): disabled by default
        # (KMLS_TRACE_SAMPLE=0 → recorder.enabled False, and every call
        # site checks that before allocating anything)
        self.recorder = SpanRecorder(
            sample=cfg.trace_sample,
            capacity=cfg.trace_buffer,
            slow_n=cfg.trace_slow_n,
        )
        # event-loop/scheduler stall collector: constructed here (the
        # robustness exposition reads it) but DRIVEN by the transports —
        # aioserver arms the loop drift tick, the threaded entrypoint a
        # sleep-drift thread — so in-process test apps spawn nothing
        self.loop_lag = (
            LoopLagMonitor(half_life_s=cfg.loop_lag_half_life_s)
            if cfg.loop_lag_half_life_s > 0
            else None
        )
        # SLO burn rates (ISSUE 12): multi-window budget consumption
        # computed lazily from the metrics counters/histograms whenever
        # /metrics or /debug/slo reads it — nothing on the request path
        self.slo = SloTracker(
            self.metrics,
            p99_target_ms=cfg.slo_p99_ms,
            error_budget=cfg.slo_error_budget,
            degrade_budget=cfg.slo_degrade_budget,
            fast_window_s=cfg.slo_fast_window_s,
            slow_window_s=cfg.slo_slow_window_s,
        )
        # one on-demand profiler capture at a time (/debug/profile —
        # utils/profiling.trace_session on a background thread; the lock
        # serializes check-and-start across handler threads)
        self._profile_thread = None
        self._profile_lock = threading.Lock()
        # epoch-keyed answer cache in front of the batcher (serving/cache
        # .py): a bundle hot swap invalidates it wholesale because the
        # engine's epoch is the key prefix — no flush coordination needed
        self.cache = (
            RecommendCache(cfg.cache_max_entries)
            if cfg.cache_enabled and cfg.cache_max_entries > 0
            else None
        )
        # continuous freshness (ISSUE 10): when the engine applies a delta
        # bundle in place (no epoch bump), only the keys whose seeds
        # intersect the delta's touched vocab may go stale — invalidate
        # exactly those instead of the wholesale epoch flush. The engine
        # notifies AFTER the patched bundle reference is live (the same
        # ordering contract the epoch bump rides), and `wholesale` applies
        # already invalidated via the epoch bump. getattr: engine test
        # doubles predating the delta path stay constructible.
        listeners = getattr(self.engine, "delta_listeners", None)
        if listeners is not None:
            listeners.append(self._on_delta_applied)
        # fleet cache tier (freshness/ring.py). Two arming levels over
        # ONE ring implementation — the same RendezvousRing the client
        # router and simulate_fleet use, so measurement, simulation and
        # routing can never disagree on an owner:
        #   KMLS_CACHE_AFFINITY=1        — measurement only (PR 10): count
        #       what fraction of traffic a router would keep local;
        #   KMLS_FLEET_PEERS non-empty   — owner-aware serving (ISSUE 15):
        #       the routing tier is live at the client/ingress, so a
        #       request this replica does not own is routing DRIFT —
        #       answer it locally (degrade gracefully, never fail), stamp
        #       X-KMLS-Cache-Owner, and count non-owned misses as
        #       kmls_cache_misrouted_total. The affinity counters keep
        #       running either way (local fraction ≈ routing health).
        self.ring = None
        self._ring_self = ""
        self.fleet_routing = False
        self.affinity_local_total = 0
        self.affinity_remote_total = 0
        self.misrouted_total = 0
        fleet_peers = [
            p.strip()
            for p in (getattr(cfg, "fleet_peers", "") or "").split(",")
            if p.strip()
        ]
        if fleet_peers or cfg.cache_affinity:
            import socket as socket_mod

            from ..freshness.ring import RendezvousRing

            if fleet_peers:
                me = (
                    getattr(cfg, "fleet_self", "")
                    or socket_mod.gethostname()
                )
                peers = fleet_peers
                self.fleet_routing = True
                if me not in peers:
                    # a SELF missing from PEERS means this replica would
                    # route ownership on an (N+1)-peer ring no client or
                    # sibling uses — the misrouted metric would measure
                    # the misconfig's noise, not routing drift. Keep
                    # serving (degrade, never fail) but say it loudly:
                    # this is the scaled-without-updating-PEERS drift the
                    # StatefulSet recipe warns about.
                    logger.error(
                        "KMLS_FLEET_SELF %r is not in KMLS_FLEET_PEERS "
                        "%r — this replica's ownership ring now differs "
                        "from the fleet's; kmls_cache_misrouted_total "
                        "will measure the misconfiguration, not routing "
                        "drift. Fix the peer list (it must track the "
                        "replica set exactly).", me, peers,
                    )
            else:
                me = cfg.cache_affinity_self or socket_mod.gethostname()
                peers = [
                    p.strip()
                    for p in (cfg.cache_affinity_peers or "").split(",")
                    if p.strip()
                ]
            if me not in peers:
                peers.append(me)
            self.ring = RendezvousRing(peers)
            self._ring_self = me
            if self.fleet_routing:
                # fleet.peer fault addressing (ISSUE 18): the stall site
                # keys replicas by sorted-peer index — stable across the
                # fleet regardless of each replica's KMLS_FLEET_PEERS
                # ordering, so a chaos harness can aim at exactly one
                self._fleet_index = sorted(peers).index(me)
        self.deadline_expired_total = 0
        # predictive serving (ISSUE 17): with KMLS_FORECAST=0 (default)
        # the hook stays None and every touchpoint — batcher submit,
        # utilization, post-delta pre-fetch — is one is-None check; the
        # forecast module's observation counter proves the zero cost,
        # compile-counter style (KMLS_COSTMODEL's pattern).
        self.forecaster = None
        self.forecast_prefetch_total = 0
        if getattr(cfg, "forecast_enabled", False):
            from .forecast import TrafficForecaster

            self.forecaster = TrafficForecaster(
                horizon_s=cfg.forecast_horizon_s,
                window_s=cfg.forecast_window_s,
                alpha=cfg.forecast_alpha,
                util_cap=cfg.forecast_util_cap,
                ramp_ratio=cfg.forecast_ramp_ratio,
                hot_top_n=cfg.forecast_prefetch_top_n,
            )
        # defer_batcher: the asyncio transport installs its loop-native
        # AsyncMicroBatcher instead — don't spawn the threaded pipeline
        if cfg.batch_window_ms > 0 and not defer_batcher:
            from .batcher import MicroBatcher

            self.batcher = MicroBatcher(
                self.engine, max_size=cfg.batch_max_size,
                window_ms=cfg.batch_window_ms,
                max_inflight=cfg.batch_max_inflight,
                adaptive=cfg.batch_adaptive_window,
                window_min_ms=cfg.batch_window_min_ms,
                shed_queue_budget_ms=cfg.shed_queue_budget_ms,
                shed_retry_after_s=cfg.shed_retry_after_s,
                shed_soft_ratio=cfg.shed_soft_ratio,
                shed_hard_ratio=cfg.shed_hard_ratio,
                shed_retry_jitter=cfg.shed_retry_jitter,
                eject_threshold=cfg.replica_eject_threshold,
                probe_interval_s=cfg.replica_probe_interval_s,
                redispatch_max=cfg.redispatch_max_retries,
                metrics=self.metrics,
                lag_monitor=self.loop_lag,
                forecaster=self.forecaster,
                recorder=self.recorder,
            )
        # template/static roots honor APP_PATH_FROM_ROOT like the reference
        # (rest_api/app/main.py:44-48 resolves its template/static dirs from
        # it; the static mount is :138): when that path carries
        # templates/static directories they take precedence — a deployment
        # can re-skin the client without rebuilding the image — else the
        # package's bundled copies serve.
        pkg_dir = os.path.dirname(__file__)
        root = cfg.app_path_from_root or ""
        template_path = _TEMPLATE_PATH
        self.static_dir = os.path.abspath(os.path.join(pkg_dir, "static"))
        if root:  # empty root must not probe CWD-relative paths
            custom_template = os.path.join(root, "templates", "client.html")
            if os.path.isfile(custom_template):
                template_path = custom_template
            custom_static = os.path.join(root, "static")
            if os.path.isdir(custom_static):
                self.static_dir = os.path.abspath(custom_static)
        with open(template_path, "r", encoding="utf-8") as fh:
            self._template = fh.read()

    # ---------- routing ----------

    def handle(
        self, method: str, path: str, body: bytes | None,
        client_host: str | None = None,
        trace_header: str | None = None,
        budget_header: str | None = None,
        fire_fleet_fault: bool = True,
    ) -> Response:
        path, _, query = path.partition("?")
        if method == "POST" and path in ("/api/recommend/", "/api/recommend"):
            return self._post_recommend(
                body, trace_header, budget_header,
                fire_fleet_fault=fire_fleet_fault,
            )
        if method == "POST" and path == "/metrics/reset":
            # measurement-harness hook: windows the latency percentiles
            # to one replay run. Loopback-only via the
            # shared guard (is_loopback_host — one copy for all four
            # guarded endpoints).
            if not is_loopback_host(client_host):
                return _json_response(403, {"detail": "localhost only"})
            discarded = self.metrics.reset_latency()
            return _json_response(
                200, {"status": "reset", "discarded": discarded}
            )
        if method == "GET":
            if path == "/":
                return self._get_client()
            if path == "/test":
                # reference: /test deep-links into the interactive docs
                return 307, {"Location": "/docs#post-api-recommend"}, b""
            if path == "/docs":
                return self._get_docs()
            if path == "/openapi.json":
                return _json_response(200, self._openapi())
            if path == "/healthz":
                return _json_response(200, {"status": "alive"})
            if path == "/readyz":
                if self.engine.finished_loading:
                    # degraded = ready-but-flagged (HTTP 200): the pod
                    # keeps taking traffic — it still answers every
                    # request, some from the last-good bundle or the
                    # fallback — so a bad artifact on the shared PVC can
                    # never readiness-fail ALL replicas at once. A 503
                    # here would restart-loop the whole fleet over data
                    # no restart can fix.
                    ages = {
                        name: round(age, 3)
                        for name, age in self._artifact_ages().items()
                    }
                    reasons = self.degraded_reasons()
                    if reasons:
                        return _json_response(
                            200, {
                                "status": "degraded", "reasons": reasons,
                                "artifact_age_seconds": ages,
                            }
                        )
                    return _json_response(
                        200,
                        {"status": "ready", "artifact_age_seconds": ages},
                    )
                return _json_response(
                    503, {"status": "awaiting first artifacts"}
                )
            if path == "/debug/traces":
                # retained traces, JSON: the per-request WHY behind a
                # /metrics percentile (tail-based retention — see
                # observability/trace.py). Bounded payload: the ring caps
                # at KMLS_TRACE_BUFFER entries. Loopback-only, exactly
                # like /metrics/reset above: retained traces carry request
                # payloads (seed songs in span attrs and shed/degraded
                # bodies) and must not be fleet-scrapeable by default —
                # the tracejoin tooling runs next to the pod it debugs.
                if not is_loopback_host(client_host):
                    return _json_response(403, {"detail": "localhost only"})
                return _json_response(200, self.recorder.debug_payload())
            if path == "/debug/slo":
                # burn-rate detail (ISSUE 12): targets, windows, the
                # cumulative inputs, fast+slow burn per SLO. Loopback-
                # only like its /debug siblings — same policy, same
                # shared guard (fleet scraping belongs to /metrics,
                # which carries the kmls_slo_burn_rate gauges).
                if not is_loopback_host(client_host):
                    return _json_response(403, {"detail": "localhost only"})
                if self.slo is None:
                    return _json_response(
                        404, {"detail": "slo tracker not configured"}
                    )
                return _json_response(200, self.slo.debug_payload())
            if path == "/debug/profile":
                if not is_loopback_host(client_host):
                    return _json_response(403, {"detail": "localhost only"})
                return self._debug_profile(query)
            if path == "/metrics":
                # ONE age snapshot per scrape: the age gauges and the
                # stale flags must describe the same instant, and the
                # underlying os.stat pass must not run three times
                ages = self._artifact_ages()
                text = self.metrics.render(
                    self.engine.reload_counter, self.engine.finished_loading,
                    cache=self.cache,
                    dispatch_counts=getattr(
                        self.engine, "dispatch_counts", None
                    ),
                    robustness=self._robustness_state(),
                    shard_counts=getattr(
                        self.engine, "shard_dispatch_counts", None
                    ),
                    seed_slots=(
                        getattr(self.engine, "seed_slots_real", 0),
                        getattr(self.engine, "seed_slots_padded", 0),
                    ),
                    cost=getattr(self.engine, "cost_model", None),
                    slo=self.slo,
                    artifact_ages=ages,
                    artifact_stale=self._artifact_stale_flags(ages),
                    mesh_shards=self._mesh_shard_states(),
                    io=iohealth.MONITOR.snapshot(),
                    shard_placement=self.engine.shard_placement(),
                )
                return 200, {"Content-Type": "text/plain; version=0.0.4"}, text.encode()
            if path.startswith("/static/"):
                return self._get_static(path[len("/static/"):])
        return _json_response(404, {"detail": "Not Found"})

    def _robustness_state(self) -> dict:
        """Engine/batcher recovery-state snapshot for /metrics (names
        ending in _total render as counters, the rest as gauges)."""
        state = {
            "artifact_quarantines_total": getattr(
                self.engine, "artifact_quarantines", 0
            ),
            "reload_failures_total": getattr(
                self.engine, "reload_failures", 0
            ),
            "reload_consecutive_failures": getattr(
                self.engine, "consecutive_reload_failures", 0
            ),
            # second model family: is the hybrid merge live, and how many
            # embedding-artifact loads degraded to rules-only
            "embedding_active": int(
                getattr(self.engine, "embedding_active", False)
            ),
            "embedding_load_failures_total": getattr(
                self.engine, "embedding_load_failures", 0
            ),
            # model layout: how many vocab shards the published bundle
            # spans (1 = replicated — a dashboard can alert on a fleet
            # unexpectedly flipping layout after a publication)
            "model_shards": getattr(self.engine, "n_shards", 1),
            # continuous freshness (ISSUE 10): delta bundles applied in
            # place vs rejected (torn/wrong-base/injected), the chain
            # position currently serving, and the age of the newest
            # APPLIED generation — the freshness-lag number the delta
            # path exists to shrink
            "delta_applied_total": getattr(
                self.engine, "delta_applied_total", 0
            ),
            "delta_rejected_total": getattr(
                self.engine, "delta_rejected_total", 0
            ),
            "delta_seq": getattr(self.engine, "delta_seq", 0),
            # quality loop (ISSUE 14): the published chain length (the
            # compaction trigger's observable) and the EFFECTIVE hybrid
            # blend weight (the measured optimum under
            # KMLS_HYBRID_BLEND_WEIGHT=measured, else the knob)
            "delta_chain_length": getattr(
                self.engine, "delta_chain_length", 0
            ),
            "hybrid_blend_weight": round(
                getattr(
                    self.engine, "blend_weight",
                    getattr(self.cfg, "hybrid_blend_weight", 0.5),
                ), 4
            ),
            "freshness_lag_seconds": round(
                getattr(self.engine, "freshness_lag_s", lambda: 0.0)(), 3
            ),
            # fleet cache affinity: what fraction of traffic a rendezvous
            # router would keep on this replica (0/0 with the layer off)
            "cache_affinity_local_total": self.affinity_local_total,
            "cache_affinity_remote_total": self.affinity_remote_total,
            # fleet cache routing (ISSUE 15): non-owned MISSES this
            # replica answered locally — routing drift at the ingress/
            # client (0 while routing is healthy or the tier is off) —
            # and the configured routing-ring size (0 = tier unarmed)
            "cache_misrouted_total": self.misrouted_total,
            "fleet_peers": (
                len(self.ring.peers)
                if (self.fleet_routing and self.ring is not None)
                else 0
            ),
        }
        ejected_fn = getattr(self.batcher, "ejected_replicas", None)
        state["replicas_ejected"] = (
            len(ejected_fn()) if callable(ejected_fn) else 0
        )
        # the autoscaling signal (ISSUE 8): kmls_utilization is what
        # kubernetes/hpa.yaml scales the fleet on — max of pipeline
        # occupancy and admission queue pressure, 1.0 = at capacity,
        # plus (forecaster armed) the bounded predictive lead term.
        # Always present (0.0 without a batcher) so the HPA's metric
        # query never comes back empty on an idle pod.
        parts_fn = getattr(self.batcher, "utilization_parts", None)
        if callable(parts_fn):
            reactive, led = parts_fn()
        else:
            util_fn = getattr(self.batcher, "utilization", None)
            reactive = led = util_fn() if callable(util_fn) else 0.0
        state["utilization"] = round(led, 4)
        if self.forecaster is not None:
            # predictive serving (ISSUE 17): the forecast's ADDED lead
            # over the reactive signal (0 at steady state — dashboards
            # see how much of kmls_utilization is prediction), the
            # rate/prediction/ratio snapshot, the zero-cost proof
            # counter, and the two actuator counters
            snap = self.forecaster.snapshot()
            state["utilization_forecast"] = round(max(0.0, led - reactive), 4)
            state["forecast_rate"] = round(snap["rate"], 3)
            state["forecast_predicted_rate"] = round(
                snap["predicted_rate"], 3
            )
            state["forecast_ratio"] = round(snap["ratio"], 4)
            state["forecast_observations_total"] = snap["observations"]
            state["forecast_prefetch_total"] = self.forecast_prefetch_total
            state["forecast_prewarm_total"] = getattr(
                self.batcher, "prewarm_total", 0
            )
        # overload-degrade admissions (the ladder rung before any 429)
        state["admission_degrade_total"] = getattr(
            self.batcher, "degrade_total", 0
        )
        # runtime health (ISSUE 9): the decayed loop/scheduler stall
        # estimate the admission ladder also folds into pressure — 0.0
        # with the collector disabled so the series always exists
        state["loop_lag_ms"] = (
            round(self.loop_lag.lag_s() * 1e3, 3)
            if self.loop_lag is not None
            else 0.0
        )
        # gray-failure spine (ISSUE 18): deadline propagation + mesh
        # hedging observables. All 0 with KMLS_HEDGE=0 / no forwarded
        # budgets — the hedge counters double as the zero-cost proof
        # (pinned by test, costmodel-counter style). expired_on_arrival
        # lives on the mesh WORKER (budget-shed frames); the hedge
        # outcome counters + slow-peer ladder on the COORDINATOR.
        state["deadline_expired_total"] = self.deadline_expired_total
        mesh = getattr(self.engine, "mesh_coordinator", None)
        worker = getattr(self.engine, "mesh_worker", None)
        state["hedge_wins_total"] = getattr(mesh, "hedge_wins", 0)
        state["hedge_losses_total"] = getattr(mesh, "hedge_losses", 0)
        state["hedge_cancelled_total"] = getattr(mesh, "hedge_cancelled", 0)
        state["peer_slow_ejections_total"] = getattr(
            mesh, "slow_ejections", 0
        )
        state["peer_slow_readmissions_total"] = getattr(
            mesh, "slow_readmissions", 0
        )
        slow_fn = getattr(mesh, "slow_ranks", None)
        state["peer_slow"] = len(slow_fn()) if callable(slow_fn) else 0
        state["mesh_straggler_degraded_total"] = getattr(
            self.engine, "mesh_straggler_degraded", 0
        )
        state["mesh_expired_on_arrival_total"] = getattr(
            worker, "expired_on_arrival", 0
        )
        # dispatches that paid a compile on the serving path (all four
        # sites in engine.py bump the one attribute) — must stay 0
        state["unwarmed_dispatches_total"] = getattr(
            self.engine, "unwarmed_dispatches", 0
        )
        # span-tracing bookkeeping: began is the zero-cost proof counter
        # (must stay 0 while KMLS_TRACE_SAMPLE=0)
        state["traces_began_total"] = self.recorder.began
        state["traces_retained_total"] = self.recorder.retained_total
        state["trace_buffer_entries"] = (
            self.recorder.retained() if self.recorder.enabled else 0
        )
        return state

    def _artifact_ages(self) -> dict:
        """Per-artifact freshness ages from the engine (empty before the
        first load, or with an engine test double predating the API)."""
        ages_fn = getattr(self.engine, "artifact_ages", None)
        return ages_fn() if callable(ages_fn) else {}

    def _stale_artifacts(
        self, ages: dict | None = None
    ) -> list[tuple[str, float]]:
        """Artifacts over the KMLS_ARTIFACT_MAX_AGE_S bound, as sorted
        (name, age) pairs — empty with the bound disabled (0). ``ages``
        lets a caller that already snapshotted the age dict reuse it
        (one os.stat pass per scrape, and age + staleness always come
        from the SAME snapshot)."""
        max_age = getattr(self.cfg, "artifact_max_age_s", 0.0)
        if max_age <= 0:
            return []
        if ages is None:
            ages = self._artifact_ages()
        return sorted(
            (name, age) for name, age in ages.items() if age > max_age
        )

    def _artifact_stale_flags(self, ages: dict) -> dict:
        """artifact → 0/1 staleness flags for the kmls_artifact_stale
        gauge, derived from the SAME age snapshot the age gauges render
        (all 0 with the bound disabled — the series still exists
        wherever ages do, so dashboards can alert on a flip)."""
        stale = {name for name, _age in self._stale_artifacts(ages)}
        return {name: int(name in stale) for name in ages}

    def _debug_profile(self, query: str) -> Response:
        """``GET /debug/profile?seconds=N`` (ISSUE 12): capture a
        ``jax.profiler`` trace of the live server for N seconds through
        ``utils/profiling.trace_session`` — the same opt-in policy as
        offline profiling: ``KMLS_PROFILE_DIR`` must be set or the
        capture is refused (409), so production serving can never be
        profiled by accident. The capture runs on a background thread
        (the async transport handles this route ON the loop — blocking
        N seconds here would freeze every connection) and the response
        returns immediately with the dump directory; one capture at a
        time."""
        from ..utils import profiling

        target = profiling.profile_dir()
        if target is None:
            return _json_response(
                409,
                {"detail": "profiling disabled: set KMLS_PROFILE_DIR "
                           "to enable /debug/profile captures"},
            )
        try:
            params = dict(
                pair.split("=", 1) for pair in query.split("&") if "=" in pair
            )
            seconds = float(params.get("seconds", "5"))
        except ValueError:
            seconds = float("nan")
        if not math.isfinite(seconds):
            # nan/inf slide through a min/max clamp (comparisons are
            # false), then kill the capture thread AFTER the 202 — reject
            # up front instead
            return _json_response(
                422, {"detail": "seconds must be a finite number"}
            )
        seconds = min(max(seconds, 0.05), 120.0)
        label = f"serve-capture-{int(time.time())}"
        # check-and-start under a lock: jax allows ONE active profiler
        # session, so two racing requests must not both start a capture
        # (the loser's thread would die after its 202 already went out)
        with self._profile_lock:
            thread = self._profile_thread
            if thread is not None and thread.is_alive():
                return _json_response(
                    409, {"detail": "a profile capture is already running"}
                )
            # the capture switches the recorder's capture mode itself
            self._profile_thread = profiling.start_capture(
                label, seconds, recorder=getattr(self, "recorder", None)
            )
        return _json_response(
            202,
            {
                "status": "capturing",
                "seconds": seconds,
                "label": label,
                "dir": os.path.join(target, label),
            },
        )

    _STATIC_TYPES = {
        ".css": "text/css; charset=utf-8",
        ".js": "text/javascript; charset=utf-8",
        ".html": "text/html; charset=utf-8",
        ".json": "application/json",
        ".svg": "image/svg+xml",
        ".png": "image/png",
        ".ico": "image/x-icon",
    }

    def _get_static(self, rel: str) -> Response:
        """Static assets under the resolved static root — the reference's
        ``/static`` mount (rest_api/app/main.py:138). Paths are confined to
        the root after symlink resolution, so neither ``..`` traversal nor
        a symlink planted inside an operator-supplied static dir can reach
        outside it."""
        full = os.path.realpath(os.path.join(self.static_dir, rel))
        root = os.path.realpath(self.static_dir)
        if not full.startswith(root + os.sep):
            return _json_response(404, {"detail": "Not Found"})
        try:
            # kmls-verify: allow[loopblock] — deliberate: static assets
            # are a handful of small local files (dashboard HTML/JS) on
            # the container image, not the PVC; a sub-ms read is cheaper
            # than an executor hop and the route is cold
            with open(full, "rb") as fh:
                data = fh.read()
        except (OSError, IsADirectoryError):
            return _json_response(404, {"detail": "Not Found"})
        ctype = self._STATIC_TYPES.get(
            os.path.splitext(full)[1].lower(), "application/octet-stream"
        )
        return 200, {"Content-Type": ctype}, data

    # ---------- endpoints ----------

    def _validate_recommend(
        self, body: bytes | None
    ) -> tuple[Response | None, list[str] | None]:
        """→ (error response, None) or (None, songs)."""
        try:
            payload = json.loads(body or b"")
        except json.JSONDecodeError:
            return _json_response(
                422, {"detail": [{"msg": "request body is not valid JSON"}]}
            ), None
        songs = payload.get("songs") if isinstance(payload, dict) else None
        if not isinstance(songs, list) or not all(isinstance(s, str) for s in songs):
            return _json_response(
                422,
                {"detail": [{"loc": ["body", "songs"],
                             "msg": "field 'songs' must be a list of strings"}]},
            ), None
        if not songs:
            # reference: empty request → 400 (rest_api/app/main.py:178-179)
            return _json_response(400, {"detail": "Request with no songs"}), None
        return None, songs

    # ---------- span tracing (ISSUE 9) ----------

    def _trace_begin(
        self, header: str | None, t_start: float, defer: bool = False,
    ):
        """→ a TraceContext for this request, or None. The one
        ``active`` check is the ENTIRE per-request cost with tracing
        disabled (KMLS_TRACE_SAMPLE=0, no capture open): no context, no
        id generation, no allocation — the recorder's ``began`` counter
        proves it. The trace starts at ``t_start`` (where the request
        began) and its ``parse`` span ends here: body read, JSON decode
        and validation are behind us. ``defer``: the transport, not the
        response builder, finishes the trace (:meth:`trace_written`)."""
        rec = self.recorder
        if not rec.active:
            return None
        trace = rec.begin(header, t_start)
        if trace is not None:
            trace.deferred = defer
            trace.span("parse", t_start, time.perf_counter())
        return trace

    def _trace_finish(self, trace, status: str, headers: dict) -> None:
        """The response is built: echo ``X-KMLS-Trace`` so a replay/bench
        client can join its client-side timing to the server-side span
        breakdown, and close the trace (tail-based retention decides
        whether it is kept) — unless the transport closes it after its
        write, in which case only the status is stamped."""
        if trace is None:
            return
        headers["X-KMLS-Trace"] = trace.trace_id
        if trace.deferred:
            trace.status = status
        else:
            self.recorder.finish(
                trace, status, time.perf_counter() - trace.t0
            )

    def trace_written(
        self, trace, t_start: float = 0.0, t_end: float = 0.0,
    ) -> None:
        """The async transport's half of a deferred trace: the response
        has been passed to ``transport.write`` (``t_start``→``t_end``,
        the ``write`` span; none where the connection was gone), so the
        root ``request`` span ends and the trace closes."""
        if t_end:
            trace.span("write", t_start, t_end)
        else:
            t_end = time.perf_counter()
        # "open": no response was ever built (the client hung up first)
        status = "disconnected" if trace.status == "open" else trace.status
        self.recorder.finish(trace, status, t_end - trace.t0)

    # ---------- degradation (the fault-tolerance contract) ----------

    def _deadline_for(self, t0: float) -> float | None:
        """Per-request perf_counter deadline from the configured budget
        (KMLS_REQUEST_DEADLINE_MS), propagated cache → batcher → device.
        None = deadlines off."""
        budget_ms = self.cfg.request_deadline_ms
        return t0 + budget_ms / 1e3 if budget_ms > 0 else None

    def _effective_deadline(
        self, t0: float, budget_header: str | None
    ) -> tuple[float | None, float | None, bool]:
        """Cross-hop deadline propagation (ISSUE 18): the effective
        deadline is the TIGHTER of the local budget
        (KMLS_REQUEST_DEADLINE_MS) and the remaining milliseconds an
        upstream hop forwarded on ``X-KMLS-Deadline-Budget`` →
        ``(deadline, forwarded_budget_ms, expired)``. ``expired=True``
        means the budget arrived already spent: the caller answers the
        degraded fallback IMMEDIATELY — counting wasted work
        (kmls_deadline_expired_total), not slow compute. A malformed
        header is ignored (local budget only): deadline propagation
        must never turn a bad proxy into an outage."""
        deadline = self._deadline_for(t0)
        if not budget_header:
            return deadline, None, False
        try:
            budget_ms = float(budget_header)
        except (TypeError, ValueError):
            return deadline, None, False
        if not math.isfinite(budget_ms):
            return deadline, None, False
        if budget_ms <= 0.0:
            return deadline, budget_ms, True
        remote = t0 + budget_ms / 1e3
        if deadline is None or remote < deadline:
            deadline = remote
        return deadline, budget_ms, False

    @staticmethod
    def _degrade_reason(exc: Exception) -> str | None:
        """Exceptions that degrade to a fallback answer instead of an
        error status: deadline exhaustion, total replica loss, and the
        admission controller's degrade band (the ladder rung BEFORE any
        429 — overload costs answer quality first, availability never)."""
        if isinstance(exc, DeadlineExceeded):
            return "deadline"
        if isinstance(exc, NoHealthyReplicas):
            return "replica-loss"
        if isinstance(exc, OverloadDegraded):
            return "overload"
        return None

    def _stamp_owner(
        self, headers: dict, songs: list[str] | None, cached: bool
    ) -> None:
        """Owner-aware serving (ISSUE 15): with the fleet routing tier
        armed (KMLS_FLEET_PEERS), a request whose rendezvous owner is
        another replica is mis-routed traffic — it is still ANSWERED
        locally (mis-routes degrade gracefully, never fail), but the
        response stamps ``X-KMLS-Cache-Owner`` so the router/operator
        can see the drift, and a non-owned MISS (work the owner's cache
        already holds) counts ``kmls_cache_misrouted_total``. Cache hits
        are stamped but not counted: a hit did no duplicate device work.
        GIL-coalesced adds, same benign-race budget as the affinity
        counters. This is the ONE owner computation in routing mode —
        the affinity counters ride the same digest instead of paying a
        second one in _cache_lookup_or_lead (answered requests only;
        sheds/errors never reach a response builder, which is exactly
        the traffic the ownership fraction should describe)."""
        if not self.fleet_routing or self.ring is None or not songs:
            return
        from ..freshness.ring import seeds_key

        owner = self.ring.owner(seeds_key(songs))
        if owner == self._ring_self:
            self.affinity_local_total += 1
            return
        self.affinity_remote_total += 1
        # identities come from operator env config: strip CR/LF so a
        # malformed peer list can never smuggle a header line
        headers["X-KMLS-Cache-Owner"] = (
            owner.replace("\r", "").replace("\n", "")
        )
        if not cached:
            self.misrouted_total += 1

    def _degraded_response(
        self, t0: float, songs: list[str], reason: str, trace=None
    ) -> Response:
        """200 with the latency-budgeted popularity fallback and an
        ``X-KMLS-Degraded: <reason>`` header — the degradation contract:
        a slow device or a dead replica set costs answer QUALITY, never a
        5xx. The fallback itself runs under the tighter of the request
        deadline and its own budget (KMLS_FALLBACK_BUDGET_MS), so the
        degraded path can't compound the overrun."""
        budget = time.perf_counter() + self.cfg.fallback_budget_ms / 1e3
        deadline = self._deadline_for(t0)
        deadline = budget if deadline is None else min(deadline, budget)
        recs = self.engine.static_recommendation(songs, deadline=deadline)
        self.metrics.record_degraded(reason)
        self.metrics.record("fallback", time.perf_counter() - t0)
        status, headers, payload = _json_response(
            200,
            {
                "songs": recs,
                "model_date": self.engine.cache_value,
                "version": self.cfg.version,
            },
        )
        headers["X-KMLS-Degraded"] = reason
        self._stamp_owner(headers, songs, cached=False)
        if trace is not None:
            # the ladder decision rides a span attribute: "overload" IS
            # the admission controller's degrade rung; deadline/replica-
            # loss degradations carry their reason the same way
            trace.annotate("reason", reason)
            if reason == "overload":
                trace.annotate("admission", "degrade")
            self._trace_finish(trace, "degraded", headers)
        return status, headers, payload

    # ---------- pod-spanning serve mesh (ISSUE 16) ----------

    def _mesh_missing_shards(self, probe: bool = False) -> list[int]:
        """Missing gang ranks from the engine's mesh coordinator — empty
        when the serve mesh is off, the gang is whole, or the engine is a
        test double predating the API. ``probe=True`` makes the caller a
        re-form detector: the coordinator re-auditions dark peers (rate-
        limited to one probe per interval), so a restarted gang member is
        re-admitted by the very traffic that found it missing."""
        fn = getattr(self.engine, "mesh_missing_shards", None)
        if not callable(fn):
            return []
        return fn(probe=probe)

    def _mesh_shard_states(self) -> dict | None:
        """``{"serving": n, "missing": m}`` for the
        kmls_serve_mesh_shards gauge, or None with the mesh off — the
        series only exists on gang members, so a replicated pod never
        exports a phantom one-member gang."""
        gang = getattr(self.engine, "gang", None)
        if gang is None:
            return None
        missing = self._mesh_missing_shards()
        return {
            "serving": gang.size - len(missing), "missing": len(missing)
        }

    def _mesh_shard_response(
        self, t0: float, songs: list[str], rank: int, trace=None
    ) -> Response:
        """Answer policy when a vocab shard (a gang member) is dark.
        With the fleet routing tier armed this gang is NOT the last line
        of defense — 503 + ``X-KMLS-Mesh-Unavailable: <rank>`` tells the
        router which shard to blame and spills the key to the next ring
        peer (the replay client counts it ``mesh_unavailable``, never
        http_5xx; Retry-After paces re-dispatch against the re-admission
        probe). Standalone, the degradation contract holds: shard loss
        costs answer QUALITY (popularity fallback), never availability."""
        rank = int(rank)
        if self.fleet_routing:
            status, headers, payload = _json_response(
                503,
                {"detail": f"serve mesh degraded: vocab shard {rank} "
                           "unavailable"},
            )
            headers["X-KMLS-Mesh-Unavailable"] = str(rank)
            # PR 8's Retry-After contract (the 429 path below): RFC 9110
            # delay-seconds is a non-negative INTEGER, and a bounded
            # jitter (KMLS_SHED_RETRY_JITTER) de-synchronizes the retry
            # storm — the un-jittered constant here re-synchronized
            # every spilled client onto the same probe tick
            base = max(self.cfg.replica_probe_interval_s, 1.0)
            jitter = max(0.0, getattr(self.cfg, "shed_retry_jitter", 0.0))
            if jitter > 0.0:
                base = random.uniform(
                    base * (1.0 - jitter), base * (1.0 + jitter)
                )
            headers["Retry-After"] = str(math.ceil(max(base, 0.0)))
            self.metrics.record_degraded(f"mesh-shard-missing:{rank}")
            if trace is not None:
                trace.annotate("mesh_shard_missing", rank)
                self._trace_finish(trace, "mesh-unavailable", headers)
            return status, headers, payload
        return self._degraded_response(
            t0, songs, f"mesh-shard-missing:{rank}", trace=trace
        )

    def degraded_reasons(self) -> list[str]:
        """Why /readyz says "degraded" (empty = fully healthy): reloads
        failing while the last-good bundle keeps serving, and/or replicas
        currently ejected by the batcher's circuit breaker."""
        reasons: list[str] = []
        consec = getattr(self.engine, "consecutive_reload_failures", 0)
        if consec > 0:
            reasons.append(
                f"reload failing x{consec} (serving last-good bundle)"
            )
        if getattr(self.engine, "embedding_degraded", False):
            # a PUBLISHED embeddings.npz failed validation/parse: the
            # bundle serves rules-only — answered, but flagged so the
            # operator knows the second model family is dark
            reasons.append("embedding artifact unusable (serving rules-only)")
        # staleness bound (ISSUE 14): any served artifact older than
        # KMLS_ARTIFACT_MAX_AGE_S flags ready-but-degraded BY NAME — an
        # aging embeddings.npz becomes an operator signal before it
        # misleads. 0 (the default) keeps the age gauges purely
        # observational.
        stale = self._stale_artifacts()
        if stale:
            max_age = self.cfg.artifact_max_age_s
            reasons.append(
                "artifacts stale (> "
                f"{max_age:.0f}s): "
                + ", ".join(
                    f"{name} ({age:.0f}s)" for name, age in stale
                )
            )
        ejected_fn = getattr(self.batcher, "ejected_replicas", None)
        if callable(ejected_fn):
            ejected = ejected_fn()
            if ejected:
                reasons.append(f"replicas ejected: {ejected}")
        # pod-spanning serve mesh (ISSUE 16): a dark gang member means a
        # vocab slab is unservable — ready-but-degraded BY RANK, and
        # probe=True makes every /readyz scrape double as the re-form
        # detector (the kubelet's readiness polling re-admits a restarted
        # member even on an otherwise idle pod)
        for rank in self._mesh_missing_shards(probe=True):
            reasons.append(f"serve_mesh_shard_missing:{rank}")
        # storage gray-failure spine (ISSUE 19): the IO-health monitor
        # convicted the artifact plane as slow (latency EWMA past
        # KMLS_IO_SLOW_MS). Degraded, NOT unready — serving runs from
        # memory; a slow PVC must never knock a healthy replica out of
        # the load balancer.
        if iohealth.MONITOR.storage_slow():
            reasons.append("storage-slow")
        return reasons

    def _recommend_error_response(self, exc: Exception, trace=None) -> Response:
        if isinstance(exc, Overloaded):
            # visible backpressure, not an error: the queue projection says
            # this request would outwait the shed budget — tell the client
            # when to come back instead of letting it rot in the queue
            status, headers, payload = _json_response(
                429,
                {"detail": "overloaded: projected queue wait "
                           f"{exc.projected_wait_ms:.0f}ms exceeds budget"},
            )
            # RFC 9110 delay-seconds is a non-negative INTEGER — a decimal
            # here crashes urllib3's Retry.parse_retry_after (the requests
            # default). ceil keeps the batcher's sub-second jitter
            # (KMLS_SHED_RETRY_JITTER) meaningful: uniform base·(1 ± j)
            # ceils to a spread across adjacent whole seconds instead of
            # rounding every draw back to the same synchronized value
            headers["Retry-After"] = str(math.ceil(max(exc.retry_after_s, 0.0)))
            if trace is not None:
                trace.annotate("admission", "shed")
                trace.annotate("retry_after_s", round(exc.retry_after_s, 3))
                self._trace_finish(trace, "shed", headers)
            return status, headers, payload
        logger.error("recommendation failed", exc_info=exc)
        self.metrics.record_error()
        status, headers, payload = _json_response(
            500, {"detail": "Internal Server Error"}
        )
        if trace is not None:
            trace.annotate("error", type(exc).__name__)
            self._trace_finish(trace, "error", headers)
        return status, headers, payload

    def _recommend_result_response(
        self, t0: float, recs: list[str], source: str, cached: bool = False,
        trace=None, songs: list[str] | None = None,
    ) -> Response:
        # respond span: answer-available (the future just resolved — the
        # caller invokes this immediately after) → response bytes built
        # (the engine's own blend/id→name is the batch trace's `compose`)
        t_respond = time.perf_counter() if trace is not None else 0.0
        self.metrics.record(source, time.perf_counter() - t0)
        status, headers, payload = _json_response(
            200,
            {
                "songs": recs,
                "model_date": self.engine.cache_value,
                "version": self.cfg.version,
            },
        )
        self._stamp_owner(headers, songs, cached=cached)
        if cached:
            # lets load harnesses (serving/replay.py) split cached vs
            # computed latency without guessing from timing
            headers["X-KMLS-Cache"] = "hit"
        # gray-failure spine (ISSUE 18): a "degraded:<reason>" source is
        # an ANSWERED-but-partial result (e.g. a mesh merge that dropped
        # a straggler slab) — same contract surface as the popularity
        # fallback: X-KMLS-Degraded + the degraded counter. The cache
        # layer independently refuses to store these (cache.put), so one
        # slow moment can't pin a partial answer past the gang recovering.
        degraded = source.startswith("degraded:")
        if degraded:
            reason = source.partition(":")[2] or source
            headers["X-KMLS-Degraded"] = reason
            self.metrics.record_degraded(reason)
        if trace is not None:
            trace.span(
                "respond", t_respond, time.perf_counter(),
                {"source": source},
            )
            if cached:
                trace.annotate("cached", True)
            if degraded:
                trace.annotate("reason", source.partition(":")[2] or source)
            self._trace_finish(trace, "ok", headers)
        return status, headers, payload

    def _on_delta_applied(self, touched: set, wholesale: bool) -> None:
        """Engine callback after a delta bundle swapped in: selectively
        invalidate the touched seed keys (wholesale applies bumped the
        epoch, which already invalidates every key for free), then —
        forecaster armed — re-materialize the predicted-hot sets the
        invalidation just cooled (actuator c)."""
        if self.cache is None or wholesale:
            return
        dropped = self.cache.invalidate_seeds(set(touched))
        logger.info(
            "delta applied: %d touched names, %d cache entries invalidated "
            "selectively", len(touched), dropped,
        )
        if self.forecaster is not None:
            names = set(touched)
            loop = getattr(self.batcher, "_loop", None)
            if loop is not None:
                # loop-native batcher: submit() is loop-confined, and this
                # callback runs on the engine's reload/delta thread — hop
                try:
                    loop.call_soon_threadsafe(self._forecast_prefetch, names)
                except RuntimeError:
                    pass  # loop already closed: a missed pre-fetch is fine
            else:
                self._forecast_prefetch(names)

    def _forecast_prefetch(self, touched: set) -> int:
        """Targeted cache pre-fetch (ISSUE 17, actuator c): for each
        predicted-hot seed set that (a) the delta just cooled (its seeds
        intersect ``touched``), (b) THIS replica owns on the rendezvous
        ring (owner only, never broadcast — no ring means every key is
        local), and (c) is not still cached, lead a normal singleflight
        batcher submission so the entry is warm before the next real
        request misses on it. Competing with live traffic is forbidden:
        the first admission-ladder rejection (Overloaded/degrade/
        no-replicas — or a loop-confinement error from a mis-threaded
        call) abandons the whole pass. → pre-fetch leads started."""
        f = self.forecaster
        if (
            f is None or self.cache is None or self.batcher is None
            or not hasattr(self.batcher, "submit")
        ):
            return 0
        from ..freshness.ring import seeds_key

        started = 0
        for seeds in f.hot_seed_sets(
            getattr(self.cfg, "forecast_prefetch_top_n", 8)
        ):
            if not any(s in touched for s in seeds):
                continue  # the delta didn't cool this set — still cached
            if self.ring is not None and not self.ring.owns(
                seeds_key(seeds), self._ring_self
            ):
                continue  # another replica's key: its owner pre-fetches it
            key = self._cache_key(seeds)
            if self.cache.contains(key):
                continue
            try:
                future, joined = self.cache.join_or_lead(
                    key, lambda s=seeds: self.batcher.submit(s)
                )
            except Exception:
                break  # overloaded or unhealthy: never compete with traffic
            if not joined:
                cache = self.cache
                future.add_done_callback(
                    lambda fut, k=key: cache.finish(k, fut)
                )
                started += 1
        self.forecast_prefetch_total += started
        return started

    def _cache_key(self, songs: list[str]) -> tuple:
        if self.cache is not None:
            return self.cache.make_key(
                self.engine.bundle_epoch, songs, self.cfg.max_seed_tracks
            )
        return RecommendCache.key(
            self.engine.bundle_epoch, songs, self.cfg.max_seed_tracks
        )

    def _cache_lookup_or_lead(
        self, songs: list[str], deadline: float | None = None, trace=None,
    ):
        """The ONE copy of the cache front half, shared by both
        transports → ``("hit", (songs, source))`` | ``("flight",
        future)`` | ``("off", None)``. A miss joins the in-flight
        singleflight future for this key or leads a new batcher
        submission (the leader's done-callback stores the answer);
        raises what ``batcher.submit`` raises (Overloaded and
        NoHealthyReplicas included). ``deadline`` rides into the batcher
        only when set — test doubles keep their bare ``submit(seeds)``
        signature. "off" covers: cache disabled, no batcher, or a batcher
        without ``submit`` (test doubles) — callers compute inline there."""
        if self.ring is not None and not self.fleet_routing:
            # affinity accounting (measurement mode) on the ONE path both
            # transports share: is THIS replica the rendezvous owner of
            # the request's cache key? (counters only — no routing;
            # GIL-coalesced adds, same benign-race budget as the
            # batcher's in-flight counts). In ROUTING mode _stamp_owner
            # drives these counters from its single owner computation
            # instead — one seeds sort + N digests per request, not two.
            from ..freshness.ring import seeds_key

            if self.ring.owner(seeds_key(songs)) == self._ring_self:
                self.affinity_local_total += 1
            else:
                self.affinity_remote_total += 1
        if (
            self.cache is None
            or self.batcher is None
            or not hasattr(self.batcher, "submit")
        ):
            return "off", None
        key = self._cache_key(songs)
        if trace is not None:
            t_cache = time.perf_counter()
            hit = self.cache.get(key)
            trace.span(
                "cache", t_cache, time.perf_counter(),
                {"hit": hit is not None},
            )
        else:
            hit = self.cache.get(key)
        if hit is not None:
            return "hit", hit
        if trace is not None:
            # traced requests always use the kwarg form (test doubles
            # with a bare submit(seeds) only run with tracing off)
            lead = lambda: self.batcher.submit(  # noqa: E731
                songs, deadline=deadline, trace=trace
            )
        elif deadline is not None:
            lead = lambda: self.batcher.submit(songs, deadline=deadline)  # noqa: E731
        else:
            lead = lambda: self.batcher.submit(songs)  # noqa: E731
        future, joined = self.cache.join_or_lead(key, lead)
        if joined and trace is not None:
            # a joiner shares the leader's batch slot: it gets no
            # queue/batch spans of its own (it never dispatched)
            trace.annotate("singleflight", "joined")
        if not joined:
            cache = self.cache
            future.add_done_callback(lambda f: cache.finish(key, f))
        # the seeds travel WITH the future so the async transport can
        # build a per-request degraded fallback when it resolves to a
        # DeadlineExceeded/NoHealthyReplicas (finish_recommend has no
        # other path back to the request body); the singleflight shares
        # one future across IDENTICAL seed sets, so the attribute is
        # consistent for every joiner
        future._kmls_seeds = songs
        return "flight", future

    def recommend_direct(
        self, songs: list[str], trace=None, deadline: float | None = None,
    ) -> tuple[list[str], str, bool]:
        """Blocking cached recommend → ``(songs, source, cache_hit)``.
        Used by the threaded POST path and the in-process replay harness;
        raises (Overloaded, DeadlineExceeded, NoHealthyReplicas included)
        like the underlying batcher/engine. ``deadline`` lets a caller
        that already tightened the budget with a forwarded
        X-KMLS-Deadline-Budget pass it through; None computes the local
        one (the pre-ISSUE-18 behavior exactly)."""
        if deadline is None:
            deadline = self._deadline_for(time.perf_counter())
        state, payload = self._cache_lookup_or_lead(songs, deadline, trace)
        if state == "hit":
            return payload[0], payload[1], True
        if state == "flight":
            timeout = 30.0
            if deadline is not None:
                timeout = max(deadline - time.perf_counter(), 0.0)
            try:
                recs, source = payload.result(timeout=timeout)
            except FuturesTimeout:
                if deadline is not None:
                    raise DeadlineExceeded(
                        "request exceeded its deadline budget in flight"
                    ) from None
                raise
            return recs, source, False
        if self.batcher is not None:
            if trace is not None and hasattr(self.batcher, "submit"):
                recs, source = self.batcher.recommend(
                    songs, deadline=deadline, trace=trace
                )
            elif deadline is not None and hasattr(self.batcher, "submit"):
                recs, source = self.batcher.recommend(songs, deadline=deadline)
            else:
                recs, source = self.batcher.recommend(songs)
        else:
            recs, source = self.engine.recommend(songs)
        if self.cache is not None:
            self.cache.put(self._cache_key(songs), (recs, source))
        return recs, source, False

    def _post_recommend(
        self, body: bytes | None, trace_header: str | None = None,
        budget_header: str | None = None,
        fire_fleet_fault: bool = True,
    ) -> Response:
        t0 = time.perf_counter()
        # gray-failure chaos site (ISSUE 18): a deterministic stall on
        # ONE fleet replica, addressed by sorted-peer index — the
        # slowpeer bench's fleet-side victim. The asyncio transport
        # consumes this site itself (faults.take on the loop timer) and
        # passes fire_fleet_fault=False so a times=N budget is never
        # decremented twice for one request.
        if fire_fleet_fault:
            faults.fire("fleet.peer", replica=self._fleet_index)
        err, songs = self._validate_recommend(body)
        if err is not None:
            return err
        # trace begins AFTER validation: malformed bodies never allocate
        trace = self._trace_begin(trace_header, t0)
        deadline, budget_ms, expired = self._effective_deadline(
            t0, budget_header
        )
        if budget_ms is not None and trace is not None:
            trace.annotate("deadline_budget_ms", round(budget_ms, 3))
        if expired:
            # the budget arrived spent: shed the compute, answer the
            # fallback — wasted-work, distinct from slow-compute
            self.deadline_expired_total += 1
            return self._degraded_response(
                t0, songs, "deadline-expired", trace=trace
            )
        # serve mesh (ISSUE 16): with a gang member known-dark, answer
        # the shard-loss policy BEFORE cache/batcher — a merged answer
        # missing one slab's candidates would be silently wrong, and
        # caching it would keep it wrong past the gang re-forming
        missing = self._mesh_missing_shards(probe=True)
        if missing:
            return self._mesh_shard_response(
                t0, songs, missing[0], trace=trace
            )
        try:
            recs, source, cached = self.recommend_direct(
                songs, trace=trace, deadline=deadline
            )
        except Exception as exc:
            if isinstance(exc, MeshShardUnavailable):
                # a gang member died mid-flight (after the pre-check)
                return self._mesh_shard_response(
                    t0, songs, exc.rank, trace=trace
                )
            reason = self._degrade_reason(exc)
            if reason is not None:
                # deadline exhausted or every replica ejected: answer
                # from the popularity fallback (X-KMLS-Degraded), not 5xx
                return self._degraded_response(t0, songs, reason, trace=trace)
            return self._recommend_error_response(exc, trace=trace)
        return self._recommend_result_response(
            t0, recs, source, cached=cached, trace=trace, songs=songs
        )

    # ---------- async-transport entry points ----------

    def submit_recommend(
        self, body: bytes | None, trace_header: str | None = None,
        budget_header: str | None = None, t_received: float | None = None,
    ):
        """Non-blocking twin of :meth:`_post_recommend` for the asyncio
        transport: → ``(response, None, t0, trace)`` when the answer is
        immediate (validation error, cache hit, shed, or the unbatched
        path), else ``(None, future, t0, trace)`` — resolve the future
        off-loop and build the reply with :meth:`finish_recommend`.
        ``trace`` rides the TUPLE, not the future: singleflight shares
        one future across joined connections, and each connection's trace
        is its own.

        ``t_received`` is when the transport began parsing this request.
        A transport that passes it owns the trace's end too: the trace
        starts there, no response builder finishes it, and the transport
        calls :meth:`trace_written` once its write has returned. Without
        it (in-process callers) the trace starts here and ends where the
        response is built, as in :meth:`_post_recommend`.

        Cache semantics mirror :meth:`recommend_direct`: hit → immediate
        response; miss → singleflight through the batcher, so concurrent
        identical misses on the event loop share ONE batch slot (asyncio
        futures take any number of done-callbacks, and ``result()`` is
        re-readable — every joined connection builds its own reply off the
        same future)."""
        t0 = time.perf_counter()
        # the fleet.peer chaos site is consumed by the TRANSPORT here,
        # not fired inline: aioserver._dispatch calls faults.take() and
        # schedules the stall on the loop timer, so an armed delay slows
        # each request without blocking every other one on the loop
        # (the threaded front end fires it in _post_recommend, where the
        # sleep costs only that handler thread)
        err, songs = self._validate_recommend(body)
        if err is not None:
            return err, None, t0, None
        trace = self._trace_begin(
            trace_header, t0 if t_received is None else t_received,
            defer=t_received is not None,
        )
        deadline, budget_ms, expired = self._effective_deadline(
            t0, budget_header
        )
        if budget_ms is not None and trace is not None:
            trace.annotate("deadline_budget_ms", round(budget_ms, 3))
        if expired:
            self.deadline_expired_total += 1
            return (
                self._degraded_response(
                    t0, songs, "deadline-expired", trace=trace
                ),
                None, t0, trace,
            )
        # serve mesh (ISSUE 16): same pre-check as _post_recommend —
        # never cache/merge an answer a dark slab can't contribute to
        missing = self._mesh_missing_shards(probe=True)
        if missing:
            return (
                self._mesh_shard_response(t0, songs, missing[0], trace=trace),
                None, t0, trace,
            )
        if self.batcher is None:
            try:
                recs, source, cached = self.recommend_direct(
                    songs, trace=trace, deadline=deadline
                )
            except Exception as exc:
                if isinstance(exc, MeshShardUnavailable):
                    return (
                        self._mesh_shard_response(
                            t0, songs, exc.rank, trace=trace
                        ),
                        None, t0, trace,
                    )
                reason = self._degrade_reason(exc)
                if reason is not None:
                    return (
                        self._degraded_response(t0, songs, reason, trace=trace),
                        None, t0, trace,
                    )
                return (
                    self._recommend_error_response(exc, trace=trace),
                    None, t0, trace,
                )
            return (
                self._recommend_result_response(
                    t0, recs, source, cached=cached, trace=trace, songs=songs
                ),
                None, t0, trace,
            )
        try:
            state, payload = self._cache_lookup_or_lead(songs, deadline, trace)
            if state == "off":
                if trace is not None:
                    future = self.batcher.submit(
                        songs, deadline=deadline, trace=trace
                    )
                elif deadline is not None:
                    future = self.batcher.submit(songs, deadline=deadline)
                else:
                    future = self.batcher.submit(songs)
                future._kmls_seeds = songs
                return None, future, t0, trace
        except Exception as exc:  # Overloaded / NoHealthyReplicas land here
            if isinstance(exc, MeshShardUnavailable):
                return (
                    self._mesh_shard_response(t0, songs, exc.rank, trace=trace),
                    None, t0, trace,
                )
            reason = self._degrade_reason(exc)
            if reason is not None:
                return (
                    self._degraded_response(t0, songs, reason, trace=trace),
                    None, t0, trace,
                )
            return (
                self._recommend_error_response(exc, trace=trace),
                None, t0, trace,
            )
        if state == "hit":
            return (
                self._recommend_result_response(
                    t0, payload[0], payload[1], cached=True, trace=trace,
                    songs=songs,
                ),
                None, t0, trace,
            )
        return None, payload, t0, trace

    def finish_recommend(self, future, t0: float, trace=None) -> Response:
        """Build the response for a completed :meth:`submit_recommend`
        future (which is done — ``result()`` never blocks here). A future
        resolved to DeadlineExceeded/NoHealthyReplicas degrades to the
        fallback answer for the seeds that rode in on the future."""
        try:
            # kmls-verify: allow[loopblock] — callers hand in a DONE
            # future (docstring contract above); result() only unwraps
            recs, source = future.result()
        except Exception as exc:
            if isinstance(exc, MeshShardUnavailable):
                songs = getattr(future, "_kmls_seeds", None) or []
                return self._mesh_shard_response(
                    t0, songs, exc.rank, trace=trace
                )
            reason = self._degrade_reason(exc)
            if reason is not None:
                songs = getattr(future, "_kmls_seeds", None) or []
                return self._degraded_response(t0, songs, reason, trace=trace)
            return self._recommend_error_response(exc, trace=trace)
        return self._recommend_result_response(
            t0, recs, source, trace=trace,
            songs=getattr(future, "_kmls_seeds", None),
        )

    def _get_client(self) -> Response:
        """Render the HTML test client with a sampled seed + static sample
        (reference: rest_api/app/main.py:190-203 — which sleeps 2 s when data
        isn't loaded yet; here the page renders immediately with a notice)."""
        # read finished_loading BEFORE best_tracks: load() publishes the
        # tracks first, so a True snapshot guarantees the best_tracks read
        # below sees the published value — the reverse order could blame
        # an empty ranking for what was really an in-flight load
        finished = self.engine.finished_loading
        best = self.engine.best_tracks
        if not best:
            # two distinct states render here: artifacts still loading, vs
            # loaded-but-empty popularity ranking (the reference's keep
            # count truncates with no minimum — int(N·pct) is legitimately
            # 0 on a tiny vocabulary). The old single message claimed
            # "not loaded yet" for both, telling the operator to retry
            # something that would never change.
            if finished:
                notice = (
                    "<p><em>Model loaded, but the popularity ranking kept "
                    "no tracks (vocabulary × TOP_TRACKS_SAVE_PERCENTILE "
                    "truncates to zero) — use <a href='/docs'>/docs</a> to "
                    "POST seed songs directly.</em></p>"
                )
            else:
                notice = (
                    "<p><em>Model artifacts not loaded yet — retry "
                    "shortly.</em></p>"
                )
            page = (
                self._template
                .replace("{{version}}", self.cfg.version)
                .replace("{{model_date}}", str(self.engine.cache_value))
                .replace("{{track_checkboxes}}", notice)
                .replace("{{sample_seed}}", "—")
                .replace("{{sample_recommendations}}", "")
            )
            return _html_response(200, page)
        names = [b["track_name"] for b in best]
        sample_pool = random.sample(names, min(12, len(names)))
        seed = random.choice(names)
        sample = self.engine.static_recommendation([seed])
        checkboxes = "\n".join(
            f'<label><input type="checkbox" value="{_esc(n)}"> {_esc(n)}</label>'
            for n in sample_pool
        )
        sample_html = "\n".join(f"<li>{_esc(s)}</li>" for s in sample)
        page = (
            self._template
            .replace("{{version}}", self.cfg.version)
            .replace("{{model_date}}", str(self.engine.cache_value))
            .replace("{{track_checkboxes}}", checkboxes)
            .replace("{{sample_seed}}", _esc(seed))
            .replace("{{sample_recommendations}}", sample_html)
        )
        return _html_response(200, page)

    def _get_docs(self) -> Response:
        """Interactive API docs: the three canned request examples
        (reference parity: rest_api/app/main.py:158-174, surfaced there via
        Swagger UI's "try it out") each load into an editable request body
        that can be sent to the live endpoint from the page."""
        examples = "\n".join(
            f"<h3>{_esc(ex['summary'])}</h3>"
            f"<pre>POST /api/recommend/\n{json.dumps(ex['value'], indent=2)}</pre>"
            f"<button class='load' data-body='{_esc(json.dumps(ex['value']))}'>"
            f"Try it</button>"
            for ex in CANNED_EXAMPLES.values()
        )
        first = json.dumps(
            next(iter(CANNED_EXAMPLES.values()))["value"], indent=2
        )
        html = f"""<!doctype html><html><head><meta charset="utf-8">
<title>API docs — Playlist Recommender</title>
<style>body{{font-family:system-ui;max-width:760px;margin:2rem auto;padding:0 1rem}}
pre{{background:#8881;padding:.8rem;border-radius:6px;overflow-x:auto}}
textarea{{width:100%;font-family:monospace;min-height:7rem}}
button{{margin:.3rem .3rem .3rem 0;padding:.35rem .9rem;cursor:pointer}}
#resp{{white-space:pre-wrap}}</style></head>
<body><h1>Playlist Recommender API {_esc(self.cfg.version)}</h1>
<p>Machine-readable spec: <a href="/openapi.json">/openapi.json</a></p>
<h2 id="post-api-recommend">POST /api/recommend/</h2>
<p>Request: <code>{{"songs": ["...", ...]}}</code> — at least one song
(empty → 400). Response: <code>{{"songs": [...], "model_date": "...",
"version": "..."}}</code>. Seeds found in the mined rules yield rule-based
recommendations; fully unknown seed sets fall back to a deterministic
popular-tracks sample.</p>
{examples}
<h2>Try it against this server</h2>
<textarea id="body" spellcheck="false">{_esc(first)}</textarea><br>
<button id="send">Send POST /api/recommend/</button>
<pre id="resp">(response appears here)</pre>
<script>
document.querySelectorAll('button.load').forEach(function (b) {{
  b.addEventListener('click', function () {{
    document.getElementById('body').value =
      JSON.stringify(JSON.parse(b.dataset.body), null, 2);
    document.getElementById('body').scrollIntoView({{behavior: 'smooth'}});
  }});
}});
document.getElementById('send').addEventListener('click', async function () {{
  var out = document.getElementById('resp');
  out.textContent = '...';
  try {{
    var r = await fetch('/api/recommend/', {{
      method: 'POST',
      headers: {{'Content-Type': 'application/json'}},
      body: document.getElementById('body').value,
    }});
    var text = await r.text();
    try {{ text = JSON.stringify(JSON.parse(text), null, 2); }} catch (e) {{}}
    out.textContent = 'HTTP ' + r.status + '\\n' + text;
  }} catch (e) {{
    out.textContent = 'request failed: ' + e;
  }}
}});
</script>
<h2>Other endpoints</h2>
<ul>
<li><code>GET /</code> — HTML test client</li>
<li><code>GET /test</code> — redirect here</li>
<li><code>GET /healthz</code>, <code>GET /readyz</code> — probes</li>
<li><code>GET /metrics</code> — Prometheus text metrics</li>
<li><code>GET /debug/traces</code>, <code>GET /debug/slo</code>,
<code>GET /debug/profile?seconds=N</code> — loopback-only debug views
(retained traces, SLO burn rates, on-demand profiler capture)</li>
</ul></body></html>"""
        return _html_response(200, html)

    def _openapi(self) -> dict:
        return {
            "openapi": "3.1.0",
            "info": {
                "title": "Playlist Recommender (TPU rebuild)",
                "version": self.cfg.version,
            },
            "paths": {
                "/api/recommend/": {
                    "post": {
                        "summary": "Recommend songs from seed songs",
                        "requestBody": {
                            "required": True,
                            "content": {
                                "application/json": {
                                    "schema": {
                                        "type": "object",
                                        "required": ["songs"],
                                        "properties": {
                                            "songs": {
                                                "type": "array",
                                                "items": {"type": "string"},
                                                "minItems": 1,
                                            }
                                        },
                                    },
                                    "examples": CANNED_EXAMPLES,
                                }
                            },
                        },
                        "responses": {
                            "200": {
                                "description": "Recommendations",
                                "content": {
                                    "application/json": {
                                        "schema": {
                                            "type": "object",
                                            "properties": {
                                                "songs": {
                                                    "type": "array",
                                                    "items": {"type": "string"},
                                                },
                                                "model_date": {"type": "string"},
                                                "version": {"type": "string"},
                                            },
                                        }
                                    }
                                },
                            },
                            "400": {"description": "Empty song list"},
                            "422": {"description": "Malformed body"},
                        },
                    }
                }
            },
        }


def _esc(s: str) -> str:
    return (
        str(s).replace("&", "&amp;").replace("<", "&lt;")
        .replace(">", "&gt;").replace('"', "&quot;").replace("'", "&#39;")
    )


# ---------- stdlib HTTP adapter ----------


def make_handler(app: RecommendApp):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # the handler writes headers and body as separate sends on an
        # unbuffered socket; with Nagle on, the body send sits behind the
        # peer's delayed ACK (~40ms) — at QPS scale that dominates latency
        disable_nagle_algorithm = True

        def _dispatch(self, method: str) -> None:
            # in-flight accounting for the SIGTERM drain: the settle in
            # serving.server exits as soon as this hits zero (idle
            # keep-alive connections sit BETWEEN requests and are rightly
            # not counted — the drain must not wait on them)
            track = hasattr(self.server, "active_lock")
            if track:
                with self.server.active_lock:
                    self.server.active_requests += 1
            try:
                body = None
                if method == "POST":
                    length = int(self.headers.get("Content-Length") or 0)
                    body = self.rfile.read(length) if length else b""
                try:
                    status, headers, payload = app.handle(
                        method, self.path, body,
                        client_host=self.client_address[0],
                        trace_header=self.headers.get("X-KMLS-Trace"),
                        budget_header=self.headers.get(
                            "X-KMLS-Deadline-Budget"
                        ),
                    )
                except Exception:
                    logger.exception("unhandled error for %s %s", method, self.path)
                    app.metrics.record_error()
                    status, headers, payload = 500, {"Content-Type": "application/json"}, (
                        b'{"detail": "Internal Server Error"}'
                    )
                self.send_response(status)
                for key, value in headers.items():
                    self.send_header(key, value)
                self.send_header("Content-Length", str(len(payload)))
                # during a SIGTERM drain (server.draining set by
                # serving.server) tell keep-alive clients to re-connect
                # elsewhere — k8s endpoint removal only diverts NEW
                # connections, established flows would otherwise keep
                # sending to the terminating pod until cut off
                drain = getattr(self.server, "draining", None)
                if drain is not None and drain.is_set():
                    self.send_header("Connection", "close")
                    self.close_connection = True
                self.end_headers()
                self.wfile.write(payload)
            finally:
                if track:
                    with self.server.active_lock:
                        self.server.active_requests -= 1

        def do_GET(self) -> None:  # noqa: N802 (stdlib API)
            self._dispatch("GET")

        def do_POST(self) -> None:  # noqa: N802
            self._dispatch("POST")

        def log_message(self, fmt: str, *args) -> None:
            logger.debug("%s - %s", self.address_string(), fmt % args)

    return Handler


class _Server(ThreadingHTTPServer):
    # stdlib default listen backlog is 5 — QPS-scale bursts get connection-
    # refused before a handler thread ever sees them
    request_queue_size = 256

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # in-flight request count, read by the SIGTERM drain settle
        self.active_requests = 0
        self.active_lock = threading.Lock()


def serve(app: RecommendApp, port: int | None = None) -> ThreadingHTTPServer:
    """Bind + return the server (caller runs ``serve_forever``); port 0 picks
    an ephemeral port (used by tests and local dev)."""
    server = _Server(
        ("0.0.0.0", port if port is not None else app.cfg.port), make_handler(app)
    )
    return server
