"""The online recommendation engine: HBM-resident rule tensors, a jitted
lookup kernel, and a double-buffered hot swap driven by the reference's
polling protocol.

Reference behaviors replicated (rest_api/app/main.py):

- artifact loading (:52-80): ``best_tracks.pickle`` is required — but where
  the reference raises and crash-loops on a fresh/empty PVC (its report lists
  this as risk #2), this engine fails SOFT: ``load()`` returns False and the
  readiness endpoint gates traffic until the first mining run lands.
- staleness detection (:82-97): compare the cached token against
  ``last_execution.txt`` content; missing file counts as stale; the cached
  value doubles as the response's ``model_date``.
- reload loop (:100-122): first load at startup + periodic re-check; a
  reload builds a complete new :class:`RuleBundle` and swaps ONE reference —
  in-flight requests keep the old bundle (the double-buffer makes the
  reference's acknowledged read-mid-swap race structurally impossible).
- lookup (:224-254): seeds filtered by rule-key membership (frequent
  singletons with empty rows ARE members); no known seed → deterministic
  static fallback (:205-222); otherwise the batched device kernel
  (ops/serve.py) does the max-merge + top-k.
- the static fallback's determinism (:214): the reference seeds ``random``
  with ``hash(tuple(sorted(seeds)))``, which is process-salted in modern
  Python (deterministic only within one process); here the seed is a stable
  blake2 digest so all replicas agree — a documented deliberate fix.

The engine prefers the tensor-native npz artifact (straight ``device_put``)
and falls back to the reference-format pickle, so it can serve a PVC
populated by either the rebuild's or the reference's mining job.

Multi-device serving: a publication builds one :class:`RuleBundle` replica
per serving device (``KMLS_SERVE_DEVICES``; rule tensors ``device_put`` to
each device, every shape bucket warmed per replica) and swaps the whole
set atomically. ``recommend_many_async(..., replica=i)`` executes a batch
on replica ``i``'s device — the batcher's least-loaded dispatcher uses
this to run concurrent batches on different devices instead of
serializing them on one in-order execution queue. ``bundle_epoch`` is the
monotonic publication counter the recommendation cache keys on.

Model-parallel serving (``KMLS_MODEL_LAYOUT=sharded|auto``): instead of
one full replica per device, a publication can build ONE logical bundle
whose rule tensors are vocab-sharded across every serving device
(``NamedSharding``; ``ops/serve.py sharded_recommend_fn``) — per-device
HBM holds ``V/S`` rule rows, so the servable catalog scales with the
mesh rather than capping at a single device. ``auto`` measures the
loaded tensor bytes against ``KMLS_DEVICE_BUDGET_BYTES`` and shards only
when a replica would not fit (parallel/layout.py is the one copy of
that decision, shared with the mining side). The sharded bundle presents
as one replica to the dispatcher, pre-warms its kernel over the same
(batch, length) bucket grid — zero compiles post-publish, same contract
— and answers bit-identically to the replicated layout (pinned by
tests/test_shard_layout.py). Per-vocab-shard seed-hit counters render as
``kmls_shard_dispatch_total`` in ``/metrics``.

Hybrid serving (the second model family): when the mining job published
an ``embeddings.npz`` (ALS item factors, ``mining/als.py``), every
replica also carries the factor matrix on its device and each batch
dispatches TWO kernels — the rule max-merge and the embedding cosine
top-k (``ops/embed.py``) — whose per-request top-k lists merge on the
completion side per ``KMLS_HYBRID_MODE`` (rules | embed | blend, weight
``KMLS_HYBRID_BLEND_WEIGHT``). A seed set unknown to the rules but known
to the embedding vocabulary (cold-start / long-tail) is answered from
the embedding space instead of the popularity fallback. An absent,
torn, or checksum-failing embedding artifact degrades to rules-only —
the exact analogue of the npz→pickle fallback — and never costs the
reload.
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import os
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults
from ..config import ServingConfig
from ..io import artifacts, iohealth, registry
from ..io.artifacts import ArtifactIntegrityError
from ..observability import costmodel as costmodel_mod
from ..ops.embed import embed_topk, factor_table
from ..ops.serve import recommend_batch

logger = logging.getLogger("kmlserver_tpu.serving")


def _start_host_copies(*arrays) -> None:
    """Start each device result's copy to the host without waiting for
    it: the copies queue behind their programs on the device's stream,
    so the ``np.asarray`` that picks a result up later finds a copy that
    is done or under way, and does not start one and sit it out."""
    for a in arrays:
        a.copy_to_host_async()


def _stamp_hedge_outcome(finish, remote):
    """The mesh layout's ``finish``: run the engine's one ``finish()`` and
    stamp the coordinator's hedge outcome (ISSUE 18) on the callable the
    batcher holds, whose request spans read ``_kmls_hedge``. Kept apart so
    that ``finish()`` does not name itself: a closure that does is freed
    by the cycle collector, not when its batch resolves, and the batch's
    device results with it."""

    def stamped() -> list[tuple[list[str], str]]:
        out = finish()
        stamped._kmls_hedge = getattr(remote, "hedge_outcome", None)
        return out

    return stamped


def blend_candidates(
    rule_pairs: list[tuple[str, float]],
    emb_pairs: list[tuple[str, float]],
    weight: float,
    k_best: int,
) -> list[str]:
    """THE hybrid blend merge — union of both model families' (name,
    score) candidates with blended scores ``(1-w)·conf + w·sim`` and the
    deterministic tie order (score desc, name asc) that keeps every
    replica and epoch composing identical answers. One copy on purpose:
    the serving engine's ``_compose_answer`` AND the offline quality
    harness (quality/eval.py) both rank through it, so the measured
    blend optimum can never describe a merge production doesn't run."""
    w = min(max(weight, 0.0), 1.0)
    scores: dict[str, float] = {}
    for name, conf in rule_pairs:
        scores[name] = (1.0 - w) * float(conf)
    for name, sim in emb_pairs:
        scores[name] = scores.get(name, 0.0) + w * float(sim)
    ranked = sorted(scores.items(), key=lambda kv: (-kv[1], kv[0]))
    return [n for n, _ in ranked[:k_best]]


def stable_seed(seed_tracks: list[str]) -> int:
    """Process-independent replacement for the reference's salted
    ``hash(tuple(sorted(seed_tracks)))`` (rest_api/app/main.py:214)."""
    digest = hashlib.blake2b(
        "\x1f".join(sorted(seed_tracks)).encode("utf-8"), digest_size=8
    ).digest()
    return int.from_bytes(digest, "big")


@dataclasses.dataclass
class RuleBundle:
    """One immutable generation of serving state. Swapped atomically.

    With multi-device serving active (``KMLS_SERVE_DEVICES``), one bundle
    exists PER local device — the vocab/index/known-mask host state is
    shared across the replica set, the rule tensors live on each replica's
    own device, and the whole set swaps as one publication."""

    vocab: list[str]
    index: dict[str, int]
    rule_ids: jax.Array  # device, int32 (V, K)
    rule_confs: jax.Array  # device, float32 (V, K)
    known_mask: np.ndarray  # host, bool (V,) — rule-dict key membership
    model_token: str  # token value when loaded
    # the device this replica's tensors are committed to (None = default
    # placement) and the generation counter the
    # recommendation cache keys on — monotonic per engine, bumped on every
    # successful publication, so a cache entry can never outlive its rules
    device: object = None
    epoch: int = 0
    # every (batch, length) seed shape warmed before publication — the
    # serving thread checks membership so an unwarmed dispatch (a compile
    # on the hot path) is counted and logged, never silent
    warmed_shapes: set = dataclasses.field(default_factory=set)
    # ---- model layout (KMLS_MODEL_LAYOUT, parallel/layout.py) ----
    # "replicated": this bundle is one full-tensor replica on `device`.
    # "sharded": ONE logical bundle whose rule tensors are vocab-sharded
    # across `mesh` (NamedSharding, P("shard", None)); the replica set is
    # exactly [this] and dispatch runs the sharded kernel below.
    layout: str = "replicated"
    mesh: object = None  # jax.sharding.Mesh spanning the serve devices
    n_shards: int = 1
    # padded per-shard vocab rows (v_pad / n_shards) — the divisor the
    # per-shard dispatch counters bucket seed ids by
    shard_size: int = 0
    # the jitted shard_map lookup bound to (mesh, k_best), resolved at
    # BUILD time (ops.serve.sharded_recommend_fn is lru-cached) so the
    # dispatch path never constructs a jit closure
    shard_kernel: object = None
    # replicated NamedSharding over `mesh` — the placement target for
    # staged seed batches (replicated layout uses `device` instead)
    seed_sharding: object = None
    # what placing the shards took at publication (the two sharded
    # ``device_put``s until both were resident) and the rule bytes each
    # device holds, in shard order — kmls_shard_place_seconds and
    # kmls_shard_resident_bytes{shard=} in /metrics
    place_seconds: float = 0.0
    shard_resident_bytes: tuple = ()
    # ---- pod-spanning serve mesh (ISSUE 16) ----
    # "mesh" layout: rule_ids/rule_confs hold ONLY this gang member's
    # vocab slab (global rows [gang_rank·shard_size, +shard_size)) on the
    # default local device; n_shards is the GANG size and shard_size the
    # slab rows, so the per-shard dispatch counters and /metrics read
    # identically to the single-process sharded layout. mesh_v is the
    # padded GLOBAL vocab width every partial scores at; mesh_lo the
    # slab's first global row as a committed device scalar (a traced
    # argument of ops.serve.shard_partial_topk — one compiled program
    # serves every rank).
    gang_rank: int = 0
    mesh_v: int = 0
    mesh_lo: object = None
    # ---- second model family (hybrid rule∪embedding serving) ----
    # ALS item factors on this replica's device (f32 (V_emb, rank), rows
    # L2-normalized) with their OWN vocabulary — the embedding id space is
    # the full encode-phase vocab, deliberately broader than the (possibly
    # Apriori-pruned) rule vocab; the hybrid merge happens at the name
    # level so the two spaces never need to agree. None = no embedding
    # artifact published (or it failed validation): rules-only serving.
    emb_factors: "jax.Array | None" = None
    emb_vocab: list[str] | None = None
    emb_index: dict[str, int] | None = None
    # (batch, length) shapes the embedding kernel was compiled for at
    # publication — same zero-compiles-post-publish discipline as
    # warmed_shapes, tracked separately because a delta apply carries
    # the factors (and their warmed shapes) over to re-warmed rule tensors
    emb_warmed_shapes: set = dataclasses.field(default_factory=set)


class RecommendEngine:
    """Holds serving state and executes lookups. Thread-safe: the bundle and
    best-tracks references are replaced atomically; readers never block."""

    def __init__(self, cfg: ServingConfig):
        self.cfg = cfg
        self.bundle: RuleBundle | None = None
        # the full replica set (one bundle per serving device); `bundle`
        # stays the primary replica for single-device callers
        self.replicas: list[RuleBundle] = []
        # monotonic publication counter — the recommendation cache's key
        # prefix. 0 = nothing published yet.
        self.bundle_epoch = 0
        # cumulative per-replica dispatch counters (Prometheus-monotonic:
        # they survive hot swaps), index-aligned with `replicas`
        self.dispatch_counts: list[int] = []
        # sharded layout: cumulative seed ids dispatched per vocab shard
        # (the load-balance signal — which shard's rows the traffic
        # actually hits), rendered as kmls_shard_dispatch_total in
        # /metrics; empty in replicated layout
        self.shard_dispatch_counts: list[int] = []
        self._dispatch_lock = threading.Lock()
        self.best_tracks: list[dict] | None = None
        self.cache_value: str | None = None  # the reference's app.cache_value
        self.finished_loading = False
        self.reload_counter = 0
        self._reload_lock = threading.Lock()
        # ---- fault-tolerance bookkeeping (rendered into /metrics) ----
        # total failed reloads: each one KEPT the last-good bundle serving
        # (the rollback counter), vs consecutive failures driving the
        # exponential retry backoff + the quarantine strike discipline
        self.reload_failures = 0
        self.consecutive_reload_failures = 0
        self.artifact_quarantines = 0
        self.last_load_error: str | None = None
        # second-model-family bookkeeping: embedding-artifact load
        # failures are SURVIVABLE (the bundle publishes rules-only), so
        # they get their own counters instead of riding reload_failures
        self.embedding_load_failures = 0
        self.last_embedding_error: str | None = None
        # True when the LAST publication wanted embeddings (file present)
        # but had to fall back to rules-only — rendered into /readyz's
        # degraded reasons and /metrics
        self.embedding_degraded = False
        # monotonic deadline before which reload_if_required() won't retry
        # a FAILED load (direct load() calls always go through — tests and
        # operator nudges must not be backoff-gated)
        self._backoff_until = 0.0
        # ---- continuous freshness (ISSUE 10) ----
        # chain position currently applied on top of the base generation:
        # the serving epoch is logically the PAIR (bundle_epoch,
        # delta_seq) — a delta apply advances delta_seq in place without
        # bumping bundle_epoch (the cache invalidates selectively instead
        # of wholesale), and a full reload resets it to 0
        self.delta_seq = 0
        self.delta_applied_total = 0
        self.delta_rejected_total = 0
        self.last_delta_error: str | None = None
        # callbacks fired AFTER a delta swap commits: (touched_names,
        # wholesale) — the app points this at the cache's selective
        # invalidation
        self.delta_listeners: list = []
        # the logical tensors deltas patch (counts included — the npz
        # load's dict shape); None when the bundle came from the pickle
        # or carries merged float64 confidences (delta-ineligible)
        self._host_state: dict | None = None
        # sha256 of the npz the host state was loaded from — the binding
        # a bundle's base_npz_sha256 must match
        self._base_npz_sha: str | None = None
        # wall-clock written_at of the newest APPLIED generation (base
        # manifest or delta chain entry) — kmls_freshness_lag_seconds
        self._applied_written_at = 0.0
        # rejection backoff for the POLLING path only (direct
        # apply_pending_deltas calls always go through, like load())
        self._delta_backoff_until = 0.0
        # bundles in the CURRENT generation's delta chain file (applied
        # or not) — the compaction trigger's observability surface,
        # rendered as kmls_delta_chain_length; 0 when no chain (or a
        # chain bound to another generation) is on the PVC
        self.delta_chain_length = 0
        # ---- quality loop (ISSUE 14) ----
        # the blend optimum read from quality.report.json at load time
        # (None: no report, unusable report, or measured mode off) —
        # committed WITH the bundle swap so answers and weight always
        # describe the same generation
        self.measured_blend_weight: float | None = None
        # the replicated layout's lookup — the same jitted function on
        # every backend
        self._kernel = partial(recommend_batch, k_best=cfg.k_best_tracks)
        # dispatches whose (batch, length) shape was never pre-warmed —
        # each one paid a jit compile on the serving path; must stay 0
        self.unwarmed_dispatches = 0
        # seed slots staged for the rule lookup, by what they hold: a
        # known seed's id, or -1 padding up to the (rows, length) bucket
        # (kmls_seed_slots_total; the real count is also each batch's
        # sum of seed lengths, for an exact roofline join)
        self.seed_slots_real = 0
        self.seed_slots_padded = 0
        # ---- device-truth cost attribution (ISSUE 12) ----
        # per-kernel MFU/roofline + memory/compile telemetry; None with
        # KMLS_COSTMODEL=0, making every call site one attribute check
        # (the disabled mode's zero-cost proof rides the module-level
        # OBSERVATIONS_TOTAL counter, began-counter style)
        self.cost_model = (
            costmodel_mod.CostModel() if cfg.costmodel_enabled else None
        )
        # per-artifact publication timestamps (wall clock) — the
        # freshness-age surface /readyz and kmls_artifact_age_seconds
        # report; empty before the first load
        self._artifact_written_at: dict[str, float] = {}
        # ---- pod-spanning serve mesh (ISSUE 16) ----
        # armed when KMLS_SERVE_GANG_COORDINATOR + SIZE>1 name a gang this
        # process belongs to; the worker serves THIS rank's partial top-k
        # to peers, the coordinator fans a batch out and merges. Both are
        # created lazily at the first mesh publication (under the reload
        # lock) and survive hot swaps — the model token carried on every
        # partial is what keeps generations honest across the gang.
        from . import mesh as mesh_mod  # local import: keeps engine import light

        self._mesh_mod = mesh_mod
        self.gang = mesh_mod.gang_from_config(cfg)
        self.mesh_worker = None
        self.mesh_coordinator = None
        # answers served merged-without-a-straggler (ISSUE 18): degraded
        # by contract, counted for /metrics (kmls_mesh_straggler_
        # degraded_total) — stays 0 with hedging off
        self.mesh_straggler_degraded = 0
        if self.gang is not None:
            # real-collectives wiring: on an accelerator gang this joins
            # the jax.distributed coordinator (GSPMD over DCN — the
            # on-chip run folds into the standing TPU-window item); on
            # the CPU backend it logs and declines, and serving uses the
            # multi-process simulation transport below instead.
            from ..parallel.distributed import maybe_initialize_serve_gang

            maybe_initialize_serve_gang(
                self.gang.coordinator, self.gang.size, self.gang.rank
            )
        # storage gray-failure spine (ISSUE 19): point the IO-health
        # monitor's free-space gauge at the artifact volume this engine
        # polls — kmls_disk_free_bytes then tracks the PVC, and every
        # artifact read below feeds the latency EWMAs behind the
        # storage-slow conviction
        iohealth.MONITOR.watch_disk(cfg.pickles_dir)

    # ---------- artifact loading / hot swap ----------

    def _token_path(self) -> str:
        return registry.token_path_for(self.cfg.base_dir, self.cfg.data_invalidation_file)

    def _read_deadline(self) -> float | None:
        """Deadline for reload-path artifact reads (None = unbounded)."""
        return self.cfg.io_read_deadline_s or None

    def _read_token(self) -> str | None:
        try:
            return artifacts.read_text(self._token_path(), op="token_poll")
        except FileNotFoundError:
            return None
        except OSError as exc:
            # a transient EIO/stall on the per-poll token read must NOT
            # flip is_data_stale — that would turn one flaky NFS read
            # into reload churn. The poll failure decays: report the
            # cached token (no change seen) and let the next poll retry.
            logger.warning(
                "token poll failed (%s); keeping cached token", exc
            )
            return self.cache_value

    def is_data_stale(self) -> bool:
        """Token-comparison staleness (reference: rest_api/app/main.py:82-97);
        missing token file counts as stale.

        Deliberate divergence: the reference's check UPDATES its cached token
        as a side effect, so (a) a failed reload permanently swallows the
        staleness signal and (b) ``model_date`` advertises data that isn't
        being served yet. Here the check is pure — ``cache_value`` moves only
        when a new bundle actually loads, so ``model_date`` always describes
        the rules answering the request."""
        token = self._read_token()
        if token is None:
            logger.warning("invalidation token %s missing", self._token_path())
            return True
        if token != self.cache_value:
            logger.info("data stale: token changed %r -> %r", self.cache_value, token)
            return True
        return False

    def load(self) -> bool:
        """Build a fresh bundle from the PVC; atomic swap on success.
        Returns False (fail-soft) when artifacts aren't there yet."""
        with self._reload_lock:
            # re-check under the lock: concurrent "nudge" threads that queued
            # behind an in-flight load must not repeat it (their staleness
            # decision predates the load that just completed)
            if self.finished_loading and not self.is_data_stale():
                return True
            if self.cost_model is not None:
                # a (re)publication is starting: bank genuine serving-
                # path compiles seen so far, so the warmup about to run
                # is absorbed by mark_published instead of billed live
                self.cost_model.note_prepublish()
            cfg = self.cfg
            best_path = os.path.join(cfg.pickles_dir, cfg.best_tracks_file)
            rec_path = os.path.join(cfg.pickles_dir, cfg.recommendations_file)
            npz_path = artifacts.tensor_artifact_path(rec_path)
            try:
                # deterministic chaos hook: KMLS_FAULT_RELOAD_FAIL / a test's
                # faults.inject("engine.load") fails the reload exactly like
                # a torn artifact — same rollback, same retry ladder
                faults.fire("engine.load")
                use_npz, use_emb = self._verify_before_load(
                    best_path, rec_path, npz_path
                )
                best = artifacts.load_pickle(
                    best_path, deadline_s=self._read_deadline()
                )
                replicas = self._build_replicas(
                    rec_path, npz_path, use_npz=use_npz
                )
                # second model family: attach ALS item factors to every
                # replica. Fail-SOFT by design — a torn/corrupt/absent
                # embeddings.npz costs the embedding path, never the
                # reload (rules-only is the documented degradation, the
                # exact analogue of the npz→pickle fallback above). The
                # degraded/error outcome stays in LOCALS until the swap
                # commits below: a reload that fails after this point
                # (warmup raise → last-good keeps serving) must not leave
                # /readyz describing the failed CANDIDATE generation.
                emb_degraded, emb_error = self._attach_embeddings(
                    replicas, use_emb=use_emb
                )
                # warm the serving kernel for every seed-bucket shape on
                # EVERY replica BEFORE publishing: the first jit compile
                # costs seconds on TPU and must not land inside a request
                # (readiness implies warmed — on all devices). Reloads with
                # unchanged tensor shapes hit the jit cache and skip this.
                # Inside the try: tensors that np.load accepts but the
                # kernel rejects must fail-soft too.
                for bundle in replicas:
                    self._warmup(bundle)
            except FileNotFoundError as exc:
                logger.warning("artifacts not ready: %s", exc)
                return False
            except Exception as exc:
                # corrupt/torn artifact (the REFERENCE mining job writes
                # non-atomically — its report acknowledges the race; this
                # engine must serve either side's PVC): keep the current
                # bundle (last-good rollback), back off the retry, and
                # quarantine persistent offenders. The invalidation token
                # is NOT consumed (cache_value only moves on success), so
                # every retry re-sees the staleness signal.
                logger.exception("artifact load failed; keeping current bundle")
                self._note_reload_failure(
                    exc, best_path, rec_path, npz_path
                )
                return False
            # atomic publication: single reference assignments. Ordering
            # contract for the epoch-keyed cache: the bundle reference
            # lands BEFORE the epoch bump, so an answer stored under the
            # new epoch can only have been computed from the new rules —
            # a stale answer can land only under the OLD epoch key, which
            # no post-swap lookup can ever construct. (The benign inverse
            # — a new-rules answer briefly stored under the old key — just
            # serves fresher data than advertised.)
            epoch = self.bundle_epoch + 1
            for bundle in replicas:
                bundle.epoch = epoch
            self.best_tracks = best
            self.replicas = replicas
            self.bundle = replicas[0]
            self.bundle_epoch = epoch
            with self._dispatch_lock:
                while len(self.dispatch_counts) < len(replicas):
                    self.dispatch_counts.append(0)
            self.cache_value = replicas[0].model_token or self.cache_value
            # continuous freshness: a full reload starts a fresh
            # (base, delta_seq) pair at seq 0 — a pending chain for THIS
            # generation applies via apply_pending_deltas right after
            # (reload_if_required chains the two)
            self.delta_seq = 0
            self._host_state = getattr(self, "_candidate_host_state", None)
            self._base_npz_sha = getattr(self, "_candidate_npz_sha", None)
            self._delta_backoff_until = 0.0
            # chain-length gauge: bundles already published for THIS
            # generation (apply_pending_deltas keeps it current as the
            # chain grows; a chain for another generation reads as 0)
            self.delta_chain_length = 0
            if self.cfg.delta_enabled:
                chain = artifacts.read_delta_state(self.cfg.pickles_dir)
                if chain is not None and chain.get("base_token") == (
                    self.cache_value
                ):
                    self.delta_chain_length = len(chain.get("entries", ()))
            # quality loop: the measured blend optimum commits WITH the
            # bundle it was measured against (fail-soft — no report or a
            # malformed one serves the configured default, loudly)
            self.measured_blend_weight = self._read_measured_blend_weight()
            manifest = artifacts.load_manifest(
                self.cfg.pickles_dir, deadline_s=self._read_deadline()
            )
            if manifest is not None and manifest.get("token") == self.cache_value:
                self._applied_written_at = float(
                    manifest.get("written_at") or time.time()
                )
            else:
                self._applied_written_at = time.time()
            self.finished_loading = True
            # embedding status commits WITH the bundle it describes
            self.embedding_degraded = emb_degraded
            self.last_embedding_error = emb_error
            if emb_degraded:
                self.embedding_load_failures += 1
            self.reload_counter += 1
            self.consecutive_reload_failures = 0
            self.last_load_error = None
            self._backoff_until = 0.0
            # per-artifact freshness bookkeeping: rules age from the
            # manifest's written_at (just resolved above), popularity/
            # embeddings from their file mtimes (the manifest covers the
            # set, not per-file stamps); delta-chain rides
            # _applied_written_at, which deltas advance in place
            ages = {"rules": self._applied_written_at}
            ages["popularity"] = self._file_written_at(
                best_path, self._applied_written_at
            )
            if replicas[0].emb_factors is not None:
                ages["embeddings"] = self._file_written_at(
                    artifacts.embeddings_artifact_path(self.cfg.pickles_dir),
                    self._applied_written_at,
                )
            self._artifact_written_at = ages
            # cost attribution (ISSUE 12): publish-time tensor-residency
            # accounting + compile-watch snapshot (post-warmup, so the
            # kmls_compiles_total counter starts at zero for this
            # generation — any growth IS a compile on the serving path)
            if self.cost_model is not None:
                self._note_publish_cost(replicas)
            logger.info(
                "reload #%d complete (epoch %d): %d tracks, %d rule keys, "
                "%d replica(s), layout %s (%d shard(s)), embeddings %s, "
                "token %r",
                self.reload_counter, epoch, len(replicas[0].vocab),
                int(replicas[0].known_mask.sum()), len(replicas),
                self.model_layout, self.n_shards,
                (
                    f"on ({len(replicas[0].emb_vocab)} tracks)"
                    if replicas[0].emb_factors is not None else "off"
                ),
                replicas[0].model_token,
            )
            return True

    def _verify_before_load(
        self, best_path: str, rec_path: str, npz_path: str
    ) -> tuple[bool, bool]:
        """Integrity gate before any bytes are trusted: validate the
        artifact set against the mining job's manifest (sizes + sha256).
        A mismatched best/recommendations pickle ABORTS the reload (raise
        → last-good keeps serving); a mismatched npz is survivable — the
        pickle carries the same generation — so it only disables the
        tensor-artifact fast path for this reload, and a mismatched
        embeddings.npz likewise only disables the embedding path (the
        rule artifacts carry the generation; rules-only is the documented
        degradation). The CURRENT token gates the check: a manifest
        stamped for another generation (a manifest-less writer — the
        reference's job — has published since) is stale and steps aside
        rather than condemning fresh bytes. → (use_npz, use_emb)."""
        if not self.cfg.verify_manifest:
            return True, True
        emb_path = artifacts.embeddings_artifact_path(self.cfg.pickles_dir)
        bad = artifacts.verify_files(
            self.cfg.pickles_dir,
            [
                os.path.basename(p)
                for p in (best_path, rec_path, npz_path, emb_path)
            ],
            token=self._read_token(),
        )
        use_npz = True
        use_emb = True
        if npz_path in bad:
            logger.warning(
                "tensor artifact %s fails its manifest checksum; "
                "falling back to the pickle", npz_path,
            )
            use_npz = False
            bad = [p for p in bad if p != npz_path]
        if emb_path in bad:
            logger.warning(
                "embedding artifact %s fails its manifest checksum; "
                "serving rules-only this generation", emb_path,
            )
            use_emb = False
            bad = [p for p in bad if p != emb_path]
        if bad:
            raise ArtifactIntegrityError(
                f"artifact checksum mismatch vs manifest: {bad}", bad
            )
        return use_npz, use_emb

    def _attach_embeddings(
        self, replicas: list[RuleBundle], use_emb: bool = True
    ) -> tuple[bool, str | None]:
        """Load ``embeddings.npz`` (if published) and commit the item
        factors to every replica's device. NEVER raises: embedding
        problems degrade to rules-only serving — a bad second-model
        artifact must not cost the first model's reload. Fires the
        ``embed.artifact`` chaos site so the degradation is
        deterministically testable.

        → ``(degraded, error)`` for the CALLER to commit alongside the
        bundle swap — engine-level status must describe the bundle that
        actually published, never a candidate whose reload later failed."""
        if self.cfg.hybrid_mode == "rules":
            # operator pinned rules-only: don't even read the file
            return False, None
        emb_path = artifacts.embeddings_artifact_path(self.cfg.pickles_dir)
        if not os.path.exists(emb_path):
            # no second model published: rules-only, not degraded
            return False, None
        try:
            if not use_emb:
                raise ArtifactIntegrityError(
                    f"{emb_path} fails its manifest checksum", [emb_path]
                )
            faults.fire("embed.artifact")
            loaded = artifacts.load_embeddings(
                emb_path, deadline_s=self._read_deadline()
            )
        except FileNotFoundError:
            # raced a writer retiring the artifact (an embed-disabled
            # publication removes it before the token rewrite): absent,
            # not corrupt — rules-only without the degraded flag
            logger.info(
                "embedding artifact %s vanished mid-load (retired by the "
                "miner); serving rules-only", emb_path,
            )
            return False, None
        except Exception as exc:
            logger.exception(
                "embedding artifact %s unusable; serving rules-only",
                emb_path,
            )
            return True, f"{type(exc).__name__}: {exc}"
        emb_vocab = loaded["vocab"]
        emb_index = {n: i for i, n in enumerate(emb_vocab)}
        # laid out once, here, as the (rank, V) table the kernel walks
        factors = factor_table(loaded["item_factors"])
        for bundle in replicas:
            bundle.emb_vocab = emb_vocab
            bundle.emb_index = emb_index
            bundle.emb_factors = (
                jax.device_put(factors, bundle.device)
                if bundle.device is not None
                else factors
            )
        return False, None

    def _note_reload_failure(
        self, exc: Exception, best_path: str, rec_path: str, npz_path: str
    ) -> None:
        """Failed-reload bookkeeping (caller holds ``_reload_lock``):
        count the rollback, arm the exponential retry backoff, and — once
        the SAME artifact set has failed ``quarantine_after_failures``
        consecutive reloads — quarantine the files that are actually
        corrupt (a single mid-update mismatch heals itself next poll and
        must never cost a good file)."""
        self.reload_failures += 1
        self.consecutive_reload_failures += 1
        self.last_load_error = f"{type(exc).__name__}: {exc}"
        backoff = min(
            self.cfg.reload_backoff_base_s
            * (2 ** (self.consecutive_reload_failures - 1)),
            self.cfg.reload_backoff_max_s,
        )
        self._backoff_until = time.monotonic() + backoff
        logger.warning(
            "reload failure #%d (consecutive); retrying in %.1fs",
            self.consecutive_reload_failures, backoff,
        )
        threshold = self.cfg.quarantine_after_failures
        if threshold > 0 and self.consecutive_reload_failures >= threshold:
            self._quarantine_corrupt_artifacts(best_path, rec_path, npz_path)

    def _quarantine_corrupt_artifacts(
        self, best_path: str, rec_path: str, npz_path: str
    ) -> None:
        """Move persistently-corrupt artifacts into pickles/quarantine/ so
        the next mining run writes fresh bytes and the bad ones stay
        inspectable. Only a PARSE failure condemns a file — a manifest
        mismatch alone never does: two polls can land inside one slow
        publish window (new pickle on disk, manifest/token still the old
        generation), and condemning on the mismatch would move a fresh,
        valid artifact aside and wedge the pod until the next mining run.
        A mismatched-but-parseable file keeps failing verification at
        reload time instead — visible as the degraded state, costing no
        good bytes."""
        probes = (
            (best_path, artifacts.load_pickle),
            (rec_path, artifacts.load_pickle),
            (npz_path, artifacts.load_rule_tensors),
        )
        for path, probe in probes:
            if not os.path.exists(path):
                continue
            try:
                probe(path, deadline_s=self._read_deadline())
                continue  # parses fine: never quarantine on suspicion
            except FileNotFoundError:
                continue
            except artifacts.IoStallError:
                # a slow mount is not corruption: condemning a good file
                # because the PROBE timed out would cost real bytes
                continue
            except Exception:
                pass
            dest = artifacts.quarantine_file(path)
            if dest is not None:
                self.artifact_quarantines += 1
                logger.warning(
                    "quarantined corrupt artifact %s -> %s", path, dest
                )

    def _build_replicas(
        self, rec_path: str, npz_path: str, use_npz: bool = True
    ) -> list[RuleBundle]:
        """Load the rule tensors once, then replicate them onto every
        serving device (``device_put`` per device). Host-side state (vocab,
        index, known mask) is shared across the set."""
        token = self._read_token() or ""
        loaded = None
        if (
            self.cfg.prefer_tensor_artifact
            and use_npz
            and os.path.exists(npz_path)
        ):
            try:
                loaded = artifacts.load_rule_tensors(
                    npz_path, deadline_s=self._read_deadline()
                )
            except artifacts.IoStallError:
                # a hung read is not a torn artifact: fail the RELOAD
                # (backoff + last-good serving) instead of falling back
                # to an equally-hung pickle read
                raise
            except Exception:
                # torn/corrupt npz next to a possibly-intact pickle of the
                # same generation: fall through to the pickle rather than
                # abandoning the whole reload
                logger.exception(
                    "tensor artifact %s unreadable; trying the pickle", npz_path
                )
        # continuous freshness: the candidate host state a delta bundle
        # can patch in place — committed alongside the swap in load().
        # Only the npz path carries the counts a patch needs, and merged
        # float64 confidences (rule_confs64) cannot be re-derived after a
        # patch, so those bundles serve deltas-disabled.
        self._candidate_host_state = None
        self._candidate_npz_sha = None
        if loaded is not None:
            vocab = loaded["vocab"]
            rule_ids = loaded["rule_ids"]
            rule_confs = loaded["rule_confs"]
            from ..ops.support import min_count_for

            known = loaded["item_counts"] >= min_count_for(
                loaded["min_support"], loaded["n_playlists"]
            )
            if self.cfg.delta_enabled and loaded.get("rule_confs64") is None:
                self._candidate_host_state = {
                    "vocab": list(vocab),
                    "rule_ids": np.asarray(rule_ids, dtype=np.int32),
                    "rule_counts": np.asarray(
                        loaded["rule_counts"], dtype=np.int32
                    ),
                    "item_counts": np.asarray(
                        loaded["item_counts"], dtype=np.int32
                    ),
                    "n_playlists": int(loaded["n_playlists"]),
                    "min_support": float(loaded["min_support"]),
                    "mode": str(loaded["mode"]),
                    "min_confidence": float(loaded["min_confidence"]),
                }
                self._candidate_npz_sha = artifacts.file_digest(npz_path)[
                    "sha256"
                ]
        else:
            rules_dict = artifacts.load_pickle(
                rec_path, deadline_s=self._read_deadline()
            )
            vocab = sorted(
                set(rules_dict)
                | {o for row in rules_dict.values() for o in row}
            )
            rule_ids, rule_confs, known = artifacts.tensors_from_rules_dict(
                rules_dict, vocab, k_max=max(
                    (len(r) for r in rules_dict.values()), default=1
                ),
            )
        index = {n: i for i, n in enumerate(vocab)}
        return self._replicas_from_arrays(
            vocab, index, np.asarray(known), rule_ids, rule_confs, token
        )

    def _replicas_from_arrays(
        self, vocab, index, known_mask, rule_ids, rule_confs, token
    ) -> list[RuleBundle]:
        """Build the replica set from host arrays — shared by the
        disk-artifact load above and the in-place delta apply
        (:meth:`apply_pending_deltas`), so a patched generation commits
        to devices through exactly the code a fresh load uses."""
        devs = self._serve_devices()
        # layout decision (parallel/layout.py, the one shared copy):
        # MEASURED rule-tensor bytes vs the per-device budget. A sharded
        # resolution builds ONE logical bundle spanning every serve
        # device instead of a replica per device; an armed serve gang
        # (ISSUE 16) resolves to "mesh" — this process holds ONLY its
        # vocab slab and the gang presents one logical replica.
        from ..parallel.layout import resolve_serve_span

        layout = resolve_serve_span(
            self.cfg.model_layout,
            int(rule_ids.nbytes + rule_confs.nbytes),
            self.cfg.device_budget_bytes,
            len(devs),
            gang_size=self.gang.size if self.gang is not None else 1,
        )
        if layout == "mesh" and len(vocab) > 0:
            if jax.process_count() > 1:
                # real-collectives path: the gang joined one jax
                # distributed world (maybe_initialize_serve_gang), so the
                # PR 7 shard_map kernel over the GLOBAL device set IS the
                # pod-spanning mesh — vocab axis on DCN via GSPMD. The
                # simulation transport below is the CPU-testable twin.
                return [
                    self._build_sharded_bundle(
                        vocab, index, known_mask, rule_ids, rule_confs,
                        token, jax.devices(),
                    )
                ]
            return [
                self._build_mesh_bundle(
                    vocab, index, known_mask, rule_ids, rule_confs, token
                )
            ]
        if layout == "sharded" and len(vocab) > 0:
            return [
                self._build_sharded_bundle(
                    vocab, index, known_mask, rule_ids, rule_confs,
                    token, devs,
                )
            ]
        ids_arr = jnp.asarray(rule_ids)
        confs_arr = jnp.asarray(rule_confs)
        return [
            RuleBundle(
                vocab=vocab, index=index,
                rule_ids=jax.device_put(ids_arr, dev),
                rule_confs=jax.device_put(confs_arr, dev),
                known_mask=known_mask, model_token=token,
                device=dev,
            )
            for dev in devs
        ]

    def _build_sharded_bundle(
        self, vocab, index, known_mask, rule_ids, rule_confs, token, devs
    ) -> RuleBundle:
        """ONE logical bundle whose rule tensors are vocab-sharded across
        ``devs`` (``NamedSharding(mesh, P("shard", None))``): per-device
        HBM holds ``V/S`` rule rows, so a catalog exceeding one device's
        budget serves as long as the MESH can hold it. The antecedent
        axis is padded to a multiple of the shard count with empty rows
        (-1 ids / 0 confs — unreachable: seed ids are always < V), and
        the lookup kernel is resolved here, at build time, so dispatch
        never constructs a jit closure (hot-path purity)."""
        from jax.sharding import NamedSharding, PartitionSpec
        from jax.sharding import Mesh as JaxMesh

        from ..ops.serve import sharded_recommend_fn

        n = len(devs)
        mesh = JaxMesh(np.asarray(devs), ("shard",))
        v, k = rule_ids.shape
        v_pad = ((v + n - 1) // n) * n
        if v_pad == v:
            # nothing to pad: no second copy of the tables on the host
            ids = np.asarray(rule_ids, dtype=np.int32)
            confs = np.asarray(rule_confs, dtype=np.float32)
        else:
            ids = np.full((v_pad, k), -1, dtype=np.int32)
            confs = np.zeros((v_pad, k), dtype=np.float32)
            ids[:v] = rule_ids
            confs[:v] = rule_confs
        row_spec = NamedSharding(mesh, PartitionSpec("shard", None))
        t_place = time.perf_counter()
        ids_dev = jax.device_put(ids, row_spec)
        confs_dev = jax.device_put(confs, row_spec)
        jax.block_until_ready((ids_dev, confs_dev))
        place_seconds = time.perf_counter() - t_place
        resident = {d: 0 for d in devs}
        for shard in (*ids_dev.addressable_shards, *confs_dev.addressable_shards):
            resident[shard.device] += int(shard.data.nbytes)
        bundle = RuleBundle(
            vocab=vocab, index=index,
            rule_ids=ids_dev, rule_confs=confs_dev,
            known_mask=known_mask, model_token=token,
            device=None, layout="sharded", mesh=mesh, n_shards=n,
            shard_size=v_pad // n,
            shard_kernel=sharded_recommend_fn(
                mesh, self.cfg.k_best_tracks
            ),
            seed_sharding=NamedSharding(mesh, PartitionSpec(None, None)),
            place_seconds=place_seconds,
            shard_resident_bytes=tuple(resident[d] for d in devs),
        )
        logger.info(
            "sharded layout: %d rule rows (+%d pad) across %d shards "
            "(%d rows, ~%.1f MiB of rule tensors per device), placed in "
            "%.2fs",
            v, v_pad - v, n, v_pad // n,
            (ids.nbytes + confs.nbytes) / n / (1 << 20), place_seconds,
        )
        return bundle

    def _build_mesh_bundle(
        self, vocab, index, known_mask, rule_ids, rule_confs, token
    ) -> RuleBundle:
        """ONE gang member's slice of the pod-spanning serve mesh: the
        vocab axis is padded to a multiple of the gang size and THIS
        process keeps only rows ``[rank·slab, (rank+1)·slab)`` — the
        servable catalog scales with the gang, not with one host. The
        dispatch math is the sharded kernel's two halves verbatim
        (ops/serve.py ``shard_partial_topk`` / ``merge_partial_topk``,
        the exact functions the shard_map kernel traces), so the gang's
        merged answer is bit-identical to the single-process sharded —
        and replicated — layouts by construction, pinned by
        tests/test_mesh.py."""
        gang = self.gang
        size = gang.size
        v, k = rule_ids.shape
        v_pad = ((v + size - 1) // size) * size
        slab = v_pad // size
        lo = gang.rank * slab
        hi = min(lo + slab, v)
        ids = np.full((slab, k), -1, dtype=np.int32)
        confs = np.zeros((slab, k), dtype=np.float32)
        if hi > lo:
            ids[: hi - lo] = rule_ids[lo:hi]
            confs[: hi - lo] = rule_confs[lo:hi]
        bundle = RuleBundle(
            vocab=vocab, index=index,
            rule_ids=jax.device_put(jnp.asarray(ids)),
            rule_confs=jax.device_put(jnp.asarray(confs)),
            known_mask=known_mask, model_token=token,
            device=None, layout="mesh", n_shards=size, shard_size=slab,
            gang_rank=gang.rank, mesh_v=v_pad,
            mesh_lo=jax.device_put(jnp.asarray(lo, dtype=jnp.int32)),
        )
        self._ensure_mesh_runtime()
        logger.info(
            "mesh layout: %d rule rows (+%d pad) across a %d-member gang "
            "— this rank (%d) holds rows [%d, %d) (~%.1f MiB)",
            v, v_pad - v, size, gang.rank, lo, lo + slab,
            (ids.nbytes + confs.nbytes) / (1 << 20),
        )
        return bundle

    def _ensure_mesh_runtime(self) -> None:
        """Start the gang's partial-protocol worker + coordinator once
        (idempotent; called under the reload lock at mesh publication).
        Both outlive hot swaps — the model token on every partial is the
        generation fence, not the sockets."""
        mesh_mod = self._mesh_mod
        if self.mesh_worker is None:
            self.mesh_worker = mesh_mod.MeshWorkerServer(
                self._mesh_serve_partial, self._mesh_status,
                port=self.cfg.serve_gang_port,
            )
            self.mesh_worker.start()
            logger.info(
                "serve-mesh worker listening on :%d (gang rank %d/%d)",
                self.mesh_worker.port, self.gang.rank, self.gang.size,
            )
        if self.mesh_coordinator is None:
            self.mesh_coordinator = mesh_mod.MeshCoordinator(
                self.gang,
                hedge=self.cfg.hedge_enabled,
                hedge_delay_ms=self.cfg.hedge_delay_ms,
                hedge_max_frac=self.cfg.hedge_max_frac,
                peer_slow_ratio=self.cfg.peer_slow_ratio,
            )

    def _mesh_serve_partial(self, seeds: np.ndarray):
        """Worker-side handler: run THIS rank's partial top-k for a
        peer's staged batch. Raising is the contract for 'shard not
        servable here' — the transport maps it to MeshShardUnavailable
        at the caller, which spills to the next ring peer."""
        # gray-failure chaos hook (ISSUE 18): a delay fault here turns
        # this gang member into the classic slow-but-alive straggler —
        # fenced, correct, late — that the coordinator's hedge machinery
        # must absorb without gating the merge
        faults.fire("mesh.peer", replica=self.gang.rank if self.gang else 0)
        bundle = self.bundle
        if bundle is None or bundle.layout != "mesh":
            raise RuntimeError("no mesh bundle published on this rank")
        shape = (int(seeds.shape[0]), int(seeds.shape[1]))
        if shape not in bundle.warmed_shapes:
            self.unwarmed_dispatches += 1
            logger.warning(
                "mesh partial for unwarmed shape %s — paying a compile "
                "on the serving path", shape,
            )
        from ..ops.serve import shard_partial_topk

        seeds_dev = jax.device_put(np.ascontiguousarray(seeds, np.int32))
        part_ids, part_confs = shard_partial_topk(
            bundle.rule_ids, bundle.rule_confs, seeds_dev, bundle.mesh_lo,
            v=bundle.mesh_v, k_best=self.cfg.k_best_tracks,
        )
        return (
            np.asarray(part_ids), np.asarray(part_confs),
            bundle.model_token or "",
        )

    def _mesh_status(self) -> dict:
        """The worker's 'ready' op payload — what a peer (or the
        coordinator's half-open probe) learns about this rank."""
        bundle = self.bundle
        return {
            "rank": self.gang.rank if self.gang is not None else 0,
            "epoch": self.bundle_epoch,
            "token": bundle.model_token if bundle is not None else None,
            "layout": bundle.layout if bundle is not None else None,
        }

    def mesh_missing_shards(self, probe: bool = False) -> list:
        """Sorted ranks of gang members the coordinator cannot currently
        serve through — empty outside mesh layout.
        ``probe=True`` re-auditions missing ranks (rate-limited inside
        the coordinator) so /readyz and the fleet's half-open probe are
        the re-form detectors without any background thread."""
        coord = self.mesh_coordinator
        if coord is None:
            return []
        return coord.missing_shards(probe=probe)

    def _serve_devices(self) -> list:
        """The local devices the replica set spans. ``serve_devices == 0``
        (auto) replicates onto every local device on accelerator backends;
        on CPU it stays at one — virtual CPU devices share the same host
        cores, so extra replicas there only multiply warmup compiles unless
        an operator (or a test) opts in via KMLS_SERVE_DEVICES. Exception:
        an EXPLICIT ``KMLS_MODEL_LAYOUT=sharded`` spans every local device
        even on CPU — the operator asked for vocab sharding, and one
        device has nothing to shard across."""
        from ..parallel.layout import validate_layout

        devs = jax.local_devices()
        n = self.cfg.serve_devices
        if n <= 0:
            if validate_layout(self.cfg.model_layout) == "sharded":
                n = len(devs)
            else:
                n = 1 if jax.default_backend() == "cpu" else len(devs)
        return devs[: max(1, min(n, len(devs)))]

    @property
    def n_replicas(self) -> int:
        """Serving replicas currently published (1 before the first load —
        the batcher's least-loaded dispatcher sizes its lanes off this)."""
        return max(1, len(self.replicas))

    def _note_dispatch(self, idx: int) -> None:
        with self._dispatch_lock:
            while len(self.dispatch_counts) <= idx:
                self.dispatch_counts.append(0)
            self.dispatch_counts[idx] += 1

    def _note_shard_dispatch(self, per_shard) -> None:
        with self._dispatch_lock:
            while len(self.shard_dispatch_counts) < len(per_shard):
                self.shard_dispatch_counts.append(0)
            for i, count in enumerate(per_shard):
                self.shard_dispatch_counts[i] += int(count)

    @property
    def model_layout(self) -> str:
        """The layout of the PUBLISHED bundle ("replicated" before the
        first load — there is nothing sharded to describe yet)."""
        bundle = self.bundle
        return bundle.layout if bundle is not None else "replicated"

    @property
    def n_shards(self) -> int:
        """Vocab shards in the published bundle (1 = replicated)."""
        bundle = self.bundle
        return bundle.n_shards if bundle is not None else 1

    def shard_placement(self) -> tuple[float, tuple] | None:
        """``(seconds the shards took to place, rule bytes each device
        holds in shard order)`` of the published bundle under the sharded
        layout, None under any other (one bundle read, so both describe
        the same publication)."""
        bundle = self.bundle
        if bundle is None or bundle.layout != "sharded":
            return None
        return bundle.place_seconds, bundle.shard_resident_bytes

    def _warmup(self, bundle: RuleBundle) -> None:
        """Compile EVERY (batch-bucket, length-bucket) shape before the
        bundle publishes: no request — whatever its batch size — ever pays
        a compile or a 32-wide kernel for a batch of 3. Covers BOTH model
        families: the rule max-merge kernel and, when embeddings are
        attached, the cosine top-k kernel over the same bucket grid."""
        warm_emb = bundle.emb_factors is not None
        # sharded layout warms ITS kernel (per-shard lookup + cross-device
        # max-merge) over the same bucket grid — every sharded bucket is
        # compiled before publication, same zero-compile contract. Mesh
        # layout warms the kernel's two factored halves instead: the
        # local slab partial (served to peers AND dispatched locally) and
        # the rank-stacked merge — every gang member compiles both for
        # every bucket before its bundle publishes.
        warm_mesh = bundle.layout == "mesh"
        kernel = bundle.shard_kernel or self._kernel
        if warm_mesh:
            from ..ops.serve import merge_partial_topk, shard_partial_topk
        if bundle.shard_kernel is not None:
            self._precompile_sharded(bundle)
        for length in self._len_buckets():
            for batch in self._batch_buckets():
                seeds = jnp.full((batch, length), -1, dtype=jnp.int32)
                target = bundle.seed_sharding or bundle.device
                rule_seeds = seeds
                if target is not None:
                    # commit the seeds to the replica's device (or, in
                    # sharded layout, replicate them over the mesh) so the
                    # warmed executable is the one its dispatches will hit
                    rule_seeds = jax.device_put(seeds, target)
                if warm_mesh:
                    kb = self.cfg.k_best_tracks
                    part_ids, part_confs = shard_partial_topk(
                        bundle.rule_ids, bundle.rule_confs, rule_seeds,
                        bundle.mesh_lo, v=bundle.mesh_v, k_best=kb,
                    )
                    stack_ids = jnp.broadcast_to(
                        part_ids, (bundle.n_shards,) + part_ids.shape
                    )
                    stack_confs = jnp.broadcast_to(
                        part_confs, (bundle.n_shards,) + part_confs.shape
                    )
                    jax.block_until_ready(
                        merge_partial_topk(stack_ids, stack_confs, k_best=kb)
                    )
                else:
                    jax.block_until_ready(
                        kernel(bundle.rule_ids, bundle.rule_confs, rule_seeds)
                    )
                bundle.warmed_shapes.add((batch, length))
                if warm_emb:
                    # the embedding kernel dispatches with _dispatch_embed's
                    # placement (bundle.device; default placement in the
                    # sharded layout, where only the RULE tensors span the
                    # mesh) — warm with the same placement, or the warmed
                    # executable would not be the dispatched one
                    emb_seeds = (
                        jax.device_put(seeds, bundle.device)
                        if bundle.device is not None else seeds
                    )
                    jax.block_until_ready(
                        embed_topk(
                            bundle.emb_factors, emb_seeds,
                            k_best=self.cfg.k_best_tracks,
                        )
                    )
                    bundle.emb_warmed_shapes.add((batch, length))

    def _precompile_sharded(self, bundle: RuleBundle) -> None:
        """Compile the sharded lookup's bucket grid side by side; the
        warm-up loop then runs each shape in turn and finds its program
        compiled (JAX keeps a lowered program's executable in memory, and
        in the persistent cache where there is one). A program over a
        four-device mesh at a 9.39M vocabulary compiles in 4.3 s, 24 of
        them one after the other in 104 s (PR 38, on the chip), and
        compiling releases the interpreter lock. Nothing runs here: two
        collective programs launched from two threads may reach the
        devices in different orders."""
        shapes = [
            (batch, length)
            for length in self._len_buckets() for batch in self._batch_buckets()
        ]

        def compile_one(shape) -> None:
            seeds = jax.device_put(
                np.full(shape, -1, dtype=np.int32), bundle.seed_sharding
            )
            bundle.shard_kernel.lower(
                bundle.rule_ids, bundle.rule_confs, seeds
            ).compile()

        workers = min(len(shapes), os.cpu_count() or 1)
        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(compile_one, shapes))

    def prewarm_touch(self) -> int:
        """Predictive shape pre-touch (ISSUE 17, actuator a): re-dispatch
        the LARGEST warmed (batch, length) bucket once per device replica
        on the live bundle, so the big-batch executables and every
        replica's dispatch path are hot before a predicted ramp sends
        real traffic through them. Publish-time warmup already compiled
        every bucket — this touch pays one dispatch per replica, never a
        compile (the shape is in ``warmed_shapes``). Best-effort and off
        the request path: the batcher runs it on a daemon thread once
        per ramp episode; failures are logged and ignored (a missed
        touch just means the ramp is served as reactively as before).
        Mesh bundles are skipped (their partial-fetch warmup is gang-
        coordinated at publish; a solo re-touch would not exercise the
        peer path). → shapes touched."""
        replicas = self.replicas
        if not replicas:
            return 0
        batch = self._batch_buckets()[-1]
        length = self._len_buckets()[-1]
        touched = 0
        for bundle in replicas:
            warm_rules = bundle.layout != "mesh"
            warm_emb = bundle.emb_factors is not None
            if not warm_rules and not warm_emb:
                continue
            try:
                seeds = jnp.full((batch, length), -1, dtype=jnp.int32)
                if warm_rules:
                    target = bundle.seed_sharding or bundle.device
                    rule_seeds = (
                        jax.device_put(seeds, target)
                        if target is not None else seeds
                    )
                    kernel = bundle.shard_kernel or self._kernel
                    jax.block_until_ready(
                        kernel(bundle.rule_ids, bundle.rule_confs, rule_seeds)
                    )
                    touched += 1
                if warm_emb:
                    emb_seeds = (
                        jax.device_put(seeds, bundle.device)
                        if bundle.device is not None else seeds
                    )
                    jax.block_until_ready(
                        embed_topk(
                            bundle.emb_factors, emb_seeds,
                            k_best=self.cfg.k_best_tracks,
                        )
                    )
                    touched += 1
            except Exception:
                logger.exception("predictive pre-touch failed (ignored)")
        return touched

    def _read_measured_blend_weight(self) -> float | None:
        """The quality loop's published blend optimum (ISSUE 14), or
        None — measured mode off, no report on the PVC, or a report
        without a usable weight. Fail-SOFT: the serving default is
        always a legitimate answer; a missing measurement must degrade
        the decision, never the reload."""
        if not getattr(self.cfg, "hybrid_blend_measured", False):
            return None
        report = artifacts.load_quality_report(self.cfg.pickles_dir)
        weight = report.get("measured_blend_weight") if report else None
        if isinstance(weight, (int, float)) and 0.0 <= float(weight) <= 1.0:
            return float(weight)
        logger.warning(
            "KMLS_HYBRID_BLEND_WEIGHT=measured but no usable "
            "quality.report.json on the PVC (report %s); serving the "
            "default weight %.2f",
            "absent" if report is None else "carries no measured weight",
            self.cfg.hybrid_blend_weight,
        )
        return None

    @property
    def blend_weight(self) -> float:
        """The EFFECTIVE hybrid blend weight: the measured optimum when
        KMLS_HYBRID_BLEND_WEIGHT=measured published one, else the
        configured float (which is also the fail-safe when measurement
        was requested but no report exists)."""
        if self.measured_blend_weight is not None:
            return self.measured_blend_weight
        return self.cfg.hybrid_blend_weight

    @property
    def embedding_active(self) -> bool:
        """True when the published bundle carries ALS item factors (the
        hybrid merge path is live)."""
        bundle = self.bundle
        return bundle is not None and bundle.emb_factors is not None

    def reload_if_required(self) -> None:
        """Reference: reload when stale or never fully loaded
        (rest_api/app/main.py:110-114). After a FAILED reload this retries
        on the exponential backoff ladder instead of every poll/nudge —
        the staleness signal survives untouched (is_data_stale is pure),
        so the retry always happens; it just stops being a busy loop
        against a poison artifact.

        Continuous freshness rides the same poll: a NOT-stale generation
        still checks the delta chain and applies new bundles in place
        (rejections back off on ``_delta_backoff_until`` so a poison
        bundle can't turn the poller into a digest-hashing busy loop;
        direct :meth:`apply_pending_deltas` calls always go through,
        mirroring load())."""
        if time.monotonic() < self._backoff_until:
            return
        if self.is_data_stale() or not self.finished_loading:
            if self.load():
                self.apply_pending_deltas()
        elif (
            self.cfg.delta_enabled
            and time.monotonic() >= self._delta_backoff_until
        ):
            self.apply_pending_deltas()

    # ---------- continuous freshness: in-place delta application ----------

    def freshness_lag_s(self) -> float:
        """Age of the newest APPLIED generation (base publication or
        delta chain entry) — what dashboards alert on as freshness lag.
        0.0 before the first load."""
        if not self._applied_written_at:
            return 0.0
        return max(time.time() - self._applied_written_at, 0.0)

    @staticmethod
    def _file_written_at(path: str, fallback: float) -> float:
        """Best-effort artifact publication stamp: the file's mtime, or
        the generation's manifest stamp when the file can't answer."""
        try:
            return os.path.getmtime(path)
        except OSError:
            return fallback

    def artifact_ages(self) -> dict[str, float]:
        """Per-artifact freshness age (seconds since publication) for
        every artifact the server currently answers from — the
        staleness-bound surface /readyz and the
        ``kmls_artifact_age_seconds`` gauge report. ``delta-chain`` is
        the age of the newest APPLIED generation (base or delta): with
        no deltas applied it equals ``rules``, and a delta apply shrinks
        it without touching the base stamp — exactly the gap the delta
        path exists to shrink. Empty before the first load."""
        if not self._artifact_written_at:
            return {}
        now = time.time()
        out = {
            name: max(now - stamp, 0.0)
            for name, stamp in self._artifact_written_at.items()
        }
        out["delta-chain"] = self.freshness_lag_s()
        return out

    def _note_publish_cost(self, replicas: list[RuleBundle]) -> None:
        """Publish-time cost-model bookkeeping (caller holds
        ``_reload_lock``; cost model known non-None): the analytic
        tensor residency the layout decision measured, the live
        bytes-in-use watermark where the backend reports one, and the
        compile-watch snapshot for every jitted kernel this generation
        dispatches (taken AFTER warmup, so post-publish cache growth is
        exactly a compile on the serving path)."""
        cm = self.cost_model
        bundle = replicas[0]
        tensor_bytes = {
            "rule_ids": int(bundle.rule_ids.nbytes),
            "rule_confs": int(bundle.rule_confs.nbytes),
        }
        if bundle.emb_factors is not None:
            tensor_bytes["embeddings"] = int(bundle.emb_factors.nbytes)
        cm.note_publish(
            tensor_bytes,
            self.cfg.device_budget_bytes,
            n_shards=bundle.n_shards,
            watermark_bytes=costmodel_mod.device_watermark_bytes(
                bundle.device
            ),
        )
        if bundle.layout == "mesh":
            # the gang dispatch composes the kernel's two factored
            # halves — watch both jit caches under one name (the
            # snapshot sums, so any post-publish compile on either
            # half reads as serving-path compile growth)
            from ..ops.serve import merge_partial_topk, shard_partial_topk

            cm.watch_compiles("serve_mesh", shard_partial_topk)
            cm.watch_compiles("serve_mesh_merge", merge_partial_topk)
        elif bundle.shard_kernel is not None:
            cm.watch_compiles("serve_sharded", bundle.shard_kernel)
        else:
            # the engine wraps the jitted fn in a partial(k_best=);
            # the jit cache lives on the underlying function
            cm.watch_compiles("serve_rules", self._kernel.func)
        if bundle.emb_factors is not None:
            cm.watch_compiles("embed_topk", embed_topk)
        cm.mark_published()

    def _note_delta_rejection(self, seq: int, message: str) -> None:
        self.delta_rejected_total += 1
        self.last_delta_error = message
        self._delta_backoff_until = (
            time.monotonic() + self.cfg.reload_backoff_base_s
        )
        logger.warning(
            "delta bundle %d REJECTED (%s); base generation keeps "
            "serving, retry after %.1fs",
            seq, message, self.cfg.reload_backoff_base_s,
        )

    def apply_pending_deltas(self) -> int:
        """Apply every not-yet-applied bundle of the current generation's
        delta chain IN PLACE → bundles applied.

        Each apply rebuilds the replica set from the patched host tensors
        through the same array path a fresh load uses (per-device
        ``device_put``; vocab-sharded layout included), re-warms the
        kernel buckets (a no-op cost when shapes are unchanged — the jit
        cache hits), and swaps the replica references WITHOUT bumping
        ``bundle_epoch``: the answer cache invalidates selectively via
        ``delta_listeners`` (only keys whose seeds intersect the touched
        vocab). The one exception is a blend-mode hybrid bundle whose
        ``n_playlists`` moved — the global 1/P confidence rescale shifts
        every blended ranking, so that apply bumps the epoch (wholesale
        invalidation, the safe direction). Any validation failure — torn
        bytes, wrong base binding, chain gap, the ``delta.apply`` chaos
        site — rejects the bundle and keeps the current state serving:
        bad delta ⇒ keep base, never a 5xx."""
        if not self.cfg.delta_enabled or not self.finished_loading:
            return 0
        state = artifacts.read_delta_state(self.cfg.pickles_dir)
        if state is None:
            return 0
        from ..freshness import delta as delta_mod

        applied = 0
        with self._reload_lock:
            if state.get("base_token") != self.cache_value:
                return 0  # chain for another generation: inert here
            # chain-length gauge: the compaction trigger must be visible
            # BEFORE the compactor acts on it, whether or not anything
            # below is new enough to apply
            self.delta_chain_length = len(state.get("entries", ()))
            pending = [
                e for e in sorted(
                    state.get("entries", []), key=lambda e: e.get("seq", 0)
                )
                if e.get("seq", 0) > self.delta_seq
            ]
            if not pending:
                return 0
            if self._host_state is None:
                logger.warning(
                    "delta chain present but this bundle has no patchable "
                    "host tensors (pickle-only load or merged-confidence "
                    "artifact); serving the base generation"
                )
                return 0
            if self.cost_model is not None:
                # same pre-warmup banking as load(): the applies below
                # re-warm patched tensors legitimately
                self.cost_model.note_prepublish()
            for entry in pending:
                seq = int(entry.get("seq", 0))
                if seq != self.delta_seq + 1:
                    self._note_delta_rejection(
                        seq, f"chain gap: expected seq {self.delta_seq + 1}"
                    )
                    break
                path = os.path.join(
                    self.cfg.pickles_dir, str(entry.get("file", ""))
                )
                try:
                    # chaos hook: KMLS_FAULT_DELTA_CORRUPT rejects here
                    faults.fire("delta.apply")
                    bundle = artifacts.load_delta_bundle(
                        path, expect_sha256=entry.get("sha256")
                    )
                    if bundle["base_token"] != self.cache_value:
                        raise ValueError(
                            "bundle base token != serving generation"
                        )
                    if (
                        self._base_npz_sha is not None
                        and bundle["base_npz_sha256"] != self._base_npz_sha
                    ):
                        raise ValueError(
                            "bundle bound to different base artifact bytes"
                        )
                    patched = delta_mod.apply_delta_to_tensors(
                        self._host_state, bundle
                    )
                    vocab, rule_ids, rule_confs, known = (
                        delta_mod.derive_serving_arrays(patched)
                    )
                    index = {n: i for i, n in enumerate(vocab)}
                    old_replicas = self.replicas
                    replicas = self._replicas_from_arrays(
                        vocab, index, known, rule_ids, rule_confs,
                        self.cache_value or "",
                    )
                    # the second model family rides along untouched:
                    # factors are already committed to each replica's
                    # device, and their warmed shapes stay warmed
                    for i, nb in enumerate(replicas):
                        if i < len(old_replicas):
                            src = old_replicas[i]
                            nb.emb_factors = src.emb_factors
                            nb.emb_vocab = src.emb_vocab
                            nb.emb_index = src.emb_index
                            nb.emb_warmed_shapes = src.emb_warmed_shapes
                    for nb in replicas:
                        self._warmup(nb)
                except Exception as exc:
                    self._note_delta_rejection(
                        seq, f"{type(exc).__name__}: {exc}"
                    )
                    break
                # blend-mode hybrid + moved P: the uniform confidence
                # rescale shifts every blended ranking, so untouched keys
                # are NOT safe — bump the epoch (wholesale invalidation)
                wholesale = (
                    self.cfg.hybrid_mode == "blend"
                    and any(r.emb_factors is not None for r in replicas)
                    and patched["n_playlists"]
                    != self._host_state["n_playlists"]
                )
                epoch = self.bundle_epoch + (1 if wholesale else 0)
                for nb in replicas:
                    nb.epoch = epoch
                # ordering contract (same as load's): replica references
                # land BEFORE the invalidation signal (epoch bump or the
                # listeners' generation bump), so an answer stored under
                # a post-invalidation key can only have been computed
                # from the patched tensors
                self.replicas = replicas
                self.bundle = replicas[0]
                if wholesale:
                    self.bundle_epoch = epoch
                self._host_state = patched
                self.delta_seq = seq
                self.delta_applied_total += 1
                self.last_delta_error = None
                self._applied_written_at = float(
                    entry.get("written_at") or time.time()
                )
                # cost attribution: an in-place apply re-publishes the
                # patched tensors (new residency, possibly new warmed
                # shapes) — re-snapshot so legitimate re-warm compiles
                # are absorbed exactly like a full publication's
                if self.cost_model is not None:
                    self._note_publish_cost(replicas)
                applied += 1
                touched = delta_mod.touched_names(bundle)
                logger.info(
                    "delta %d applied in place (epoch %d/%d): %d changed "
                    "rows, %d tombstones, %d touched names%s",
                    seq, self.bundle_epoch, self.delta_seq,
                    len(bundle["changed_rows"]), len(bundle["tombstones"]),
                    len(touched),
                    " [wholesale invalidation]" if wholesale else "",
                )
                for fn in list(self.delta_listeners):
                    try:
                        fn(touched, wholesale)
                    except Exception:
                        logger.exception("delta listener failed")
        return applied

    # ---------- lookups ----------

    def _len_buckets(self) -> list[int]:
        """Coarse seed-length buckets: every (batch, length) shape a request
        can produce is warmed at load time, so no request ever pays a
        compile. The cap itself is always a member — a >128-seed bucket must
        be warmable too."""
        cap = self.cfg.max_seed_tracks
        return sorted({min(b, cap) for b in (1, 8, 32, 128)} | {cap})

    def _bucket_len(self, n: int) -> int:
        buckets = self._len_buckets()
        for b in buckets:
            if n <= b:
                return b
        return buckets[-1]

    def _batch_buckets(self) -> list[int]:
        """Power-of-two batch buckets 1, 2, 4, …, up to (and always
        including) ``batch_max_size`` — the full set the warmup compiles."""
        cap = max(self.cfg.batch_max_size, 1)
        buckets = []
        b = 1
        while b < cap:
            buckets.append(b)
            b *= 2
        buckets.append(cap)
        return buckets

    def _bucket_batch(self, n: int) -> int:
        """Smallest warmed batch bucket holding ``n`` rows; oversized
        batches (possible only via direct ``recommend_many`` calls — the
        micro-batcher caps at ``batch_max_size``) round up to a multiple
        of the cap, keeping the shape set bounded."""
        cap = max(self.cfg.batch_max_size, 1)
        if n > cap:
            return ((n + cap - 1) // cap) * cap
        for b in self._batch_buckets():
            if n <= b:
                return b
        return cap

    @staticmethod
    def _fill_seed_rows(
        bundle: RuleBundle, seed_sets: list[list[str]],
        arr: np.ndarray, length: int,
    ) -> tuple[np.ndarray, int]:
        """Membership-filter each seed set into its -1-padded row of
        ``arr`` → (per-row any-known-seed mask, how many slots of the
        array hold a seed). The ONE copy of the seed filtering rule."""
        for r, seeds in enumerate(seed_sets):
            ids = [
                bundle.index[s]
                for s in seeds
                if s in bundle.index and bundle.known_mask[bundle.index[s]]
            ][:length]
            arr[r, : len(ids)] = ids
        filled = arr[: len(seed_sets)] >= 0
        return filled.any(axis=1), int(filled.sum())

    def _note_staged(
        self, arr: np.ndarray, n_real: int, trace, stage: int | None,
    ) -> None:
        """The staged array is filled and on its way: count its slots
        (always on: two integer adds a batch) and, on a traced batch,
        close the ``stage`` span (its id ``stage`` was reserved when it
        began) and say what was staged."""
        with self._dispatch_lock:
            self.seed_slots_real += n_real
            self.seed_slots_padded += arr.size - n_real
        if trace is not None:
            trace.lap("stage", span_id=stage)
            trace.attrs.update(
                rows=arr.shape[0], length=arr.shape[1], seeds_real=n_real
            )

    def _stage_seeds(
        self, bundle: RuleBundle, seed_sets: list[list[str]],
        rows: int, length: int, trace=None, stage: int | None = None,
    ) -> tuple[np.ndarray, jax.Array, np.ndarray]:
        """Fill the padded (rows, length) seed-index array and transfer it
        → (host seed array, device seed array, per-row any-known-seed
        mask). The host array is a fresh one per dispatch and is never
        refilled, so a transfer still under way (or, in the mesh layout,
        a peer fan-out still serializing it) cannot see another batch's
        seeds. The transfer targets the bundle's own placement (its
        device; the mesh-replicated sharding in the sharded layout), so a
        replica's dispatch runs on the replica's chip. ``trace`` is the
        batch's trace (None = untraced) and ``stage`` its open ``stage``
        span's id, reserved by the caller where staging began: the span
        ends where the transfer has been issued, and holds ``fill_rules``
        (the array filled) and ``put_rules`` (the transfer issued)."""
        shape = (rows, length)
        if trace is not None:
            t_fill = time.perf_counter()
        arr = np.full(shape, -1, dtype=np.int32)
        known_rows, n_real = self._fill_seed_rows(
            bundle, seed_sets, arr, length
        )
        if trace is not None:
            trace.child("fill_rules", stage, t_fill)
        if bundle.n_shards > 1 and bundle.shard_size > 0:
            # per-shard dispatch accounting: which vocab shard's rows
            # this batch's seed ids actually hit (host integer math on
            # the already-staged array — no device sync)
            hit = arr[arr >= 0]
            if hit.size:
                self._note_shard_dispatch(np.bincount(
                    hit // bundle.shard_size, minlength=bundle.n_shards
                ))
        if trace is not None:
            t_put = time.perf_counter()
        seeds_dev = jax.device_put(
            arr, bundle.seed_sharding or bundle.device
        )
        if trace is not None:
            # the mesh-replicated placement copies the array to each device
            trace.child("put_rules", stage, t_put, {
                "bytes": arr.nbytes,
                "devices": len(bundle.seed_sharding.device_set)
                if bundle.seed_sharding is not None else 1,
            })
        self._note_staged(arr, n_real, trace, stage)
        if shape not in bundle.warmed_shapes:
            # a compile is landing on the serving path — count it loudly
            self.unwarmed_dispatches += 1
            logger.warning(
                "unwarmed seed shape %s dispatched (compile on the "
                "serving path); warmed buckets: batches %s x lengths %s",
                shape, self._batch_buckets(), self._len_buckets(),
            )
        return arr, seeds_dev, known_rows

    def _dispatch_rules(
        self, bundle: RuleBundle, arr: np.ndarray, seeds_dev: jax.Array,
        deadline: float | None,
    ):
        """Enqueue the rule lookup for a staged batch: the one step of a
        dispatch that a layout varies → ``(device results, pick_up,
        remote)``. ``device results`` are the arrays whose copies to the
        host the caller starts; ``pick_up()`` blocks and returns the
        batch's host ``(top ids, top confidences)``; ``remote`` is the
        mesh coordinator's peer-fetch handle (its ``dropped`` ranks and
        ``hedge_outcome`` are read after the pick-up), None in the local
        layouts.

        Local layouts (replicated, sharded) enqueue the bundle's kernel —
        the vocab-sharded lookup resolved at publication, or the
        per-replica one — and pick its two results up as they are.

        The mesh layout fans the host array to every gang peer FIRST
        (socket I/O overlaps the local device work), enqueues this
        rank's slab partial, and at pick-up stacks the rank-ordered
        partials and runs the merge — the same two functions the
        single-process shard_map kernel composes, so the answer is
        bit-identical by construction. A dead gang member surfaces as
        :class:`~.mesh.MeshShardUnavailable` out of ``pick_up()``: the
        app maps it to the gang-degraded signal (503 +
        ``X-KMLS-Mesh-Unavailable`` under fleet routing) and the routed
        client spills the request to the next ring peer."""
        if bundle.layout != "mesh":
            top_ids, top_confs = (bundle.shard_kernel or self._kernel)(
                bundle.rule_ids, bundle.rule_confs, seeds_dev
            )

            def pick_up_local() -> tuple[np.ndarray, np.ndarray]:
                # waits for the copies the caller started at dispatch
                return np.asarray(top_ids), np.asarray(top_confs)

            return (top_ids, top_confs), pick_up_local, None

        from ..ops.serve import merge_partial_topk, shard_partial_topk

        kb = self.cfg.k_best_tracks
        # deadline propagation: stamp the REMAINING budget on the peer
        # frames (computed now — staging time already spent), so a
        # backed-up worker sheds expired partials instead of computing
        # results nobody will wait for
        budget_ms = None
        if deadline is not None:
            budget_ms = max(0.0, (deadline - time.perf_counter()) * 1e3)
        remote = self.mesh_coordinator.fetch_partials(
            arr, bundle.model_token or "", budget_ms=budget_ms
        )
        part_ids, part_confs = shard_partial_topk(
            bundle.rule_ids, bundle.rule_confs, seeds_dev, bundle.mesh_lo,
            v=bundle.mesh_v, k_best=kb,
        )

        def pick_up_mesh() -> tuple[np.ndarray, np.ndarray]:
            local_ids = np.asarray(part_ids)  # blocks on the device
            local_confs = np.asarray(part_confs)
            # blocks on the slowest peer; raises MeshShardUnavailable
            # for the first rank the gang cannot serve through
            parts = remote()
            stack_ids = np.empty(
                (bundle.n_shards,) + local_ids.shape, dtype=np.int32
            )
            stack_confs = np.empty(
                (bundle.n_shards,) + local_confs.shape, dtype=np.float32
            )
            stack_ids[bundle.gang_rank] = local_ids
            stack_confs[bundle.gang_rank] = local_confs
            for rank, (ids_r, confs_r) in parts.items():
                stack_ids[rank] = ids_r
                stack_confs[rank] = confs_r
            # hedged straggler-drop / deadline-shed (ISSUE 18): ranks the
            # coordinator dropped contribute NOTHING to the merge — their
            # slots get -inf confidences so the max-merge never selects
            # them (the caller marks every answer degraded)
            for rank in getattr(remote, "dropped", None) or ():
                stack_ids[rank] = 0
                stack_confs[rank] = np.float32(-np.inf)
            merged_ids, merged_confs = merge_partial_topk(
                stack_ids, stack_confs, k_best=kb
            )
            return np.asarray(merged_ids), np.asarray(merged_confs)

        return (part_ids, part_confs), pick_up_mesh, remote

    # ---------- second model family: embedding dispatch + hybrid merge ----

    def _serves_embed(self, bundle: RuleBundle) -> bool:
        """Whether a batch on ``bundle`` dispatches the embedding lookup
        at all: the bundle carries factors and the operator did not pin
        rules-only."""
        return bundle.emb_factors is not None and self.cfg.hybrid_mode != "rules"

    def _embed_goes_first(self, bundle: RuleBundle) -> bool:
        """Whether a batch on ``bundle`` launches the embedding lookup
        before the rule lookup (:meth:`recommend_many_async`): wherever it
        serves the embedding family, but in the mesh layout, whose peer
        fan-out goes first."""
        return self._serves_embed(bundle) and bundle.layout != "mesh"

    def _dispatch_embed(
        self, bundle: RuleBundle, seed_sets: list[list[str]],
        n_rows: int, length: int, trace=None, parent: int | None = None,
    ):
        """Dispatch the embedding cosine top-k for a batch → ``(device
        top_ids, device top_sims, host known-row mask)``, or None when the
        bundle carries no factors / the operator pinned rules-only. Runs
        on the DISPATCH path (no host syncs — jax dispatch is async) and
        starts its results' copies to the host right after the enqueue;
        the caller's ``finish()`` picks them up. The (n_rows, length)
        shape must come from the warmed bucket grid — an unwarmed shape
        is counted and logged exactly like the rule kernel's. On a traced
        batch (``trace``; ``parent`` is the id of the open span it runs
        in: ``stage`` where it goes first, ``dispatch`` in the mesh
        layout) the fill, the transfer and the enqueue are
        ``fill_embed``, ``put_embed`` and ``enqueue_embed``."""
        if not self._serves_embed(bundle):
            return None
        if trace is not None:
            t_fill = time.perf_counter()
        arr = np.full((n_rows, length), -1, dtype=np.int32)
        known = np.zeros(len(seed_sets), dtype=bool)
        index = bundle.emb_index or {}
        for r, seeds in enumerate(seed_sets):
            ids = [index[s] for s in seeds if s in index][:length]
            arr[r, : len(ids)] = ids
            known[r] = len(ids) > 0
        if trace is not None:
            trace.child("fill_embed", parent, t_fill)
        if not known.any():
            # no row has an embed-known seed: the kernel's output would be
            # ignored wholesale — skip the transfer + full-vocab matmul
            return None
        if trace is not None:
            t_put = time.perf_counter()
        seeds_dev = jax.device_put(arr, bundle.device)
        if trace is not None:
            trace.child(
                "put_embed", parent, t_put,
                {"bytes": arr.nbytes, "devices": 1},
            )
        shape = (n_rows, length)
        if shape not in bundle.emb_warmed_shapes:
            self.unwarmed_dispatches += 1
            logger.warning(
                "unwarmed embedding seed shape %s dispatched (compile on "
                "the serving path); warmed buckets: batches %s x lengths %s",
                shape, self._batch_buckets(), self._len_buckets(),
            )
        if trace is not None:
            t_enqueue = time.perf_counter()
        top_ids, top_sims = embed_topk(
            bundle.emb_factors, seeds_dev, k_best=self.cfg.k_best_tracks
        )
        if trace is not None:
            trace.child("enqueue_embed", parent, t_enqueue)
        _start_host_copies(top_ids, top_sims)
        return top_ids, top_sims, known

    def _compose_answer(
        self, bundle: RuleBundle, seeds: list[str], rule_known: bool,
        ids_row, confs_row, emb_row,
    ) -> tuple[list[str], str]:
        """Merge the two model families' top-k for ONE request → (songs,
        source ∈ {"rules", "embed", "hybrid", "fallback", "empty"}).

        ``emb_row`` is ``(ids, sims, known)`` host rows or None (no
        embeddings / rules-only mode) — None reproduces the legacy
        rules-only behavior bit for bit. The merge is pure host float
        arithmetic over ≤ 2·k candidates with a deterministic tie order
        (score desc, name asc), so every replica — and every cache epoch
        over identical artifacts — composes the identical answer."""
        emb_known = emb_row is not None and bool(emb_row[2])
        if not rule_known and not emb_known:
            return self.static_recommendation(seeds), "fallback"
        if not emb_known:
            songs = [bundle.vocab[int(i)] for i in ids_row if i >= 0]
            return songs, ("rules" if songs else "empty")
        emb_pairs = [
            (bundle.emb_vocab[int(i)], float(s))
            for i, s in zip(emb_row[0], emb_row[1])
            if i >= 0
        ]
        if self.cfg.hybrid_mode == "embed" or not rule_known:
            # embed-only mode, or a cold-start seed the rules have never
            # seen: the embedding answer IS the answer (this is the
            # scenario the second model family exists for)
            songs = [n for n, _ in emb_pairs]
            return songs, ("embed" if songs else "empty")
        # blend: union of both candidate lists, scores mixed by the
        # effective weight (the knob, or the measured optimum under
        # KMLS_HYBRID_BLEND_WEIGHT=measured) — one shared merge with the
        # offline harness, so eval numbers describe this exact ranking
        rule_pairs = [
            (bundle.vocab[int(i)], float(c))
            for i, c in zip(ids_row, confs_row)
            if i >= 0
        ]
        songs = blend_candidates(
            rule_pairs, emb_pairs, self.blend_weight, self.cfg.k_best_tracks
        )
        return songs, ("hybrid" if songs else "empty")

    def recommend(self, seed_tracks: list[str]) -> tuple[list[str], str]:
        """→ (songs, source), source ∈ {"rules", "embed", "hybrid",
        "fallback", "empty"}: a batch of one.

        Mirrors rest_api/app/main.py:224-254, including: degraded fallback
        while rules are loading (:225-228), membership filter (:235),
        fallback only when NO seed is known to EITHER model family
        (:236-238 — the reference knows only rules), and results that may
        legitimately be empty when all known seeds have empty rows.
        """
        return self.recommend_many_async([seed_tracks])()[0]

    def recommend_many_async(
        self, seed_sets: list[list[str]], replica: int | None = None,
        deadline: float | None = None, trace=None,
    ):
        """Batched lookup split into DISPATCH (device calls enqueued, returns
        immediately — jax dispatch is asynchronous) and FINISH (a zero-arg
        callable that blocks on the results and builds the responses).

        One skeleton for every layout: stage the rule seeds (one fresh
        host array, one transfer), enqueue the rule lookup
        (:meth:`_dispatch_rules` — the only step a layout varies), stage
        and enqueue the embedding lookup (:meth:`_dispatch_embed`), and
        start each result's device → host copy
        (:func:`_start_host_copies`) right after its program's enqueue,
        behind it on the device's stream. Where the bundle serves the
        embedding family beside local rule tensors, the embedding lookup
        is launched FIRST: the two programs are independent, the rule
        program is short (tens of microseconds against the embedding
        scan's ~1.3 ms on a v5e), so launched first it left the chip
        idle through the embedding family's fill, transfer and enqueue;
        launched second, the rule family's host work runs while the chip
        scans the catalog. The mesh layout keeps the rule lookup first:
        its peer fan-out's socket round trip is the longest pole there.
        ``finish()`` picks the results up in program order, the chip's
        in-order stream finishing them in that order: a pick-up finds its
        copy done or under way, and does not start one and sit it out
        before the next can begin.

        The split lets the micro-batcher pipeline device calls: a
        dispatch-block-respond loop caps throughput at batch_size over
        the blocked call's latency; overlapping the next dispatch with
        the previous batch's device time and transfer removes that
        ceiling. :meth:`recommend` is this path with a batch of one.

        ``replica`` selects which device replica executes the batch (the
        least-loaded dispatcher in serving/batcher.py passes it); None
        uses the primary. Concurrent batches on DIFFERENT replicas run
        on different devices instead of serializing on one in-order
        execution queue.

        ``deadline`` (perf_counter seconds, the batcher's earliest
        pending deadline) propagates across the mesh as each partial
        frame's remaining-budget field — a gang peer sheds work that
        expired in transit instead of computing it (ISSUE 18). The
        local layouts ignore it (their budget is enforced at the
        app layer, as before).

        ``trace`` is the batch's own trace (``SpanRecorder.begin_batch``;
        None = untraced, and then each site below is one is-None check).
        Every layout records the same spans on it with
        ``TraceContext.lap``, each where the work happens: ``stage``
        (:meth:`_note_staged`: everything before the rule program is
        enqueued) and ``dispatch`` (the rule enqueue — in the mesh layout
        the peer fan-out too — and the starting of its results' copies)
        here; ``handoff`` (from the end of ``dispatch`` to ``finish()``
        starting: the batcher's bookkeeping and its hop to the thread
        that runs ``finish()``), ``fetch_embed`` (the embedding pair's
        pick-up), ``fetch_rules`` (the rule pair's), in program order,
        and ``compose`` in ``finish()``. Inside ``stage`` lie
        ``fill_rules`` and ``put_rules`` (:meth:`_stage_seeds`); inside
        ``dispatch`` lies ``enqueue_rules`` (the whole
        :meth:`_dispatch_rules` call); ``fill_embed``, ``put_embed`` and
        ``enqueue_embed`` (:meth:`_dispatch_embed`) lie at the front of
        ``stage`` where the embedding lookup goes first, and after
        ``enqueue_rules`` inside ``dispatch`` in the mesh layout. Each
        child is recorded with ``TraceContext.child`` under its parent's
        reserved id. What a parent does outside its children (the shard
        count, the copies' start) is its own time. The fallback (nothing
        published yet) records ``handoff`` and ``compose`` alone."""
        if trace is not None:
            trace.skip()  # the engine's part of the batch starts here
        replicas = self.replicas
        idx = 0
        if replica is not None and replicas:
            idx = replica % len(replicas)
        bundle = replicas[idx] if replicas else self.bundle
        if bundle is None:
            # degrade + nudge a reload, like the reference's late-load path
            threading.Thread(target=self.reload_if_required, daemon=True).start()

            def finish_fallback() -> list[tuple[list[str], str]]:
                if trace is not None:
                    trace.lap("handoff")
                out = [
                    (self.static_recommendation(s), "fallback")
                    for s in seed_sets
                ]
                if trace is not None:
                    trace.lap("compose")
                return out

            return finish_fallback

        length = self._bucket_len(
            max((len(s) for s in seed_sets), default=1)
        )
        # pad the batch dimension UP to the nearest power-of-two bucket: a
        # varying batch dimension would compile a fresh kernel per distinct
        # size, and padding every batch to the 32-wide cap (the old scheme)
        # made a batch of 3 pay a 32-row kernel — ~8x the work on the
        # lane ranking. Every bucket is pre-warmed at bundle publish.
        n_rows = self._bucket_batch(max(len(seed_sets), 1))
        cm = self.cost_model
        # both families take the one (n_rows, length) bucket and the
        # replica's device, both async, both picked up in finish()
        embed_first = self._embed_goes_first(bundle)
        stage = trace.reserve() if trace is not None else None
        emb, t_embed = None, 0.0
        if embed_first:
            t_embed = time.perf_counter() if cm is not None else 0.0
            emb = self._dispatch_embed(
                bundle, seed_sets, n_rows, length, trace, stage
            )
        arr, seeds_dev, known_rows = self._stage_seeds(
            bundle, seed_sets, n_rows, length, trace, stage
        )
        t_kernel = time.perf_counter() if cm is not None else 0.0
        dispatch = None
        if trace is not None:
            dispatch, t_enqueue = trace.reserve(), time.perf_counter()
        rule_results, pick_up_rules, remote = self._dispatch_rules(
            bundle, arr, seeds_dev, deadline
        )
        if trace is not None:
            trace.child("enqueue_rules", dispatch, t_enqueue)
        _start_host_copies(*rule_results)
        if not embed_first:
            emb = self._dispatch_embed(
                bundle, seed_sets, n_rows, length, trace, dispatch
            )
        self._note_dispatch(idx)
        if trace is not None:
            trace.lap("dispatch", span_id=dispatch)

        def pick_up_embed(t_from: float):
            """Pick the embedding pair up → (host ids, sims, known mask),
            and the moment it was picked up (0.0 without a cost model)."""
            emb_host = (np.asarray(emb[0]), np.asarray(emb[1]), emb[2])
            t_done = 0.0
            if cm is not None:
                # from ``t_from`` (its launch, or the pick-up of the
                # program ahead of it on the in-order stream) to its own
                # pick-up: the rest of the embedding lookup and its copy
                t_done = time.perf_counter()
                cm.observe_kernel(
                    "embed_topk", t_done - t_from,
                    b=n_rows, l=length, v=len(bundle.emb_vocab or ()),
                    r=int(bundle.emb_factors.shape[0]),
                    k_best=self.cfg.k_best_tracks,
                )
            if trace is not None:
                trace.lap("fetch_embed")
            return emb_host, t_done

        def finish() -> list[tuple[list[str], str]]:
            if trace is not None:
                trace.lap("handoff")
            # chaos hook ON the completion path — where a real kernel
            # failure or stall surfaces (delay faults sleep here, fail
            # faults raise into the batcher's circuit breaker)
            faults.fire("replica.kernel", replica=idx)
            # pick-ups in program order: each is the fence between the
            # program it waits for and the one after it
            emb_host = None
            t_from = t_kernel
            if embed_first and emb is not None:
                emb_host, t_from = pick_up_embed(t_embed)
            host_ids, host_confs = pick_up_rules()
            if trace is not None:
                trace.lap("fetch_rules")
            if cm is not None:
                # per-kernel attribution: its dispatch (or the embedding
                # pair's pick-up, where that program ran ahead) → the
                # rule pair's pick-up, an upper bound on the rule
                # lookup's device time (the same semantics as the
                # batcher's kmls_device_ms interval), so the derived MFU
                # is a lower bound
                t_rules = time.perf_counter()
                dims = dict(
                    b=n_rows, l=length, k_max=bundle.rule_ids.shape[1],
                    v=len(bundle.vocab), k_best=self.cfg.k_best_tracks,
                    shards=bundle.n_shards,
                )
                if bundle.layout == "mesh":
                    cm.observe_kernel(
                        "serve_mesh", t_rules - t_from, **dims
                    )
                elif bundle.shard_kernel is not None:
                    cm.observe_kernel(
                        "serve_sharded", t_rules - t_from, **dims
                    )
                else:
                    cm.observe_kernel(
                        "serve_rules", t_rules - t_from, **dims
                    )
                t_from = t_rules
            if not embed_first and emb is not None:
                emb_host, _ = pick_up_embed(t_from)
            out: list[tuple[list[str], str]] = []
            for r, seeds in enumerate(seed_sets):
                emb_row = None if emb_host is None else (
                    emb_host[0][r], emb_host[1][r], emb_host[2][r]
                )
                out.append(self._compose_answer(
                    bundle, seeds, bool(known_rows[r]),
                    host_ids[r], host_confs[r], emb_row,
                ))
            if getattr(remote, "dropped", None):
                # a merge without a dropped rank's slab is a partial
                # catalog. The degraded source string is the
                # per-request side channel: the app maps it to
                # X-KMLS-Degraded (never a 5xx) and the answer cache
                # refuses to store it, so a recovered gang never serves
                # a stale partial-catalog answer from cache
                self.mesh_straggler_degraded += len(out)
                out = [
                    (songs, "degraded:mesh-straggler") for songs, _src in out
                ]
            if trace is not None:
                trace.lap("compose")
            return out

        return finish if remote is None else _stamp_hedge_outcome(finish, remote)

    def recommend_many(
        self, seed_sets: list[list[str]]
    ) -> list[tuple[list[str], str]]:
        """Batched device call over aggregated concurrent requests (the QPS
        path): ONE kernel invocation serves the whole batch."""
        return self.recommend_many_async(seed_sets)()

    def static_recommendation(
        self, seed_tracks: list[str], deadline: float | None = None
    ) -> list[str]:
        """Deterministic popular-tracks sample (reference:
        rest_api/app/main.py:205-222), keyed by a stable hash of the seeds.

        ``deadline`` (perf_counter seconds) latency-budgets the fallback
        itself: a request that arrives here with its budget already spent
        gets the cheapest legitimate answer — the head of the popularity
        ranking, no hashing or sampling — so the degraded path can never
        be the thing that blows the deadline further."""
        best = self.best_tracks
        if not best:
            return []
        names = [b["track_name"] for b in best]
        k = min(self.cfg.k_best_tracks, len(names))
        if deadline is not None and time.perf_counter() >= deadline:
            return names[:k]
        rng = random.Random(stable_seed(seed_tracks))
        return rng.sample(names, k)

    # ---------- background polling ----------

    def start_polling(self) -> threading.Thread:
        """First load + periodic staleness re-check, like the reference's
        lifespan + @repeat_every timer (rest_api/app/main.py:100-108)."""

        def loop() -> None:
            interval = max(self.cfg.polling_wait_in_minutes * 60.0, 0.05)
            while True:  # first load included: a crash must not kill the poller
                try:
                    self.reload_if_required()
                except Exception:
                    logger.exception("reload failed; will retry next poll")
                time.sleep(interval)

        thread = threading.Thread(target=loop, daemon=True, name="kmls-reload-poller")
        thread.start()
        return thread
