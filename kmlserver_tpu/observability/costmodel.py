"""Device-truth cost attribution (ISSUE 12): per-kernel MFU/roofline,
memory accounting, and compile telemetry.

PR 9 made every *request* visible; this module makes the *device* side
visible: where device time goes per kernel, how far each jitted kernel
sits from the backend's peak FLOP/s (MFU), whether it is compute- or
bandwidth-bound (roofline), how much HBM headroom the layout decision
actually has, and whether a compile ever sneaks onto the serving path in
production — the invariant that until now lived only in tests.

Three pieces:

- **Analytic cost specs** (:data:`KERNEL_COST_SPECS`): for every jitted
  kernel the project dispatches — the rule lookup serve kernel
  (``ops/serve.py recommend_batch``), its vocab-sharded twin
  (``sharded_recommend_fn``), the embedding cosine top-k
  (``ops/embed.py embed_topk``), the ALS half-sweeps (``mining/als.py``), the pair-support count
  (``parallel/support.py`` / ``ops/support.py``), and the delta
  restricted recount (``parallel/support.restricted_pair_counts``) — a
  FLOPs(shape) and bytes-moved(shape) formula. The formulas are
  leading-order analytic counts (matmul 2·m·n·k, scatter/compare work,
  top-k ~ n·log2(k)), not instrumented truth: combined with the fenced
  device timings the serving/mining paths already take, they yield
  achieved FLOP/s, achieved bytes/s, MFU against the backend peak, and
  a roofline classification (arithmetic intensity vs the ridge point).

- **Peak table**: per-device-kind dense peak FLOP/s and HBM bytes/s,
  overridable via ``KMLS_PEAK_FLOPS`` / ``KMLS_PEAK_BYTES_PER_S`` (the
  TPU window pins the exact chip; the CPU default is deliberately
  generous so MFU stays a LOWER bound and never exceeds 1).

- **:class:`CostModel`**: the serving-side accumulator. The engine calls
  :meth:`observe_kernel` on the completion path with the fenced device
  seconds and the dispatch shape; ``/metrics`` renders
  ``kmls_kernel_device_seconds{kernel}`` and friends from it. It also
  carries the compile watcher (``kmls_compiles_total{kernel}`` — jit
  cache growth after ``mark_published``, the live form of the
  zero-compiles-post-publish invariant) and the publish-time memory
  accounting (analytic tensor bytes vs ``KMLS_DEVICE_BUDGET_BYTES`` +
  live ``memory_stats()`` gauges where the backend provides them).

Zero-cost when disabled (``KMLS_COSTMODEL=0``): the engine holds no
CostModel at all and every call site is one ``is not None`` check. The
module-level :data:`OBSERVATIONS_TOTAL` counter proves it the same way
the compile counter proves zero-compile serving: a test drives traffic
with the knob off and asserts the counter never moved.

kmls-verify's ``costspec`` checker (analysis/costspec.py) keeps this
honest statically: every ``observe_kernel("<name>", ...)`` call site
must name a registered spec, every spec must have a call site, and every
series rendered here must be in ``METRIC_REGISTRY``.
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
from typing import Callable

# module-level observation counter — the zero-cost proof (began-counter
# discipline, ISSUE 9): must never move while KMLS_COSTMODEL=0, because
# a disabled engine holds no CostModel and nothing can reach
# observe_kernel. Benign GIL-coalesced increments, diagnostics only.
OBSERVATIONS_TOTAL = 0

PEAK_FLOPS_ENV = "KMLS_PEAK_FLOPS"
PEAK_BYTES_ENV = "KMLS_PEAK_BYTES_PER_S"

# per-chip dense peak (FLOP/s, HBM bytes/s) by device-kind substring,
# matched case-insensitively in order. Published bf16-dense MXU peaks —
# our kernels run f32/int32, so MFU reads conservative (a lower bound),
# which is the honest direction for a headline. The CPU entry is a
# deliberately GENEROUS envelope for the same reason: achieved/peak must
# never exceed 1 on any host this runs on.
PEAK_TABLE: tuple[tuple[str, float, float], ...] = (
    ("v6", 918e12, 1640e9),   # v6e (Trillium)
    ("v5p", 459e12, 2765e9),
    ("v5", 197e12, 819e9),    # v5e / "v5 lite" (matched after v5p)
    ("v4", 275e12, 1200e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
    ("cpu", 2e11, 1e11),
)


def resolve_peaks(device=None) -> tuple[float, float, str]:
    """→ ``(peak_flops, peak_bytes_per_s, source)``. Env knobs win
    (``KMLS_PEAK_FLOPS`` / ``KMLS_PEAK_BYTES_PER_S``); otherwise the
    table is keyed by the device kind of ``device`` (default: the first
    local device). A device kind the table does not know raises
    ``ValueError`` unless BOTH knobs supply its peaks — an accelerator
    judged against the CPU row would report a utilization of nothing."""
    env_flops = os.getenv(PEAK_FLOPS_ENV)
    env_bytes = os.getenv(PEAK_BYTES_ENV)
    kind = ""
    if device is None and (not env_flops or not env_bytes):
        import jax

        device = jax.local_devices()[0]
    if device is not None:
        kind = f"{getattr(device, 'platform', '')} {getattr(device, 'device_kind', '')}"
    flops = bw = 0.0
    auto_source = f"auto:{kind.strip()}"
    lowered = kind.lower()
    for needle, table_flops, table_bw in PEAK_TABLE:
        if needle in lowered:
            flops, bw = table_flops, table_bw
            break
    else:
        if not (env_flops and env_bytes):
            raise ValueError(
                f"device kind {kind.strip()!r} is not in PEAK_TABLE; add "
                f"its published peaks there, or set {PEAK_FLOPS_ENV} and "
                f"{PEAK_BYTES_ENV}"
            )
    if env_flops:
        flops = float(env_flops)
    if env_bytes:
        bw = float(env_bytes)
    # provenance must name BOTH values' origins: with only one knob set
    # the other side of the roofline ridge still comes from the table,
    # and labeling that "env" would claim a calibration nobody did
    if env_flops and env_bytes:
        source = "env"
    elif env_flops or env_bytes:
        source = f"env+{auto_source}"
    else:
        source = auto_source
    return flops, bw, source


def _log2k(k: float) -> float:
    """Comparison depth of a top-k pass, floored at 1."""
    return max(1.0, math.log2(max(float(k), 2.0)))


@dataclasses.dataclass(frozen=True)
class CostSpec:
    """Analytic leading-order cost of one jitted kernel, as functions of
    its dispatch shape (a plain dims dict — missing dims default sanely
    so a partial caller still gets an order-of-magnitude number)."""

    name: str
    flops: Callable[[dict], float]
    bytes_moved: Callable[[dict], float]
    doc: str


def _d(dims: dict, key: str, default: float = 1.0) -> float:
    return float(dims.get(key, default))


def _rank_lanes_flops(lanes: float, k_best: float) -> float:
    # the epilogue's k_best rounds over a row's candidate lanes
    # (ops/serve.py _masked_topk_from_candidates): max, compare, min and
    # mask, ≈4 ops per lane a round; nothing of width v
    return 4.0 * k_best * lanes


def _rank_lanes_bytes(lanes: float, k_best: float) -> float:
    # each round reads a lane's id and conf and writes its conf back
    return 12.0 * k_best * lanes


def _serve_flops(dims: dict) -> float:
    # mask the b·l·k_max gathered lanes (≈2 ops per lane: compare +
    # select), then rank them in k_best rounds
    b, length, k_max = _d(dims, "b"), _d(dims, "l"), _d(dims, "k_max")
    lanes, k_best = b * length * k_max, _d(dims, "k_best", 10)
    return 2.0 * lanes + _rank_lanes_flops(lanes, k_best)


def _serve_bytes(dims: dict) -> float:
    # rule-row gather (ids+confs, 8 B/lane), the rounds over those lanes,
    # seeds in, top-k out
    b, length, k_max = _d(dims, "b"), _d(dims, "l"), _d(dims, "k_max")
    lanes, k_best = b * length * k_max, _d(dims, "k_best", 10)
    return (
        b * length * (k_max * 8.0 + 4.0)
        + _rank_lanes_bytes(lanes, k_best)
        + b * k_best * 8.0
    )


def _merge_flops(dims: dict) -> float:
    # the cross-shard merge ranks the shards·k_best gathered lanes of a
    # row pairwise (ops/serve.py _merge_partial_topk_impl): ≈2 compares
    # per (lane, lane) pair, nothing of width v
    b, shards, k_best = _d(dims, "b"), _d(dims, "shards"), _d(dims, "k_best", 10)
    return b * 2.0 * (shards * k_best) ** 2


def _sharded_serve_flops(dims: dict) -> float:
    # the replicated kernel's work, but every shard's partial ranks all
    # the lanes (the other shards' seeds masked dead), plus the
    # cross-shard merge in candidate space
    b, length, k_max = _d(dims, "b"), _d(dims, "l"), _d(dims, "k_max")
    shards, k_best = _d(dims, "shards"), _d(dims, "k_best", 10)
    extra = (shards - 1.0) * _rank_lanes_flops(b * length * k_max, k_best)
    return _serve_flops(dims) + extra + _merge_flops(dims)


def _sharded_serve_bytes(dims: dict) -> float:
    # the other shards' rounds, and the all_gather of (shards, b, k_best)
    # partials (both tensors, send+receive); the merge reads only those
    # lanes
    b, length, k_max = _d(dims, "b"), _d(dims, "l"), _d(dims, "k_max")
    shards, k_best = _d(dims, "shards"), _d(dims, "k_best", 10)
    extra = (shards - 1.0) * _rank_lanes_bytes(b * length * k_max, k_best)
    return _serve_bytes(dims) + extra + 2.0 * shards * b * k_best * 8.0


def _mesh_serve_flops(dims: dict) -> float:
    # ONE gang member's share of the pod-spanning lookup: the sharded
    # kernel's per-shard half (1/shards of the candidate-lane gather; the
    # slab partial's rounds pass over every lane, the other shards' seeds
    # masked dead) plus the coordinator-side merge over the rank-stacked
    # partials — peers' slab work runs on peer processes and is
    # attributed there
    b, length, k_max = _d(dims, "b"), _d(dims, "l"), _d(dims, "k_max")
    shards, k_best = _d(dims, "shards"), _d(dims, "k_best", 10)
    lanes = b * length * k_max
    return (
        2.0 * lanes / max(shards, 1.0)
        + _rank_lanes_flops(lanes, k_best)
        + _merge_flops(dims)
    )


def _mesh_serve_bytes(dims: dict) -> float:
    # slab gather (1/shards of the rule lanes) + the partial's rounds +
    # the gang exchange: the seed batch sent to every peer and
    # (shards-1) stacked (b, k_best) partials received over DCN (or the
    # simulation transport's sockets)
    b, length, k_max = _d(dims, "b"), _d(dims, "l"), _d(dims, "k_max")
    shards, k_best = _d(dims, "shards"), _d(dims, "k_best", 10)
    return (
        b * length * (k_max * 8.0 / max(shards, 1.0) + 4.0)
        + _rank_lanes_bytes(b * length * k_max, k_best)
        + (shards - 1.0) * b * (k_best * 8.0 + length * 4.0)
        + b * k_best * 8.0
    )


def _embed_flops(dims: dict) -> float:
    # one blocked pass: a (b·l, r) x (r, v) product walked in column
    # tiles (2·b·l·r·v), the max over each request's l rows while the
    # tile is at hand (b·l·v), final top-k over the (b, v) maxima
    b, length, v = _d(dims, "b"), _d(dims, "l"), _d(dims, "v")
    r, k_best = _d(dims, "r"), _d(dims, "k_best", 10)
    return b * length * v * (2.0 * r + 1.0) + b * v * _log2k(k_best)


def _embed_bytes(dims: dict) -> float:
    # the factor table read ONCE per batch + the gathered seed rows +
    # the (b, v) maxima written by the tile loop and read by the top-k
    # + seeds/outputs
    b, length, v = _d(dims, "b"), _d(dims, "l"), _d(dims, "v")
    r, k_best = _d(dims, "r"), _d(dims, "k_best", 10)
    return v * r * 4.0 + b * length * r * 4.0 + 2.0 * b * v * 4.0 + b * (
        length * 4.0 + k_best * 8.0
    )


def _als_flops(dims: dict) -> float:
    # per iteration: two big×skinny matmuls (X F and Xᵀ U, 2·p·v·r
    # each), two rank² Gramians, two batched normal-equation solves
    p, v, r = _d(dims, "p"), _d(dims, "v"), _d(dims, "r")
    iters = _d(dims, "iters")
    return iters * (
        4.0 * p * v * r + 2.0 * r * r * (p + v) + 2.0 * r * r * r
    )


def _als_bytes(dims: dict) -> float:
    # X (f32) streamed twice per iteration + both factor matrices
    # read/written per half-sweep
    p, v, r = _d(dims, "p"), _d(dims, "v"), _d(dims, "r")
    iters = _d(dims, "iters")
    return iters * (2.0 * p * v * 4.0 + 4.0 * r * (p + v) * 4.0)


def _support_flops(dims: dict) -> float:
    # C = XᵀX: one (v, p) x (p, v) contraction
    p, v = _d(dims, "p"), _d(dims, "v")
    return 2.0 * p * v * v


def _support_bytes(dims: dict) -> float:
    # int8 one-hot read (both operands of the symmetric contraction) +
    # the int32 count matrix out
    p, v = _d(dims, "p"), _d(dims, "v")
    return 2.0 * p * v + v * v * 4.0


def _recount_flops(dims: dict) -> float:
    # C[R, :] = X[:, R]ᵀ X — the row slice of the same contraction
    p, v, rows = _d(dims, "p"), _d(dims, "v"), _d(dims, "rows")
    return 2.0 * p * rows * v


def _recount_bytes(dims: dict) -> float:
    p, v, rows = _d(dims, "p"), _d(dims, "v"), _d(dims, "rows")
    return p * v + p * rows + rows * v * 4.0


def _sparse_count_flops(dims: dict) -> float:
    # one mirrored add per expanded pair event (2·E accumulates) plus
    # the O(nnz) expansion arithmetic itself — nnz-proportional, the
    # dense p·v² term is exactly what this kernel does NOT pay
    events, nnz = _d(dims, "events"), _d(dims, "nnz")
    return 2.0 * events + 4.0 * nnz


def _sparse_count_bytes(dims: dict) -> float:
    # expanded keys written+sorted+read (~12 B/event over the hybrid's
    # chunks), the membership indices in, the (v, v) int32 counts out
    events, nnz, v = _d(dims, "events"), _d(dims, "nnz"), _d(dims, "v")
    return 12.0 * events + 8.0 * nnz + v * v * 4.0


def _sparse_als_flops(dims: dict) -> float:
    # per iteration: two gather+segment-add products over the nnz
    # events (2·nnz·r each), two rank² Gramians, two batched solves —
    # the 4·p·v·r dense term collapses to 4·nnz·r
    nnz, p, v, r = _d(dims, "nnz"), _d(dims, "p"), _d(dims, "v"), _d(dims, "r")
    iters = _d(dims, "iters")
    return iters * (
        4.0 * nnz * r + 2.0 * r * r * (p + v) + 2.0 * r * r * r
    )


def _sparse_als_bytes(dims: dict) -> float:
    # index vectors streamed twice per iteration + the gathered factor
    # rows (r f32 per event per product) + both factor matrices
    # read/written per half-sweep
    nnz, p, v, r = _d(dims, "nnz"), _d(dims, "p"), _d(dims, "v"), _d(dims, "r")
    iters = _d(dims, "iters")
    return iters * (
        16.0 * nnz + 8.0 * nnz * r + 4.0 * r * (p + v) * 4.0
    )


# THE registry: every jitted kernel the project dispatches has an entry,
# and every entry is observed by some dispatch site — both directions
# machine-checked by kmls-verify's `costspec` checker (checker 8).
KERNEL_COST_SPECS: dict[str, CostSpec] = {
    "serve_rules": CostSpec(
        "serve_rules", _serve_flops, _serve_bytes,
        "replicated rule gather + candidate-lane top-k (ops/serve.py "
        "recommend_batch; dims b, l, k_max, k_best)",
    ),
    "serve_sharded": CostSpec(
        "serve_sharded", _sharded_serve_flops, _sharded_serve_bytes,
        "vocab-sharded lookup + all_gather max-merge (ops/serve.py "
        "sharded_recommend_fn; dims + shards)",
    ),
    "serve_mesh": CostSpec(
        "serve_mesh", _mesh_serve_flops, _mesh_serve_bytes,
        "pod-spanning gang lookup: local slab partial + rank-stacked "
        "merge (ops/serve.py shard_partial_topk/merge_partial_topk via "
        "serving/mesh.py; dims + shards)",
    ),
    "embed_topk": CostSpec(
        "embed_topk", _embed_flops, _embed_bytes,
        "embedding cosine top-k (ops/embed.py embed_topk; dims b, l, "
        "v, r, k_best)",
    ),
    "als_sweep": CostSpec(
        "als_sweep", _als_flops, _als_bytes,
        "ALS half-sweeps, full training loop (mining/als.py; dims p, "
        "v, r, iters)",
    ),
    "support_count": CostSpec(
        "support_count", _support_flops, _support_bytes,
        "pair-support contraction C = XᵀX (ops/support.py, "
        "parallel/support.py; dims p, v)",
    ),
    "delta_recount": CostSpec(
        "delta_recount", _recount_flops, _recount_bytes,
        "delta restricted recount C[R, :] (parallel/support."
        "restricted_pair_counts; dims p, v, rows)",
    ),
    "sparse_count": CostSpec(
        "sparse_count", _sparse_count_flops, _sparse_count_bytes,
        "sparse CSR×bitpacked pair-support hybrid (ops/sparse.py "
        "sparse_pair_counts_np/_device; dims events, nnz, v)",
    ),
    "als_sweep_sparse": CostSpec(
        "als_sweep_sparse", _sparse_als_flops, _sparse_als_bytes,
        "ALS half-sweeps over the compressed interaction matrix "
        "(mining/als.py _train_sparse; dims nnz, p, v, r, iters)",
    ),
}


def phase_cost(kernel: str, **dims) -> tuple[float, float]:
    """Analytic ``(flops, bytes_moved)`` for one kernel invocation — the
    mining side's per-phase attribution (jobmetrics) and the bench's
    expected-work numerator both read this, so the serving and batch
    attributions can never use different formulas."""
    spec = KERNEL_COST_SPECS[kernel]
    return spec.flops(dims), spec.bytes_moved(dims)


def classify_roofline(
    flops: float, bytes_moved: float, peak_flops: float, peak_bytes_s: float
) -> str:
    """→ ``"compute"`` | ``"bandwidth"``: arithmetic intensity
    (flops/byte) vs the ridge point (peak_flops / peak_bytes_per_s).
    At or above the ridge the kernel can saturate the MXU; below it the
    memory system is the ceiling and MFU is bounded by
    intensity · peak_bw / peak_flops."""
    intensity = flops / max(bytes_moved, 1.0)
    ridge = peak_flops / max(peak_bytes_s, 1.0)
    return "compute" if intensity >= ridge else "bandwidth"


class CompileWatcher:
    """Live form of the zero-compiles-post-publish invariant: per-kernel
    jit-cache sizes snapshotted at publication; growth afterwards IS a
    compile on the serving path, exported as
    ``kmls_compiles_total{kernel}``. A re-publication legitimately warms
    new shapes — :meth:`mark_published` banks the running count and
    re-snapshots, so the counter stays monotonic and only ever counts
    compiles that landed OUTSIDE a publication."""

    def __init__(self):
        self._fns: dict[str, object] = {}
        self._base: dict[str, int] = {}
        self._accum: dict[str, int] = {}

    @staticmethod
    def _size(fn) -> int:
        probe = getattr(fn, "_cache_size", None)
        if probe is None:
            return 0
        try:
            return int(probe())
        except Exception:
            return 0

    def watch(self, kernel: str, fn) -> None:
        """Track ``fn``'s jit cache under ``kernel``. First sight
        snapshots the current size, so compiles that predate watching
        (another engine in the same process — the jitted functions are
        module-level) are never billed here."""
        if fn is None:
            return
        if self._fns.get(kernel) is not fn:
            self._fns[kernel] = fn
            self._base[kernel] = self._size(fn)
            self._accum.setdefault(kernel, 0)

    def note_prepublish(self) -> None:
        """Call BEFORE a (re)publication's warmup begins: growth since
        the last snapshot is genuine serving-path compiles — bank it so
        the counter stays monotonic — and re-baseline, so the warmup
        compiles about to happen land between this and
        :meth:`mark_published`, where they are absorbed."""
        for kernel, fn in self._fns.items():
            cur = self._size(fn)
            self._accum[kernel] = self._accum.get(kernel, 0) + max(
                0, cur - self._base.get(kernel, cur)
            )
            self._base[kernel] = cur

    def mark_published(self) -> None:
        """Call AFTER warmup: re-snapshot WITHOUT banking — everything
        since :meth:`note_prepublish` was the publication legitimately
        warming its shapes, not a compile on the serving path."""
        for kernel, fn in self._fns.items():
            self._base[kernel] = self._size(fn)

    def compiles(self) -> dict[str, int]:
        """kernel → compiles since its last publication snapshot (plus
        everything banked across earlier publications)."""
        out: dict[str, int] = {}
        for kernel, fn in self._fns.items():
            cur = self._size(fn)
            out[kernel] = self._accum.get(kernel, 0) + max(
                0, cur - self._base.get(kernel, cur)
            )
        return out


class CostModel:
    """Per-kernel device-time/FLOPs/bytes accumulator + compile watcher
    + publish-time memory accounting. One per engine; the app renders it
    into ``/metrics``. The observe path is completion-side only (never
    under a dispatch lock): one dict update under a private lock, no
    allocation beyond the first sight of a kernel name."""

    def __init__(self, peak_flops: float = 0.0, peak_bytes_s: float = 0.0):
        if peak_flops > 0 and peak_bytes_s > 0:
            # both pinned: never touch jax (unit tests construct here)
            self.peak_flops, self.peak_bytes_s = peak_flops, peak_bytes_s
            self.peak_source = "explicit"
        else:
            resolved_flops, resolved_bw, resolved_src = resolve_peaks()
            self.peak_flops = peak_flops if peak_flops > 0 else resolved_flops
            self.peak_bytes_s = (
                peak_bytes_s if peak_bytes_s > 0 else resolved_bw
            )
            # partial override: name both origins (see resolve_peaks)
            self.peak_source = (
                f"explicit+{resolved_src}"
                if (peak_flops > 0 or peak_bytes_s > 0)
                else resolved_src
            )
        self._lock = threading.Lock()
        # kernel -> [device_s, flops, bytes, dispatches]
        self._kernels: dict[str, list[float]] = {}
        # dispatches naming a kernel with no registered spec: kept
        # serving (zero-flop observation) but counted loudly — the
        # runtime shadow of the costspec checker's static guarantee
        self.unspecced: dict[str, int] = {}
        self.observations = 0
        self.compile_watcher = CompileWatcher()
        # ---- publish-time memory accounting (engine-fed) ----
        self.tensor_bytes: dict[str, int] = {}  # artifact -> bytes (total)
        self.budget_bytes = 0
        self.n_shards = 1
        self.publish_watermark_bytes = 0

    # ---------- observation (hot completion path) ----------

    def observe_kernel(self, kernel: str, device_s: float, **dims) -> None:
        """Fold one fenced kernel timing into the per-kernel totals.
        ``device_s`` is dispatch→result-on-host (the same semantics as
        the batcher's device attribution: an upper bound on device time,
        so the derived MFU is a lower bound)."""
        global OBSERVATIONS_TOTAL
        OBSERVATIONS_TOTAL += 1  # benign race: zero-cost proof counter
        spec = KERNEL_COST_SPECS.get(kernel)
        if spec is None:
            with self._lock:
                self.unspecced[kernel] = self.unspecced.get(kernel, 0) + 1
                entry = self._kernels.setdefault(kernel, [0.0, 0.0, 0.0, 0])
                entry[0] += max(device_s, 0.0)
                entry[3] += 1
                self.observations += 1
            return
        flops = spec.flops(dims)
        moved = spec.bytes_moved(dims)
        with self._lock:
            entry = self._kernels.setdefault(kernel, [0.0, 0.0, 0.0, 0])
            entry[0] += max(device_s, 0.0)
            entry[1] += flops
            entry[2] += moved
            entry[3] += 1
            self.observations += 1

    # ---------- compile telemetry ----------

    def watch_compiles(self, kernel: str, fn) -> None:
        self.compile_watcher.watch(kernel, fn)

    def note_prepublish(self) -> None:
        self.compile_watcher.note_prepublish()

    def mark_published(self) -> None:
        self.compile_watcher.mark_published()

    def compiles_post_publish(self) -> dict[str, int]:
        return self.compile_watcher.compiles()

    # ---------- memory accounting ----------

    def note_publish(
        self,
        tensor_bytes: dict[str, int],
        budget_bytes: int,
        n_shards: int = 1,
        watermark_bytes: int = 0,
    ) -> None:
        """Publish-time snapshot from the engine: analytic per-artifact
        tensor bytes (the same numbers layout.py's auto decision
        measured), the per-device budget they were judged against, and
        the live bytes-in-use watermark where the backend reports one."""
        with self._lock:
            self.tensor_bytes = dict(tensor_bytes)
            self.budget_bytes = int(budget_bytes)
            self.n_shards = max(1, int(n_shards))
            self.publish_watermark_bytes = int(watermark_bytes)

    def per_device_tensor_bytes(self) -> int:
        with self._lock:
            total = sum(self.tensor_bytes.values())
            return total // self.n_shards

    def headroom_bytes(self) -> int:
        """Budget minus the analytic per-device tensor residency — how
        observable the auto-layout decision's margin is."""
        with self._lock:
            total = sum(self.tensor_bytes.values())
            return self.budget_bytes - total // self.n_shards

    # ---------- derived stats ----------

    def kernel_stats(self) -> dict[str, dict]:
        """kernel → {device_s, dispatches, flops, bytes, flops_per_s,
        bytes_per_s, mfu, roofline} (rates 0 while no time observed)."""
        with self._lock:
            snap = {k: list(v) for k, v in self._kernels.items()}
        out: dict[str, dict] = {}
        for kernel, (device_s, flops, moved, n) in snap.items():
            flops_s = flops / device_s if device_s > 0 else 0.0
            bytes_s = moved / device_s if device_s > 0 else 0.0
            out[kernel] = {
                "device_s": device_s,
                "dispatches": n,
                "flops": flops,
                "bytes": moved,
                "flops_per_s": flops_s,
                "bytes_per_s": bytes_s,
                "mfu": min(flops_s / self.peak_flops, 1.0)
                if self.peak_flops > 0
                else 0.0,
                "roofline": classify_roofline(
                    flops, moved, self.peak_flops, self.peak_bytes_s
                ),
            }
        return out

    def summary(self) -> dict:
        """The /debug + bench view: peaks, per-kernel stats, compile
        counts, memory accounting."""
        return {
            "peak_flops": self.peak_flops,
            "peak_bytes_per_s": self.peak_bytes_s,
            "peak_source": self.peak_source,
            "observations": self.observations,
            "kernels": self.kernel_stats(),
            "compiles_post_publish": self.compiles_post_publish(),
            "unspecced": dict(self.unspecced),
            "tensor_bytes": dict(self.tensor_bytes),
            "budget_bytes": self.budget_bytes,
            "headroom_bytes": self.headroom_bytes(),
            "publish_watermark_bytes": self.publish_watermark_bytes,
        }

    # ---------- exposition ----------

    @staticmethod
    def device_memory_lines() -> list[str]:
        """Live ``memory_stats()`` gauges where the backend provides
        them (TPU does; CPU returns None → no lines, series absent —
        the analytic accounting below covers every backend)."""
        import jax

        in_use: list[str] = []
        limit: list[str] = []
        for i, dev in enumerate(jax.local_devices()):
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            used = stats.get("bytes_in_use")
            cap = stats.get("bytes_limit")
            if used is not None:
                in_use.append(f'kmls_device_bytes_in_use{{device="{i}"}} {int(used)}')
            if cap is not None:
                limit.append(f'kmls_device_bytes_limit{{device="{i}"}} {int(cap)}')
        lines: list[str] = []
        if in_use:
            lines.append("# TYPE kmls_device_bytes_in_use gauge")
            lines += in_use
        if limit:
            lines.append("# TYPE kmls_device_bytes_limit gauge")
            lines += limit
        return lines

    def render_lines(self) -> list[str]:
        """The cost-attribution block of ``/metrics``. Every series here
        is declared in ``serving.metrics.METRIC_REGISTRY`` (the metrics
        checker covers this file as a serving exposition surface)."""
        stats = self.kernel_stats()
        compiles = self.compiles_post_publish()
        lines = [
            "# TYPE kmls_costmodel_observations_total counter",
            f"kmls_costmodel_observations_total {self.observations}",
        ]
        if stats:
            blocks: list[tuple[str, str, Callable[[dict], str]]] = [
                ("kmls_kernel_device_seconds", "counter",
                 lambda s: f"{s['device_s']:.6f}"),
                ("kmls_kernel_dispatches_total", "counter",
                 lambda s: str(s["dispatches"])),
                ("kmls_kernel_flops_per_second", "gauge",
                 lambda s: f"{s['flops_per_s']:.6g}"),
                ("kmls_kernel_bytes_per_second", "gauge",
                 lambda s: f"{s['bytes_per_s']:.6g}"),
                ("kmls_mfu", "gauge", lambda s: f"{s['mfu']:.6g}"),
                ("kmls_kernel_compute_bound", "gauge",
                 lambda s: str(int(s["roofline"] == "compute"))),
            ]
            for name, mtype, value_of in blocks:
                lines.append(f"# TYPE {name} {mtype}")
                for kernel in sorted(stats):
                    lines.append(
                        f'{name}{{kernel="{kernel}"}} {value_of(stats[kernel])}'
                    )
        if compiles:
            lines.append("# TYPE kmls_compiles_total counter")
            for kernel in sorted(compiles):
                lines.append(
                    f'kmls_compiles_total{{kernel="{kernel}"}} {compiles[kernel]}'
                )
        with self._lock:
            unspecced_total = sum(self.unspecced.values())
            tensor_bytes = dict(self.tensor_bytes)
            budget = self.budget_bytes
            watermark = self.publish_watermark_bytes
        lines += [
            "# TYPE kmls_costmodel_unspecced_total counter",
            f"kmls_costmodel_unspecced_total {unspecced_total}",
        ]
        if tensor_bytes:
            lines.append("# TYPE kmls_model_tensor_bytes gauge")
            for artifact in sorted(tensor_bytes):
                lines.append(
                    f'kmls_model_tensor_bytes{{artifact="{artifact}"}} '
                    f"{tensor_bytes[artifact]}"
                )
            lines += [
                "# TYPE kmls_device_budget_bytes gauge",
                f"kmls_device_budget_bytes {budget}",
                "# TYPE kmls_device_headroom_bytes gauge",
                f"kmls_device_headroom_bytes {self.headroom_bytes()}",
                "# TYPE kmls_publish_watermark_bytes gauge",
                f"kmls_publish_watermark_bytes {watermark}",
            ]
        lines += self.device_memory_lines()
        return lines


def device_watermark_bytes(device=None) -> int:
    """Current ``bytes_in_use`` of ``device`` (default: first local), or
    0 where the backend has no ``memory_stats`` (CPU) — the publish-time
    watermark the engine records next to the analytic accounting."""
    import jax

    if device is None:
        devs = jax.local_devices()
        if not devs:
            return 0
        device = devs[0]
    try:
        stats = device.memory_stats()
    except Exception:
        return 0
    if not stats:
        return 0
    return int(stats.get("bytes_in_use", 0) or 0)
