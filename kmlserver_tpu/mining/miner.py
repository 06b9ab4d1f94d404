"""Device mining driver: baskets → rule tensors.

The TPU replacement for the reference's mlxtend call + expansion loops
(reference: machine-learning/main.py:262-313): encode memberships on device,
one MXU matmul for pair supports, threshold + top-k emission. Exact — not an
approximation — per the dominance argument in ``ops/support.py``.

Config wiring:
- ``cfg.confidence_mode`` selects the reference fast path's
  support-as-confidence semantics (``"support"``) or the dormant slow
  path's true asymmetric confidence (``"confidence"``,
  machine-learning/main.py:224-260).
- ``cfg.max_itemset_len`` ≥ 3 additionally computes a frequent-itemset
  census (per-length counts, exact via MXU pair→triple→quad extension up
  to length 4; ≥ 5 is reported as not enumerated rather than silently
  ignored), and in confidence mode merges the multi-antecedent rules those
  itemsets imply (see ops/rules.py merge_confidence_contributions).
- ``cfg.bitpack_threshold_elems``: selects when the bit-packed Pallas
  popcount path (ops/popcount.py) replaces the dense int8 matmul — 32×
  denser in HBM, exact. ``"auto"`` (default) dispatches on estimated HBM
  footprint via :func:`bitpack_wanted`: the MXU matmul wins by an order of
  magnitude whenever the dense operands fit, so bitpack is reserved for
  shapes that genuinely don't (true config-4 scale).
- ``cfg.prune_vocab_threshold``: above this vocabulary size, infrequent
  items are pruned before pair counting (exact by the Apriori property) —
  the step that makes 1M-track vocabularies feasible.

Timing: the reference brackets rule generation with wall-clock timestamps and
prints the elapsed time (machine-learning/main.py:264,306-308); ``mine`` does
the same with ``block_until_ready`` so device work is actually inside the
bracket.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..config import MiningConfig
from ..ops import cpu_popcount, encode, rules, support
from ..parallel import layout as layout_mod
from ..utils.profiling import PhaseTimer, trace_session
from .vocab import Baskets, Vocab


@dataclasses.dataclass
class MiningResult:
    tensors: rules.RuleTensors
    # names for the tensor rows — the (possibly Apriori-pruned) vocabulary
    vocab_names: list[str]
    n_playlists: int
    n_tracks: int  # full dataset unique-track count (pre-pruning)
    duration_s: float
    pruned_vocab: int | None = None  # size after pruning, when it ran
    itemset_census: dict[int, int] | None = None  # length → frequent-itemset count
    phase_timings: dict[str, float] | None = None  # profiling detail (§5)
    # confidence mode with max_itemset_len >= 3: True when the triple-rule
    # merge ran, False when it had to be skipped (confidences pairwise-only),
    # None when not applicable
    triple_merge_applied: bool | None = None
    # which pair-count route ran: "native-cpu", "dense-fused",
    # "sparse-hybrid", "sparse-sharded", or (staged branch, straight from
    # pair_count_fn) "dense", "bitpack-mxu", "bitpack-vpu",
    # "sharded-bitpack", "sharded-dense-<impl>"
    count_path: str | None = None
    # how the dispatch decided (mining/dispatch.py CountPlan.source:
    # override/threshold/table/heuristic) — provenance for job telemetry
    count_path_source: str | None = None
    # exact pair-event count the sparse plan measured (None: not measured)
    sparse_events: int | None = None


def bitpack_plan_bytes(
    n_playlists: int,
    n_tracks: int,
    *,
    n_devices: int = 1,
    n_rows: int = 0,
) -> int:
    """Planned per-device bytes of the bit-packed formulation: bitset
    slab (word axis sharded over dp) + int32 counts with top-k scratch +
    one unpacked int8 slab (the mxu impl's per-scan-step intermediate) +
    membership operands. THE one copy of this footprint — the dispatch
    heuristic (mining/dispatch.py) and :func:`bitpack_wanted` must agree
    on what 'bitpack fits' means or the sparse rescue mis-fires."""
    from ..ops import popcount as pc

    v_pad, w_pad = pc.padded_shape(n_tracks, n_playlists)
    return (
        v_pad * w_pad * 4 // max(n_devices, 1)
        + 8 * v_pad * v_pad
        + v_pad * pc.word_chunk() * 32
        + 8 * n_rows // max(n_devices, 1)
    )


def bitpack_wanted(
    n_playlists: int,
    n_tracks: int,
    threshold: int | str | None,
    *,
    hbm_budget_bytes: int = 12 << 30,
    n_devices: int = 1,
    n_rows: int = 0,
    backend: str | None = None,
) -> bool:
    """The ONE bitpack-vs-dense dispatch decision (single-chip and sharded).

    - ``threshold == "auto"``: bitpack when the dense formulation's
      planned HBM — the int8 one-hot (sharded over ``n_devices``) plus the
      int32 count matrix and an equal-size top-k scratch (replicated) —
      exceeds ``hbm_budget_bytes`` per device. On the TPU backend that
      memory-fit rule is the whole decision (the MXU matmul beats the VPU
      popcount kernel by an order of magnitude whenever its operands fit);
      on non-TPU backends (``backend`` given and != "tpu") a SPEED rule
      also applies: above ~64M one-hot elements the 32×-compressed bitset
      operand streams through cache where the dense one thrashes it —
      measured 1.1 s vs 43 s on XLA:CPU at 100k×2k — so bitpack wins even
      though dense fits. Callers that only ask "does dense FIT?" (the
      census override in ``mine``) pass ``backend=None``.
    - ``threshold`` an int: the explicit element-count semantic (tests and
      demos use tiny values to force a path).
    - ``threshold is None`` (or ``"none"``/``"never"``, the env spellings):
      never bitpack.
    """
    if isinstance(threshold, str):
        if threshold == "auto":
            # one-hot (sharded) + count/top-k matrices (replicated) + the
            # int32 membership operands that coexist with the one-hot
            # during the encode scatter — data-proportional terms only;
            # the budget's headroom covers XLA workspace, not operands
            dense_bytes = (
                n_playlists * n_tracks // max(n_devices, 1)
                + 8 * n_tracks * n_tracks
                + 8 * n_rows // max(n_devices, 1)
            )
            if dense_bytes > hbm_budget_bytes:
                # the bitpack route is the fallback, not a guarantee:
                # check ITS footprint too (bitpack_plan_bytes — shared
                # with the dispatch heuristic) and warn loudly when
                # NEITHER formulation fits, so an impending allocator
                # failure is diagnosable before the opaque OOM
                bitpack_bytes = bitpack_plan_bytes(
                    n_playlists, n_tracks,
                    n_devices=n_devices, n_rows=n_rows,
                )
                if bitpack_bytes > hbm_budget_bytes:
                    print(
                        "WARNING: neither the dense one-hot "
                        f"(~{dense_bytes / (1 << 30):.1f} GiB) nor the "
                        f"bit-packed path (~{bitpack_bytes / (1 << 30):.1f} "
                        "GiB: bitset + counts + unpack slab) fits the "
                        f"{hbm_budget_bytes / (1 << 30):.1f} GiB HBM budget "
                        f"per device (x{max(n_devices, 1)}); proceeding "
                        "bit-packed but expect an allocator failure — "
                        "shard over more devices or raise min_support to "
                        "shrink the frequent vocabulary"
                    )
                return True
            return (
                backend is not None
                and backend != "tpu"
                and n_playlists * n_tracks // max(n_devices, 1) > 1 << 26
            )
        if threshold in ("none", "never"):
            return False
        raise ValueError(
            f"bitpack threshold must be 'auto', 'none'/'never', None, or an "
            f"element count, got {threshold!r}"
        )
    if threshold is None:
        return False
    return n_playlists * n_tracks > threshold


def pair_count_fn(
    baskets: Baskets,
    mesh: "jax.sharding.Mesh | None" = None,
    bitpack_threshold_elems: int | str | None = None,
    sharded_impl: str = "gspmd",
    hbm_budget_bytes: int = 12 << 30,
) -> tuple[jax.Array, jax.Array | None, str]:
    """One-hot encode + pair-support count: sharded, bit-packed, or dense.

    Returns ``(counts, x_onehot_or_None, path)`` — the one-hot matrix is
    handed back on the dense single-device path so downstream steps
    (itemset census) reuse it instead of re-encoding; on the sharded and
    bit-packed paths the full int8 matrix deliberately never exists
    (that's their point), so ``None`` is returned. ``path`` names the
    route that actually ran (``"dense"``, ``"bitpack-mxu"``,
    ``"bitpack-vpu"``, ``"sharded-bitpack"``, ``"sharded-dense-<impl>"``)
    — the ONE source for ``MiningResult.count_path``, so artifacts can
    never desynchronize from the dispatch.
    """
    if mesh is not None:
        if bitpack_wanted(
            baskets.n_playlists, baskets.n_tracks, bitpack_threshold_elems,
            hbm_budget_bytes=hbm_budget_bytes, n_devices=mesh.devices.size,
            n_rows=len(baskets.playlist_rows),
            backend=jax.default_backend(),
        ):
            # config-4 scale: bit-packed slabs sharded over dp, per-chip
            # counts from the bitset slab, psum over ICI. The bitpack impl
            # shards the word axis over dp ONLY — on a dp×tp mesh the tp
            # chips would each redundantly hold the full per-host slab
            # (per-chip memory O(V·P/(32·dp)) instead of
            # O(V·P/(32·n_chips))), so flatten every device onto dp first.
            from ..ops.popcount import resolve_counts_impl
            from ..parallel.mesh import AXIS_TP, make_mesh
            from ..parallel.support import sharded_bitpack_pair_counts

            if mesh.shape.get(AXIS_TP, 1) > 1:
                mesh = make_mesh(
                    "auto", devices=list(mesh.devices.flatten())
                )
            # same backend gating as the single-device branch below: the
            # env-selected impl applies on TPU; off-TPU pin the pure-XLA
            # mxu impl so a TPU-targeted KMLS_BITPACK_IMPL=vpu can never
            # put a CPU mesh run into interpreted-Pallas territory
            impl = (
                resolve_counts_impl()
                if jax.default_backend() == "tpu"
                else "mxu"
            )
            return (
                sharded_bitpack_pair_counts(baskets, mesh, impl=impl), None,
                "sharded-bitpack",
            )
        from ..parallel.support import sharded_pair_counts

        return (
            sharded_pair_counts(baskets, mesh, impl=sharded_impl), None,
            f"sharded-dense-{sharded_impl}",
        )
    if bitpack_wanted(
        baskets.n_playlists, baskets.n_tracks, bitpack_threshold_elems,
        hbm_budget_bytes=hbm_budget_bytes, n_rows=len(baskets.playlist_rows),
        backend=jax.default_backend(),
    ):
        from ..ops.popcount import popcount_pair_counts, resolve_counts_impl

        # off-TPU the Pallas VPU kernel would run in Python-level
        # interpreter mode — a massive perf cliff on exactly the large
        # inputs this path targets — but the MXU unpack-matmul impl is
        # pure XLA and compiles on every backend, so the bitset path (and
        # its 32× memory saving) is available everywhere; only the kernel
        # choice is backend-gated
        impl = (
            resolve_counts_impl()
            if jax.default_backend() == "tpu"
            else "mxu"
        )
        counts = popcount_pair_counts(
            baskets.playlist_rows, baskets.track_ids,
            n_playlists=baskets.n_playlists, n_tracks=baskets.n_tracks,
            impl=impl,
        )
        return counts, None, f"bitpack-{impl}"
    x = encode.onehot_matrix(
        jnp.asarray(baskets.playlist_rows),
        jnp.asarray(baskets.track_ids),
        n_playlists=baskets.n_playlists,
        n_tracks=baskets.n_tracks,
    )
    return support.pair_counts(x), x, "dense"


def native_cpu_eligible(cfg: MiningConfig, mesh=None) -> bool:
    """True when the native POPCNT fallback carries pair counting: CPU
    backend, single device, and no downstream step (itemset census,
    triple/quad extensions) needing device intermediates. May trigger the
    one-time native build — call OUTSIDE any timed bracket. The ONE copy
    of this gate — the sweep harness must stay in lockstep with the miner."""
    return (
        mesh is None
        and cfg.max_itemset_len < 3
        and cfg.native_cpu_pair_counts
        and jax.default_backend() == "cpu"
        and cpu_popcount.available()
    )


def native_pair_counts(baskets: Baskets) -> np.ndarray:
    """The native counter invoked exactly as the miner invokes it."""
    return cpu_popcount.pair_counts(
        baskets.playlist_rows, baskets.track_ids,
        n_playlists=baskets.n_playlists, n_tracks=baskets.n_tracks,
    )


PAIR_CAPACITY = 1 << 16


def compute_triple_extension(
    x: jax.Array,
    counts: jax.Array,
    min_count: int,
    pair_capacity: int = PAIR_CAPACITY,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int] | None:
    """Frequent pairs + their triple extensions, computed ONCE and shared by
    the itemset census and the confidence-mode triple-rule merge.

    → ``(pair_i, pair_j, pair_counts, triple_counts, n_pairs)`` as host
    arrays, or None when the frequent-pair count overflows ``pair_capacity``
    (reported honestly by the caller rather than silently truncated)."""
    pair_i, pair_j, pair_counts, n_pairs = support.frequent_pairs(
        counts, jnp.int32(min_count), capacity=pair_capacity
    )
    n_pairs = int(n_pairs)
    if n_pairs > pair_capacity:
        return None
    t = support.triple_counts(
        x, jnp.where(pair_i >= 0, pair_i, 0), jnp.where(pair_j >= 0, pair_j, 0)
    )
    return (
        np.asarray(pair_i),
        np.asarray(pair_j),
        np.asarray(pair_counts),
        np.asarray(t),
        n_pairs,
    )


TRIPLE_CAPACITY = 1 << 16


def frequent_triples_from_extension(
    triple_data: tuple, min_count: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Unique frequent triples (i < j < k) + their supports, extracted from
    the pair→triple extension. Each triple appears under exactly one pair
    row (its (i, j) with k > j), so restricting to k > j dedups across the
    three pair rows that could generate it."""
    pi, pj, _, t, _ = triple_data
    valid = pi >= 0
    v = t.shape[1]
    k_ids = np.arange(v)[None, :]
    mask = valid[:, None] & (k_ids > pj[:, None]) & (t >= min_count)
    e_idx, k_idx = np.nonzero(mask)
    return (
        pi[e_idx].astype(np.int32),
        pj[e_idx].astype(np.int32),
        k_idx.astype(np.int32),
        t[e_idx, k_idx].astype(np.int32),
    )


def compute_quad_extension(
    x: jax.Array,
    triple_data: tuple,
    min_count: int,
    capacity: int = TRIPLE_CAPACITY,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray] | None:
    """Frequent triples + their quad extensions:
    ``(ti, tj, tk, triple_supports, quad_counts (E3, V))`` as host arrays,
    or None when the frequent-triple count exceeds ``capacity``. Triple
    index arrays are padded to a multiple of 1024 (-1 sentinels) so the jit
    shape set stays bounded across runs."""
    ti, tj, tk, tc = frequent_triples_from_extension(triple_data, min_count)
    n = len(ti)
    if n > capacity:
        return None
    if n == 0:
        return ti, tj, tk, tc, np.zeros((0, x.shape[1]), np.int32)
    padded = ((n + 1023) // 1024) * 1024
    pad = padded - n
    ti_p = np.concatenate([ti, np.full(pad, -1, np.int32)])
    tj_p = np.concatenate([tj, np.zeros(pad, np.int32)])
    tk_p = np.concatenate([tk, np.zeros(pad, np.int32)])
    tc_p = np.concatenate([tc, np.zeros(pad, np.int32)])
    q = support.quad_counts(
        x,
        jnp.where(jnp.asarray(ti_p) >= 0, jnp.asarray(ti_p), 0),
        jnp.asarray(tj_p),
        jnp.asarray(tk_p),
    )
    return ti_p, tj_p, tk_p, tc_p, np.asarray(q)


def _itemset_census(
    counts: jax.Array,
    min_count: int,
    max_len: int,
    triple_data: tuple | None,
    n_pairs: int | None,
    quad_data: tuple | None = None,
) -> dict[int, int]:
    """Exact frequent-itemset counts per length (1, 2, and — via the shared
    triple extension — 3). Lengths beyond 3, and length 3 when the extension
    isn't available (sharded mining / capacity overflow), are reported as -1
    (not enumerated) rather than silently dropped."""
    item_counts = np.asarray(jnp.diagonal(counts))
    census = {1: int((item_counts >= min_count).sum())}

    def finish(first_unenumerated: int) -> dict[int, int]:
        # EVERY non-enumerated length gets an explicit -1, never a missing key
        for length in range(first_unenumerated, max_len + 1):
            census[length] = -1
        return census

    if max_len < 2:
        return census
    if n_pairs is None:
        n_pairs = int(
            support.frequent_pairs(
                counts, jnp.int32(min_count), capacity=1
            )[3]
        )
    census[2] = n_pairs
    if max_len < 3:
        return census
    if triple_data is None:
        return finish(3)  # capacity overflow / sharded x: report honestly
    if quad_data is not None:
        # quad extraction already enumerated the triples — reuse its count
        census[3] = int((quad_data[0] >= 0).sum())
    else:
        # one shared dedup rule with the rule merge: a triple {i,j,k} is
        # counted once, under its frequent (i,j) row with k > j > i
        census[3] = len(
            frequent_triples_from_extension(triple_data, min_count)[0]
        )
    if max_len < 4:
        return census
    if quad_data is None:
        return finish(4)  # triple-capacity overflow: report honestly
    ti, tj, tk, _, q = quad_data
    v = q.shape[1] if q.ndim == 2 else 0
    l_ids = np.arange(v)[None, :]
    # quad {i,j,k,l} counted once: under its (i,j,k) with l > k > j > i
    qmask = (ti >= 0)[:, None] & (l_ids > tk[:, None]) & (q >= min_count)
    census[4] = int(qmask.sum())
    return finish(5)


def prune_infrequent(baskets: Baskets, min_count: int) -> tuple[Baskets, np.ndarray]:
    """Apriori pre-filter: drop items whose SINGLETON support is below
    min_count before pair counting. Exact — an infrequent item cannot occur
    in any frequent itemset — and the step that collapses a 1M-track
    vocabulary (dense pair matrix: 4 TB) to the few thousand frequent items
    that can actually form rules. Host cost is one bincount + remap over the
    membership rows. Returns (reduced baskets, kept original ids)."""
    item_counts = np.bincount(baskets.track_ids, minlength=baskets.n_tracks)
    keep_ids = np.flatnonzero(item_counts >= min_count)
    remap = np.full(baskets.n_tracks, -1, dtype=np.int32)
    remap[keep_ids] = np.arange(len(keep_ids), dtype=np.int32)
    mapped = remap[baskets.track_ids]  # one gather over the rows, reused
    selected = mapped >= 0
    names = [baskets.vocab.names[i] for i in keep_ids]
    reduced = Baskets(
        playlist_rows=baskets.playlist_rows[selected],
        track_ids=mapped[selected],
        n_playlists=baskets.n_playlists,  # denominator stays ALL playlists
        vocab=Vocab(names=names, index={n: i for i, n in enumerate(names)}),
    )
    return reduced, keep_ids


def mine(
    baskets: Baskets,
    cfg: MiningConfig,
    mesh: "jax.sharding.Mesh | None" = None,
) -> MiningResult:
    """Run the full mining compute, timed like the reference's rule step."""
    timer = PhaseTimer()
    # model layout (KMLS_MODEL_LAYOUT): under the sharded layout a run
    # with no mesh — or the default dp-major auto mesh — gets a
    # vocab-major 1xN mesh over the local devices, so the one-hot, the
    # counts, and the emission all shard the vocab axis. Idempotent; a
    # replicated layout leaves the mesh untouched.
    mesh = layout_mod.mining_mesh(cfg, mesh)
    # native-library availability (and, on a fresh checkout, the one-time
    # g++ build it triggers) resolves BEFORE the reference-parity timer:
    # library setup is environment preparation, not rule generation — the
    # same reason the bench excludes jit compilation via warm-up
    native_cpu_ok = native_cpu_eligible(cfg, mesh)
    t0 = time.perf_counter()
    n_total = baskets.n_tracks
    pruned_vocab = None
    mined_baskets = baskets
    with trace_session("mine"):
        if baskets.n_tracks > cfg.prune_vocab_threshold:
            with timer.phase("apriori_prune"):
                min_count = support.min_count_for(
                    cfg.min_support, baskets.n_playlists
                )
                mined_baskets, _ = prune_infrequent(baskets, min_count)
                pruned_vocab = mined_baskets.n_tracks
            if mined_baskets.n_tracks == 0:
                if baskets.n_tracks <= 4096:
                    # nothing frequent, small vocab: fall back to the
                    # unpruned vocabulary (emission finds no rules either
                    # way) so no downstream shape is zero-sized
                    mined_baskets = baskets
                    pruned_vocab = None
                else:
                    # nothing frequent, LARGE vocab: restoring the full
                    # vocabulary would re-create the infeasible shapes
                    # pruning exists to avoid (a 1M-track dense count
                    # matrix is 4 TB) just to discover an empty result —
                    # emit it host-side for free instead
                    k = cfg.k_max_consequents
                    tensors = rules.RuleTensors(
                        rule_ids=np.full((0, k), -1, np.int32),
                        rule_counts=np.zeros((0, k), np.int32),
                        rule_confs=np.zeros((0, k), np.float32),
                        item_counts=np.zeros(0, np.int32),
                        n_playlists=baskets.n_playlists,
                        min_support=cfg.min_support,
                        min_count=min_count,
                        mode=cfg.confidence_mode,
                        min_confidence=cfg.min_confidence,
                        n_frequent_items=0,
                        n_songs_missing=n_total,
                        overflow_rows=0,
                        row_valid_counts=np.zeros(0, np.int32),
                    )
                    census = (
                        {length: 0 for length in
                         range(1, cfg.max_itemset_len + 1)}
                        if cfg.max_itemset_len >= 3 else None
                    )
                    return MiningResult(
                        tensors=tensors,
                        vocab_names=[],
                        n_playlists=baskets.n_playlists,
                        n_tracks=n_total,
                        duration_s=time.perf_counter() - t0,
                        pruned_vocab=0,
                        itemset_census=census,
                        phase_timings=dict(timer.phases),
                        count_path="pruned-empty",
                    )
        # the fused single-jit path (encode→matmul→emission, one compiled
        # program + one batched fetch) applies whenever no downstream step
        # needs the one-hot or count matrix on device: single-device dense
        # mining without an itemset census or triple/quad extensions. The
        # sharded, bit-packed, and census paths keep the staged pipeline.
        #
        # WHICH family counts is the measured three-way dispatch
        # (mining/dispatch.py): explicit KMLS_COUNT_PATH override →
        # explicit legacy threshold → measured (density, shape) table
        # cell → legacy bitpack_wanted heuristic. The plan measures the
        # exact density and pair-event volume with one O(nnz) host
        # bincount before any device work is committed.
        from . import dispatch as dispatch_mod

        plan = dispatch_mod.plan_count_path(
            cfg, mined_baskets.n_playlists, mined_baskets.n_tracks,
            len(mined_baskets.playlist_rows),
            backend=jax.default_backend(),
            n_devices=mesh.devices.size if mesh is not None else 1,
            baskets=mined_baskets,
        )
        wants_bitpack = plan.path == "bitpack"
        use_sparse = plan.path == "sparse"
        plan_source = plan.source
        if use_sparse and cfg.max_itemset_len >= 3:
            # the itemset census and the triple/quad extensions need
            # materialized device intermediates the sparse route never
            # builds — the same exactness-over-speed guard the bitpack
            # override below applies; fall back to what the legacy
            # dispatch would have chosen. LOUDLY — a pinned/table sparse
            # decision must never be dropped in silence — and the
            # telemetry source says what actually decided, not the plan
            # that was overridden.
            print(
                "NOTE: max_itemset_len >= 3 needs materialized device "
                "intermediates for the census/triple merge, which the "
                f"sparse path never builds — the {plan.source} sparse "
                "decision is overridden by the legacy dense/bitpack "
                "dispatch"
            )
            use_sparse = False
            plan_source = "census-override"
            wants_bitpack = bitpack_wanted(
                mined_baskets.n_playlists, mined_baskets.n_tracks, "auto",
                hbm_budget_bytes=cfg.hbm_budget_bytes,
                n_rows=len(mined_baskets.playlist_rows),
                backend=jax.default_backend(),
            )
        # exactness guard: the itemset census and the confidence-mode
        # triple/quad merge need the dense one-hot (x) — the bit-packed
        # route never materializes it and would silently downgrade those
        # to pairwise-only. When the dense formulation FITS the budget,
        # prefer it over a forced (explicit-threshold) bitpack; when it
        # doesn't fit, bitpack proceeds and the loud pairwise-only
        # warning below stands (dense was never an option).
        staged_threshold = cfg.bitpack_threshold_elems
        if plan.source == "override":
            # a pinned family must reach the staged pair_count_fn branch
            # too, which re-derives bitpack-vs-dense from the threshold
            if wants_bitpack:
                staged_threshold = 1
            elif plan.path == "dense":
                staged_threshold = None
        if (
            wants_bitpack
            and mesh is None
            and cfg.max_itemset_len >= 3
            and not bitpack_wanted(
                mined_baskets.n_playlists, mined_baskets.n_tracks, "auto",
                hbm_budget_bytes=cfg.hbm_budget_bytes,
                n_rows=len(mined_baskets.playlist_rows),
            )
        ):
            print(
                "NOTE: max_itemset_len >= 3 needs the dense one-hot for "
                "the census/triple merge and it fits the HBM budget — "
                "overriding the bitpack threshold with the dense path"
            )
            wants_bitpack = False
            plan_source = "census-override"
            # the override must reach pair_count_fn too, or the staged
            # branch would re-derive bitpack from the raw cfg threshold
            staged_threshold = None
        # CPU fallback with the native POPCNT kernel: when no TPU is
        # reachable, XLA:CPU's int8 matmul dominates the bracket (~75%);
        # the native bit-packed counter is the same exact XᵀX ~40x faster
        # (native/kmls_popcount.cpp). Same eligibility as the fused path
        # (no downstream step may need the one-hot or counts on device).
        # The native counter is the dense family's CPU implementation:
        # a measured/override SPARSE plan outranks it (that is the very
        # comparison the scale_sparse bench banks), and an explicit
        # bitpack override pins the bit-packed family as named.
        use_native_cpu = (
            native_cpu_ok
            and not use_sparse
            and not (plan.source == "override" and plan.path == "bitpack")
        )
        # vocab-sharded count+emit (the model-parallel layout's mining
        # half): counts stay column-sharded across the mesh and each
        # shard emits its own antecedent rows — the (V, V) matrix never
        # lands on one device. Exact (bit-identical emission); the
        # census/triple paths need materialized intermediates, so they
        # keep the staged pipeline and report honestly.
        use_shard_mine = (
            layout_mod.wants_sharded_mining(cfg, mesh)
            and not wants_bitpack
            and not use_sparse
            and cfg.max_itemset_len < 3
        )
        use_fused = (
            mesh is None
            and not wants_bitpack
            and not use_sparse
            and cfg.max_itemset_len < 3
            and not use_native_cpu
        )
        counts = x = None
        if use_sparse:
            count_path = None  # the sparse branch names hybrid vs sharded
        elif use_native_cpu:
            count_path = "native-cpu"
        elif use_shard_mine:
            count_path = f"sharded-vocab-{cfg.sharded_impl}"
        elif use_fused:
            count_path = "dense-fused"
        else:
            count_path = None  # the staged branch reports what actually ran
        if use_sparse:
            # the sparse family (ops/sparse.py): CSR-style pair-event
            # expansion + bitpacked long-basket sub-count — only the nnz
            # membership pairs are touched, no (P, V) operand exists in
            # any layout. Counts are bit-identical integers, so every
            # emission twin downstream yields identical rule tensors.
            with timer.phase("sparse_mine"):
                from ..ops import sparse as sparse_mod

                min_count = support.min_count_for(
                    cfg.min_support, mined_baskets.n_playlists
                )
                thr = cfg.sparse_long_basket or None
                if layout_mod.wants_sharded_mining(cfg, mesh):
                    from ..parallel.support import (
                        sparse_sharded_rule_tensors,
                    )

                    emitted = sparse_sharded_rule_tensors(
                        mined_baskets, mesh, min_count,
                        cfg.k_max_consequents, long_basket_threshold=thr,
                    )
                    tensors = rules.assemble_rule_tensors(
                        *emitted,
                        n_playlists=mined_baskets.n_playlists,
                        min_support=cfg.min_support,
                        k_max=cfg.k_max_consequents,
                        mode=cfg.confidence_mode,
                        min_confidence=cfg.min_confidence,
                        n_total_songs=n_total,
                        n_tracks=mined_baskets.n_tracks,
                    )
                    count_path = "sparse-sharded"
                else:
                    count_path = "sparse-hybrid"
                    if jax.default_backend() == "cpu":
                        # fully sparse count→emit when no long baskets:
                        # membership pairs straight to rule rows, the
                        # (V, V) matrix never exists. Long baskets fall
                        # back to the materialized-matrix route (sparse
                        # count + dense emission) — same tensors.
                        emitted = sparse_mod.sparse_rule_rows(
                            mined_baskets.playlist_rows,
                            mined_baskets.track_ids,
                            n_playlists=mined_baskets.n_playlists,
                            n_tracks=mined_baskets.n_tracks,
                            min_count=min_count,
                            k_max=cfg.k_max_consequents,
                            long_basket_threshold=thr,
                        )
                        if emitted is not None:
                            tensors = rules.assemble_rule_tensors(
                                *emitted,
                                n_playlists=mined_baskets.n_playlists,
                                min_support=cfg.min_support,
                                k_max=cfg.k_max_consequents,
                                mode=cfg.confidence_mode,
                                min_confidence=cfg.min_confidence,
                                n_total_songs=n_total,
                                n_tracks=mined_baskets.n_tracks,
                            )
                        else:
                            counts_host = sparse_mod.sparse_pair_counts_np(
                                mined_baskets.playlist_rows,
                                mined_baskets.track_ids,
                                n_playlists=mined_baskets.n_playlists,
                                n_tracks=mined_baskets.n_tracks,
                                long_basket_threshold=thr,
                            )
                            tensors = rules.mine_rules_from_counts_np(
                                counts_host,
                                n_playlists=mined_baskets.n_playlists,
                                min_support=cfg.min_support,
                                k_max=cfg.k_max_consequents,
                                mode=cfg.confidence_mode,
                                min_confidence=cfg.min_confidence,
                                n_total_songs=n_total,
                            )
                    else:
                        counts_dev = sparse_mod.sparse_pair_counts_device(
                            mined_baskets.playlist_rows,
                            mined_baskets.track_ids,
                            n_playlists=mined_baskets.n_playlists,
                            n_tracks=mined_baskets.n_tracks,
                            long_basket_threshold=thr,
                        )
                        tensors = rules.mine_rules_from_counts(
                            counts_dev,
                            n_playlists=mined_baskets.n_playlists,
                            min_support=cfg.min_support,
                            k_max=cfg.k_max_consequents,
                            mode=cfg.confidence_mode,
                            min_confidence=cfg.min_confidence,
                            n_total_songs=n_total,
                        )
        elif use_native_cpu:
            with timer.phase("native_pair_counts"):
                counts_np = native_pair_counts(mined_baskets)
            with timer.phase("rule_emission"):
                tensors = rules.mine_rules_from_counts_np(
                    counts_np,
                    n_playlists=mined_baskets.n_playlists,
                    min_support=cfg.min_support,
                    k_max=cfg.k_max_consequents,
                    mode=cfg.confidence_mode,
                    min_confidence=cfg.min_confidence,
                    n_total_songs=n_total,
                )
        elif use_shard_mine:
            with timer.phase("sharded_mine"):
                from ..parallel.support import sharded_rule_tensors

                min_count = support.min_count_for(
                    cfg.min_support, mined_baskets.n_playlists
                )
                emitted = sharded_rule_tensors(
                    mined_baskets, mesh, min_count,
                    cfg.k_max_consequents, impl=cfg.sharded_impl,
                )
                tensors = rules.assemble_rule_tensors(
                    *emitted,
                    n_playlists=mined_baskets.n_playlists,
                    min_support=cfg.min_support,
                    k_max=cfg.k_max_consequents,
                    mode=cfg.confidence_mode,
                    min_confidence=cfg.min_confidence,
                    n_total_songs=n_total,
                    n_tracks=mined_baskets.n_tracks,
                )
        elif use_fused:
            with timer.phase("fused_mine"):
                min_count = support.min_count_for(
                    cfg.min_support, mined_baskets.n_playlists
                )
                emitted = jax.device_get(
                    rules.fused_dense_rule_tensors(
                        jnp.asarray(mined_baskets.playlist_rows),
                        jnp.asarray(mined_baskets.track_ids),
                        jnp.int32(min_count),
                        n_playlists=mined_baskets.n_playlists,
                        n_tracks=mined_baskets.n_tracks,
                        k_max=cfg.k_max_consequents,
                    )
                )
                # the fused program compacts its outputs to int16 when the
                # static shapes allow (ops/rules.py); upcast back to the
                # int32 RuleTensors contract and log what actually crossed
                # the link
                fetch_bytes = sum(a.nbytes for a in emitted)
                print(
                    f"Fused fetch: {fetch_bytes / 1e6:.3f} MB device->host "
                    f"({mined_baskets.n_tracks}x{cfg.k_max_consequents} "
                    f"rule tensors, {emitted[0].dtype}/{emitted[1].dtype})"
                )
                emitted = tuple(
                    np.asarray(a, dtype=np.int32) for a in emitted
                )
                tensors = rules.assemble_rule_tensors(
                    *emitted,
                    n_playlists=mined_baskets.n_playlists,
                    min_support=cfg.min_support,
                    k_max=cfg.k_max_consequents,
                    mode=cfg.confidence_mode,
                    min_confidence=cfg.min_confidence,
                    n_total_songs=n_total,
                    n_tracks=mined_baskets.n_tracks,
                )
        else:
            with timer.phase("pair_counts"):
                counts, x, count_path = pair_count_fn(
                    mined_baskets, mesh,
                    bitpack_threshold_elems=staged_threshold,
                    sharded_impl=cfg.sharded_impl,
                    hbm_budget_bytes=cfg.hbm_budget_bytes,
                )
                jax.block_until_ready(counts)
            with timer.phase("rule_emission"):
                tensors = rules.mine_rules_from_counts(
                    counts,
                    n_playlists=mined_baskets.n_playlists,
                    min_support=cfg.min_support,
                    k_max=cfg.k_max_consequents,
                    mode=cfg.confidence_mode,
                    min_confidence=cfg.min_confidence,
                    n_total_songs=n_total,
                )
        triple_data = None
        quad_data = None
        triple_merge_applied = None
        needs_triples = (
            cfg.confidence_mode == "confidence" and cfg.max_itemset_len >= 3
        )
        if needs_triples:
            # multi-antecedent rules from frequent triples/quads: the
            # slow-path semantics pairwise mining cannot dominate
            # (ops/rules.py) — part of rule generation, inside the bracket
            if cfg.max_itemset_len >= 5:
                print(
                    "WARNING: confidence-mode antecedents are enumerated up "
                    f"to size 3 (itemsets of length 4); max_itemset_len="
                    f"{cfg.max_itemset_len} rules from longer itemsets are "
                    "not merged and confidences may understate them"
                )
            if x is not None:
                with timer.phase("triple_extension"):
                    triple_data = compute_triple_extension(
                        x, counts, tensors.min_count
                    )
            if triple_data is not None:
                if cfg.max_itemset_len >= 4:
                    with timer.phase("quad_extension"):
                        quad_data = compute_quad_extension(
                            x, triple_data, tensors.min_count
                        )
                    if quad_data is None:
                        print(
                            "WARNING: quad-rule merge skipped (frequent "
                            "triples exceed capacity); confidences include "
                            "antecedents up to size 2 only"
                        )
                # the O(E×V) contribution builds are the merge's dominant
                # host cost — keep them inside the timed merge phase
                with timer.phase("confidence_merge"):
                    contributions = [
                        rules.antecedent_contributions(
                            (triple_data[0], triple_data[1]),
                            triple_data[2], triple_data[3],
                            min_count=tensors.min_count,
                            min_confidence=cfg.min_confidence,
                        )
                    ]
                    if quad_data is not None:
                        contributions.append(
                            rules.antecedent_contributions(
                                (quad_data[0], quad_data[1], quad_data[2]),
                                quad_data[3], quad_data[4],
                                min_count=tensors.min_count,
                                min_confidence=cfg.min_confidence,
                            )
                        )
                    tensors = rules.merge_confidence_contributions(
                        tensors, contributions, k_max=cfg.k_max_consequents
                    )
                triple_merge_applied = True
            else:
                # sharded/bit-packed path (no one-hot matrix) or frequent
                # pairs over capacity: the merge CANNOT run — say so loudly,
                # confidences are pairwise-only (inexact for itemsets ≥ 3)
                triple_merge_applied = False
                print(
                    "WARNING: confidence-mode triple-rule merge skipped "
                    + (
                        "(frequent pairs exceed capacity)"
                        if x is not None
                        else "(one-hot matrix not materialized on the "
                        "sharded/bit-packed path)"
                    )
                    + "; confidences are pairwise-only"
                )
        duration = time.perf_counter() - t0
        census = None
        if cfg.max_itemset_len >= 3:
            # census-only extensions (support mode) run OUTSIDE the
            # rule-generation bracket: reporting, not rule work
            if triple_data is None and x is not None and not needs_triples:
                with timer.phase("triple_extension"):
                    triple_data = compute_triple_extension(
                        x, counts, tensors.min_count
                    )
            if (
                cfg.max_itemset_len >= 4
                and quad_data is None
                and triple_data is not None
                and x is not None
                and not needs_triples
            ):
                with timer.phase("quad_extension"):
                    quad_data = compute_quad_extension(
                        x, triple_data, tensors.min_count
                    )
            with timer.phase("itemset_census"):
                census = _itemset_census(
                    counts,
                    tensors.min_count,
                    cfg.max_itemset_len,
                    triple_data,
                    triple_data[4] if triple_data is not None else None,
                    quad_data,
                )
    return MiningResult(
        tensors=tensors,
        vocab_names=list(mined_baskets.vocab.names),
        n_playlists=mined_baskets.n_playlists,
        n_tracks=n_total,
        duration_s=duration,
        pruned_vocab=pruned_vocab,
        itemset_census=census,
        phase_timings=dict(timer.phases),
        triple_merge_applied=triple_merge_applied,
        count_path=count_path,
        count_path_source=plan_source,
        sparse_events=plan.pair_events,
    )
