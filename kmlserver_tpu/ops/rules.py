"""Rule-tensor emission — the device-side replacement for the reference's
pure-Python itemset→rule-dict expansion loops
(reference: machine-learning/main.py:284-296).

The output layout is a padded dense set of arrays resident in HBM:

    rule_ids    int32 (V, K_max) — consequent track ids, -1 padding
    rule_counts int32 (V, K_max) — co-occurrence counts (pair support × P)
    item_counts int32 (V,)       — singleton supports (the matrix diagonal)

Key semantic detail (reference: machine-learning/main.py:287-291): the
reference creates a rule-dict KEY for every member of every frequent itemset
— including frequent singletons, whose value stays an EMPTY dict. Those keys
matter downstream: the API's seed-membership filter treats them as known (an
all-known-but-empty request returns an empty list, NOT the static fallback —
rest_api/app/main.py:235-238), and the printed missing-songs counter is
``total_songs - len(keys)`` (main.py:304), i.e. it counts items below
min_support, not items without partners. Hence ``item_counts`` (the matrix
diagonal) travels with the rule rows: frequent items ARE the key set.

Per the dominance argument in ``ops/support.py``, row *i*'s contents are
exactly {j ≠ i : pair_count[i, j] ≥ min_count} with stored "confidence"
pair_count[i, j] / P. Emission is one masked row-wise ``top_k``. Counts (not
float supports) travel to host so dict expansion can reproduce the
reference's float64 ``count / P`` arithmetic bit-for-bit.

Two confidence modes:

- ``"support"``   — the reference fast path's semantics: symmetric rules
  carrying the itemset support (machine-learning/main.py:286).
- ``"confidence"`` — the dormant slow path's true asymmetric confidence
  (machine-learning/main.py:224-260, fpgrowth_py at :226-227):
  conf(a→b) = support({a,b}) / support({a}), thresholded at
  ``min_confidence``; rules are no longer symmetric.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .support import min_count_for


@partial(jax.jit, static_argnames=("k_max",))
def emit_rule_tensors(pair_count_matrix: jax.Array, min_count: jax.Array, *, k_max: int):
    """Threshold + per-row top-k over the pair-count matrix.

    Returns ``(rule_ids, rule_counts, row_valid_counts)`` where
    ``row_valid_counts[i]`` is the TRUE number of frequent consequents of i
    (may exceed ``k_max``; the caller detects truncation overflow).
    """
    v = pair_count_matrix.shape[0]
    offdiag = ~jnp.eye(v, dtype=bool)
    valid = offdiag & (pair_count_matrix >= min_count)
    row_valid_counts = valid.sum(axis=1, dtype=jnp.int32)
    score = jnp.where(valid, pair_count_matrix, -1)
    k = min(k_max, v)
    top_counts, top_ids = jax.lax.top_k(score, k)
    keep = top_counts > 0
    rule_ids = jnp.where(keep, top_ids, -1).astype(jnp.int32)
    rule_counts = jnp.where(keep, top_counts, 0)
    if k < k_max:  # static pad up to the declared row capacity
        pad = ((0, 0), (0, k_max - k))
        rule_ids = jnp.pad(rule_ids, pad, constant_values=-1)
        rule_counts = jnp.pad(rule_counts, pad)
    return rule_ids, rule_counts, row_valid_counts


_CONF_BLOCK_ROWS = 4096  # x K_max 256 x 8 B: an 8 MB quotient a block


def derive_confs(
    rule_counts: np.ndarray,
    item_counts: np.ndarray,
    n_playlists: int,
    mode: str,
) -> np.ndarray:
    """THE count→confidence arithmetic, shared by the miner and every npz
    consumer (float64 division, then float32 for the serving tensors).
    Computed a block of rows at a time: the float64 quotient of a whole
    (V, K) table is twice the table (19 GB at 9.39M x 256) paged in for
    one pass; a block's stays in cache. Element for element the same."""
    rule_counts = np.asarray(rule_counts)
    rows = len(rule_counts)
    if mode == "support":
        denom = np.broadcast_to(np.float64(n_playlists), (rows, 1))
    else:
        denom = np.maximum(item_counts, 1)[:, None].astype(np.float64)
    out = np.empty(rule_counts.shape, dtype=np.float32)
    for lo in range(0, rows, _CONF_BLOCK_ROWS):
        hi = lo + _CONF_BLOCK_ROWS
        out[lo:hi] = rule_counts[lo:hi].astype(np.float64) / denom[lo:hi]
    return out


def expand_rules_dict(
    vocab_names: list[str],
    rule_ids: np.ndarray,
    rule_counts: np.ndarray,
    item_counts: np.ndarray,
    *,
    n_playlists: int,
    min_support: float,
    mode: str = "support",
    rule_confs64: np.ndarray | None = None,
) -> dict[str, dict[str, float]]:
    """THE canonical tensor→dict expansion, shared by the mining artifact
    writer and every npz consumer. Reproduces the reference pickle exactly:
    every frequent item is a key (empty dict when it has no partners),
    confidences are float64 ``count / P`` (support mode) or
    ``count / item_count`` (confidence mode). When ``rule_confs64`` is given
    (triple-antecedent merge: per-rule denominators), the stored float64
    confidences are used verbatim instead of re-deriving from counts."""
    min_count = min_count_for(min_support, n_playlists)
    # infrequent items are not keys (reference main.py:284 loop); all the
    # vectorized work below touches ONLY the frequent rows — with pruning
    # disabled at large V the full (V, K_max) float64 temporary would be
    # gigabytes for rows that are never expanded
    freq_rows = np.flatnonzero(item_counts >= min_count)
    if rule_confs64 is not None:
        conf_rows = rule_confs64[freq_rows]
    elif mode == "support":
        # IEEE-identical to the reference's per-entry int(c)/P float
        # division (int32 counts are exactly representable in float64),
        # vectorized — the expansion is inside the timed mining bracket
        conf_rows = rule_counts[freq_rows] / float(n_playlists)
    else:
        conf_rows = rule_counts[freq_rows] / np.maximum(
            item_counts[freq_rows], 1
        )[:, None].astype(np.float64)
    ids_rows = rule_ids[freq_rows]
    valid_rows = ids_rows >= 0
    # one C-level gather for every name/conf in the dict, then per-row
    # slicing — the expansion runs inside the timed mining bracket, and
    # per-entry Python lookups were ~20% of it. An object array makes
    # names_arr[idx].tolist() a single fancy-index + materialize.
    names_arr = np.asarray(vocab_names, dtype=object)
    rk, ck = np.nonzero(valid_rows)
    flat_names = names_arr[ids_rows[rk, ck]].tolist()
    flat_confs = conf_rows[rk, ck].tolist()
    bounds = np.concatenate(
        [[0], np.cumsum(valid_rows.sum(axis=1))]
    ).tolist()
    key_names = names_arr[freq_rows].tolist()
    out: dict[str, dict[str, float]] = {}
    for k in range(len(freq_rows)):
        lo, hi = bounds[k], bounds[k + 1]
        out[key_names[k]] = dict(zip(flat_names[lo:hi], flat_confs[lo:hi]))
    return out


@dataclasses.dataclass
class RuleTensors:
    """Host-side mined result + provenance."""

    rule_ids: np.ndarray  # int32 (V, K_max)
    rule_counts: np.ndarray  # int32 (V, K_max)
    rule_confs: np.ndarray  # float32 (V, K_max), serving-ready
    item_counts: np.ndarray  # int32 (V,)
    n_playlists: int
    min_support: float
    min_count: int
    mode: str  # "support" | "confidence"
    min_confidence: float
    n_frequent_items: int  # == len(keys) of the expanded dict
    n_songs_missing: int  # total_songs - len(keys) (reference main.py:304)
    overflow_rows: int  # rows whose true consequent set exceeded K_max
    # emission-time TRUE consequent-set sizes (may exceed K_max); lets the
    # multi-antecedent merge keep the overflow count honest after it can no
    # longer see the entries emission truncated away
    row_valid_counts: np.ndarray | None = None  # int32 (V,)
    # set when confidences can NOT be re-derived from counts alone — i.e.
    # triple-antecedent contributions are merged in (conf = s3/c_ab has a
    # per-rule denominator); float64 so dict expansion keeps full precision
    rule_confs64: np.ndarray | None = None

    @property
    def frequent_item_mask(self) -> np.ndarray:
        return self.item_counts >= self.min_count

    def to_rules_dict(self, vocab_names: list[str]) -> dict[str, dict[str, float]]:
        return expand_rules_dict(
            vocab_names,
            self.rule_ids,
            self.rule_counts,
            self.item_counts,
            n_playlists=self.n_playlists,
            min_support=self.min_support,
            mode=self.mode,
            rule_confs64=self.rule_confs64,
        )


def antecedent_contributions(
    members: tuple[np.ndarray, ...],  # each int (E,), -1 padded
    ant_counts: np.ndarray,  # int (E,) support of the antecedent itemset
    ext_counts: np.ndarray,  # int (E, V) support of antecedent ∪ {col}
    *,
    min_count: int,
    min_confidence: float,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Directed rule contributions from one antecedent size, vectorized.

    For each row e — an antecedent itemset A = {members[0][e], …} — and each
    column c with ``ext_counts[e, c] ≥ min_count``, the rule A→c holds at
    conf = ext/ant. The reference slow path assigns that confidence from
    EVERY member of A to c (machine-learning/main.py:247-255), so each hit
    yields ``len(members)`` directed (row, col, conf) entries. Columns that
    are themselves members hold the antecedent's own support, not a proper
    extension, and are masked out. → (rows, cols, vals).
    """
    e_valid = np.flatnonzero((members[0] >= 0) & (ant_counts > 0))
    ext = ext_counts[e_valid]  # (E', V)
    ms = [m[e_valid].astype(np.int64) for m in members]
    ac = ant_counts[e_valid].astype(np.int64)
    mask = ext >= min_count
    if e_valid.size:
        e_rows = np.arange(e_valid.size)
        for m in ms:
            mask[e_rows, m] = False
    conf = ext.astype(np.int64) / ac[:, None].astype(np.float64)
    mask &= conf >= min_confidence
    e_hit, k_hit = np.nonzero(mask)
    vals_hit = conf[e_hit, k_hit]
    rows = np.concatenate([m[e_hit] for m in ms])
    cols = np.tile(k_hit.astype(np.int64), len(ms))
    vals = np.tile(vals_hit, len(ms))
    return rows, cols, vals


def merge_confidence_contributions(
    tensors: "RuleTensors",
    contributions: list[tuple[np.ndarray, np.ndarray, np.ndarray]],
    *,
    k_max: int,
) -> "RuleTensors":
    """Fold multi-antecedent rule contributions into the pairwise confidence
    tensors — the part of the reference slow path's semantics
    (machine-learning/main.py:224-260) that pairwise mining cannot dominate:
    conf({a,b}→c) = s3/s(ab) (and conf({a,b,c}→d) = s4/s(abc), …) may
    exceed every pairwise confidence involving the consequent. Rules whose
    antecedent is a PROPER SUBSET of another frequent itemset's antecedent
    at the same size-or-less ARE dominated (sL/c_A ≤ s(A∪{c})/c_A), so
    (L-1)-antecedent contributions per itemset length L are sufficient for
    exactness at that max length.

    Contributions max-merge with the pairwise rows, re-rank per row
    (confidence descending, ties by lower consequent id), truncate to
    ``k_max``.
    """
    v = tensors.rule_ids.shape[0]
    denom = np.maximum(tensors.item_counts, 1).astype(np.float64)

    # sparse (row, col, conf) entries from the pairwise emission
    rb, kb = np.nonzero(tensors.rule_ids >= 0)
    cols_b = tensors.rule_ids[rb, kb].astype(np.int64)
    vals_b = tensors.rule_counts[rb, kb].astype(np.int64) / denom[rb]

    rows = np.concatenate([rb.astype(np.int64)] + [c[0] for c in contributions])
    cols = np.concatenate([cols_b] + [c[1] for c in contributions])
    vals = np.concatenate([vals_b] + [c[2] for c in contributions])

    # max-dedup per (row, col): sort by (row, col, conf desc), keep first
    order = np.lexsort((-vals, cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    keep_first = np.ones(len(rows), dtype=bool)
    keep_first[1:] = (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])
    rows, cols, vals = rows[keep_first], cols[keep_first], vals[keep_first]

    # per-row rank by conf desc (ties: lower col id — deterministic)
    order = np.lexsort((cols, -vals, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    row_start = np.ones(len(rows), dtype=bool)
    row_start[1:] = rows[1:] != rows[:-1]
    seg_id = np.cumsum(row_start) - 1
    rank = np.arange(len(rows)) - np.flatnonzero(row_start)[seg_id]
    # honest overflow: a row is truncated if the MERGED candidate set
    # exceeds k_max, or if emission already truncated it (the merge can't
    # see those dropped entries — tensors.row_valid_counts remembers them)
    overflow_mask = np.zeros(v, dtype=bool)
    if len(rows):
        row_sizes = np.bincount(seg_id)
        overflow_mask[rows[row_start]] = row_sizes > k_max
    if tensors.row_valid_counts is not None:
        overflow_mask |= tensors.row_valid_counts > k_max
    overflow = int(overflow_mask.sum())
    keep = rank < k_max
    rows, cols, vals, rank = rows[keep], cols[keep], vals[keep], rank[keep]

    rule_ids = np.full((v, k_max), -1, dtype=np.int32)
    rule_confs64 = np.zeros((v, k_max), dtype=np.float64)
    rule_ids[rows, rank] = cols
    rule_confs64[rows, rank] = vals
    return dataclasses.replace(
        tensors,
        rule_ids=rule_ids,
        # counts cannot back these confidences (per-rule denominators);
        # consumers MUST use rule_confs64 — artifacts.load_rule_tensors
        # refuses an artifact where this invariant is broken
        rule_counts=np.zeros((v, k_max), dtype=np.int32),
        rule_confs=rule_confs64.astype(np.float32),
        rule_confs64=rule_confs64,
        overflow_rows=overflow,
    )


@partial(jax.jit, static_argnames=("n_playlists", "n_tracks", "k_max"))
def fused_dense_rule_tensors(
    playlist_rows: jax.Array,
    track_ids: jax.Array,
    min_count: jax.Array,
    *,
    n_playlists: int,
    n_tracks: int,
    k_max: int,
):
    """One-hot encode → MXU pair matmul → threshold/top-k emission as ONE
    compiled program: membership pairs in, finished rule tensors out.

    The unfused path (``pair_count_fn`` + :func:`mine_rules_from_counts`)
    dispatches eager encode ops, syncs on the count matrix, then issues four
    separate device→host fetches — each paying a full host<->device round
    trip, and at small shapes the round trips, not the compute, are the
    bracket. Fusing also lets XLA schedule encode/matmul/top-k without
    host turnarounds. Used by ``mining.miner.mine`` whenever no
    intermediate (one-hot matrix, count matrix) is needed downstream."""
    from . import encode, support

    x = encode.onehot_matrix(
        playlist_rows, track_ids, n_playlists=n_playlists, n_tracks=n_tracks
    )
    counts = support.pair_counts(x)
    rule_ids, rule_counts, row_valid = emit_rule_tensors(
        counts, min_count, k_max=k_max
    )
    # compact the device→host transfer: ids and row sizes fit int16
    # whenever V ≤ 32767, counts whenever P ≤ 32767 — both static at trace
    # time — halving the bytes fetched. The host upcasts back to the int32
    # RuleTensors contract.
    id_dt = jnp.int16 if n_tracks <= 32767 else jnp.int32
    ct_dt = jnp.int16 if n_playlists <= 32767 else jnp.int32
    return (
        rule_ids.astype(id_dt),
        rule_counts.astype(ct_dt),
        row_valid.astype(id_dt),
        jnp.diagonal(counts).astype(ct_dt),
    )


def emit_rule_tensors_np(
    pair_count_matrix: np.ndarray, min_count: int, *, k_max: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Numpy twin of :func:`emit_rule_tensors` for the native-CPU mining
    path — XLA:CPU's ``top_k`` costs ~400 ms at ds2 shape where
    argpartition costs ~100 ms.

    Tie semantics replicated EXACTLY (equal counts rank by ascending column
    index, like lax.top_k) via a composite integer key ``score·V + (V-1-j)``
    that is strictly totally ordered, so partition/sort order is unique."""
    v = pair_count_matrix.shape[0]
    # int32 end to end when the key range fits (counts ≤ P make this the
    # common case): the (V, V) passes are memory-bound, and int64
    # intermediates double every one of them. The bound uses the
    # OFF-diagonal max — the diagonal holds item supports, which dominate
    # pair counts and never enter the score, so including them would flip
    # to int64 needlessly (diagonal zeroed in place and restored: one O(V)
    # touch instead of a (V, V) masked copy).
    if pair_count_matrix.flags.writeable:
        diag_save = np.diagonal(pair_count_matrix).copy()
        np.fill_diagonal(pair_count_matrix, 0)
        try:
            max_count = int(pair_count_matrix.max(initial=0))
        finally:
            np.fill_diagonal(pair_count_matrix, diag_save)
    else:  # read-only input (e.g. a jax-backed view): masked copy instead
        masked = pair_count_matrix.copy()
        np.fill_diagonal(masked, 0)
        max_count = int(masked.max(initial=0))
        del masked
    key_dtype = (
        np.int32
        if (max_count + 1) * v < np.iinfo(np.int32).max
        else np.int64
    )
    counts = pair_count_matrix.astype(key_dtype, copy=False)
    valid = counts >= min_count
    np.fill_diagonal(valid, False)
    row_valid_counts = valid.sum(axis=1, dtype=np.int32)
    score = np.where(valid, counts, key_dtype(-1))
    key = score * key_dtype(v) + (
        v - 1 - np.arange(v, dtype=key_dtype)[None, :]
    )
    k = min(k_max, v)
    if k < v:
        part = np.argpartition(-key, k - 1, axis=1)[:, :k]
    else:
        part = np.broadcast_to(np.arange(v)[None, :], (v, v)).copy()
    part_key = np.take_along_axis(key, part, axis=1)
    order = np.argsort(-part_key, axis=1)
    top_ids = np.take_along_axis(part, order, axis=1)
    top_counts = np.take_along_axis(score, top_ids, axis=1)
    keep = top_counts > 0
    rule_ids = np.where(keep, top_ids, -1).astype(np.int32)
    rule_counts = np.where(keep, top_counts, 0).astype(np.int32)
    if k < k_max:  # pad up to the declared row capacity
        pad = ((0, 0), (0, k_max - k))
        rule_ids = np.pad(rule_ids, pad, constant_values=-1)
        rule_counts = np.pad(rule_counts, pad)
    return rule_ids, rule_counts, row_valid_counts


def mine_rules_from_counts_np(
    pair_count_matrix: np.ndarray,
    *,
    n_playlists: int,
    min_support: float,
    k_max: int,
    mode: str = "support",
    min_confidence: float = 0.0,
    n_total_songs: int | None = None,
) -> RuleTensors:
    """Host-only emission from a host count matrix (the native-CPU path):
    no device round trip anywhere. Prefers the native C++ top-k (a bounded
    per-row heap, ~5 ms at ds2 shape vs ~82 ms for the numpy argpartition
    route); :func:`emit_rule_tensors_np` remains the fallback and the
    cross-check twin — all three emitters are pinned identical by test."""
    min_count = min_count_for(min_support, n_playlists)
    emitted = None
    from . import cpu_popcount

    if cpu_popcount.available():
        try:
            emitted = cpu_popcount.emit_topk(
                pair_count_matrix, min_count, k_max=k_max
            )
        except RuntimeError:
            emitted = None
    if emitted is None:
        emitted = emit_rule_tensors_np(
            pair_count_matrix, min_count, k_max=k_max
        )
    rule_ids, rule_counts, row_valid = emitted
    return assemble_rule_tensors(
        rule_ids, rule_counts, row_valid,
        np.diagonal(pair_count_matrix).astype(np.int32, copy=True),
        n_playlists=n_playlists, min_support=min_support, k_max=k_max,
        mode=mode, min_confidence=min_confidence,
        n_total_songs=n_total_songs,
        n_tracks=int(pair_count_matrix.shape[0]),
    )


def assemble_rule_tensors(
    rule_ids: np.ndarray,
    rule_counts: np.ndarray,
    row_valid: np.ndarray,
    item_counts: np.ndarray,
    *,
    n_playlists: int,
    min_support: float,
    k_max: int,
    mode: str = "support",
    min_confidence: float = 0.0,
    n_total_songs: int | None = None,
    n_tracks: int | None = None,
) -> RuleTensors:
    """Host-side assembly shared by the fused and unfused emission paths:
    confidence filtering/derivation + provenance/overflow stats."""
    if mode not in ("support", "confidence"):
        raise ValueError(f"confidence mode must be 'support' or 'confidence', got {mode!r}")
    min_count = min_count_for(min_support, n_playlists)
    n_frequent = int((item_counts >= min_count).sum())
    if mode == "confidence":
        # confidence filter applied HOST-SIDE in float64, so device float32
        # rounding can never flip a min_confidence decision (the same
        # no-float-flip rule integer min_count enforces for support). Within
        # a row, conf ordering == count ordering (fixed denominator), so the
        # device top-k's ranking is already correct and the filter removes a
        # suffix of each row.
        conf64 = rule_counts / np.maximum(item_counts, 1)[:, None].astype(np.float64)
        keep = (rule_ids >= 0) & (conf64 >= min_confidence)
        rule_ids = np.where(keep, rule_ids, -1).astype(np.int32)
        rule_counts = np.where(keep, rule_counts, 0)
    confs = derive_confs(rule_counts, item_counts, n_playlists, mode)
    return RuleTensors(
        rule_ids=rule_ids,
        rule_counts=rule_counts,
        rule_confs=confs,
        item_counts=item_counts,
        n_playlists=n_playlists,
        min_support=min_support,
        min_count=min_count,
        mode=mode,
        min_confidence=min_confidence,
        n_frequent_items=n_frequent,
        n_songs_missing=(
            n_total_songs if n_total_songs is not None else int(n_tracks)
        ) - n_frequent,
        overflow_rows=int((row_valid > k_max).sum()),
        row_valid_counts=row_valid.astype(np.int32),
    )


def mine_rules_from_counts(
    pair_count_matrix: jax.Array,
    *,
    n_playlists: int,
    min_support: float,
    k_max: int,
    mode: str = "support",
    min_confidence: float = 0.0,
    n_total_songs: int | None = None,
) -> RuleTensors:
    """Full emission from a materialized count matrix: device
    threshold/top-k, then host assembly + stats. The path for sharded and
    bit-packed mining (where the counts already exist); the dense
    single-device path uses :func:`fused_dense_rule_tensors` instead.

    ``n_total_songs``: the dataset's full unique-track count when the count
    matrix covers a PRUNED vocabulary (Apriori pre-filter) — keeps the
    missing-songs counter meaning what the reference prints
    (total_songs - frequent keys, machine-learning/main.py:304)."""
    min_count = min_count_for(min_support, n_playlists)
    rule_ids, rule_counts, row_valid = emit_rule_tensors(
        pair_count_matrix, jnp.int32(min_count), k_max=k_max
    )
    diag = jnp.diagonal(pair_count_matrix)
    # one batched fetch — four sequential np.asarray calls would pay four
    # host<->device round trips
    rule_ids, rule_counts, row_valid, item_counts = jax.device_get(
        (rule_ids, rule_counts, row_valid, diag)
    )
    return assemble_rule_tensors(
        rule_ids, rule_counts, row_valid, item_counts,
        n_playlists=n_playlists, min_support=min_support, k_max=k_max,
        mode=mode, min_confidence=min_confidence,
        n_total_songs=n_total_songs,
        n_tracks=int(pair_count_matrix.shape[0]),
    )
