"""Sharded pair-support counting over a ``(dp, tp)`` device mesh.

The distributed replacement for what the reference cannot do at all (its
mining is single-process CPU — SURVEY.md §2.4): the one-hot basket matrix
``X (P, V)`` is laid out ``P('dp', 'tp')`` — transactions sharded over
``dp``, vocabulary columns over ``tp`` — and the pair-count matrix
``C = XᵀX`` is produced column-sharded ``P(None, 'tp')``.

Three interchangeable implementations, all exact:

- ``impl="gspmd"`` — annotate shardings on the plain matmul and let XLA's
  SPMD partitioner insert the collectives. The idiomatic default.
- ``impl="allgather"`` — explicit ``shard_map``: ``all_gather`` the column
  shards over ``tp`` (one ICI hop, Ulysses-style all-to-all analogue), one
  local matmul, ``psum`` partial counts over ``dp``.
- ``impl="ring"`` — explicit ``shard_map`` ring: column blocks rotate around
  the ``tp`` axis via ``ppermute`` (ring-attention-style neighbor exchange),
  computing one ``(V_loc, V_loc)`` output block per step, overlapping
  compute with neighbor transfers and never materializing the full ``X`` on
  any chip. Peak per-chip memory O(P/dp · V/tp), vs O(P/dp · V) for
  all-gather — the path for 1M-track vocabularies.

All variants ``psum`` over ``dp``, so the collective volume rides ICI, and
pad P to a multiple of dp and V to a multiple of tp with zero rows/columns
(zero rows/columns contribute zero counts; padding columns are sliced off).
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..mining.vocab import Baskets
from ..ops import encode
from .mesh import AXIS_DP, AXIS_TP, round_up


def _onehot_padded(baskets: Baskets, p_pad: int, v_pad: int, mesh: Mesh) -> jax.Array:
    """Build the one-hot matrix directly into the ``P('dp','tp')`` layout."""
    build = jax.jit(
        partial(encode.onehot_matrix, n_playlists=p_pad, n_tracks=v_pad),
        out_shardings=NamedSharding(mesh, P(AXIS_DP, AXIS_TP)),
    )
    return build(
        jnp.asarray(baskets.playlist_rows), jnp.asarray(baskets.track_ids)
    )


def _dot_pt(a: jax.Array, b: jax.Array) -> jax.Array:
    """Contract dim 0 (playlists) of both operands → int32 counts."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _gspmd_counts(mesh: Mesh):
    return jax.jit(
        _dot_pt,
        in_shardings=(
            NamedSharding(mesh, P(AXIS_DP, AXIS_TP)),
            NamedSharding(mesh, P(AXIS_DP, AXIS_TP)),
        ),
        out_shardings=NamedSharding(mesh, P(None, AXIS_TP)),
    )


def _allgather_counts(mesh: Mesh):
    def local(x_local: jax.Array) -> jax.Array:
        # (P_loc, V_loc) → gather full columns (P_loc, V), one matmul,
        # psum partials over dp → (V, V_loc)
        x_cols = jax.lax.all_gather(x_local, AXIS_TP, axis=1, tiled=True)
        c_local = _dot_pt(x_cols, x_local)
        return jax.lax.psum(c_local, AXIS_DP)

    return jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=P(AXIS_DP, AXIS_TP),
            out_specs=P(None, AXIS_TP),
        )
    )


def _ring_counts(mesh: Mesh):
    tp = mesh.shape[AXIS_TP]

    def local(x_local: jax.Array) -> jax.Array:
        v_loc = x_local.shape[1]
        my = jax.lax.axis_index(AXIS_TP)
        perm = [(j, (j + 1) % tp) for j in range(tp)]

        def step(i, carry):
            block, out = carry
            # `block` currently holds shard (my - i) mod tp's columns
            src = jax.lax.rem(my - i + tp, tp)
            c = _dot_pt(block, x_local)  # (V_loc, V_loc) block of C
            out = jax.lax.dynamic_update_slice(out, c, (src * v_loc, 0))
            block = jax.lax.ppermute(block, AXIS_TP, perm)
            return block, out

        # mark the accumulator device-varying so the fori_loop carry type
        # matches after blocks of `c` (which varies per shard) land in it
        out0 = jax.lax.pcast(
            jnp.zeros((v_loc * tp, v_loc), dtype=jnp.int32),
            (AXIS_DP, AXIS_TP), to="varying",
        )
        _, out = jax.lax.fori_loop(0, tp, step, (x_local, out0))
        return jax.lax.psum(out, AXIS_DP)

    return jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=P(AXIS_DP, AXIS_TP),
            out_specs=P(None, AXIS_TP),
        )
    )


_IMPLS = {
    "gspmd": _gspmd_counts,
    "allgather": _allgather_counts,
    "ring": _ring_counts,
}


def sharded_bitpack_pair_counts(
    baskets: Baskets,
    mesh: Mesh,
    interpret: bool | None = None,
    variant: str | None = None,
    swar: bool | None = None,
    impl: str | None = None,
) -> jax.Array:
    """Pair counts over the mesh with BIT-PACKED operands: the playlist
    (word) axis is sharded over ``dp``, each chip counts its slab (MXU
    unpack-matmul or the Pallas VPU kernel, ``impl``), partial counts
    ``psum`` over ICI.

    Per-chip memory is O(V · P/(32·dp)) — 32× below the sharded dense
    int8 path — which is what makes BASELINE.json config 4 (10M baskets,
    1M-track vocabulary Apriori-pruned to the frequent items) fit in HBM.
    Requires a ``Nx1`` mesh: the word axis shards over ``dp`` only, and a
    ``tp > 1`` mesh would silently replicate the full slab on every tp chip
    (defeating the memory budget), so it is rejected — callers flatten all
    devices onto ``dp`` first (mining.miner.pair_count_fn does).
    """
    from ..ops import popcount as pc

    if mesh.shape.get(AXIS_TP, 1) > 1:
        raise ValueError(
            f"sharded_bitpack_pair_counts needs a dp-only (Nx1) mesh, got "
            f"{dict(mesh.shape)}; flatten devices onto dp first"
        )
    # impl/kernel-opt resolution happens in counts_from_sharded_bitset
    # (the ONE copy of that gating)
    dp = mesh.shape[AXIS_DP]
    v = baskets.n_tracks
    vt = pc.v_tile()
    v_pad = round_up(max(v, vt), vt)
    w_total = round_up(
        (baskets.n_playlists + 31) // 32, dp * pc.word_chunk()
    )
    build = jax.jit(
        lambda pr, ti: pc.bitpack_by_track(
            pr, ti,
            n_playlists=baskets.n_playlists, n_tracks=v,
            v_pad=v_pad, w_pad=w_total,
        ),
        out_shardings=NamedSharding(mesh, P(None, AXIS_DP)),
    )
    bt = build(
        jnp.asarray(baskets.playlist_rows), jnp.asarray(baskets.track_ids)
    )

    return counts_from_sharded_bitset(
        bt, mesh, impl=impl, interpret=interpret, variant=variant, swar=swar
    )[:v, :v]


def counts_from_sharded_bitset(
    bt: jax.Array,
    mesh: Mesh,
    impl: str | None = None,
    interpret: bool | None = None,
    variant: str | None = None,
    swar: bool | None = None,
) -> jax.Array:
    """Pair counts from an ALREADY word-axis-dp-sharded padded bitset
    ``(v_pad, w_pad) uint32``: each chip counts its slab, partials
    ``psum`` over ICI. The compute core of
    :func:`sharded_bitpack_pair_counts`, exposed for callers whose bitset
    never existed as membership pairs (device-side workload generation,
    data/device_synthetic.py). Returns the full padded ``(v_pad, v_pad)``
    counts (replicated)."""
    from ..ops import popcount as pc

    if mesh.shape.get(AXIS_TP, 1) > 1:
        raise ValueError(
            f"counts_from_sharded_bitset needs a dp-only (Nx1) mesh, got "
            f"{dict(mesh.shape)}; flatten devices onto dp first"
        )
    impl = pc.resolve_counts_impl(impl)
    if impl == "vpu":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        variant, swar = pc.resolve_kernel_opts(variant, swar)
    return _sharded_counts_fn(mesh, impl, interpret, variant, swar)(bt)


@functools.lru_cache(maxsize=32)
def _sharded_counts_fn(mesh, impl, interpret, variant, swar):
    """Cached jitted program per (mesh, impl, kernel opts): rebuilding the
    jit(shard_map(...)) closure per call would retrace + recompile every
    invocation — a warm pass would silently pay full compile time."""
    from ..ops import popcount as pc

    def local(bt_local: jax.Array) -> jax.Array:
        if impl == "mxu":
            # per-shard blocked unpack-matmul (pure XLA — composes under
            # shard_map on any backend, no interpret mode involved)
            c = pc.mxu_pair_counts_padded(bt_local)
        else:
            c = pc.popcount_pair_counts_padded(
                bt_local, interpret=interpret, variant=variant, swar=swar
            )
        return jax.lax.psum(c, AXIS_DP)

    return jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=P(None, AXIS_DP),
            out_specs=P(None, None),
            # the pallas_call's out_shape carries no vma annotation; the
            # psum makes the output mesh-invariant, checked by the tests
            check_vma=False,
        )
    )


def _padded_sharded_counts(
    baskets: Baskets, mesh: Mesh, impl: str = "gspmd"
) -> tuple[jax.Array, int]:
    """Pair counts over the mesh, still PADDED (``v_pad`` a multiple of
    ``tp``) and still column-sharded ``P(None, 'tp')`` → ``(counts, v)``.
    The sharded rule emission consumes the padded sharded matrix directly
    (slicing would gather it); :func:`sharded_pair_counts` slices for
    callers that want the plain (V, V) result."""
    if impl not in _IMPLS:
        raise ValueError(f"impl must be one of {sorted(_IMPLS)}, got {impl!r}")
    p_pad = round_up(max(baskets.n_playlists, 1), mesh.shape[AXIS_DP])
    v_pad = round_up(max(baskets.n_tracks, 1), mesh.shape[AXIS_TP])
    x = _onehot_padded(baskets, p_pad, v_pad, mesh)
    counts = _IMPLS[impl](mesh)(x) if impl != "gspmd" else _IMPLS[impl](mesh)(x, x)
    return counts, baskets.n_tracks


def sharded_pair_counts(
    baskets: Baskets, mesh: Mesh, impl: str = "gspmd"
) -> jax.Array:
    """Pair-count matrix (V, V) int32, computed over the mesh. The result
    keeps its ``P(None, 'tp')`` sharding; downstream rule emission is a
    row/column-local threshold+top-k that composes under the same jit."""
    counts, v = _padded_sharded_counts(baskets, mesh, impl)
    return counts[:v, :v]


@functools.lru_cache(maxsize=16)
def _sharded_emit_fn(mesh: Mesh, k_max: int):
    """Vocab-sharded rule emission (the model-parallel layout's miner
    half): each ``tp`` shard emits the rule rows for ITS slice of the
    antecedent axis from its resident block of the count matrix — the
    full (V, V) counts never exist on one device, which is what lets the
    mine phase accept inputs the dense replicated path cannot hold.

    The count matrix arrives column-sharded ``P(None, 'tp')`` (each shard
    holds ``C[:, lo:hi]``); ``C = XᵀX`` is symmetric, so the transpose of
    the local block IS the shard's row slab ``C[lo:hi, :]`` — no
    collective needed between counting and emission. Per-row semantics
    are exactly ``ops.rules.emit_rule_tensors`` (global-index diagonal
    masking, threshold, top-k with lax.top_k's index tie order), so the
    gathered tensors are bit-identical to the dense emission (pinned by
    tests/test_shard_layout.py). Outputs come back row-sharded
    ``P('tp', None)`` — the exact layout the sharded SERVING bundle
    wants, one vocab axis end to end."""

    def local(c_block: jax.Array, min_count: jax.Array):
        rows = c_block.T  # (V_loc, v_pad) = C[lo:hi, :] by symmetry
        v_loc, v_pad = rows.shape
        lo = jax.lax.axis_index(AXIS_TP).astype(jnp.int32) * v_loc
        row_ids = lo + jnp.arange(v_loc, dtype=jnp.int32)[:, None]
        col_ids = jnp.arange(v_pad, dtype=jnp.int32)[None, :]
        valid = (col_ids != row_ids) & (rows >= min_count)
        row_valid = valid.sum(axis=1, dtype=jnp.int32)
        score = jnp.where(valid, rows, -1)
        k = min(k_max, v_pad)
        top_counts, top_ids = jax.lax.top_k(score, k)
        keep = top_counts > 0
        rule_ids = jnp.where(keep, top_ids, -1).astype(jnp.int32)
        rule_counts = jnp.where(keep, top_counts, 0)
        if k < k_max:  # static pad up to the declared row capacity
            pad = ((0, 0), (0, k_max - k))
            rule_ids = jnp.pad(rule_ids, pad, constant_values=-1)
            rule_counts = jnp.pad(rule_counts, pad)
        # the slab's diagonal — element (r, lo + r) — = singleton supports
        item_counts = jnp.take_along_axis(rows, row_ids, axis=1)[:, 0]
        return rule_ids, rule_counts, row_valid, item_counts

    return jax.jit(
        jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(None, AXIS_TP), P()),
            out_specs=(
                P(AXIS_TP, None), P(AXIS_TP, None), P(AXIS_TP), P(AXIS_TP)
            ),
            # outputs are per-shard slabs of dp-invariant data; the
            # transpose/top_k chain carries no vma annotation to check
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=16)
def _restricted_counts_fn(mesh: Mesh):
    """Cached jitted restricted recount per mesh: gather the requested
    columns of the ``P('dp','tp')`` one-hot (replicated over tp) and
    contract the playlist axis against the full sharded matrix —
    ``C[R, :] = X[:, R]ᵀ X``, the row slice of the same int32 MXU
    contraction the full count path runs."""
    return jax.jit(
        lambda x, ids: _dot_pt(jnp.take(x, ids, axis=1), x),
        in_shardings=(
            NamedSharding(mesh, P(AXIS_DP, AXIS_TP)),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P(None, AXIS_TP)),
    )


def restricted_pair_counts(
    baskets: Baskets, row_ids, mesh: "Mesh | None" = None,
    count_path: str | None = None,
):
    """Rows ``row_ids`` of the pair-count matrix ``C = XᵀX`` → host
    ``(R, V) int32`` — the delta-mining recount (freshness/delta.py):
    only the affected baskets' vocab columns are recounted, against ALL
    baskets, so each returned row is bit-identical to the corresponding
    row of the full count matrix. With ``mesh`` the one-hot rides the
    same ``P('dp','tp')`` layout as the full sharded count path; without
    one it is a single jit over the dense encode.

    ``count_path="sparse"`` (the freshness route consults the SAME
    measured dispatcher as the full mine — mining/dispatch.py — so a
    sparse-eligible delta never silently pays the dense recount) expands
    only the baskets that contain a requested antecedent
    (ops/sparse.py); exact integer accumulation keeps every row
    bit-identical to the dense contraction, mesh or not — and since no
    one-hot is built at all, the mesh adds nothing it needs."""
    import numpy as _np

    row_ids = _np.asarray(row_ids, dtype=_np.int32)
    v = baskets.n_tracks
    if row_ids.size == 0:
        return _np.zeros((0, v), dtype=_np.int32)
    if _np.any(row_ids < 0) or _np.any(row_ids >= v):
        raise ValueError(f"row_ids outside the vocabulary (V={v})")
    if count_path == "sparse":
        from ..ops import sparse as sparse_mod

        return sparse_mod.sparse_restricted_pair_counts_np(
            baskets.playlist_rows, baskets.track_ids, row_ids,
            n_playlists=baskets.n_playlists, n_tracks=v,
        )
    if mesh is None:
        # small-work host path: a delta job is a COLD process, and a jit
        # compile (~0.3 s) would dwarf a thin row-slice recount — scatter
        # the one-hot in numpy and BLAS the slice instead. float64 keeps
        # every count exact (≤ n_playlists ≪ 2^53), so the int32 result
        # is bit-identical to the device contraction.
        if baskets.n_playlists * v <= 16_000_000:
            x = _np.zeros((baskets.n_playlists, v), dtype=_np.float64)
            x[baskets.playlist_rows, baskets.track_ids] = 1.0
            return (x[:, row_ids].T @ x).astype(_np.int32)
        x = encode.onehot_matrix(
            jnp.asarray(baskets.playlist_rows),
            jnp.asarray(baskets.track_ids),
            n_playlists=baskets.n_playlists,
            n_tracks=v,
        )
        counts = _dot_pt(jnp.take(x, jnp.asarray(row_ids), axis=1), x)
        return _np.asarray(jax.device_get(counts))
    p_pad = round_up(max(baskets.n_playlists, 1), mesh.shape[AXIS_DP])
    v_pad = round_up(max(v, 1), mesh.shape[AXIS_TP])
    x = _onehot_padded(baskets, p_pad, v_pad, mesh)
    counts = _restricted_counts_fn(mesh)(x, jnp.asarray(row_ids))
    return _np.asarray(jax.device_get(counts))[:, :v]


def sparse_sharded_rule_tensors(
    baskets: Baskets,
    mesh: Mesh,
    min_count: int,
    k_max: int,
    long_basket_threshold: int | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The SPARSE count feeding the SAME vocab-sharded emission: counts
    come from the CSR×bitpacked hybrid (ops/sparse.py — only the nnz
    membership pairs are ever touched; the ``(P, V)`` one-hot never
    exists in any layout), then ride ``P(None, 'tp')`` into the exact
    per-shard emission kernel the dense sharded path uses
    (:func:`_sharded_emit_fn`), so the emitted tensors are bit-identical
    to every other path by construction. What the sharded layout buys
    here is the EMISSION memory shape (each device holds only its
    ``C[:, lo:hi]`` block and emits its own antecedent rows); what the
    sparse count buys is skipping the dense/bitpack count FLOPs — the
    two compose."""
    import numpy as np

    from ..ops import sparse as sparse_mod

    tp = mesh.shape[AXIS_TP]
    v = baskets.n_tracks
    v_pad = round_up(max(v, 1), tp)
    counts_np = sparse_mod.sparse_pair_counts_np(
        baskets.playlist_rows, baskets.track_ids,
        n_playlists=baskets.n_playlists, n_tracks=v,
        long_basket_threshold=long_basket_threshold,
    )
    if v_pad != v:
        counts_np = np.pad(counts_np, ((0, v_pad - v), (0, v_pad - v)))
    counts = jax.device_put(
        counts_np, NamedSharding(mesh, P(None, AXIS_TP))
    )
    emitted = _sharded_emit_fn(mesh, k_max)(counts, jnp.int32(min_count))
    rule_ids, rule_counts, row_valid, item_counts = jax.device_get(emitted)
    return (
        np.asarray(rule_ids[:v]),
        np.asarray(rule_counts[:v]),
        np.asarray(row_valid[:v]),
        np.asarray(item_counts[:v]),
    )


def sharded_rule_tensors(
    baskets: Baskets,
    mesh: Mesh,
    min_count: int,
    k_max: int,
    impl: str = "gspmd",
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The vocab-sharded count→emit mining core
    (``KMLS_MODEL_LAYOUT=sharded``): one-hot sharded ``P('dp','tp')``,
    counts sharded ``P(None,'tp')``, emission per row shard — only the
    (V, K_max) rule tensors (K_max ≪ V) ever reach one host. Returns
    host ``(rule_ids, rule_counts, row_valid, item_counts)`` sliced to
    the true vocab, bit-identical to the dense single-device emission."""
    import numpy as _np

    counts, v = _padded_sharded_counts(baskets, mesh, impl)
    emitted = _sharded_emit_fn(mesh, k_max)(counts, jnp.int32(min_count))
    rule_ids, rule_counts, row_valid, item_counts = jax.device_get(emitted)
    return (
        _np.asarray(rule_ids[:v]),
        _np.asarray(rule_counts[:v]),
        _np.asarray(row_valid[:v]),
        _np.asarray(item_counts[:v]),
    )
