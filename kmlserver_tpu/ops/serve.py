"""The serving hot path as one jitted device call.

Replaces the reference's per-request pure-Python dict max-merge + sort
(reference: rest_api/app/main.py:224-254): seed songs' rule rows are gathered
from the HBM-resident rule tensors, and their (id, conf) lanes max-merged
and ranked among themselves, round by round, into the top-K names —
batched over B concurrent requests so 1k QPS rides a handful of device
calls.

Semantics parity notes:
- seeds absent from the rule tensors contribute nothing (the reference
  filters seeds by dict membership, rest_api/app/main.py:235);
- a recommendation may be another seed song (the reference's merge does not
  exclude seeds — only each row's own antecedent is absent from its row);
- merge is max over per-seed confidences (defaultdict max-merge at :240-247),
  then descending sort, then top ``K_BEST_TRACKS`` (:250-253). Equal
  confidences come back in ascending id order, on every backend: that
  stands in for Python's stable sort order on ties; the set of returned
  confidences is identical.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp


def _masked_topk_from_candidates(
    cand_ids: jax.Array,  # int32 (B, N) GLOBAL ids, -1 = dead lane
    cand_confs: jax.Array,  # float32 (B, N), 0 = dead lane
    *,
    k_best: int,
):
    """The gather kernels' epilogue: the max-merge of (id, conf) candidate
    lanes and their masked top-k, ranked among the N lanes themselves.

    A lane is live when id ≥ 0 and conf > 0. Each of ``k_best`` rounds
    takes a row's highest live conf ``m``, emits the lowest id among the
    lanes at ``m``, and kills every lane carrying that id: an id comes
    out once, at its highest conf (the max-merge), in (conf desc, id asc)
    order. Once a row's live lanes run out its slots read -1 / 0. No
    sort and nothing of vocabulary width: the work is k_best passes over
    the N = L·K_max lanes, whatever V is. The answer is the dense
    scatter-max into (B, V) + ``top_k``'s, on every backend, with equal
    confidences in ascending id order (tests/test_shard_layout.py holds
    it to a numpy reference)."""
    b = cand_ids.shape[0]
    live = (cand_ids >= 0) & (cand_confs > 0)
    confs = jnp.where(live, cand_confs, 0.0)
    no_id = jnp.iinfo(jnp.int32).max

    def one_round(j, carry):
        confs, top_ids, top_confs = carry
        m = confs.max(axis=1, keepdims=True)  # (B, 1)
        hit = m > 0
        pick = jnp.where(hit & (confs == m), cand_ids, no_id).min(
            axis=1, keepdims=True
        )
        pick = jnp.where(hit, pick, -1)
        confs = jnp.where(cand_ids == pick, 0.0, confs)
        top_ids = jax.lax.dynamic_update_slice(top_ids, pick, (0, j))
        top_confs = jax.lax.dynamic_update_slice(
            top_confs, jnp.where(hit, m, 0.0), (0, j)
        )
        return confs, top_ids, top_confs

    _, top_ids, top_confs = jax.lax.fori_loop(
        0, k_best, one_round,
        (
            confs,
            jnp.full((b, k_best), -1, jnp.int32),
            jnp.zeros((b, k_best), cand_confs.dtype),
        ),
    )
    return top_ids, top_confs


def _recommend_batch_impl(
    rule_ids: jax.Array,  # int32 (V, K_max), -1 padded
    rule_confs: jax.Array,  # float32 (V, K_max), 0 padded
    seed_ids: jax.Array,  # int32 (B, L), -1 padded
    *,
    k_best: int,
):
    """→ ``(top_ids int32 (B, k_best) with -1 padding, top_confs f32)``."""
    b = seed_ids.shape[0]
    safe_seeds = jnp.where(seed_ids >= 0, seed_ids, 0)
    gathered_ids = rule_ids[safe_seeds]  # (B, L, K)
    gathered_confs = rule_confs[safe_seeds]  # (B, L, K)
    valid = (gathered_ids >= 0) & (seed_ids >= 0)[..., None]
    return _masked_topk_from_candidates(
        jnp.where(valid, gathered_ids, -1).reshape(b, -1),
        jnp.where(valid, gathered_confs, 0.0).reshape(b, -1),
        k_best=k_best,
    )


recommend_batch = partial(jax.jit, static_argnames=("k_best",))(
    _recommend_batch_impl
)

# (There is no seed-buffer-donating twin: the int32 (B, L) seed batch has the
# shape and dtype of neither output, so XLA can alias it to nothing — on
# the v5e every one of the 24 warm-up compiles answered "Some donated
# buffers were not usable", PR 21. Every backend runs this one function.)


# ---------------------------------------------------------------------------
# Vocab-sharded layout (KMLS_MODEL_LAYOUT=sharded): the rule tensors are
# partitioned along the vocab (antecedent) axis across a 1-D device mesh —
# per-device HBM holds V/S rows instead of V, so the servable catalog scales
# with the mesh instead of capping at one device (the ALX sharding recipe,
# PAPERS.md). Lookup runs as one shard_map program:
#
#   1. each shard maps the replicated seed batch onto its own row range
#      (seeds outside the range contribute nothing — exactly the replicated
#      kernel's membership semantics, partitioned),
#   2. gathers its rows and ranks their (GLOBAL id, conf) lanes among
#      themselves into a per-shard top-k partial (the shared epilogue:
#      nothing of width V, though consequent ids span the full vocab),
#   3. all_gather of the (B, k) partials over the shard axis, then a
#      merge that ranks the S·k gathered lanes among themselves (equal ids
#      max-merged, order by conf desc then id asc) — nothing of width V,
#      replicated on every shard.
#
# Exactness, tie order included: for any consequent in the true global
# top-k, the shard where it attains its max partial score must rank it
# inside ITS top-k (fewer than k competitors beat it there, or they would
# beat it globally too), so the gathered candidate set contains every true
# winner at its exact global score, and ranking those lanes by (conf desc,
# id asc) — the order of every partial — reproduces the replicated
# kernel's output bit for bit, on every backend (pinned by
# tests/test_shard_layout.py against it and a numpy reference).
# ---------------------------------------------------------------------------


def _shard_partial_topk_impl(
    rule_ids_loc: jax.Array,  # int32 (V_loc, K) — GLOBAL consequent ids
    rule_confs_loc: jax.Array,  # float32 (V_loc, K)
    seed_ids: jax.Array,  # int32 (B, L), -1 padded, GLOBAL ids, replicated
    lo: jax.Array,  # int32 scalar: this shard's first global row
    *,
    v: int,
    k_best: int,
):
    """One shard's (B, k_best) top-k partial at GLOBAL ids.

    The seed batch is mapped onto this shard's row range [lo, lo+V_loc)
    (seeds outside contribute nothing — the replicated kernel's
    membership semantics, partitioned), its rows gathered, and the
    candidates pushed through THE shared epilogue. ``lo`` is a traced
    scalar so one compiled program serves every shard — inside
    shard_map it is ``axis_index * v_loc``; on a serve-mesh gang member
    it is ``rank * v_loc``. ``v``, the global vocab width, sizes
    nothing: the epilogue's work is the candidates'."""
    v_loc = rule_ids_loc.shape[0]
    b = seed_ids.shape[0]
    in_shard = (seed_ids >= lo) & (seed_ids < lo + v_loc)
    local_seeds = jnp.where(in_shard, seed_ids - lo, -1)
    safe_seeds = jnp.where(local_seeds >= 0, local_seeds, 0)
    gathered_ids = rule_ids_loc[safe_seeds]  # (B, L, K)
    gathered_confs = rule_confs_loc[safe_seeds]
    valid = (gathered_ids >= 0) & (local_seeds >= 0)[..., None]
    return _masked_topk_from_candidates(
        jnp.where(valid, gathered_ids, -1).reshape(b, -1),
        jnp.where(valid, gathered_confs, 0.0).reshape(b, -1),
        k_best=k_best,
    )


def _merge_partial_topk_impl(
    all_ids: jax.Array,  # int32 (S, B, k_best) partials, SHARD order
    all_confs: jax.Array,  # float32 (S, B, k_best)
    *,
    k_best: int,
):
    """Cross-shard max-merge of per-shard partials → final (B, k_best).

    Ranks the S·k_best gathered lanes of each row among themselves, by
    pairwise comparison: no sort, nothing of vocabulary width. A lane is
    live when id ≥ 0 and conf > 0. Of the live lanes that share an id
    (one per shard at most, but any number is handled) the one with the
    highest conf, the lowest lane on a tie, represents it — the max-merge.
    A representative's rank is the count of representatives ahead of it
    by (conf desc, id asc), the order of every partial;
    ranks below ``k_best`` fill their slot, the rest stay -1 / 0. The
    answer is the dense scatter-max + top-k's whatever the shard order;
    inside shard_map it is all_gather's, on the serve mesh ascending gang
    rank."""
    s, b, k = all_ids.shape
    n = s * k
    ids = jnp.swapaxes(all_ids, 0, 1).reshape(b, n)
    confs = jnp.swapaxes(all_confs, 0, 1).reshape(b, n)
    live = (ids >= 0) & (confs > 0)
    confs = jnp.where(live, confs, 0.0)
    # [b, i, j]: lane j against lane i
    c_i, c_j = confs[:, :, None], confs[:, None, :]
    id_i, id_j = ids[:, :, None], ids[:, None, :]
    lane = jnp.arange(n, dtype=jnp.int32)
    first = (lane[None, :] < lane[:, None])[None]
    shadowed = (live[:, None, :] & (id_j == id_i)
                & ((c_j > c_i) | ((c_j == c_i) & first)))
    rep = live & ~shadowed.any(axis=2)
    ahead = rep[:, None, :] & ((c_j > c_i) | ((c_j == c_i) & (id_j < id_i)))
    rank = ahead.sum(axis=2, dtype=jnp.int32)
    slot = rep[:, :, None] & (
        rank[:, :, None] == jnp.arange(k_best, dtype=jnp.int32)
    )  # (B, n, k_best): at most one lane per slot
    top_ids = jnp.where(slot, ids[:, :, None], -1).max(axis=1)
    top_confs = jnp.where(slot, confs[:, :, None], 0.0).max(axis=1)
    return top_ids, top_confs


# Jitted module-level twins for the multi-process serve mesh
# (serving/mesh.py): each gang member runs shard_partial_topk over its
# resident vocab slab, the coordinator stacks the partials in rank order
# and runs merge_partial_topk — the SAME two functions the shard_map
# kernel below composes, which is what makes gang answers bit-identical
# to the single-process sharded kernel by construction rather than by
# parallel maintenance (pinned in tests/test_mesh.py).
shard_partial_topk = partial(jax.jit, static_argnames=("v", "k_best"))(
    _shard_partial_topk_impl
)
merge_partial_topk = partial(jax.jit, static_argnames=("k_best",))(
    _merge_partial_topk_impl
)


def _sharded_recommend_local(
    rule_ids_loc: jax.Array,  # int32 (V_loc, K) — GLOBAL consequent ids
    rule_confs_loc: jax.Array,  # float32 (V_loc, K)
    seed_ids: jax.Array,  # int32 (B, L), -1 padded, GLOBAL ids, replicated
    *,
    k_best: int,
    axis: str,
    n_shards: int,
):
    v_loc = rule_ids_loc.shape[0]
    v = v_loc * n_shards  # padded global vocab width
    lo = jax.lax.axis_index(axis).astype(jnp.int32) * v_loc
    part_ids, part_confs = _shard_partial_topk_impl(
        rule_ids_loc, rule_confs_loc, seed_ids, lo, v=v, k_best=k_best,
    )
    all_ids = jax.lax.all_gather(part_ids, axis)  # (S, B, k_best)
    all_confs = jax.lax.all_gather(part_confs, axis)
    return _merge_partial_topk_impl(all_ids, all_confs, k_best=k_best)


@functools.lru_cache(maxsize=8)
def sharded_recommend_fn(mesh, k_best: int, axis: str = "shard"):
    """The jitted sharded lookup for one (mesh, k_best) — cached so the
    serving engine resolves it ONCE at bundle build (publication side) and
    every dispatch reuses the same compiled program: rebuilding the
    jit(shard_map(...)) closure per call would retrace on the hot path.

    Contract: ``rule_ids``/``rule_confs`` laid out
    ``NamedSharding(mesh, P(axis, None))`` with the padded vocab length a
    multiple of the shard count; ``seed_ids`` replicated. Output
    (replicated) is bit-identical to :func:`recommend_batch` on the same
    (unpadded) tensors."""
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    local = partial(
        _sharded_recommend_local,
        k_best=k_best, axis=axis, n_shards=n_shards,
    )
    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        # the all_gather makes both outputs mesh-invariant; the epilogue's
        # loop carry starts invariant and turns varying, which the
        # checker refuses
        check_vma=False,
    )

    def _recommend_batch_sharded(rule_ids, rule_confs, seed_ids):
        # the XLA module takes this function's name
        # (``jit__recommend_batch_sharded``): whoever reads a device trace
        # for the rule lookup by ``recommend_batch`` finds both layouts
        return sharded(rule_ids, rule_confs, seed_ids)

    return jax.jit(_recommend_batch_sharded)
