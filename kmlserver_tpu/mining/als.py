"""TPU-native ALS matrix factorization — the second model family.

FP-Growth rules (the paper's only model) structurally cannot answer
cold-start seeds or long-tail tracks that never co-occur above
``min_support``: a track with no frequent pair has an empty rule row, and
a track pruned before pair counting isn't even a rule-dict key. A learned
embedding space has no such floor — every track that appears in ANY
playlist gets a vector, and similarity generalizes across co-occurrence
gaps. ALX (PAPERS.md) is the recipe this follows: alternating least
squares over the playlist×track interaction matrix, where each half-sweep
is a batched normal-equation solve — matmul-shaped work that rides the
MXU, not a per-row Python loop.

Formulation: the binary membership matrix ``X ∈ {0,1}^{P×V}`` (the same
matrix the encode phase already produces as the mining one-hot) is
factorized as ``X ≈ U Fᵀ`` minimizing

    ‖X − U Fᵀ‖²_F + λ(‖U‖²_F + ‖F‖²_F)

with every cell observed (zeros included). Because the loss weights all
cells equally, both half-sweeps share ONE rank×rank Gramian, so the
per-row normal equations collapse into a single batched solve:

    U ← X F (FᵀF + λI)⁻¹        (all P users at once)
    F ← Xᵀ U (UᵀU + λI)⁻¹       (all V items at once)

Each iteration is two (big × skinny) matmuls plus two rank×rank solves —
exactly the shape ALX shards across TPU pods. Two layouts:

- **replicated** (default): the whole sweep on one device, as before.
- **mesh-sharded** (``KMLS_MODEL_LAYOUT=sharded``, or ``auto`` when the
  dense interaction matrix busts the per-device budget): the ALX recipe
  proper — the interaction matrix shards along the VOCAB axis of the
  same ``tp`` mesh the sharded miner uses (``P(None, 'tp')``), the item
  factors shard with it (``P('tp', None)``), and the user half-sweep's
  two reductions (``FᵀF`` Gramian and ``X F``) become ``psum``s over the
  vocab axis while the ITEM half-sweep stays fully shard-local
  (``X[:, lo:hi]ᵀ U`` touches only resident columns). Per-device memory
  drops to O(P·V/tp), so the auto layout can TRAIN an embedding the
  single-device HBM guard would previously have skipped. Collective
  reduction order makes the sharded factors float-equal-but-not-bit-
  equal to the replicated ones, which is exactly why ``model_layout``
  joined the checkpoint fingerprint (mining/checkpoint.py): resume
  within a layout is bit-identical, across layouts it re-trains.

- **sparse storage** (``KMLS_ALS_SPARSE``, ISSUE 13): the binary
  interaction matrix is kept COMPRESSED — the two int32 index vectors
  are the whole representation — and both big×skinny products become
  chunked gather+segment-adds over the nnz events (Tensor Casting's
  gather/scatter co-design). Memory drops from O(P·V) to O(nnz), so
  ``auto`` trains catalogs whose dense f32 matrix busts the HBM guard
  on a single device. Sparse factors are float-equal-but-not-bit-equal
  to dense ones (accumulation order), so the knob joins the checkpoint
  fingerprint exactly as ``model_layout`` did (v3 note there).

Serving consumes only the ITEM factors: seed→candidate scores are
cosine similarities in item space (item-item collaborative filtering),
so the published artifact carries the L2-normalized item factors and the
user factors are discarded after training.

Determinism: factor init comes from a fixed-seed host RNG and every
device op is deterministic on a fixed backend, so two trainings of the
same baskets on the same host produce bit-identical factors — which is
what lets the ``embed`` phase checkpoint resume bit-identically and the
manifest sha256 prove it.
"""

from __future__ import annotations

import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..config import MiningConfig
from ..ops import encode
from .vocab import Baskets


@jax.jit
def _als_sweep(
    x_mat: jax.Array,  # f32 (P, V) binary interactions
    user_f: jax.Array,  # f32 (P, R)
    item_f: jax.Array,  # f32 (V, R)
    reg: jax.Array,  # f32 scalar
) -> tuple[jax.Array, jax.Array]:
    """One alternating sweep: users then items, each a single batched
    normal-equation solve against the shared rank×rank Gramian."""
    rank = user_f.shape[1]
    eye = jnp.eye(rank, dtype=user_f.dtype)
    g_item = item_f.T @ item_f + reg * eye  # (R, R)
    # solve (R,R) @ Uᵀ = (X F)ᵀ for all P rows at once
    user_f = jnp.linalg.solve(g_item, (x_mat @ item_f).T).T
    g_user = user_f.T @ user_f + reg * eye
    item_f = jnp.linalg.solve(g_user, (x_mat.T @ user_f).T).T
    return user_f, item_f


@jax.jit
def _als_loss(
    x_mat: jax.Array, user_f: jax.Array, item_f: jax.Array, reg: jax.Array
) -> jax.Array:
    resid = x_mat - user_f @ item_f.T
    return (
        jnp.sum(resid * resid)
        + reg * (jnp.sum(user_f * user_f) + jnp.sum(item_f * item_f))
    )


ALS_SPARSE_MODES = ("auto", "always", "never")

# accumulation-chunk ceiling for the sparse half-sweeps: bounds the
# gathered (chunk, R) intermediate so peak memory is nnz-INDEPENDENT
# beyond the index arrays themselves
_SPARSE_CHUNK = 1 << 16


def _als_chunk(nnz: int) -> int:
    """Power-of-two accumulation chunk: capped by ``_SPARSE_CHUNK``, and
    scaled DOWN to the event count at small shapes so the fixed chunk
    buffer never dominates the sparse memory plan (the budget math and
    the sweep must agree — both call this)."""
    chunk = 256
    while chunk < min(max(nnz, 1), _SPARSE_CHUNK):
        chunk <<= 1
    return chunk


def resolve_als_sparse(value: str | None) -> str:
    """``KMLS_ALS_SPARSE`` validation. Fail-safe direction: sparse and
    dense factors are float-DIFFERENT (accumulation order), so a typo
    must resolve to ``auto`` — the default, whose dense-while-it-fits
    behavior is exactly what every existing deployment trains today."""
    word = (value or "auto").strip().lower()
    if word in ALS_SPARSE_MODES:
        return word
    import logging

    logging.getLogger("kmlserver_tpu.mining").warning(
        "KMLS_ALS_SPARSE=%r is not one of %s; using 'auto'",
        value, "/".join(ALS_SPARSE_MODES),
    )
    return "auto"


def sparse_als_bytes(nnz: int, p: int, v: int, rank: int) -> int:
    """Planned device bytes for the COMPRESSED formulation: the two
    int32 index vectors (the interaction matrix is binary — indices ARE
    the values), both factor matrices + their normal-equation right-hand
    sides, and one fixed-size gathered chunk. nnz-proportional — the
    dense ``P·V`` term is gone, which is the whole point."""
    return 8 * nnz + 8 * rank * (p + v) + 4 * _als_chunk(nnz) * rank


def _sparse_accumulate(seg, gidx, mat, n_out: int, chunk: int):
    """``out[s] += mat[g]`` over the padded event stream, in fixed-size
    chunks under ``lax.scan`` so the gathered intermediate never exceeds
    ``(chunk, R)``. Padding rides sentinel ids: ``seg == n_out`` lands in
    a scratch row sliced off at the end; the matching gather id is
    clipped (its value lands only in the dropped row). Traced inline by
    the jitted sweep/loss wrappers."""
    import jax

    rank = mat.shape[1]

    def step(acc, k):
        s = jax.lax.dynamic_slice_in_dim(seg, k * chunk, chunk)
        g = jax.lax.dynamic_slice_in_dim(gidx, k * chunk, chunk)
        vals = mat[jnp.minimum(g, mat.shape[0] - 1)]
        return acc.at[s].add(vals), None

    acc0 = jnp.zeros((n_out + 1, rank), mat.dtype)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(seg.shape[0] // chunk))
    return acc[:n_out]


@functools.partial(jax.jit, static_argnames=("p", "v", "chunk"))
def _sparse_als_sweep(rows, cols, user_f, item_f, reg, *, p, v, chunk):
    """One alternating sweep over the COMPRESSED interaction matrix:
    the two big×skinny products ``X F`` and ``Xᵀ U`` become chunked
    gather+segment-adds over the nnz events (Tensor Casting's
    gather/scatter co-design is the reference shape); the rank×rank
    Gramians and solves are unchanged — they never saw X at all."""
    rank = user_f.shape[1]
    eye = jnp.eye(rank, dtype=user_f.dtype)
    g_item = item_f.T @ item_f + reg * eye
    xf = _sparse_accumulate(rows, cols, item_f, p, chunk)  # X F, (P, R)
    user_f = jnp.linalg.solve(g_item, xf.T).T
    g_user = user_f.T @ user_f + reg * eye
    xtu = _sparse_accumulate(cols, rows, user_f, v, chunk)  # Xᵀ U, (V, R)
    item_f = jnp.linalg.solve(g_user, xtu.T).T
    return user_f, item_f


@functools.partial(jax.jit, static_argnames=("p", "chunk"))
def _sparse_als_loss(rows, cols, user_f, item_f, reg, nnz, *, p, chunk):
    """Exact training loss without densifying:
    ``‖X − U Fᵀ‖² = nnz − 2·Σ_nnz u_r·f_c + ‖U Fᵀ‖²`` where
    ``‖U Fᵀ‖² = Σ (UᵀU)∘(FᵀF)`` — every X-dependent term reduces over
    the nnz events only (X is binary: Σx² = nnz)."""
    import jax

    gram = jnp.sum((user_f.T @ user_f) * (item_f.T @ item_f))

    def step(acc, k):
        r = jax.lax.dynamic_slice_in_dim(rows, k * chunk, chunk)
        c = jax.lax.dynamic_slice_in_dim(cols, k * chunk, chunk)
        u = user_f[jnp.minimum(r, user_f.shape[0] - 1)]
        f = item_f[jnp.minimum(c, item_f.shape[0] - 1)]
        valid = (r < p).astype(user_f.dtype)
        return acc + jnp.sum(jnp.sum(u * f, axis=1) * valid), None

    cross, _ = jax.lax.scan(
        step, jnp.float32(0.0), jnp.arange(rows.shape[0] // chunk)
    )
    penalty = reg * (jnp.sum(user_f * user_f) + jnp.sum(item_f * item_f))
    return nnz - 2.0 * cross + gram + penalty


def _train_sparse(
    baskets: Baskets, user_init: np.ndarray, item_init: np.ndarray,
    reg: jax.Array, iters: int, p: int, v: int,
) -> tuple[np.ndarray, float]:
    """The compressed-storage sweep loop → ``(item factors, final
    loss)``. Deterministic: fixed host init, fixed chunking, XLA's
    deterministic scatter-add — two runs on the same backend produce
    bit-identical factors (test-pinned), which is what lets the embed
    checkpoint resume and the manifest sha256 keep their guarantees."""
    nnz = len(baskets.playlist_rows)
    chunk = _als_chunk(nnz)
    pad = (-nnz) % chunk if nnz else chunk
    rows = np.concatenate(
        [np.asarray(baskets.playlist_rows, np.int32), np.full(pad, p, np.int32)]
    )
    cols = np.concatenate(
        [np.asarray(baskets.track_ids, np.int32), np.full(pad, v, np.int32)]
    )
    rows_d, cols_d = jnp.asarray(rows), jnp.asarray(cols)
    user_f = jnp.asarray(user_init)
    item_f = jnp.asarray(item_init)
    for _ in range(iters):
        user_f, item_f = _sparse_als_sweep(
            rows_d, cols_d, user_f, item_f, reg, p=p, v=v, chunk=chunk
        )
    loss = float(
        _sparse_als_loss(
            rows_d, cols_d, user_f, item_f, reg, jnp.float32(nnz),
            p=p, chunk=chunk,
        )
    )
    return np.array(jax.device_get(item_f)), loss


@functools.lru_cache(maxsize=8)
def _sharded_sweep_fn(mesh):
    """One ALS sweep with the item axis sharded over the mesh's vocab
    (``tp``) axis — the ALX partitioning of these exact matmuls. The user
    half-sweep reduces over items (``psum`` of the Gramian and of
    ``X F``); the item half-sweep is embarrassingly shard-local. Cached
    per mesh so the iteration loop reuses one compiled program."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS_TP

    def local(x_loc, user_f, item_f_loc, reg):
        # x_loc (P, V_loc) f32; user_f (P, R) replicated; item_f_loc
        # (V_loc, R) — this shard's rows of the item-factor matrix
        rank = user_f.shape[1]
        eye = jnp.eye(rank, dtype=user_f.dtype)
        g_item = (
            jax.lax.psum(item_f_loc.T @ item_f_loc, AXIS_TP) + reg * eye
        )
        xf = jax.lax.psum(x_loc @ item_f_loc, AXIS_TP)  # (P, R)
        user_f = jnp.linalg.solve(g_item, xf.T).T
        g_user = user_f.T @ user_f + reg * eye
        item_f_loc = jnp.linalg.solve(g_user, (x_loc.T @ user_f).T).T
        return user_f, item_f_loc

    return jax.jit(
        jax.shard_map(
            local, mesh=mesh,
            in_specs=(
                P(None, AXIS_TP), P(None, None), P(AXIS_TP, None), P()
            ),
            out_specs=(P(None, None), P(AXIS_TP, None)),
            # the psums make user_f mesh-invariant; item_f varies by
            # design (it IS the sharded output)
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=8)
def _sharded_loss_fn(mesh):
    """Training loss over the column-sharded interaction matrix: local
    residual + local item-factor penalty, ``psum`` over the vocab axis;
    the (replicated) user-factor penalty is added once by the caller."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS_TP

    def local(x_loc, user_f, item_f_loc, reg):
        resid = x_loc - user_f @ item_f_loc.T
        return jax.lax.psum(
            jnp.sum(resid * resid) + reg * jnp.sum(item_f_loc * item_f_loc),
            AXIS_TP,
        )

    return jax.jit(
        jax.shard_map(
            local, mesh=mesh,
            in_specs=(
                P(None, AXIS_TP), P(None, None), P(AXIS_TP, None), P()
            ),
            out_specs=P(),
            check_vma=False,
        )
    )


def normalize_factors(item_factors: np.ndarray) -> np.ndarray:
    """Row-L2-normalize → unit vectors, so serving dot products are cosine
    similarities in [-1, 1] and blend cleanly with rule confidences. A
    zero row (can't arise from baskets — every vocab track appears at
    least once — but a loaded artifact must not NaN) keeps a zero vector."""
    norms = np.linalg.norm(item_factors, axis=1, keepdims=True)
    return (item_factors / np.maximum(norms, 1e-12)).astype(np.float32)


def _als_shards(cfg: MiningConfig, mesh, p: int, v: int, rank: int) -> int:
    """How many vocab shards the trainer lays the item axis over (1 =
    the legacy single-device sweep). Sharding engages only when the mesh
    spans the vocab (``tp``) axis AND the layout knob asks for it —
    explicitly (``sharded``), or via ``auto`` exactly when the
    single-device dense formulation would bust the HBM budget (the case
    that previously SKIPPED the embed phase: the mesh can hold what one
    device cannot). Deterministic in (config, dataset shape, mesh), so
    every rank of a multi-host job decides identically."""
    if mesh is None:
        return 1
    from ..parallel.mesh import AXIS_TP

    from ..parallel.layout import validate_layout

    tp = mesh.shape.get(AXIS_TP, 1)
    if tp <= 1:
        return 1
    layout = validate_layout(getattr(cfg, "model_layout", "replicated"))
    if layout == "sharded":
        return tp
    # auto: the LAYOUT decision measures against KMLS_DEVICE_BUDGET_BYTES
    # (0 = fall back to the HBM dispatch budget — the documented contract
    # in config.py); the fit GUARD below still budgets compute against
    # hbm_budget_bytes, which is a different question (can the planned
    # slab run) than this one (should the matrix shard at all)
    layout_budget = (
        getattr(cfg, "device_budget_bytes", 0) or cfg.hbm_budget_bytes
    )
    if (
        layout == "auto"
        and 5 * p * v + 8 * rank * (p + v) > layout_budget
    ):
        return tp
    return 1


def train_embeddings(
    baskets: Baskets, cfg: MiningConfig, seed: int = 0, mesh=None
) -> dict[str, Any]:
    """Train item embeddings over the transaction DB → the ``embed``
    phase's checkpoint payload:

    ``{"item_factors": f32 (V, rank) L2-normalized, "rank", "iters",
    "reg", "final_loss", "duration_s"}`` — or, when the dense
    formulation would not fit ``cfg.hbm_budget_bytes``, a payload with
    ``item_factors=None`` and a ``skipped`` reason (the pipeline then
    publishes a rules-only generation; the skip is a function of config
    + dataset shape, so every rank — and every resume — decides it
    identically).

    The interaction matrix is the SAME encode the mining path uses
    (``ops.encode.onehot_matrix`` over the deduplicated membership
    pairs), cast to f32 — two writers, one spine.
    """
    rank = max(1, cfg.als_rank)
    iters = max(1, cfg.als_iters)
    reg = jnp.float32(cfg.als_reg)
    p, v = baskets.n_playlists, baskets.n_tracks
    nnz = len(baskets.playlist_rows)
    shards = _als_shards(cfg, mesh, p, v, rank)
    # HBM-fit guard: the DENSE formulation materializes the interaction
    # matrix as f32 — 4x the int8 footprint the mining path's bitpack
    # dispatch exists to avoid — and under the sharded layout the
    # matrix-shaped terms divide across the vocab shards (the ALX
    # point), so the guard budgets the PER-DEVICE slab: X (P·V f32) +
    # its int8 encode source + both factor matrices and their
    # normal-equation right-hand sides. The SPARSE storage
    # (``KMLS_ALS_SPARSE``, ISSUE 13) replaces the P·V term with the
    # nnz-proportional compressed form, so `auto` now TRAINS the
    # catalogs the dense floor previously skipped; the deterministic
    # skip remains only when the knob pins dense-or-nothing ("never")
    # or even the compressed form busts the budget. Storage resolution
    # is a function of (config, dataset shape, budget), so every rank —
    # and every resume — decides identically.
    storage_mode = resolve_als_sparse(getattr(cfg, "als_sparse", "auto"))
    dense_bytes = 5 * p * v // shards + 8 * rank * (p + v)
    sparse_bytes = sparse_als_bytes(nnz, p, v, rank)
    use_sparse = False
    if storage_mode == "always":
        if shards > 1:
            print(
                "NOTE: KMLS_ALS_SPARSE=always under the mesh-sharded "
                "layout keeps the sharded dense half-sweeps (the mesh "
                "already divides the matrix); sparse storage applies to "
                "single-device training"
            )
        elif sparse_bytes > cfg.hbm_budget_bytes:
            # a pinned storage mode gets the SAME deterministic guard as
            # dense: training dense instead would silently change the
            # factors the pin exists to fix, and proceeding would OOM
            # after the expensive mine — skip loudly instead
            return {
                "item_factors": None,
                "rank": rank,
                "iters": iters,
                "reg": float(cfg.als_reg),
                "final_loss": None,
                "duration_s": 0.0,
                "storage": "none",
                "skipped": (
                    f"KMLS_ALS_SPARSE=always pins the compressed form "
                    f"but ~{sparse_bytes >> 20} MiB for {nnz} nnz "
                    f"exceeds hbm_budget_bytes "
                    f"({cfg.hbm_budget_bytes >> 20} MiB); embed phase "
                    "skipped — serving stays rules-only"
                ),
            }
        else:
            use_sparse = True
    elif (
        storage_mode == "auto"
        and shards == 1
        and dense_bytes > cfg.hbm_budget_bytes
        and sparse_bytes <= cfg.hbm_budget_bytes
    ):
        use_sparse = True
    if not use_sparse and dense_bytes > cfg.hbm_budget_bytes:
        return {
            "item_factors": None,
            "rank": rank,
            "iters": iters,
            "reg": float(cfg.als_reg),
            "final_loss": None,
            "duration_s": 0.0,
            "storage": "none",
            "skipped": (
                f"dense {p}x{v} interaction matrix (~{dense_bytes >> 20} MiB"
                f" per device across {shards} shard(s))"
                f" exceeds hbm_budget_bytes ({cfg.hbm_budget_bytes >> 20} "
                "MiB) and sparse storage is "
                + (
                    "disabled (KMLS_ALS_SPARSE=never)"
                    if storage_mode == "never"
                    else f"also over budget (~{sparse_bytes >> 20} MiB "
                    f"for {nnz} nnz)"
                    if shards == 1
                    else "single-device only (sharded layout active)"
                )
                + "; embed phase skipped — serving stays rules-only"
            ),
        }
    t0 = time.perf_counter()
    # fixed-seed HOST init: device RNG streams differ across backends,
    # host bytes do not — resume/fingerprint identity depends on this.
    # The draw ORDER (users then items) is shared by both layouts.
    rng = np.random.default_rng(seed)
    user_init = rng.standard_normal((p, rank)).astype(np.float32) / np.sqrt(
        rank
    )
    item_init = rng.standard_normal((v, rank)).astype(np.float32) / np.sqrt(
        rank
    )
    if use_sparse:
        item_raw, final_loss = _train_sparse(
            baskets, user_init, item_init, reg, iters, p, v
        )
        item_host = normalize_factors(item_raw)
    elif shards > 1:
        item_raw, final_loss = _train_sharded(
            baskets, mesh, user_init, item_init, reg, iters, p, v
        )
        item_host = normalize_factors(item_raw)
    else:
        x_mat = encode.onehot_matrix(
            jnp.asarray(baskets.playlist_rows),
            jnp.asarray(baskets.track_ids),
            n_playlists=p,
            n_tracks=v,
        ).astype(jnp.float32)
        user_f = jnp.asarray(user_init)
        item_f = jnp.asarray(item_init)
        for _ in range(iters):
            user_f, item_f = _als_sweep(x_mat, user_f, item_f, reg)
        final_loss = float(_als_loss(x_mat, user_f, item_f, reg))
        item_host = normalize_factors(np.array(jax.device_get(item_f)))
    duration_s = time.perf_counter() - t0
    return {
        "item_factors": item_host,
        "rank": rank,
        "iters": iters,
        "reg": float(cfg.als_reg),
        "final_loss": final_loss,
        "duration_s": duration_s,
        "shards": shards,
        "storage": "sparse" if use_sparse else "dense",
        "nnz": nnz,
    }


def _train_sharded(
    baskets: Baskets, mesh, user_init: np.ndarray, item_init: np.ndarray,
    reg: jax.Array, iters: int, p: int, v: int,
) -> tuple[np.ndarray, float]:
    """The mesh-sharded sweep loop → ``(item factors (V, R) host, final
    loss)``. The interaction matrix is built DIRECTLY into its
    ``P(None, 'tp')`` layout (no single-device staging — the whole point
    is that no device ever holds all of X), the item factors ride
    ``P('tp', None)``, and the padded vocab rows are zero-initialized so
    they stay exactly zero through every sweep (zero interaction columns
    solve to zero rows) and slice off at the end."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import AXIS_TP, round_up

    tp = mesh.shape[AXIS_TP]
    v_pad = round_up(max(v, 1), tp)
    rank = user_init.shape[1]
    build = jax.jit(
        lambda pr, ti: encode.onehot_matrix(
            pr, ti, n_playlists=p, n_tracks=v_pad
        ).astype(jnp.float32),
        out_shardings=NamedSharding(mesh, P(None, AXIS_TP)),
    )
    x_mat = build(
        jnp.asarray(baskets.playlist_rows), jnp.asarray(baskets.track_ids)
    )
    user_f = jax.device_put(
        user_init, NamedSharding(mesh, P(None, None))
    )
    item_padded = np.zeros((v_pad, rank), dtype=np.float32)
    item_padded[:v] = item_init
    item_f = jax.device_put(
        item_padded, NamedSharding(mesh, P(AXIS_TP, None))
    )
    sweep = _sharded_sweep_fn(mesh)
    for _ in range(iters):
        user_f, item_f = sweep(x_mat, user_f, item_f, reg)
    user_host = np.array(jax.device_get(user_f))
    loss = float(_sharded_loss_fn(mesh)(x_mat, user_f, item_f, reg))
    loss += float(reg) * float(np.sum(user_host * user_host))
    item_host = np.array(jax.device_get(item_f))[:v]
    return item_host, loss
