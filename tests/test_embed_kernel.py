"""The blocked embedding lookup (ISSUE 27) against a float64 numpy
reference written here: one pass over the factor table per batch, seeds
as the rows of the product, the catalog walked in column tiles.

Under ``JAX_PLATFORMS=cpu`` the matmul is float32, so the ids are exact;
the similarity tolerance is the one ``chip_smoke.py`` uses on the chip,
where the products are bfloat16.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kmlserver_tpu.observability import costmodel
from kmlserver_tpu.ops import embed as embed_ops
from kmlserver_tpu.ops.embed import embed_topk, factor_table

SIM_TOL = 2.0 ** -7
K_BEST = 32  # > V at the smallest catalog: the static pad is exercised


def _factors(v: int, rank: int, seed: int, ties: bool = True) -> np.ndarray:
    rng = np.random.default_rng(seed)
    f = rng.standard_normal((v, rank)).astype(np.float32)
    f /= np.linalg.norm(f, axis=1, keepdims=True)
    if ties:
        # exact ties: every fifth item repeats its predecessor, so the pair
        # scores identically against any seed and order falls to the id
        f[5::5] = f[4:-1:5]
    return f


def _seeds(v: int, batch: int, length: int, seed: int) -> np.ndarray:
    """-1 padded rows of every kind the kernel has to take: full, half
    padded, padding in the middle, one seed repeated, and (from the
    second row of a batch on) a row of padding alone."""
    rng = np.random.default_rng(seed)
    # seeds are never one of a tied pair: the pair's other half would score
    # 1 - (a rounding error), a near-tie with every other such half
    pool = np.flatnonzero(np.isin(np.arange(v) % 5, (1, 2, 3)))
    out = np.full((batch, length), -1, dtype=np.int32)
    for row in range(batch):
        kind = row % 4
        n = length if kind == 0 else max(1, length // 2)
        picks = rng.choice(pool, size=min(n, len(pool)), replace=False)
        out[row, : len(picks)] = picks
        if kind == 2 and length >= 4:
            out[row, 1] = -1  # a hole, not only a tail
        if kind == 3 and length >= 2:
            out[row, 1] = out[row, 0]  # the same seed twice
    if batch > 1:
        out[1] = -1
    return out


def _reference(factors: np.ndarray, seeds: np.ndarray, k_best: int):
    f64 = factors.astype(np.float64)
    v = len(f64)
    ids = np.full((len(seeds), k_best), -1, dtype=np.int64)
    sims = np.zeros((len(seeds), k_best))
    for row, seed_row in enumerate(seeds):
        live = seed_row[seed_row >= 0]
        if not len(live):
            continue
        score = (f64[live] @ f64.T).max(axis=0)
        score[live] = -np.inf
        order = np.argsort(-score, kind="stable")[: min(k_best, v - len(set(live)))]
        ids[row, : len(order)] = order
        sims[row, : len(order)] = score[order]
    return ids, sims


# a budget this small makes every shape below walk several tiles at these
# catalog sizes: 3000 is no multiple of any tile width, 3072 a multiple of
# each (128, 256, 512, 1024), 4100 leaves a clamped last tile of 4 columns
SMALL_TILE_ELEMS = 1 << 13


@pytest.mark.parametrize(
    "batch,length", [(1, 1), (1, 8), (1, 128), (4, 32), (32, 8), (2, 128), (4, 128)]
)
@pytest.mark.parametrize("rank", [8, 32])
@pytest.mark.parametrize("v", [24, 3000, 3072, 4100])
def test_blocked_lookup_matches_float64_reference(monkeypatch, v, rank, batch, length):
    monkeypatch.setattr(embed_ops, "_TILE_ELEMS", SMALL_TILE_ELEMS)
    # the plan follows the seed length alone, whatever the batch
    n_tiles, tile = embed_ops._tile_plan(max(length, embed_ops._MIN_ROWS), v)
    assert (n_tiles > 1) == (v > 24)
    assert (v % tile == 0) == (v in (24, 3072))
    factors = _factors(v, rank, seed=v + rank)
    seeds = _seeds(v, batch, length, seed=batch * 1000 + length)
    # a fresh jit: the module-level one would keep a trace made under
    # the real budget for this shape
    kernel = jax.jit(partial(embed_ops._embed_topk_impl, k_best=K_BEST))
    ids, sims = kernel(factor_table(factors), jnp.asarray(seeds))
    ids, sims = np.asarray(ids), np.asarray(sims)
    want_ids, want_sims = _reference(factors, seeds, K_BEST)
    assert ids.shape == sims.shape == (batch, K_BEST)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(sims, want_sims, rtol=0, atol=SIM_TOL)
    for row in range(batch):
        live = set(seeds[row][seeds[row] >= 0].tolist())
        assert not live & set(ids[row].tolist())  # seeds never recommended
        if not live:
            assert (ids[row] == -1).all() and (sims[row] == 0).all()


def test_lone_all_padding_row_returns_nothing():
    factors = _factors(200, 8, seed=1)
    ids, sims = embed_topk(
        factor_table(factors), jnp.full((1, 8), -1, jnp.int32), k_best=10
    )
    assert (np.asarray(ids) == -1).all() and (np.asarray(sims) == 0).all()


@pytest.mark.parametrize("others", ["mixed", "padding"])
@pytest.mark.parametrize("batch", [2, 4, 8, 32])
@pytest.mark.parametrize("length", [1, 8, 32, 128])
def test_answer_does_not_depend_on_the_batch(length, batch, others):
    """PERF.md §6 finding 4, mended, and kept by PR 36's fused maximum:
    the same seed row alone and inside a batch of 2, 4, 8 or 32
    is scored by the same product at the same precision, whether the
    batch's other rows are live or padding alone. Row 1 holds ONE seed at
    every length, and at length 1 every row does (``_MIN_ROWS``: alone it
    is repeated to a sublane tile, in a batch of eight it is not; on the
    chip the two are bitwise equal, PERF.md §6 PR 36). (The CPU's float32
    sums may associate differently with the row count: the last bits, far
    below any rank gap.)"""
    v = 5000
    table = factor_table(_factors(v, 32, seed=7, ties=False))
    rng = np.random.default_rng(length * 100 + batch)
    seeds = np.full((batch, length), -1, dtype=np.int32)
    probes = sorted({0, 1, batch - 1})
    for row in range(batch):
        if others == "padding" and row != batch - 1:
            continue
        n = 1 if row == 1 else max(1, length - row % 3)
        seeds[row, :n] = rng.choice(v, size=n, replace=False)
    batch_ids, batch_sims = embed_topk(table, jnp.asarray(seeds), k_best=10)
    batch_ids, batch_sims = np.asarray(batch_ids), np.asarray(batch_sims)
    for row in probes:
        ids, sims = embed_topk(table, jnp.asarray(seeds[row : row + 1]), k_best=10)
        np.testing.assert_array_equal(np.asarray(ids)[0], batch_ids[row])
        np.testing.assert_allclose(
            np.asarray(sims)[0], batch_sims[row], rtol=0, atol=2.0 ** -20
        )
        live = (seeds[row] >= 0).any()
        assert (batch_ids[row] >= 0).all() == live


@pytest.mark.parametrize(
    "rows,v",
    [(8, 24), (8, 2262292), (128, 2262292), (4096, 2262292), (4096, 3000), (1 << 20, 70000)],
)
def test_tile_plan_bounds_the_product_and_covers_the_catalog(rows, v):
    n_tiles, tile = embed_ops._tile_plan(rows, v)
    assert n_tiles * tile >= v and tile <= v
    assert tile == v or tile % 128 == 0
    # one tile's product fits the budget, unless a single 128-lane group
    # of this many rows already exceeds it
    assert rows * tile <= max(embed_ops._TILE_ELEMS, rows * 128)
    assert (n_tiles - 1) * tile < v  # no tile lies wholly past the end


def test_factor_table_is_the_transposed_float32_layout():
    factors = _factors(50, 8, seed=3).astype(np.float64)
    table = factor_table(factors)
    assert table.shape == (8, 50) and table.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(table), factors.astype(np.float32).T)


def test_cost_model_counts_one_read_of_the_factors_per_batch():
    spec = costmodel.KERNEL_COST_SPECS["embed_topk"]
    dims = dict(b=32, l=128, v=2262292, r=32, k_best=10)
    table_bytes = dims["v"] * dims["r"] * 4
    assert table_bytes < spec.bytes_moved(dims) < 4 * table_bytes
    # the table's share does not grow with the seed axis
    longer = spec.bytes_moved({**dims, "l": 256}) - spec.bytes_moved(dims)
    assert longer < 0.01 * table_bytes
    assert spec.flops(dims) >= 2.0 * 32 * 128 * 2262292 * 32
