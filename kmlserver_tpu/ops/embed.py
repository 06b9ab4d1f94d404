"""The embedding serving hot path as one jitted device call.

The second model family's lookup kernel (the rule twin is
``ops/serve.py``): seed songs' unit item vectors are gathered from the
HBM-resident factor table, scored against EVERY item by dot product
(cosine similarity — the factors are row-normalized at publication),
max-merged over the seeds, and the top-K extracted — batched over B
concurrent requests, same shape-bucket discipline as the rule kernel so
every (batch, length) a request can produce is pre-warmed at publish.

Semantics, mirroring the rule kernel where the models agree and
diverging only where the geometry demands it:

- ``-1``-padded seeds contribute nothing (parity with the rule kernel's
  membership filter);
- the merge is a MAX over per-seed similarities (parity with the rule
  max-merge: "how strongly does the closest seed pull this item");
- the SEED items themselves are masked out of the candidates — a unit
  vector's nearest neighbor is itself (cosine 1.0), and "you might like
  the songs you just told me about" is not a recommendation. The rule
  kernel doesn't need this mask because a rule row never contains its
  own antecedent;
- rows with no valid seed return all ``-1`` (the engine's membership
  filter degrades those to the popularity fallback before dispatch, so
  this is belt-and-braces, not the primary path);
- equal scores come out lowest item id first (``lax.top_k``'s order).

Memory shape: ONE pass over the factors per batch, and no product in
HBM at any batch size. The batch's ``B·L`` seed vectors are gathered once
and kept as ``(B, L, R)``; then the ``(R, V)`` factor table
(:func:`factor_table` — laid out once, where the model is placed, so the
catalog is the minor axis and the program re-lays nothing) is walked in
column tiles. A tile step is one ``(B, L, R) × (R, tile)`` product at the
backend's default precision (bfloat16 products, float32 sums on the
TPU, at every batch size) whose maximum over ``L`` is taken where the
product is produced: the reduction runs over the WHOLE of the product's
``L`` axis, which is the pattern the TPU compiler fuses into the matrix
unit's output (one ``convolution_reduce_fusion`` writing ``(B, tile)``).
Flattening the seeds to ``(B·L, R)`` and reducing ``L``-row groups of
the product is the same arithmetic and is NOT fused when ``B > 1``: the
``(B·L, tile)`` float32 product is then written out and read back by a
stand-alone ``reduce_max`` (a ``(2, 128)`` batch's program took 5.64 ms
on the chip that way and takes 1.94 ms this way; PERF.md §6, PR 36).
Only the ``(B, V)`` maxima are kept; the tile width follows the seed
length alone (:func:`_tile_plan`), so a request meets the same tiles
alone and in a batch.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# large-but-finite floor instead of -inf: masked lanes stay out of every
# max without breeding NaNs through 0·inf corners
_NEG = jnp.float32(-3.0e38)

# the most elements one request's (L, tile) float32 product may hold per
# tile step: 32 MiB. No product is materialised at any batch size (the
# maximum over L is fused into it), so this no longer bounds a buffer:
# it fixes the tile width per seed length, so that a (B, L) batch walks
# the tiles a lone (1, L) request walks (PR 27's plan for the lone
# buckets, unmoved). Planning on B·L rows instead, as when the product
# was live, is nowhere faster on the chip and up to 53% slower (narrow
# tiles, many trips: PERF.md §6, PR 36)
_TILE_ELEMS = 1 << 23

# a product of fewer rows than one sublane tile is lowered to a float32
# vector-unit reduction, not the matrix unit's bfloat16 pass: a lone
# one-seed request would be scored at another precision than the same
# request inside a batch. This counts ALL the rows of a step's product,
# B·L: the compiler lays the (B, L) rows out together in the sublanes,
# so a one-seed row in a batch of eight already rides the matrix unit
# (bitwise equal to the row alone on the chip; counting L instead costs
# 29% at (32, 1): PERF.md §6, PR 36). The seed axis of so small a batch
# is repeated up to this many rows (a max over duplicates changes
# nothing).
_MIN_ROWS = 8


def factor_table(item_factors) -> jax.Array:
    """Unit item factors ``(V, R)`` → the ``(R, V)`` float32 table
    :func:`embed_topk` walks. Called ONCE where a model is placed (engine
    bundle, ``EmbeddingModel``, the eval phase, the chip smoke) — the
    kernel itself never transposes or copies the table."""
    return jnp.asarray(
        np.ascontiguousarray(np.asarray(item_factors, dtype=np.float32).T)
    )


def _tile_plan(rows: int, v: int) -> tuple[int, int]:
    """→ ``(n_tiles, tile)``: the fewest equal column tiles, each a whole
    number of 128-lane groups, at which one request's ``(rows, tile)``
    product stays under ``_TILE_ELEMS``. The last tile is clamped to end
    at ``v`` (it overlaps its neighbor by < 128·n_tiles columns), so ``v``
    needs no padding."""
    widest = max(128, _TILE_ELEMS // rows // 128 * 128)
    n_tiles = -(-v // widest)
    tile = min(v, -(-v // (n_tiles * 128)) * 128)
    return n_tiles, tile


def _embed_topk_impl(
    item_factors: jax.Array,  # f32 (R, V) factor_table(), unit columns
    seed_ids: jax.Array,  # int32 (B, L), -1 padded
    *,
    k_best: int,
):
    """→ ``(top_ids int32 (B, k_best) with -1 padding, top_sims f32)``."""
    r, v = item_factors.shape
    b = seed_ids.shape[0]
    if seed_ids.size < _MIN_ROWS:
        seed_ids = jnp.tile(seed_ids, (1, -(-_MIN_ROWS // seed_ids.size)))
    length = seed_ids.shape[1]
    valid = seed_ids >= 0
    # padding slots score as a repeat of one of the row's real seeds, so
    # the tile loop needs no mask; all-padding rows are blanked after the
    # top-k, on (B, k) instead of (B, V)
    stand_in = jnp.max(seed_ids, axis=1, keepdims=True)
    safe_seeds = jnp.maximum(jnp.where(valid, seed_ids, stand_in), 0)
    vecs = jnp.take(item_factors, safe_seeds.reshape(-1), axis=1).T
    vecs = vecs.reshape(b, length, r)

    n_tiles, tile = _tile_plan(max(length, _MIN_ROWS), v)

    def score_tile(scores, i):
        start = jnp.minimum(i * tile, v - tile)
        block = jax.lax.dynamic_slice(item_factors, (0, start), (r, tile))
        # an identity (float32's own format) that pins the table to
        # float32 INSIDE the loop: without it XLA's bfloat16 propagation
        # converts the whole table ahead of the loop on every call — a
        # second pass over the factors and a 145 MB temporary
        block = jax.lax.reduce_precision(block, exponent_bits=8, mantissa_bits=23)
        # (B, L, R) × (R, tile) reduced over the whole L axis: fused into
        # the product for every B. A (B·L, R) product reshaped to
        # (B, L, tile) before the max is not (module docstring).
        sims = jnp.dot(vecs, block, preferred_element_type=jnp.float32)
        best = sims.max(axis=1)
        return jax.lax.dynamic_update_slice(scores, best, (0, start)), None

    scores, _ = jax.lax.scan(
        score_tile,
        jnp.full((b, v), _NEG, dtype=jnp.float32),
        jnp.arange(n_tiles, dtype=jnp.int32),
    )
    # mask the seeds out of their own candidate set (self-similarity is
    # trivially maximal); padding scatters out of bounds and is dropped
    batch_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
    targets = jnp.where(valid, seed_ids, v)
    scores = scores.at[batch_idx, targets].set(_NEG, mode="drop")
    k = min(k_best, v)
    top_sims, top_ids = jax.lax.top_k(scores, k)
    found = (top_sims > _NEG / 2) & valid.any(axis=1, keepdims=True)
    top_ids = jnp.where(found, top_ids, -1)
    top_sims = jnp.where(found, top_sims, 0.0)
    if k < k_best:  # static pad so callers always see k_best columns
        pad = ((0, 0), (0, k_best - k))
        top_ids = jnp.pad(top_ids, pad, constant_values=-1)
        top_sims = jnp.pad(top_sims, pad)
    return top_ids, top_sims


embed_topk = partial(jax.jit, static_argnames=("k_best",))(_embed_topk_impl)
