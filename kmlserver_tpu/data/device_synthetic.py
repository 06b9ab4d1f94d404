"""Device-resident synthetic workload generation — config-4 scale data
born in HBM, in the compressed operand format, with zero host involvement.

The host generator (``data/synthetic.py``) draws ~1.8× the target rows,
deduplicates (playlist, track) pairs with a 900M-element sort, and ships
the result through the host→device link — 645 s of host time plus ~4 GB
of transfer for BASELINE config 4 (10M playlists × 1M tracks, 500M rows).
This module replaces all of it with the TPU-native formulation:

**Bernoulli-Zipf bipartite model.** Membership of playlist p in track t is
an independent Bernoulli(q_t) with ``q_t = min(1, target_rows · w_t / P)``
and ``w_t`` the same Zipf popularity law the host generator samples from
(``data/synthetic.py zipf_weights``). Expected per-track membership counts
match the host model's (``target_rows · w_t``, capped); set semantics hold
BY CONSTRUCTION — a (p, t) pair either exists or not, so the bit-packed
operand needs no dedup at all (the additive bitset scatter's documented
precondition, ops/popcount.py popcount_pair_counts). The generator emits
the ``(v_pad, w_pad)`` uint32 bitset DIRECTLY: each frequent track's row is
a stream of Bernoulli(q_t) bits packed 32/word, produced by a jitted scan
over row blocks. No membership array ever exists, on host or device.

**Exact Apriori pruning, analytically.** Only candidate-frequent rows are
generated: tracks whose EXPECTED count ``P·q_t`` is at least
``min_count − margin·sqrt(min_count)``. For an excluded track,
P(Binomial(P, q_t) ≥ min_count) ≤ exp(−margin²/2) (Chernoff) — at the
default margin of 8 standard deviations that is < 1e-14 per track, < 1e-8
after a union bound over 10⁶ tracks: no empirically-frequent item is ever
dropped, which is the exactness contract of the Apriori prune. Rows kept
by the margin but empirically below ``min_count`` are discarded by rule
emission on their TRUE (bitset-popcount) counts, exactly like any pruned
mining run. Padded rows get q = 0 and stay all-zero.

The counting and emission downstream are the production paths untouched:
``ops/popcount.mxu_pair_counts_padded`` on the generated bitset, then
``ops/rules.mine_rules_from_counts``.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .synthetic import zipf_weights

# margin (in standard deviations of Binomial at min_count) for the
# analytic candidate-frequent cut; 8σ ⇒ drop probability < 1e-8 after a
# union bound over a 10⁶-track vocabulary
CANDIDATE_MARGIN_SIGMAS = 8.0


def zipf_bit_probs(
    n_tracks: int,
    n_playlists: int,
    target_rows: int,
    zipf_exponent: float = 1.0,
) -> np.ndarray:
    """Per-track membership probability ``q_t`` (float64, descending)."""
    w = zipf_weights(n_tracks, zipf_exponent)
    return np.minimum(target_rows * w / n_playlists, 1.0)


def candidate_frequent_count(
    q: np.ndarray,
    n_playlists: int,
    min_count: int,
    margin_sigmas: float = CANDIDATE_MARGIN_SIGMAS,
) -> int:
    """How many (Zipf-descending) tracks clear the analytic candidate cut
    ``P·q_t ≥ min_count − margin·sqrt(min_count)``. Every track outside is
    empirically infrequent with probability ≥ 1 − exp(−margin²/2).

    The σ bound only separates when ``min_count > margin² (+1)``; below
    that the margin swallows the threshold and ANY track with q > 0 could
    be empirically frequent — then every such track is a candidate
    (smoke shapes only; production min_counts are in the thousands)."""
    cut = min_count - margin_sigmas * np.sqrt(max(min_count, 1))
    if cut <= 1.0:
        return int((q > 0).sum())
    return int(np.searchsorted(-(q * n_playlists), -cut, side="right"))


def _scan_bernoulli_words(
    keys: jax.Array,  # (n_blocks, key)
    q_blocks: jax.Array,  # (n_blocks, row_block)
    valid: jax.Array,  # (w_width, 32) uint32 — 1 where the bit position is real
    *,
    row_block: int,
    w_width: int,
) -> jax.Array:
    """The ONE generator core (single-device and per-shard): scan over row
    blocks, each drawing Bernoulli bits and packing 32/word. The scan
    bounds the transient uniform buffer to ``row_block × w_width × 32``
    floats while the packed output accumulates at 1/32 of that.
    → ``(n_blocks·row_block, w_width) uint32``."""
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def step(carry, args):
        key, qb = args  # (row_block,)
        u = jax.random.uniform(key, (row_block, w_width, 32))
        bits = (u < qb[:, None, None]).astype(jnp.uint32) * valid[None]
        words = jnp.sum(  # distinct powers of two: the sum IS the OR
            bits << shifts, axis=-1, dtype=jnp.uint32
        )
        return carry, words

    _, blocks = jax.lax.scan(step, None, (keys, q_blocks))
    return blocks.reshape(-1, w_width)


def _position_mask(
    word_offset, w_width: int, n_playlists: int
) -> jax.Array:
    """(w_width, 32) uint32: 1 where global bit position
    ``(word_offset + w)·32 + b`` is a real playlist — word padding beyond
    ``n_playlists`` must stay zero or it counts as phantom playlists."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    positions = (
        (word_offset + jnp.arange(w_width, dtype=jnp.uint32))[:, None] * 32
        + shifts[None, :]
    )
    return (positions < n_playlists).astype(jnp.uint32)


@partial(jax.jit, static_argnames=("n_playlists", "v_pad", "w_pad", "row_block"))
def bitset_from_probs(
    q_padded: jax.Array,  # (v_pad,) float32; 0 for pad rows
    seed: int,
    *,
    n_playlists: int,
    v_pad: int,
    w_pad: int,
    row_block: int = 32,
) -> jax.Array:
    """Generate the ``(v_pad, w_pad)`` uint32 bitset: bit p of word
    ``[t, p // 32]`` ~ Bernoulli(q_padded[t]) for p < n_playlists, all
    independent; bit positions beyond ``n_playlists`` stay zero."""
    if v_pad % row_block:
        raise ValueError(f"v_pad {v_pad} must be a multiple of row_block {row_block}")
    n_blocks = v_pad // row_block
    keys = jax.random.split(jax.random.PRNGKey(seed), n_blocks)
    return _scan_bernoulli_words(
        keys,
        q_padded.reshape(n_blocks, row_block),
        _position_mask(jnp.uint32(0), w_pad, n_playlists),
        row_block=row_block,
        w_width=w_pad,
    )


def sharded_bitset_from_probs(
    q_padded: jax.Array,  # (v_pad,) float32; 0 for pad rows
    seed: int,
    mesh,
    *,
    n_playlists: int,
    v_pad: int,
    w_pad: int,
    row_block: int = 32,
) -> jax.Array:
    """Multi-chip twin of :func:`bitset_from_probs`: the bitset is born
    ALREADY word-axis-dp-sharded — each chip generates only its own
    ``w_pad/dp`` slab (PRNG keys folded by shard index, bit positions
    masked against the slab's global offset), so no chip ever holds or
    communicates another's slab. Feed the result to
    ``parallel.support.counts_from_sharded_bitset`` for psum'd counts —
    BASELINE config 4 on a v5e-4 with zero host involvement."""
    from ..parallel.mesh import AXIS_DP, AXIS_TP

    if mesh.shape.get(AXIS_TP, 1) > 1:
        raise ValueError(
            f"sharded_bitset_from_probs needs a dp-only (Nx1) mesh, got "
            f"{dict(mesh.shape)}"
        )
    dp = mesh.shape[AXIS_DP]
    if w_pad % dp:
        raise ValueError(f"w_pad {w_pad} must divide over dp={dp}")
    w_local = w_pad // dp
    if v_pad % row_block:
        raise ValueError(
            f"v_pad {v_pad} must be a multiple of row_block {row_block}"
        )
    n_blocks = v_pad // row_block
    # uint32 truncation keeps full-range Python seeds valid (PRNGKey
    # folds 32 bits of entropy either way)
    return _sharded_gen_fn(mesh, n_playlists, w_local, row_block, n_blocks)(
        q_padded, jnp.uint32(seed & 0xFFFFFFFF)
    )


@functools.lru_cache(maxsize=32)
def _sharded_gen_fn(mesh, n_playlists, w_local, row_block, n_blocks):
    """Cached jitted program per (mesh, shape): the seed rides as a traced
    argument so re-generation with a new seed hits the compile cache."""
    import jax.sharding as jsh

    from ..parallel.mesh import AXIS_DP

    def shard_gen(q_full: jax.Array, seed: jax.Array) -> jax.Array:
        shard = jax.lax.axis_index(AXIS_DP)
        base = jax.random.fold_in(jax.random.PRNGKey(seed), shard)
        return _scan_bernoulli_words(
            jax.random.split(base, n_blocks),
            q_full.reshape(n_blocks, row_block),
            # mask against THIS slab's global word offset
            _position_mask(
                (shard * w_local).astype(jnp.uint32), w_local, n_playlists
            ),
            row_block=row_block,
            w_width=w_local,
        )

    spec = jsh.PartitionSpec
    return jax.jit(
        jax.shard_map(
            shard_gen, mesh=mesh, in_specs=(spec(), spec()),
            out_specs=spec(None, AXIS_DP),
        )
    )


def device_synthetic_bitset(
    n_playlists: int,
    n_tracks: int,
    target_rows: int,
    min_count: int,
    *,
    zipf_exponent: float = 1.0,
    seed: int = 0,
    row_block: int = 32,
    margin_sigmas: float = CANDIDATE_MARGIN_SIGMAS,
    mesh=None,
) -> tuple[jax.Array, int, dict]:
    """Full device-side workload: → ``(bitset (v_pad, w_pad) uint32,
    n_candidates, info)``. ``info`` carries the analytic accounting
    (expected total rows over the FULL vocabulary incl. never-generated
    infrequent tracks, the candidate cut, HBM bytes). With ``mesh`` (a
    dp-only Nx1 mesh) the bitset is born word-axis-sharded, each chip
    generating only its slab."""
    from ..ops import popcount as pc

    q = zipf_bit_probs(n_tracks, n_playlists, target_rows, zipf_exponent)
    f = candidate_frequent_count(q, n_playlists, min_count, margin_sigmas)
    if f == 0:
        raise ValueError(
            f"no candidate-frequent tracks at min_count {min_count}; "
            "lower min_support or raise target_rows"
        )
    v_pad, w_pad = pc.padded_shape(f, n_playlists)
    q_padded = np.zeros(v_pad, dtype=np.float32)
    q_padded[:f] = q[:f]
    if mesh is not None:
        from ..parallel.mesh import AXIS_DP, round_up

        w_pad = round_up(w_pad, mesh.shape[AXIS_DP] * pc.word_chunk())
        bitset = sharded_bitset_from_probs(
            jnp.asarray(q_padded), seed, mesh, n_playlists=n_playlists,
            v_pad=v_pad, w_pad=w_pad, row_block=row_block,
        )
    else:
        bitset = bitset_from_probs(
            jnp.asarray(q_padded), seed, n_playlists=n_playlists,
            v_pad=v_pad, w_pad=w_pad, row_block=row_block,
        )
    info = {
        "model": "bernoulli-zipf",
        "expected_rows_total": float(n_playlists * q.sum()),
        "expected_rows_candidates": float(n_playlists * q[:f].sum()),
        "candidate_cut_count": f,
        "margin_sigmas": margin_sigmas,
        "v_pad": v_pad,
        "w_pad": w_pad,
        "bitset_bytes": int(v_pad) * int(w_pad) * 4,
    }
    return bitset, f, info
