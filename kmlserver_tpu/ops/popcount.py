"""Pallas TPU kernel: pair-support counting over bit-packed baskets.

The dense int8 ``XᵀX`` path (ops/support.py) stores one byte per
(playlist, track) cell — at BASELINE.json config 4 scale (10M playlists ×
1M tracks) that's 10 TB and infeasible. Packing the PLAYLIST axis into
uint32 bit-words shrinks the operand 32× and turns pair counting into

    C[i, j] = Σ_w popcount(Bt[i, w] & Bt[j, w])

where ``Bt (V, ceil(P/32)) uint32`` holds track i's playlist membership as a
bitset. The kernel tiles that computation for the VPU:

- grid ``(i_tile, j_tile, w_chunk)``: output tile ``(TI, TJ) int32`` revisited
  across the trailing ``w_chunk`` dimension and accumulated in place
  (zero-initialized at the first chunk via ``@pl.when``);
- per step, row block A ``(TI, WK)`` and column block B ``(TJ, WK)`` live in
  VMEM; AND + popcount + word-sum run on the VPU — no MXU, no unpacking;
- V is padded to the 128-lane tile and P to 32·WK word chunks with zero
  bits, which contribute zero counts and are sliced away by the caller.

Two implementations share the bit-packed operand (``impl=`` /
``KMLS_BITPACK_IMPL``): ``"mxu"`` (default) is a pure-XLA blocked
unpack-matmul (:func:`mxu_pair_counts_padded`) that puts the contraction on
the MXU; ``"vpu"`` is the Pallas AND+popcount kernel below. The VPU kernel
itself has two variants (``variant=``) with identical results:

- ``"bcast"`` (default): fully vectorized — slices the word chunk into
  SUB-wide pieces and broadcasts ``(TI, 1, SUB) & (1, TJ, SUB)``; only
  static shapes, no dynamic VMEM indexing.
- ``"row"``: a ``fori_loop`` over the TI rows with dynamic sublane reads
  (``a_ref[i, :]``) — smaller intermediates, more loop overhead.

``swar=True`` replaces ``jax.lax.population_count`` with an adds-and-shifts
SWAR popcount (Hacker's Delight fig. 5-2, public-domain identity).

What the chip said (TPU v5e, jax 0.9.0 / libtpu 0.0.34, PR 21): all four
variant × popcount combinations compile with ``interpret=False`` at the
default tiles and match :func:`mxu_pair_counts_padded` exactly on a
68 × 17 × 4 grid (65,536 playlists × 2,171 tracks — every axis multi-step,
so the accumulate-across-chunks branch runs). The Mosaic popcount primitive
lowers; neither the dynamic sublane index of ``"row"`` nor the minor-axis
reduce of ``"bcast"`` is refused. ``chip_smoke.py`` keeps checking the
default combination; which one is fastest is not measured.

On non-TPU backends the kernel runs in interpreter mode (tests); the public
entry point selects it there by itself.

Tile sizes are env-tunable (``KMLS_POPCOUNT_TILE_I/TILE_J/WORD_CHUNK``) for
on-hardware tuning without a code change; defaults keep every operand on
the (8, 128) 32-bit tile grid. Per-step VMEM at the defaults: the operand
and output blocks are ≈ 0.33 MB (the pipeline double-buffers them), and
the ``"bcast"`` variant adds a ``(TI, TJ, SUB)`` uint32 intermediate of
2 MiB per live copy — which the compiler fits in its default scoped VMEM.
Like ``KMLS_POPCOUNT_VARIANT``, the tile knobs are read LAZILY at
kernel-build time (:func:`resolve_tiles`) — an env change after import
takes effect on the next call, and because the resolved sizes ride the
jit static arguments, a changed tile can never silently reuse a program
compiled for the old one. (They were read once at module import until
ISSUE 13; tests now pin the lazy behavior.)
"""

from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import encode

TILE_I_DEFAULT = 32
TILE_J_DEFAULT = 128
WORD_CHUNK_DEFAULT = 512
_SUB = 128  # lane-aligned word slice for the bcast variant's 3D intermediate

VARIANTS = ("bcast", "row")
COUNT_IMPLS = ("mxu", "vpu")


def resolve_tiles(
    tile_i: int | None = None,
    tile_j: int | None = None,
    word_chunk: int | None = None,
) -> tuple[int, int, int]:
    """``(TILE_I, TILE_J, WORD_CHUNK)`` — explicit args > env knobs >
    defaults, validated. THE one read point for the tile knobs, called
    at kernel-build time (never at import: a deployment that exports
    the knobs after importing the package must still be heard)."""
    if tile_i is None:
        tile_i = int(os.environ.get("KMLS_POPCOUNT_TILE_I", TILE_I_DEFAULT))
    if tile_j is None:
        tile_j = int(os.environ.get("KMLS_POPCOUNT_TILE_J", TILE_J_DEFAULT))
    if word_chunk is None:
        word_chunk = int(
            os.environ.get("KMLS_POPCOUNT_WORD_CHUNK", WORD_CHUNK_DEFAULT)
        )
    if tile_i < 1 or tile_j < 1 or word_chunk < 1:
        raise ValueError(
            f"popcount tiles must be positive, got "
            f"{tile_i}x{tile_j}x{word_chunk}"
        )
    if word_chunk > _SUB and word_chunk % _SUB != 0:
        raise ValueError(
            f"KMLS_POPCOUNT_WORD_CHUNK={word_chunk} must be a multiple of "
            f"{_SUB} (or at most {_SUB}): the bcast kernel slices word "
            f"chunks in {_SUB}-wide pieces and a ragged tail would be "
            "dropped"
        )
    return tile_i, tile_j, word_chunk


def v_tile(tile_i: int | None = None, tile_j: int | None = None) -> int:
    """The vocab-axis padding unit: the vocab must pad to a multiple of
    BOTH tile sizes — rounding to max() silently leaves output rows
    unwritten when TILE_I ∤ TILE_J."""
    ti, tj, _ = resolve_tiles(tile_i, tile_j)
    return math.lcm(ti, tj)


def word_chunk() -> int:
    """The resolved word-chunk size (lazy env read)."""
    return resolve_tiles()[2]


def resolve_counts_impl(impl: str | None = None) -> str:
    """Bit-packed counting implementation (``KMLS_BITPACK_IMPL``):

    - ``"mxu"`` (default): blocked unpack-matmul — scan over word-chunk
      slabs, unpack each uint32 slab to int8 bits in registers, one native
      int8×int8→int32 MXU contraction per slab (:func:`mxu_pair_counts_padded`).
      Pure XLA (no Mosaic lowering involved), runs natively on every
      backend, and puts the operations on the MXU, where the chip has its
      integer peak (config 4 is ≈1.3·10¹⁵ int8 ops). The two impls have not
      been timed against each other on the chip (ROADMAP queue 3 item 4).
      It is fast off-TPU too — measured 1.1 s vs 43 s for the
      dense int8 matmul on XLA:CPU at 100k×2k (the compressed operand
      streams through cache where the dense one thrashes it), so it is
      also the right fallback when the native CPU counter can't build.
    - ``"vpu"``: the Pallas AND+popcount kernel (``variant``/``swar``
      selectable) — no unpacked intermediate at all; kept as the
      cross-check twin and for shapes where unpacked slabs are unwelcome.
    """
    if impl is None:
        impl = os.environ.get("KMLS_BITPACK_IMPL", "mxu")
    if impl not in COUNT_IMPLS:
        raise ValueError(f"impl must be one of {COUNT_IMPLS}, got {impl!r}")
    return impl


def resolve_kernel_opts(
    variant: str | None, swar: bool | None
) -> tuple[str, bool]:
    """Kernel variant/popcount-impl selection with env-var defaults
    (``KMLS_POPCOUNT_VARIANT``, ``KMLS_POPCOUNT_SWAR``) — shared by the
    single-chip entry AND the dp-sharded path so a deployment can be
    retargeted without a code change on either."""
    if variant is None:
        variant = os.environ.get("KMLS_POPCOUNT_VARIANT", "bcast")
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}, got {variant!r}")
    if swar is None:
        swar = os.environ.get("KMLS_POPCOUNT_SWAR", "0") == "1"
    return variant, swar


def _popcount_words(x: jax.Array, swar: bool) -> jax.Array:
    """Per-word popcount → int32. ``swar=False`` uses the hardware/XLA
    primitive; ``swar=True`` uses shifts+adds only (no multiply, no
    popcount primitive), for backends where the primitive won't lower."""
    if not swar:
        return jax.lax.population_count(x).astype(jnp.int32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    x = x + (x >> 16)
    x = x + (x >> 8)
    return (x & jnp.uint32(0x3F)).astype(jnp.int32)


def _kernel_row(a_ref, b_ref, out_ref, *, swar: bool):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    b_block = b_ref[:]  # (TJ, WK) uint32

    def row(i, _):
        anded = jnp.bitwise_and(a_ref[i, :], b_block)  # broadcast (TJ, WK)
        out_ref[i, :] += jnp.sum(_popcount_words(anded, swar), axis=1)
        return 0

    jax.lax.fori_loop(0, a_ref.shape[0], row, 0)


def _kernel_bcast(a_ref, b_ref, out_ref, *, swar: bool):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    a = a_ref[:]  # (TI, WK)
    b = b_ref[:]  # (TJ, WK)
    ti, wk = a.shape
    tj = b.shape[0]
    sub = min(_SUB, wk)

    # static Python unroll (wk/sub is a compile-time constant, default 4)
    # rather than a fori_loop over traced slice starts: static slices are
    # what this body was compiled and checked with on a v5e (module
    # docstring)
    acc = jnp.zeros((ti, tj), jnp.int32)
    for c in range(wk // sub):
        a_c = a[:, c * sub:(c + 1) * sub]  # (TI, SUB)
        b_c = b[:, c * sub:(c + 1) * sub]  # (TJ, SUB)
        anded = a_c[:, None, :] & b_c[None, :, :]  # (TI, TJ, SUB)
        acc = acc + jnp.sum(_popcount_words(anded, swar), axis=2)
    out_ref[:] += acc


_KERNELS = {"row": _kernel_row, "bcast": _kernel_bcast}


def popcount_pair_counts_padded(
    bt: jax.Array,
    *,
    interpret: bool = False,
    variant: str = "bcast",
    swar: bool = False,
    tile_i: int | None = None,
    tile_j: int | None = None,
    word_chunk: int | None = None,
) -> jax.Array:
    """Pair counts from an already-padded bitset matrix
    ``bt (V_pad, W_pad) uint32`` with V_pad % lcm(TILE_I, TILE_J) == 0
    and W_pad % WORD_CHUNK == 0. → int32 (V_pad, V_pad). Tile sizes
    resolve HERE (env or explicit) and ride the jit static args, so a
    knob change after import builds — and caches — a new program."""
    ti, tj, wk = resolve_tiles(tile_i, tile_j, word_chunk)
    return _popcount_padded_jit(
        bt, interpret=interpret, variant=variant, swar=swar,
        tile_i=ti, tile_j=tj, word_chunk=wk,
    )


@partial(
    jax.jit,
    static_argnames=("interpret", "variant", "swar", "tile_i", "tile_j", "word_chunk"),
)
def _popcount_padded_jit(
    bt: jax.Array,
    *,
    interpret: bool,
    variant: str,
    swar: bool,
    tile_i: int,
    tile_j: int,
    word_chunk: int,
) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    v_pad, w_pad = bt.shape
    if v_pad % tile_i or v_pad % tile_j or w_pad % word_chunk:
        raise ValueError(
            f"bt {bt.shape} must pad V to a multiple of lcm(TILE_I, TILE_J)"
            f"={math.lcm(tile_i, tile_j)} and W to a multiple of "
            f"WORD_CHUNK={word_chunk}; a truncating grid would silently "
            "skip output tiles"
        )
    grid = (v_pad // tile_i, v_pad // tile_j, w_pad // word_chunk)
    return pl.pallas_call(
        partial(_KERNELS[variant], swar=swar),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (tile_i, word_chunk),
                lambda i, j, k: (i, k),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (tile_j, word_chunk),
                lambda i, j, k: (j, k),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile_i, tile_j), lambda i, j, k: (i, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((v_pad, v_pad), jnp.int32),
        interpret=interpret,
    )(bt, bt)


def mxu_pair_counts_padded(
    bt: jax.Array, *, word_chunk: int | None = None
) -> jax.Array:
    """Pair counts from a padded bitset via blocked unpack-matmul on the MXU.

    Identical contract to :func:`popcount_pair_counts_padded` —
    ``bt (V_pad, W_pad) uint32`` → int32 ``(V_pad, V_pad)`` — but the
    compute lands on the MXU instead of the VPU:

        C = Σ_k U_k · U_kᵀ,   U_k = unpack_bits(bt[:, k·WK:(k+1)·WK]) int8

    Each scan step slices one word-chunk slab, unpacks its 32 bit-planes
    into an ``(V_pad, WK·32)`` int8 operand (the bit→column order is
    irrelevant: both operands of the self-contraction use the same order),
    and issues one native int8×int8→int32 contraction. Exact: every
    partial product is 0/1 and accumulation is integer. The unpacked slab
    is 8× the bitset slab but only one slab exists at a time — HBM holds
    the 32×-compressed bitset, which is the whole point of the path.

    Pure XLA: no Pallas/Mosaic involvement, so it runs natively (not
    interpreted) on CPU test backends and carries zero lowering risk on
    TPU generations. The word-chunk knob resolves here (lazy env read)
    and rides the inner jit's static arg.
    """
    wk = min(resolve_tiles(word_chunk=word_chunk)[2], bt.shape[1])
    return _mxu_padded_jit(bt, word_chunk=wk)


@partial(jax.jit, static_argnames=("word_chunk",))
def _mxu_padded_jit(bt: jax.Array, *, word_chunk: int) -> jax.Array:
    v_pad, w_pad = bt.shape
    wk = word_chunk
    if w_pad % wk:
        raise ValueError(
            f"W_pad {w_pad} must be a multiple of the word chunk {wk} "
            f"(padded_shape guarantees this); a ragged tail would be dropped"
        )
    bits = jnp.arange(32, dtype=jnp.uint32)

    def step(acc: jax.Array, k: jax.Array):
        slab = jax.lax.dynamic_slice(bt, (0, k * wk), (v_pad, wk))
        unpacked = (
            ((slab[:, :, None] >> bits[None, None, :]) & jnp.uint32(1))
            .astype(jnp.int8)
            .reshape(v_pad, wk * 32)
        )
        acc = acc + jax.lax.dot_general(
            unpacked,
            unpacked,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return acc, None

    acc0 = jnp.zeros((v_pad, v_pad), jnp.int32)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(w_pad // wk))
    return acc


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def padded_shape(n_tracks: int, n_playlists: int) -> tuple[int, int]:
    """``(v_pad, w_pad)`` the kernel actually allocates: the vocabulary
    padded to ``lcm(TILE_I, TILE_J)`` and the bitset word count
    ``ceil(P/32)`` padded to ``WORD_CHUNK`` (tiles resolved lazily, so
    this tracks the env knobs call-by-call). The ONE copy of this math —
    bench/demo HBM accounting must call it, not re-derive it (the two
    hand-derived copies drifted twice)."""
    ti, tj, wk = resolve_tiles()
    vt = math.lcm(ti, tj)
    v_pad = _round_up(max(n_tracks, vt), vt)
    w_pad = _round_up(
        (n_playlists + encode.WORD_BITS - 1) // encode.WORD_BITS, wk
    )
    return v_pad, w_pad


def bitpack_by_track(
    playlist_rows: np.ndarray,
    track_ids: np.ndarray,
    *,
    n_playlists: int,
    n_tracks: int,
    v_pad: int,
    w_pad: int,
) -> jax.Array:
    """Bitset matrix (v_pad, w_pad) uint32: bit p of word ``Bt[t, p // 32]``
    set iff playlist p contains track t. The packer is the same scatter as
    ``encode.bitpack_matrix`` with the axes' roles swapped."""
    if n_playlists > w_pad * encode.WORD_BITS:
        raise ValueError(f"w_pad {w_pad} too small for {n_playlists} playlists")
    return encode.bitpack_matrix(
        jnp.asarray(track_ids),  # rows = tracks
        jnp.asarray(playlist_rows),  # bits = playlists
        n_playlists=v_pad,
        n_tracks=w_pad * encode.WORD_BITS,
    )


def popcount_pair_counts(
    playlist_rows: np.ndarray,
    track_ids: np.ndarray,
    *,
    n_playlists: int,
    n_tracks: int,
    interpret: bool | None = None,
    variant: str | None = None,
    swar: bool | None = None,
    impl: str | None = None,
) -> jax.Array:
    """Public entry: membership pairs → (V, V) int32 pair counts from the
    bit-packed operand. Pairs must be DEDUPLICATED (the ``build_baskets``
    invariant, ops/encode.py): a duplicate would add twice in the dense
    one-hot but OR to one bit here, silently diverging the counts.
    ``impl`` (default ``KMLS_BITPACK_IMPL``, "mxu")
    selects :func:`mxu_pair_counts_padded` (blocked unpack-matmul) or the
    Pallas VPU popcount kernel; interpreter mode auto-enables off-TPU for
    the VPU kernel only (the MXU path is pure XLA and runs natively
    everywhere). variant/swar default from ``KMLS_POPCOUNT_VARIANT`` /
    ``KMLS_POPCOUNT_SWAR`` so the deployed job can be retargeted without a
    code change."""
    impl = resolve_counts_impl(impl)
    v_pad, w_pad = padded_shape(n_tracks, n_playlists)
    bt = bitpack_by_track(
        playlist_rows, track_ids,
        n_playlists=n_playlists, n_tracks=n_tracks,
        v_pad=v_pad, w_pad=w_pad,
    )
    if impl == "mxu":
        counts = mxu_pair_counts_padded(bt)
    else:
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        variant, swar = resolve_kernel_opts(variant, swar)
        counts = popcount_pair_counts_padded(
            bt, interpret=interpret, variant=variant, swar=swar
        )
    return counts[:n_tracks, :n_tracks]
