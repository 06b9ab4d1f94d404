#!/usr/bin/env python
"""Config-4 scale mechanics: large-vocabulary mining through the
Apriori-prune → bit-packed counting path, with explicit HBM math.

BASELINE.json config 4 is synthetic 10M playlists × 1M tracks on v5e-4 —
far beyond the dense one-hot path (the (P, V) int8 matrix alone would be
10 TB). The feasible route, demonstrated end to end here at a bounded
shape, is exactly the one the miner takes automatically
(mining/miner.py pair_count_fn):

1. Apriori prune: items below min_count cannot appear in any frequent
   itemset (exact), collapsing V to the frequent vocabulary F.
2. Bit-pack the playlist axis: (F, ceil(P/32)) uint32 bitsets — 32× below
   int8 — built on device by one scatter (ops/popcount.py bitpack_by_track).
3. Pair counts from the bitset (single chip: ops/popcount.py — MXU
   unpack-matmul by default, Pallas VPU kernel via KMLS_BITPACK_IMPL=vpu;
   on a mesh: dp-sharded bitset slabs + psum over ICI,
   parallel/support.py sharded_bitpack_pair_counts).
4. Rule emission on the (F, F) count matrix.

Prints one JSON line with the measured numbers and the HBM accounting;
stderr carries the narrative. Run on TPU for real timings (bench.py runs
this as its `scale` phase); on CPU the kernel is interpreted, so keep
shapes small with --playlists/--tracks/--rows.
"""

from __future__ import annotations

import argparse
import os
import sys

# runnable as `python scripts/<name>.py` from anywhere: the repo root
# (not scripts/) is what must be importable
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import json
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def gib(n_bytes: float) -> float:
    return n_bytes / (1 << 30)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--playlists", type=int, default=1_000_000)
    parser.add_argument("--tracks", type=int, default=100_000)
    parser.add_argument("--rows", type=int, default=50_000_000)
    parser.add_argument("--min-support", type=float, default=0.001)
    parser.add_argument(
        "--mesh", default="none",
        help="'none' = single chip; 'auto' or 'DPx1' = dp-sharded bitset slabs",
    )
    parser.add_argument("--k-max", type=int, default=64)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument(
        "--require-native", action="store_true",
        help="exit 3 unless the native CPU pair-count path is available — "
        "at large shapes the dense fallback would allocate a V x P one-hot "
        "(tens of GB) instead of failing fast",
    )
    args = parser.parse_args()

    if args.require_native:
        from kmlserver_tpu.ops import cpu_popcount

        if not cpu_popcount.available():
            log("native pair-count library unavailable; refusing to fall "
                "back to the dense path at this shape (--require-native)")
            return 3

    import numpy as np

    from kmlserver_tpu.config import MiningConfig
    from kmlserver_tpu.data.synthetic import synthetic_baskets
    from kmlserver_tpu.mining.miner import mine, prune_infrequent
    from kmlserver_tpu.ops import popcount as pc
    from kmlserver_tpu.ops.support import min_count_for

    import jax

    dev = jax.devices()[0]
    log(f"device: {dev.platform} ({dev.device_kind}) x{len(jax.devices())}")

    t0 = time.perf_counter()
    baskets = synthetic_baskets(
        n_playlists=args.playlists, n_tracks=args.tracks,
        target_rows=args.rows, seed=args.seed,
    )
    rows = len(baskets.playlist_rows)
    log(
        f"workload: {rows:,} memberships, {args.playlists:,} playlists, "
        f"{args.tracks:,} tracks (generated in "
        f"{time.perf_counter() - t0:.1f}s host-side)"
    )

    # ---- the HBM math (the argument that the path fits) ----
    min_count = min_count_for(args.min_support, baskets.n_playlists)
    pruned, _ = prune_infrequent(baskets, min_count)
    f = pruned.n_tracks
    # exactly what popcount_pair_counts allocates — never re-derived here
    f_pad, w_pad = pc.padded_shape(f, args.playlists)
    dense_unpruned = args.playlists * args.tracks  # int8 bytes
    dense_pruned = args.playlists * f
    bitset_bytes = f_pad * w_pad * 4
    counts_bytes = f_pad * f_pad * 4
    log(
        f"Apriori prune @ min_support {args.min_support} "
        f"(min_count {min_count}): {args.tracks:,} -> {f:,} frequent items"
    )
    log(
        f"HBM: dense unpruned one-hot {gib(dense_unpruned):.2f} GiB; "
        f"dense pruned {gib(dense_pruned):.2f} GiB; "
        f"bitset (F_pad {f_pad} x W_pad {w_pad} uint32) "
        f"{gib(bitset_bytes):.3f} GiB ({dense_pruned / bitset_bytes:.0f}x "
        f"below dense-pruned); counts {gib(counts_bytes):.3f} GiB"
    )

    # ---- the measured runs ----
    mesh = None
    if args.mesh != "none":
        from kmlserver_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(args.mesh)
        log(f"mesh: {dict(mesh.shape)} ({mesh.devices.size} devices)")

    def timed_mine(label, cfg, b, warm=False):
        """One logged mine() call; with ``warm`` a first untimed run
        absorbs every jit/Mosaic compile (like the bench's mining phase —
        compilation is environment preparation, not rule generation)."""
        if warm:
            mine(b, cfg, mesh=mesh)
        res = mine(b, cfg, mesh=mesh)
        log(
            f"mine[{label}]: {res.duration_s:.2f}s rule generation "
            f"({rows / res.duration_s:,.0f} membership rows/s; phase "
            "timings: "
            + ", ".join(
                f"{k} {v:.2f}s" for k, v in (res.phase_timings or {}).items()
            )
            + ")"
        )
        return res

    # 1. the bit-packed path, forced — the config-4 mechanics this demo
    # exists to prove (at TRUE config-4 shape dense cannot fit; here the
    # same code runs at a bounded shape). Cold: includes kernel compiles.
    cfg_bitpack = MiningConfig(
        min_support=args.min_support,
        k_max_consequents=args.k_max,
        bitpack_threshold_elems=1,  # force the bit-packed path
        prune_vocab_threshold=1,  # force the Apriori prune
    )
    result = timed_mine("bitpack cold", cfg_bitpack, baskets)
    assert result.pruned_vocab == f
    dur = result.duration_s
    n_rules = int((np.asarray(result.tensors.rule_ids) >= 0).sum())
    log(f"{n_rules:,} rules over {f:,} frequent items")

    out = {
        "playlists": args.playlists,
        "tracks": args.tracks,
        "rows": rows,
        "min_support": args.min_support,
        "frequent_items": f,
        "bitset_gib": round(gib(bitset_bytes), 4),
        "dense_pruned_gib": round(gib(dense_pruned), 3),
        "mine_s": round(dur, 3),
        "rows_per_s": round(rows / dur, 1),
        "n_rules": n_rules,
        "mesh": args.mesh,
        "platform": dev.platform,
    }
    # checkpoint after EVERY section: the consumer (bench.py) parses the
    # LAST stdout line, so if a later run blows the phase timeout the
    # richest checkpoint that finished still carries the headline keys
    print(json.dumps(out), flush=True)

    # 2. auto dispatch — what the miner actually does at this shape with
    # default config (HBM-fit dense/bitpack decision, mining/miner.py
    # bitpack_wanted). Warm: compile excluded, like the bench's headline.
    cfg_auto = MiningConfig(
        min_support=args.min_support, k_max_consequents=args.k_max
    )
    result_auto = timed_mine("auto warm", cfg_auto, baskets, warm=True)
    auto_rules = int((np.asarray(result_auto.tensors.rule_ids) >= 0).sum())
    if auto_rules != n_rules:
        log(f"WARNING: auto path emitted {auto_rules:,} rules vs "
            f"{n_rules:,} on the bitpack path")
    out["auto_mine_s"] = round(result_auto.duration_s, 3)
    out["auto_path"] = result_auto.count_path
    out["auto_rows_per_s"] = round(rows / result_auto.duration_s, 1)
    print(json.dumps(out), flush=True)  # checkpoint (see above)

    # 3. device-resident (TPU only): membership arrays pre-staged in HBM,
    # Apriori prune done — isolates on-chip compute + the rule fetch from
    # the ~300 MB host->device input transfer. Labeled separately, never
    # the headline.
    if dev.platform == "tpu":
        import dataclasses as _dc

        pruned_dev = _dc.replace(
            pruned,
            playlist_rows=jax.device_put(pruned.playlist_rows),
            track_ids=jax.device_put(pruned.track_ids),
        )
        jax.block_until_ready(
            (pruned_dev.playlist_rows, pruned_dev.track_ids)
        )
        cfg_res = MiningConfig(
            min_support=args.min_support,
            k_max_consequents=args.k_max,
            prune_vocab_threshold=10**9,  # already pruned
        )
        result_res = timed_mine("device-resident warm", cfg_res, pruned_dev, warm=True)
        out["device_resident_mine_s"] = round(result_res.duration_s, 3)
        out["device_resident_path"] = result_res.count_path

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
