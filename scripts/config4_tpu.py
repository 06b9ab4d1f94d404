#!/usr/bin/env python
"""BASELINE.json config 4 on a single TPU chip: 10M playlists × 1M tracks,
500M membership rows, mined EXACTLY through the bit-packed path.

Two modes:

- default (host generation): the lean sibling of ``scripts/scale_demo.py``
  — host generation (~645 s at this shape) + prune + exactly TWO mine()
  calls (cold, then warm); every extra mine re-pays a multi-GB
  host→device transfer. HBM at the default shape
  (v5e, 16 GiB): bitset (8192 × 312832 words) ≈ 9.56 GiB + pruned
  membership operands ≈ 2×1.4 GiB + (F_pad)² int32 counts ≈ 0.26 GiB +
  an unpacked slab ≈ 0.13 GiB.
- ``--device-gen``: the workload is born IN HBM as a Bernoulli-Zipf
  bitset (data/device_synthetic.py) — no host generation, no prune step
  (the Apriori cut is analytic), no bulk transfer; generation takes
  seconds on device, so the whole config fits one bounded chip call.
  HBM: bitset ≈ 9.56 GiB + ~2.6 GiB transient uniforms during
  generation + counts/slab as above.

Either way the MXU unpack-matmul impl carries the contraction:
≈1.3·10¹⁵ int8 ops ≈ 3.4 s at the chip's 394 TOPS peak.
``CONFIG4_CPU_r03.json`` documents the same shape on one CPU core
(77.8 s); this script produces the TPU twin.

Prints one JSON line (stdout); narrative on stderr. Exits 3 off-TPU
unless --allow-cpu (the CPU artifact already exists — rerunning it here
just burns ~15 min), and refuses shapes whose XLA:CPU contraction would
take hours even then.
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


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--playlists", type=int, default=10_000_000)
    parser.add_argument("--tracks", type=int, default=1_000_000)
    parser.add_argument("--rows", type=int, default=500_000_000)
    parser.add_argument("--min-support", type=float, default=0.0005)
    parser.add_argument("--k-max", type=int, default=64)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--allow-cpu", action="store_true")
    parser.add_argument(
        "--skip-warm", action="store_true",
        help="stop after the cold mine (half the host->device transfers)",
    )
    parser.add_argument(
        "--device-gen", action="store_true",
        help="generate the workload ON DEVICE as a Bernoulli-Zipf bitset "
        "(data/device_synthetic.py): no host generation (645 s at this "
        "shape), no host->device bulk transfer — the config-4 mechanics "
        "timed with no transfer in the bracket",
    )
    parser.add_argument(
        "--mesh", default="none",
        help="device-gen only: 'none' = single chip; 'auto' or 'DPx1' = "
        "each chip births its own word slab, counts psum over ICI "
        "(the v5e-4 path)",
    )
    args = parser.parse_args()

    import jax

    dev = jax.devices()[0]
    log(f"device: {dev.platform} ({dev.device_kind}) x{len(jax.devices())}")
    if dev.platform != "tpu":
        if not args.allow_cpu:
            log("not a TPU backend (CONFIG4_CPU_r03.json already covers "
                "CPU); pass --allow-cpu to run anyway")
            return 3
        # off-TPU the host-gen path's only carrier that finishes in
        # minutes is the native POPCNT counter; without it the miner would
        # take the bitset-mxu route, which is memory-safe but ~10¹⁵ int8
        # ops on XLA:CPU (hours) — refuse rather than wedge the session.
        # (--device-gen never uses the native library; its own shape-based
        # guard lives in run_device_gen.)
        if not args.device_gen:
            from kmlserver_tpu.ops import cpu_popcount

            if not cpu_popcount.available():
                log("native pair-count library unavailable; the XLA:CPU "
                    "bitset route would take hours at this shape — refusing")
                return 3

    import numpy as np

    from kmlserver_tpu.config import MiningConfig
    from kmlserver_tpu.data.synthetic import synthetic_baskets
    from kmlserver_tpu.mining.miner import mine, prune_infrequent
    from kmlserver_tpu.ops import popcount as pc
    from kmlserver_tpu.ops.support import min_count_for

    if args.device_gen:
        return run_device_gen(args, dev)

    t0 = time.perf_counter()
    baskets = synthetic_baskets(
        n_playlists=args.playlists, n_tracks=args.tracks,
        target_rows=args.rows, seed=args.seed,
    )
    rows = len(baskets.playlist_rows)
    gen_s = time.perf_counter() - t0
    log(f"workload: {rows:,} memberships, {args.playlists:,} playlists, "
        f"{args.tracks:,} tracks (generated in {gen_s:.1f}s host-side)")

    # prune OUTSIDE the device bracket so the transferred operands are the
    # pruned ones (~60-70% of rows) — at this shape the input transfer is
    # a large non-compute cost and the unpruned operands are 4 GB
    min_count = min_count_for(args.min_support, baskets.n_playlists)
    t0 = time.perf_counter()
    pruned, _ = prune_infrequent(baskets, min_count)
    prune_s = time.perf_counter() - t0
    f = pruned.n_tracks
    f_pad, w_pad = pc.padded_shape(f, args.playlists)
    log(f"Apriori prune @ min_support {args.min_support} (min_count "
        f"{min_count}): {args.tracks:,} -> {f:,} frequent items in "
        f"{prune_s:.1f}s host-side; {len(pruned.playlist_rows):,} rows kept")
    log(f"HBM plan: bitset {f_pad}x{w_pad} uint32 = "
        f"{f_pad * w_pad * 4 / (1 << 30):.2f} GiB; counts "
        f"{f_pad * f_pad * 4 / (1 << 30):.2f} GiB; operands "
        f"{2 * len(pruned.playlist_rows) * 4 / (1 << 30):.2f} GiB")

    del baskets  # host RAM: the unpruned copy is no longer needed

    # skip re-pruning inside mine(); force bitpack (dense cannot fit)
    cfg = MiningConfig(
        min_support=args.min_support,
        k_max_consequents=args.k_max,
        bitpack_threshold_elems=1,
        prune_vocab_threshold=10**9,
    )

    def one_mine(label: str):
        res = mine(pruned, cfg)
        log(f"mine[{label}]: {res.duration_s:.2f}s rule generation "
            f"({rows / res.duration_s:,.0f} membership rows/s of the "
            f"original {rows:,}; path {res.count_path}; phase timings: "
            + ", ".join(f"{k} {v:.2f}s"
                        for k, v in (res.phase_timings or {}).items())
            + ")")
        return res

    result = one_mine("cold")
    n_rules = int((np.asarray(result.tensors.rule_ids) >= 0).sum())
    log(f"{n_rules:,} rules over {f:,} frequent items")
    out = {
        "playlists": args.playlists,
        "tracks": args.tracks,
        "rows": rows,
        "min_support": args.min_support,
        "frequent_items": f,
        "bitset_gib": round(f_pad * w_pad * 4 / (1 << 30), 3),
        "gen_s": round(gen_s, 1),
        "prune_host_s": round(prune_s, 2),
        "mine_cold_s": round(result.duration_s, 3),
        # CONFIG4_CPU_r03.json's 77.8 s bracket INCLUDES its 19.2 s Apriori
        # prune (scale_demo.py prunes inside mine()); here the prune runs
        # outside the device bracket so the transferred operands are the
        # pruned ones — prune_plus_mine keys are the apples-to-apples
        # comparison against that artifact, mine_* keys are device-only
        "prune_plus_mine_cold_s": round(prune_s + result.duration_s, 3),
        "n_rules": n_rules,
        "count_path": result.count_path,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }
    if not args.skip_warm:
        result_w = one_mine("warm")
        out["mine_s"] = round(result_w.duration_s, 3)
        out["rows_per_s"] = round(rows / result_w.duration_s, 1)
        out["prune_plus_mine_s"] = round(prune_s + result_w.duration_s, 3)

    print(json.dumps(out))
    return 0


def run_device_gen(args, dev) -> int:
    """Config 4 with the workload born in HBM: Bernoulli-Zipf bitset
    generation on device (exact-by-construction set semantics, analytic
    Apriori candidate cut — data/device_synthetic.py), then the production
    counting + emission paths. The mine bracket (counts + emission) is the
    apples-to-apples twin of CONFIG4_CPU's count+emit phases; generation
    is timed separately like the host path's excluded 645 s."""
    import numpy as np

    from kmlserver_tpu.data.device_synthetic import (
        candidate_frequent_count, device_synthetic_bitset, zipf_bit_probs,
    )
    from kmlserver_tpu.ops import popcount as pc
    from kmlserver_tpu.ops import rules as rules_mod
    from kmlserver_tpu.ops.support import min_count_for

    min_count = min_count_for(args.min_support, args.playlists)
    if dev.platform != "tpu":
        # shape guard: the unpack-matmul is ~2·P·F² int8 ops; past ~10¹²
        # XLA:CPU needs many minutes and the default shape needs hours —
        # refuse instead of wedging (small smoke shapes pass)
        f_est = candidate_frequent_count(
            zipf_bit_probs(args.tracks, args.playlists, args.rows),
            args.playlists, min_count,
        )
        est_ops = 2.0 * args.playlists * f_est * f_est
        if est_ops > 1e12:
            log(f"--device-gen on a CPU backend at this shape needs "
                f"~{est_ops:.1e} int8 ops on XLA:CPU (hours) — refusing; "
                "use a smaller --playlists/--tracks/--rows for smoke runs")
            return 3
    mesh = None
    if args.mesh != "none":
        from kmlserver_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(args.mesh)
        log(f"mesh: {dict(mesh.shape)} ({mesh.devices.size} devices) — "
            "each chip births its own word slab")
    t0 = time.perf_counter()
    bitset, f_cand, info = device_synthetic_bitset(
        args.playlists, args.tracks, args.rows, min_count, seed=args.seed,
        mesh=mesh,
    )
    bitset.block_until_ready()
    gen_cold_s = time.perf_counter() - t0
    log(
        f"device-gen bitset: {info['v_pad']}x{info['w_pad']} uint32 "
        f"({info['bitset_bytes'] / (1 << 30):.2f} GiB), {f_cand:,} "
        f"candidate-frequent tracks of {args.tracks:,} "
        f"(analytic cut at {info['margin_sigmas']:.0f} sigma), expected "
        f"{info['expected_rows_total']:,.0f} memberships model-wide — "
        f"generated in {gen_cold_s:.2f}s on device (cold)"
    )

    # the sharded path resolves its counting impl from KMLS_BITPACK_IMPL;
    # resolve it HERE too so the emitted count_path label cannot lie about
    # which kernel the timings belong to (the single-chip branch is
    # hardcoded mxu)
    counts_impl = pc.resolve_counts_impl() if mesh is not None else "mxu"

    def mine_bracket():
        t = time.perf_counter()
        if mesh is not None:
            from kmlserver_tpu.parallel.support import (
                counts_from_sharded_bitset,
            )

            counts = counts_from_sharded_bitset(bitset, mesh, impl=counts_impl)
        else:
            counts = pc.mxu_pair_counts_padded(bitset)
        counts.block_until_ready()
        count_s = time.perf_counter() - t
        t = time.perf_counter()
        mined = rules_mod.mine_rules_from_counts(
            counts, n_playlists=args.playlists,
            min_support=args.min_support, k_max=args.k_max,
            n_total_songs=args.tracks,
        )
        emit_s = time.perf_counter() - t
        return counts, mined, count_s, emit_s

    counts, mined, count_s, emit_s = mine_bracket()
    n_rules = int((np.asarray(mined.rule_ids) >= 0).sum())
    measured_rows = int(mined.item_counts.astype(np.int64).sum())
    log(
        f"mine[cold]: counts {count_s:.2f}s + emission {emit_s:.2f}s; "
        f"{mined.n_frequent_items:,} empirically frequent items, "
        f"{n_rules:,} rules; {measured_rows:,} candidate memberships "
        "measured on device"
    )
    out = {
        "playlists": args.playlists,
        "tracks": args.tracks,
        "rows": round(info["expected_rows_total"]),
        "rows_basis": "expected-model-rows (bernoulli-zipf); "
        "candidate memberships measured on device in rows_measured",
        "rows_measured": measured_rows,
        "min_support": args.min_support,
        "workload_model": info["model"],
        "candidate_tracks": f_cand,
        "frequent_items": mined.n_frequent_items,
        "bitset_gib": round(info["bitset_bytes"] / (1 << 30), 3),
        "gen_device_s": round(gen_cold_s, 3),
        "mine_cold_s": round(count_s + emit_s, 3),
        "count_cold_s": round(count_s, 3),
        "emit_cold_s": round(emit_s, 3),
        "n_rules": n_rules,
        "count_path": (
            f"bitpack-{counts_impl}-devicegen"
            + ("-sharded" if mesh is not None else "")
        ),
        "mesh": args.mesh,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }
    print(json.dumps(out), flush=True)  # checkpoint before the warm pass

    if not args.skip_warm:
        del counts
        _, _, count_w, emit_w = mine_bracket()
        out["mine_s"] = round(count_w + emit_w, 3)
        out["count_s"] = round(count_w, 3)
        out["emit_s"] = round(emit_w, 3)
        # normalize by the memberships the mine actually counted, keeping
        # the key comparable with host-path rows/s; the
        # model-wide expectation travels separately, unmistakably named
        out["rows_per_s"] = round(measured_rows / (count_w + emit_w), 1)
        out["model_rows_per_s"] = round(
            info["expected_rows_total"] / (count_w + emit_w), 1
        )
        log(f"mine[warm]: counts {count_w:.2f}s + emission {emit_w:.2f}s")
        print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
