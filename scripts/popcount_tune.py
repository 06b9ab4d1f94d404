#!/usr/bin/env python
"""On-hardware sweep for the bit-packed counting impls (Pallas VPU
kernel tiles + the MXU unpack-matmul).

The kernel's tiles are env-tunable (``KMLS_POPCOUNT_TILE_I/TILE_J/
WORD_CHUNK``, ops/popcount.py) precisely so they can be tuned on real
hardware without a code change — this script is the tuner. Each config runs
in its OWN subprocess (the tile constants bind at module import from the
env), asserts count equality against the dense MXU path once, then reports
amortized kernel time (pipelined dispatches — per-blocked-call time is
floored by the host<->device round trip, which would drown a kernel of a
few milliseconds).

Prints one JSON line: every config's (ms, words/s) plus the winner. Run on
TPU; off-TPU the kernel interprets and the sweep measures Python, so the
script refuses unless --allow-interpret.

Usage (ds2 shape by default):
    python scripts/popcount_tune.py
    python scripts/popcount_tune.py --playlists 1000000 --tracks 4096 \
        --rows 5000000 --configs 32x128x512 64x128x512 32x256x256
"""

from __future__ import annotations

import argparse
import os
import sys

# runnable as `python scripts/<name>.py` from anywhere: the repo root
# (not scripts/) is what must be importable
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import json
import subprocess

DEFAULT_CONFIGS = (
    "32x128x512",   # the shipped default
    "64x128x512",
    "128x128x512",
    "32x128x1024",
    "64x256x512",
    "8x128x512",
)

_WORKER = r"""
import json, statistics, sys, time
import numpy as np
import jax, jax.numpy as jnp
from kmlserver_tpu.data.synthetic import synthetic_baskets
from kmlserver_tpu.ops import encode, support
from kmlserver_tpu.ops import popcount as pc

n_playlists, n_tracks, target_rows = map(int, sys.argv[1:4])
variant = sys.argv[4]
check = sys.argv[5] == "1"
allow_interpret = sys.argv[6] == "1"

dev = jax.devices()[0]
interpret = dev.platform != "tpu"
if interpret and not allow_interpret:
    print("SKIP: not a TPU backend", file=sys.stderr)
    sys.exit(3)
_ti, _tj, _wk = pc.resolve_tiles()
print(f"device: {dev.platform} ({dev.device_kind}) tiles "
      f"{_ti}x{_tj}x{_wk}", file=sys.stderr, flush=True)

baskets = synthetic_baskets(
    n_playlists=n_playlists, n_tracks=n_tracks, target_rows=target_rows,
    seed=123)
kw = dict(n_playlists=baskets.n_playlists, n_tracks=baskets.n_tracks)
if variant == "mxu":
    # the blocked unpack-matmul impl: tiles are XLA's business, only
    # WORD_CHUNK (slab width) applies — pure XLA, never interpreted
    fn = lambda: pc.popcount_pair_counts(
        baskets.playlist_rows, baskets.track_ids, impl="mxu", **kw)
else:
    fn = lambda: pc.popcount_pair_counts(
        baskets.playlist_rows, baskets.track_ids, impl="vpu",
        interpret=interpret, variant=variant, **kw)
out = fn()
out.block_until_ready()  # compile
if check:
    pr, ti = jnp.asarray(baskets.playlist_rows), jnp.asarray(baskets.track_ids)
    dense = jax.jit(
        lambda a, b: support.pair_counts(encode.onehot_matrix(a, b, **kw))
    )(pr, ti)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(out))
    print("counts == dense: EXACT", file=sys.stderr, flush=True)

n_amort = 3 if interpret else 20
t0 = time.perf_counter()
jax.block_until_ready([fn() for _ in range(n_amort)])
ms = (time.perf_counter() - t0) / n_amort * 1e3

v_pad, w_pad = pc.padded_shape(baskets.n_tracks, baskets.n_playlists)
word_ops = v_pad * v_pad * w_pad
print(json.dumps({
    "ms": ms, "words_per_s": word_ops / (ms / 1e3),
    "v_pad": v_pad, "w_pad": w_pad,
}))
"""


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--playlists", type=int, default=2246)
    parser.add_argument("--tracks", type=int, default=2171)
    parser.add_argument("--rows", type=int, default=240249)
    parser.add_argument(
        "--configs", nargs="+", default=list(DEFAULT_CONFIGS),
        help="TIxTJxWORD_CHUNK triples",
    )
    parser.add_argument(
        "--variants", nargs="+", default=["mxu", "bcast", "row"],
        help="VPU kernel variants and/or 'mxu' (the unpack-matmul impl; "
        "only the WORD_CHUNK third of each config applies to it)",
    )
    parser.add_argument(
        "--allow-interpret", action="store_true",
        help="permit running off-TPU (measures the interpreter, not the chip)",
    )
    parser.add_argument("--timeout", type=float, default=600.0)
    args = parser.parse_args()

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = []
    mxu_chunks_seen: set[int] = set()
    for config in args.configs:
        ti, tj, wk = (int(x) for x in config.split("x"))
        for variant in args.variants:
            if variant == "mxu":
                # only WORD_CHUNK matters to the unpack-matmul impl;
                # don't re-measure it per tile pair
                if wk in mxu_chunks_seen:
                    continue
                mxu_chunks_seen.add(wk)
            env = os.environ.copy()
            env.update(
                KMLS_POPCOUNT_TILE_I=str(ti),
                KMLS_POPCOUNT_TILE_J=str(tj),
                KMLS_POPCOUNT_WORD_CHUNK=str(wk),
            )
            label = f"{config}/{variant}"
            try:
                proc = subprocess.run(
                    [sys.executable, "-c", _WORKER,
                     str(args.playlists), str(args.tracks), str(args.rows),
                     variant, "1", "1" if args.allow_interpret else "0"],
                    capture_output=True, text=True, timeout=args.timeout,
                    env=env, cwd=repo_root,
                )
            except subprocess.TimeoutExpired:
                print(f"{label}: TIMEOUT (backend hang?)", file=sys.stderr)
                continue
            for line in proc.stderr.splitlines():
                print(f"[{label}] {line}", file=sys.stderr)
            if proc.returncode == 3:
                print("not a TPU backend; pass --allow-interpret to sweep "
                      "the interpreter anyway", file=sys.stderr)
                return 3
            if proc.returncode != 0:
                print(f"{label}: FAILED (exit {proc.returncode})",
                      file=sys.stderr)
                continue
            r = json.loads(proc.stdout.strip().splitlines()[-1])
            r["config"] = config
            r["variant"] = variant
            results.append(r)
            print(
                f"{label}: {r['ms']:.2f}ms amortized, "
                f"{r['words_per_s'] / 1e9:.2f} Gwords/s",
                file=sys.stderr,
            )
            # checkpoint after every measured config: a harness that
            # kills a half-done sweep (a call's time limit) salvages the
            # last line instead of losing every measurement
            print(json.dumps(_summary(args, results, partial=True)),
                  flush=True)
    if not results:
        print(json.dumps({"error": "no config succeeded"}))
        return 1
    print(json.dumps(_summary(args, results, partial=False)))
    return 0


def _summary(args, results: list[dict], *, partial: bool) -> dict:
    best = min(results, key=lambda r: r["ms"])
    out = {
        "shape": f"{args.playlists}x{args.tracks}",
        "best_config": best["config"],
        "best_variant": best["variant"],
        "best_ms": round(best["ms"], 3),
        "best_words_per_s": round(best["words_per_s"]),
        "results": [
            {"config": r["config"], "variant": r["variant"],
             "ms": round(r["ms"], 3),
             "words_per_s": round(r["words_per_s"])}
            for r in results
        ],
    }
    if partial:
        out["partial"] = True
    return out


if __name__ == "__main__":
    sys.exit(main())
