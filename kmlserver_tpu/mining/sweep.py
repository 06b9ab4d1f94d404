"""Support-sweep experiment harness — the reference's disabled
``experiment_supports`` loop resurrected as a first-class benchmark driver
(reference: machine-learning/main.py:450-473; its output chart — coverage vs
min_support vs runtime — appears in the project report p.5).

Reference behavior: loop min_support over ``arange(0.03, 0.2, 0.0025)``,
re-run rule generation per support, record (missing songs, duration) to
``fp_growth_experiment_results.csv``.

TPU-first improvement: the pair-count matrix does not depend on min_support,
so it's computed ONCE and only the (cheap, device-side) threshold + top-k
emission re-runs per support point — turning the reference's
full-re-mine-per-point sweep into one matmul plus N emissions. Both phases
are timed separately and recorded honestly.

Run: ``python -m kmlserver_tpu.mining.sweep`` (env: BASE_DIR/DATASETS_DIR
as the job, plus KMLS_SWEEP_START/STOP/STEP).
"""

from __future__ import annotations

import os
import sys
import time

import jax
import numpy as np

from ..config import BASE_INDEX, MiningConfig
from ..data.csv import read_tracks
from ..io import registry
from ..io.artifacts import atomic_write_text
from ..ops import rules as rules_mod
from .miner import (
    native_cpu_eligible, native_pair_counts, pair_count_fn, prune_infrequent,
)
from .vocab import build_baskets

RESULTS_FILE = "fp_growth_experiment_results.csv"


def run_sweep(
    cfg: MiningConfig,
    supports: np.ndarray,
    dataset: str | None = None,
    mesh=None,
) -> list[dict]:
    """→ one record per support point:
    ``{min_support, missing_songs, frequent_items, duration_s}``.

    With ``mesh``, the count-once phase runs sharded (the same
    ``pair_count_fn`` dispatch the miner uses: dense dp×tp or dp-sharded
    bitset slabs); the per-point emissions reuse the replicated counts."""
    if dataset is None:
        datasets = registry.get_dataset_list(cfg)
        index = registry.get_next_run_index(cfg, datasets)
        dataset = datasets[index - BASE_INDEX]
    table = read_tracks(dataset, cfg.sample_ratio)
    baskets = build_baskets(table)
    n_total = baskets.n_tracks

    # resolved before the timer: may trigger the one-time native build
    use_native = native_cpu_eligible(cfg, mesh)

    t0 = time.perf_counter()
    # pruning must use the SMALLEST support in the sweep to stay exact for
    # every point
    mined_baskets = baskets
    if baskets.n_tracks > cfg.prune_vocab_threshold:
        from ..ops.support import min_count_for

        mined_baskets, _ = prune_infrequent(
            baskets, min_count_for(float(supports.min()), baskets.n_playlists)
        )
    if use_native:
        # the miner's native CPU fallback, via its own gate + call helpers
        counts = native_pair_counts(mined_baskets)
        emit = rules_mod.mine_rules_from_counts_np
    else:
        counts, _, _ = pair_count_fn(
            mined_baskets, mesh,
            bitpack_threshold_elems=cfg.bitpack_threshold_elems,
            sharded_impl=cfg.sharded_impl,
            hbm_budget_bytes=cfg.hbm_budget_bytes,
        )
        jax.block_until_ready(counts)
        emit = rules_mod.mine_rules_from_counts
    count_s = time.perf_counter() - t0
    print(f"pair counts once: {count_s:.3f}s (shared across the sweep)")

    records = []
    for s in supports:
        t0 = time.perf_counter()
        tensors = emit(
            counts,
            n_playlists=mined_baskets.n_playlists,
            min_support=float(s),
            k_max=cfg.k_max_consequents,
            mode=cfg.confidence_mode,
            min_confidence=cfg.min_confidence,
            n_total_songs=n_total,
        )
        duration = time.perf_counter() - t0
        records.append(
            {
                # full precision: rounding here would change min_count_for
                # at exact-threshold points (rounded only for CSV display)
                "min_support": float(s),
                "missing_songs": tensors.n_songs_missing,
                "frequent_items": tensors.n_frequent_items,
                "duration_s": round(duration, 6),
            }
        )
        print(
            f"min_support {s:.4f}: missing {tensors.n_songs_missing}, "
            f"emission {duration * 1e3:.1f}ms"
        )
    return records


DENSITY_GRID = (0.05, 0.01, 0.002, 0.0005, 0.00005)
DENSITY_SHAPES = ((4000, 1000), (20000, 2000), (80000, 2500))


def run_density_sweep(
    densities=DENSITY_GRID,
    shapes=DENSITY_SHAPES,
    *,
    seed: int = 123,
    max_elems: int | None = None,
    max_rows: int = 4_000_000,
    dense_max_elems: int = 1 << 25,
    sparse_max_events: int = 150_000_000,
    repeat: int = 1,
) -> list[dict]:
    """The DENSITY axis of the sweep (ISSUE 13): time all three count
    families — dense MXU contraction, bit-packed unpack-matmul, sparse
    CSR×bitpacked hybrid — on synthetic workloads across a
    (density, shape) grid, verify the counts bit-identical per point,
    and record per-path wall clock. One record per measured point:

    ``{density, elems, shape, rows, dense_s, bitpack_s, sparse_s,
    identical, winner}``

    This IS the measurement that populates the dispatch lookup table
    (``mining/dispatch.table_from_records``): the bench's
    ``scale_sparse`` phase runs it on the live backend and banks the
    result, and the packaged ``dispatch_table.json`` carries the last
    banked sweep. Timings exclude compile (one warm pass per jitted
    path); best-of-``repeat`` keeps a neighbor's noise out of a cell."""
    import jax.numpy as jnp

    from ..data.synthetic import synthetic_baskets
    from ..ops import encode as encode_mod
    from ..ops import popcount as pc
    from ..ops import sparse as sparse_mod
    from ..ops import support as support_mod

    records = []
    for n_playlists, n_tracks in shapes:
        elems = n_playlists * n_tracks
        if max_elems is not None and elems > max_elems:
            continue
        for density in densities:
            target = int(density * elems)
            if target < 16 or target > max_rows:
                continue
            baskets = synthetic_baskets(
                n_playlists=n_playlists, n_tracks=n_tracks,
                target_rows=target, seed=seed,
            )
            rows = len(baskets.playlist_rows)
            results: dict[str, np.ndarray] = {}
            timings: dict[str, float | None] = {
                "dense": None, "bitpack": None, "sparse": None,
            }

            def best_of(fn):
                best = None
                out = None
                for _ in range(max(repeat, 1)):
                    t0 = time.perf_counter()
                    out = fn()
                    dt = time.perf_counter() - t0
                    best = dt if best is None else min(best, dt)
                return out, best

            def run_dense():
                x = encode_mod.onehot_matrix(
                    jnp.asarray(baskets.playlist_rows),
                    jnp.asarray(baskets.track_ids),
                    n_playlists=n_playlists, n_tracks=n_tracks,
                )
                return np.asarray(
                    jax.block_until_ready(support_mod.pair_counts(x))
                )

            def run_bitpack():
                return np.asarray(
                    jax.block_until_ready(
                        pc.popcount_pair_counts(
                            baskets.playlist_rows, baskets.track_ids,
                            n_playlists=n_playlists, n_tracks=n_tracks,
                            impl="mxu",
                        )
                    )
                )

            def run_sparse():
                return sparse_mod.sparse_pair_counts_np(
                    baskets.playlist_rows, baskets.track_ids,
                    n_playlists=n_playlists, n_tracks=n_tracks,
                )

            # per-path guards keep the grid affordable — an unmeasured
            # path is an HONEST None (the table lookup then can't pick
            # it for the cell), never a silently extrapolated number
            if elems <= dense_max_elems:
                run_dense()  # warm: compile is env prep, not counting
                results["dense"], timings["dense"] = best_of(run_dense)
            run_bitpack()
            results["bitpack"], timings["bitpack"] = best_of(run_bitpack)
            events, _ = sparse_mod.pair_event_count(
                baskets.playlist_rows, n_playlists
            )
            if events <= sparse_max_events:
                results["sparse"], timings["sparse"] = best_of(run_sparse)

            ref_name = next(k for k in ("dense", "bitpack") if k in results)
            identical = all(
                np.array_equal(results[ref_name], other)
                for other in results.values()
            )
            timed = {k: v for k, v in timings.items() if v is not None}
            winner = min(timed, key=timed.get)
            records.append(
                {
                    "density": rows / max(elems, 1),
                    "elems": elems,
                    "shape": f"{n_playlists}x{n_tracks}",
                    "rows": rows,
                    **{
                        f"{k}_s": (None if v is None else round(v, 5))
                        for k, v in timings.items()
                    },
                    "identical": identical,
                    "winner": winner,
                }
            )
            print(
                f"density {rows / max(elems, 1):.5f} {n_playlists}x"
                f"{n_tracks}: "
                + " ".join(
                    f"{k} {v:.3f}s" for k, v in timed.items()
                )
                + f" -> {winner} (identical={identical})"
            )
    return records


def write_results_csv(cfg: MiningConfig, records: list[dict]) -> str:
    path = os.path.join(cfg.base_dir, RESULTS_FILE)
    header = "min_support,missing_songs,frequent_items,duration_s"
    lines = [header] + [
        f'{round(r["min_support"], 6)},{r["missing_songs"]},'
        f'{r["frequent_items"]},{r["duration_s"]}'
        for r in records
    ]
    atomic_write_text(path, "\n".join(lines) + "\n")
    return path


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "density":
        # the density axis (ISSUE 13): measure the three count families
        # across the (density, shape) grid and bank the winners into a
        # measured dispatch table — `python -m kmlserver_tpu.mining.sweep
        # density [table_out.json]` (default: the packaged table the
        # dispatcher consults).
        import socket

        from . import dispatch as dispatch_mod

        records = run_density_sweep()
        dev = jax.devices()[0]
        table = dispatch_mod.table_from_records(
            records, jax.default_backend(),
            measured_on=f"{socket.gethostname()}/{dev.device_kind}",
            banked_at=time.time(),
            base=dispatch_mod.load_table(),
        )
        out = (
            sys.argv[2] if len(sys.argv) > 2
            else dispatch_mod.builtin_table_path()
        )
        dispatch_mod.save_table(out, table)
        print(
            f"wrote measured dispatch table ({len(records)} points, "
            f"backend {jax.default_backend()}) to {out}"
        )
        return 0
    cfg = MiningConfig.from_env()
    start = float(os.getenv("KMLS_SWEEP_START", "0.03"))
    stop = float(os.getenv("KMLS_SWEEP_STOP", "0.2"))
    step = float(os.getenv("KMLS_SWEEP_STEP", "0.0025"))
    supports = np.arange(start, stop, step)  # reference grid (main.py:452)
    # the sweep honors the same KMLS_MESH_SHAPE contract as the mining job,
    # including multi-host bootstrap: under a distributed runtime
    # KMLS_MESH_SHAPE=auto must build the hybrid DCN×ICI mesh, not a flat
    # local-device one
    from ..parallel.distributed import maybe_initialize, resolve_mesh

    distributed = maybe_initialize()
    records = run_sweep(
        cfg, supports,
        mesh=resolve_mesh(cfg.mesh_shape, distributed=distributed),
    )
    path = write_results_csv(cfg, records)
    print(f"wrote {len(records)} sweep points to {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
