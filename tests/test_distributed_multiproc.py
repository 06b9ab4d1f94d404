"""Multi-process distributed runtime smoke test.

Round 1 covered only the env parsing and single-process mesh factoring of
``parallel/distributed.py``; the actual ``jax.distributed.initialize``
bootstrap (distributed.py maybe_initialize) and the rank-0 write gating in
the mining pipeline (mining/pipeline.py run_mining_job) were never executed
in multi-process form. This spawns TWO real processes — a localhost gRPC
coordinator, 2 virtual CPU devices each, a 4-device global mesh — and runs
the FULL mining job in both: every rank participates in the sharded
collectives, exactly one rank writes the shared-PVC artifacts, and the
distributed result must equal a single-process run bit-for-bit.
"""

from __future__ import annotations

import os
import pickle
import socket
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os, sys

rank, port, base = sys.argv[1], sys.argv[2], sys.argv[3]
# 2 virtual CPU devices per process -> 4 global; env must be set before jax
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["KMLS_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
os.environ["KMLS_NUM_PROCESSES"] = "2"
os.environ["KMLS_PROCESS_ID"] = rank

from kmlserver_tpu.parallel.distributed import maybe_initialize, make_hybrid_mesh

assert maybe_initialize() is True
import jax

assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, len(jax.devices())
assert len(jax.local_devices()) == 2

mesh = make_hybrid_mesh()
# tp must stay intra-process ("intra-host" = ICI analogue): every row of the
# device grid must live on one process
for row in mesh.devices:
    assert len({d.process_index for d in row}) == 1, "tp row spans processes"

from kmlserver_tpu.config import MiningConfig
from kmlserver_tpu.mining.pipeline import run_mining_job

cfg = MiningConfig(
    base_dir=base,
    datasets_dir=os.path.join(base, "datasets"),
    min_support=0.1,
    k_max_consequents=16,
)
summary = run_mining_job(cfg, mesh=mesh)
print(f"RANK {rank} WROTE {bool(summary.artifact_paths)} "
      f"TOKEN {bool(summary.token)} MISSING {summary.n_songs_missing}")

# config-4's distributed dependency: the BIT-PACKED pair-count path with the
# word axis dp-sharded across PROCESS boundaries (the DCN analogue), Pallas
# kernel per device (interpreted on CPU), partial counts psum-ed globally.
# Every rank must read back the full replicated counts, equal to a numpy
# ground truth.
import numpy as np
from kmlserver_tpu.data.synthetic import synthetic_baskets
from kmlserver_tpu.parallel.mesh import make_mesh
from kmlserver_tpu.parallel.support import sharded_bitpack_pair_counts

b = synthetic_baskets(n_playlists=50, n_tracks=30, target_rows=400, seed=11)
flat = make_mesh("auto")  # all 4 devices (2 per process) on dp
counts = sharded_bitpack_pair_counts(b, flat)
assert counts.is_fully_replicated, counts.sharding
x = np.zeros((b.n_playlists, b.n_tracks), np.int32)
x[b.playlist_rows, b.track_ids] = 1
np.testing.assert_array_equal(np.asarray(counts), x.T @ x)
print(f"RANK {rank} BITPACK EXACT")

# device-born workload across PROCESS boundaries: every device (two per
# process) generates only its own word slab of a Bernoulli-Zipf bitset,
# and the psum'd counts must equal brute force on the generated
# memberships — config 4's multi-host generation + counting story
from kmlserver_tpu.data.device_synthetic import device_synthetic_bitset
from kmlserver_tpu.ops.encode import unpack_bits
from kmlserver_tpu.parallel.support import counts_from_sharded_bitset

bitset, f_gen, _ = device_synthetic_bitset(
    64, 40, 400, min_count=1, seed=6, mesh=flat
)
gen_counts = counts_from_sharded_bitset(bitset, flat)
assert gen_counts.is_fully_replicated, gen_counts.sharding
# the slabs live on different PROCESSES — allgather before unpacking the
# ground truth (the counts themselves are already replicated)
from jax.sharding import NamedSharding, PartitionSpec as P

gathered = jax.jit(
    lambda a: a, out_shardings=NamedSharding(flat, P())
)(bitset)
# unpack_bits' n_tracks param slices the bit columns (= playlists here);
# int32 cast: a numpy int8 matmul would overflow
xg = np.asarray(unpack_bits(gathered, 64))[:f_gen].astype(np.int32)
np.testing.assert_array_equal(
    np.asarray(gen_counts)[:f_gen, :f_gen], xg @ xg.T
)
print(f"RANK {rank} DEVICEGEN EXACT")
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# Minimal 2-process jax.distributed CPU bootstrap — nothing but init and
# a process_count() check. If THIS can't run, the dead-rank watchdog test
# below can only ever time out on the environment, not on the watchdog.
_WORKER_PROBE = r"""
import os, sys

rank, port = sys.argv[1], sys.argv[2]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["KMLS_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
os.environ["KMLS_NUM_PROCESSES"] = "2"
os.environ["KMLS_PROCESS_ID"] = rank

from kmlserver_tpu.parallel.distributed import maybe_initialize

assert maybe_initialize() is True
import jax

assert jax.process_count() == 2, jax.process_count()
print(f"PROBE RANK {rank} OK", flush=True)
"""


def _scrubbed_env() -> dict[str, str]:
    env = os.environ.copy()
    for var in ("XLA_FLAGS", "JAX_PLATFORMS", "KMLS_COORDINATOR_ADDRESS",
                "KMLS_NUM_PROCESSES", "KMLS_PROCESS_ID",
                "KMLS_FAULT_RANK_DEAD"):
        env.pop(var, None)
    return env


_PROBE_RESULT: list[str | None] = []


def _distributed_cpu_init_blocker() -> str | None:
    """Probe (cached per session): spawn the minimal 2-process CPU
    bootstrap once and return None when it works, else a short reason
    naming what the ENVIRONMENT cannot do. Sandboxed CI runners without
    working localhost gRPC (or with a coordinator service that never
    comes up) fail here identically at every commit — skipping with the
    probe's reason keeps the watchdog test meaningful where it CAN run
    instead of reporting an environment defect as a watchdog defect."""
    if _PROBE_RESULT:
        return _PROBE_RESULT[0]
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER_PROBE, str(rank), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_scrubbed_env(), cwd=_REPO,
        )
        for rank in range(2)
    ]
    reason: str | None = None
    try:
        outs = [p.communicate(timeout=90)[0] for p in procs]
        for rank, (p, out) in enumerate(zip(procs, outs)):
            if p.returncode != 0 or f"PROBE RANK {rank} OK" not in out:
                tail = "\n".join(out.strip().splitlines()[-3:])
                reason = (
                    f"2-process jax.distributed CPU init failed on "
                    f"rank {rank} (rc={p.returncode}): {tail}"
                )
                break
    except subprocess.TimeoutExpired:
        reason = "2-process jax.distributed CPU init hung (>90s)"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate(timeout=30)
    _PROBE_RESULT.append(reason)
    return reason


# Dead-rank watchdog acceptance (ISSUE 4): rank 1 joins the distributed
# runtime, then goes silent — KMLS_FAULT_RANK_DEAD stops its heartbeats and
# it never enters the collective. Without the watchdog rank 0 would block in
# sync_global_devices FOREVER (the multi-host failure mode the reference's
# stack shares with any XLA collective). With it, rank 0 must exit
# EXIT_RANK_DEAD within the configured timeout (+ scheduling slack).
_WORKER_DEADRANK = r"""
import os, sys, time

rank, port, base = sys.argv[1], sys.argv[2], sys.argv[3]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["KMLS_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
os.environ["KMLS_NUM_PROCESSES"] = "2"
os.environ["KMLS_PROCESS_ID"] = rank
if rank == "1":
    os.environ["KMLS_FAULT_RANK_DEAD"] = "1"

from kmlserver_tpu.parallel.distributed import RankWatchdog, maybe_initialize

assert maybe_initialize() is True
import jax

# AFTER initialize: importing mining.job runs a jax computation during
# module import, and jax.distributed.initialize() refuses to run once
# any computation has executed
from kmlserver_tpu.mining.job import EXIT_RANK_DEAD

wd = RankWatchdog(
    os.path.join(base, "heartbeats"), rank=int(rank), num_processes=2,
    heartbeat_interval_s=0.25, timeout_s=6.0, collective_timeout_s=12.0,
    exit_code=EXIT_RANK_DEAD,
)
wd.start()
print(f"RANK {rank} WATCHDOG UP", flush=True)

if rank == "1":
    # dead rank: heartbeats silenced by the fault, never joins the
    # collective. Sleep far past rank 0's timeout — if rank 0's watchdog
    # fails, the TEST times out instead of passing.
    time.sleep(120)
    sys.exit(0)

from jax.experimental import multihost_utils

with wd.guard("sync"):
    # blocks forever on the silent peer; only the watchdog can end this
    multihost_utils.sync_global_devices("deadrank-test")
print("RANK 0 UNEXPECTEDLY PASSED THE BARRIER", flush=True)
sys.exit(1)
"""


@pytest.mark.slow
@pytest.mark.chaos
def test_dead_rank_aborts_within_timeout(tmp_path):
    import time as _time

    from kmlserver_tpu.mining.job import EXIT_RANK_DEAD

    blocker = _distributed_cpu_init_blocker()
    if blocker is not None:
        pytest.skip(f"distributed-cpu-init-unavailable: {blocker}")
    port = _free_port()
    env = _scrubbed_env()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER_DEADRANK,
             str(rank), str(port), str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=_REPO,
        )
        for rank in range(2)
    ]
    try:
        t0 = _time.monotonic()
        # rank 0 must die with the documented code, BOUNDED: its 6 s
        # timeout + distributed bootstrap + jax import slack
        out0, _ = procs[0].communicate(timeout=120)
        elapsed = _time.monotonic() - t0
        assert procs[0].returncode == EXIT_RANK_DEAD, out0
        assert "RANK WATCHDOG ABORT" in out0, out0
        assert elapsed < 110, f"abort took {elapsed:.0f}s — not bounded"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate(timeout=30)


@pytest.mark.slow
def test_two_process_mining_job(tmp_path):
    from kmlserver_tpu.config import MiningConfig
    from kmlserver_tpu.data.csv import write_tracks_csv
    from kmlserver_tpu.data.synthetic import synthetic_table
    from kmlserver_tpu.mining.pipeline import run_mining_job

    ds_dir = tmp_path / "dist" / "datasets"
    ds_dir.mkdir(parents=True)
    table = synthetic_table(
        n_playlists=60, n_tracks=40, target_rows=600, seed=5
    )
    write_tracks_csv(str(ds_dir / "2023_spotify_ds1.csv"), table)

    port = _free_port()
    env = os.environ.copy()
    # the workers configure their own jax env; scrub the pytest session's
    for var in ("XLA_FLAGS", "JAX_PLATFORMS", "KMLS_COORDINATOR_ADDRESS",
                "KMLS_NUM_PROCESSES", "KMLS_PROCESS_ID"):
        env.pop(var, None)
    base = str(tmp_path / "dist")
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(rank), str(port), base],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=_REPO,
        )
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"

    # exactly one writer (rank 0): duplicate history appends would corrupt
    # the rotation, concurrent artifact writes could tear the API's read
    wrote = [f"RANK {r} WROTE True" in outs[r] for r in range(2)]
    assert wrote == [True, False], outs
    assert "TOKEN True" in outs[0] and "TOKEN False" in outs[1]

    # the cross-process bitpack path verified exact on BOTH ranks
    for r in range(2):
        assert f"RANK {r} BITPACK EXACT" in outs[r], outs[r]
        assert f"RANK {r} DEVICEGEN EXACT" in outs[r], outs[r]

    # artifacts landed once, on the shared "PVC"
    pickles = tmp_path / "dist" / "pickles"
    assert (pickles / "recommendations.pickle").exists()
    assert (tmp_path / "dist" / "last_execution.txt").exists()

    # the distributed result equals a single-process mine of the same CSV
    with open(pickles / "recommendations.pickle", "rb") as f:
        dist_rules = pickle.load(f)
    solo_base = tmp_path / "solo"
    solo_ds = solo_base / "datasets"
    solo_ds.mkdir(parents=True)
    write_tracks_csv(str(solo_ds / "2023_spotify_ds1.csv"), table)
    solo = run_mining_job(
        MiningConfig(
            base_dir=str(solo_base), datasets_dir=str(solo_ds),
            min_support=0.1, k_max_consequents=16,
        )
    )
    with open(solo.artifact_paths["recommendations"], "rb") as f:
        solo_rules = pickle.load(f)
    assert dist_rules.keys() == solo_rules.keys()
    for key in dist_rules:
        assert dist_rules[key].keys() == solo_rules[key].keys()
        np.testing.assert_allclose(
            [dist_rules[key][c] for c in dist_rules[key]],
            [solo_rules[key][c] for c in dist_rules[key]],
        )
