"""Container entrypoint for the batch mining job.

Run as ``python -m kmlserver_tpu.mining.job`` — the rebuild's equivalent of
the reference job image's ``CMD uv run main.py``
(reference: machine-learning/Dockerfile:10, machine-learning/main.py:421-484).
Configured entirely by environment variables (kubernetes/job.yaml contract).

Exit-code contract (kubernetes/job.yaml podFailurePolicy binds it):

- ``0``  — success (the reference's ``sys.exit(0)``, main.py:484).
- ``64`` (EXIT_FATAL_CONFIG) — the job can NEVER succeed as configured:
  bad env (rank >= world size, malformed mesh shape), no datasets on the
  PVC, invalid dataset content. Retrying burns TPU quota for the same
  failure, so the Job's podFailurePolicy fails the whole Job on it.
- ``75`` (EXIT_RESUMABLE, EX_TEMPFAIL) — transient abort: an injected
  preemption-style crash, the publication lease held/lost to another
  writer, or the PVC out of space even after reclamation
  (``StorageExhaustedError`` / ENOSPC — retention frees space, then a
  retry resumes). A retry resumes from the phase checkpoint; podFailurePolicy
  Ignores it (does not count against backoffLimit — a preempted pod is
  not a crashing pod).
- ``76`` (EXIT_RANK_DEAD) — the dead-rank watchdog bounded a multi-host
  hang (peer heartbeats stale, or a collective blocked past
  KMLS_RANK_TIMEOUT_S). Also resumable: the replacement gang restarts
  from the checkpoint.
- anything else (``1``) — an unclassified crash; counted against
  ``backoffLimit`` as usual.
"""

from __future__ import annotations

import sys
import traceback

from ..config import MiningConfig
from .pipeline import run_mining_job

EXIT_OK = 0
EXIT_FATAL_CONFIG = 64  # EX_USAGE: retrying cannot help
EXIT_RESUMABLE = 75  # EX_TEMPFAIL: retry resumes from the checkpoint
EXIT_RANK_DEAD = 76  # EX_PROTOCOL: watchdog-bounded multi-host hang

# the codes a k8s retry can make progress on (job.yaml podFailurePolicy)
RETRYABLE_EXIT_CODES = (EXIT_RESUMABLE, EXIT_RANK_DEAD)


def classify_exception(exc: BaseException) -> int:
    """Map an abort to the exit-code contract above. The ONE policy
    deciding what k8s should retry."""
    import errno

    from .. import faults
    from ..io.artifacts import (
        LeaseHeldError,
        LeaseLostError,
        StorageExhaustedError,
    )
    from .vocab import DuplicateArtistURIError

    if isinstance(exc, faults.FaultInjected):
        return EXIT_RESUMABLE  # the chaos stand-in for a preemption
    if isinstance(exc, (LeaseHeldError, LeaseLostError)):
        # another writer is live (or superseded us): back off and retry —
        # by then the holder has finished or its lease expired
        return EXIT_RESUMABLE
    if isinstance(exc, StorageExhaustedError) or (
        isinstance(exc, OSError) and exc.errno == errno.ENOSPC
    ):
        # disk full is an OPERATOR condition, not a config bug: reclaim/
        # retention frees space and a retry resumes from the checkpoint.
        # Must precede the FileNotFoundError branch — both are OSErrors.
        return EXIT_RESUMABLE
    if isinstance(exc, (DuplicateArtistURIError, ValueError, FileNotFoundError)):
        # bad config/env/data: the same inputs fail the same way forever
        return EXIT_FATAL_CONFIG
    return 1


def main() -> int:
    # join the multi-host runtime when configured (no-op single-process);
    # must precede the first device access
    from ..parallel.distributed import (
        RankWatchdog,
        distributed_env,
        maybe_initialize,
    )

    watchdog = None
    try:
        distributed = maybe_initialize()
        cfg = MiningConfig.from_env()
        # persistent XLA compilation cache (utils/jaxcache.py): the
        # pseudo-CronJob re-runs this container every ~20 min and would
        # otherwise re-pay every jit compile each run. Before any jit.
        from ..utils.jaxcache import enable_compilation_cache

        enable_compilation_cache()
        from ..parallel.distributed import resolve_mesh
        from ..parallel.mesh import describe_devices

        print(f"Devices: {describe_devices()}", flush=True)

        if distributed and cfg.rank_timeout_s > 0:
            from .checkpoint import heartbeat_dir

            _, num_processes, process_id = distributed_env()
            watchdog = RankWatchdog(
                heartbeat_dir(cfg),
                rank=process_id,
                num_processes=num_processes,
                heartbeat_interval_s=cfg.rank_heartbeat_interval_s,
                timeout_s=cfg.rank_timeout_s,
                collective_timeout_s=cfg.collective_timeout_s or None,
                exit_code=EXIT_RANK_DEAD,
            )
            watchdog.start()

        run_mining_job(
            cfg,
            mesh=resolve_mesh(cfg.mesh_shape, distributed=distributed),
            watchdog=watchdog,
        )
        return EXIT_OK
    except Exception as exc:
        code = classify_exception(exc)
        traceback.print_exc()
        kind = "resumable" if code in RETRYABLE_EXIT_CODES else (
            "fatal-config" if code == EXIT_FATAL_CONFIG else "unclassified"
        )
        print(f"Job aborted ({kind}): exiting {code}", flush=True)
        return code
    finally:
        if watchdog is not None:
            watchdog.stop()


if __name__ == "__main__":
    sys.exit(main())
