"""Device-mesh construction.

The reference has no collective backend at all — its only "parallelism" is
Kubernetes replica scaling, and its inter-process bus is a shared filesystem
(reference: kubernetes/deployment.yaml:10, kubernetes/pvc.yaml:10-11;
SURVEY.md §2.4). The rebuild's mining compute shards over a 2-D
``(dp, tp)`` mesh instead:

- ``dp`` — data parallelism over the *transaction* (playlist) axis; partial
  pair-count matrices are combined with ``psum`` over ICI;
- ``tp`` — tensor parallelism over the *item* (track vocabulary) axis for
  large vocabularies; pair-count blocks are exchanged with ``all_gather`` or
  a ``ppermute`` ring.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_DP = "dp"
AXIS_TP = "tp"


def parse_mesh_shape(shape: str) -> tuple[int, int]:
    """Parse ``"4x2"`` → ``(4, 2)`` = (dp, tp)."""
    parts = shape.lower().split("x")
    if len(parts) != 2:
        raise ValueError(f"mesh shape must be 'DPxTP', got {shape!r}")
    return int(parts[0]), int(parts[1])


def make_mesh(
    shape: str | tuple[int, int] = "auto",
    devices: list | None = None,
) -> Mesh:
    """Build a ``(dp, tp)`` mesh. ``"auto"`` puts every device on ``dp``
    (transaction sharding scales furthest for the reference's workload
    profile: many baskets, modest vocab)."""
    devices = devices if devices is not None else jax.devices()
    if shape == "auto":
        dp, tp = len(devices), 1
    elif isinstance(shape, str):
        dp, tp = parse_mesh_shape(shape)
    else:
        dp, tp = shape
    if dp * tp != len(devices):
        raise ValueError(
            f"mesh {dp}x{tp} needs {dp * tp} devices, have {len(devices)}"
        )
    grid = np.asarray(devices).reshape(dp, tp)
    return Mesh(grid, (AXIS_DP, AXIS_TP))


def describe_devices() -> str:
    """``platform=… device_kind=… count=…`` as JAX reports them — the
    start-up line of both entry points, so a log (or ``chip_smoke.py``)
    can tell which device a process actually ran on."""
    devices = jax.devices()
    return (
        f"platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind} count={len(devices)}"
    )


def round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple
