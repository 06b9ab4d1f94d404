"""Test harness: force JAX onto a virtual 8-device CPU platform.

Multi-chip sharding is tested without TPU hardware via XLA's host platform
with 8 virtual devices. The recipe lives in ONE place,
``kmlserver_tpu.utils.virtualcpu`` — conftest import is early enough for the
env half of it to beat the first backend initialization. Real chips are
reached through ``chip_smoke.py``, one process per chip.
"""

import os

from kmlserver_tpu.utils.virtualcpu import force_virtual_cpu

# session-wide and deliberately permanent: env mutations are inherited by
# any python subprocess a test spawns
force_virtual_cpu(8)

# hermetic against ambient config: a developer shell with the env-var
# contract exported (BASE_DIR=..., MIN_SUPPORT=...) must not leak into
# tests that construct configs from env/defaults
for _var in (
    "BASE_DIR", "DATASETS_DIR", "PICKLE_DIR", "PICKLES_FOLDER",
    "MIN_SUPPORT", "REGEX_FILENAME", "SAMPLE_RATIO", "K_BEST_TRACKS",
    "POLLING_WAIT_IN_MINUTES", "VERSION", "RECOMMENDATIONS_FILE",
    "BEST_TRACKS_FILE", "DATA_INVALIDATION_FILE",
):
    os.environ.pop(_var, None)
for _var in [v for v in os.environ if v.startswith("KMLS_")]:
    os.environ.pop(_var, None)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def tiny_baskets():
    """A hand-written transaction DB with known frequent pairs.

    5 playlists over 6 tracks (t0..t5):
      p0: t0 t1 t2
      p1: t0 t1
      p2: t0 t1 t3
      p3: t2 t3
      p4: t0 t4
    Pair counts: (t0,t1)=3, (t0,t2)=1, (t0,t3)=1, (t0,t4)=1,
                 (t1,t2)=1, (t1,t3)=1, (t2,t3)=2.
    t5 never appears.
    """
    return [
        ["t0", "t1", "t2"],
        ["t0", "t1"],
        ["t0", "t1", "t3"],
        ["t2", "t3"],
        ["t0", "t4"],
    ]
