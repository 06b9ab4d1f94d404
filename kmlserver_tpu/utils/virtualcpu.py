"""Force JAX onto a virtual N-device CPU platform — the one shared recipe.

Tier-1 tests and the multichip dry run validate multi-device code on XLA's
host platform with N virtual CPU devices: it needs no accelerator, and a
process that never touches the chip cannot take it from the one process
that is allowed to hold it. The pin must land BEFORE the first device
touch, via both environment (inherited by subprocesses, honored
pre-import) and ``jax.config`` (for a process that already imported jax).
Real chips are reached through ``chip_smoke.py`` instead.

Used by ``tests/conftest.py`` (session-wide, permanent) and
``__graft_entry__.dryrun_multichip`` (scoped, env restored afterwards).
Keep this the ONLY copy of the recipe.
"""

from __future__ import annotations

import os

_ENV_KEYS = ("JAX_PLATFORMS", "XLA_FLAGS")


def force_virtual_cpu(n_devices: int = 8) -> dict[str, str | None]:
    """Pin this process to a virtual ``n_devices``-device CPU platform.

    Safe to call before or after jax has been imported (already-initialized
    backends are torn down). Returns the prior values of the environment
    variables it mutated (``None`` = was unset) so a scoped caller can
    restore them with :func:`restore_env`; the in-process ``jax.config``
    pin is deliberately left in place — a process that has computed on the
    virtual mesh must not drift onto another backend mid-run.
    """
    prior: dict[str, str | None] = {k: os.environ.get(k) for k in _ENV_KEYS}
    os.environ["JAX_PLATFORMS"] = "cpu"
    # drop any stale device-count flag before appending ours: the in-process
    # count is pinned via jax_num_cpu_devices below, but subprocesses see
    # only the env — a leftover different count would win there
    flags = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax
    from jax.extend import backend as _jeb

    _jeb.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    return prior


def restore_env(prior: dict[str, str | None]) -> None:
    """Undo ``force_virtual_cpu``'s environment mutations (for callers whose
    process goes on to spawn children that must see the original env)."""
    for key, value in prior.items():
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = value
