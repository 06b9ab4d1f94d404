"""The server child: the program's own entry point, and one line at exit.

Runs the module named on the command line exactly as ``python -m <module>``
would, in this process, and when it ends prints ``BENCH_DEVICE {...}``: the
devices as JAX reports them and each one's ``memory_stats()``. The parent
never touches JAX while this child holds the chip, and a process's peak
memory is only readable from inside it. Nothing of the program is patched.
"""

import json
import runpy
import sys


def _device_line() -> str:
    import jax

    devices = jax.local_devices()
    stats = []
    for d in devices:
        try:
            stats.append(d.memory_stats() or {})
        except Exception:  # a backend without memory_stats reports none
            stats.append({})
    return "BENCH_DEVICE " + json.dumps({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "peak_bytes": [int(s.get("peak_bytes_in_use", 0)) for s in stats],
        "limit_bytes": [int(s.get("bytes_limit", 0)) for s in stats],
    })


if __name__ == "__main__":
    module = sys.argv[1]
    sys.argv = [module] + sys.argv[2:]
    try:
        runpy.run_module(module, run_name="__main__", alter_sys=True)
    finally:
        print(_device_line(), flush=True)
