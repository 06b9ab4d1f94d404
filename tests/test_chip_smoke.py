"""chip_smoke.py in tier-1: the same flow the chip runs, at a toy size on
virtual CPU devices — and the proof that without ``--tiny-cpu`` a machine
with no TPU gets an error, never a CPU run."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, env=None, cwd=REPO, script=SMOKE):
    return subprocess.run(
        [sys.executable, script, *args], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=600,
    )


def test_tiny_cpu_runs_every_leg(tmp_path):
    """Mine → serve → cache → kernels, plus the four-device legs, through
    the real entry points; every child's compile cache lands where
    JAX_COMPILATION_CACHE_DIR says."""
    cache = tmp_path / "placed-cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
    proc = _run("--tiny-cpu", env=env)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    # the verdict line is the driver's interface: exactly these keys
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    assert lines[-2].startswith("[summary] ")
    final = json.loads(lines[-2][lines[-2].index("{"):])
    assert final["tiny_cpu"] is True
    assert final["legs"] == [
        "mine", "serve", "cache", "serve_sharded", "kernels",
    ]
    assert final["count_path"] == "dense-fused"
    assert final["count_path_mesh"].startswith("sharded-")
    assert final["sources"]["fallback"] == 0
    assert final["compiles_after_warmup"] == 0
    assert list(final)[-1] == "claim" and final["claim"] is None
    # no timing from a CPU run, under any name
    assert "setup_seconds" not in final
    # every result line names the device the child reported
    for line in lines[:-1]:
        assert "platform=cpu device_kind=cpu count=4" in line, line
    # the cache went where the variable pointed, and the second server
    # start found everything there
    entries = final["cache_entries"]
    assert entries["after_second_start"] == entries["after_first_start"] > 0
    # (the sharded and kernel legs that follow add their own programs)
    assert len(os.listdir(cache)) >= entries["after_second_start"]
    assert f"cache_dir={cache}" in proc.stdout


def test_no_tpu_is_an_error_not_a_cpu_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line of any kind
    assert "platform=cpu" in proc.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script without the program must not pass."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_bytes(open(SMOKE, "rb").read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run("--tiny-cpu", env=env, cwd=str(tmp_path), script=str(lone))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
