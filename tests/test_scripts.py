"""Subprocess smoke tests for the artifact-producing scripts.

scale_demo.py and config4_tpu.py run UNATTENDED on scarce TPU windows
(bench.py's scale phase; the round's pool watcher) — a regression would
silently lose flagship artifacts, so their contract (exit code, JSON keys,
checkpoint lines) is pinned here at tiny CPU shapes.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, env_extra=None):
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    # deliberately NO PYTHONPATH: the scripts must be self-sufficient via
    # their own sys.path insert — a chip-tool call runs them as bare
    # `python scripts/<name>.py`
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", script), *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )


def _json_lines(stdout: str) -> list[dict]:
    out = []
    for line in stdout.strip().splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


def test_scale_demo_contract():
    proc = _run(
        "scale_demo.py", "--playlists", "4000", "--tracks", "1500",
        "--rows", "60000", "--min-support", "0.01",
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
    lines = _json_lines(proc.stdout)
    # checkpoints: at least the post-bitpack and post-auto lines (the
    # bench salvages the LAST parseable line on a phase timeout)
    assert len(lines) >= 2
    final = lines[-1]
    for key in ("mine_s", "rows_per_s", "frequent_items", "n_rules",
                "auto_mine_s", "auto_path", "platform"):
        assert key in final, key
    # every checkpoint carries the headline key
    assert all("mine_s" in line for line in lines)
    assert final["platform"] == "cpu"


def test_config4_runner_contract():
    proc = _run(
        "config4_tpu.py", "--playlists", "4000", "--tracks", "1500",
        "--rows", "60000", "--min-support", "0.01", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
    final = _json_lines(proc.stdout)[-1]
    for key in ("mine_cold_s", "mine_s", "prune_plus_mine_s", "n_rules",
                "count_path", "frequent_items"):
        assert key in final, key


def test_config4_runner_refuses_cpu_without_flag():
    proc = _run(
        "config4_tpu.py", "--playlists", "4000", "--tracks", "1500",
        "--rows", "60000",
    )
    assert proc.returncode == 3
    assert "not a TPU backend" in proc.stderr
