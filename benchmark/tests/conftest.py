"""Shared by the benchmark's tests: run one cell at the toy sizes on the CPU,
in this checkout or in a copy of it that a later PR's files were added to."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
LATER = os.path.join(HERE, "later_pr")


def run_script(root: str, script: str, args: list[str], extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", script), *args, "--smoke"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )


def run_cell(cell: str, trace: int = 0, extra_env=None, root: str = ROOT, seconds: int = 3,
             seed: int = 2147483999):
    """→ (the result line, standard error)."""
    proc = run_script(root, "run.py", [
        "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace),
    ], extra_env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.fixture(scope="session")
def later_root(tmp_path_factory) -> str:
    """A checkout as a later PR would leave it: ``BENCHMARK.json`` with the
    entries of ``later_pr/entries.json`` appended and ``later_pr``'s files
    added under ``benchmark/``; no file that was there is edited."""
    root = str(tmp_path_factory.mktemp("later_pr"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "kmlserver_tpu"), os.path.join(root, "kmlserver_tpu"))
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    with open(os.path.join(LATER, "entries.json"), encoding="utf-8") as fh:
        for group, entries in json.load(fh).items():
            bench[group] += entries
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as fh:
        json.dump(bench, fh)
    for sub in ("configs", "workloads", "traffic"):
        for name in os.listdir(os.path.join(LATER, sub)):
            dst = os.path.join(root, "benchmark", sub, name)
            assert not os.path.exists(dst), dst
            shutil.copy(os.path.join(LATER, sub, name), dst)
    return root
