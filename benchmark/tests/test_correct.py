"""``correct`` has to be able to come out false: the control (the reference
in the next precision down, in the program's place) and a fault planted
under the harness (an answer altered where the engine composes it), in the
cell that is here and in the rules-only one a later PR would bring.
Toy catalog, CPU; the readings at the cell's own size are in PERF.md.
"""

import json
import os

import pytest
from conftest import HERE, ROOT, run_cell, run_script

CELLS = [("serve-hybrid-steady", False), ("serve-rules-steady", True)]


@pytest.mark.parametrize("cell,later", CELLS)
def test_the_control_is_not_correct(cell, later, request):
    root = request.getfixturevalue("later_root") if later else ROOT
    proc = run_script(root, "control.py", ["--workload", cell, "--seeds", "3", "--seconds", "5"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is False
    over = [n for n, p in out["checked"].items() if p["value"] > p["limit"]]
    assert over, out


@pytest.mark.parametrize("cell,later", CELLS)
def test_an_altered_answer_is_not_correct(cell, later, request):
    """The whole of a run but its look for a chip, with every third answer's
    last track replaced where the engine composes it."""
    root = request.getfixturevalue("later_root") if later else ROOT
    result, stderr = run_cell(cell, root=root, extra_env={
        "BENCH_FAULT": "alter_answer", "PYTHONPATH": os.path.join(HERE, "fault_shim"),
    })
    assert result["attempted"] > result["failed"]
    assert result["correct"] is False
    checked = result["checked"]
    assert checked["answers_wrong"]["value"] + checked["order_gap"]["value"] > 0
    assert "[checked] correct False" in stderr
