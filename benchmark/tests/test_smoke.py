"""The harness end to end at a toy catalog on the CPU, and the manifest.

Run with ``pytest benchmark/tests``. No timing is asserted anywhere.
"""

import glob
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest
from conftest import LATER, ROOT, run_cell

sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark import trace as trace_mod  # noqa: E402
from benchmark.loadgen import Records  # noqa: E402
from benchmark.session import GRACE_S, NoAccelerator, Session, Window  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = ["correct", "attempted", "failed", "metrics", "device"]
CELLS = manifest.cells()
with open(os.path.join(LATER, "entries.json"), encoding="utf-8") as fh:
    LATER_CELLS = [w["name"] for w in json.load(fh)["workloads"]]


def check_line(cell: str, result: dict, stderr: str, bench: dict) -> None:
    assert list(result) == KEYS + ["checked"]  # the compared numbers come last
    assert result["correct"] is True
    # no capacity is asserted: a busy CPU may shed some of the toy traffic
    assert 0 <= result["failed"] < result["attempted"]
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(result["metrics"]) == want
    for name, pair in result["checked"].items():
        assert f"[checked] {name} " in stderr
        assert pair["value"] <= pair["limit"]
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.mark.parametrize("cell", CELLS)
def test_run_prints_the_contracts_line(cell):
    seed = 2147484111
    for kept in glob.glob(os.path.join(ROOT, ".bench_work", "generations", f"*-{seed}")):
        shutil.rmtree(kept)
    result, stderr = run_cell(cell, seed=seed)
    check_line(cell, result, stderr, manifest.benchmark())
    # the seed's second run finds its generation published
    assert "generation found published" not in stderr
    again, stderr = run_cell(cell, seed=seed)
    assert "generation found published" in stderr
    assert again["correct"] is True and again["attempted"] == result["attempted"]


@pytest.mark.parametrize("cell", LATER_CELLS)
def test_a_later_pr_adds_a_cell_as_files_only(cell, later_root):
    """A rules-only configuration, zipf-pool sharing with burst arrivals,
    and a closed loop: each is data over the laws that are here."""
    result, stderr = run_cell(cell, root=later_root, seconds=2)
    check_line(cell, result, stderr, manifest.benchmark())
    if "repeat" in cell:  # shared seed sets: the answer cache has to hit
        traced, _ = run_cell(cell, trace=1, root=later_root, seconds=2)
        assert traced["correct"] is True
        assert traced["metrics"]["cache_hit_pct"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS[:1])
def test_traced_run_reports_per_layer_metrics_and_breakdown(cell):
    result, _ = run_cell(cell, trace=1)
    assert list(result) == KEYS + ["breakdown", "checked"]
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    per_layer = {m["name"] for m in manifest.benchmark()["per_layer"]}
    assert result["metrics"] and set(result["metrics"]) <= per_layer
    assert "setup_s" not in result["metrics"]
    for key in ("device_ops", "idle_gaps"):
        assert len(result["breakdown"][key]) <= 10
    # that capture came from a CPU backend and has no device plane: only the
    # smoke run may read it; a real run's reduction refuses it
    capture = trace_mod.find_capture(
        os.path.join(ROOT, ".bench_work", cell, "profile")
    )
    assert trace_mod.reduce(capture, allow_host=True)["busy_s"] > 0
    with pytest.raises(trace_mod.EmptyCapture):
        trace_mod.reduce(capture)


@pytest.mark.parametrize("last_done,never,elapsed", [
    (9.5, False, 10.0),  # every answer inside the window: its length
    (10.4, False, 10.4),  # an answer after the close: the clock is read after it
    (9.5, True, 10.0 + GRACE_S),  # one never came: the whole wait counts
])
def test_served_rps_counts_every_answer_over_all_of_the_time(last_done, never, elapsed):
    n = 4
    rec = Records(
        due=np.array([1.0, 2.0, 3.0, 9.0]), sent=np.array([1.0, 2.0, 3.0, 9.0]),
        done=np.array([1.1, 2.1, 3.1, last_done]), status=np.full(n, 200),
        degraded=np.zeros(n, dtype=bool), bodies=[b"{}"] * n,
    )
    if never:
        rec.status[1], rec.done[1] = 0, np.nan
    win = Window(rec, [()] * n, 10.0, {}, {})
    assert win.failed == int(never) and win.never == int(never)
    assert win.end_to_end()["served_rps"] == pytest.approx((n - int(never)) / elapsed)


def test_without_an_accelerator_nothing_is_measured():
    ses = Session(CELLS[0], 7)
    ses.params.update(ses.cfg["generator"]["smoke_params"])  # toy sizes, real rules
    ses.env_extra.update(ses.cfg["server"]["smoke_env"], JAX_PLATFORMS="cpu")
    try:
        with pytest.raises(NoAccelerator):
            ses.start(10)
    finally:
        ses.close()


@pytest.mark.parametrize("cell", CELLS)
def test_an_open_loops_tail_has_ten_samples_beyond_it(cell):
    """The highest percentile a cell reports has ten requests of a window
    beyond it (the choosing-metrics guide): with five, whether one of them
    met a pause of the machine decides the tail."""
    loaded = manifest.Cell(cell)
    tails = [int(m.group(1)) for m in (
        re.fullmatch(r".*_p(\d+)_ms", e["name"]) for e in loaded.end_to_end()
    ) if m]
    if loaded.workload.get("closed_callers") or not tails:
        pytest.skip("no fixed rate, or no percentile among the cell's end-to-end metrics")
    n = float(loaded.workload["rate_rps"]) * loaded.bench["run_seconds"]
    assert n * (1.0 - max(tails) / 100.0) >= 10.0, (cell, n, max(tails))


def test_manifest_names_units_and_files():
    bench = manifest.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [c["name"] for c in bench["configs"]] + CELLS + [
        m["name"] for m in bench["end_to_end"] + bench["per_layer"]
    ]
    for n in names + [w["traffic"] for w in bench["workloads"]]:
        assert NAME.match(n), n
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and 0 < m["bound"] <= 0.1
    for c in bench["configs"]:
        assert len(c["source"]) <= 200 and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert json.load(open(os.path.join(ROOT, c["file"])))["reduced"] == c["reduced"]
    for cell in CELLS:
        loaded = manifest.Cell(cell)  # resolves configuration, traffic, workload
        assert isinstance(loaded.workload["rate_rps"], (int, float))
        assert len(loaded.entry["why"]) <= 200
        for entry, spec in loaded.per_layer():
            assert UNIT.match(entry["unit"]) and entry["moves"] in e2e
            for key in ("unit", "better", "source", "layer", "moves"):
                assert spec[key] == entry[key], (entry["name"], key)
            manifest.resolve(spec["reader"]["kind"])
