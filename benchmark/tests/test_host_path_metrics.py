"""The host path's spans and the measured clock skew as per-layer metrics:
their files, and the toy smoke's traced line.

Run with ``pytest benchmark/tests``. No timing is asserted.
"""

import json
import os
import sys

from conftest import ROOT, run_cell

sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

SPAN_METRICS = {"fill_ms", "put_ms", "enqueue_ms", "handoff_ms"}
WITH_A_DEVICE_PLANE = {"idle_fetch_ms"}


def test_the_five_metric_files_load_and_name_their_readers():
    per_layer = {m["name"]: m for m in manifest.benchmark()["per_layer"]}
    for name in SPAN_METRICS | WITH_A_DEVICE_PLANE:
        with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as fh:
            spec = json.load(fh)
        entry = per_layer[name]
        assert spec["name"] == name and entry["layer"] == spec["layer"]
        assert entry["moves"] == spec["moves"] == "recommend_p50_ms"
        assert "workloads" not in entry  # every cell reports them
        assert spec["source"] == entry["source"] == (
            "device_trace" if name in WITH_A_DEVICE_PLANE else "program_counter"
        )
        manifest.resolve(spec["reader"]["kind"])


def test_smoke_traced_run_prints_the_host_path_spans():
    """The toy run on the CPU: the four span metrics are on the line; its
    capture has no device plane, so the measured-skew metric is left out."""
    result, stderr = run_cell(manifest.cells()[0], trace=1, seed=2147484444)
    got = result["metrics"]
    assert SPAN_METRICS <= set(got), sorted(got)
    assert not WITH_A_DEVICE_PLANE & set(got)
    assert "[clock]" in stderr and "no device plane in the capture" in stderr
    for name in SPAN_METRICS:
        assert got[name]["value"] >= 0.0, name
    # every batch has each of them, and they lie inside stage and dispatch
    inside = sum(got[name]["value"] for name in ("fill_ms", "put_ms", "enqueue_ms"))
    assert inside <= got["stage_ms"]["value"] + got["dispatch_ms"]["value"]
