"""The four-chip cell at its toy size on four host devices: the sharded
program runs (not ``resolve_layout``'s single-device fallback), and what
the layout records about itself reaches the traced run's line.
"""

import json
import os

from conftest import ROOT, run_cell

from benchmark import manifest, shard_readers

CELL = "serve-rules-sharded"


def test_the_toy_cell_serves_through_the_sharded_program():
    result, stderr = run_cell(CELL, trace=1)
    assert result["correct"] is True
    assert result["device"]["count"] == 4
    metrics = result["metrics"]
    # 2,999 tracks over four shards: no shard holds the seeds alone
    assert 25.0 <= metrics["shard_seed_share_max_pct"]["value"] < 60.0
    assert metrics["place_s"]["value"] > 0
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["unwarmed_in_window"]["value"] == 0
    with open(os.path.join(ROOT, ".bench_work", CELL, "server.log")) as fh:
        log = fh.read()
    assert "sharded layout: 2999 rule rows (+1 pad) across 4 shards" in log


def test_the_resident_share_is_the_fullest_shards_over_a_chips_memory():
    cell = manifest.Cell(CELL)
    spec = {m["name"]: s for m, s in cell.per_layer()}["shard_resident_pct"]
    scrape = {
        ("kmls_shard_resident_bytes", frozenset({("shard", str(i))})): b
        for i, b in enumerate((4.0e9, 4.8e9, 4.8e9, 1.0e9))
    }
    ctx = {"prom_start": {}, "prom_end": scrape, "peaks": cell.peaks}
    read = manifest.resolve(spec["reader"]["kind"])
    assert read(spec["reader"], dict(ctx, device_kind="TPU v5 lite")) == 30.0
    assert read(spec["reader"], dict(ctx, device_kind="cpu")) is None
    # a program from before the series existed: nothing to read, no error
    empty = dict(ctx, prom_end={}, device_kind="TPU v5 lite")
    assert read(spec["reader"], empty) is None
    assert shard_readers.read_share_max({"series": "kmls_shard_dispatch_total"}, empty) is None


def test_the_configuration_keeps_the_hybrid_cells_guarantees():
    with open(os.path.join(ROOT, "benchmark", "configs", "mpd-hybrid.json")) as fh:
        hybrid = json.load(fh)
    cfg = manifest.Cell(CELL).config
    assert cfg["guarantees"] == hybrid["guarantees"]
    # the ladder's budget is the hybrid cell's, in the file and at the server
    assert cfg["shed_queue_budget_ms"] == hybrid["shed_queue_budget_ms"]
    assert (cfg["server"]["env"]["KMLS_SHED_QUEUE_BUDGET_MS"]
            == hybrid["server"]["env"]["KMLS_SHED_QUEUE_BUDGET_MS"])
    # the padded tables are more than a chip holds; the one cut is the fill
    assert cfg["n_tracks"] * cfg["k_max"] * 8 > 16.9e9
    assert cfg["reduced"] == ["fill_divisor"]
    # a server that is not ready in time ends the run inside the run's 360 s
    assert cfg["server"]["ready_timeout_s"] < 360 - 51 - 100


def _toy_params(**over):
    cfg = manifest.Cell(CELL).config["generator"]
    return dict(cfg["params"], **cfg["smoke_params"], **over)


def test_a_program_that_publishes_too_slowly_is_refused_before_it_builds(monkeypatch):
    """By a measured probe through the program's own publication path, not
    by anything the program says of itself: a program from before PR 38
    takes 134 s to publish this catalog and 577 s a run (PERF.md section
    6) and would be cut at the run's limit."""
    import time

    import pytest

    from benchmark.generators import wide_catalog
    from kmlserver_tpu.io import artifacts

    params = _toy_params(publish_budget_s=0.5)
    wide_catalog.build(params, 1)  # the toy tables are written in milliseconds

    save = artifacts.save_rule_tensors

    def slow_save(*args, **kwargs):
        time.sleep(0.6)
        return save(*args, **kwargs)

    monkeypatch.setattr(artifacts, "save_rule_tensors", slow_save)
    with pytest.raises(SystemExit) as refused:
        wide_catalog.build(params, 1)
    assert refused.value.code not in (0, None) and "not run" in str(refused.value.code)


def test_rows_drawn_by_blocks_keep_the_generators_laws(monkeypatch):
    """One block or many, on however many threads: the same catalog again
    from the same seed, and every row as ``catalog.py`` states its laws."""
    import numpy as np

    from benchmark.generators import catalog, wide_catalog

    params = _toy_params()
    one = wide_catalog.build(params, 7)
    monkeypatch.setattr(wide_catalog, "BLOCK_RULES", 97)
    assert int(one.live.sum()) > 10 * wide_catalog.BLOCK_RULES
    many = wide_catalog.build(params, 7)
    monkeypatch.setattr(wide_catalog.os, "cpu_count", lambda: 3)
    again = wide_catalog.build(params, 7)
    assert np.array_equal(many.rule_ids, again.rule_ids)
    assert np.array_equal(many.rule_counts, again.rule_counts)
    assert not np.array_equal(many.rule_ids, wide_catalog.build(params, 8).rule_ids)

    k = int(params["k_max"])
    by_law = catalog.build(params, 7)
    for cat in (one, many):
        assert np.array_equal(cat.item_counts, by_law.item_counts)
        assert np.array_equal(cat.rank_to_id, by_law.rank_to_id)
        assert cat.names == by_law.names and np.array_equal(cat.known, by_law.known)
        want = np.minimum(k, cat.item_counts // int(params["fill_divisor"]))
        # duplicates and self-references dropped: a row may hold slightly fewer
        assert (cat.live <= want).all() and cat.live.sum() > 0.9 * want.sum()
        assert abs(int(cat.live.sum()) - int(by_law.live.sum())) < 0.02 * by_law.live.sum()
        slots = np.arange(k)[None, :]
        live = slots < cat.live[:, None]
        assert (cat.rule_ids[live] >= 0).all() and (cat.rule_ids[~live] == -1).all()
        assert (cat.rule_counts[~live] == 0).all()
        floor = np.maximum(1, np.ceil(cat.min_confidence * cat.item_counts))[:, None]
        counts = cat.rule_counts
        assert ((counts >= floor) | ~live).all() and (counts <= cat.item_counts[:, None]).all()
        assert (np.diff(counts, axis=1) <= 0).all()  # descending, then the zero padding
        rows = np.broadcast_to(np.arange(len(cat.names))[:, None], counts.shape)
        assert (cat.rule_ids != rows).all()
        pairs = (rows * len(cat.names) + cat.rule_ids)[live]
        assert len(np.unique(pairs)) == len(pairs)
