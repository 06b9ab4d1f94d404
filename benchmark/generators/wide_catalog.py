"""The catalog generator for a catalog whose padded rule tables run to tens
of GB: ``catalog.py``'s laws, its ``Catalog`` and its publication, with the
rows drawn a block at a time on every core of the host.

A run has 360 s at the driver and the catalog is built in every run.
``catalog.build`` draws all live rules in one pass on one core: 0.6 us a
rule and ten arrays of 8 bytes a rule, which at this catalog's 9.39M rows
allowed 25M live rules (1% of the slots) in a minute. Here ranks are cut
into blocks of about ``BLOCK_RULES`` live rules, each block drawn, sorted
and written by one thread (numpy releases the interpreter lock in all of
it): 169M rules in 18.8 s on the chip's 30-core host (PERF.md section 4).
The laws are ``catalog.py``'s, word for word; the draws differ (one random
stream a block), so the same seed gives another catalog than
``catalog.build`` does, and the same one on every host whatever its cores.

A program that publishes such tables too slowly for the run's limit is
refused before anything is built, by a measured probe: ``build`` writes
``PROBE_ROWS`` rows as full as the catalog's through the program's own
``save_rule_tensors``, and where the whole catalog's publication would by
that rate take longer than ``publish_budget_s`` the run ends at once with
a code of its own. On the chip's host the probe projects 50.4 s for PR
38's tree (the publication then took 51.0) and 189 s for its parent, which
besides takes 310 s to load this catalog, 577 s a run, and would be cut at
the limit (PERF.md section 6).
"""

from __future__ import annotations

import os
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import catalog
from .catalog import publish, published  # noqa: F401  (the generator's other two parts)

BLOCK_RULES = 1 << 20  # live rules a block: ~100 MB of temporaries a thread
PROBE_ROWS = 1 << 16  # x k_max 256 x 8 B: 134 MB through the publication path


def projected_publish_s(params: dict, live_by_rank: np.ndarray) -> float:
    """Seconds the program's ``save_rule_tensors`` would take for this
    catalog's tables, from the fastest of three timed writes of
    ``PROBE_ROWS`` rows (all of the catalog where it is smaller): every
    n-th rank's row, as full as the catalog's, its slots drawn uniformly."""
    from kmlserver_tpu.io import artifacts

    v, k = int(params["n_tracks"]), int(params["k_max"])
    rows = min(v, PROBE_ROWS)
    live = (np.arange(k)[None, :] < live_by_rank[:: v // rows][:rows, None])
    rng = np.random.default_rng(0)
    ids = np.where(live, rng.integers(0, v, live.shape), -1).astype(np.int32)
    counts = np.where(live, rng.integers(1, 64, live.shape), 0).astype(np.int32)
    names = [f"t{i}" for i in range(rows)]
    best = float("inf")
    with tempfile.TemporaryDirectory() as tmp:
        for _ in range(3):
            t = time.monotonic()
            artifacts.save_rule_tensors(
                os.path.join(tmp, "probe.npz"), vocab=names, rule_ids=ids,
                rule_counts=counts, item_counts=np.full(rows, 64, dtype=np.int32),
                n_playlists=int(params["n_playlists"]),
                min_support=float(params["min_support"]),
                mode=str(params["confidence_mode"]),
                min_confidence=float(params["min_confidence"]),
            )
            best = min(best, time.monotonic() - t)
    return best * v / rows


def build(params: dict, seed: int) -> catalog.Catalog:
    v, k = int(params["n_tracks"]), int(params["k_max"])
    pop = catalog.popularity(params)
    counts_by_rank = np.maximum(1, np.floor(pop)).astype(np.int64)
    live_by_rank = np.minimum(k, counts_by_rank // int(params["fill_divisor"]))
    budget = float(params["publish_budget_s"])
    projected = projected_publish_s(params, live_by_rank)
    if projected > budget:
        raise SystemExit(
            f"this program would take {projected:.0f} s to publish this catalog "
            f"(measured on {min(v, PROBE_ROWS)} of its rows), over the {budget:.0f} s "
            "that a run's time limit leaves for it "
            "(benchmark/generators/wide_catalog.py): not run"
        )
    rank_to_id = catalog._rng(seed, 1).permutation(v).astype(np.int32)
    item_counts = np.empty(v, dtype=np.int32)
    item_counts[rank_to_id] = counts_by_rank
    cdf = np.cumsum(pop)
    cdf /= cdf[-1]
    top = catalog._rng(seed, 4).uniform(params["top_conf_lo"], params["top_conf_hi"], size=v)

    # popularity descends, so the rows that hold rules are the first ranks;
    # a block ends where the running count of rules passes the next multiple
    ends = np.cumsum(live_by_rank)
    n_rows = int(np.count_nonzero(live_by_rank))
    cuts = np.searchsorted(ends, np.arange(BLOCK_RULES, int(ends[-1]), BLOCK_RULES), side="left") + 1
    bounds = np.unique(np.concatenate(([0], np.minimum(cuts, n_rows), [n_rows])))

    rule_ids = np.empty((v, k), dtype=np.int32)
    rule_counts = np.zeros((v, k), dtype=np.int32)
    live_out = np.zeros(v, dtype=np.int64)

    def draw_block(b: int, lo: int, hi: int) -> None:
        """Draw, dedupe, sort and write the rule rows of ranks ``lo:hi``."""
        want = live_by_rank[lo:hi]
        total = int(want.sum())
        rng = np.random.default_rng([int(seed), 2, b])
        row_rank = np.repeat(np.arange(lo, hi, dtype=np.int64), want)
        slot = np.arange(total, dtype=np.int64) - np.repeat(np.cumsum(want) - want, want)
        cons_rank = np.minimum(np.searchsorted(cdf, rng.random(total)), v - 1)
        raw = (
            top[row_rank]
            * (slot + 1.0) ** -float(params["slot_decay"])
            * np.exp(float(params["slot_jitter"]) * rng.standard_normal(total))
        )
        row_count = counts_by_rank[row_rank]
        floor_count = np.maximum(1, np.ceil(float(params["min_confidence"]) * row_count))
        pair = np.maximum(
            floor_count, np.floor(row_count * np.minimum(raw, 1.0))
        ).astype(np.int64)
        # drop duplicates of (row, consequent) and self-references
        key = row_rank * v + cons_rank
        order = np.argsort(key, kind="stable")
        keep = np.ones(total, dtype=bool)
        keep[order[1:]] = key[order[1:]] != key[order[:-1]]
        keep &= cons_rank != row_rank
        row_rank, cons_rank, pair = row_rank[keep], cons_rank[keep], pair[keep]
        # descending counts along each row; rows stay grouped
        order = np.lexsort((-pair, row_rank))
        row_rank, cons_rank, pair = row_rank[order], cons_rank[order], pair[order]
        live = np.bincount(row_rank - lo, minlength=hi - lo).astype(np.int64)
        slot = np.arange(len(row_rank), dtype=np.int64) - np.repeat(np.cumsum(live) - live, live)
        rows = rank_to_id[row_rank]
        rule_ids[rows, slot] = rank_to_id[cons_rank]
        rule_counts[rows, slot] = pair
        live_out[lo:hi] = live

    workers = os.cpu_count() or 1
    stripe = -(-v // (4 * workers))
    with ThreadPoolExecutor(max_workers=workers) as pool:
        # the -1 padding first (in id order, a stripe a task), the names on
        # this thread meanwhile; then the blocks, the names' index meanwhile
        fills = [
            pool.submit(rule_ids[lo:lo + stripe].fill, -1) for lo in range(0, v, stripe)
        ]
        digits = int(params["name_digits"])
        prefix = str(params["name_prefix"])
        names = [f"{prefix}{i:0{digits}d}" for i in range(v)]
        for f in fills:
            f.result()
        blocks = pool.map(
            draw_block, range(len(bounds) - 1), bounds[:-1].tolist(), bounds[1:].tolist()
        )
        name_to_id = {n: i for i, n in enumerate(names)}
        list(blocks)  # a block's error is raised here
    live = np.zeros(v, dtype=np.int32)
    live[rank_to_id] = live_out

    n_playlists = int(params["n_playlists"])
    min_support = float(params["min_support"])
    factors = None
    rank = int(params.get("embedding_rank", 0))
    if rank > 0:
        factors = catalog._rng(seed, 3).standard_normal((v, rank), dtype=np.float32)
        factors /= np.linalg.norm(factors, axis=1, keepdims=True)
    return catalog.Catalog(
        names=names, name_to_id=name_to_id,
        rule_ids=rule_ids, rule_counts=rule_counts,
        item_counts=item_counts, live=live,
        known=item_counts >= catalog.min_count(min_support, n_playlists),
        rank_to_id=rank_to_id, pop_cdf=cdf,
        n_playlists=n_playlists, min_support=min_support,
        mode=str(params["confidence_mode"]),
        min_confidence=float(params["min_confidence"]), factors=factors,
    )
