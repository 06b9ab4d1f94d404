"""Synthetic published generation at a catalog's shape, made from a seed.

A configuration file names this module under ``generator.module`` and gives
its laws under ``generator.params``; nothing here knows a configuration's
name. ``build`` makes the arrays the plain reference keeps in memory;
``publish`` writes them through the program's own publication path
(``kmlserver_tpu.io.artifacts``) so the server loads them as it loads a
miner's output. The rule *contents* are synthetic (a real mine of 66M
memberships takes minutes on the host); the shapes are the catalog's.

Laws (all parameters come from the configuration file):

- popularity: Zipf-Mandelbrot, expected playlist count of the track at
  popularity rank r (0-based) is ``head_count * (1 + shift) / (r + 1 + shift)
  ** exponent``; ``item_count = max(1, floor(that))``.
- catalog order: track id = a seeded permutation of popularity rank, so a
  vocab shard holds tracks of every popularity.
- row fill: the track at rank r has ``min(k_max, item_count // fill_divisor)``
  live consequents; popular tracks fill all k_max, the tail holds few or none.
- consequents: drawn popularity-weighted (inverse CDF of the law above),
  duplicates and self-references dropped, so a row may hold slightly fewer.
- counts: slot j of a row holds ``floor(item_count * min(1, u * (j+1) **
  -decay * exp(jitter * n)))`` with ``u ~ U(top_conf_lo, top_conf_hi)`` per
  row and ``n ~ N(0,1)`` per slot, floored at ``ceil(min_confidence *
  item_count)``; each row is then sorted descending (trailing -1 padding).
- names: ``name_prefix`` + the id in ``name_digits`` decimal digits.
- factors: unit-norm float32 rows of a seeded standard normal (V, rank).
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
import time

import numpy as np


@dataclasses.dataclass
class Catalog:
    """What one seed publishes, as the reference holds it."""

    names: list[str]
    name_to_id: dict[str, int]
    rule_ids: np.ndarray  # int32 (V, K), -1 padded
    rule_counts: np.ndarray  # int32 (V, K), 0 padded
    item_counts: np.ndarray  # int32 (V,)
    live: np.ndarray  # int32 (V,) live consequents per row
    known: np.ndarray  # bool (V,) rule-key membership
    rank_to_id: np.ndarray  # int32 (V,) the catalog order
    pop_cdf: np.ndarray  # float64 (V,) by popularity rank
    n_playlists: int
    min_support: float
    mode: str
    min_confidence: float
    factors: np.ndarray | None  # float32 (V, R), unit rows

    def confs_of(self, rows: np.ndarray) -> np.ndarray:
        """float32 confidences of the given rule rows: the published
        count arithmetic (float64 division, then float32), row by row so
        that no (V, K) float array is ever made."""
        counts = self.rule_counts[rows].astype(np.float64)
        if self.mode == "support":
            return (counts / self.n_playlists).astype(np.float32)
        denom = np.maximum(self.item_counts[rows], 1)[:, None].astype(np.float64)
        return (counts / denom).astype(np.float32)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def popularity(params: dict) -> np.ndarray:
    """Expected playlist count by popularity rank (float64, descending)."""
    v = int(params["n_tracks"])
    shift = float(params["zipf_shift"])
    ranks = np.arange(1, v + 1, dtype=np.float64)
    return (
        float(params["head_count"]) * (1.0 + shift) ** float(params["zipf_exponent"])
        / (ranks + shift) ** float(params["zipf_exponent"])
    )


def min_count(min_support: float, n_playlists: int) -> int:
    """Smallest count c with c / n_playlists >= min_support."""
    c = int(math.ceil(min_support * n_playlists))
    while c > 1 and (c - 1) / n_playlists >= min_support:
        c -= 1
    return max(c, 1)


def build(params: dict, seed: int) -> Catalog:
    v = int(params["n_tracks"])
    k = int(params["k_max"])
    pop = popularity(params)
    counts_by_rank = np.maximum(1, np.floor(pop)).astype(np.int64)
    rank_to_id = _rng(seed, 1).permutation(v).astype(np.int32)
    item_counts = np.empty(v, dtype=np.int32)
    item_counts[rank_to_id] = counts_by_rank
    cdf = np.cumsum(pop)
    cdf /= cdf[-1]

    live_by_rank = np.minimum(k, counts_by_rank // int(params["fill_divisor"]))
    total = int(live_by_rank.sum())
    starts = np.cumsum(live_by_rank) - live_by_rank
    row_rank = np.repeat(np.arange(v, dtype=np.int64), live_by_rank)
    slot = np.arange(total, dtype=np.int64) - np.repeat(starts, live_by_rank)
    rng = _rng(seed, 2)
    cons_rank = np.minimum(np.searchsorted(cdf, rng.random(total)), v - 1)
    top = rng.uniform(params["top_conf_lo"], params["top_conf_hi"], size=v)
    raw = (
        top[row_rank]
        * (slot + 1.0) ** -float(params["slot_decay"])
        * np.exp(float(params["slot_jitter"]) * rng.standard_normal(total))
    )
    row_count = counts_by_rank[row_rank]
    floor_count = np.maximum(
        1, np.ceil(float(params["min_confidence"]) * row_count)
    )
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
    live_by_rank = np.bincount(row_rank, minlength=v).astype(np.int64)
    starts = np.cumsum(live_by_rank) - live_by_rank
    slot = np.arange(len(row_rank), dtype=np.int64) - np.repeat(starts, live_by_rank)

    rule_ids = np.full((v, k), -1, dtype=np.int32)
    rule_counts = np.zeros((v, k), dtype=np.int32)
    rows = rank_to_id[row_rank]
    rule_ids[rows, slot] = rank_to_id[cons_rank]
    rule_counts[rows, slot] = pair
    live = np.zeros(v, dtype=np.int32)
    live[rank_to_id] = live_by_rank

    n_playlists = int(params["n_playlists"])
    mode = str(params["confidence_mode"])
    min_support = float(params["min_support"])
    known = item_counts >= min_count(min_support, n_playlists)

    digits = int(params["name_digits"])
    prefix = str(params["name_prefix"])
    names = [f"{prefix}{i:0{digits}d}" for i in range(v)]
    factors = None
    rank = int(params.get("embedding_rank", 0))
    if rank > 0:
        factors = _rng(seed, 3).standard_normal((v, rank), dtype=np.float32)
        factors /= np.linalg.norm(factors, axis=1, keepdims=True)
    return Catalog(
        names=names,
        name_to_id={n: i for i, n in enumerate(names)},
        rule_ids=rule_ids, rule_counts=rule_counts,
        item_counts=item_counts, live=live, known=known,
        rank_to_id=rank_to_id, pop_cdf=cdf,
        n_playlists=n_playlists, min_support=min_support, mode=mode,
        min_confidence=float(params["min_confidence"]), factors=factors,
    )


def published(base_dir: str) -> bool:
    """Whether ``base_dir`` holds a finished generation: the token (written
    last) is there and every file of the manifest is on disk with the
    manifest's size and sha256."""
    from kmlserver_tpu.io import artifacts

    pickles = os.path.join(base_dir, "pickles")
    manifest = artifacts.load_manifest(pickles)
    if manifest is None or not os.path.isfile(os.path.join(base_dir, "last_execution.txt")):
        return False
    files = list(manifest["files"])
    if not files or not all(os.path.isfile(os.path.join(pickles, f)) for f in files):
        return False
    return not artifacts.verify_files(pickles, files)


def publish(cat: Catalog, params: dict, base_dir: str, log=print) -> dict:
    """Write ``cat`` as a published generation under ``base_dir`` through
    the program's publication functions → seconds spent per step."""
    from kmlserver_tpu.io import artifacts

    pickles = os.path.join(base_dir, "pickles")
    os.makedirs(pickles, exist_ok=True)
    spent: dict[str, float] = {}

    errors: list[BaseException] = []

    def timed(name, fn):
        t0 = time.monotonic()
        try:
            fn()
        except BaseException as exc:  # relayed to the caller after join
            errors.append(exc)
        spent[name] = time.monotonic() - t0

    rec = os.path.join(pickles, "recommendations.pickle")
    npz = artifacts.tensor_artifact_path(rec)
    files = ["best_tracks.pickle", os.path.basename(npz)]
    top_n = int(params["popular_tracks_kept"])
    order = np.lexsort((np.arange(len(cat.names)), -cat.item_counts))[:top_n]
    best = [
        {"track_name": cat.names[i], "count": int(cat.item_counts[i])}
        for i in order
    ]
    jobs = [
        ("best_tracks", lambda: artifacts.save_pickle(
            best, os.path.join(pickles, "best_tracks.pickle")
        )),
        ("rule_tensors", lambda: artifacts.save_rule_tensors(
            npz, vocab=cat.names, rule_ids=cat.rule_ids,
            rule_counts=cat.rule_counts, item_counts=cat.item_counts,
            n_playlists=cat.n_playlists, min_support=cat.min_support,
            mode=cat.mode, min_confidence=cat.min_confidence,
        )),
    ]
    emb = artifacts.embeddings_artifact_path(pickles)
    if cat.factors is not None:
        jobs.append(("embeddings", lambda: artifacts.save_embeddings(
            emb, vocab=cat.names, item_factors=cat.factors,
            rank=cat.factors.shape[1], iters=0, reg=0.0,
        )))
        files.append(os.path.basename(emb))
    else:
        artifacts.remove_embeddings(pickles)
    writers = [threading.Thread(target=timed, args=job) for job in jobs]
    # the two npz writers spend their time in zlib, which releases the
    # interpreter lock, so they run side by side
    for w in writers:
        w.start()
    for w in writers:
        w.join()
    if errors:
        raise errors[0]
    token = time.strftime("%Y-%m-%d %H:%M:%S") + f".{time.time_ns() % 10**9 // 1000:06d}"
    timed("manifest", lambda: artifacts.write_manifest(pickles, files, token=token))
    artifacts.atomic_write_text(os.path.join(base_dir, "last_execution.txt"), token)
    log(f"[publish] {base_dir}: " + ", ".join(f"{k} {s:.1f}s" for k, s in spent.items()))
    return spent

