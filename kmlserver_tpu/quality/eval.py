"""Offline ranking evaluation — the measured half of the quality loop.

Nothing in PRs 1–13 measures whether the answers are any GOOD: the blend
weight is a knob nobody swept, and "serves fast" says nothing about
"serves well". This module is the offline evaluation harness the Google
ads-infra paper (PAPERS.md, arXiv:2501.10546) grounds as a first-class
production pipeline stage, and ALX (arXiv:2112.02194) is the precedent
for running TPU-batched factorization evaluation inside the training
loop rather than as an offline afterthought.

Design contract, in order of importance:

- **deterministic split** — leave-``n``-out per playlist, selected by a
  keyed blake2 hash over ``(salt, playlist row, track name)``: no RNG
  state, no dict order, no host dependence — two runs (or two ranks, or
  a checkpoint resume on a different machine) produce byte-identical
  splits. Playlists shorter than ``min_basket`` are not evaluated (a
  1-track basket has nothing to complete).
- **zero leakage by construction** — the evaluated models are trained
  on the TRAIN membership pairs only (the held-out pairs are removed
  before the miner/ALS ever see them) and :func:`holdout_split` asserts
  the two pair sets are disjoint before returning.
- **production kernels** — candidates come from the SAME jitted device
  kernels the serving engine dispatches (``ops.serve.recommend_batch``,
  ``ops.embed.embed_topk``) and the blend merge is the engine's own
  :func:`~kmlserver_tpu.serving.engine.blend_candidates` (one copy of
  the tie-order-critical math), so an offline number can never describe
  a ranking production would not serve.
- **deterministic report** — the ``eval`` phase payload carries no
  timestamps or tokens, so a checkpoint-resumed publication writes a
  byte-identical ``quality.report.json`` (the mining chaos suite's
  bit-identity bar covers it via the manifest sha256).

Metrics per serving mode (rules / embed / blend / popularity fallback):
``recall@k`` (hits over min(k, |targets|)), ``mrr`` (reciprocal rank of
the first hit within the top-k), and ``coverage`` (fraction of eval
playlists answered by the MODEL rather than the fallback).
"""

from __future__ import annotations

import dataclasses
import hashlib
import random
from typing import Any

import numpy as np

from ..config import MiningConfig
from ..mining.vocab import Baskets, Vocab

QUALITY_REPORT_VERSION = 1
# split identity salt: versioned so a future split change is a LOUD
# report-version bump, never a silent drift of the evaluated population
SPLIT_SALT = "kmls-eval-v1"
# seed cap per eval request — mirrors serving's KMLS_MAX_SEED_TRACKS
# default (the harness measures what a production request could carry)
EVAL_SEED_CAP = 128
# kernel batch rows per device call (power-of-two, serving-bucket style)
EVAL_BATCH = 64


def _pair_digest(row: int, name: str) -> int:
    """Stable per-(playlist, track) hold-out key — blake2, not
    ``hash()`` (process-salted), not RNG (order-dependent)."""
    h = hashlib.blake2b(
        f"{SPLIT_SALT}|{row}|{name}".encode("utf-8"), digest_size=8
    ).digest()
    return int.from_bytes(h, "big")


@dataclasses.dataclass
class HoldoutSplit:
    """One deterministic held-out split: train-side baskets plus the
    per-playlist (seeds, targets) the harness completes."""

    train: Baskets
    # aligned lists, one entry per evaluated playlist
    eval_rows: list[int]
    seed_names: list[list[str]]
    target_names: list[list[str]]
    n_eligible: int  # playlists long enough to evaluate (pre-cap)


def holdout_split(
    baskets: Baskets,
    n_holdout: int = 1,
    min_basket: int = 3,
    max_playlists: int = 0,
) -> HoldoutSplit:
    """Leave-``n_holdout``-out per playlist, deterministically.

    Within each eligible playlist (≥ ``min_basket`` tracks, floored so
    at least two seed tracks always remain) the ``n_holdout`` member
    tracks with the smallest pair digest are held out; the rest stay as
    seeds AND as training membership. ``max_playlists`` > 0 caps the
    evaluated set to the playlists with the smallest row digests (again
    hash-selected — a prefix slice would bias toward low pids)."""
    min_basket = max(min_basket, n_holdout + 2)
    rows = baskets.playlist_rows.astype(np.int64)
    tids = baskets.track_ids.astype(np.int64)
    order = np.lexsort((tids, rows))
    rows_s, tids_s = rows[order], tids[order]
    sizes = np.bincount(rows_s, minlength=baskets.n_playlists)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    eligible = np.flatnonzero(sizes >= min_basket)
    n_eligible = len(eligible)
    if max_playlists > 0 and len(eligible) > max_playlists:
        keyed = sorted(
            eligible.tolist(),
            key=lambda r: _pair_digest(r, "<row>"),
        )
        eligible = np.asarray(sorted(keyed[:max_playlists]), dtype=np.int64)
    names = baskets.vocab.names
    heldout_mask = np.zeros(len(rows_s), dtype=bool)
    eval_rows: list[int] = []
    seed_names: list[list[str]] = []
    target_names: list[list[str]] = []
    for r in eligible.tolist():
        lo = int(starts[r])
        hi = lo + int(sizes[r])
        member = tids_s[lo:hi]
        digests = [_pair_digest(r, names[int(t)]) for t in member]
        picked = sorted(range(len(member)), key=lambda i: digests[i])
        held = set(picked[:n_holdout])
        heldout_mask[lo + np.asarray(sorted(held), dtype=np.int64)] = True
        eval_rows.append(r)
        seed_names.append(
            [names[int(member[i])] for i in range(len(member)) if i not in held]
        )
        target_names.append([names[int(member[i])] for i in sorted(held)])
    keep = ~heldout_mask
    train = Baskets(
        playlist_rows=rows_s[keep].astype(np.int32),
        track_ids=tids_s[keep].astype(np.int32),
        n_playlists=baskets.n_playlists,
        vocab=baskets.vocab,
    )
    # leakage guard, asserted by construction: the held-out pairs and the
    # train pairs partition the membership set — an intersection would
    # mean the models train on the answers they are scored against
    v = np.int64(baskets.n_tracks)
    train_keys = set((rows_s[keep] * v + tids_s[keep]).tolist())
    held_keys = set((rows_s[heldout_mask] * v + tids_s[heldout_mask]).tolist())
    if train_keys & held_keys:
        raise AssertionError(
            "held-out pairs leaked into the train split — the split is "
            "broken, refusing to evaluate"
        )
    return HoldoutSplit(
        train=train,
        eval_rows=eval_rows,
        seed_names=seed_names,
        target_names=target_names,
        n_eligible=n_eligible,
    )


def _batched_candidates(kernel, tensor_args, seed_id_lists, k: int):
    """Run a jitted top-k kernel over padded (EVAL_BATCH, L) seed
    batches → per-request ``(top_ids, top_scores)`` host rows. One fixed
    shape per harness run, so the kernel compiles once."""
    import jax.numpy as jnp

    n = len(seed_id_lists)
    length = max(
        1, min(max((len(s) for s in seed_id_lists), default=1), EVAL_SEED_CAP)
    )
    out_ids = np.full((n, k), -1, dtype=np.int32)
    out_scores = np.zeros((n, k), dtype=np.float32)
    for lo in range(0, n, EVAL_BATCH):
        chunk = seed_id_lists[lo:lo + EVAL_BATCH]
        arr = np.full((EVAL_BATCH, length), -1, dtype=np.int32)
        for r, ids in enumerate(chunk):
            ids = ids[:length]
            arr[r, : len(ids)] = ids
        ids_d, scores_d = kernel(*tensor_args, jnp.asarray(arr), k_best=k)
        out_ids[lo:lo + len(chunk)] = np.asarray(ids_d)[: len(chunk)]
        out_scores[lo:lo + len(chunk)] = np.asarray(scores_d)[: len(chunk)]
    return out_ids, out_scores


def _rank_metrics(
    answer: list[str], targets: list[str], k: int
) -> tuple[float, float]:
    """→ (recall@k, reciprocal rank of the first hit in the top-k)."""
    target_set = set(targets)
    top = answer[:k]
    hits = sum(1 for name in top if name in target_set)
    recall = hits / max(min(k, len(target_set)), 1)
    rr = 0.0
    for rank, name in enumerate(top, start=1):
        if name in target_set:
            rr = 1.0 / rank
            break
    return recall, rr


def _fallback_answer(best_names: list[str], seeds: list[str], k: int) -> list[str]:
    """The popularity fallback, exactly as serving composes it: a
    stable-seeded sample over the popularity ranking (engine
    .static_recommendation's arithmetic, deadline path excluded)."""
    from ..serving.engine import stable_seed

    if not best_names:
        return []
    kk = min(k, len(best_names))
    rng = random.Random(stable_seed(seeds))
    return rng.sample(best_names, kk)


def run_eval_phase(
    cfg: MiningConfig,
    baskets: Baskets,
    mesh=None,
) -> dict[str, Any]:
    """The ``eval`` pipeline phase: split → train both model families on
    the train half → score every serving mode on basket completion →
    sweep the blend weight → the deterministic quality report (the
    phase's checkpoint payload AND the ``quality.report.json`` body)."""
    from ..mining import als as als_mod
    from ..mining.miner import mine
    from ..ops.embed import embed_topk, factor_table
    from ..ops.serve import recommend_batch
    from ..ops.support import min_count_for
    from ..serving.engine import blend_candidates
    from .sweep import DEFAULT_BLEND_WEIGHT, sweep_blend_weight

    k = max(1, cfg.eval_k)
    split = holdout_split(
        baskets,
        n_holdout=max(1, cfg.eval_holdout_n),
        max_playlists=cfg.eval_max_playlists,
    )
    n_eval = len(split.eval_rows)
    print(
        f"Eval split: {n_eval} playlists evaluated "
        f"({split.n_eligible} eligible), leave-{max(1, cfg.eval_holdout_n)}"
        f"-out, {len(split.train.playlist_rows)} train pairs"
    )
    report: dict[str, Any] = {
        "version": QUALITY_REPORT_VERSION,
        "split": {
            "salt": SPLIT_SALT,
            "holdout_n": max(1, cfg.eval_holdout_n),
            "n_eval_playlists": n_eval,
            "n_eligible_playlists": split.n_eligible,
            "n_train_pairs": int(len(split.train.playlist_rows)),
        },
        "k": k,
        "modes": {},
        "sweep": None,
        "measured_blend_weight": None,
    }
    if n_eval == 0:
        print("Eval: no playlist long enough to hold out — empty report")
        return report

    # ---- train both model families on the TRAIN split only ----
    result = mine(split.train, cfg, mesh=mesh)
    tensors = result.tensors
    rule_vocab = result.vocab_names
    rule_index = {n: i for i, n in enumerate(rule_vocab)}
    known = tensors.item_counts >= min_count_for(
        tensors.min_support, tensors.n_playlists
    )
    emb = None
    if cfg.embed_enabled:
        emb_payload = als_mod.train_embeddings(split.train, cfg, mesh=mesh)
        if emb_payload.get("item_factors") is not None:
            emb = {
                "factors": np.asarray(
                    emb_payload["item_factors"], dtype=np.float32
                ),
                "vocab": list(split.train.vocab.names),
            }
    # popularity ranking for the fallback mode: same tie order (count
    # desc, name asc) and same no-minimum percentile TRUNCATION as
    # production's most_frequent_tracks — a tiny vocabulary can
    # legitimately keep nothing, exactly like a production PVC. One
    # DELIBERATE divergence, for leakage-freedom: counts come from the
    # TRAIN membership pairs (deduplicated — Baskets dedups by
    # construction), not the full CSV's raw rows, so a held-out pair
    # can never vote for its own popularity.
    pop_counts = np.bincount(
        split.train.track_ids, minlength=split.train.n_tracks
    )
    pop_order = np.lexsort(
        (np.asarray(split.train.vocab.names, dtype=object), -pop_counts)
    )
    keep_n = int(len(pop_order) * cfg.top_tracks_save_percentile)
    best_names = [
        split.train.vocab.names[int(i)] for i in pop_order[:keep_n]
    ]

    # ---- candidates through the production kernels, batched ----
    import jax.numpy as jnp

    rule_seed_ids = [
        [
            rule_index[n]
            for n in seeds
            if n in rule_index and bool(known[rule_index[n]])
        ][:EVAL_SEED_CAP]
        for seeds in split.seed_names
    ]
    rule_args = (
        jnp.asarray(tensors.rule_ids), jnp.asarray(tensors.rule_confs),
    )
    r_ids, r_confs = _batched_candidates(
        recommend_batch, rule_args, rule_seed_ids, k
    )
    rule_pairs: list[list[tuple[str, float]]] = [
        [
            (rule_vocab[int(i)], float(c))
            for i, c in zip(r_ids[e], r_confs[e])
            if i >= 0
        ]
        for e in range(n_eval)
    ]
    emb_pairs: list[list[tuple[str, float]]] | None = None
    emb_seed_ids: list[list[int]] = []
    if emb is not None:
        emb_index = {n: i for i, n in enumerate(emb["vocab"])}
        emb_seed_ids = [
            [emb_index[n] for n in seeds if n in emb_index][:EVAL_SEED_CAP]
            for seeds in split.seed_names
        ]
        e_ids, e_sims = _batched_candidates(
            embed_topk, (factor_table(emb["factors"]),), emb_seed_ids, k
        )
        emb_pairs = [
            [
                (emb["vocab"][int(i)], float(s))
                for i, s in zip(e_ids[e], e_sims[e])
                if i >= 0
            ]
            for e in range(n_eval)
        ]

    # ---- per-mode composition (the engine's serving semantics) ----
    def compose(mode: str, weight: float, e: int) -> tuple[list[str], bool]:
        """→ (answer names, answered-by-model) for eval playlist ``e``,
        mirroring engine._compose_answer mode for mode."""
        rk = bool(rule_seed_ids[e])
        ek = emb_pairs is not None and bool(emb_seed_ids[e])
        seeds = split.seed_names[e]
        if mode == "popularity" or (not rk and not ek):
            return _fallback_answer(best_names, seeds, k), False
        if mode == "rules":
            if not rk:
                return _fallback_answer(best_names, seeds, k), False
            return [n for n, _ in rule_pairs[e]], True
        if mode == "embed":
            if not ek:
                return _fallback_answer(best_names, seeds, k), False
            return [n for n, _ in emb_pairs[e]], True
        # blend: union of both families (embed-only when the rules have
        # never seen the seeds — the cold-start path; rules-only when no
        # embedding candidates exist)
        if not ek:
            return [n for n, _ in rule_pairs[e]], True
        if not rk:
            return [n for n, _ in emb_pairs[e]], True
        return (
            blend_candidates(rule_pairs[e], emb_pairs[e], weight, k), True
        )

    def score_mode(mode: str, weight: float = DEFAULT_BLEND_WEIGHT) -> dict:
        recalls, rrs, covered = [], [], 0
        for e in range(n_eval):
            answer, by_model = compose(mode, weight, e)
            recall, rr = _rank_metrics(answer, split.target_names[e], k)
            recalls.append(recall)
            rrs.append(rr)
            covered += int(by_model and bool(answer))
        return {
            "recall_at_k": round(float(np.mean(recalls)), 6),
            "mrr": round(float(np.mean(rrs)), 6),
            "coverage": round(covered / n_eval, 6),
        }

    report["modes"]["rules"] = score_mode("rules")
    report["modes"]["popularity"] = score_mode("popularity")
    if emb_pairs is not None:
        report["modes"]["embed"] = score_mode("embed")
        report["modes"]["blend"] = score_mode("blend")
        sweep = sweep_blend_weight(
            lambda w, e: compose("blend", w, e)[0],
            split.target_names, n_eval, k,
        )
        report["sweep"] = sweep
        report["measured_blend_weight"] = sweep["best_weight"]
    else:
        # no second model family this generation: blend degenerates to
        # rules-only and there is no weight to measure — the serving
        # side's `measured` mode falls back to its default, loudly
        report["modes"]["blend"] = report["modes"]["rules"]
    for mode in ("rules", "embed", "blend", "popularity"):
        stats = report["modes"].get(mode)
        if stats:
            print(
                f"Eval {mode}: recall@{k} {stats['recall_at_k']:.4f}, "
                f"MRR {stats['mrr']:.4f}, coverage {stats['coverage']:.3f}"
            )
    if report["measured_blend_weight"] is not None:
        print(
            f"Eval blend sweep: measured optimum w="
            f"{report['measured_blend_weight']} "
            f"(recall@{k} {report['sweep']['best_recall_at_k']:.4f})"
        )
    return report


__all__ = [
    "EVAL_SEED_CAP",
    "HoldoutSplit",
    "QUALITY_REPORT_VERSION",
    "SPLIT_SALT",
    "holdout_split",
    "run_eval_phase",
]
