"""The plain reference and the comparison that decides ``correct``.

Numpy only; imports nothing of the program and reads nothing the program
wrote: it works from the catalog arrays the generator made from the seed.

Semantics (the reference system's, as the rebuild keeps them): a request's
rule score of a track is the MAX over its known seeds' rule rows of the
confidence; its similarity is the MAX over its seeds of the cosine to the
seed's factor row, seeds themselves excluded. Rules-only answers are the
top ``k_best`` by rule score. Blended answers take the top ``k_best`` of
each family, give a candidate ``(1-w) * conf`` if the rule family chose it
plus ``w * sim`` if the embedding family chose it, and return the top
``k_best`` of the union. Equal scores may come in either order.

Numbers compared (each has a limit in the configuration file):

- ``answers_wrong``: answers with an unknown or repeated name, too few
  names, or a name no family could have chosen. Exact: limit 0.
- ``never_answered``: requests of the window with no response a minute
  after its close. Limit 0.
- ``order_gap``: the most by which a candidate that an answer ranks lower,
  or leaves out, surely outscores one that it ranks higher, in blended-score
  units by the reference's arithmetic. 0 for an exact answer.
- ``rule_order_gap``: the same among the tracks that only the rule family
  could have chosen, whose blended score is ``(1-w) * conf`` with nothing
  of the similarities in it: the most by which one that an answer ranks
  lower, or leaves out, outscores one that it ranks higher. Confidences are
  max-merged exactly, so this is 0 for an exact answer whatever precision
  the similarities are computed in.
- ``sim_gap`` (blended answers only): the most by which a served track that
  only the embedding family could have chosen lies below the reference's
  ``k_best``-th best similarity.

Where similarity decides membership of a family's top ``k_best`` within
``margin`` (the ``sim_gap`` limit) of the reference's threshold, the
candidate's score is an interval and only sure violations count.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np


@dataclasses.dataclass
class Lowered:
    """The control: the reference computed in the next precision down."""

    confidences: str | None = None  # dtype name, e.g. "bfloat16"
    factors: str | None = None  # dtype name, e.g. "float8_e4m3fn"


def _round_to(x: np.ndarray, dtype_name: str) -> np.ndarray:
    import ml_dtypes

    return x.astype(getattr(ml_dtypes, dtype_name)).astype(np.float32)


class Reference:
    def __init__(self, cat, answer_cfg: dict, max_seeds: int, lowered: Lowered | None = None):
        self.cat = cat
        self.k = int(answer_cfg["k_best"])
        self.w = float(answer_cfg["blend_weight"])
        self.max_seeds = max_seeds
        self.lowered = lowered or Lowered()
        self.factors = cat.factors if self.w > 0 else None
        if self.factors is not None and self.lowered.factors:
            self.factors = _round_to(self.factors, self.lowered.factors)

    # ---- the two families ----

    def rule_scores(self, seeds: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """→ (track ids, max-merged float32 confidence), conf > 0 only."""
        cat = self.cat
        rows = seeds[cat.known[seeds]][: self.max_seeds]
        if len(rows) == 0:
            return np.empty(0, np.int64), np.empty(0, np.float32)
        ids = cat.rule_ids[rows].ravel()
        confs = cat.confs_of(rows).ravel()
        if self.lowered.confidences:
            confs = _round_to(confs, self.lowered.confidences)
        live = (ids >= 0) & (confs > 0)
        ids, confs = ids[live], confs[live]
        order = np.lexsort((-confs, ids))
        ids, confs = ids[order], confs[order]
        first = np.ones(len(ids), dtype=bool)
        first[1:] = ids[1:] != ids[:-1]
        return ids[first].astype(np.int64), confs[first]

    def similarities(self, seeds: np.ndarray, block: int = 16384) -> np.ndarray:
        """→ float32 (V,) max cosine over the seeds, seeds set to -inf.
        In blocks of rows small enough that a block's (seeds, rows)
        products stay in cache."""
        f = self.factors
        vecs = f[seeds[: self.max_seeds]]
        out = np.empty(f.shape[0], dtype=np.float32)
        for lo in range(0, f.shape[0], block):
            out[lo:lo + block] = (vecs @ f[lo:lo + block].T).max(axis=0)
        out[seeds[: self.max_seeds]] = -np.inf
        return out

    # ---- an answer of the reference's own (also the control's answer) ----

    def answer(self, seeds: np.ndarray) -> list[int]:
        ids, confs = self.rule_scores(seeds)
        top = np.lexsort((ids, -confs))[: self.k]
        score = {int(i): (1.0 - self.w) * float(c) for i, c in zip(ids[top], confs[top])}
        if self.factors is not None:
            sims = self.similarities(seeds)
            best = np.argpartition(-sims, self.k)[: self.k]
            for i in best:
                score[int(i)] = score.get(int(i), 0.0) + self.w * float(sims[i])
        ranked = sorted(score.items(), key=lambda kv: (-kv[1], kv[0]))
        return [i for i, _ in ranked[: self.k]]

    # ---- judging a served answer ----

    def judge(self, seeds: np.ndarray, served: list[int] | None, margin: float) -> dict:
        """→ {"wrong": bool, "order_gap": float, "rule_order_gap": float,
        "sim_gap": float | None, "rule_only": served tracks that only the
        rule family could have chosen}. ``served`` is None for a body that did not parse or a name
        that the catalog does not hold."""
        k, w = self.k, self.w
        if served is None or len(set(served)) != len(served) or len(served) > k:
            return {"wrong": True, "order_gap": 0.0, "rule_order_gap": 0.0, "sim_gap": None,
                    "rule_only": 0}
        ids, confs = self.rule_scores(seeds)
        conf_of = dict(zip(ids.tolist(), confs.tolist()))
        ranked = np.sort(confs)[::-1]
        c_k = float(ranked[k - 1]) if len(ranked) >= k else 0.0  # k-th best conf
        sims = tau = None
        if self.factors is not None:
            sims = self.similarities(seeds)
            tau = float(-np.partition(-sims, k - 1)[k - 1])

        def bounds(i: int) -> tuple[float, float]:
            c = float(conf_of.get(i, 0.0))
            lo = hi = 0.0
            if c > 0 and c >= c_k:
                hi += (1.0 - w) * c
                if c > c_k or np.count_nonzero(confs >= c_k) <= k:
                    lo += (1.0 - w) * c
            if sims is not None:
                s = float(sims[i])
                if s >= tau - margin:
                    hi += w * s
                if s >= tau + margin:
                    lo += w * s
            return lo, hi

        sure = {int(i) for i in ids[confs > c_k]}
        if np.count_nonzero(confs >= c_k) <= k:
            sure |= {int(i) for i in ids[confs >= c_k]}
        if sims is not None:
            sure |= {int(i) for i in np.flatnonzero(sims >= tau + margin)}
        wrong = len(served) < min(k, len(sure))
        sim_gap = 0.0 if sims is not None else None
        served_bounds = [bounds(i) for i in served]

        def rules_alone(i: int) -> bool:
            return sims is None or float(sims[i]) < tau - margin

        pure = []  # confidences of the served tracks only the rule family could have chosen
        for i, (_, hi) in zip(served, served_bounds):
            if hi <= 0.0:
                wrong = True
            by_rules = conf_of.get(i, 0.0) > 0 and conf_of[i] >= c_k
            if sims is not None and not by_rules:
                sim_gap = max(sim_gap, tau - float(sims[i]))
            if by_rules and rules_alone(i):
                pure.append(float(conf_of[i]))
        rule_order_gap = 0.0
        if pure:
            left_out = [
                float(c) for i, c in zip(ids[confs > min(pure)].tolist(), confs[confs > min(pure)])
                if i not in served
            ]
            later = pure + left_out
            for pos, c in enumerate(pure):
                rest = later[pos + 1:]
                if rest:
                    rule_order_gap = max(rule_order_gap, (1.0 - w) * (max(rest) - c))
        # whoever comes later, or is left out though surely a candidate,
        # may not surely outscore whoever comes earlier
        later = [lo for lo, _ in served_bounds] + [
            bounds(i)[0] for i in sure - set(served)
        ]
        order_gap = 0.0
        for pos, (_, hi) in enumerate(served_bounds):
            rest = later[pos + 1:]
            if rest:
                order_gap = max(order_gap, max(rest) - hi)
        return {"wrong": wrong, "order_gap": order_gap, "rule_order_gap": rule_order_gap,
                "sim_gap": sim_gap, "rule_only": len(pure)}


def parse_answer(body: bytes | None, name_to_id: dict) -> list[int] | None:
    try:
        songs = json.loads(body)["songs"]
        return [name_to_id[s] for s in songs]
    except (TypeError, ValueError, KeyError):
        return None


def choose_sample(lengths: np.ndarray, eligible: np.ndarray, n: int, seed: int) -> np.ndarray:
    """Indices to judge: all eligible when ``n`` is 0, else ``n`` drawn from
    the seed with the longest request among them."""
    idx = np.flatnonzero(eligible)
    if n <= 0 or len(idx) <= n:
        return idx
    rng = np.random.default_rng([int(seed), 21])
    pick = set(rng.choice(idx, size=n, replace=False).tolist())
    pick.add(int(idx[np.argmax(lengths[idx])]))
    return np.asarray(sorted(pick))


def compare(ref: Reference, seed_sets, answers, sample: np.ndarray, limits: dict) -> dict:
    """Judge ``answers[i]`` (id lists or None) for ``i`` in ``sample`` →
    the numbers compared, by name, and beside them (with no limit) how much
    of what was served only the rule family could have chosen."""
    margin = float(limits.get("sim_gap", 0.0))
    out = {"answers_wrong": 0, "order_gap": 0.0, "rule_order_gap": 0.0, "served_tracks": 0,
           "rule_only_tracks": 0, "answers_with_rule_only": 0}
    if ref.factors is not None:
        out["sim_gap"] = 0.0
    for i in sample:
        j = ref.judge(np.asarray(seed_sets[i], dtype=np.int64), answers[i], margin)
        out["answers_wrong"] += int(j["wrong"])
        out["served_tracks"] += len(answers[i] or ())
        out["rule_only_tracks"] += j["rule_only"]
        out["answers_with_rule_only"] += j["rule_only"] > 0
        out["order_gap"] = max(out["order_gap"], j["order_gap"])
        out["rule_order_gap"] = max(out["rule_order_gap"], j["rule_order_gap"])
        if j["sim_gap"] is not None and "sim_gap" in out:
            out["sim_gap"] = max(out["sim_gap"], j["sim_gap"])
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """→ (correct, {name: {"value", "limit"}}) over the limits' names."""
    table = {}
    ok = True
    for name, limit in limits.items():
        if name not in numbers:
            continue
        value = numbers[name]
        table[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, table
