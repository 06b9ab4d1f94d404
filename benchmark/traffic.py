"""The one traffic generator: seed sets and due times from a mix file.

A mix (``benchmark/traffic/<name>.json``) is data: the seed-length mix
and three laws, each named ``module:function`` with its parameters beside
it: how a seed set is drawn (``seed_sets``), how sets are shared among
requests (``sharing``) and when requests are due (``arrivals``). The laws
here are ``walk``; ``distinct`` and ``zipf_pool``; ``poisson`` and ``burst``.
A cell (``benchmark/workloads/<name>.json``) adds the rate and, for a closed
loop, the callers. Everything is made from ``--seed`` here; the server
receives only requests.

Every seed gets the same multiset of lengths and of inter-arrival gaps, in
another order, so that two seeds differ in order and content, not in the
amount of work.
"""

from __future__ import annotations

import numpy as np

from . import manifest


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def lengths_for(mix: dict, n: int, seed: int) -> np.ndarray:
    """Exactly proportional seed-set lengths (largest remainder), shuffled."""
    sizes = np.asarray(mix["lengths"], dtype=np.int64)
    w = np.asarray(mix["weights"], dtype=np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    out = np.repeat(sizes, counts)
    _rng(seed, 11).shuffle(out)
    return out


class walk:
    """Seed-set law: one popularity-drawn track that has a live rule row,
    then tracks drawn from the rule rows of tracks already chosen (a slot
    uniform over the row's live consequents), so seeds co-occur as a
    playlist's do and rule rows are hit; a walk that stalls is filled with
    popularity draws."""

    def __init__(self, cat, law: dict, seed: int):
        self.cat = cat
        self.rng = _rng(seed, 12)

    def _popular(self, need_row: bool) -> int:
        cat = self.cat
        while True:
            rank = int(np.searchsorted(cat.pop_cdf, self.rng.random()))
            tid = int(cat.rank_to_id[min(rank, len(cat.rank_to_id) - 1)])
            if not need_row or cat.live[tid] > 0:
                return tid

    def draw(self, length: int) -> tuple[int, ...]:
        cat, rng = self.cat, self.rng
        chosen = [self._popular(need_row=True)]
        have = set(chosen)
        budget = 8 * length
        picks = rng.random((budget, 2))
        for a, b in picks:
            if len(chosen) >= length:
                break
            src = chosen[int(a * len(chosen))]
            n_live = int(cat.live[src])
            if n_live == 0:
                continue
            nxt = int(cat.rule_ids[src, int(b * n_live)])
            if nxt not in have:
                have.add(nxt)
                chosen.append(nxt)
        while len(chosen) < length:
            nxt = self._popular(need_row=False)
            if nxt not in have:
                have.add(nxt)
                chosen.append(nxt)
        return tuple(chosen)


def distinct(law: dict, sampler, mix: dict, n: int, seed: int, avoid=()) -> list:
    """Sharing law: no two sets alike, so the answer cache misses."""
    out, seen = [], {tuple(sorted(s)) for s in avoid}
    for length in lengths_for(mix, n, seed):
        while True:
            s = sampler.draw(int(length))
            key = tuple(sorted(s))
            if key not in seen:
                seen.add(key)
                out.append(s)
                break
    return out


def zipf_pool(law: dict, sampler, mix: dict, n: int, seed: int, avoid=()) -> list:
    """Sharing law: ``n`` draws with exponent ``exponent`` from a pool of
    ``pool`` distinct sets, so popular sets repeat."""
    pool = distinct(law, sampler, mix, int(law["pool"]), seed, avoid)
    w = np.arange(1, len(pool) + 1, dtype=np.float64) ** -float(law["exponent"])
    picks = _rng(seed, 13).choice(len(pool), size=n, p=w / w.sum())
    return [pool[i] for i in picks]


def seed_sets(cat, mix: dict, n: int, seed: int, avoid=()) -> list[tuple[int, ...]]:
    """``n`` seed sets (track ids, request order), drawn by the mix's
    seed-set law and shared by its sharing law. No set equals one of
    ``avoid`` (the warm-up's, which the answer cache already holds)."""
    sampler = manifest.resolve(mix["seed_sets"]["law"])(cat, mix["seed_sets"], seed)
    sharing = mix["sharing"]
    return manifest.resolve(sharing["law"])(sharing, sampler, mix, n, seed, avoid)


def poisson(law: dict, t: np.ndarray) -> np.ndarray:
    """Arrival law: exponential gaps at one rate all through the window."""
    return t


def burst(law: dict, t: np.ndarray) -> np.ndarray:
    """Arrival law: the rate is ``factor`` times higher during ``fraction``
    of each of ``n_bursts`` periods (the mean rate stays the cell's): maps
    uniform-rate time through the inverse of the rate's integral."""
    f, frac, nb = float(law["factor"]), float(law["fraction"]), int(law["n_bursts"])
    mean = 1.0 + frac * (f - 1.0)
    period = t * nb
    k = np.floor(period)
    x = (period - k) * mean  # work done within the period
    inside = np.where(x < frac * f, x / f, frac + (x - frac * f))
    return (k + inside) / nb


def arrivals(mix: dict, rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds) of an open loop at ``rate`` requests/s:
    n = round(rate * seconds) exponential gaps from a stream fixed in the
    mix, scaled to fill the window, in an order drawn from the seed, then
    shaped by the mix's arrival law."""
    law = mix["arrivals"]
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng([int(law["gap_stream"]), n]).exponential(1.0, n)
    _rng(seed, 14).shuffle(gaps)
    t = (np.cumsum(gaps) - gaps / 2.0) / gaps.sum()  # uniform-rate time in (0, 1)
    return manifest.resolve(law["law"])(law, t) * seconds
