"""Readers for what the vocabulary-sharded layout records about itself.

Both take the fullest shard of a labelled series from the server's
``/metrics`` (``benchmark.readers`` sums over label sets or takes the first;
a shard's metric is about the one that holds or is hit most). A server
that exports no such series (another layout, or a program from before the
series existed) gives None, and the metric is left out of the line.

- ``read_share_max``: the largest label set's share of the series' growth
  over the window, in percent: with ``kmls_shard_dispatch_total`` the
  fullest shard's share of the window's seed hits (100 / shards is even).
- ``read_level_max_pct_of_memory``: the largest label set's value at the
  window's end over one device's memory (``memory_bytes`` of the device
  kind in ``peaks.json``), in percent: with ``kmls_shard_resident_bytes``
  the share of a chip that the fullest shard's rule rows take. A device
  kind that ``peaks.json`` does not hold (the CPU smoke's) gives None too.
"""

from __future__ import annotations


def _by_labels(scrape: dict, series: str) -> dict:
    return {labels: v for (name, labels), v in scrape.items() if name == series}


def read_share_max(reader: dict, ctx: dict) -> float | None:
    end = _by_labels(ctx["prom_end"], reader["series"])
    start = _by_labels(ctx["prom_start"], reader["series"])
    grown = [v - start.get(labels, 0.0) for labels, v in end.items()]
    if not grown or sum(grown) <= 0:
        return None
    return 100.0 * max(grown) / sum(grown)


def read_level_max_pct_of_memory(reader: dict, ctx: dict) -> float | None:
    levels = _by_labels(ctx["prom_end"], reader["series"])
    if not levels:
        return None
    peaks = ctx["peaks"].get(ctx["device_kind"])
    if peaks is None:  # the CPU smoke: no chip whose memory it could be a share of
        return None
    return 100.0 * max(levels.values()) / float(peaks["memory_bytes"])
