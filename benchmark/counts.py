"""What an operation needs, counted from its meaning, not its program.

A kernel's roofline share is the least time the chip could take for the
operation's semantics over the time the program took, so a PR that replaces
the kernel is held to the same yardstick and cannot pass 100%. The inputs
are the harness's own record of what it sent while the trace was open
(``seed_lens``: un-padded known-seed counts per request; ``batches``: how
often the module ran, from the trace) and the configuration's sizes.

- ``rule_lookup``: per request the ``l`` gathered rule rows (``k_max`` ids +
  ``k_max`` confidences, 8 B a slot), ``l`` seed ids in (4 B), ``k_best``
  (id, score) pairs out (8 B). No FLOPs to speak of: the bandwidth roof.
- ``embed_lookup``: per executed batch one read of the item factors
  (``V * R * 4`` B); per request ``l * R * 4`` B of seed rows, ``k_best * 8``
  B out, and ``2 * l * R * V`` FLOPs at the program's matmul precision
  (``matmul_dtype`` in the metric file): whichever roof is lower.
"""

from __future__ import annotations


def rule_lookup(cfg: dict, seed_lens, batches: int) -> tuple[float, float]:
    rows = float(sum(seed_lens))
    n = len(seed_lens)
    bytes_ = rows * cfg["k_max"] * 8 + rows * 4 + n * cfg["k_best"] * 8
    return 0.0, bytes_


def embed_lookup(cfg: dict, seed_lens, batches: int) -> tuple[float, float]:
    v, r = cfg["n_tracks"], cfg["embedding_rank"]
    rows = float(sum(seed_lens))
    n = len(seed_lens)
    flops = 2.0 * rows * r * v
    bytes_ = batches * v * r * 4 + rows * r * 4 + n * cfg["k_best"] * 8
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, peaks: dict, matmul_dtype: str) -> tuple[float, str]:
    """→ (the least time the chip could take, which roof bounds it)."""
    t_flops = flops / peaks["flops_per_s"][matmul_dtype] if flops else 0.0
    t_bytes = bytes_ / peaks["bytes_per_s"]
    return (t_flops, "compute") if t_flops > t_bytes else (t_bytes, "bandwidth")
