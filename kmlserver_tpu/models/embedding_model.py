"""The deployable embedding model: item factors + vocab + jitted apply.

The second model family's twin of :class:`~.rule_model.RuleModel` — same
three primitives, different math: training is ALS matrix factorization
(``mining/als.py``), inference is the cosine top-k kernel
(``ops/embed.py``), serialization is the manifest-covered
``embeddings.npz`` (``io/artifacts.py``). The serving engine carries the
factors inside its :class:`~kmlserver_tpu.serving.engine.RuleBundle`
replicas for the hybrid merge; this object is the standalone view for
library users who want embedding recommendations without the job/API
stack.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..config import MiningConfig
from ..io import artifacts
from ..mining.vocab import Baskets
from ..ops.embed import embed_topk, factor_table


@dataclasses.dataclass
class EmbeddingModel:
    """ALS item-embedding model over a track vocabulary."""

    vocab: list[str]
    index: dict[str, int]
    item_factors: jax.Array  # float32 (rank, V) factor_table(), unit columns, device
    rank: int

    # ---------- construction ----------

    @classmethod
    def _from_factors(
        cls, vocab: list[str], item_factors: np.ndarray
    ) -> "EmbeddingModel":
        """The one place host factors become a device-resident model."""
        return cls(
            vocab=list(vocab),
            index={n: i for i, n in enumerate(vocab)},
            item_factors=factor_table(item_factors),
            rank=int(item_factors.shape[1]),
        )

    @staticmethod
    def fit(
        baskets: Baskets, cfg: MiningConfig | None = None
    ) -> "EmbeddingModel":
        """Train from a transaction DB (the ALS "training" step)."""
        from ..mining.als import train_embeddings

        cfg = cfg or MiningConfig()
        result = train_embeddings(baskets, cfg)
        return EmbeddingModel._from_factors(
            baskets.vocab.names, result["item_factors"]
        )

    @staticmethod
    def load(npz_path: str) -> "EmbeddingModel":
        """Load from the embedding artifact the mining job publishes."""
        loaded = artifacts.load_embeddings(npz_path)
        return EmbeddingModel._from_factors(
            loaded["vocab"], loaded["item_factors"]
        )

    # ---------- inference ----------

    def encode_seeds(
        self, seed_sets: list[list[str]], pad_len: int | None = None
    ) -> np.ndarray:
        """Seed names → int32 (B, L) id batch, -1 padded; unknown names drop."""
        ids = [
            [self.index[s] for s in seeds if s in self.index]
            for seeds in seed_sets
        ]
        length = pad_len or max((len(r) for r in ids), default=1) or 1
        out = np.full((len(seed_sets), length), -1, dtype=np.int32)
        for r, row in enumerate(ids):
            out[r, : min(len(row), length)] = row[:length]
        return out

    def recommend(
        self, seed_sets: list[list[str]], k_best: int = 10
    ) -> list[list[str]]:
        """Batched apply: ONE device call for the whole batch, with the
        same power-of-two shape bucketing as :class:`RuleModel` so varying
        call shapes reuse a bounded compiled-kernel set."""
        longest = max((len(s) for s in seed_sets), default=1)
        pad_len = 1 << max(longest - 1, 0).bit_length()
        seed_arr = self.encode_seeds(seed_sets, pad_len=pad_len)
        n_rows = 1 << max(len(seed_sets) - 1, 0).bit_length()
        if n_rows > seed_arr.shape[0]:
            seed_arr = np.concatenate(
                [seed_arr, np.full((n_rows - seed_arr.shape[0], pad_len), -1,
                                   dtype=np.int32)]
            )
        top_ids, _ = self.apply_fn(k_best)(
            self.item_factors, jnp.asarray(seed_arr)
        )
        top_ids = np.asarray(top_ids)[: len(seed_sets)]
        return [
            [self.vocab[int(i)] for i in row if i >= 0] for row in top_ids
        ]

    @staticmethod
    def apply_fn(k_best: int = 10):
        """The raw jittable forward step (cosine top-k over item space)."""
        return partial(embed_topk, k_best=k_best)
