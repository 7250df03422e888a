"""GAME dataset: columnar layout of scored examples with id columns.

Reference analog: GameDatum (photon-lib data/GameDatum.scala:33-55) and the
DataFrame->RDD[(uniqueId, GameDatum)] conversion (photon-client
data/GameConverters.scala:38-110). Instead of an RDD of per-example objects,
examples live in columnar arrays indexed by a dense uniqueId = row position:
response/offset/weight vectors, one SparseBatch per feature shard (all
row-aligned), and integer-coded id columns (entity keys) with host-side
vocabularies. Scores and residuals are then plain [n] device arrays — the
KeyValueScore analog (photon-lib data/KeyValueScore.scala) is vector
addition, no joins.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Sequence

import numpy as np

from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.telemetry.trace import span


@dataclasses.dataclass(frozen=True)
class IdColumn:
    """An entity-id column: dense integer codes + the value vocabulary."""

    codes: np.ndarray  # int64[n] index into vocab
    vocab: np.ndarray  # unique original values (any dtype), code -> value

    @property
    def num_entities(self) -> int:
        return len(self.vocab)

    @staticmethod
    def from_values(values: Sequence) -> "IdColumn":
        # a sort of every row's id: seconds at 18M rows
        with span("dataset.ids"):
            vocab, codes = np.unique(np.asarray(values), return_inverse=True)
            return IdColumn(codes=codes.astype(np.int64), vocab=vocab)


@dataclasses.dataclass(frozen=True)
class GameDataset:
    """Row-aligned columnar GAME data.

    ``feature_shards`` maps shard name -> SparseBatch whose rows align with
    the response arrays (the featureShardContainer analog); ``id_columns``
    maps id type (e.g. 'userId') -> IdColumn. Row padding conventions follow
    SparseBatch (padded rows have weight 0).
    """

    response: np.ndarray  # f64[n]
    offset: np.ndarray  # f64[n]
    weight: np.ndarray  # f64[n]
    feature_shards: Mapping[str, SparseBatch]
    id_columns: Mapping[str, IdColumn]

    @property
    def num_rows(self) -> int:
        return len(self.response)

    def shard(self, name: str) -> SparseBatch:
        if name not in self.feature_shards:
            raise KeyError(
                f"unknown feature shard '{name}'; have {sorted(self.feature_shards)}"
            )
        return self.feature_shards[name]

    def device_shard(self, name: str) -> SparseBatch:
        """Device copy of a shard, uploaded once and cached — scoring in
        the CD loop reuses one HBM copy instead of re-uploading host
        leaves every call."""
        cache = self.__dict__.setdefault("_device_shards", {})
        hit = cache.get(name)
        if hit is None:
            hit = self.shard(name).device()
            cache[name] = hit
        return hit

    def batch_for(
        self, shard_name: str, extra_offsets: Optional[np.ndarray] = None
    ) -> SparseBatch:
        """Shard batch with (response, offset [+extra], weight) attached."""
        b = self.shard(shard_name)
        off = self.offset if extra_offsets is None else self.offset + extra_offsets
        n_pad = b.num_rows

        def pad(a, fill=0.0):
            out = np.full((n_pad,), fill)
            out[: self.num_rows] = a
            return out.astype(b.dtype)  # host; consumers upload once

        return dataclasses.replace(
            b,
            labels=pad(self.response),
            offsets=pad(off),
            weights=pad(self.weight),
        )


def build_game_dataset(
    response: np.ndarray,
    feature_shards: Mapping[str, SparseBatch],
    id_columns: Optional[Mapping[str, Sequence]] = None,
    offset: Optional[np.ndarray] = None,
    weight: Optional[np.ndarray] = None,
) -> GameDataset:
    """The shards and id columns as one row-aligned dataset, under a
    ``dataset.game`` span (children ``dataset.pad_rows`` where a shard is
    padded to the others' rows, ``dataset.ids`` an id column's codes)."""
    with span("dataset.game", rows=len(response)):
        n = len(response)
        for name, b in feature_shards.items():
            if b.num_rows < n:
                raise ValueError(
                    f"feature shard '{name}' has {b.num_rows} rows < {n} "
                    "examples"
                )
        # All score/residual paths combine per-shard [n_pad] vectors, so
        # every shard must share one padded row count — normalize to the max.
        n_pad = max(b.num_rows for b in feature_shards.values())
        with span("dataset.pad_rows"):
            feature_shards = {
                name: (b if b.num_rows == n_pad
                       else b.pad_rows_to(n_pad, b.nnz))
                for name, b in feature_shards.items()
            }
        return GameDataset(
            response=np.asarray(response, np.float64),
            offset=(np.zeros(n) if offset is None
                    else np.asarray(offset, np.float64)),
            weight=(np.ones(n) if weight is None
                    else np.asarray(weight, np.float64)),
            feature_shards=dict(feature_shards),
            id_columns={
                k: v if isinstance(v, IdColumn) else IdColumn.from_values(v)
                for k, v in (id_columns or {}).items()
            },
        )
