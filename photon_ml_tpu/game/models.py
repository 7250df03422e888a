"""GAME model containers: fixed-effect, random-effect, and the composite
GAME model whose score is the sum of sub-model scores.

Reference analog: photon-lib model/GAMEModel.scala:32-188 (sum-of-scores at
:125-127, single-task enforcement at :181-187), photon-api
model/{FixedEffectModel,RandomEffectModel}.scala. Sub-model scores are raw
margins x.w (no offsets, no link), matching DatumScoringModel semantics —
offsets enter only through training objectives and evaluator inputs.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.ops.losses import get_loss

Array = jax.Array

#: nnz processed per device dispatch in RandomEffectModel.score — module
#: level (not a local) so tests can shrink it to exercise the chunk
#: boundary without 8M-nnz fixtures.
SCORE_CHUNK = 8_000_000


def map_vocab_codes(vocab: np.ndarray, values: np.ndarray) -> np.ndarray:
    """Map raw id values to codes in a (sorted unique) vocabulary; -1 for
    values the vocabulary has never seen. Entity identity is the id VALUE,
    not a dataset-local integer code (the RDD analog joins by id string)."""
    pos = np.searchsorted(vocab, values)
    pos_c = np.minimum(pos, len(vocab) - 1)
    hit = vocab[pos_c] == values
    return np.where(hit, pos_c, -1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FixedEffectModel:
    """Global GLM coefficients over one feature shard (original space)."""

    coefficients: Array  # f[num_features]
    shard_name: str = dataclasses.field(metadata=dict(static=True))

    def score(self, data: GameDataset) -> Array:
        """Raw scores x.w for every example row ([n_pad] aligned array)."""
        return data.device_shard(self.shard_name).dot_rows(self.coefficients)

    def to_summary_string(self) -> str:
        w = np.asarray(self.coefficients)
        nnz = int(np.sum(np.abs(w) > 1e-9))
        return (
            f"FixedEffectModel(shard={self.shard_name}, features={len(w)}, "
            f"nonzero={nnz}, |w|2={float(np.linalg.norm(w)):.4g})"
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RandomEffectBucketModel:
    """Per-entity coefficients for one geometry bucket, aligned with the
    bucket's sorted projection (local id k <-> global feature projection[k]).

    ``variances`` (optional) are per-coefficient posterior variances from the
    Hessian-diagonal inverse at each entity's optimum — the computeVariances
    path of SingleNodeOptimizationProblem.scala:57-88; entries for padded
    local features (projection == sentinel) are meaningless.
    """

    coefficients: Array  # f[E, K]
    projection: Array  # i32[E, K] sorted global ids; sentinel = num_global
    entity_codes: Array  # i32[E]
    variances: Optional[Array] = None  # f[E, K] when computed


@dataclasses.dataclass(frozen=True)
class RandomEffectModel:
    """All per-entity models for one random-effect coordinate.

    The coefficient table is sharded across buckets exactly as the training
    data was (model co-located with its entity's data — the bin-packing
    co-partitioning analog, RandomEffectOptimizationProblem.scala:28-131).
    """

    id_name: str
    shard_name: str
    buckets: tuple[RandomEffectBucketModel, ...]
    entity_bucket: np.ndarray  # host: TRAINING entity code -> bucket (-1 none)
    entity_pos: np.ndarray
    vocab: np.ndarray  # training id vocabulary (sorted unique values)

    def _codes_for(self, data: GameDataset) -> np.ndarray:
        """Map a dataset's entity VALUES to training codes (-1 if unseen)."""
        return self._grouping_for(data)[0]

    def _grouping_for(
        self, data: GameDataset
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(codes, row_bucket, row_pos) host arrays for ``data``."""
        entry = self._grouping_entry(data)
        return entry["codes"], entry["row_bucket"], entry["row_pos"]

    def _grouping_entry(self, data: GameDataset) -> dict:
        """The cached grouping of ``data`` for this model's tables:
        ``codes``, ``row_bucket``, ``row_pos`` host arrays — the
        O(n log V) vocabulary join and bucket/position placement — and
        whatever :meth:`score` keeps beside them.

        Memoized per (model, dataset): repeated scoring of the same
        dataset (validation every CD iteration, the serving registry's
        parity checks) must not redo the host-side numpy work. The cache
        lives on the dataset (like ``device_shard``) keyed by id column,
        and is validated by TABLE IDENTITY — a different model object with
        its own vocab/placement recomputes instead of reusing stale
        arrays. Hits/misses are ``scoring.code_cache.{hits,misses}``.
        """
        cache = data.__dict__.setdefault("_re_group_cache", {})
        # keyed by (id column, vocab identity) so two coordinates sharing
        # an id column keep separate entries instead of thrashing one; the
        # entry pins the vocab object, so its id() cannot be recycled
        key = (self.id_name, id(self.vocab))
        entry = cache.get(key)
        if (
            entry is not None
            and entry["vocab"] is self.vocab
            and entry["entity_bucket"] is self.entity_bucket
            and entry["entity_pos"] is self.entity_pos
        ):
            telemetry.counter("scoring.code_cache.hits").inc()
            return entry
        telemetry.counter("scoring.code_cache.misses").inc()
        idc = data.id_columns[self.id_name]
        codes = map_vocab_codes(self.vocab, idc.vocab[idc.codes])
        known = codes >= 0
        safe_codes = np.where(known, codes, 0)
        row_bucket = np.where(known, self.entity_bucket[safe_codes], -1)
        row_pos = np.where(known, self.entity_pos[safe_codes], -1)
        cache[key] = {
            "vocab": self.vocab,
            "entity_bucket": self.entity_bucket,
            "entity_pos": self.entity_pos,
            "codes": codes,
            "row_bucket": row_bucket,
            "row_pos": row_pos,
        }
        return cache[key]

    def to_summary_string(self) -> str:
        n_models = int(np.sum(self.entity_bucket >= 0))
        dims = [int(b.coefficients.shape[1]) for b in self.buckets]
        return (
            f"RandomEffectModel(id={self.id_name}, shard={self.shard_name}, "
            f"entities={n_models}/{len(self.vocab)}, "
            f"buckets={len(self.buckets)}, local_dims={dims})"
        )

    def _score_chunks(self, data: GameDataset) -> list:
        """``data``'s nonzeros grouped by this model's buckets, in chunks
        of at most ``SCORE_CHUNK``, on the device: [(bucket, values, rows,
        global columns, entity positions)]. Built and uploaded once a
        dataset and table placement and kept with the grouping (validation
        scores the same rows after every coordinate update; the per-bucket
        masks over every nonzero and their upload were most of a call)."""
        entry = self._grouping_entry(data)
        chunks = entry.setdefault("chunks", {}).get(SCORE_CHUNK)
        if chunks is not None:
            return chunks
        batch = data.shard(self.shard_name)
        n = data.num_rows
        row_bucket, row_pos = entry["row_bucket"], entry["row_pos"]
        vals = np.asarray(batch.values)
        rows = np.asarray(batch.rows)
        cols = np.asarray(batch.cols)
        live = (vals != 0) & (rows < n)
        bucket_of_nnz = np.where(live, row_bucket[np.minimum(rows, n - 1)], -1)
        chunks = []
        for b_idx in range(len(self.buckets)):
            sel_idx = np.nonzero(bucket_of_nnz == b_idx)[0]
            # nnz are processed in bounded chunks: the per-nnz [*, K] /
            # [K, *] gathers otherwise materialize O(total_nnz x 128)-padded
            # fusion outputs (a 20M-row shard measured a 51 GB allocation
            # attempt)
            for lo in range(0, len(sel_idx), SCORE_CHUNK):
                part = sel_idx[lo:lo + SCORE_CHUNK]
                chunks.append((
                    b_idx,
                    jnp.asarray(vals[part], batch.dtype),
                    jnp.asarray(rows[part], jnp.int32),
                    jnp.asarray(cols[part], jnp.int32),
                    jnp.asarray(row_pos[rows[part]], jnp.int32),
                ))
        entry["chunks"][SCORE_CHUNK] = chunks
        return chunks

    def score(self, data: GameDataset) -> Array:
        """Scores for every example row; entities without a model score 0.

        Device program per bucket chunk (``re_score_rows``): rows are
        grouped by entity bucket on host, once a dataset, then each nnz
        looks up its coefficient in the entity's sorted projection,
        multiplies and adds at its row. Entities unseen in training
        contribute nothing — matching the reference's behavior of scoring
        only entities with models (RandomEffectModel joins by entity id).
        """
        if data.id_columns.get(self.id_name) is None:
            raise KeyError(f"scoring data lacks id column '{self.id_name}'")
        batch = data.shard(self.shard_name)
        scores = jnp.zeros((batch.num_rows,), dtype=batch.dtype)
        scorer = _row_scorer()
        for b_idx, v, r, g, pos in self._score_chunks(data):
            bm = self.buckets[b_idx]
            scores = scorer(
                scores, bm.coefficients, bm.projection, v, r, g, pos
            )
        return scores


@lru_cache(maxsize=1)
def _row_scorer():
    """``scores`` plus one chunk of nonzeros' value x coefficient, each at
    its row: one named program a chunk shape (eagerly it was a dozen one-op
    programs a bucket, each a compile of its own). Elementwise float32: no
    product here goes through the MXU."""

    def score_rows(scores, coefficients, projection, v, r, g, pos):
        K = projection.shape[1]
        if K <= 64:
            # TRANSPOSED compare-scan: [K, m] keeps the long nnz dim in
            # lanes (a [m, K] gather pads lanes 128/K-fold — at K=4 that
            # is 32x pure padding); each column matches at most one
            # projection slot, so the masked sum IS the lookup
            proj_t = projection.T[:, pos]  # [K, m]
            coef_t = coefficients.T[:, pos]  # [K, m]
            w = jnp.sum(
                jnp.where(proj_t == g[None, :], coef_t, 0.0), axis=0
            )
        else:
            proj_rows = projection[pos]  # [m, K]
            k = jax.vmap(jnp.searchsorted)(proj_rows, g)  # [m]
            k = jnp.minimum(k, K - 1)
            hit = jnp.take_along_axis(proj_rows, k[:, None], axis=1)[:, 0] == g
            w = jnp.where(
                hit,
                jnp.take_along_axis(coefficients[pos], k[:, None], axis=1)[
                    :, 0
                ],
                0.0,
            )
        return scores.at[r].add(v * w)

    return telemetry.instrumented_jit(
        score_rows, name="re_score_rows", multi_shape=True
    )


@dataclasses.dataclass(frozen=True)
class GameModel:
    """Named sub-models; score = sum of sub-model scores (GAMEModel:125-127).
    All coordinates share one task type (GAMEModel.scala:181-187)."""

    task: str
    models: Mapping[str, object]  # name -> FixedEffectModel | RandomEffectModel

    def __post_init__(self):
        get_loss(self.task)

    def score(self, data: GameDataset) -> Array:
        total = None
        for model in self.models.values():
            s = model.score(data)
            total = s if total is None else total + s
        if total is None:
            raise ValueError("GAME model has no sub-models")
        return total

    def predict_mean(self, data: GameDataset) -> Array:
        raw = self.score(data)
        scores = raw + jnp.asarray(
            np.pad(data.offset, (0, raw.shape[0] - data.num_rows))
        ).astype(jnp.float32)
        name = get_loss(self.task).name
        if name == "logistic":
            return jax.nn.sigmoid(scores)
        if name == "poisson":
            return jnp.exp(scores)
        return scores

    def with_model(self, name: str, model) -> "GameModel":
        new = dict(self.models)
        new[name] = model
        return dataclasses.replace(self, models=new)

    def to_summary_string(self) -> str:
        """Structured one-summary-per-sub-model log string (the reference's
        toSummaryString protocol, e.g. GAMEModel/RandomEffectDataSet
        .toSummaryString)."""
        lines = [f"GameModel(task={self.task}, coordinates={len(self.models)})"]
        for name, sub in self.models.items():
            summary = (
                sub.to_summary_string()
                if hasattr(sub, "to_summary_string")
                else repr(sub)
            )
            lines.append(f"  {name}: {summary}")
        return "\n".join(lines)
