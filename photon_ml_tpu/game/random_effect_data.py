"""Random-effect datasets: per-entity grouping, size bucketing, and
per-entity feature projection — the TPU answer to the reference's
RandomEffectDataSet + RandomEffectDataSetPartitioner + IndexMapProjector
(photon-api data/RandomEffectDataSet.scala:45-435,
data/RandomEffectDataSetPartitioner.scala:42-148,
projector/IndexMapProjectorRDD.scala:27-77).

Where Spark bin-packs entities into JVM partitions and runs heterogeneous
per-entity solves, XLA needs fixed shapes: entities are grouped into
geometry buckets keyed by (rows, local-feature-count) rounded up to powers
of two; a bucket's nnz width is its own fullest entity's, rounded up the
same way. Each bucket is a stack of same-shaped per-entity sparse problems
solved by ONE vmapped optimizer call. A heavy-tailed id column (rows per
entity from 1 to 1e5) spreads over dozens of such classes, each a compile
of the solver and of the scorer, so the classes of one coordinate are
MERGED, cheapest pair first, until at most ``MAX_GEOMETRY_CLASSES`` are
left (:func:`merge_geometry_classes`): that is the bound on recompilation.
No row and no entity is dropped to get there: a merge only pads.

Per-entity index-map projection (the reference's key scaling trick —
projector/README.md says it reaches ~1e8 entities x ~1e3 features): each
entity's observed global feature ids become local ids 0..K-1 via the sorted
array ``projection``; the tiny K-dim local solve never touches the global
feature space.

Active-data caps use reservoir sampling with weight rescaling, matching
RandomEffectDataSet.scala:294-357; rows beyond the cap become passive data
(scored but not trained on; :368-409).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.telemetry.device import accounted_upload
from photon_ml_tpu.telemetry.trace import span

Array = jax.Array


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x - 1).bit_length())


#: the most geometry buckets one random-effect coordinate may have: each
#: is one compile of ``re_solve*`` and one of ``re_score*``
MAX_GEOMETRY_CLASSES = 8
#: what one entity's K x K factorisation costs a Newton iteration, in units
#: of one design cell read three times: XLA's batched Cholesky took 1.6 us
#: a 16 x 16 matrix on a v5e (PERF.md section 7, PR 23's reading) against
#: ~24 ps a cell for the passes over the design, so K**3 cells x 16
_FACTOR_CELLS = 16.0


def _class_cost(E, R, K):
    """Work of one Newton iteration over a class of E entities padded to
    R rows and K local features, in design cells: the passes over the
    [R, K] design and the K x K factorisation."""
    return E * (R * K + _FACTOR_CELLS * K ** 3)


def merge_geometry_classes(
    classes: np.ndarray, counts: np.ndarray, max_classes: int
) -> np.ndarray:
    """Merge (R, K) geometry classes until at most ``max_classes`` stand.

    ``classes`` is [C, 2] (rows, local features per entity, both already
    rounded up), ``counts`` [C] the entities of each. Two classes merge
    into (max R, max K); each round merges the pair whose merge adds the
    least work (:func:`_class_cost`: thin classes and classes that differ
    in R alone go first, a merge across K pays the factorisation at the
    wider K and goes last). Returns [C]: for each input class, the index
    (into ``classes``) of the class it ended in. C is tens, so the search
    over pairs is nothing.
    """
    live = {
        i: (int(r), int(k), int(e))
        for i, ((r, k), e) in enumerate(zip(classes, counts))
    }
    target = np.arange(len(classes))
    while len(live) > max(int(max_classes), 1):
        best = None
        keys = sorted(live)
        for x, a in enumerate(keys):
            ra, ka, ea = live[a]
            for b in keys[x + 1:]:
                rb, kb, eb = live[b]
                r, k = max(ra, rb), max(ka, kb)
                extra = (
                    _class_cost(ea + eb, r, k)
                    - _class_cost(ea, ra, ka) - _class_cost(eb, rb, kb)
                )
                if best is None or extra < best[0]:
                    best = (extra, a, b, (r, k, ea + eb))
        _, a, b, merged = best
        live[a] = merged
        del live[b]
        target[target == b] = a
    return target


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EntityBucket:
    """A stack of E same-geometry per-entity sparse problems (LOCAL feature
    ids). Padding: rows -> R-1 with value 0; weights 0 on padded rows;
    projection -> num_global (sentinel past any feature id)."""

    values: Array  # f[E, nnz]
    rows: Array  # i32[E, nnz] local row ids
    cols: Array  # i32[E, nnz] LOCAL feature ids
    labels: Array  # f[E, R]
    offsets: Array  # f[E, R] base offsets
    weights: Array  # f[E, R]
    projection: Array  # i32[E, K] sorted global feature id per local id
    entity_codes: Array  # i32[E]; -1 padding entity
    row_index: Array  # i32[E, R] global example row; -1 padding
    num_local_features: int = dataclasses.field(metadata=dict(static=True))
    num_global_features: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_entities(self) -> int:
        return self.entity_codes.shape[0]

    @property
    def rows_per_entity(self) -> int:
        return self.labels.shape[1]

    def entity_batch(self) -> SparseBatch:
        """View as a SparseBatch with leading entity axis, for vmap."""
        return SparseBatch(
            values=self.values,
            rows=self.rows,
            cols=self.cols,
            labels=self.labels,
            offsets=self.offsets,
            weights=self.weights,
            num_features=self.num_local_features,
        )

    def with_extra_offsets(self, per_row: Array) -> "EntityBucket":
        """Add residual scores (global [n] array) to this bucket's offsets
        via row_index gather — the addScoresToOffsets analog."""
        extra = jnp.where(
            self.row_index >= 0,
            jnp.take(per_row, jnp.maximum(self.row_index, 0), fill_value=0),
            0.0,
        )
        return dataclasses.replace(self, offsets=self.offsets + extra)


@dataclasses.dataclass(frozen=True)
class RandomEffectDataset:
    """All buckets for one random-effect coordinate, plus entity placement.

    ``entity_bucket``/``entity_pos`` map entity code -> (bucket idx,
    position) for model lookup; -1 for entities with no active data.
    ``passive_rows`` are example rows excluded from training by the
    active-data cap, still scored at CD time.
    """

    id_name: str
    shard_name: str
    buckets: tuple[EntityBucket, ...]
    num_entities: int
    entity_bucket: np.ndarray  # i32[num_entities]
    entity_pos: np.ndarray  # i32[num_entities]
    passive_rows: np.ndarray  # i64[num_passive] global example rows
    num_global_features: int

    def _device_bucket_full(self, i: int) -> EntityBucket:
        """Per-bucket device-upload memo: every consumer (COO coordinates,
        the factored coordinate, stripped dense variants sharing the
        per-row leaves) resolves through ONE upload per bucket. A full
        bucket requested after its STRIPPED variant reuses the stripped
        upload's per-row leaves and only adds the COO arrays."""
        memo = self.__dict__.setdefault("_device_bucket_memo", {})
        hit = memo.get(i)
        if hit is None:
            stripped = self.__dict__.get(
                "_device_bucket_stripped_memo", {}
            ).get(i)
            b = self.buckets[i]
            if stripped is not None:
                coo = accounted_upload(
                    lambda: jax.device_put((b.values, b.rows, b.cols))
                )
                hit = dataclasses.replace(
                    stripped, values=coo[0], rows=coo[1], cols=coo[2]
                )
            else:
                hit = accounted_upload(lambda: jax.device_put(b))
            memo[i] = hit
        return hit

    def device_buckets(self) -> tuple[EntityBucket, ...]:
        """Device copies of the buckets, uploaded once and cached — every
        coordinate/fit over this dataset shares one HBM copy."""
        return tuple(
            self._device_bucket_full(i) for i in range(len(self.buckets))
        )

    def dense_designs(self) -> tuple:
        """Per-bucket PACKED dense device designs as [E, R*K] rows
        (row-major per entity; solvers reshape inside jit — see
        coordinates._packed_dense_batch), or None where the COO layout
        wins — built host-side once, cached like device_buckets. Bucket by
        bucket: densify on the host (span ``layout``), place (span
        ``upload``), drop the host copy."""
        from photon_ml_tpu.game.coordinates import _bucket_dense_design

        cached = self.__dict__.get("_dense_designs")
        if cached is None:
            designs = []
            for b in self.buckets:
                with span("layout"), span("re_layout.densify"):
                    x = _bucket_dense_design(b)
                designs.append(
                    None if x is None
                    else accounted_upload(lambda: jax.device_put(x))
                )
            cached = tuple(designs)
            object.__setattr__(self, "_dense_designs", cached)
        return cached

    def device_buckets_for_dense(self) -> tuple[EntityBucket, ...]:
        """Device buckets with the COO arrays STRIPPED for buckets that
        solve on their dense design (the dense path never touches
        values/rows/cols — uploading them would double the HBM/transfer
        cost). Per-row leaves are SHARED with :meth:`device_buckets`'s
        uploads when those exist, so a dataset serving both a COO consumer
        (e.g. the factored coordinate) and a dense one holds one copy of
        everything and the full COO only where someone needs it."""
        cached = self.__dict__.get("_device_buckets_dense")
        if cached is None:
            dense = self.dense_designs()
            memo = self.__dict__.setdefault("_device_bucket_memo", {})
            smemo = self.__dict__.setdefault(
                "_device_bucket_stripped_memo", {}
            )
            out = []
            for i, (b, x) in enumerate(zip(self.buckets, dense)):
                if x is None:
                    out.append(self._device_bucket_full(i))
                    continue
                full = memo.get(i)
                if full is not None:
                    # COO already resident for another consumer — reuse
                    # its leaves, nothing new to upload
                    out.append(full)
                    continue
                # (1, 1) stubs: a per-entity (E, 1) placeholder would PAD
                # its lanes 1->128 on TPU — 70 MB of pure padding per stub
                # at 138K entities
                stub = np.zeros((1, 1), np.float32)
                stub_i = np.zeros((1, 1), np.int32)
                stripped = accounted_upload(
                    lambda: jax.device_put(
                        dataclasses.replace(
                            b, values=stub, rows=stub_i, cols=stub_i
                        )
                    )
                )
                smemo[i] = stripped  # later full requests reuse the leaves
                out.append(stripped)
            cached = tuple(out)
            object.__setattr__(self, "_device_buckets_dense", cached)
        return cached

    def device_buckets_stripped(self) -> tuple[EntityBucket, ...]:
        """Device buckets that hold the per-row leaves alone (labels,
        offsets, weights, row_index, entity codes): the COO arrays and the
        projections are (1, 1) stubs. For the factored coordinate, whose
        design is one array over all buckets and whose solves are
        ``latent_dim`` wide whatever an entity observed. Uploaded once."""
        cached = self.__dict__.get("_device_buckets_stripped")
        if cached is None:
            stub = np.zeros((1, 1), np.float32)
            stub_i = np.zeros((1, 1), np.int32)
            cached = tuple(
                accounted_upload(
                    lambda b=b: jax.device_put(dataclasses.replace(
                        b, values=stub, rows=stub_i, cols=stub_i,
                        projection=stub_i)))
                for b in self.buckets)
            object.__setattr__(self, "_device_buckets_stripped", cached)
        return cached

    def to_summary_string(self) -> str:
        """RandomEffectDataSet.toSummaryString analog (:174-197): per-bucket
        geometry + active/passive split."""
        n_active = int(np.sum(self.entity_bucket >= 0))
        lines = [
            f"RandomEffectDataset(id={self.id_name}, shard={self.shard_name}, "
            f"active_entities={n_active}/{self.num_entities}, "
            f"passive_rows={len(self.passive_rows)})"
        ]
        for i, b in enumerate(self.buckets):
            lines.append(
                f"  bucket {i}: entities={b.num_entities} "
                f"rows/entity={b.rows_per_entity} "
                f"local_features={b.num_local_features} "
                f"nnz/entity={b.values.shape[1]}"
            )
        return "\n".join(lines)


_PEARSON_STD_EPS = 1e-8  # MathConst.MEDIUM_PRECISION_TOLERANCE_THRESHOLD


def _pearson_keep_mask(
    nv: np.ndarray,
    nc: np.ndarray,
    ne: np.ndarray,
    y_of_nnz: np.ndarray,
    y_act: np.ndarray,
    ent_of_row: np.ndarray,
    act_counts: np.ndarray,
    num_global: int,
    ratio: float,
) -> np.ndarray:
    """Keep mask over nnz: per entity, retain the top
    ceil(ratio * num_rows) features by |Pearson(feature, label)|.

    Vectorized analog of LocalDataSet.computePearsonCorrelationScore
    (LocalDataSet.scala:221-282) + featureSelectionOnActiveData
    (RandomEffectDataSet.scala:420-434): a near-constant feature is treated
    as the intercept — the FIRST such feature per entity scores 1, later
    duplicates 0. Sums follow the reference exactly (sparse sums; zero rows
    contribute only to the label moments).
    """
    n_ent = len(act_counts)
    # per-(entity, feature) sums over the entity's nnz
    pair_key = ne * np.int64(num_global) + nc
    uniq, inv = np.unique(pair_key, return_inverse=True)
    s_v = np.bincount(inv, weights=nv, minlength=len(uniq))
    s_vv = np.bincount(inv, weights=nv * nv, minlength=len(uniq))
    s_vy = np.bincount(inv, weights=nv * y_of_nnz, minlength=len(uniq))
    p_ent = (uniq // np.int64(num_global)).astype(np.int64)

    # per-entity label moments over ALL active rows
    n_e = act_counts.astype(np.float64)
    ly = np.bincount(ent_of_row, weights=y_act, minlength=n_ent)
    lyy = np.bincount(ent_of_row, weights=y_act * y_act, minlength=n_ent)

    n_p = n_e[p_ent]
    numerator = n_p * s_vy - s_v * ly[p_ent]
    std = np.sqrt(np.abs(n_p * s_vv - s_v * s_v))
    denominator = std * np.sqrt(
        np.maximum(n_p * lyy[p_ent] - ly[p_ent] ** 2, 0.0)
    )
    score = np.abs(numerator / (denominator + 1e-12))
    constant = std < _PEARSON_STD_EPS
    if np.any(constant):
        # first constant feature per entity acts as the intercept (score 1)
        c_idx = np.nonzero(constant)[0]
        first = np.zeros(len(uniq), bool)
        # uniq is sorted by (entity, col): the first constant per entity is
        # the one whose predecessor constant has a different entity
        is_first = np.ones(len(c_idx), bool)
        is_first[1:] = p_ent[c_idx[1:]] != p_ent[c_idx[:-1]]
        first[c_idx[is_first]] = True
        score = np.where(constant, np.where(first, 1.0, 0.0), score)

    # rank within entity by descending score; keep rank < ceil(ratio * n_e)
    order = np.lexsort((-score, p_ent))
    starts = np.searchsorted(p_ent[order], np.arange(n_ent))
    rank = np.empty(len(uniq), np.int64)
    rank[order] = np.arange(len(uniq)) - starts[p_ent[order]]
    k_e = np.ceil(ratio * n_e).astype(np.int64)
    keep_pair = rank < k_e[p_ent]
    return keep_pair[inv]


def build_random_effect_dataset(
    data: GameDataset,
    id_name: str,
    shard_name: str,
    active_rows_per_entity: Optional[int] = None,
    min_rows_per_entity: int = 1,
    features_to_samples_ratio: Optional[float] = None,
    seed: int = 0,
    dtype=jnp.float32,
    class_by_features: bool = True,
) -> RandomEffectDataset:
    """Group, cap, project, and bucket one random-effect coordinate's data.

    ``class_by_features=False`` classes the entities by their rows alone
    (the factored coordinate: every entity's solve is ``latent_dim`` wide,
    whatever it observed); a bucket's local width is then its widest
    entity's and costs the solve nothing.

    Fully vectorized host build: sorting/searchsorted/bincount over bulk
    arrays with one small Python loop over geometry CLASSES (tens), never
    over entities — the ingest-rate answer to the reference's cluster-side
    groupByKey (RandomEffectDataSetPartitioner.scala:96-148). Builds 100K
    entities / 1M rows in seconds (tests/test_re_build.py measures).
    """
    with span("re_layout.group"):  # sort, cap, regroup, project
        if id_name not in data.id_columns:
            raise KeyError(f"unknown id column '{id_name}'; have {sorted(data.id_columns)}")
        idc = data.id_columns[id_name]
        batch = data.shard(shard_name)
        n = data.num_rows
        num_global = batch.num_features
        rng = np.random.default_rng(seed)

        np_dtype = np.dtype(dtype)
        vals = np.asarray(batch.values)
        rows = np.asarray(batch.rows)
        cols = np.asarray(batch.cols)
        # valid nnz only (value != 0 excludes padding); drop padded-row nnz
        live = (vals != 0) & (rows < n)
        vals, rows, cols = vals[live], rows[live], cols[live]

        codes = np.asarray(idc.codes)  # [n]

        # --- active/passive row selection (vectorized reservoir cap) ---
        # group rows by entity with a random within-group order: rank < cap keeps
        # a uniform sample per entity (the reservoir-with-rescale semantics of
        # RandomEffectDataSet.scala:294-357)
        cap = active_rows_per_entity
        if cap is None:
            # no cap, no sample: the order within an entity decides nothing,
            # and a stable sort leaves each entity's rows ascending
            grp_order = np.argsort(codes, kind="stable")
        else:
            rand_key = rng.random(n)
            # entity-grouped, random within
            grp_order = np.lexsort((rand_key, codes))
        g_codes = codes[grp_order]
        uniq_codes, grp_starts, grp_counts = np.unique(
            g_codes, return_index=True, return_counts=True
        )
        ent_of_pos = np.searchsorted(uniq_codes, g_codes)
        rank_in_ent = np.arange(n) - grp_starts[ent_of_pos]

        counts_of_pos = grp_counts[ent_of_pos]
        active_pos = counts_of_pos >= min_rows_per_entity
        weights = data.weight.copy()
        if cap is not None:
            capped = counts_of_pos > cap
            active_pos &= ~capped | (rank_in_ent < cap)
            # weight rescale so the capped sample represents the full count
            resc = capped & (rank_in_ent < cap)
            weights[grp_order[resc]] *= counts_of_pos[resc] / cap
        act_rows_unsorted = grp_order[active_pos]
        passive_rows = np.sort(grp_order[~active_pos])

        # --- regroup active rows sorted by (entity, row id) ---
        act_codes_u = codes[act_rows_unsorted]
        if cap is None:  # already entity-major with ascending rows
            act_rows, act_codes = act_rows_unsorted, act_codes_u
        else:
            o = np.lexsort((act_rows_unsorted, act_codes_u))
            act_rows = act_rows_unsorted[o]  # entity-major, row-sorted
            act_codes = act_codes_u[o]
        act_uniq, act_starts, act_counts = np.unique(
            act_codes, return_index=True, return_counts=True
        )
        n_act = len(act_rows)
        n_ent = len(act_uniq)
        ent_of_row = np.searchsorted(act_uniq, act_codes)  # [n_act]
        local_row = np.arange(n_act) - act_starts[ent_of_row]

        # per global row: its local row id and entity index (-1 if inactive)
        row_local = np.full(n, -1, np.int64)
        row_local[act_rows] = local_row
        row_ent = np.full(n, -1, np.int64)
        row_ent[act_rows] = ent_of_row

        # --- nnz of active rows, sorted by (entity, local row) ---
        keep_nnz = row_ent[rows] >= 0
        nv, nr, nc = vals[keep_nnz], rows[keep_nnz], cols[keep_nnz]
        # segment_sum contract: rows sorted per entity. (entity, local row)
        # is the row's place among the active rows: one key, one stable sort
        row_place = np.full(n, -1, np.int64)
        row_place[act_rows] = np.arange(n_act)
        o2 = np.argsort(row_place[nr], kind="stable")
        nv, nc, ngr = nv[o2], nc[o2], nr[o2]
        ne, nlr = row_ent[ngr], row_local[ngr]

        if features_to_samples_ratio is not None:
            # per-entity Pearson feature selection for low-data entities
            # (RandomEffectDataSet.scala:420-434)
            keep = _pearson_keep_mask(
                nv,
                nc,
                ne,
                y_of_nnz=np.asarray(data.response)[ngr],
                y_act=np.asarray(data.response)[act_rows],
                ent_of_row=ent_of_row,
                act_counts=act_counts,
                num_global=num_global,
                ratio=float(features_to_samples_ratio),
            )
            nv, nc, ne, nlr = nv[keep], nc[keep], ne[keep], nlr[keep]

        nnz_counts = np.bincount(ne, minlength=n_ent).astype(np.int64)
        nnz_starts = np.concatenate([[0], np.cumsum(nnz_counts)[:-1]])
        slot = np.arange(len(nv)) - nnz_starts[ne]

        # --- per-entity projection: unique observed global cols ---
        pair_key = ne * np.int64(num_global) + nc
        uniq_pairs = np.unique(pair_key)
        proj_ent = uniq_pairs // num_global
        proj_col = (uniq_pairs % num_global).astype(np.int64)
        proj_counts = np.bincount(proj_ent, minlength=n_ent).astype(np.int64)
        proj_starts = np.concatenate([[0], np.cumsum(proj_counts)[:-1]])
        proj_slot = np.arange(len(uniq_pairs)) - proj_starts[proj_ent]
        # local col id of each nnz = rank of its col in its entity's projection
        local_col = np.searchsorted(uniq_pairs, pair_key) - nnz_starts_like(
            proj_starts, ne
        )

    with span("re_layout.bucket"):  # classes, then one fill a class
        # --- geometry classes: pow2 (R, K), merged down to a bounded number;
        # a class's nnz width is its own fullest entity's ---
        Rs = _next_pow2_arr(act_counts)
        Ks = _next_pow2_arr(np.maximum(proj_counts, 1))
        geom = np.stack(
            [Rs, Ks if class_by_features else np.ones_like(Ks)], axis=1)
        fine, fine_of_ent, fine_counts = np.unique(
            geom, axis=0, return_inverse=True, return_counts=True
        )
        fine_of_ent = fine_of_ent.reshape(-1)
        target = merge_geometry_classes(fine, fine_counts, MAX_GEOMETRY_CLASSES)
        kept, class_of_fine = np.unique(target, return_inverse=True)
        rk = np.array(
            [[fine[target == t, 0].max(), fine[target == t, 1].max()]
             for t in kept], np.int64,
        ).reshape(len(kept), 2)
        # np.unique sorted `fine` by (R, K); a merged class sits where its
        # first member sat, so order the classes by their own (R, K) again
        class_order = np.lexsort((rk[:, 1], rk[:, 0]))
        class_rank = np.empty(len(kept), np.int64)
        class_rank[class_order] = np.arange(len(kept))
        class_of_ent = class_rank[class_of_fine[fine_of_ent]]
        rk = rk[class_order]
        if not class_by_features:
            np.maximum.at(rk[:, 1], class_of_ent, Ks)
        nz_max = np.zeros(len(kept), np.int64)
        np.maximum.at(nz_max, class_of_ent, nnz_counts)
        classes = np.concatenate(
            [rk, _next_pow2_arr(np.maximum(nz_max, 1))[:, None]], axis=1
        )

        # position of each entity within its bucket (order of appearance =
        # ascending entity code, since act_uniq is sorted)
        ent_pos = np.zeros(n_ent, np.int64)
        for b_idx in range(len(classes)):
            sel = class_of_ent == b_idx
            ent_pos[sel] = np.arange(int(sel.sum()))

        num_entities = idc.num_entities
        entity_bucket = np.full(num_entities, -1, np.int32)
        entity_pos = np.full(num_entities, -1, np.int32)
        entity_bucket[act_uniq] = class_of_ent
        entity_pos[act_uniq] = ent_pos

        response = data.response
        offset = data.offset

        buckets = []
        for b_idx, (R, K, NZ) in enumerate(classes):
            R, K, NZ = int(R), int(K), int(NZ)
            esel = class_of_ent == b_idx
            E = int(esel.sum())
            bcode = act_uniq[esel].astype(np.int32)

            bv = np.zeros((E, NZ))
            br = np.full((E, NZ), R - 1, np.int32)
            bc = np.zeros((E, NZ), np.int32)
            bl = np.zeros((E, R))
            bo = np.zeros((E, R))
            bw = np.zeros((E, R))
            bp = np.full((E, K), num_global, np.int32)
            brix = np.full((E, R), -1, np.int32)

            # rows of this class's entities
            rsel = esel[ent_of_row]
            d_e = ent_pos[ent_of_row[rsel]]
            d_r = local_row[rsel]
            src = act_rows[rsel]
            bl[d_e, d_r] = response[src]
            bo[d_e, d_r] = offset[src]
            bw[d_e, d_r] = weights[src]
            brix[d_e, d_r] = src

            # nnz of this class's entities
            zsel = esel[ne]
            z_e = ent_pos[ne[zsel]]
            z_s = slot[zsel]
            bv[z_e, z_s] = nv[zsel]
            br[z_e, z_s] = nlr[zsel]
            bc[z_e, z_s] = local_col[zsel]

            # projections of this class's entities
            psel = esel[proj_ent]
            p_e = ent_pos[proj_ent[psel]]
            p_s = proj_slot[psel]
            bp[p_e, p_s] = proj_col[psel]

            # leaves stay HOST numpy (transfer-free build; coordinates upload
            # once via RandomEffectDataset.device_buckets)
            buckets.append(
                EntityBucket(
                    values=bv.astype(np_dtype),
                    rows=br,
                    cols=bc,
                    labels=bl.astype(np_dtype),
                    offsets=bo.astype(np_dtype),
                    weights=bw.astype(np_dtype),
                    projection=bp,
                    entity_codes=bcode,
                    row_index=brix,
                    num_local_features=K,
                    num_global_features=num_global,
                )
            )

    return RandomEffectDataset(
        id_name=id_name,
        shard_name=shard_name,
        buckets=tuple(buckets),
        num_entities=num_entities,
        entity_bucket=entity_bucket,
        entity_pos=entity_pos,
        passive_rows=passive_rows.astype(np.int64),
        num_global_features=num_global,
    )


def nnz_starts_like(starts: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Gather segment starts for each element's segment id."""
    return starts[idx]


def _next_pow2_arr(x: np.ndarray) -> np.ndarray:
    """Vectorized _next_pow2 over an int array."""
    x = np.asarray(x, np.int64)
    out = np.ones_like(x)
    nz = x > 1
    out[nz] = 1 << np.ceil(np.log2(x[nz])).astype(np.int64)
    return out
