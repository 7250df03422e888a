"""``game_fit``'s driver for a GLMix fit with several random effects: ragged
fixed-effect rows, one id column and one sparse shard a random-effect
coordinate (``generators/movielens_mixed.py``'s splits: per shard padded
``<shard>_cols`` / ``<shard>_vals``, ids by column), the same set-up and the
same unit of work.

``shapes()`` describes every coordinate for the counts functions: the
fixed effect with its own nonzeros, each random effect with its geometry
buckets as the program built them (entities, padded rows and local
features a bucket, and whether the bucket solves on its dense design) and
with the unpadded sums ``counts/re_newton_pass.py`` prices a Newton pass
from. Set-up refuses, before it generates a row, a program that does not
bound its geometry classes (``random_effect_data.MAX_GEOMETRY_CLASSES``,
which ``shapes()`` reports), and after the build one that does not report
its random-effect layout (counters ``re.<coordinate>.buckets``): such a
program, the parent of PR 30, would lay 138,493 users out in dozens of pow2
classes, each a compile, and run float32 products in one bfloat16 pass.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.drivers import game_fit

#: the least a rehearsal keeps of each count, so that its laws stay
#: feasible: a user's ratings go to distinct movies
REHEARSAL_USERS = 8
MOVIES_PER_MEAN_USER = 4


class Driver(game_fit.Driver):
    def __init__(self, config, traffic, seed, rows=None, force_tiled=False):
        full = dict(config["data"]["shape"])
        super().__init__(config, traffic, seed, rows=None,
                         force_tiled=force_tiled)
        if rows is not None:
            # a rehearsal: rows, users and movies shrink together; the floor
            # of 20 and the laws' sigmas stay
            scale = rows / full["rows"]
            shape = self.shape
            shape["rows"] = max(int(rows), 64)
            shape["validation_rows"] = max(
                int(full["validation_rows"] * scale), 16)
            shape["users"] = max(int(full["users"] * scale), REHEARSAL_USERS)
            total = shape["rows"] + shape["validation_rows"]
            mean = math.ceil(total / shape["users"])
            shape["movies"] = max(
                int(full["movies"] * scale), MOVIES_PER_MEAN_USER * mean)
            shape["rated_movies"] = max(
                int(shape["movies"] * full["rated_movies"] / full["movies"]),
                2 * mean)
        # the reference reads an id column's entity count through the shape
        self.shape["entities"] = dict(config["data"]["entities"])
        self.shape["shards"] = dict(config["data"]["shards"])
        self.re_coordinates = {
            name: c for name, c in config["train"]["coordinates"].items()
            if c["type"] == "random_effect"}

    # -- set-up --------------------------------------------------------------

    def _dataset(self, split: dict):
        from photon_ml_tpu.game import build_game_dataset
        from photon_ml_tpu.ops.sparse import SparseBatch

        n = len(split["y"])
        shards = {}
        for name, width in self.shape["shards"].items():
            vals = split[name + "_vals"]
            row, slot = np.nonzero(vals)  # the pad slots hold value 0
            shards[name] = SparseBatch.from_coo(
                values=vals[row, slot], rows=row,
                cols=split[name + "_cols"][row, slot], labels=split["y"],
                num_features=int(width),
            )
        return build_game_dataset(
            response=split["y"], feature_shards=shards,
            id_columns={c: split[c] for c in self.shape["entities"]})

    def setup(self) -> None:
        from photon_ml_tpu import telemetry
        from photon_ml_tpu.game import random_effect_data

        # read first, because `shapes()` reports it and a program without it
        # should not spend minutes laying out classes it cannot bound
        self.max_buckets = getattr(
            random_effect_data, "MAX_GEOMETRY_CLASSES", None)
        if self.max_buckets is None:
            raise RuntimeError(
                "the program does not bound its random-effect geometry "
                "classes (game/random_effect_data.py::MAX_GEOMETRY_CLASSES)")
        super().setup()
        counters = telemetry.snapshot()["counters"]
        missing = [name for name in self.re_coordinates
                   if not counters.get(f"re.{name}.buckets")]
        if missing:
            raise RuntimeError(
                "the program does not report the random-effect layout of "
                f"{missing} (counters re.<coordinate>.buckets)")

    # -- what the timed path produced ------------------------------------------

    def _re_table(self, model) -> np.ndarray:
        """[entities, features] coefficients of one random effect, a row
        per id; ids the fit never saw stay zero (they score zero)."""
        entities = int(self.shape[self.config["data"]["entities"][
            model.id_name]])
        features = int(self.shape["shards"][model.shard_name])
        table = np.zeros((entities, features))
        vocab = np.asarray(model.vocab).astype(np.int64)
        for bm in model.buckets:
            codes = np.asarray(bm.entity_codes)
            coef = np.asarray(bm.coefficients, np.float64)
            proj = np.asarray(bm.projection)
            ok = (codes >= 0)[:, None] & (proj < features)
            e, k = np.nonzero(ok)
            table[vocab[codes[e]], proj[e, k]] = coef[e, k]
        return table

    def shapes(self) -> dict:
        train = self.raw["train"]
        out = {"rows": self.shape["rows"], "coordinates": {}}
        for name, c in self.coordinates.items():
            if hasattr(c, "re_data"):
                out["coordinates"][name] = dict(
                    _re_shape(c, self.shape["rows"]),
                    max_buckets=int(self.max_buckets))
                continue
            entry = {
                "kind": "fixed_effect_coo",
                "nnz": int(np.count_nonzero(train[c.shard_name + "_vals"])),
                "features": int(self.shape["shards"][c.shard_name])}
            tiled = getattr(c, "_tiled", None)
            if tiled is not None and not hasattr(tiled, "parts"):
                entry.update(
                    kind="fixed_effect", T=int(tiled.num_tiles),
                    S=int(tiled.vals.shape[2]), B=int(tiled.num_blocks),
                    strided=tiled.rlo is None)
            out["coordinates"][name] = entry
        return out


def _re_shape(coordinate, rows: int) -> dict:
    """One random-effect coordinate as the program laid it out. A bucket is
    [entities, padded rows, padded local features, padded nonzeros, dense];
    ``pass`` holds the sums over the entities' OWN rows r and local
    features k that one Newton pass is priced from."""
    data = coordinate.re_data
    buckets, r_all, k_all = [], [], []
    for b, x in zip(data.buckets, coordinate._dense_x):
        buckets.append([
            int(b.num_entities), int(b.rows_per_entity),
            int(b.num_local_features), int(b.values.shape[1]),
            x is not None])
        r_all.append((np.asarray(b.row_index) >= 0).sum(axis=1))
        k_all.append((np.asarray(b.projection)
                      < b.num_global_features).sum(axis=1))
    r = np.concatenate(r_all).astype(np.float64)
    k = np.concatenate(k_all).astype(np.float64)
    return {
        "kind": "random_effect", "buckets": buckets, "rows": int(rows),
        # the rows' mean local features: `counts/glmix_fit.py` prices a row
        # at K dense features, and the global width would overcount
        "features": float((r * k).sum() / r.sum()),
        "global_features": int(data.num_global_features),
        "entities": int(len(r)),
        "pass": {"rk": float((r * k).sum()), "rkk": float((r * k * k).sum()),
                 "kkk": float((k ** 3).sum()), "k_min": float(k.min()),
                 "r_sum": float(r.sum())},
    }
