"""``game_fit_mixed``'s driver for a GAME fit with a FACTORED random effect
(matrix factorization): the same splits, set-up and unit of work, one more
coordinate type.

Set-up refuses, before it generates a row, a program whose factored
coordinate refits its projection over a materialised Kronecker design
(``game/factored.py::KRON_FREE_REFIT`` is what it asks for): at this
configuration's size that design is 288M nonzeros in five device arrays and
an L-BFGS over XLA's gather / scatter-add, minutes a fit, and the host-side
``np.repeat`` / ``argsort`` over it would not end inside a run's limit.

What is compared of the factored coordinate is its OWN scores
``(A x) . c_entity`` over the TRAINING rows (a rotation or rescaling of
the latent space leaves them alone, and they pass through the coordinate's
own row order, which no validation score does). The reference starts from
the program's initial ``A`` and scores the rows this driver names, both
handed over as data: ``shape["latent_init"][<coordinate>]`` [K, d] and
``shape["compared_rows"][<coordinate>]`` (the training rows' entity ids and
one-hot columns: ``benchmark/tests/readings.py``'s half-batch fault fits
every second training row, and scores all of them with what it fitted).
``shapes()`` describes the coordinate to
``counts/glmix_fit.py`` as its latent per-entity solves (kind
``random_effect``, ``latent_dim`` features a row: an undercount, the refit
is ``counts/mf_refit_pass.py``'s) and carries what that file needs under
``mf``.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers import game_fit_mixed

FACTORED = "factored_random_effect"


class Driver(game_fit_mixed.Driver):
    def __init__(self, config, traffic, seed, rows=None, force_tiled=False):
        super().__init__(config, traffic, seed, rows=rows,
                         force_tiled=force_tiled)
        self.factored = {
            name: c for name, c in config["train"]["coordinates"].items()
            if c["type"] == FACTORED}
        for name, c in self.factored.items():
            # the one-hot shard is as wide as the movies are many
            self.shape["shards"][c["shard_name"]] = int(self.shape["movies"])
            if force_tiled:  # a rehearsal off the chip: interpret mode
                self.train_json["coordinates"][name]["layout"] = "tiled"

    def setup(self) -> None:
        from photon_ml_tpu import telemetry
        from photon_ml_tpu.game import factored

        if not getattr(factored, "KRON_FREE_REFIT", False):
            raise RuntimeError(
                "the program's factored coordinate refits its projection "
                "over a materialised Kronecker design (game/factored.py has "
                "no KRON_FREE_REFIT): rows x latent_dim nonzeros in five "
                "device arrays, minutes a fit at this configuration's size")
        super().setup()
        counters = telemetry.snapshot()["counters"]
        for name in self.factored:
            if counters.get(f"mf.{name}.kron_nnz_materialised", 1):
                raise RuntimeError(
                    f"coordinate {name}: counter mf.{name}."
                    "kron_nnz_materialised is missing or not 0")

    def fit(self, annotate: bool = False) -> dict:
        # the reference starts from the program's own initial projection
        # and scores the training rows, whichever of them it fitted
        init = self.shape.setdefault("latent_init", {})
        rows = self.shape.setdefault("compared_rows", {})
        train = self.raw["train"]
        for name, c in self.factored.items():
            if name not in init and hasattr(self, "coordinates"):
                init[name] = np.asarray(
                    self.coordinates[name].initialize_model()
                    .projection.matrix, np.float32)
            rows.setdefault(name, {
                "ids": train[c["id_name"]],
                "cols": train[c["shard_name"] + "_cols"][:, 0]})
        return super().fit(annotate)

    def outputs(self) -> dict:
        out = {"coefficients": {}, "steps": self.fits[-1]["steps"]}
        n, n_val = self.train.num_rows, self.validation_data.num_rows
        for name, m in self.last_model.models.items():
            if name in self.factored:
                out["coefficients"][name] = np.asarray(
                    self.coordinates[name].score(m), np.float64)[:n]
            else:
                out["coefficients"][name] = np.asarray(
                    m.coefficients, np.float64)
        scores = np.asarray(
            self.last_model.score(self.validation_data), np.float64)
        if not np.all(np.isfinite(scores)):
            raise FloatingPointError("non-finite validation score")
        out["validation_scores"] = scores[:n_val]
        return out

    def shapes(self) -> dict:
        held = {name: self.coordinates[name] for name in self.factored}
        coordinates = self.coordinates
        self.coordinates = {
            k: v for k, v in coordinates.items() if k not in held}
        try:
            out = super().shapes()
        finally:
            self.coordinates = coordinates
        rows = int(self.shape["rows"])
        for name, c in held.items():
            data = c.re_data
            out["coordinates"][name] = {
                "kind": "random_effect",
                "buckets": [
                    [int(b.num_entities), int(b.rows_per_entity),
                     int(c.latent_dim)] for b in data.buckets],
                "rows": rows, "features": float(c.latent_dim),
                "global_features": int(data.num_global_features),
                "max_buckets": int(self.max_buckets),
                "mf": {"nnz": int(c._nnz), "rows": rows,
                       "latent_dim": int(c.latent_dim),
                       "features": int(data.num_global_features)},
            }
            if hasattr(c._design, "num_tiles"):  # the kernels' own shares
                out["coordinates"][name]["T"] = int(c._design.num_tiles)
        return out
