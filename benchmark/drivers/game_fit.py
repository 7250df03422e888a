"""The system under test for training cells: what ``cli train`` builds from
its JSON config, held in one process, fitted again and again from zero.

Set-up is ``parse_game_config`` -> ``GameEstimator`` ->
``_build_coordinates(data, mesh=None)`` on data generated in memory; one unit
of work is one call of ``run_coordinate_descent`` as ``GameEstimator.fit``
makes it, with ``initial_models=None``. This file is the only one of the
benchmark that imports the program.
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 rows: int | None = None, force_tiled: bool = False):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.shape = dict(config["data"]["shape"])
        if rows is not None:
            # a rehearsal: rows, users and fixed-effect features shrink
            # together, so that the small problem keeps the cell's rows per
            # user and nonzeros per feature (its conditioning); nnz per row
            # and features per user never change
            scale = rows / self.shape["rows"]
            for key, least in (("rows", 8), ("validation_rows", 8),
                               ("users", 8), ("fe_features", 128)):
                if self.shape.get(key):
                    self.shape[key] = max(int(self.shape[key] * scale), least)
        self.train_json = train_json(config, traffic, force_tiled)
        self.fits: list[dict] = []  # one record per fit, window or not
        self.spans: dict[str, float] = {}
        self.raw = None

    # -- set-up --------------------------------------------------------------

    def _dataset(self, split: dict):
        from photon_ml_tpu.game import build_game_dataset
        from photon_ml_tpu.ops.sparse import SparseBatch

        names = self.config["data"]
        n, k = split["cols"].shape
        d = int(self.shape["fe_features"])
        shards = {
            names["fe_shard"]: SparseBatch.from_coo(
                values=split["vals"].reshape(-1),
                rows=np.repeat(np.arange(n, dtype=np.int64), k),
                cols=split["cols"].reshape(-1),
                labels=split["y"], num_features=d,
            )
        }
        ids = {}
        if split["users"] is not None:
            r = split["xu"].shape[1]
            shards[names["re_shard"]] = SparseBatch.from_coo(
                values=split["xu"].reshape(-1),
                rows=np.repeat(np.arange(n, dtype=np.int64), r),
                cols=np.tile(np.arange(r, dtype=np.int64), n),
                labels=split["y"], num_features=r,
            )
            ids[names["id_column"]] = split["users"]
        return build_game_dataset(
            response=split["y"], feature_shards=shards, id_columns=ids)

    def setup(self) -> None:
        from photon_ml_tpu.config import parse_game_config
        from photon_ml_tpu.game import GameEstimator
        from photon_ml_tpu.game.coordinate_descent import ValidationSpec
        from photon_ml_tpu.optim.guard import GuardSpec

        t0 = time.perf_counter()
        gen = importlib.import_module(
            "benchmark.generators." + self.config["data"]["generator"])
        self.raw = gen.generate(self.shape, self.seed)
        self.spans["generate_data"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        self.train = self._dataset(self.raw["train"])
        self.validation_data = self._dataset(self.raw["validation"])
        self.spans["build_dataset"] = time.perf_counter() - t0

        self.game_config = parse_game_config(self.train_json)
        self.estimator = GameEstimator(self.game_config)
        # `cli train` runs guarded unless its config says otherwise
        self.guard = GuardSpec()
        t0 = time.perf_counter()
        self.coordinates = self.estimator._build_coordinates(
            self.train, mesh=None)
        self.spans["build_coordinates"] = time.perf_counter() - t0
        self.validation = ValidationSpec(
            data=self.validation_data,
            evaluators=list(self.game_config.evaluators))
        t0 = time.perf_counter()
        record = self.fit()  # compiles every shape the window uses
        if not record["ok"]:
            raise RuntimeError(
                "the warm-up fit failed: "
                + record.get("error", "non-finite step or an AOT fallback"))
        self.spans["warmup_fit"] = time.perf_counter() - t0

    # -- one unit of work ------------------------------------------------------

    def fit(self, annotate: bool = False) -> dict:
        """One whole fit from zero coefficients. Returns its record. With
        ``annotate`` the fit and each coordinate's stretch of it (update,
        scores, validation) are written into the profiler's trace."""
        from photon_ml_tpu import telemetry
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )

        # a cache hit: the same coordinates with their per-fit state reset,
        # as every GameEstimator.fit over the same data gets them
        coords = self.estimator._build_coordinates(self.train, mesh=None)
        if any(coords[k] is not v for k, v in self.coordinates.items()):
            raise RuntimeError("the estimator rebuilt its coordinates")
        trackers = []
        marks = _Marks(
            list(coords) * self.game_config.num_iterations, annotate)
        record = {"ok": False, "start": time.perf_counter()}
        fallbacks = telemetry.counter("xla.fallback_calls")
        before = fallbacks.value
        try:
            result = run_coordinate_descent(
                coords,
                task=self.game_config.task,
                num_iterations=self.game_config.num_iterations,
                validation=self.validation,
                initial_models=None,
                on_step=lambda e: (
                    trackers.append(getattr(
                        coords[e["coordinate"]], "last_tracker", None)),
                    marks.step()),
                guard=self.guard,
            )
        except Exception as e:  # noqa: BLE001 - a fit that raises has failed
            record["error"] = f"{type(e).__name__}: {e}"
            record["end"] = time.perf_counter()
            self.fits.append(record)
            return record
        finally:
            marks.close()
        record["end"] = time.perf_counter()
        record["steps"] = [
            step_facts(entry, tr)
            for entry, tr in zip(result.history, trackers)
        ]
        finite = all(
            np.isfinite(s["loss"]) and np.isfinite(s["seconds"])
            and all(np.isfinite(v) for v in s["metrics"].values())
            for s in record["steps"]
        )
        record["ok"] = bool(finite and fallbacks.value == before)
        # ISSUE 23's unit of work: every training row once per CD iteration
        record["work"] = float(
            self.shape["rows"] * self.game_config.num_iterations)
        self.last_model = result.model
        self.fits.append(record)
        return record

    # -- what the timed path produced --------------------------------------------

    def outputs(self) -> dict:
        """The last fit's coefficients, per-step losses and validation
        metrics, and its model's scores on the validation rows."""
        from photon_ml_tpu.game.models import RandomEffectModel

        out = {"coefficients": {}, "steps": self.fits[-1]["steps"]}
        for name, m in self.last_model.models.items():
            if isinstance(m, RandomEffectModel):
                out["coefficients"][name] = self._re_table(m)
            else:
                out["coefficients"][name] = np.asarray(
                    m.coefficients, np.float64)
        n_val = self.validation_data.num_rows
        scores = np.asarray(
            self.last_model.score(self.validation_data), np.float64)
        if not np.all(np.isfinite(scores)):
            raise FloatingPointError("non-finite validation score")
        out["validation_scores"] = scores[:n_val]
        return out

    def _re_table(self, model) -> np.ndarray:
        """[users, re_features] coefficients, a row per user id; users the
        fit never saw stay zero (they score zero)."""
        table = np.zeros(
            (int(self.shape["users"]), int(self.shape["re_features"])))
        vocab = np.asarray(model.vocab).astype(np.int64)
        for bm in model.buckets:
            codes = np.asarray(bm.entity_codes)
            coef = np.asarray(bm.coefficients, np.float64)
            proj = np.asarray(bm.projection)
            for k in range(proj.shape[1]):
                ok = (codes >= 0) & (proj[:, k] < table.shape[1])
                table[vocab[codes[ok]], proj[ok, k]] = coef[ok, k]
        return table

    def shapes(self) -> dict:
        """Sizes the counts functions need, read off the built layouts."""
        out = {"rows": self.shape["rows"], "coordinates": {}}
        for name, c in self.coordinates.items():
            tiled = getattr(c, "_tiled", None)
            if tiled is not None:
                out["coordinates"][name] = {
                    "kind": "fixed_effect", "T": int(tiled.num_tiles),
                    "S": int(tiled.vals.shape[2]),
                    "B": int(tiled.num_blocks),
                    "nnz": int(self.shape["rows"])
                    * int(self.shape["fe_nnz_per_row"]),
                    "features": int(tiled.num_features),
                }
            elif hasattr(c, "re_data"):
                out["coordinates"][name] = {
                    "kind": "random_effect",
                    "buckets": [
                        [int(b.num_entities), int(b.rows_per_entity),
                         int(b.num_local_features)]
                        for b in c.re_data.buckets
                    ],
                    "rows": int(self.shape["rows"]),
                    "features": int(self.shape["re_features"]),
                }
            else:
                out["coordinates"][name] = {"kind": "fixed_effect_coo"}
        return out

    def free(self) -> None:
        """Drop everything the program holds on the device."""
        import jax

        for attr in ("coordinates", "estimator", "validation", "train",
                     "validation_data", "last_model"):
            self.__dict__.pop(attr, None)
        gc.collect()
        jax.clear_caches()
        gc.collect()


class _Marks:
    """Host annotations in the profiler's trace: ``bench:unit`` around the
    whole fit and ``bench:coordinate:<name>`` from the end of one update's
    bookkeeping to the end of the next (initial scores fall to the first)."""

    def __init__(self, names: list[str], on: bool):
        #: ``names``: the coordinate of every step of the fit, in order
        self.names, self.on, self.i, self.open = names, on, 0, []
        if on:
            self._enter("bench:unit")
            self._enter("bench:coordinate:" + names[0])

    def _enter(self, name: str) -> None:
        import jax

        a = jax.profiler.TraceAnnotation(name)
        a.__enter__()
        self.open.append(a)

    def step(self) -> None:
        if not self.on:
            return
        self.open.pop().__exit__(None, None, None)
        self.i += 1
        if self.i < len(self.names):
            self._enter("bench:coordinate:" + self.names[self.i])

    def close(self) -> None:
        while self.open:
            self.open.pop().__exit__(None, None, None)


def train_json(config: dict, traffic: dict, force_tiled: bool) -> dict:
    """The cell's ``cli train`` config: the configuration's coordinates,
    each completed by what the traffic mix says for its type."""
    out = {k: v for k, v in config["train"].items() if k != "coordinates"}
    out["num_iterations"] = traffic["num_iterations"]
    out["evaluators"] = traffic["evaluators"]
    out["coordinates"] = {}
    for name, coord in config["train"]["coordinates"].items():
        coord = {**coord, **traffic["per_type"][coord["type"]]}
        if force_tiled and coord["type"] == "fixed_effect":
            coord["layout"] = "tiled"  # rehearsal off the chip: interpret mode
        out["coordinates"][name] = coord
    return out


def step_facts(entry: dict, tracker) -> dict:
    """One (iteration, coordinate) update: its seconds, the objective value
    its solver ended on (summed over entities for a random effect), the
    solver's iterations (mean over entities), and validation metrics."""
    loss = iters = float("nan")
    if tracker is not None and hasattr(tracker, "final_value"):
        loss, iters = float(tracker.final_value), float(tracker.iterations)
    elif tracker is not None and hasattr(tracker, "final_values"):
        loss = float(np.sum(tracker.final_values, dtype=np.float64))
        iters = float(np.mean(tracker.iterations))
    return {
        "iteration": int(entry["iteration"]),
        "coordinate": entry["coordinate"],
        "seconds": float(entry["seconds"]),
        "loss": loss,
        "solver_iterations": iters,
        "metrics": {k: float(v) for k, v in entry.get("metrics", {}).items()},
    }
