"""North-star end-to-end benchmark: the BASELINE.md headline pipeline as
ONE driver invocation at MovieLens-20M scale.

MovieLens-20M-shaped synthetic data (20M ratings, 138,493 users, 26,744
movies — the real dataset is not fetchable in this hermetic environment,
so labels are planted from a known GLMix model, which also gives the AUC a
ground-truth ceiling):

    generate -> write TrainingExampleAvro (native columnar writer)
      -> `cli train` (feature indexing -> ingest -> GLMix fit:
         FE + per-user RE + per-movie RE + factored MF -> validation AUC
         -> model + index-map save)
      -> `cli score` (model load -> ingest validation -> score ->
         ScoringResultAvro write -> AUC)

Reference analog: the reference's full-pipeline fixture test
(photon-client/src/integTest/.../cli/game/training/DriverTest.scala:75-411)
at Yahoo-music scale; here the same composition is proven at the
north-star's 20M rows on one chip.

Prints ONE JSON line: metric north_star_e2e, value = end-to-end pipeline
seconds (train driver + scoring driver; fixture generation/write are
bench infrastructure and reported separately in detail).
"""

from __future__ import annotations

import json
import os
import tempfile
import time

import numpy as np

N_ROWS = 20_000_000
N_VAL = 1_000_000
N_USERS = 138_493
N_MOVIES = 26_744
# Feature volumes are sized so the whole pipeline's device residency fits
# one 16 GB chip alongside the 4 coordinates (FE tiled layout + two dense
# RE bucket sets + the MF kron refit): ~2.5 GB of design data at 20M
# rows. Larger per-row feature budgets belong to the multi-host path.
FE_SPACE = 2_000  # movieFeatures id space
FE_NNZ = 4  # movieFeatures per movie
CTX = 4  # movieCtx / userCtx dims


def _generate(rng, n, movie_cols, movie_vals, emb_m, emb_u, w_g, a_u, b_m):
    """One split's rows: ids, label, and the three feature bags."""
    users = rng.integers(0, N_USERS, size=n)
    movies = rng.integers(0, N_MOVIES, size=n)

    # logit = w_g . movieFeatures + a_u . emb_m + b_m . emb_u
    logit = (
        np.einsum("ij,ij->i", movie_vals[movies], w_g[movie_cols[movies]])
        + np.einsum("ij,ij->i", emb_m[movies], a_u[users])
        + np.einsum("ij,ij->i", emb_u[users], b_m[movies])
    )
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float64)

    bags = {
        "movieFeatures": (
            np.arange(0, (n + 1) * FE_NNZ, FE_NNZ, dtype=np.int64),
            movie_cols[movies].reshape(-1).astype(np.int32),
            movie_vals[movies].reshape(-1).astype(np.float64),
        ),
        "movieCtx": (
            np.arange(0, (n + 1) * CTX, CTX, dtype=np.int64),
            np.tile(
                np.arange(FE_SPACE, FE_SPACE + CTX, dtype=np.int32), n
            ),
            emb_m[movies].reshape(-1).astype(np.float64),
        ),
        "userCtx": (
            np.arange(0, (n + 1) * CTX, CTX, dtype=np.int64),
            np.tile(
                np.arange(
                    FE_SPACE + CTX, FE_SPACE + 2 * CTX, dtype=np.int32
                ),
                n,
            ),
            emb_u[users].reshape(-1).astype(np.float64),
        ),
    }
    return users, movies, y, logit, bags


def _opt(opt_type="lbfgs", max_iterations=15):
    return {
        "type": opt_type,
        "max_iterations": max_iterations,
        "tolerance": 1e-7,
        "regularization": "l2",
        "regularization_weight": 1.0,
    }


def main():
    import shutil

    from photon_ml_tpu.utils import setup_logging

    setup_logging()  # phase timers (timed()) go to stderr for diagnosis
    workdir = tempfile.mkdtemp(prefix="northstar_")
    try:
        _run(workdir)
    finally:
        # the fixture is ~9 GB — never leave it behind for the next round
        shutil.rmtree(workdir, ignore_errors=True)


def _run(workdir):
    from photon_ml_tpu.data.avro import write_training_examples_fast

    rng = np.random.default_rng(0)
    t_gen0 = time.perf_counter()
    # static world: per-movie sparse features + ctx embeddings + truth
    movie_cols = rng.integers(
        0, FE_SPACE, size=(N_MOVIES, FE_NNZ)
    ).astype(np.int32)
    movie_vals = rng.normal(size=(N_MOVIES, FE_NNZ))
    emb_m = rng.normal(size=(N_MOVIES, CTX)) * 0.7
    emb_u = rng.normal(size=(N_USERS, CTX)) * 0.7
    w_g = rng.normal(size=FE_SPACE) * 0.4
    a_u = rng.normal(size=(N_USERS, CTX)) * 0.4
    b_m = rng.normal(size=(N_MOVIES, CTX)) * 0.4

    names = (
        [f"f{i}" for i in range(FE_SPACE)]
        + [f"mctx{j}" for j in range(CTX)]
        + [f"uctx{j}" for j in range(CTX)]
    )
    user_vocab = [str(u) for u in range(N_USERS)]
    movie_vocab = [str(m) for m in range(N_MOVIES)]

    paths = {}
    gen_s = write_s = 0.0
    for split, n in (("train", N_ROWS), ("val", N_VAL)):
        t0 = time.perf_counter()
        users, movies, y, logit, bags = _generate(
            rng, n, movie_cols, movie_vals, emb_m, emb_u, w_g, a_u, b_m
        )
        gen_s += time.perf_counter() - t0
        p = os.path.join(workdir, f"{split}.avro")
        t0 = time.perf_counter()
        write_training_examples_fast(
            p, y, bags, names,
            {"userId": (users, user_vocab), "movieId": (movies, movie_vocab)},
        )
        write_s += time.perf_counter() - t0
        paths[split] = p
        if split == "val":
            # ground-truth ceiling for the AUC the fit should approach
            order = np.argsort(logit)
            ranks = np.empty(n)
            ranks[order] = np.arange(1, n + 1)
            pos = y > 0.5
            n_pos, n_neg = int(pos.sum()), int((~pos).sum())
            auc_ceiling = (
                (ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                / (n_pos * n_neg)
            )
    gen_s, write_s = round(gen_s, 3), round(write_s, 3)
    t_fixture = time.perf_counter() - t_gen0

    model_out = os.path.join(workdir, "model")
    config = {
        "task": "logistic",
        "input": {
            "format": "avro",
            "paths": [paths["train"]],
            "feature_shards": {
                "movieFeatures": ["movieFeatures"],
                "movieCtx": ["movieCtx"],
                "userCtx": ["userCtx"],
            },
            "id_columns": ["userId", "movieId"],
        },
        "validation": {"paths": [paths["val"]]},
        "coordinates": {
            "fixed": {
                "type": "fixed_effect",
                "shard_name": "movieFeatures",
                "optimizer": _opt("lbfgs", 10),
            },
            "per-user": {
                "type": "random_effect",
                "shard_name": "movieCtx",
                "id_name": "userId",
                "optimizer": _opt("newton", 8),
                "active_rows_per_entity": 256,
            },
            "per-movie": {
                "type": "random_effect",
                "shard_name": "userCtx",
                "id_name": "movieId",
                "optimizer": _opt("newton", 8),
                "active_rows_per_entity": 256,
            },
            "mf": {
                "type": "factored_random_effect",
                "shard_name": "movieCtx",
                "id_name": "userId",
                "latent_dim": 2,
                "mf_iterations": 1,
                "optimizer": _opt("lbfgs", 8),
                "latent_optimizer": _opt("lbfgs", 8),
                # the kron refit is built from ACTIVE rows; a tight cap
                # bounds its nnz at rows_cap * users * dim * latent
                "active_rows_per_entity": 32,
            },
        },
        "num_iterations": 1,
        "evaluators": ["auc"],
        "output_dir": model_out,
    }

    from photon_ml_tpu import telemetry
    from photon_ml_tpu.cli.train import run as train_run
    from photon_ml_tpu.cli.score import run as score_run

    # optional span JSONL / metrics flush via PHOTON_TRACE_OUT /
    # PHOTON_TELEMETRY_OUT; fetch + compile counters ride the JSON below
    # either way, so "upload+compile dominated" phases are quantified
    telemetry.configure_from_env()

    # an hours-scale pipeline must never be silent (BENCH_r05 timed out
    # with zero output): one progress line every 30s to stderr via the
    # progress logger, with span path + rows/s + HBM (train_run's own
    # heartbeat is redundant under ours — disabled to avoid double lines)
    config["heartbeat"] = False
    with telemetry.Heartbeat(interval=30.0):
        t0 = time.perf_counter()
        train_summary = train_run(config)
        train_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        score_summary = score_run(
            model_dir=os.path.join(model_out, "best"),
            input_spec={**config["input"], "paths": [paths["val"]]},
            output_path=os.path.join(workdir, "scores.avro"),
            evaluators=("auc",),
        )
        score_s = time.perf_counter() - t0

    import jax

    from photon_ml_tpu.telemetry.report import RunReport

    # roofline summary over the whole pipeline (None = no instrumented
    # executables ran / "unknown" cost fields on analysis-less backends):
    # MFU, bandwidth utilization, comms fraction, compile-time share, and
    # the top executables by cost — the attribution BENCH_r05 lacked
    device_util = RunReport.from_live().device_utilization()

    pipeline_s = train_s + score_s
    print(
        json.dumps(
            {
                "metric": "north_star_e2e",
                "value": round(pipeline_s, 1),
                "unit": "s",
                "vs_baseline": None,
                "detail": {
                    "rows_train": N_ROWS,
                    "rows_val": N_VAL,
                    "users": N_USERS,
                    "movies": N_MOVIES,
                    "train_driver_s": round(train_s, 1),
                    "score_driver_s": round(score_s, 1),
                    "fixture_generate_s": gen_s,
                    "fixture_write_s": write_s,
                    "fixture_total_s": round(t_fixture, 1),
                    "validation_auc": train_summary.get("best_metric"),
                    "auc_ceiling_planted": round(float(auc_ceiling), 4),
                    "scoring_auc": score_summary.get("metrics", {}).get(
                        "auc"
                    ),
                    "phases": [
                        {
                            k: (round(v, 2) if isinstance(v, float) else v)
                            for k, v in e.items()
                            if k in ("iteration", "coordinate", "seconds")
                        }
                        for e in train_summary.get("history", [])
                    ],
                    "platform": jax.devices()[0].platform,
                    # shared telemetry schema (counters of snapshot()):
                    # device_fetches / device_fetch_seconds are the host's
                    # waits on the device, jit_compiles the compile count
                    "telemetry": telemetry.snapshot()["counters"],
                    "device_utilization": device_util,
                },
            },
            default=float,
        )
    )

    trace_out = os.environ.get("PHOTON_TRACE_OUT")
    if trace_out:
        # run report beside the bench JSON: the phase-time tree and
        # fetch/compile accounting, readable without opening Perfetto
        import sys

        from photon_ml_tpu.telemetry.report import RunReport, report_path

        report = RunReport.from_live()
        # per-member suffixing in a fleet (matches the trace sink's path)
        md_path = report_path(telemetry.member_artifact_path(trace_out))
        with open(md_path, "w", encoding="utf-8") as fh:
            fh.write(report.to_markdown())
        report.save_json(md_path[: -len(".md")] + ".json")
        print(f"run report: {md_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
