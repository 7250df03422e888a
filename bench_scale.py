"""Benchmark: BASELINE config #5 — full GAME at ~1B coefficients, one chip.

Shape mirrors the MovieLens-20M GAME stack (FE + per-user RE + per-item RE
+ MF latent factors) at the reference's headline coefficient scale
(/root/reference/README.md:73): 1M user models x 512 local dims + 1M item
models x 512 + 2M latent rows x 16 + a 10K-feature FE ≈ **1.056B trained
coefficients**.

HBM residency math (v5e, 16 GB):
  - each RE coefficient table is N*K*4 = 2.0 GB and stays RESIDENT for its
    whole fit (ShardedCoefficientTable, donated in-place chunk updates);
  - the dense training data (R*4 bytes per coefficient) does NOT fit and
    streams per entity chunk: a 125K-entity chunk is 2.0 GB of design +
    ~2 GB optimizer state, double-buffered against the next chunk's
    generation. Peak live ≈ table 2 + chunk 2x2 + state 2 ≈ 8 GB.
  - across a mesh the table and chunks shard over the entity axis
    (tests/test_streaming.py + __graft_entry__.dryrun_multichip prove the
    sharded path on the 8-device virtual CPU mesh).

Chunk data is generated ON DEVICE from a planted per-entity model, so the
number is the trainer's and not the upload's (16 chunks x 2 GB would be
~5 s at the 6.8 GB/s host->device rate chip_smoke.py measured, PR 21);
the host-upload streaming path is the same trainer code, exercised by
tests/test_streaming.py, and has no chip number yet (ROADMAP S8).

Prints one JSON line: game_1B_coeffs_trained_per_sec.
"""

from __future__ import annotations

import functools
import json
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.game.streaming import (
        ShardedCoefficientTable,
        StreamingRandomEffectTrainer,
    )
    from photon_ml_tpu.ops.dense import DenseBatch
    from photon_ml_tpu.optim import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )

    cfg = OptimizerConfig(
        max_iterations=8,
        tolerance=1e-5,
        lbfgs_history=4,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def gen_chunk(key, E, R, K):
        """Planted logistic per-entity problems: X ~ N(0,1), w* ~ N(0, .3),
        offsets stand in for the residual scores of the other coordinates."""
        kx, kw, ky, ko = jax.random.split(key, 4)
        x = jax.random.normal(kx, (E, R, K), jnp.float32)
        w_true = jax.random.normal(kw, (E, K), jnp.float32) * 0.3
        off = jax.random.normal(ko, (E, R), jnp.float32) * 0.2
        z = jnp.einsum("erk,ek->er", x, w_true) + off
        y = (
            jax.random.uniform(ky, (E, R)) < jax.nn.sigmoid(z)
        ).astype(jnp.float32)
        return DenseBatch(
            x=x, labels=y, offsets=off, weights=jnp.ones((E, R), jnp.float32)
        )

    def run_re(name, n_entities, dim, chunk_entities, rows, seed,
               opt_cfg=cfg):
        table = ShardedCoefficientTable(n_entities, dim)
        trainer = StreamingRandomEffectTrainer("logistic", opt_cfg)
        key = jax.random.key(seed)

        def chunk_source(i):
            return lambda: gen_chunk(
                jax.random.fold_in(key, i), chunk_entities, rows, dim
            )

        chunks = [
            (start, chunk_source(i))
            for i, start in enumerate(
                range(0, n_entities, chunk_entities)
            )
        ]
        # warm every compiled path at the REAL shapes (including the
        # full-size table's chunk reader/writer — jits are
        # shape-specialized), then reset the table: compile time is not
        # trainer throughput
        trainer.train(table, chunks[:1])
        table = ShardedCoefficientTable(n_entities, dim)

        t0 = time.perf_counter()
        stats = trainer.train(table, chunks)  # final fetch = true sync
        secs = time.perf_counter() - t0
        # per-entity tracker sample OUTSIDE the timed window (the packed
        # telemetry fetch is a host wait the trainer proper never makes):
        # the FIRST chunk's entities only — labeled as such below
        tr_stats = trainer.train(
            ShardedCoefficientTable(n_entities, dim),
            chunks[:1],
            with_tracker=True,
        ).tracker
        its = tr_stats.iterations
        pct = {
            f"p{p}": int(np.percentile(its, p)) for p in (50, 90, 99)
        }
        return {
            "name": name,
            "coefficients": stats.total_coefficients,
            "entities": stats.total_entities,
            "chunks": stats.num_chunks,
            "mean_iterations": round(stats.mean_iterations, 2),
            "tracker_sample_entities": len(its),  # first chunk only
            "iteration_percentiles_first_chunk": pct,
            # reasons >= 3: a tolerance test fired (codes: 0 not-converged,
            # 1 max-iterations, 2 line-search stall; optim/common.py)
            "converged_frac_first_chunk": round(
                float(np.mean(tr_stats.reasons >= 3)), 4
            ),
            "stalled_frac_first_chunk": round(
                float(np.mean(tr_stats.reasons == 2)), 4
            ),
            "seconds": round(secs, 3),
            "table_gb": round(table.nbytes / 2**30, 2),
        }

    parts = []
    parts.append(run_re("per_user_re", 1_000_000, 512, 125_000, 8, seed=1))
    parts.append(run_re("per_item_re", 1_000_000, 512, 125_000, 8, seed=2))
    parts.append(run_re("mf_latent", 2_000_000, 16, 1_000_000, 8, seed=3))

    total_coeffs = sum(p["coefficients"] for p in parts)
    total_secs = sum(p["seconds"] for p in parts)
    rate = total_coeffs / total_secs

    print(
        json.dumps(
            {
                "metric": "game_1B_coeffs_trained_per_sec",
                "value": round(rate, 1),
                "unit": "coeffs/s",
                "vs_baseline": None,
                "detail": {
                    "total_coefficients": total_coeffs,
                    "total_seconds": round(total_secs, 3),
                    "parts": parts,
                    "platform": jax.devices()[0].platform,
                },
            }
        )
    )


if __name__ == "__main__":
    main()
