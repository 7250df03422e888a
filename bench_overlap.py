"""Host->device streaming overlap measurement, wired into the bench.py /
bench_suite.py driver chain (``bench_suite --overlap``) so the streaming-
overlap number gets a per-round trajectory instead of living only in
PERF_NOTES.md.

Streams HOST numpy chunks through StreamingRandomEffectTrainer twice:
through the ingest pipeline's bounded double buffer (prefetch=True: a
background feeder thread runs decode + the H2D ``device_put`` of chunk
i+1 while chunk i's solve runs — ``photon_ml_tpu.ingest.double_buffered``,
the same facility the out-of-core ChunkStream uploader uses) and fully
synchronous (prefetch=False: a scalar fetch between chunks serializes
feed and solve). Reports both wall-clocks and the overlap factor as the
``overlap_factor`` metric — a factor > 1 proves the solve overlapped
decode+upload instead of serializing behind them.

Budget: ``PHOTON_BENCH_BUDGET_S`` is honored — a run starting past the
deadline emits a valid ``{"metric": "overlap_factor", "truncated": true}``
line instead of silence.

Caveat: the overlap factor is bounded by (transfer + compute) /
max(transfer, compute): it approaches 2x only where the two are
comparable. The builders' one reading (1.14x, PERF_NOTES "Round 4: 1B")
was taken where transfer dwarfed compute; on the machine builders have
now host->device runs at 6.8 GB/s (chip_smoke.py, PR 21) and the factor
is not measured (ROADMAP S8). The mechanics (enqueue ordering, donation,
result correctness) are identical either way, and both arms must produce
the SAME table.
"""

from __future__ import annotations

import json
import time

import numpy as np

OVERLAP_METRICS = ("overlap_factor",)


def run_overlap(deadline=None) -> dict[str, float | None]:
    """Measure the prefetch-vs-sync overlap factor; emits one JSON line.
    Returns ``{metric: value-or-None}`` for the ``--gate`` flow."""
    from bench_suite import truncated_line

    if deadline is not None and time.monotonic() > deadline:
        print(truncated_line("overlap_factor"), flush=True)
        return {"overlap_factor": None}

    from photon_ml_tpu.game.streaming import (
        ShardedCoefficientTable,
        StreamingRandomEffectTrainer,
    )
    from photon_ml_tpu.ops.dense import DenseBatch
    from photon_ml_tpu.optim import (
        OptimizerConfig,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )

    import jax

    n_ent, rows, k, n_chunks = 16_384, 32, 64, 8
    per = n_ent // n_chunks
    rng = np.random.default_rng(0)
    W = rng.normal(size=(n_ent, k)).astype(np.float32)
    cfg = OptimizerConfig(
        optimizer_type=OptimizerType.LBFGS,
        max_iterations=15,
        tolerance=1e-7,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )

    def chunk(lo, hi):
        X = rng.normal(size=(hi - lo, rows, k)).astype(np.float32)
        z = np.einsum("erk,ek->er", X, W[lo:hi])
        y = (rng.random((hi - lo, rows)) < 1 / (1 + np.exp(-z))).astype(
            np.float32
        )
        return DenseBatch(
            x=X,
            labels=y,
            offsets=np.zeros((hi - lo, rows), np.float32),
            weights=np.ones((hi - lo, rows), np.float32),
        )

    chunks = [
        (i * per, chunk(i * per, (i + 1) * per)) for i in range(n_chunks)
    ]
    chunk_mb = sum(
        leaf.nbytes for leaf in jax.tree.leaves(chunks[0][1])
    ) / 2**20

    results = {}
    tables = {}
    for mode in (True, False):
        trainer = StreamingRandomEffectTrainer(
            "logistic", cfg, prefetch=mode, prefetch_depth=2
        )
        table = ShardedCoefficientTable(n_ent, k)
        trainer.train(table, chunks[:1])  # compile warm-up
        table = ShardedCoefficientTable(n_ent, k)
        t0 = time.perf_counter()
        trainer.train(table, chunks)
        jax.block_until_ready(table.coefficients)
        results["prefetch" if mode else "sync"] = time.perf_counter() - t0
        tables[mode] = table.to_numpy()

    np.testing.assert_allclose(tables[True], tables[False], atol=1e-6)
    factor = results["sync"] / results["prefetch"]
    print(
        json.dumps(
            {
                "metric": "overlap_factor",
                "value": round(factor, 3),
                "unit": "x",
                "vs_baseline": None,
                "detail": {
                    "prefetch_s": round(results["prefetch"], 3),
                    "sync_s": round(results["sync"], 3),
                    "via": "ingest.double_buffered",
                    "prefetch_depth": 2,
                    "chunks": n_chunks,
                    "chunk_mb": round(chunk_mb, 1),
                    "entities": n_ent,
                    "dim": k,
                    "arms_identical": True,
                    "platform": jax.devices()[0].platform,
                    # CPU backend: "device" compute and the feeder thread
                    # share the same cores AND device_put is a memcpy, so
                    # no overlap win is physically available — the run
                    # proves mechanics (ordering, bounded queue, identical
                    # tables), not the speedup
                    "simulated": jax.devices()[0].platform == "cpu",
                },
            }
        ),
        flush=True,
    )
    return {"overlap_factor": round(factor, 3)}


def main():
    from bench_suite import budget_deadline

    run_overlap(deadline=budget_deadline())


if __name__ == "__main__":
    main()
