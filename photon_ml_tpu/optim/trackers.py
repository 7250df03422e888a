"""Optimization trackers: aggregate solve telemetry per coordinate update.

Reference analog: photon-api optimization/*Tracker.scala —
FixedEffectOptimizationTracker wraps one OptimizationStatesTracker;
RandomEffectOptimizationTracker aggregates per-entity trackers into
convergence-reason counts (countConvergenceReasons) and iteration
StatCounter stats (getNumIterationStats). Here the vmapped bucket solves
already return per-entity iteration/reason ARRAYS, so aggregation is a few
bincounts — no RDD reduce.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from photon_ml_tpu.optim.common import CONVERGENCE_REASON_NAMES
from photon_ml_tpu.telemetry import metrics as _metrics


@functools.lru_cache(maxsize=1)
def _fe_packer():
    """(iterations, reason, value, grad_norms[iterations]) as ONE vector in
    the solve's float dtype (the two small ints are exact in it): one
    executable, so that the tracker costs one fetch and no eager ops."""
    import jax.numpy as jnp

    from photon_ml_tpu.telemetry import instrumented_jit

    def pack(iterations, reason, value, grad_norms):
        dt = jnp.result_type(value, grad_norms)
        return jnp.stack([
            iterations.astype(dt), reason.astype(dt), value.astype(dt),
            grad_norms[iterations].astype(dt),
        ])

    return instrumented_jit(pack, name="fe_tracker_pack", multi_shape=True)


@dataclasses.dataclass(frozen=True)
class FixedEffectOptimizationTracker:
    """One solve's terminal telemetry (FixedEffectOptimizationTracker)."""

    iterations: int
    reason: str
    final_value: float
    final_grad_norm: float

    @staticmethod
    def from_result(res) -> "FixedEffectOptimizationTracker":
        """ONE packed host fetch (the wait on the solve), accounted by
        ``telemetry.sync_fetch`` like the random-effect tracker's."""
        import jax.numpy as jnp

        from photon_ml_tpu.telemetry import sync_fetch

        packed = sync_fetch(
            _fe_packer()(
                jnp.asarray(res.iterations), jnp.asarray(res.reason),
                jnp.asarray(res.value), jnp.asarray(res.grad_norms),
            ),
            label="fe_tracker",
        )
        it = int(packed[0])
        _metrics.counter("fe_solves").inc()
        _metrics.histogram("fe_solve_iterations").observe(it)
        return FixedEffectOptimizationTracker(
            iterations=it,
            reason=CONVERGENCE_REASON_NAMES.get(int(packed[1]), "Unknown"),
            final_value=float(packed[2]),
            final_grad_norm=float(packed[3]),
        )

    def to_summary_string(self) -> str:
        return (
            f"iterations={self.iterations} reason={self.reason} "
            f"value={self.final_value:.6g} |grad|={self.final_grad_norm:.3g}"
        )


_PERCENTILES = (5, 25, 50, 75, 95)


def _pct(a: np.ndarray) -> dict[str, float]:
    if len(a) == 0:
        return {f"p{p}": 0.0 for p in _PERCENTILES}
    qs = np.percentile(a, _PERCENTILES)
    return {f"p{p}": float(q) for p, q in zip(_PERCENTILES, qs)}


@dataclasses.dataclass(frozen=True)
class RandomEffectOptimizationTracker:
    """Per-entity solve telemetry for one coordinate update, aggregated
    across geometry buckets (RandomEffectOptimizationTracker analog).

    ``final_values`` (optional) are the per-entity terminal objective values;
    together with ``iterations`` they feed the distribution summaries the
    reference aggregates per entity (RandomEffectOptimizationTracker.scala
    getNumIterationStats / per-state StatCounters)."""

    iterations: np.ndarray  # i32[n_entities]
    reasons: np.ndarray  # i32[n_entities]
    final_values: np.ndarray | None = None  # f32[n_entities]

    @staticmethod
    def from_device_parts(
        its: list, reasons: list, vals: list
    ) -> "RandomEffectOptimizationTracker":
        """Build from per-bucket DEVICE arrays (padding already sliced off)
        with ONE packed host fetch: the f32 terminal values ride the i32
        concat via bitcast — every device->host fetch is a host wait, so
        all three telemetry vectors cross together (and the crossing is
        accounted by telemetry.sync_fetch)."""
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.telemetry import sync_fetch

        if not its:
            z = np.zeros(0, np.int32)
            return RandomEffectOptimizationTracker(
                iterations=z, reasons=z, final_values=np.zeros(0, np.float32)
            )
        packed = sync_fetch(
            jnp.concatenate(
                [
                    jnp.concatenate(its).astype(jnp.int32),
                    jnp.concatenate(reasons).astype(jnp.int32),
                    jax.lax.bitcast_convert_type(
                        jnp.concatenate(vals).astype(jnp.float32), jnp.int32
                    ),
                ]
            ),
            label="re_tracker",
        )
        n = len(packed) // 3
        tracker = RandomEffectOptimizationTracker(
            iterations=packed[:n],
            reasons=packed[n : 2 * n],
            final_values=packed[2 * n :].view(np.float32),
        )
        _metrics.counter("re_solved_entities").inc(n)
        # per-entity solve-iteration distribution, the registry-level view
        # of getNumIterationStats (fed once per coordinate update)
        _metrics.histogram("re_solve_iterations").observe_many(
            tracker.iterations
        )
        return tracker

    def count_convergence_reasons(self) -> dict[str, int]:
        """countConvergenceReasons analog: reason name -> entity count."""
        out: dict[str, int] = {}
        codes, counts = np.unique(self.reasons, return_counts=True)
        for code, count in zip(codes, counts):
            name = CONVERGENCE_REASON_NAMES.get(int(code), "Unknown")
            out[name] = out.get(name, 0) + int(count)
        return out

    def iteration_stats(self) -> dict[str, float]:
        """getNumIterationStats analog (count/mean/std/min/max)."""
        it = self.iterations
        if len(it) == 0:
            return {"count": 0, "mean": 0.0, "stdev": 0.0, "min": 0.0, "max": 0.0}
        return {
            "count": int(len(it)),
            "mean": float(it.mean()),
            "stdev": float(it.std()),
            "min": float(it.min()),
            "max": float(it.max()),
        }

    def percentile_summary(self) -> dict[str, dict[str, float]]:
        """Distribution summaries of per-entity iterations and terminal
        objective values (p5/p25/p50/p75/p95 — the per-entity StatCounter
        aggregation of RandomEffectOptimizationTracker.scala)."""
        out = {"iterations": _pct(self.iterations)}
        if self.final_values is not None:
            out["final_loss"] = _pct(self.final_values)
        return out

    def to_summary_string(self) -> str:
        s = self.iteration_stats()
        reasons = ", ".join(
            f"{k}: {v}" for k, v in sorted(self.count_convergence_reasons().items())
        )
        pcts = self.percentile_summary()
        it_p = pcts["iterations"]
        lines = (
            f"entities={s['count']} iterations(mean={s['mean']:.2f}, "
            f"std={s['stdev']:.2f}, min={s['min']:.0f}, max={s['max']:.0f}, "
            f"p50={it_p['p50']:.0f}, p95={it_p['p95']:.0f}) "
            f"convergence {{{reasons}}}"
        )
        if "final_loss" in pcts:
            fl = pcts["final_loss"]
            lines += (
                f" final_loss(p5={fl['p5']:.4g}, p50={fl['p50']:.4g}, "
                f"p95={fl['p95']:.4g})"
            )
        return lines


@dataclasses.dataclass(frozen=True)
class FactoredRandomEffectOptimizationTracker:
    """Per-MF-iteration telemetry for the factored coordinate: each
    alternation step pairs the latent-space RE solve's per-entity tracker
    with the latent-matrix refit's tracker (the reference keeps exactly this
    pair per iteration, FactoredRandomEffectOptimizationProblem.scala's
    Array[(RandomEffectOptimizationTracker, FixedEffectOptimizationTracker)]).
    ``matrix`` is None in fixed-projection mode (no refit happens)."""

    steps: tuple  # of (RandomEffectOptimizationTracker, FE tracker | None)

    @property
    def final_value(self) -> float:
        """The objective the update ended on: the last refit's final value
        (data loss + the projection's L2 term), or in fixed-projection mode
        the entities' final values summed."""
        re_t, fe_t = self.steps[-1]
        if fe_t is not None:
            return float(fe_t.final_value)
        return float(np.sum(re_t.final_values, dtype=np.float64))

    @property
    def iterations(self) -> float:
        """The last alternation's latent solves: the entities' mean
        iterations (a refit's own count is ``steps[i][1].iterations``)."""
        its = self.steps[-1][0].iterations
        return float(np.mean(its)) if len(its) else 0.0

    def to_summary_string(self) -> str:
        lines = []
        for i, (re_t, fe_t) in enumerate(self.steps):
            lines.append(f"MF iteration {i}:")
            lines.append("  latent RE: " + re_t.to_summary_string())
            if fe_t is not None:
                lines.append("  latent matrix: " + fe_t.to_summary_string())
        return "\n".join(lines)
