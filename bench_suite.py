"""Benchmark suite: the remaining BASELINE.md configs beyond bench.py (#1)
and bench_game.py (#4). Prints ONE JSON line PER config.

  #2: linear regression + TRON, sparse 1M x 10K (elastic-net is L1-bearing
      and TRON rejects L1 per OptimizerFactory parity, so TRON runs the L2
      member of the elastic family; an OWLQN elastic-net line is measured
      alongside for the L1 half).
  #3: Poisson regression with offset training + per-coefficient box
      constraints.

Timing recipe: warm up (with other argument values than the timed
call's), then time one call and wait for it by fetching a scalar.

Budget: ``PHOTON_BENCH_BUDGET_S`` caps this process's wall clock. When the
budget runs out mid-suite, the remaining configs are SKIPPED but still
emit valid JSON — ``{"metric": ..., "value": null, "truncated": true}`` —
so harness consumers see every expected metric instead of an rc=124 with
partial output (the BENCH_r05 failure mode).

Gate: ``--gate baseline.json`` compares this run's rows/s values against a
baseline (a ``{metric: value}`` dict keyed by THESE suite metric names,
or an earlier run's bench JSON lines) and exits 3 when any metric
regressed more than ``--gate-threshold`` (default 20%) — the CI perf
gate. A gate that compared nothing exits 2 — whether the baseline shares
no metric names with the suite or the budget truncated every gateable
metric — so a mis-wired or starved gate can never pass silently.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

GATE_EXIT_CODE = 3

SUITE_METRICS = (
    "linreg_tron_1Mx10K_rows_per_sec_per_chip",
    "linreg_owlqn_elasticnet_1Mx10K_rows_per_sec_per_chip",
    "poisson_offsets_box_1Mx10K_rows_per_sec_per_chip",
    # per-kernel utilization (telemetry.profile, every dispatch sampled):
    # achieved MFU of the profiled GLM value+grad solve and the fraction
    # of the timed window spent inside it — both HIGHER is better, so
    # they ride the default gate direction, not LOWER_IS_BETTER_METRICS
    "glm_value_grad_mfu",
    "hot_dispatch_fraction",
)

#: The solver configs (#2, #3 + the elastic-net half) — the leading
#: SUITE_METRICS entries, one timed step each; the utilization pair is
#: derived from its own profiled step after them.
_SOLVER_METRICS = SUITE_METRICS[:3]
_UTILIZATION_METRICS = SUITE_METRICS[3:]

#: Gate metrics where a RISE is the regression (wall-time ratios and
#: latency/flatness SLOs); all other gated metrics are rates where a
#: drop regresses.
LOWER_IS_BETTER_METRICS = frozenset({
    "sweep_over_single_ratio",
    "serving_slo_p99_ms",
    "serving_slo_p99_swap_ratio",
    "serving_slo_p99_nearline_ratio",
    "serving_nearline_apply_ms",
    # serving fleet (bench_serving run_serving_fleet_bench): resize-window
    # p99 flatness and hard-kill recovery both regress upward
    "serving_fleet_p99_resize_ratio",
    "serving_fleet_kill_recovery_s",
    # request-scoped tracing (bench_serving run_trace_overhead): traced
    # over untraced wall clock — the tracer's ring+tail-sampling cost
    # per request regresses upward; the acceptance line is <= 1.05
    "serving_trace_overhead_ratio",
    # fleet observability (bench_multichip): time lost waiting at
    # collectives and per-member MFU imbalance both regress upward
    "fleet_collective_wait_fraction",
    "fleet_mfu_spread",
    # freshness conductor (bench_freshness staleness section): seconds
    # from delta-event mtime to registry hot-swap confirmed — the
    # pipeline tier's headline SLO regresses upward
    "event_to_served_staleness_p99_s",
    # quality diagnostics (bench_diagnostics): B=64 bootstrap wall clock
    # as a multiple of one fit — the lane-vectorization claim (<= 2.0 on
    # TPU) regresses upward
    "bootstrap_overhead_ratio",
})


#: Safety margin reserved BEFORE the PHOTON_BENCH_BUDGET_S wall so the
#: process can kill a running sub-benchmark, flush truncated placeholder
#: lines, and write the run report while the harness's outer `timeout -k`
#: has not yet fired. BENCH_r05 lost its whole run to rc=124 because the
#: old deadline ran right up to the wall: the budget check passed, the
#: sub-benchmark was capped AT the remaining budget, and the cleanup after
#: the cap landed past it. Override with PHOTON_BENCH_MARGIN_S.
DEFAULT_BUDGET_MARGIN_S = 30.0


def budget_margin() -> float:
    raw = os.environ.get("PHOTON_BENCH_MARGIN_S")
    if not raw:
        return DEFAULT_BUDGET_MARGIN_S
    try:
        return float(raw)
    except ValueError:
        # a malformed margin must not kill the bench before any metric
        # prints — that would be worse than the rc=124 it guards against
        print(
            f"ignoring malformed PHOTON_BENCH_MARGIN_S={raw!r}; "
            f"using {DEFAULT_BUDGET_MARGIN_S}",
            file=sys.stderr,
        )
        return DEFAULT_BUDGET_MARGIN_S


def budget_deadline(now: float | None = None):
    """Monotonic flush-by deadline from PHOTON_BENCH_BUDGET_S (the budget
    minus the flush margin), or None (no cap). Work must STOP at this
    deadline; the reserved margin pays for truncated-line flushes and the
    run report so the process exits 0 before the outer kill."""
    budget = os.environ.get("PHOTON_BENCH_BUDGET_S")
    if not budget:
        return None
    try:
        budget_s = float(budget)
    except ValueError:
        print(
            f"ignoring malformed PHOTON_BENCH_BUDGET_S={budget!r}; "
            "running uncapped",
            file=sys.stderr,
        )
        return None
    margin = budget_margin()
    # a budget at or below the margin must not silently skip ALL work:
    # keep at least half the budget for benchmarking, and say so
    usable = max(budget_s - margin, budget_s * 0.5)
    if budget_s <= margin:
        print(
            f"PHOTON_BENCH_BUDGET_S={budget_s:g} <= flush margin "
            f"{margin:g}s; keeping {usable:g}s for work — expect "
            "heavy truncation",
            file=sys.stderr,
        )
    return (time.monotonic() if now is None else now) + usable


def truncated_line(metric: str) -> str:
    """The valid-JSON placeholder for a budget-skipped metric."""
    return json.dumps(
        {
            "metric": metric,
            "value": None,
            "unit": None,
            "vs_baseline": None,
            "truncated": True,
        }
    )


def _sparse_problem(rng, n_rows, n_features, nnz_per_row, kind):
    nnz = n_rows * nnz_per_row
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), nnz_per_row)
    cols = rng.integers(0, n_features, size=nnz)
    values = rng.normal(size=nnz)
    w_true = rng.normal(size=n_features) * 0.5
    margins = np.zeros(n_rows)
    np.add.at(margins, rows, values * w_true[cols])
    if kind == "linear":
        y = margins + 0.1 * rng.normal(size=n_rows)
        offsets = None
    elif kind == "poisson":
        offsets = rng.normal(size=n_rows) * 0.3  # exposure offsets
        y = rng.poisson(np.exp(np.clip(0.2 * margins + offsets, -4, 4)))
        y = y.astype(np.float64)
    else:
        raise ValueError(kind)
    return values, rows, cols, y, offsets


def _run(solver, batch, w0, n_rows):
    import jax

    res = solver(w0, batch)
    float(res.value)  # warm-up sync
    t0 = time.perf_counter()
    res = solver(w0 + 1e-6, batch)  # fresh args defeat result caching
    final = float(res.value)
    elapsed = time.perf_counter() - t0
    iters = int(res.iterations)
    # rows/s counts EVERY full pass over the data the solver made —
    # including TRON's truncated-CG Hessian-vector passes
    # (SolveResult.data_passes) — so all optimizer lines are comparable
    passes = int(res.data_passes)
    return {
        "elapsed_s": round(elapsed, 3),
        "iterations": iters,
        "data_passes": passes,
        "final_loss": final,
        "rows_per_sec": round(n_rows * passes / elapsed, 1),
        "platform": jax.devices()[0].platform,
    }


def run_suite(deadline=None) -> dict[str, float | None]:
    """Run the configs in order, emitting one JSON line each; configs past
    the budget deadline emit truncated placeholders instead. Returns
    {metric: rows_per_sec or None}."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.ops.tiled import TiledBatch
    from photon_ml_tpu.optim import (
        BoxConstraints,
        LBFGSConfig,
        TRONConfig,
        glm_adapter,
        lbfgs_solve,
        owlqn_solve,
        tron_solve,
    )

    rng = np.random.default_rng(0)
    n_rows, n_features, nnz_per_row = 1_000_000, 10_000, 20
    w0 = jnp.zeros((n_features,), jnp.float32)
    results: dict[str, float | None] = {}
    cache: dict[str, object] = {}

    def linear_batch():
        if "linear" not in cache:
            values, rows, cols, y, _ = _sparse_problem(
                rng, n_rows, n_features, nnz_per_row, "linear"
            )
            cache["linear"] = TiledBatch.from_coo(
                values=values, rows=rows, cols=cols, labels=y,
                num_features=n_features,
            )
        return cache["linear"]

    # --- config #2: linear + TRON (L2) -----------------------------------
    def run_tron():
        obj = make_objective("squared", l2_weight=1.0)
        tron_cfg = TRONConfig(max_iterations=10, tolerance=0.0)

        def tron_run(w0, b):
            return tron_solve(glm_adapter(obj, b), w0, tron_cfg)

        return _run(jax.jit(tron_run), linear_batch(), w0, n_rows)

    # elastic-net half: OWLQN with l1=0.5, l2=0.5
    def run_owlqn():
        obj_en = make_objective("squared", l2_weight=0.5)
        lcfg = LBFGSConfig(max_iterations=20, tolerance=0.0)

        def owlqn_run(w0, b):
            return owlqn_solve(
                glm_adapter(obj_en, b), w0, jnp.float32(0.5), lcfg
            )

        return _run(jax.jit(owlqn_run), linear_batch(), w0, n_rows)

    # --- config #3: Poisson + offsets + box constraints ------------------
    def run_poisson():
        values, rows, cols, y, offsets = _sparse_problem(
            rng, n_rows, n_features, nnz_per_row, "poisson"
        )
        batch = TiledBatch.from_coo(
            values=values, rows=rows, cols=cols, labels=y,
            offsets=offsets, num_features=n_features,
        )
        obj_p = make_objective("poisson", l2_weight=1.0)
        constraints = BoxConstraints(
            lower=jnp.asarray(np.full(n_features, -0.5), jnp.float32),
            upper=jnp.asarray(np.full(n_features, 0.5), jnp.float32),
        )

        def poisson_run(w0, b):
            return lbfgs_solve(
                glm_adapter(obj_p, b), w0,
                LBFGSConfig(max_iterations=20, tolerance=0.0),
                constraints=constraints,
            )

        return _run(jax.jit(poisson_run), batch, w0, n_rows)

    steps = zip(_SOLVER_METRICS, (run_tron, run_owlqn, run_poisson))
    truncated = False
    for metric, step in steps:
        if truncated or (
            deadline is not None and time.monotonic() > deadline
        ):
            truncated = True  # budget spent: skip everything remaining
            print(truncated_line(metric), flush=True)
            results[metric] = None
            continue
        d = step()
        results[metric] = d["rows_per_sec"]
        print(
            json.dumps(
                {
                    "metric": metric,
                    "value": d["rows_per_sec"],
                    "unit": "rows/s",
                    "vs_baseline": None,
                    "detail": d,
                }
            ),
            flush=True,
        )

    # --- per-kernel utilization (telemetry.profile) ----------------------
    # One profiled GLM value+grad solve over the cached linear batch:
    # instrumented_jit + the dispatch sampler at every=1 give an honest
    # (fetch-synchronized) per-dispatch time, from which achieved MFU and
    # the hot-dispatch fraction of the timed window follow. Unknowable
    # values (no cost analysis / unknown device peak) are SKIPPED with a
    # note, never gated as zero.
    if truncated or (
        deadline is not None and time.monotonic() > deadline
    ):
        for metric in _UTILIZATION_METRICS:
            print(truncated_line(metric), flush=True)
            results[metric] = None
        return results
    from photon_ml_tpu import telemetry

    telemetry.profile.set_sample_every(1)
    obj_glm = make_objective("squared", l2_weight=1.0)
    glm_cfg = LBFGSConfig(max_iterations=20, tolerance=0.0)

    def glm_value_grad(w, b):
        return lbfgs_solve(glm_adapter(obj_glm, b), w, glm_cfg)

    solver = telemetry.instrumented_jit(
        glm_value_grad, name="suite_glm_value_grad"
    )
    batch = linear_batch()
    # warm up: the compile wait lands before the timed window
    float(telemetry.sync_fetch(solver(w0, batch).value, label="warmup"))
    # hot fraction = exclusive profiled seconds accrued DURING the timed
    # window / wall elapsed; the warmup dispatch (compile wait) lands
    # before the snapshot so it can't inflate the fraction
    excl0 = telemetry.profile.exclusive_seconds_by_name().get(
        "suite_glm_value_grad", 0.0
    )
    t0 = time.perf_counter()
    res = solver(w0 + 1e-6, batch)
    float(telemetry.sync_fetch(res.value, label="loss"))
    util_elapsed = time.perf_counter() - t0
    excl1 = telemetry.profile.exclusive_seconds_by_name().get(
        "suite_glm_value_grad", 0.0
    )
    prof = telemetry.profile.merged_profiles(
        names=("suite_glm_value_grad",)
    ).get("suite_glm_value_grad")
    mfu = None if prof is None else prof.get("mfu")
    hot_fraction = None
    if excl1 > excl0 and util_elapsed > 0:
        hot_fraction = round(
            min((excl1 - excl0) / util_elapsed, 1.0), 6
        )
    for metric, value in zip(
        _UTILIZATION_METRICS, (mfu, hot_fraction)
    ):
        print(
            json.dumps(
                {
                    "metric": metric,
                    "value": value,
                    "unit": "fraction",
                    "vs_baseline": None,
                    "detail": {
                        "executable": "suite_glm_value_grad",
                        "profile": prof,
                    },
                }
            ),
            flush=True,
        )
        if value is not None:
            results[metric] = value
        else:
            print(
                f"gate: {metric}: unavailable on this backend (no "
                "cost analysis or unknown device peak) — skipped",
                file=sys.stderr,
            )
    return results


def load_gate_baseline(path: str) -> dict[str, float]:
    """Baseline formats accepted: a bare ``{metric: value}`` dict, JSONL
    of earlier bench output lines (``{"metric": ..., "value": ...}``), or
    — for generality — any report-shaped JSON with ``key_metrics``
    (run_gate errors if its names don't overlap the suite's)."""
    with open(path, encoding="utf-8") as fh:
        text = fh.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict):
        if "key_metrics" in doc:
            doc = doc["key_metrics"]
        return {
            k: float(v)
            for k, v in doc.items()
            if isinstance(v, (int, float))
        }
    out: dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if (
            isinstance(rec, dict)
            and rec.get("metric")
            and isinstance(rec.get("value"), (int, float))
        ):
            out[rec["metric"]] = float(rec["value"])
    return out


def run_gate(
    results: dict[str, float | None], baseline: dict[str, float],
    threshold: float,
) -> int:
    """Compare measured rows/s against the baseline (higher is better);
    returns the process exit code. Truncated (None) metrics are not
    gateable and are reported as skipped."""
    from photon_ml_tpu.telemetry.report import compare_metrics

    current = {k: v for k, v in results.items() if v is not None}
    # rows/s-style metrics regress when they DROP; ratio-of-walltime
    # metrics (the sweep bench) regress when they RISE
    directions = {
        name: (-1 if name in LOWER_IS_BETTER_METRICS else +1)
        for name in set(current) | set(baseline)
    }
    deltas = compare_metrics(
        current, baseline, threshold=threshold, directions=directions
    )
    # metrics this run measured that the baseline has never seen (e.g. the
    # multichip_* lines against a pre-multichip BENCH_r05 baseline) are
    # SKIPPED WITH A NOTE — a baseline that predates a metric must never
    # fail the gate (nor crash it); the next baseline refresh picks it up
    for name in sorted(set(current) - set(baseline)):
        print(
            f"gate: {name}: new metric, not in baseline — skipped "
            "(refresh the baseline to start gating it)",
            file=sys.stderr,
        )
    for d in deltas:
        status = "REGRESSED" if d.regressed else "ok"
        print(
            f"gate: {d.metric}: {d.current:.1f} vs baseline "
            f"{d.baseline:.1f} ({d.change:+.1%}) {status}",
            file=sys.stderr,
        )
    truncated_overlap = False
    for name, value in results.items():
        if value is None:
            truncated_overlap = truncated_overlap or name in baseline
            print(f"gate: {name}: truncated, not gated", file=sys.stderr)
    if not deltas:
        # a gate that compared NOTHING must not pass: neither a
        # mismatched baseline (wrong metric names — a permanent false
        # pass) nor a run whose every gateable metric was budget-
        # truncated (a real regression would stay green)
        reason = (
            "every overlapping metric was budget-truncated; nothing "
            "was compared"
            if truncated_overlap
            else "no comparable metrics between this run "
            f"({sorted(results)}) and the baseline ({sorted(baseline)})"
        )
        print(f"gate: ERROR — {reason}", file=sys.stderr)
        return 2
    if any(d.regressed for d in deltas):
        return GATE_EXIT_CODE
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--gate",
        metavar="baseline.json",
        help="compare rows/s against this baseline and exit nonzero on "
        "a regression beyond --gate-threshold",
    )
    parser.add_argument(
        "--gate-threshold",
        type=float,
        default=0.2,
        help="fractional regression threshold for --gate (default 0.2)",
    )
    parser.add_argument(
        "--multichip",
        action="store_true",
        help="also run bench_multichip.py (1-vs-8-device scaling "
        "efficiency) and include its metrics in the gate; baselines that "
        "predate the multichip_* metrics skip them with a note",
    )
    parser.add_argument(
        "--sweep",
        action="store_true",
        help="also run bench_sweep.py (16-config λ-sweep wall time as a "
        "multiple of single-fit wall time) and include "
        "sweep_over_single_ratio in the gate; baselines that predate it "
        "skip with a note",
    )
    parser.add_argument(
        "--overlap",
        action="store_true",
        help="also run bench_overlap.py (streaming prefetch overlap "
        "factor) and include overlap_factor in the gate; baselines that "
        "predate it skip with a note",
    )
    parser.add_argument(
        "--ingest",
        action="store_true",
        help="also run bench_ingest.py (one-shot reader + ingest "
        "pipeline rows/s) and include both metrics in the gate; "
        "baselines that predate ingest_pipeline_rows_per_sec skip it "
        "with a note",
    )
    parser.add_argument(
        "--freshness",
        action="store_true",
        help="also run bench_freshness.py (incremental warm-start retrain "
        "vs full retrain at a 5%% delta — time-to-fresh-model speedup "
        "with a quality-parity assertion) and include freshness_speedup "
        "in the gate; baselines that predate it skip with a note",
    )
    parser.add_argument(
        "--diagnostics",
        action="store_true",
        help="also run bench_diagnostics.py (B=64 GLMix bootstrap wall "
        "time as a multiple of one fit — the vmapped resample-lane "
        "claim, <= 2.0 on TPU) and include bootstrap_overhead_ratio in "
        "the gate (lower is better); baselines that predate it skip "
        "with a note",
    )
    parser.add_argument(
        "--serving",
        action="store_true",
        help="also run bench_serving.py's sustained-load SLO sweep "
        "(offered-load grid, p99-across-hot-swap and across-nearline "
        "flatness, time-to-applied-update) plus the request-tracing "
        "overhead A/B and include the serving_slo_* and "
        "serving_trace_overhead_ratio metrics in the gate; baselines "
        "that predate them skip with a note",
    )
    args = parser.parse_args(argv)
    from photon_ml_tpu import faults

    if faults.warn_if_armed():
        if args.gate:
            # gated runs are the CI perf contract: numbers produced under
            # injection are not comparable to any baseline — refuse
            print(
                "bench_suite: refusing --gate with PHOTON_FAULT_PLAN "
                "armed (injected faults corrupt gated metrics)",
                file=sys.stderr,
            )
            return 2
    deadline = budget_deadline()
    results = run_suite(deadline=deadline)
    if args.multichip:
        from bench_multichip import run_multichip

        results.update(run_multichip(deadline=deadline))
    if args.sweep:
        from bench_sweep import run_sweep_bench

        results.update(run_sweep_bench(deadline=deadline))
    if args.overlap:
        from bench_overlap import run_overlap

        results.update(run_overlap(deadline=deadline))
    if args.ingest:
        from bench_ingest import run_ingest

        results.update(run_ingest(deadline=deadline))
    if args.freshness:
        from bench_freshness import run_freshness

        results.update(run_freshness(deadline=deadline))
    if args.diagnostics:
        from bench_diagnostics import run_diagnostics

        results.update(run_diagnostics(deadline=deadline))
    if args.serving:
        from bench_serving import run_serving_slo, run_trace_overhead

        results.update(run_serving_slo(deadline=deadline))
        results.update(run_trace_overhead(deadline=deadline))
    if args.gate:
        return run_gate(
            results, load_gate_baseline(args.gate), args.gate_threshold
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
