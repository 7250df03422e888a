"""Benchmark driver: ALL FIVE BASELINE.md configs + aux throughput lines.

Prints one JSON line per metric ({"metric", "value", "unit",
"vs_baseline"}), headline first:

  1. glm_logistic_1Mx10K_rows_per_sec_per_chip   (config #1, inline)
     + tiled_layout_build_rows_per_sec           (host layout build)
  2. linreg_tron_1Mx10K_rows_per_sec_per_chip    (config #2, bench_suite)
     + linreg_owlqn_elasticnet_...               (elastic-net variant)
  3. poisson_offsets_box_1Mx10K_rows_per_sec...  (config #3, bench_suite)
  4. glmix_fe_re_logistic_1Mx100Kusers_coeffs... (config #4, bench_game)
  5. game_1B_coeffs_trained_per_sec              (config #5, bench_scale)
  +  multichip_* scaling efficiency at 1 vs 8 devices (bench_multichip)
  +  avro_ingest_rows_per_sec                    (bench_ingest)

One process per chip: this launcher never imports jax. Every benchmark —
the headline too (``bench.py --headline-only``) — runs as its own child,
one after another, each taking the chip and giving it back. A failing
child emits an {"metric": ..., "error": ...} line, the run goes on, and
the launcher exits 1 at the end (a budget cut is not a failure). The
reference publishes no numbers (BASELINE.json "published": {}), so
vs_baseline is null throughout.

PHOTON_BENCH_BUDGET_S caps the whole run's wall clock: once spent, the
remaining sub-benchmarks are skipped but every expected metric still
emits a valid JSON line with "truncated": true (no more silent rc=124 —
the BENCH_r05 failure mode). With PHOTON_TRACE_OUT set, a run report
(markdown + JSON baseline) is written beside the trace at the end.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu import telemetry
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.ops.tiled import TiledBatch
    from photon_ml_tpu.optim import LBFGSConfig, glm_adapter, lbfgs_solve
    from photon_ml_tpu.utils import enable_compile_cache

    enable_compile_cache()
    # spans/metrics opt in via PHOTON_TRACE_OUT / PHOTON_TELEMETRY_OUT; the
    # snapshot below rides the bench JSON either way (one shared schema)
    telemetry.configure_from_env()
    # profile EVERY dispatch: the bench is a handful of dispatches (the
    # 1/N sampling default exists for hour-long fits), and the per-kernel
    # MFU / hot-dispatch-fraction lines below need the timed dispatch
    # itself honestly measured, not extrapolated from warmup
    telemetry.profile.set_sample_every(1)
    # an armed PHOTON_FAULT_PLAN would corrupt the bench numbers silently
    # (injected stalls/errors read as regressions) — same loud warning the
    # train/serve drivers give
    from photon_ml_tpu import faults

    faults.warn_if_armed()

    n_rows = 1_000_000
    n_features = 10_000
    nnz_per_row = 20
    max_iters = 20

    rng = np.random.default_rng(0)
    nnz = n_rows * nnz_per_row
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), nnz_per_row)
    cols = rng.integers(0, n_features, size=nnz)
    values = rng.normal(size=nnz)
    w_true = rng.normal(size=n_features) * 0.5
    # labels from a planted model so the optimizer does real work
    margins = np.zeros(n_rows)
    np.add.at(margins, rows, values * w_true[cols])
    y = (rng.random(n_rows) < 1.0 / (1.0 + np.exp(-margins))).astype(np.float64)

    # Tiled one-hot-matmul layout: the pallas fast path (ops/tiled.py);
    # round-1's padded-COO SparseBatch path measured ~850K rows/s here.
    # The one-time host layout build is reported as its own metric (it is
    # excluded from the steady-state training throughput below).
    t0 = time.perf_counter()
    batch = TiledBatch.from_coo(
        values=values, rows=rows, cols=cols, labels=y, num_features=n_features
    )
    t_layout = time.perf_counter() - t0
    obj = make_objective("logistic", l2_weight=1.0)
    cfg = LBFGSConfig(max_iterations=max_iters, tolerance=0.0)  # fixed work

    def run(w0, batch):
        # batch enters as a jit argument, not a closure constant: a
        # captured 332 MB design would be baked into the program
        return lbfgs_solve(glm_adapter(obj, batch), w0, cfg)

    # accounted jit (telemetry.xla): the headline's compile time, FLOPs
    # and bytes-accessed land in the executable registry for the detail
    run_jit = telemetry.instrumented_jit(run, name="bench_lbfgs")

    # compile + warmup (from another w0 than the timed run's); the scalar
    # fetch inside the timed window waits for the whole solve
    w_warm = jnp.asarray(rng.normal(size=n_features) * 1e-3, jnp.float32)
    float(run_jit(w_warm, batch).value)

    w0 = jnp.zeros((n_features,), jnp.float32)
    t0 = time.perf_counter()
    with telemetry.span("bench_lbfgs", rows=n_rows, features=n_features):
        res = run_jit(w0, batch)
        # forces execution + D2H sync, through the accounted fetch point
        final_value = float(telemetry.sync_fetch(res.value, label="loss"))
    elapsed = time.perf_counter() - t0

    iters = int(res.iterations)
    passes = int(res.data_passes)  # init eval + one per iteration (LBFGS)
    rows_per_sec = n_rows * passes / elapsed

    # roofline detail: per-solve cost analysis + achieved-vs-peak numbers
    # (None = "unknown": backends without cost analysis / unknown peaks)
    rec = run_jit.record_for(w0, batch)
    peak_flops, peak_bw = telemetry.xla.device_peaks()
    device_util = {
        "flops_per_solve": None if rec is None else rec.flops,
        "bytes_accessed_per_solve": None if rec is None else rec.bytes_accessed,
        "compile_seconds": None if rec is None else round(rec.compile_seconds, 3),
        "mfu": (
            round(rec.flops / (elapsed * peak_flops), 6)
            if rec is not None and rec.flops and peak_flops
            else None
        ),
        "bandwidth_utilization": (
            round(rec.bytes_accessed / (elapsed * peak_bw), 6)
            if rec is not None and rec.bytes_accessed and peak_bw
            else None
        ),
    }
    layout_line = json.dumps(
        {
            "metric": "tiled_layout_build_rows_per_sec",
            "value": round(n_rows / t_layout, 1),
            "unit": "rows/s",
            "vs_baseline": None,
            "detail": {"seconds": round(t_layout, 2), "nnz": nnz},
        }
    )

    print(
        json.dumps(
            {
                "metric": "glm_logistic_1Mx10K_rows_per_sec_per_chip",
                "value": round(rows_per_sec, 1),
                "unit": "rows/s",
                "vs_baseline": None,
                "detail": {
                    "elapsed_s": round(elapsed, 3),
                    "lbfgs_iterations": iters,
                    "final_loss": final_value,
                    "platform": jax.devices()[0].platform,
                    "device": str(jax.devices()[0]),
                    # same schema as TrainingFinishEvent.metrics_snapshot /
                    # --telemetry-out: fetch + compile accounting for the run
                    "telemetry": telemetry.snapshot()["counters"],
                    "device_utilization": device_util,
                },
            }
        ),
        flush=True,
    )
    # the layout-build rate prints AFTER the headline: harness consumers
    # take the first metric line as the training-throughput headline
    print(layout_line, flush=True)

    # executable-level utilization (telemetry.profile): the headline
    # solve's sampled honest timings → per-kernel MFU and the fraction of
    # the timed window actually spent inside the profiled executable.
    # Null values stay null ("unknown": no cost analysis / no known
    # device peak) — the gate skips them rather than gating a fake 0.
    prof = telemetry.profile.merged_profiles(names=("bench_lbfgs",)).get(
        "bench_lbfgs"
    )
    mfu = None if prof is None else prof.get("mfu")
    hot_fraction = None
    if (
        prof is not None
        and prof.get("mean_dispatch_seconds")
        and elapsed > 0
    ):
        hot_fraction = round(
            min(prof["mean_dispatch_seconds"] / elapsed, 1.0), 6
        )
    for metric, value in (
        ("glm_value_grad_mfu", mfu),
        ("hot_dispatch_fraction", hot_fraction),
    ):
        print(
            json.dumps(
                {
                    "metric": metric,
                    "value": value,
                    "unit": "fraction",
                    "vs_baseline": None,
                    "detail": {"executable": "bench_lbfgs",
                               "profile": prof},
                }
            ),
            flush=True,
        )


#: The metric lines main() itself prints (config #1 + the layout build +
#: the profiled per-kernel utilization pair).
HEADLINE_METRICS = (
    "glm_logistic_1Mx10K_rows_per_sec_per_chip",
    "tiled_layout_build_rows_per_sec",
    "glm_value_grad_mfu",
    "hot_dispatch_fraction",
)


def run_headline(deadline=None) -> bool:
    """Config #1, always as a ``bench.py --headline-only`` child: this
    launcher must stay off jax, or it would hold the chip every later
    child needs. Under a budget the child is capped at what remains, so
    a budget expiring MID-solve still ends in truncated lines instead of
    the outer timeout's rc=124. Returns False when the child failed for a
    reason other than the budget."""
    from bench_suite import truncated_line

    emitted = set()
    remaining = None if deadline is None else deadline - time.monotonic()
    failure = None  # non-budget failure: report an error, not "truncated"
    if remaining is None or remaining > 0:
        here = os.path.dirname(os.path.abspath(__file__))
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "bench.py"),
                 "--headline-only"],
                capture_output=True,
                text=True,
                timeout=(
                    1500 if remaining is None else max(remaining - 5.0, 1.0)
                ),
                cwd=here,
            )
            out = proc.stdout
            if proc.returncode != 0:
                failure = f"rc={proc.returncode}: {proc.stderr[-400:]}"
        except subprocess.TimeoutExpired as e:
            out = e.stdout or ""
            if remaining is None:  # its own limit, not a budget's
                failure = "headline timed out"
        except (subprocess.SubprocessError, OSError) as e:
            out = ""
            failure = str(e)[-400:]
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        for line in out.splitlines():
            line = line.strip()
            if line.startswith("{"):
                print(line, flush=True)
                emitted.add(_metric_of(line))
        if (
            failure is None
            and (remaining is None or remaining > 60)
            and not emitted
        ):
            # plenty of budget yet nothing printed: a crash, not a skip
            failure = "headline produced no metrics"
    if failure is not None:
        # a crashed headline must look like an ERROR, never like a
        # budget skip (same contract as run_sub_benchmarks)
        print(
            json.dumps(
                {"metric": "bench_headline", "value": None, "unit": None,
                 "vs_baseline": None, "error": failure}
            ),
            flush=True,
        )
        return False
    for metric in HEADLINE_METRICS:
        if metric not in emitted:
            print(truncated_line(metric), flush=True)
    return True


from bench_suite import SUITE_METRICS as _SUITE_METRICS

#: Expected metric lines per sub-benchmark, so a budget-skipped script
#: still emits one valid truncated line PER metric it would have printed.
#: bench_suite's names come from its own module — one source of truth.
from bench_diagnostics import DIAGNOSTICS_METRICS as _DIAGNOSTICS_METRICS
from bench_freshness import FRESHNESS_METRICS as _FRESHNESS_METRICS
from bench_ingest import INGEST_METRICS as _INGEST_METRICS
from bench_multichip import MULTICHIP_METRICS as _MULTICHIP_METRICS
from bench_overlap import OVERLAP_METRICS as _OVERLAP_METRICS
from bench_sweep import SWEEP_METRICS as _SWEEP_METRICS

_SCRIPT_METRICS = {
    "bench_suite.py": _SUITE_METRICS,
    "bench_game.py": ("glmix_fe_re_logistic_1Mx100Kusers_coeffs_per_sec",),
    "bench_scale.py": ("game_1B_coeffs_trained_per_sec",),
    "bench_multichip.py": _MULTICHIP_METRICS,
    "bench_sweep.py": _SWEEP_METRICS,
    "bench_overlap.py": _OVERLAP_METRICS,
    "bench_ingest.py": _INGEST_METRICS,
    "bench_freshness.py": _FRESHNESS_METRICS,
    "bench_diagnostics.py": _DIAGNOSTICS_METRICS,
    "bench_serving.py": ("serving_p50_ms", "serving_p99_ms",
                         "serving_rows_per_sec",
                         "serving_fleet_p99_resize_ratio",
                         "serving_fleet_kill_recovery_s"),
    "bench_northstar.py": ("north_star_e2e",),
}


def run_sub_benchmarks(deadline=None) -> list[str]:
    """Forward the JSON lines of every sub-benchmark (configs #2-#5 +
    ingestion + the north-star e2e pipeline), each in its own process,
    one at a time. Returns the scripts that failed for a reason other
    than the budget.

    ``deadline`` (monotonic seconds, from PHOTON_BENCH_BUDGET_S): scripts
    that would start past it are skipped with truncated placeholder lines,
    and a running script's timeout is capped at the remaining budget —
    metrics it printed before the cap are forwarded, the rest truncated.
    """
    from bench_suite import truncated_line

    here = os.path.dirname(os.path.abspath(__file__))
    failed = []
    # north-star (20M-row full pipeline) runs last and longest; the
    # driver's BASELINE numbers come from the earlier lines either way
    for script in ("bench_suite.py", "bench_game.py", "bench_scale.py",
                   "bench_multichip.py", "bench_sweep.py",
                   "bench_overlap.py", "bench_ingest.py",
                   "bench_freshness.py", "bench_diagnostics.py",
                   "bench_serving.py",
                   "bench_northstar.py"):
        path = os.path.join(here, script)
        expected = _SCRIPT_METRICS.get(script, (script.replace(".py", ""),))
        remaining = (
            None if deadline is None else deadline - time.monotonic()
        )
        if remaining is not None and remaining <= 0:
            for metric in expected:
                print(truncated_line(metric), flush=True)
            continue
        timeout = 1500 if script != "bench_northstar.py" else 4500
        budget_capped = False
        if remaining is not None:
            # keep a kill grace INSIDE the remaining budget: the deadline
            # is the flush-by time (bench_suite.budget_deadline already
            # excludes the exit margin), so the subprocess must be dead —
            # including the kill escalation — with seconds to spare for
            # forwarding its partial output and the truncated lines
            capped = max(remaining - 5.0, 1.0)
            if capped < timeout:
                timeout = capped
                budget_capped = True
        emitted = set()
        try:
            proc = subprocess.run(
                [sys.executable, path],
                capture_output=True,
                text=True,
                timeout=timeout,
                cwd=here,
            )
            for line in proc.stdout.splitlines():
                line = line.strip()
                if line.startswith("{"):
                    print(line, flush=True)
                    emitted.add(_metric_of(line))
            if proc.returncode != 0 or not emitted:
                raise RuntimeError(
                    f"rc={proc.returncode}: {proc.stderr[-400:]}"
                )
        except (subprocess.SubprocessError, RuntimeError, OSError) as e:
            # a timed-out sub-benchmark may have emitted metrics already —
            # forward them before the error/truncated lines
            partial = getattr(e, "stdout", None) or ""
            if isinstance(partial, bytes):
                partial = partial.decode(errors="replace")
            for line in partial.splitlines():
                line = line.strip()
                if line.startswith("{"):
                    print(line, flush=True)
                    emitted.add(_metric_of(line))
            over_budget = deadline is not None and (
                time.monotonic() >= deadline
                or (
                    budget_capped
                    and isinstance(e, subprocess.TimeoutExpired)
                )
            )
            if over_budget:
                # the budget, not the benchmark, ended this script: emit
                # valid truncated lines for whatever it never printed
                for metric in expected:
                    if metric not in emitted:
                        print(truncated_line(metric), flush=True)
            else:
                failed.append(script)
                print(
                    json.dumps(
                        {"metric": script.replace(".py", ""), "value": None,
                         "unit": None, "vs_baseline": None,
                         "error": str(e)[-400:]}
                    ),
                    flush=True,
                )
    return failed


def _metric_of(json_line: str):
    try:
        return json.loads(json_line).get("metric")
    except json.JSONDecodeError:
        return None


def write_run_report():
    """With PHOTON_TRACE_OUT set, render this process's telemetry as a run
    report beside the trace (markdown + JSON compare baseline for the
    bench_suite --gate / cli report --compare flows). Called by the
    headline child — the launcher has no telemetry of its own.

    Sub-benchmarks inherit the same env var, and the last one to run
    (bench_northstar.py, the e2e whose silence motivated this layer)
    rewrites both the trace file and its report."""
    trace_out = os.environ.get("PHOTON_TRACE_OUT")
    if not trace_out:
        return
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry.report import RunReport, report_path

    # same per-member suffixing the trace sink applied: in a fleet each
    # process owns its report instead of last-writer-winning one file
    md_path = report_path(telemetry.member_artifact_path(trace_out))
    report = RunReport.from_live()
    with open(md_path, "w", encoding="utf-8") as fh:
        fh.write(report.to_markdown())
    report.save_json(md_path[: -len(".md")] + ".json")
    print(f"run report: {md_path}", file=sys.stderr)


if __name__ == "__main__":
    from bench_suite import budget_deadline

    if "--headline-only" in sys.argv:
        # the child of run_headline: config #1 and its report, no recursion
        main()
        write_run_report()
        sys.exit(0)
    _deadline = budget_deadline()
    _headline_ok = run_headline(deadline=_deadline)
    _failed = run_sub_benchmarks(deadline=_deadline)
    sys.exit(0 if _headline_ok and not _failed else 1)
