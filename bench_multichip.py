"""Benchmark: multi-chip GSPMD scaling efficiency (ROADMAP item 1).

Measures the two headline training loops at 1 device vs N devices on the
SAME host and reports *scaling efficiency*, plus the ``game_10B``
sharded-capacity config that only fits when the coefficient tables span
the mesh:

  multichip_glm_rows_per_sec        headline GLM logistic FE solve: flat
                                    design committed P("batch"), whole
                                    LBFGS while-loop in one GSPMD jit
                                    (parallel.distributed.gspmd_solve)
  multichip_glmix_cd_coeffs_per_sec GLMix CD inner loop: streamed
                                    entity-sharded RE chunk solves over
                                    P("model") (game.streaming)
  multichip_game10B_per_device_gb   the game_10B config's per-device
                                    table bytes (estimate_table_bytes)
                                    + proof that the unsharded fit is
                                    REFUSED with a headroom message

Each line's detail carries the 1-device and N-device rates,
``scaling_efficiency`` (the N-device/1-device speedup — target >= 6x on
real 8-chip hardware), ``parallel_efficiency`` (speedup / devices), and
the ``comms.*`` byte estimates recorded by the solves so RunReport's
comms fraction stays honest.

Where it runs is decided before jax is imported, from the environment
alone (a process that has asked jax for its devices holds the chip):
with ``JAX_PLATFORMS=cpu`` — a rehearsal, asked for by name — the script
re-execs itself once under ``XLA_FLAGS=--xla_force_host_platform_device_
count=N`` and every line says ``"simulated": true`` (N virtual CPU devices
share one socket; the ratio measures the host, not ICI). Otherwise it
runs in this process on the devices jax reports and exits non-zero when
there are fewer than two: it never moves itself off a chip in silence.

Budget: honors ``PHOTON_BENCH_BUDGET_S`` — metrics skipped past the
deadline emit valid ``{"truncated": true}`` JSON (bench_suite recipe).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

#: Devices the scaling comparison targets (env-overridable).
DEFAULT_DEVICES = 8

MULTICHIP_METRICS = (
    "multichip_glm_rows_per_sec",
    "multichip_glmix_cd_coeffs_per_sec",
    "multichip_game10B_per_device_gb",
    # fleet observability (ISSUE 13): a real 2-process gloo fleet run
    # aggregated by telemetry.fleet_report — how much of the fleet's time
    # went to waiting at collectives, and how far apart the members' MFU
    # sits (both lower-is-better; bench_suite gates them that way)
    "fleet_collective_wait_fraction",
    "fleet_mfu_spread",
)

#: The game_10B configuration: ~10.24B coefficients of per-entity state.
#: One 16 GB chip cannot hold the 40.96 GB f32 table — the fit only
#: exists sharded (PAPER.md "hundreds of billions" needs the pod).
GAME_10B = {
    "name": "game_10B",
    "entities": 20_000_000,
    "dim": 512,
    "chunk_entities": 62_500,
    "rows_per_entity": 8,
}

#: Per-chip HBM assumed when the backend publishes no memory stats
#: (PHOTON_CHIP_HBM_GB overrides); 16 GB = v5e.
DEFAULT_CHIP_HBM_GB = 16.0


def _chip_hbm_bytes() -> int:
    raw = os.environ.get("PHOTON_CHIP_HBM_GB")
    if raw:
        try:
            return int(float(raw) * 2**30)
        except ValueError:
            print(f"ignoring malformed PHOTON_CHIP_HBM_GB={raw!r}",
                  file=sys.stderr)
    from photon_ml_tpu.telemetry import memory as telemetry_memory

    stats = telemetry_memory.hbm_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return int(DEFAULT_CHIP_HBM_GB * 2**30)


def game_10b_plan(n_devices: int) -> dict:
    """The game_10B memory math: total/per-device table bytes and whether
    the table fits a single chip (it must not — that is the point)."""
    from photon_ml_tpu.telemetry.memory import (
        DEFAULT_SAFETY_FRACTION,
        estimate_table_bytes,
    )

    total = estimate_table_bytes(GAME_10B["entities"], GAME_10B["dim"])
    chip = _chip_hbm_bytes()
    usable = int(chip * DEFAULT_SAFETY_FRACTION)
    min_devices = -(-total // usable)
    return {
        "total_coefficients": GAME_10B["entities"] * GAME_10B["dim"],
        "table_bytes": total,
        "table_gb": round(total / 2**30, 2),
        "chip_hbm_gb": round(chip / 2**30, 2),
        "per_device_bytes": total // max(n_devices, 1),
        "per_device_gb": round(total / max(n_devices, 1) / 2**30, 3),
        "fits_unsharded": total <= usable,
        "min_devices": int(min_devices),
    }


def check_game_10b_headroom(n_devices: int) -> None:
    """Refuse the game_10B fit when its per-device table shard cannot fit
    one chip — BEFORE any allocation, with the memory math in the error.
    ``n_devices=1`` (unsharded) must always refuse on real chips."""
    from photon_ml_tpu.telemetry.memory import DEFAULT_SAFETY_FRACTION

    plan = game_10b_plan(n_devices)
    per_dev = plan["table_bytes"] // max(n_devices, 1)
    usable = int(_chip_hbm_bytes() * DEFAULT_SAFETY_FRACTION)
    if per_dev > usable:
        raise RuntimeError(
            f"game_10B refuses to run on {n_devices} device(s): the "
            f"{plan['table_gb']} GB coefficient table needs "
            f"{plan['per_device_gb']} GB per device but only "
            f"{usable / 2**30:.2f} GB of {plan['chip_hbm_gb']} GB HBM is "
            f"usable per chip — shard the entity axis over at least "
            f"{plan['min_devices']} devices (--mesh model={plan['min_devices']})"
        )


def _needs_virtual_devices(n_devices: int) -> bool:
    """True when the environment asks for the CPU backend and has not yet
    given it ``n_devices`` virtual devices. Reads the environment only:
    no jax import, so the parent of a re-exec never holds a device."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        return False
    return not any(
        f.startswith("--xla_force_host_platform_device_count=")
        and int(f.split("=", 1)[1]) >= n_devices
        for f in os.environ.get("XLA_FLAGS", "").split()
    )


def _reexec_forced(n_devices: int) -> int:
    """Re-exec under a forced n-device virtual CPU platform and forward
    the child's metric lines (the dryrun_multichip recipe)."""
    env = dict(os.environ)
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    here = os.path.abspath(__file__)
    proc = subprocess.run(
        [sys.executable, here],
        env=env,
        cwd=os.path.dirname(here),
        capture_output=True,
        text=True,
        timeout=3600,
    )
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            print(line, flush=True)
    if proc.returncode != 0:
        print(proc.stderr[-2000:], file=sys.stderr)
    return proc.returncode


def _timed_rate(run, units: float) -> tuple[float, dict]:
    """``run(salt)`` returns a scalar device value; warm with one salt,
    time a different one, wait for it by fetching the scalar."""
    from photon_ml_tpu import telemetry

    float(telemetry.sync_fetch(run(0), label="warmup"))
    t0 = time.perf_counter()
    final = float(telemetry.sync_fetch(run(1), label="timed"))
    elapsed = time.perf_counter() - t0
    return units / elapsed, {"elapsed_s": round(elapsed, 3),
                             "final_value": final}


def bench_glm(n_devices: int, simulated: bool) -> dict:
    """Headline GLM FE solve at 1 vs N devices (GSPMD data parallel)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry import metrics as telemetry_metrics
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.ops.sparse import SparseBatch
    from photon_ml_tpu.ops.tiled import TiledBatch
    from photon_ml_tpu.optim import LBFGSConfig, glm_adapter, lbfgs_solve
    from photon_ml_tpu.optim.factory import OptimizerConfig
    from photon_ml_tpu.parallel import gspmd_solve, make_mesh, place_batch

    # full headline shape on real chips; a CPU mesh gets a scaled-down
    # problem (same code paths, tractable wall clock)
    if simulated:
        n_rows, n_features, nnz_per_row, iters = 100_000, 2_000, 10, 8
    else:
        n_rows, n_features, nnz_per_row, iters = 1_000_000, 10_000, 20, 20
    rng = np.random.default_rng(0)
    nnz = n_rows * nnz_per_row
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), nnz_per_row)
    cols = rng.integers(0, n_features, size=nnz)
    values = rng.normal(size=nnz)
    w_true = rng.normal(size=n_features) * 0.5
    margins = np.zeros(n_rows)
    np.add.at(margins, rows, values * w_true[cols])
    y = (rng.random(n_rows) < 1.0 / (1.0 + np.exp(-margins))).astype(np.float64)

    make = TiledBatch.from_coo if not simulated else SparseBatch.from_coo
    batch = make(
        values=values, rows=rows, cols=cols, labels=y,
        num_features=n_features,
    )
    obj = make_objective("logistic", l2_weight=1.0)
    lcfg = LBFGSConfig(max_iterations=iters, tolerance=0.0)  # fixed work
    cfg = OptimizerConfig(max_iterations=iters, tolerance=0.0,
                          regularization_weight=1.0)

    # -- 1 device: plain jit solve on the default device ------------------
    def single(w0, b):
        return lbfgs_solve(glm_adapter(obj, b), w0, lcfg)

    single_jit = telemetry.instrumented_jit(single, name="bench_mc_glm_1dev")

    def run_single(salt):
        w0 = jnp.full((n_features,), salt * 1e-6, jnp.float32)
        return single_jit(w0, batch).value

    passes = iters + 1  # init eval + one pass per LBFGS iteration
    rate_1, d1 = _timed_rate(run_single, n_rows * passes)

    # -- N devices: flat design committed P("batch"), one GSPMD jit -------
    mesh = make_mesh({"batch": n_devices})
    sharded = place_batch(batch, mesh)
    comms_before = telemetry_metrics.peek_counter("comms.bytes_total") or 0.0

    def run_mesh(salt):
        w0 = jnp.full((n_features,), salt * 1e-6, jnp.float32)
        return gspmd_solve("logistic", sharded, cfg, w0, mesh).value

    rate_n, dn = _timed_rate(run_mesh, n_rows * passes)
    comms_bytes = (telemetry_metrics.peek_counter("comms.bytes_total") or 0.0) - comms_before

    speedup = rate_n / rate_1 if rate_1 else None
    return {
        "metric": "multichip_glm_rows_per_sec",
        "value": round(rate_n, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "detail": {
            "devices": n_devices,
            "simulated": simulated,
            "rows": n_rows,
            "features": n_features,
            "data_passes": passes,
            "rows_per_sec_1dev": round(rate_1, 1),
            "rows_per_sec_ndev": round(rate_n, 1),
            "scaling_efficiency": None if speedup is None else round(speedup, 3),
            "parallel_efficiency": (
                None if speedup is None else round(speedup / n_devices, 3)
            ),
            "comms_bytes_estimated": comms_bytes,
            "single_device": d1,
            "mesh": dn,
        },
    }


def bench_glmix_cd(n_devices: int, simulated: bool) -> dict:
    """GLMix CD inner loop: streamed entity-sharded RE solves at 1 vs N
    devices (the coordinate-descent hot path at streaming scale)."""
    import functools

    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.telemetry import metrics as telemetry_metrics
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
    from photon_ml_tpu.parallel import make_mesh

    if simulated:
        n_entities, dim, chunk, rows = 4096, 32, 1024, 8
    else:
        n_entities, dim, chunk, rows = 1_000_000, 512, 125_000, 8
    cfg = OptimizerConfig(
        max_iterations=8,
        tolerance=1e-5,
        lbfgs_history=4,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def gen_chunk(key, E, R, K):
        kx, kw, ky, ko = jax.random.split(key, 4)
        x = jax.random.normal(kx, (E, R, K), jnp.float32)
        w_star = jax.random.normal(kw, (E, K), jnp.float32) * 0.3
        off = jax.random.normal(ko, (E, R), jnp.float32) * 0.2
        z = jnp.einsum("erk,ek->er", x, w_star) + off
        y = (jax.random.uniform(ky, (E, R)) < jax.nn.sigmoid(z)).astype(
            jnp.float32
        )
        return DenseBatch(
            x=x, labels=y, offsets=off, weights=jnp.ones((E, R), jnp.float32)
        )

    def run(mesh) -> float:
        table = ShardedCoefficientTable(n_entities, dim, mesh=mesh)
        trainer = StreamingRandomEffectTrainer("logistic", cfg, mesh=mesh)
        key = jax.random.key(7)
        chunks = [
            (start, (lambda i=i: gen_chunk(
                jax.random.fold_in(key, i), chunk, rows, dim
            )))
            for i, start in enumerate(range(0, n_entities, chunk))
        ]
        trainer.train(table, chunks[:1])  # warm the compiled paths
        table = ShardedCoefficientTable(n_entities, dim, mesh=mesh)
        t0 = time.perf_counter()
        stats = trainer.train(table, chunks)  # final fetch = true sync
        secs = time.perf_counter() - t0
        return stats.total_coefficients / secs

    rate_1 = run(None)
    comms_before = telemetry_metrics.peek_counter("comms.bytes_total") or 0.0
    rate_n = run(make_mesh({"model": n_devices}))
    comms_bytes = (telemetry_metrics.peek_counter("comms.bytes_total") or 0.0) - comms_before
    speedup = rate_n / rate_1 if rate_1 else None
    return {
        "metric": "multichip_glmix_cd_coeffs_per_sec",
        "value": round(rate_n, 1),
        "unit": "coeffs/s",
        "vs_baseline": None,
        "detail": {
            "devices": n_devices,
            "simulated": simulated,
            "entities": n_entities,
            "dim": dim,
            "coeffs_per_sec_1dev": round(rate_1, 1),
            "coeffs_per_sec_ndev": round(rate_n, 1),
            "scaling_efficiency": None if speedup is None else round(speedup, 3),
            "parallel_efficiency": (
                None if speedup is None else round(speedup / n_devices, 3)
            ),
            "comms_bytes_estimated": comms_bytes,
        },
    }


def bench_game_10b(n_devices: int, simulated: bool) -> dict:
    """The sharded-capacity config: memory math + the unsharded refusal.

    The actual 10B fit only runs on real hardware with enough chips AND
    an explicit opt-in (PHOTON_RUN_10B=1) — it is a capacity proof, not a
    throughput line. Everywhere else this verifies the math and that the
    unsharded attempt is refused with the headroom message."""
    plan = game_10b_plan(n_devices)
    refusal = None
    try:
        check_game_10b_headroom(1)
    except RuntimeError as e:
        refusal = str(e)
    sharded_ok = True
    sharded_error = None
    try:
        check_game_10b_headroom(max(n_devices, plan["min_devices"]))
    except RuntimeError as e:  # even the sharded plan does not fit
        sharded_ok = False
        sharded_error = str(e)
    ran_fit = False
    if (
        not simulated
        and sharded_ok
        and n_devices >= plan["min_devices"]
        and os.environ.get("PHOTON_RUN_10B") == "1"
    ):
        import jax

        from photon_ml_tpu.game.streaming import ShardedCoefficientTable
        from photon_ml_tpu.parallel import make_mesh

        mesh = make_mesh({"model": n_devices})
        check_game_10b_headroom(n_devices)
        table = ShardedCoefficientTable(
            GAME_10B["entities"], GAME_10B["dim"], mesh=mesh
        )
        assert table.sharding is not None
        ran_fit = True
        del table
    return {
        "metric": "multichip_game10B_per_device_gb",
        "value": plan["per_device_gb"],
        "unit": "GB/device",
        "vs_baseline": None,
        "detail": {
            "devices": n_devices,
            "simulated": simulated,
            **plan,
            "unsharded_refused": refusal is not None,
            "refusal": refusal,
            "sharded_plan_fits": sharded_ok,
            "sharded_plan_error": sharded_error,
            "table_allocated": ran_fit,
        },
    }


#: One shared fleet run feeds both fleet_* metric lines (module-level
#: memo: the steps loop calls one step per metric).
_FLEET_OBS_CACHE: dict[str, dict] = {}


def _fleet_observability_lines(simulated: bool) -> dict[str, dict]:
    """Run one supervised 2-process gloo fleet with per-member telemetry
    and derive the fleet_* metrics from the aggregated FleetReport —
    the bench-side proof the whole observability chain (identity
    suffixing -> collective-wait attribution -> fleet aggregation)
    holds under a real multi-process fit.

    These two lines are ALWAYS ``simulated: true``, regardless of the
    host platform: the supervised workers force JAX_PLATFORMS=cpu + gloo
    by harness design (tools/fleet._worker_env), so even on a TPU box
    this measures the CPU fleet — the plumbing, not the hardware. The
    CPU has no row in the peaks table, so per-member MFU, and with it
    fleet_mfu_spread, is null ("unknown") here. A failed run is memoized
    too: the second metric step must not repeat a known-failing (up to
    420 s) fleet launch."""
    import shutil
    import tempfile

    from photon_ml_tpu.telemetry.fleet_report import FleetReport
    from tools import fleet

    if _FLEET_OBS_CACHE:
        cached_error = _FLEET_OBS_CACHE.get("error")
        if cached_error is not None:
            raise RuntimeError(cached_error)
        return _FLEET_OBS_CACHE
    workdir = tempfile.mkdtemp(prefix="bench_fleet_obs_")
    try:
        report = fleet.run_fleet(fleet.FleetSpec(
            workdir=workdir,
            num_processes=2,
            devices_per_process=2,
            progress_heartbeat_every_s=0.5,
            timeout_s=420.0,
        ))
        if not report.get("ok"):
            raise RuntimeError(
                f"fleet observability run failed: "
                f"{json.dumps(report, default=str)[:1500]}"
            )
        fleet_report = FleetReport.load(report["telemetry_dir"])
        km = fleet_report.key_metrics()
    except Exception as e:
        # memoize EVERY failure shape (launch error, not-ok report,
        # aggregation error): the second metric step must never repeat a
        # known-failing fleet launch, and no attempt may leak its workdir
        _FLEET_OBS_CACHE["error"] = f"{type(e).__name__}: {e}"[:1600]
        shutil.rmtree(workdir, ignore_errors=True)
        raise
    detail = {
        "simulated": True,  # the fleet is CPU+gloo even on a TPU host
        "host_platform_simulated": simulated,
        "num_processes": 2,
        "devices_per_process": 2,
        "lost_members": fleet_report.lost_members(),
        "straggler": fleet_report.straggler(),
        "fleet_rows_per_sec": km.get("fleet_rows_per_sec"),
        "fleet_collective_wait_s": km.get("fleet_collective_wait_s"),
        "member_mfu": {
            str(m.process_index): m.key_metrics().get("mfu")
            for m in fleet_report.members
        },
    }
    # the aggregates are extracted; repeated gated bench runs must not
    # accumulate full fleet workdirs (checkpoints + traces) in tempdir
    shutil.rmtree(workdir, ignore_errors=True)
    _FLEET_OBS_CACHE.update({
        "fleet_collective_wait_fraction": {
            "metric": "fleet_collective_wait_fraction",
            "value": km.get("fleet_collective_wait_fraction"),
            "unit": "fraction",
            "vs_baseline": None,
            "detail": detail,
        },
        "fleet_mfu_spread": {
            "metric": "fleet_mfu_spread",
            "value": km.get("fleet_mfu_spread"),
            "unit": "mfu delta",
            "vs_baseline": None,
            "detail": detail,
        },
    })
    return _FLEET_OBS_CACHE


def run_multichip(deadline=None) -> dict[str, float | None]:
    """Emit the multichip metric lines (budget-aware); returns
    {metric: value or None} for the bench_suite --gate flow."""
    from bench_suite import truncated_line

    import jax

    from photon_ml_tpu import telemetry

    telemetry.configure_from_env()
    n_devices = int(
        os.environ.get("PHOTON_MULTICHIP_DEVICES", str(DEFAULT_DEVICES))
    )
    n_devices = min(n_devices, len(jax.devices()))
    if n_devices < 2:
        raise SystemExit(
            f"bench_multichip needs at least 2 devices; jax reports "
            f"{jax.devices()} — it does not move itself onto virtual CPU "
            "devices (ask for that rehearsal by name: JAX_PLATFORMS=cpu)"
        )
    simulated = jax.devices()[0].platform != "tpu"
    steps = (
        ("multichip_glm_rows_per_sec", lambda: bench_glm(n_devices, simulated)),
        (
            "multichip_glmix_cd_coeffs_per_sec",
            lambda: bench_glmix_cd(n_devices, simulated),
        ),
        (
            "multichip_game10B_per_device_gb",
            lambda: bench_game_10b(n_devices, simulated),
        ),
        (
            "fleet_collective_wait_fraction",
            lambda: _fleet_observability_lines(simulated)[
                "fleet_collective_wait_fraction"
            ],
        ),
        (
            "fleet_mfu_spread",
            lambda: _fleet_observability_lines(simulated)["fleet_mfu_spread"],
        ),
    )
    results: dict[str, float | None] = {}
    truncated = False
    for metric, step in steps:
        if truncated or (
            deadline is not None and time.monotonic() > deadline
        ):
            truncated = True
            print(truncated_line(metric), flush=True)
            results[metric] = None
            continue
        try:
            line = step()
        except Exception as e:  # noqa: BLE001 — report, don't kill the suite
            print(
                json.dumps(
                    {"metric": metric, "value": None, "unit": None,
                     "vs_baseline": None, "error": str(e)[-400:]}
                ),
                flush=True,
            )
            results[metric] = None
            continue
        results[metric] = line["value"]
        print(json.dumps(line), flush=True)
    return results


def main() -> int:
    n_devices = int(
        os.environ.get("PHOTON_MULTICHIP_DEVICES", str(DEFAULT_DEVICES))
    )
    if _needs_virtual_devices(n_devices):  # decided before any jax import
        return _reexec_forced(n_devices)
    from bench_suite import budget_deadline

    run_multichip(deadline=budget_deadline())
    return 0


if __name__ == "__main__":
    sys.exit(main())
