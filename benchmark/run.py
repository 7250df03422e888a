"""One run of one benchmark cell, in one process that holds the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to a cell is data found by name: the cell
(``workloads/<cell>.json``) names its configuration (``configs/``), its
traffic mix (``traffic/``) and its limits; the configuration names its
driver (``drivers/``), generator (``generators/``) and plain reference
(``reference/``); ``BENCHMARK.json`` says which metrics the cell reports and
each metric (``metrics/<name>.json``) names its reader (``readers/``) with
its parameters. This file names no cell, configuration or metric.

Set-up (process start to the end of the warm-up unit) is ``setup_s``. The
window then runs whole units of work, starts none once ``--seconds`` have
passed, and is as long as it measures. With ``--trace 1`` the window is ONE
unit under the profiler (the warm-up unit is the untraced one before it). After the window: the
device's peak memory is read, the program's state is freed, the plain
reference runs, and the comparison decides ``correct``.

Without a TPU whose ``device_kind`` is in ``peaks.json`` the run fails. The
one exception is ``--rehearsal-rows N`` (control flow off the chip at a tiny
size): its last line says which device it ran on, and ``correct`` is the
only thing a reader may take from it.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
OUT = os.path.join(HERE, "out")  # traces and scratch output, git-ignored


def seconds_before_import() -> float:
    """Seconds this process had lived when this file began to run (the
    interpreter's own start-up), from /proc; 0 where /proc cannot say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return max(age - (time.perf_counter() - _T0), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, group: str) -> list[str]:
    return [
        m["name"] for m in bench[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def read_metric(name: str, ctx: dict):
    spec = load_json("metrics", name + ".json")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    value = reader.read(ctx, **spec.get("params", {}))
    if value is None:
        return None
    return {"value": float(value), "unit": spec["unit"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal-rows", type=int, default=None)
    args = ap.parse_args(argv)
    t_process = _T0 - seconds_before_import()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    peaks_table = load_json("peaks.json")

    # the program's own rule for the compile cache, and no other
    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    rehearsal = args.rehearsal_rows is not None
    on_chip = dev.platform == "tpu" and dev.device_kind in peaks_table
    if not rehearsal and not on_chip:
        print(f"no accelerator of a known kind: jax reports {dev.platform} "
              f"'{dev.device_kind}'", file=sys.stderr)
        return 3
    if not rehearsal and len(devices) < int(cell["chips"]):
        print(f"the cell needs {cell['chips']} chips, jax reports "
              f"{len(devices)}", file=sys.stderr)
        return 3
    used = devices[: int(cell["chips"])]

    from photon_ml_tpu import telemetry

    def counters() -> dict:
        return dict(telemetry.snapshot()["counters"])

    driver_mod = importlib.import_module(
        "benchmark.drivers." + config["driver"])
    driver = driver_mod.Driver(
        config, traffic, args.seed, rows=args.rehearsal_rows,
        force_tiled=rehearsal and dev.platform != "tpu")
    driver.setup()
    marks = {"setup_end": counters()}
    setup_s = time.perf_counter() - t_process

    # -- the window -------------------------------------------------------------
    trace_dir = os.path.join(OUT, args.workload, "trace")
    marks["window_start"] = counters()
    first = len(driver.fits)
    window_start = time.perf_counter()
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            driver.fit(annotate=True)
        finally:
            jax.profiler.stop_trace()
    else:
        while True:
            driver.fit()
            if time.perf_counter() - window_start >= args.seconds:
                break
    fits = driver.fits[first:]
    window_s = fits[-1]["end"] - window_start
    marks["window_end"] = counters()

    stats = [d.memory_stats() or {} for d in used]
    memory = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))

    ctx = {
        "chips": int(cell["chips"]), "setup_s": setup_s,
        "spans": dict(driver.spans), "counters": marks, "fits": fits,
        "window_s": window_s, "shapes": driver.shapes(),
        "peaks": peaks_table.get(dev.device_kind) if on_chip else None,
        "memory": memory, "notes": {},
    }
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(memory.get("peak_bytes_in_use", 0)),
    }
    result = {"correct": False, "attempted": len(fits),
              "failed": sum(1 for f in fits if not f["ok"])}
    breakdown = None
    if args.trace:
        from benchmark import tracing

        trace = tracing.load(tracing.find_xplane(trace_dir),
                             host_ops_as_device=not on_chip)
        window = tracing.annotation_window(trace.annotations, "unit")
        ctx["trace"] = trace
        if window is not None and trace.devices:
            busy = [tracing.busy_seconds(ev, window)
                    for ev in trace.devices.values()]
            device["busy_s"] = sum(busy) / len(busy)
            device["window_s"] = (window[1] - window[0]) * 1e-9
            ctx["traced"] = device  # busy_s and window_s, for the readers
            events = next(iter(trace.devices.values()))
            inside = [e for e in events
                      if e[1] >= window[0] and e[1] < window[1]]
            own = tracing.self_seconds(inside)
            breakdown = {
                "device_ops": [
                    [tracing.short_name(n), s] for n, s in
                    sorted(own.items(), key=lambda kv: -kv[1])[:10]],
                "idle_gaps": tracing.idle_gaps(
                    events, window, trace.annotations),
            }

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for name in cell_metrics(bench, args.workload, group):
        value = read_metric(name, ctx)
        if value is not None:
            metrics[name] = value

    # -- correct: the last timed fit's outputs against the plain reference ---------
    compared, values, steps = {}, {}, []
    reference_s = None
    if result["failed"] == 0:
        program = driver.outputs()
        raw, shape, train_json = driver.raw, driver.shape, driver.train_json
        driver.free()
        ref_mod = importlib.import_module(
            "benchmark.reference." + config["reference"])
        t0 = time.perf_counter()
        reference = ref_mod.fit(raw, shape, train_json)
        reference_s = time.perf_counter() - t0
        from benchmark import compare

        values = compare.numbers(program, reference)
        steps = [
            {"coordinate": p["coordinate"], "iteration": p["iteration"],
             "loss": [p["loss"], r["loss"]],
             "solver_iterations": [p["solver_iterations"],
                                   r["solver_iterations"]],
             "metrics": [p["metrics"], r["metrics"]]}
            for p, r in zip(program["steps"], reference["steps"])]
        result["correct"], compared = compare.judge(values, cell["limits"])
    else:
        for f in fits:
            if not f["ok"]:
                print("failed fit:", f.get("error", "non-finite or fallback"),
                      file=sys.stderr)

    result.update(metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result.update(
        workload=args.workload, seed=args.seed, window_s=window_s,
        seconds_per_unit=[f["end"] - f["start"] for f in fits],
        spans=ctx["spans"], notes=ctx["notes"], reference_s=reference_s,
        bytes_in_use=int(memory.get("bytes_in_use", 0)),
        rehearsal=rehearsal, steps=steps, numbers=values,
        compared=compared)
    for name, c in compared.items():
        print(f"compared {name} = {c['value']:.6g}  limit {c['limit']:.6g}",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
