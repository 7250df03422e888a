"""The readings that the limits of ``correct`` are set from, many seeds in
one process (set-up is most of a run, so a dozen seeds as a dozen runs would
cost a dozen set-ups of the harness and interpreter).

    python3 benchmark/tests/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--rows N]

For every seed: the program's fit (the same driver, entry and sizes the
benchmark times) against the plain reference -> the LOWER readings. For the
control seeds: the reference computed in bfloat16 put in the program's place
-> the UPPER readings. For the fault seeds: the reference with half of the
training rows left out and the rest counted twice, put in the program's
place. One JSON line per reading on standard output. ``--rows`` shrinks the
problem (tests, rehearsals off the chip); without it the cell's own size
runs and a TPU is required.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load(workload: str):
    from benchmark.run import load_json

    cell = load_json("workloads", workload + ".json")
    return (cell, load_json("configs", cell["config"] + ".json"),
            load_json("traffic", cell["traffic"] + ".json"))


def half_batch(raw: dict, train_json: dict):
    """The fault 'half of the batch left out, the mean taken over the
    rest': every second training row, and so that the sum over the rest
    stands for the whole, the L2 weight halved (losses then read half)."""
    tr = {k: (None if v is None else v[::2]) for k, v in raw["train"].items()}
    tj = json.loads(json.dumps(train_json))
    for coord in tj["coordinates"].values():
        coord["optimizer"]["regularization_weight"] *= 0.5
    return {"train": tr, "validation": raw["validation"]}, tj


def one_seed(files, seed: int, rows, control: bool, fault: bool,
             force_tiled: bool):
    """``files``: a cell's (cell, configuration, traffic mix), as ``load``
    gives them for a cell of the benchmark."""
    from benchmark import compare

    cell, config, traffic = files
    workload = cell["name"]
    driver_mod = importlib.import_module(
        "benchmark.drivers." + config["driver"])
    ref_mod = importlib.import_module(
        "benchmark.reference." + config["reference"])
    driver = driver_mod.Driver(config, traffic, seed, rows=rows,
                               force_tiled=force_tiled)
    t0 = time.perf_counter()
    driver.setup()
    record = driver.fits[-1]
    if not record["ok"]:
        raise RuntimeError(f"seed {seed}: the fit failed: {record}")
    program = driver.outputs()
    raw, shape, train_json = driver.raw, driver.shape, driver.train_json
    driver.free()
    t1 = time.perf_counter()
    reference = ref_mod.fit(raw, shape, train_json)
    t2 = time.perf_counter()
    out = [{
        "what": "program", "workload": workload, "seed": seed,
        "numbers": compare.numbers(program, reference),
        "program_s": t1 - t0, "reference_s": t2 - t1,
        "solver_iterations": [
            [s["solver_iterations"] for s in program["steps"]],
            [s["solver_iterations"] for s in reference["steps"]]],
    }]
    if control:
        lowered = ref_mod.fit(raw, shape, train_json, lower="bfloat16")
        out.append({"what": "control_bfloat16", "workload": workload,
                    "seed": seed,
                    "numbers": compare.numbers(lowered, reference)})
    if fault:
        raw2, tj2 = half_batch(raw, train_json)
        halved = ref_mod.fit(raw2, shape, tj2)
        for step in halved["steps"]:
            step["loss"] *= 2.0
        out.append({"what": "fault_half_batch", "workload": workload,
                    "seed": seed,
                    "numbers": compare.numbers(halved, reference)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args(argv)

    def ints(text):
        return [int(x) for x in text.split(",") if x]

    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    if args.rows is None and not on_tpu:
        print("the cell's own size needs the chip", file=sys.stderr)
        return 3
    control, fault = ints(args.control_seeds), ints(args.fault_seeds)
    files = load(args.workload)
    for seed in ints(args.seeds):
        for line in one_seed(files, seed, args.rows,
                             seed in control, seed in fault,
                             force_tiled=not on_tpu):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
