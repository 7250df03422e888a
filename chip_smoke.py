"""chip_smoke.py — the quickest proof that the GLMix main path still starts,
fits, scores and serves on the chip, through the entry points a user calls.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the mesh paths, run by the builder

From ``--seed`` it generates BASELINE config #4 at the width ``bench_game.py``
uses (1M rows; a 10K-feature sparse fixed-effect shard at 20 nnz/row; 100K
users x 10 random-effect features; planted GLMix labels; a held-out
validation split), writes it as TrainingExampleAvro with the repo's writer,
and drives the real CLI, one child process after another:

    cli train (GLMix, layout "auto") -> cli score -> cli serve (+ HTTP)
    cli train (fixed effects only: one TRON and one OWLQN/box coordinate,
               so the hv / hv_at / margins_pair kernels run too)

This parent never imports jax: a process that has touched jax holds the
chip, and every phase needs it. It fails (non-zero exit, no result line)
unless jax reports a TPU, the fixed-effect solve really contains Mosaic
kernels, losses are finite, validation AUC is within ``AUC_MARGIN`` of the
planted model's own, no executable fell back from its AOT compile, the
native Avro decoder did the reading, and every phase returned its own
success code. Each earlier stdout line is one JSON object of facts (phase
seconds, compiles, the compile cache, peak HBM, AUC, and a ``transport``
line: how ``block_until_ready``, fetches and uploads behave on this
machine). The last line is the contract's:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

With ``--chips 4`` it runs only the generated data through
``cli train --mesh batch=4`` and ``--mesh batch=2,model=2`` and the one-chip
fit they are compared with. ``--rows`` shrinks everything for a rehearsal.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import queue
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(ROOT, "chip_smoke_work")  # git-ignored, made anew
# where the program keeps its compile cache unless the environment places
# it (photon_ml_tpu/utils/compile_cache.py; this parent cannot import it)
DEFAULT_CACHE_DIR = os.path.join(ROOT, ".jax_cache")
LOG_COPY = os.path.join(ROOT, "chiprun_out", "chip_smoke")  # brought back

# BASELINE config #4 at bench_game.py's width. --rows scales the rows and,
# with them, users (rows/10) and fixed-effect features (rows/100): nnz per
# row and random-effect features per user are shapes and never change.
N_ROWS = 1_000_000
FE_NNZ = 20
RE_FEATURES = 10
CD_ITERATIONS = 2

# The planted model's validation AUC (~0.905) is a ceiling no fit reaches:
# it knows every user's 10 coefficients, the fit sees ~10 rows per user.
# This config's fit lands ~0.087 under it (0.818, float32 COO on the CPU,
# PR 21). A rehearsal at --rows 2000 has a thirteenth of the rows per
# fixed-effect feature and guards control flow, not quality: it gets twice
# the margin.
AUC_MARGIN = 0.10
# `cli score` and `cli serve` read the model `cli train` saved: same rows,
# same numbers up to f32 summation order.
SCORE_AUC_TOL = 1e-3
SERVE_SCORE_TOL = 1e-4
# A mesh fit sums the same f32 terms in another order: fixed-effect
# coefficients agree to a few parts in a thousand of the largest (4e-4 on
# four chips, PR 21), validation AUC to the fourth place (3e-7). The 100K
# per-user Newton solves are another matter: ten rows for ten coefficients,
# and on the TPU their matmuls run at jax's default precision, so another
# program (sharded inputs) rounds otherwise. The worst single coefficient
# of 1.6M moved by 2.4% of the largest between the one-chip and the batch=4
# fit on the chip (7.8e-4 of it on the CPU, float32 throughout); the bound
# on it is loose, the AUC bound is the one that holds the fit.
MESH_COEF_TOL = 5e-3
MESH_RE_COEF_TOL = 0.1
MESH_AUC_TOL = 5e-4

# Steered by tests/test_chip_smoke.py only: the rehearsal runs where jax has
# no TPU, with pallas in interpret mode.
REQUIRED_PLATFORM = "tpu"
FE_LAYOUT = None  # None = leave the config's default ("auto")

PHASE_TIMEOUT_S = 900
SERVE_EXIT_CODE = 75  # `cli serve` drains on SIGTERM and exits 75 by design


class SmokeFailure(Exception):
    pass


def emit(**line) -> None:
    print(json.dumps(line, default=float), flush=True)


# ---------------------------------------------------------------------------
# children: each runs in its own process, prints one JSON line last
# ---------------------------------------------------------------------------


def child_probe(spec: dict) -> None:
    """Device identity, the transport facts, and one small cached compile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu import telemetry
    from photon_ml_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    # this probe's programs compile in well under the default one-second
    # write threshold; cache them anyway, so that the second probe process
    # can show whether the directory is found again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    dev = jax.devices()[0]
    out = {
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
        "cache_dir": cache_dir,
    }
    if dev.platform != spec["platform"]:
        print(json.dumps(out))
        return

    dim, iters = int(spec["dim"]), int(spec["iters"])

    @jax.jit
    def busy(x):
        def body(_, a):
            return jnp.tanh(a @ x) * 0.5

        return jax.lax.fori_loop(0, iters, body, x)

    def fresh(i):
        return jnp.full((dim, dim), 0.001 * (i + 1), jnp.bfloat16)

    t0 = time.perf_counter()
    jax.block_until_ready(busy(fresh(0)))  # compile (or cache hit) + run
    first_call_s = time.perf_counter() - t0

    def median(xs):
        return float(np.median(xs))

    # 1. does block_until_ready wait? dispatch returns early; the wait ends
    #    with the kernel; a fetch after it finds the value already there
    dispatch, block, fetch_after = [], [], []
    for i in range(1, 6):
        x = jax.block_until_ready(fresh(i))
        t0 = time.perf_counter()
        y = busy(x)
        t1 = time.perf_counter()
        jax.block_until_ready(y)
        t2 = time.perf_counter()
        float(y[0, 0])
        t3 = time.perf_counter()
        dispatch.append(t1 - t0)
        block.append(t2 - t1)
        fetch_after.append(t3 - t2)
    # 2. the same kernel timed by a scalar fetch alone
    fetch_only = []
    for i in range(6, 11):
        x = jax.block_until_ready(fresh(i))
        t0 = time.perf_counter()
        float(busy(x)[0, 0])
        fetch_only.append(time.perf_counter() - t0)
    # 3. is an identical repeated call any faster than a fresh one?
    x = jax.block_until_ready(fresh(11))
    jax.block_until_ready(busy(x))
    identical = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(busy(x))
        identical.append(time.perf_counter() - t0)
    # 4. latency of fetching one ready scalar
    add_one = jax.jit(lambda a: a + 1.0)
    scalar_fetch = []
    s = jnp.float32(0)
    for _ in range(50):
        s = jax.block_until_ready(add_one(s))
        t0 = time.perf_counter()
        np.asarray(s)
        scalar_fetch.append(time.perf_counter() - t0)
    # 5. host->device and device->host bandwidth
    mb = int(spec["transfer_mb"])
    h2d, d2h = [], []
    for i in range(3):
        host = np.full((mb, 1 << 18), i + 1, np.float32)  # mb MiB
        t0 = time.perf_counter()
        on_dev = jax.block_until_ready(jax.device_put(host))
        h2d.append(host.nbytes / (time.perf_counter() - t0) / 1e9)
        on_dev = jax.block_until_ready(on_dev + 1.0)  # a buffer no host has
        t0 = time.perf_counter()
        np.asarray(on_dev)
        d2h.append(host.nbytes / (time.perf_counter() - t0) / 1e9)
        del on_dev
    # 6. a large closure constant inside a jitted program
    const = np.arange(int(spec["closure_mb"]) << 18, dtype=np.float32)
    t0 = time.perf_counter()
    closure_ok = bool(
        np.isfinite(float(jax.jit(lambda a: a + const.sum())(jnp.float32(1))))
    )
    closure_s = time.perf_counter() - t0

    counters = telemetry.snapshot()["counters"]
    stats = dev.memory_stats() or {}
    out.update(
        transport={
            "kernel_dispatch_s": median(dispatch),
            "kernel_block_until_ready_s": median(block),
            "fetch_after_block_s": median(fetch_after),
            "kernel_by_fetch_only_s": median(fetch_only),
            "block_until_ready_waits": median(fetch_after)
            < 0.25 * median(block),
            "identical_call_s": median(identical),
            "fresh_call_s": median(block) + median(dispatch),
            "scalar_fetch_s": median(scalar_fetch),
            "host_to_device_gbps": median(h2d),
            "device_to_host_gbps": median(d2h),
            "transfer_mb": mb,
            "closure_constant_mb": int(spec["closure_mb"]),
            "closure_constant_ok": closure_ok,
            "closure_constant_s": closure_s,
        },
        cache={
            "first_call_s": first_call_s,
            "hits": counters.get("jit_cache_hits", 0),
            "writes": counters.get("jit_cache_writes", 0),
            "compile_requests": counters.get("jit_compiles", 0),
        },
        peak_hbm_bytes=stats.get("peak_bytes_in_use"),
    )
    print(json.dumps(out))


def child_generate(spec: dict) -> None:
    """Planted GLMix data -> train.avro, val.avro, a few sample rows."""
    import numpy as np

    from photon_ml_tpu.data import avro_native
    from photon_ml_tpu.data.avro import write_training_examples_fast

    if avro_native._lib() is None:
        raise SystemExit(
            "the native Avro library did not build or load: refusing to "
            "run the smoke on the pure-Python decoder"
        )
    rng = np.random.default_rng(spec["seed"])
    n_users, n_feat = spec["users"], spec["fe_features"]
    w_true = rng.normal(size=n_feat) * 0.5
    wu_true = rng.normal(size=(n_users, RE_FEATURES)) * 0.5
    names = [f"g{i}" for i in range(n_feat)] + [
        f"u{j}" for j in range(RE_FEATURES)
    ]
    vocab = [str(u) for u in range(n_users)]
    out = {}
    for split, n in (("train", spec["rows"]), ("val", spec["val_rows"])):
        cols = rng.integers(0, n_feat, size=(n, FE_NNZ)).astype(np.int32)
        vals = rng.normal(size=(n, FE_NNZ))
        users = rng.integers(0, n_users, size=n)
        xu = rng.normal(size=(n, RE_FEATURES))
        logit = (vals * w_true[cols]).sum(1) + (xu * wu_true[users]).sum(1)
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float64)
        bags = {
            "global": (
                np.arange(0, (n + 1) * FE_NNZ, FE_NNZ, dtype=np.int64),
                cols.reshape(-1),
                vals.reshape(-1),
            ),
            "user": (
                np.arange(0, (n + 1) * RE_FEATURES, RE_FEATURES, dtype=np.int64),
                np.tile(
                    np.arange(n_feat, n_feat + RE_FEATURES, dtype=np.int32), n
                ),
                xu.reshape(-1),
            ),
        }
        path = os.path.join(spec["dir"], f"{split}.avro")
        write_training_examples_fast(
            path, y, bags, names, {"userId": (users, vocab)}
        )
        out[f"{split}_bytes"] = os.path.getsize(path)
        if split == "val":
            order = np.argsort(logit)
            ranks = np.empty(n)
            ranks[order] = np.arange(1, n + 1)
            pos = y > 0.5
            n_pos, n_neg = int(pos.sum()), int((~pos).sum())
            out["planted_auc"] = float(
                (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)
            )
            # the first rows of the validation file, as serve requests
            k = spec["samples"]
            samples = [
                {
                    "ids": {"userId": vocab[int(users[r])]},
                    "features": {
                        "global": [
                            [names[int(c)], "", float(v)]
                            for c, v in zip(cols[r], vals[r])
                        ],
                        "user": [
                            [names[n_feat + j], "", float(xu[r, j])]
                            for j in range(RE_FEATURES)
                        ],
                    },
                }
                for r in range(k)
            ]
            with open(os.path.join(spec["dir"], "samples.json"), "w") as f:
                json.dump(samples, f)
    print(json.dumps(out))


def child_first_scores(spec: dict) -> None:
    """The first ``k`` predictionScores of a ScoringResultAvro file."""
    from photon_ml_tpu.data.avro import read_avro

    scores = []
    for rec in read_avro(spec["path"]):
        scores.append(float(rec["predictionScore"]))
        if len(scores) == spec["k"]:
            break
    print(json.dumps({"scores": scores}))


CHILDREN = {
    "probe": child_probe,
    "generate": child_generate,
    "first_scores": child_first_scores,
}


# ---------------------------------------------------------------------------
# the parent: no jax here
# ---------------------------------------------------------------------------


def _last_json(text: str) -> dict:
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    raise SmokeFailure("phase printed no JSON line")


def run_phase(name: str, argv: list[str], ok_codes=(0,),
              env: dict | None = None) -> tuple[dict, float]:
    """Run one child to its end (``env``: variables added to its
    environment); return (its last JSON line, seconds)."""
    log = os.path.join(WORKDIR, "logs", f"{name}.err")
    t0 = time.perf_counter()
    with open(log, "w") as err:
        proc = subprocess.run(
            argv, cwd=ROOT, stdout=subprocess.PIPE, stderr=err, text=True,
            timeout=PHASE_TIMEOUT_S, env=None if env is None else {
                **os.environ, **env},
        )
    seconds = time.perf_counter() - t0
    if proc.returncode not in ok_codes:
        with open(log) as f:
            sys.stderr.write(f.read()[-6000:])
        raise SmokeFailure(f"phase '{name}' exited {proc.returncode}")
    return _last_json(proc.stdout), seconds


def child_argv(name: str, spec: dict) -> list[str]:
    code = (
        "import json, sys, chip_smoke; "
        "chip_smoke.CHILDREN[sys.argv[1]](json.loads(sys.argv[2]))"
    )
    return [sys.executable, "-c", code, name, json.dumps(spec)]


def cli_argv(*args: str) -> list[str]:
    return [sys.executable, "-m", "photon_ml_tpu.cli", *args]


def read_telemetry(path: str) -> dict:
    """The last metrics snapshot a CLI child flushed to ``path``."""
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    if not lines:
        raise SmokeFailure(f"{path} holds no telemetry snapshot")
    return json.loads(lines[-1])["snapshot"]


def telemetry_facts(snap: dict) -> dict:
    c, g = snap.get("counters", {}), snap.get("gauges", {})
    devices = sorted(
        k.split(".")[2] for k in g
        if k.startswith("memory.device.") and k.endswith(".bytes_in_use")
    )
    return {
        "xla_compiles": c.get("xla.compiles", 0),
        "xla_compile_seconds": c.get("xla.compile_seconds", 0.0),
        "jit_compiles": c.get("jit_compiles", 0),
        "jit_compile_seconds": c.get("jit_compile_seconds", 0.0),
        "cache_hits": c.get("jit_cache_hits", 0),
        "cache_writes": c.get("jit_cache_writes", 0),
        "fallback_calls": c.get("xla.fallback_calls", 0),
        "native_rows": c.get("avro.native_rows", 0),
        "python_rows": c.get("avro.python_rows", 0),
        "device_fetches": c.get("device_fetches", 0),
        "device_fetch_seconds": c.get("device_fetch_seconds", 0.0),
        "placement_devices": {
            k[len("placement."):-len(".devices")]: int(v)
            for k, v in g.items()
            if k.startswith("placement.") and k.endswith(".devices")
        },
        "bytes_in_use": {
            d: g.get(f"memory.device.{d}.bytes_in_use") for d in devices
        },
        "peak_hbm_bytes": {
            d: g.get(f"memory.device.{d}.peak_bytes_in_use") for d in devices
        },
    }


def check_run_facts(name: str, facts: dict, expect_rows: int) -> None:
    if facts["fallback_calls"]:
        raise SmokeFailure(
            f"{name}: xla.fallback_calls == {facts['fallback_calls']} "
            "(an AOT compile failed and was re-dispatched; see its log)"
        )
    if facts["python_rows"] or facts["native_rows"] != expect_rows:
        raise SmokeFailure(
            f"{name}: the native Avro decoder read {facts['native_rows']} of "
            f"{expect_rows} rows (pure-Python: {facts['python_rows']})"
        )


def ir_dir(name: str) -> str:
    """Where a ``cli train`` child's jax dumps the module of every program
    it compiles or loads from the cache (``JAX_DUMP_IR_TO``)."""
    return os.path.join(WORKDIR, "ir", name)


def mosaic_kernels(name: str, executable: str) -> int:
    """Mosaic calls (``@tpu_custom_call``) in the module ``jit_<executable>``
    the child ``name`` handed the compiler: the StableHLO jax dumps at
    ``compile_or_get_cached``, on a persistent-cache hit too."""
    paths = glob.glob(os.path.join(
        ir_dir(name), f"jax_ir*_jit_{executable}_compile.mlir"))
    count = 0
    for path in paths:
        with open(path) as f:
            count += f.read().count("@tpu_custom_call(")
    return count


def check_tiled(name: str, executable: str) -> None:
    """The FE solve must hold Mosaic kernels: pallas lowered for the TPU."""
    if REQUIRED_PLATFORM != "tpu":
        return  # the rehearsal: pallas is interpreted, no Mosaic kernel exists
    kernels = mosaic_kernels(name, executable)
    if kernels < 1:
        raise SmokeFailure(
            f"{name}: executable '{executable}' holds no tpu_custom_call — "
            "the fixed effect did not take the tiled pallas path"
        )


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def check_history(name: str, summary: dict) -> None:
    history = summary.get("history") or []
    if not history:
        raise SmokeFailure(f"{name}: empty coordinate-descent history")
    for entry in history:
        text = json.dumps(entry).lower()
        if "nan" in text or "infinity" in text:
            raise SmokeFailure(f"{name}: non-finite value in {entry}")
    if not _finite(summary.get("best_metric")):
        raise SmokeFailure(f"{name}: validation metric {summary.get('best_metric')}")


def final_auc(summary: dict) -> float:
    return summary["history"][-1]["metrics"]["auc"]


def optimizer(kind="lbfgs", max_iterations=20, **extra) -> dict:
    return {
        "type": kind, "max_iterations": max_iterations, "tolerance": 0.0,
        "regularization": "l2", "regularization_weight": 1.0, **extra,
    }


def fixed_effect(opt: dict) -> dict:
    coord = {"type": "fixed_effect", "shard_name": "global", "optimizer": opt}
    if FE_LAYOUT is not None:
        coord["layout"] = FE_LAYOUT
    return coord


def input_block(path: str) -> dict:
    return {
        "format": "avro", "paths": [path],
        "feature_shards": {"global": ["global"], "user": ["user"]},
        "id_columns": ["userId"], "add_intercept": False,
    }


def glmix_config(out_dir: str) -> dict:
    """BASELINE config #4 as bench_game.py fits it: LBFGS fixed effect,
    per-user random effect, two coordinate-descent iterations."""
    return {
        "task": "logistic",
        "input": input_block(os.path.join(WORKDIR, "train.avro")),
        "validation": {"paths": [os.path.join(WORKDIR, "val.avro")]},
        "coordinates": {
            "fixed": fixed_effect(optimizer("lbfgs")),
            "per-user": {
                "type": "random_effect", "shard_name": "user",
                "id_name": "userId",
                "optimizer": optimizer("newton", tolerance=1e-7),
            },
        },
        "num_iterations": CD_ITERATIONS,
        "evaluators": ["auc"],
        "output_dir": out_dir,
    }


def solvers_config(out_dir: str) -> dict:
    """Two short fixed-effect coordinates at the same width: TRON (the
    ``hv`` / ``hv_at`` kernels) and OWLQN under a box (``margins_pair``)."""
    cfg = glmix_config(out_dir)
    cfg["coordinates"] = {
        "fe-tron": fixed_effect(optimizer("tron", 3)),
        "fe-owlqn-box": fixed_effect(optimizer(
            "lbfgs", 5, regularization="elastic_net", alpha=0.5,
            box_constraints=[[0, -0.25, 0.25], [1, None, 0.0]],
        )),
    }
    cfg["num_iterations"] = 1
    return cfg


def train(name: str, config: dict, total_rows: int, *mesh: str):
    """One `cli train` child; returns (summary, facts, seconds)."""
    cfg_path = os.path.join(WORKDIR, f"{name}.json")
    tel_path = os.path.join(WORKDIR, f"{name}.telemetry.jsonl")
    with open(cfg_path, "w") as f:
        json.dump(config, f)
    summary, seconds = run_phase(name, cli_argv(
        "train", "--config", cfg_path, "--telemetry-out", tel_path, *mesh),
        env={"JAX_DUMP_IR_TO": ir_dir(name)})
    facts = telemetry_facts(read_telemetry(tel_path))
    check_run_facts(name, facts, total_rows)
    check_history(name, summary)
    return summary, facts, seconds


def serve_and_query(model_dir: str, samples: list[dict]) -> dict:
    """Start `cli serve`, answer a handful of HTTP requests, drain it."""
    tel_path = os.path.join(WORKDIR, "serve.telemetry.jsonl")
    log = open(os.path.join(WORKDIR, "logs", "serve.err"), "w")
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        cli_argv("serve", "--model-dir", model_dir, "--port", "0",
                 "--telemetry-out", tel_path),
        cwd=ROOT, stdout=subprocess.PIPE, stderr=log, text=True,
    )
    lines: queue.Queue = queue.Queue()
    threading.Thread(
        target=lambda: [lines.put(ln) for ln in proc.stdout], daemon=True
    ).start()
    try:
        banner = None
        deadline = time.monotonic() + PHASE_TIMEOUT_S
        while banner is None:
            if proc.poll() is not None:
                raise SmokeFailure(f"cli serve exited {proc.returncode} early")
            if time.monotonic() > deadline:
                raise SmokeFailure("cli serve never announced its port")
            try:
                line = lines.get(timeout=0.5)
            except queue.Empty:
                continue
            if line.startswith("{") and "serving" in line:
                banner = json.loads(line)["serving"]
        ready_s = time.perf_counter() - t0
        base = f"http://127.0.0.1:{banner['port']}"

        def post(rows):
            req = urllib.request.Request(
                base + "/v1/score", json.dumps({"rows": rows}).encode(),
                {"Content-Type": "application/json"},
            )
            t = time.perf_counter()
            with urllib.request.urlopen(req, timeout=60) as resp:
                body = json.loads(resp.read())
            return body["scores"], time.perf_counter() - t

        half = len(samples) // 2
        scores, latencies = [], []
        for rows in (samples[:1], samples[1:half], samples[half:]):
            got, dt = post(rows)
            if len(got) != len(rows):
                raise SmokeFailure(f"asked {len(rows)} scores, got {len(got)}")
            scores += got
            latencies.append(dt)
        stranger = json.loads(json.dumps(samples[0]))
        stranger["ids"]["userId"] = "no-such-user"
        unseen, dt = post([stranger])
        latencies.append(dt)
        with urllib.request.urlopen(base + "/metricsz", timeout=60) as resp:
            live = json.loads(resp.read())
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        log.close()
    if rc != SERVE_EXIT_CODE:
        raise SmokeFailure(f"cli serve exited {rc}, not {SERVE_EXIT_CODE}")
    if not all(_finite(s) and 0.0 <= s <= 1.0 for s in scores + unseen):
        raise SmokeFailure(f"served scores are not probabilities: {scores[:4]}")
    if abs(scores[0] - unseen[0]) < 1e-6:
        raise SmokeFailure(
            "a known user scored like an unseen one: the random effect "
            "did not reach the served score"
        )
    fallbacks = live.get("counters", {}).get("xla.fallback_calls", 0)
    if fallbacks:
        raise SmokeFailure(f"serve: xla.fallback_calls == {fallbacks}")
    return {
        "scores": scores, "known": scores[0], "unseen": unseen[0],
        "ready_seconds": ready_s, "request_seconds": latencies,
        "model_version": banner.get("model_version"),
    }


def sizes(rows: int) -> dict:
    return {
        "rows": rows,
        "val_rows": max(rows // 10, 500),
        "users": max(rows // 10, 8),
        "fe_features": max(rows // 100, 256),
        "samples": 16,
    }


def probe(size: dict) -> dict:
    full = size["rows"] >= N_ROWS
    out, seconds = run_phase("probe", child_argv("probe", {
        "platform": REQUIRED_PLATFORM,
        # ~100 ms of bf16 matmuls on a v5e at the full size
        "dim": 4096 if full else 128, "iters": 150 if full else 8,
        "transfer_mb": 256 if full else 4, "closure_mb": 64 if full else 1,
    }))
    out["seconds"] = seconds
    return out


def require_device(device: dict, count=None) -> None:
    if device["platform"] != REQUIRED_PLATFORM:
        raise SmokeFailure(
            f"jax reports platform '{device['platform']}', not "
            f"'{REQUIRED_PLATFORM}': this smoke has no CPU branch"
        )
    if count is not None and device["count"] != count:
        raise SmokeFailure(f"--chips {count} needs {count} devices: {device}")


def generate(seed: int, size: dict) -> dict:
    out, seconds = run_phase("generate", child_argv(
        "generate", {**size, "seed": seed, "dir": WORKDIR}))
    emit(phase="generate", seconds=seconds, **size, **out)
    return out


def check_auc(name: str, auc: float, planted: float, size: dict) -> None:
    margin = AUC_MARGIN if size["rows"] >= N_ROWS else 2 * AUC_MARGIN
    if not auc >= planted - margin:
        raise SmokeFailure(
            f"{name}: validation AUC {auc:.4f} is more than {margin} "
            f"under the planted model's {planted:.4f}"
        )


def entries(directory: str) -> int:
    return len(os.listdir(directory)) if os.path.isdir(directory) else 0


def one_chip(seed: int, size: dict) -> dict:
    default_entries_before = entries(DEFAULT_CACHE_DIR)
    first = probe(size)
    device = first["device"]
    require_device(device)
    emit(phase="transport", seconds=first["seconds"], device=device,
         **first["transport"])
    data = generate(seed, size)
    total_rows = size["rows"] + size["val_rows"]

    model_dir = os.path.join(WORKDIR, "model")
    summary, facts, seconds = train("train", glmix_config(model_dir), total_rows)
    check_tiled("train", "fe_solve")
    auc = final_auc(summary)
    check_auc("train", auc, data["planted_auc"], size)
    emit(phase="train", seconds=seconds, validation_auc=auc,
         planted_auc=data["planted_auc"],
         coordinate_steps=[
             [e["iteration"], e["coordinate"], e["seconds"],
              e["metrics"]["auc"]] for e in summary["history"]],
         **facts)

    # "final", the model after the last coordinate-descent step, not
    # "best": which step validates best differs between sizes and runs
    saved = os.path.join(model_dir, "final")
    score_cfg = os.path.join(WORKDIR, "score.json")
    with open(score_cfg, "w") as f:
        json.dump({"input": input_block(os.path.join(WORKDIR, "val.avro"))}, f)
    scores_path = os.path.join(WORKDIR, "scores.avro")
    scored, seconds = run_phase("score", cli_argv(
        "score", "--model-dir", saved, "--config", score_cfg,
        "--output", scores_path, "--evaluators", "auc"))
    score_auc = scored["metrics"]["auc"]
    if scored["num_rows"] != size["val_rows"]:
        raise SmokeFailure(f"cli score read {scored['num_rows']} rows")
    if not abs(score_auc - auc) <= SCORE_AUC_TOL:
        raise SmokeFailure(
            f"cli score AUC {score_auc} vs cli train's validation {auc}")
    emit(phase="score", seconds=seconds, rows=scored["num_rows"],
         auc=score_auc)

    with open(os.path.join(WORKDIR, "samples.json")) as f:
        samples = json.load(f)
    t0 = time.perf_counter()
    served = serve_and_query(saved, samples)
    seconds = time.perf_counter() - t0
    # the batch scorer saved margins for the same rows: the served means
    # must be their logistic
    batch, _ = run_phase("first_scores", child_argv(
        "first_scores", {"path": scores_path, "k": len(samples)}))
    worst = max(
        abs(s - 1.0 / (1.0 + math.exp(-m)))
        for s, m in zip(served.pop("scores"), batch["scores"])
    )
    if not worst <= SERVE_SCORE_TOL:
        raise SmokeFailure(
            f"served scores differ from cli score's by {worst}")
    emit(phase="serve", seconds=seconds, requests=4, rows=len(samples) + 1,
         max_abs_diff_vs_cli_score=worst, **served)

    summary, facts, seconds = train(
        "solvers", solvers_config(os.path.join(WORKDIR, "solvers")),
        total_rows)
    check_tiled("solvers", "fe_solve")
    emit(phase="solvers", seconds=seconds,
         validation_auc=final_auc(summary),
         trackers=[[e["coordinate"], e.get("tracker")]
                   for e in summary["history"]],
         **facts)

    second = probe(size)
    if second["cache"]["hits"] < 1:
        raise SmokeFailure(
            f"a second process found nothing in {second['cache_dir']}: "
            f"{second['cache']}"
        )
    if first["cache_dir"] != DEFAULT_CACHE_DIR and (
        entries(DEFAULT_CACHE_DIR) != default_entries_before
    ):
        raise SmokeFailure(
            f"{first['cache_dir']} is the placed cache, yet "
            f"{DEFAULT_CACHE_DIR} was written too"
        )
    emit(phase="cache", dir=first["cache_dir"],
         placed_by_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         entries=entries(first["cache_dir"]),
         first_process=first["cache"], second_process=second["cache"],
         probe_peak_hbm_bytes=second["peak_hbm_bytes"])
    return device


def load_coefficients(model_dir: str):
    import numpy as np  # numpy only: still no jax in this process

    fe = np.load(os.path.join(
        model_dir, "final", "fixed-effect", "fixed", "coefficients.npz"))
    re = np.load(os.path.join(
        model_dir, "final", "random-effect", "per-user", "model.npz"))
    tables = [re[k] for k in sorted(re.files) if k.startswith("coefficients_")]
    return fe["coefficients"], tables


def compare_with_one_chip(name, mesh, fit, reference, facts) -> dict:
    """A mesh fit against the one-chip fit: what differs, and the checks
    on it and on where the design and the tables were put."""
    import numpy as np  # numpy only: still no jax in this process

    (auc, fe, tables), (auc0, fe0, tables0) = fit, reference
    re0 = np.concatenate([t.ravel() for t in tables0])
    re_diff = np.concatenate([t.ravel() for t in tables]) - re0
    diff = {
        "fe_max_abs_diff": float(np.abs(fe - fe0).max()),
        "fe_max_abs": float(np.abs(fe0).max()),
        "re_max_abs_diff": float(np.abs(re_diff).max()),
        "re_max_abs": float(np.abs(re0).max()),
        "re_rms_diff": float(np.sqrt(np.mean(re_diff ** 2))),
        "re_rms": float(np.sqrt(np.mean(re0 ** 2))),
        "auc_diff": abs(auc - auc0),
    }
    emit(**diff, phase=f"{name} vs one-chip")
    spread, in_use = facts["placement_devices"], facts["bytes_in_use"]
    if spread.get("fixed.design") != 4:
        raise SmokeFailure(f"{name}: FE design on {spread} devices")
    if "model" in mesh and spread.get("per-user.coefficients") != 4:
        raise SmokeFailure(f"{name}: RE tables on {spread} devices")
    if REQUIRED_PLATFORM == "tpu" and (
        len(in_use) != 4 or not all(in_use.values())
    ):
        raise SmokeFailure(
            f"{name}: per-device bytes_in_use {in_use}: everything sits "
            "on device 0"
        )
    if diff["fe_max_abs_diff"] > MESH_COEF_TOL * diff["fe_max_abs"] or (
        diff["re_max_abs_diff"] > MESH_RE_COEF_TOL * diff["re_max_abs"]
    ):
        raise SmokeFailure(
            f"{name}: coefficients differ from the one-chip fit: {diff}")
    if diff["auc_diff"] > MESH_AUC_TOL:
        raise SmokeFailure(f"{name}: AUC differs: {diff}")
    return diff


def four_chips(seed: int, size: dict) -> dict:
    device = probe(size)["device"]
    require_device(device, count=4)
    data = generate(seed, size)
    total_rows = size["rows"] + size["val_rows"]
    fits = {}
    for name, mesh in (
        ("one-chip", ""), ("batch4", "batch=4"),
        ("batch2-model2", "batch=2,model=2"),
    ):
        out_dir = os.path.join(WORKDIR, f"model-{name}")
        summary, facts, seconds = train(
            name, glmix_config(out_dir), total_rows,
            *(("--mesh", mesh) if mesh else ()))
        check_tiled(name, "gspmd_solve" if mesh else "fe_solve")
        auc = final_auc(summary)
        check_auc(name, auc, data["planted_auc"], size)
        fits[name] = (auc, *load_coefficients(out_dir))
        emit(phase=name, seconds=seconds, mesh=mesh, validation_auc=auc,
             planted_auc=data["planted_auc"], **facts)
        if mesh:
            compare_with_one_chip(
                name, mesh, fits[name], fits["one-chip"], facts)
    return device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: only the mesh fits and the one-chip fit they are compared "
        "with (run by the builder)")
    parser.add_argument(
        "--rows", type=int, default=N_ROWS,
        help="training rows; users and FE features scale with it (a "
        "rehearsal size — the default is the real one)")
    args = parser.parse_args(argv)

    shutil.rmtree(WORKDIR, ignore_errors=True)
    os.makedirs(os.path.join(WORKDIR, "logs"))
    t0 = time.perf_counter()
    try:
        run = one_chip if args.chips == 1 else four_chips
        device = run(args.seed, sizes(args.rows))
    except (SmokeFailure, subprocess.TimeoutExpired) as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        # the children's logs outlive the run; the ~0.7 GB of Avro and
        # models do not — nothing of it is a result
        shutil.rmtree(LOG_COPY, ignore_errors=True)
        shutil.copytree(os.path.join(WORKDIR, "logs"), LOG_COPY)
        shutil.rmtree(WORKDIR, ignore_errors=True)
    emit(phase="total", seconds=time.perf_counter() - t0)
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
