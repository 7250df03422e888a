"""What PR 30 adds to the benchmark: the MovieLens-shaped generator, the
blocked reference, the counts of a per-entity Newton pass, the reader of a
program family's roofline share, and a rehearsal of ``ml20m_glmix.cd_fit``
(a few thousand rows) in which the program is ``correct``, the bfloat16
control is not, by the number PERF.md names as this cell's precision guard,
and the half-batch fault is not."""

import json

import numpy as np
import pytest

from benchmark import compare, run
from benchmark.counts import glmix_fit, re_newton_pass
from benchmark.drivers import game_fit_mixed
from benchmark.generators import movielens_mixed
from benchmark.readers import trace_module_roofline
from benchmark.reference import glmix_plain_ragged
from benchmark.tests import readings

CELL = "ml20m_glmix.cd_fit"
#: PERF.md section 3: the number by which the bfloat16 control fails here
GUARD = "val_score_rel"
ROWS = 20000


def test_newton_pass_hand_count():
    # one entity of r = 10 rows and k = 3 local features solved in 4
    # iterations: pass_cells = 4 * 10 * 3; per iteration 2rk + 2rk + 2rk^2
    # + k^3/3 FLOPs and the design read three times at 4 bytes a value
    shape = {"pass": {"k_min": 3.0}}
    flops, nbytes = re_newton_pass.per_fit(shape, 4.0, 120.0)
    assert flops == 4 * (2 * 30 + 2 * 30 + 2 * 90 + 27 / 3)
    assert nbytes == 4 * 3 * 30 * 4


def test_newton_pass_undercounts_wider_entities():
    # two entities, k = 2 and k = 6, one iteration each of r = 5 rows: the
    # k^2 and k^3 terms are taken at the least k, the rk terms exactly
    shape = {"pass": {"k_min": 2.0}}
    exact = sum(4 * 5 * k + 2 * 5 * k * k + k ** 3 / 3 for k in (2, 6))
    flops, nbytes = re_newton_pass.per_fit(shape, 2.0, 5 * 2 + 5 * 6)
    assert flops < exact and nbytes == 12 * 40


def test_whole_fit_counts_read_both_random_effects():
    shapes = {"rows": 100, "coordinates": {
        "fixed": {"kind": "fixed_effect", "nnz": 400, "features": 32},
        "per-user": {"kind": "random_effect", "rows": 100, "features": 19.5},
        "per-movie": {"kind": "random_effect", "rows": 100, "features": 1.0}}}
    steps = [{"coordinate": c, "solver_iterations": 3.0}
             for c in ("fixed", "per-user", "per-movie")]
    counted = list(glmix_fit.per_fit(shapes, steps))
    assert len(counted) == 3 and all(f > 0 and b > 0 for f, b in counted)
    # the K = 1 effect: 3 iterations x 100 rows x (4 + 2) + 2 a row
    assert counted[2] == (3 * 100 * 6 + 200, 4 * 100 * 4 * 4.0)


def test_roofline_reader_needs_counters_launches_and_peaks(monkeypatch):
    shape = {"kind": "random_effect", "pass": {"k_min": 1.0}}
    marks = {"window_start": {"re.per-user.lane_iterations": 10,
                              "re.per-user.pass_cells": 1000},
             "window_end": {"re.per-user.lane_iterations": 30,
                            "re.per-user.pass_cells": 5000}}
    ctx = {"peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e9},
           "counters": marks, "fits": [{"ok": True}, {"ok": True}],
           "shapes": {"coordinates": {"per-user": shape,
                                      "fixed": {"kind": "fixed_effect"}}}}
    args = dict(pattern="^jit_re_solve", counts="re_newton_pass",
                prefix="re", counters=["lane_iterations", "pass_cells"],
                kind="random_effect")
    monkeypatch.setattr(
        trace_module_roofline.trace_module, "read",
        lambda ctx, pattern: 1e-3)
    # per fit: 2,000 cells x 12 bytes at 1e9 B/s = 24 us of a measured 1 ms
    assert trace_module_roofline.read(ctx, **args) == pytest.approx(2.4)
    monkeypatch.setattr(
        trace_module_roofline.trace_module, "read",
        lambda ctx, pattern: None)
    assert trace_module_roofline.read(ctx, **args) is None
    monkeypatch.setattr(
        trace_module_roofline.trace_module, "read",
        lambda ctx, pattern: 1e-3)
    assert trace_module_roofline.read(dict(ctx, peaks=None), **args) is None
    # a program without the counters (the parent): nothing, and no raise
    bare = dict(ctx, counters={"window_start": {}, "window_end": {}})
    assert trace_module_roofline.read(bare, **args) is None


def test_generator_laws_floor_and_seeds():
    cell, config, traffic = readings.load(CELL)
    shape = game_fit_mixed.Driver(config, traffic, 1, rows=ROWS).shape
    law = movielens_mixed.entity_law(1000, 150000, 20, 9254, 1.42, 0)
    assert law.sum() == 150000 and law.min() == 20 and law.max() == 9254
    assert np.median(law) < law.mean()  # a heavy tail
    a = movielens_mixed.generate(shape, 2147483777)
    b = movielens_mixed.generate(shape, 2147483777)
    c = movielens_mixed.generate(shape, 3000000019)
    tr = a["train"]
    assert len(tr["y"]) == shape["rows"]
    assert tr["global_cols"].shape == tr["global_vals"].shape == (
        shape["rows"], 10)
    assert tr["user_cols"].shape[1] == 9 and tr["movie_cols"].shape[1] == 1
    for key in tr:
        np.testing.assert_array_equal(tr[key], b["train"][key])
    assert (tr["userId"] != c["train"]["userId"]).any()
    # the same fit under every seed: rows and labels stay, ids are renamed
    np.testing.assert_array_equal(tr["y"], c["train"]["y"])
    np.testing.assert_array_equal(tr["global_vals"], c["train"]["global_vals"])
    per_user = np.bincount(
        np.concatenate([tr["userId"], a["validation"]["userId"]]))
    assert per_user.min() >= 20  # the README's floor, over both splits
    # the same users' counts for every seed, under other ids
    assert np.array_equal(np.sort(np.bincount(tr["userId"])),
                          np.sort(np.bincount(c["train"]["userId"])))
    assert np.array_equal(np.sort(np.bincount(tr["movieId"])),
                          np.sort(np.bincount(c["train"]["movieId"])))


def test_rehearsal_is_correct_and_reports_its_layers(capsys):
    from photon_ml_tpu import telemetry

    telemetry.reset()
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "0.1", "--trace", "1", "--rehearsal-rows", str(ROWS)])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0
    metrics = line["metrics"]
    for name in ("re_user_solve_s_per_fit", "re_item_solve_s_per_fit",
                 "re_update_s_per_fit", "residual_s_per_fit", "re_layout_s"):
        assert metrics[name]["value"] > 0, name
    assert metrics["re_padding_ratio"]["value"] >= 1.0
    assert metrics["re_straggler_ratio"]["value"] >= 1.0
    assert metrics["compiles_in_window"]["value"] == 0
    assert [s["coordinate"] for s in line["steps"]] == [
        "fixed", "per-user", "per-movie"] * 2  # two sweeps, six evaluations
    # no per-entity solve reaches the traffic's ceiling of 20
    assert all(s["solver_iterations"][0] < 20 for s in line["steps"][1:3])


def test_control_and_fault_are_not_correct_in_this_cell():
    """The bfloat16 control fails by ``val_score_rel``, the guard of this
    cell as of ``glm_fe.lbfgs_fit`` (the coefficients cannot do it here:
    PERF.md section 3), and the half-batch fault fails too."""
    files = readings.load(CELL)
    limits = files[0]["limits"]
    lines = readings.one_seed(files, seed=2147483777, rows=ROWS,
                              control=True, fault=True, force_tiled=True)
    by_kind = {line["what"]: line["numbers"] for line in lines}
    assert compare.judge(by_kind["program"], limits)[0] is True
    assert compare.judge(by_kind["fault_half_batch"], limits)[0] is False
    control = by_kind["control_bfloat16"]
    assert compare.judge(control, limits)[0] is False
    assert control[GUARD] > limits[GUARD]
    assert by_kind["program"][GUARD] < limits[GUARD] / 2
