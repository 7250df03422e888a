"""What PR 26 adds to the benchmark: the click-log generator, the counts of
the panel kernels, the two readers, the driver's description of the panel
layout, and a rehearsal of ``criteo_fe.lbfgs_fit`` that is seen to take the
panel layout (interpret mode, a few thousand rows)."""

import json

import numpy as np
import pytest

from benchmark import run
from benchmark.counts import glmix_fit, panel_pass
from benchmark.generators import criteo_hashed
from benchmark.readers import counter_ratio, trace_kernel_seconds

CELL = "criteo_fe.lbfgs_fit"


def test_panel_pass_hand_count():
    # a tail of 1,000 nonzeros over 2 classes, 256 rows, 512 tail features:
    # a call is half the multiply-adds, half the (8 B a nonzero + 4 B a
    # coefficient), and one float32 a row
    flops, nbytes = panel_pass.per_call(
        {"nnz": 1000, "T": 2, "classes": 2, "features": 512})
    assert flops == 1000
    assert nbytes == (8 * 1000 + 4 * 512) / 2 + 4 * 256


def test_whole_fit_counts_read_the_panel_coordinate():
    shapes = {"rows": 10, "coordinates": {
        "fixed": {"kind": "fixed_effect", "nnz": 390, "features": 1000},
        "fixed.hot": {"T": 1, "nnz": 300}, "fixed.tail": {"T": 1, "nnz": 90}}}
    steps = [{"coordinate": "fixed", "solver_iterations": 3.0}]
    (flops, nbytes), = glmix_fit.per_fit(shapes, steps)
    assert flops == 4 * 390 * 4 + 2 * 390
    assert nbytes == (8 * 390 + 12 * 10) * 4 + 8 * 390


def test_generator_shapes_skew_and_seeds():
    shape = {"rows": 5000, "validation_rows": 500, "fe_features": 50_000,
             "fe_nnz_per_row": 39}
    a = criteo_hashed.generate(shape, 2147483777)
    b = criteo_hashed.generate(shape, 2147483777)
    c = criteo_hashed.generate(shape, 3000000019)
    tr = a["train"]
    assert tr["cols"].shape == tr["vals"].shape == (5000, 39)
    assert tr["cols"].dtype == np.int32 and tr["vals"].dtype == np.float32
    assert tr["users"] is None and tr["xu"] is None
    np.testing.assert_allclose(tr["vals"], 1 / np.sqrt(39))
    assert 0 <= tr["cols"].min() and tr["cols"].max() < 50_000
    np.testing.assert_array_equal(tr["cols"], b["train"]["cols"])
    np.testing.assert_array_equal(tr["y"], b["train"]["y"])
    assert (tr["cols"] != c["train"]["cols"]).any()
    assert 0.2 < tr["y"].mean() < 0.3            # the click rate
    counts = np.sort(np.bincount(tr["cols"].reshape(-1)))[::-1]
    assert counts[:4096].sum() > 0.6 * counts.sum()   # a hot head
    # the hash is fixed: the hottest feature is the same for every seed
    assert (np.bincount(tr["cols"].reshape(-1)).argmax()
            == np.bincount(c["train"]["cols"].reshape(-1)).argmax())
    with pytest.raises(ValueError):
        criteo_hashed.generate({**shape, "fe_nnz_per_row": 20}, 1)


def test_counter_ratio_reads_nothing_from_a_program_without_the_counters():
    marks = {"setup_end": {"layout.slots": 300.0, "layout.nnz": 200.0}}
    args = dict(numerator="layout.slots", denominator="layout.nnz",
                at="setup_end")
    assert counter_ratio.read({"counters": marks}, **args) == 1.5
    assert counter_ratio.read({"counters": {"setup_end": {}}}, **args) is None
    assert counter_ratio.read({"counters": {}}, **args) is None


def test_trace_kernel_seconds():
    class Trace:
        devices = {"tpu:0": [("%panel_margins.3 = ...", 0, 2_000_000),
                             ("%panel_scatter.1 = ...", 5, 1_000_000),
                             ("%tiled_margins.1 = ...", 9, 7_000_000)]}

    ctx = {"trace": Trace()}
    assert trace_kernel_seconds.read(ctx, "^%panel_") == pytest.approx(3e-3)
    assert trace_kernel_seconds.read(ctx, "^%tiled_") == pytest.approx(7e-3)
    assert trace_kernel_seconds.read(ctx, "^%nothing") is None
    assert trace_kernel_seconds.read({}, "^%panel_") is None


def test_rehearsal_takes_the_panel_layout(capsys):
    from photon_ml_tpu import telemetry

    # a run is a process of its own: set-up metrics read the process's FIRST
    # build of the coordinates, so forget what earlier tests built
    telemetry.reset()
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "0.1", "--trace", "1", "--rehearsal-rows", "3000"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0
    # the layout's own spans and counters: only the panel path has them
    assert line["metrics"]["panel_layout_s"]["value"] > 0
    assert line["metrics"]["layout_padding_ratio"]["value"] >= 1.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_control_fails_by_the_objective_in_this_cell():
    """The bfloat16 control is not correct here, and by ``first_loss_rel``:
    every value of a click-log row is the same 1/sqrt(39), so a fit in
    bfloat16 absorbs the values' rounding in its coefficients and scores
    the validation rows nearly as the float32 fit does, but it cannot reach
    the float32 objective. (``test_control.py``'s last assertion names
    ``val_score_rel``, the guard of ``glm_fe.lbfgs_fit``.)"""
    from benchmark import compare
    from benchmark.tests import readings

    files = readings.load(CELL)
    limits = files[0]["limits"]
    lines = readings.one_seed(files, seed=2147483777, rows=20000,
                              control=True, fault=True, force_tiled=True)
    by_kind = {line["what"]: line["numbers"] for line in lines}
    assert compare.judge(by_kind["program"], limits)[0] is True
    assert compare.judge(by_kind["fault_half_batch"], limits)[0] is False
    control = by_kind["control_bfloat16"]
    assert compare.judge(control, limits)[0] is False
    assert control["first_loss_rel"] > limits["first_loss_rel"]
    assert by_kind["program"]["first_loss_rel"] < (
        limits["first_loss_rel"] / 3)
