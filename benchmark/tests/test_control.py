"""The control has to come out as not correct: the plain reference computed
in bfloat16 (the precision below the float32 the configuration states), put
in the program's place, fails at least one of the cell's numbers, while the
program itself passes all of them. Same for the half-batch fault planted in
the reference. The readings at the cells' own sizes, on the chip, are in
PERF.md; this keeps the comparison honest at a size a test run can hold.

The last test keeps the reference's random-effect path alive while no cell
of the benchmark uses it (PERF.md section 7, first): a small GLMix fit on
the CPU, where the program's per-user solves are float32, agrees with it."""

import json
import os

import pytest

from benchmark import compare
from benchmark.tests import readings

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_fault_fail_where_the_program_passes(cell):
    files = readings.load(cell)
    limits = files[0]["limits"]
    lines = readings.one_seed(files, seed=2147483777, rows=20000,
                              control=True, fault=True, force_tiled=True)
    by_kind = {line["what"]: line["numbers"] for line in lines}
    assert compare.judge(by_kind["program"], limits)[0] is True
    for kind in ("control_bfloat16", "fault_half_batch"):
        correct, compared = compare.judge(by_kind[kind], limits)
        assert correct is False, (kind, compared)
    # the control fails by the validation scores: the bfloat16 scoring pass
    # alone reads 2.4e-3
    assert by_kind["control_bfloat16"]["val_score_rel"] > (
        limits["val_score_rel"])


GLMIX = (
    {"name": "glmix_tiny"},
    {
        "driver": "game_fit", "reference": "glmix_plain",
        "train": {"task": "logistic", "coordinates": {
            "fixed": {"type": "fixed_effect", "shard_name": "global"},
            "per-user": {"type": "random_effect", "shard_name": "user",
                         "id_name": "userId"}}},
        "data": {
            "generator": "planted_glmix", "fe_shard": "global",
            "re_shard": "user", "id_column": "userId",
            "shape": {"rows": 20000, "validation_rows": 2000, "users": 2000,
                      "fe_features": 128, "fe_nnz_per_row": 20,
                      "re_features": 10}},
    },
    {
        "num_iterations": 2, "evaluators": ["auc"],
        "per_type": {
            "fixed_effect": {"optimizer": {
                "type": "lbfgs", "max_iterations": 5, "tolerance": 0.0,
                "regularization": "l2", "regularization_weight": 1.0}},
            "random_effect": {"optimizer": {
                "type": "newton", "max_iterations": 20, "tolerance": 1e-07,
                "regularization": "l2", "regularization_weight": 1.0}}},
    },
)


def test_random_effect_reference_agrees_with_the_program_in_float32():
    line, = readings.one_seed(GLMIX, seed=5, rows=None, control=False,
                              fault=False, force_tiled=True)
    got = line["numbers"]
    assert got["coef_rel.per-user"] < 1e-3, got
    assert got["coef_rel.fixed"] < 1e-3 and got["val_score_rel"] < 1e-3, got
    assert got["val_metric_gap"] < 1e-4, got
