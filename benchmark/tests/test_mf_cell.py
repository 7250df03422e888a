"""What PR 33 adds to the benchmark: the matrix-factorization generator
(``movielens_mixed``'s ratings + a one-hot movie shard + a planted rank-16
term), the alternating reference, the counts of a refit pass, and a
rehearsal of ``ml20m_mf.cd_fit`` (a few thousand rows) in which the program
is within the rehearsal's own limits and the bfloat16 control and the
half-batch fault are not."""

import json

import numpy as np

from benchmark import compare, run
from benchmark.counts import mf_refit_pass
from benchmark.drivers import game_fit_mf
from benchmark.generators import movielens_mf, movielens_mixed
from benchmark.tests import readings

CELL = "ml20m_mf.cd_fit"
ROWS = 20000
#: the rehearsal's OWN limits: the cell's are set from readings at its own
#: size on the chip, and 20,000 rows on this CPU read otherwise (2,200
#: validation rows; a refit that float32 ends at 9 to 11 iterations).
#: Readings here, PR 33: program largest of two seeds / bfloat16 control /
#: half-batch fault, in the order of the keys: 1.07e-3 / 3.63e-3 / 0.194;
#: 8.6e-4 / 5.57e-3 / 1.40; 1.10e-3 / 6.57e-3 / 1.44; 2.95e-7 / 7.4e-7 /
#: 1.07e-3; 2.06e-5 / 1.08e-4 / 0.353; 6.2e-5 / 1.13e-4 / 1.57e-2
REHEARSAL_LIMITS = {
    "coef_rel.fixed": 2e-3, "coef_rel.user-x-movie": 2.2e-3,
    "val_score_rel": 2.7e-3, "first_loss_rel": 3e-6, "step_loss_rel": 1e-3,
    "val_metric_gap": 2e-3,
}


def test_refit_pass_hand_count():
    # 10 nonzeros over 4 rows, K = 3, d = 5: an evaluation is 4*K FLOPs a
    # nonzero; 8 B a nonzero + (4*K + 12) B a row + 4*K*d B for A
    shape = {"mf": {"nnz": 10, "rows": 4, "latent_dim": 3, "features": 5}}
    flops, nbytes = mf_refit_pass.per_fit(shape, 2.0)
    assert flops == 2 * 4 * 3 * 10
    assert nbytes == 2 * (80 + 24 * 4 + 60)
    flops, nbytes = mf_refit_pass.per_call(shape)
    assert flops == 2 * 3 * 10 and nbytes == 80 + 12 * 4 + 60


def test_generator_keeps_the_mixed_cells_ratings_and_adds_the_one_hot():
    cell, config, traffic = readings.load(CELL)
    shape = game_fit_mf.Driver(config, traffic, 1, rows=ROWS).shape
    a = movielens_mf.generate(shape, 2147483777)
    b = movielens_mf.generate(shape, 3000000019)
    mixed = movielens_mixed.generate(shape, 2147483777)
    tr = a["train"]
    # the same ratings as ml20m_glmix_logistic's generator makes of the
    # shape; its users are renamed by the seed, these keep their rank
    for key in ("movieId", "global_cols", "global_vals"):
        np.testing.assert_array_equal(tr[key], mixed["train"][key])
    assert len(np.unique(tr["userId"])) == len(
        np.unique(mixed["train"]["userId"]))
    assert tr["movie_onehot_cols"].shape == (shape["rows"], 1)
    assert tr["movie_onehot_cols"].max() < shape["rated_movies"]
    assert np.all(tr["movie_onehot_vals"] == 1)
    # one computation for every seed: labels, rows, COLUMNS and the users'
    # order stay, the movies' ids are renamed
    for key in ("y", "movie_onehot_cols", "userId"):
        np.testing.assert_array_equal(tr[key], b["train"][key])
    assert (tr["movieId"] != b["train"]["movieId"]).any()
    assert 0.4 < tr["y"].mean() < 0.6
    fixed, latent = movielens_mf.margin_std(shape)
    assert 0.3 < fixed < 3.0 and 0.5 < latent < 1.5  # both terms matter


def test_rehearsal_is_correct_and_reports_its_layers(capsys):
    from photon_ml_tpu import telemetry

    telemetry.reset()
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "0.1", "--trace", "1", "--rehearsal-rows", str(ROWS)])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    assert set(line["compared"]) == set(REHEARSAL_LIMITS)
    assert compare.judge(
        {k: v["value"] for k, v in line["compared"].items()},
        REHEARSAL_LIMITS)[0] is True, line["compared"]
    assert line["failed"] == 0
    metrics = line["metrics"]
    for name in ("mf_update_s_per_fit",
                 "mf_refit_s_per_fit", "mf_layout_s", "fe_solve_s_per_fit",
                 "build_coordinates_s", "compile_s", "cd_overhead_s_per_fit"):
        assert metrics[name]["value"] > 0, name
    assert metrics["mf_straggler_ratio"]["value"] >= 1.0
    assert metrics["compiles_in_window"]["value"] == 0
    assert [s["coordinate"] for s in line["steps"]] == [
        "fixed", "user-x-movie"] * 2  # two sweeps, four evaluations
    assert all(s["solver_iterations"][0] < 20 for s in line["steps"][1::2])


def test_control_and_fault_are_not_correct_in_this_cell():
    files = readings.load(CELL)
    limits = REHEARSAL_LIMITS
    lines = readings.one_seed(files, seed=2147483777, rows=ROWS,
                              control=True, fault=True, force_tiled=True)
    by_kind = {line["what"]: line["numbers"] for line in lines}
    assert compare.judge(by_kind["program"], limits)[0] is True
    assert compare.judge(by_kind["fault_half_batch"], limits)[0] is False
    assert compare.judge(by_kind["control_bfloat16"], limits)[0] is False
