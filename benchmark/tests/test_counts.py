"""Each counts function against a count made by hand at a tiny shape."""

from benchmark.counts import glmix_fit, tiled_pass


def test_tiled_pass_hand_count():
    # 12 nonzeros in one tile of 128 rows: 12 multiply-adds; 12 values and
    # 12 columns of 4 bytes; 128 rows read once and written once
    flops, nbytes = tiled_pass.per_call({"nnz": 12, "T": 1, "S": 128, "B": 1})
    assert flops == 24
    assert nbytes == 12 * 8 + 128 * 4 * 2


def test_glmix_fit_hand_count():
    shapes = {
        "rows": 4,
        "coordinates": {
            "fixed": {"kind": "fixed_effect", "nnz": 12, "T": 1},
            "per-user": {"kind": "random_effect", "rows": 4, "features": 2},
            "coo": {"kind": "fixed_effect_coo"},
        },
    }
    steps = [
        {"coordinate": "fixed", "solver_iterations": 2.0},
        {"coordinate": "per-user", "solver_iterations": 3.0},
        {"coordinate": "coo", "solver_iterations": 2.0},
        {"coordinate": "fixed", "solver_iterations": float("nan")},
    ]
    got = list(glmix_fit.per_fit(shapes, steps))
    # fixed: 3 evaluations of 4 FLOPs/nnz + one scoring pass of 2 FLOPs/nnz;
    # 3 reads of (8 B/nnz + 3 row vectors) + the scoring read
    fe = (4 * 12 * 3 + 2 * 12, (8 * 12 + 12 * 4) * 3 + 8 * 12)
    # per-user: 3 iterations x 4 rows x (2K + 2K + 2K^2 = 16) + scores 2K a
    # row; 4 reads of 4 rows x (K + 3) float32
    re = (3 * 4 * 16 + 2 * 2 * 4, 4 * 4 * 5 * 4)
    assert got == [fe, re]  # the COO coordinate and the NaN step count nothing
