"""Operations and bytes one whole coordinate-descent fit requires, one
(FLOPs, bytes) pair per coordinate update, from the shapes and from the
iterations the program's own trackers report.

Fixed effect, per objective evaluation (iterations + 1 of them): margins and
gradient are one multiply-add per nonzero each (4 FLOPs/nnz) over ONE read
of the design (8 bytes/nnz) and of three per-row vectors; its scores are one
more gather pass. Random effect, per Newton iteration and row of K dense
features: margin 2K, gradient 2K, Hessian 2K^2 FLOPs, (K + 3) float32 read;
its scores 2K FLOPs a row. Line-search evaluations, validation scoring, the
Cholesky solves and AUC are left out: an undercount is allowed, an overcount
is not."""


def per_fit(shapes: dict, steps: list[dict]):
    for step in steps:
        shape = shapes["coordinates"][step["coordinate"]]
        its = step["solver_iterations"]
        if its != its:  # no tracker: nothing can be counted for this update
            continue
        if shape["kind"] == "fixed_effect":
            nnz, rows = shape["nnz"], shapes["rows"]
            evals = its + 1
            yield (4.0 * nnz * evals + 2.0 * nnz,
                   (8.0 * nnz + 12.0 * rows) * evals + 8.0 * nnz)
        elif shape["kind"] == "random_effect":
            rows, k = shape["rows"], shape["features"]
            yield (its * rows * (4.0 * k + 2.0 * k * k) + 2.0 * k * rows,
                   (its + 1) * rows * (k + 3) * 4.0)
