"""Operations and bytes the refit of a factored coordinate's projection
requires: ``vec(A)`` [K, d] refitted as one GLM whose margins are
``sum_l C[:, l] * (X A[l, :])`` and whose gradient w.r.t. ``A[l, :]`` is
``X^T (g * C[:, l])``, X the shard's design (nnz nonzeros over ``rows``
rows), C the rows' latent vectors.

Counted from what the ALGORITHM needs, whatever implements it: one
evaluation (margins + gradient) is two multiply-adds per nonzero and latent
dimension (4*K FLOPs a nonzero), and has to read the design once (8 bytes a
nonzero), the rows' latent vectors (4*K bytes a row), three per-row vectors
(12 bytes a row) and ``A`` (4*K*d bytes). The evaluations are the program's
own count (counter ``mf.<coordinate>.refit_evaluations``: L-BFGS iterations
+ 1 a refit). Line-search evaluations in margin space, the two-loop
recursion and the padding rows of the coordinate's buckets are left out: an
undercount keeps a roofline share under 100%.

``per_call`` is ONE of an evaluation's two passes over the design (the
projection ``X A^T`` or the scatter ``X^T (g * C)``) as a Mosaic call makes
it: half the operations, the design and ``A`` once, and the [K, rows] side
it writes or reads."""


def _sizes(shape: dict):
    mf = shape["mf"]
    return (float(mf["nnz"]), float(mf["rows"]), float(mf["latent_dim"]),
            float(mf["features"]))


def per_fit(shape: dict, evaluations: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one fit's refit evaluations."""
    nnz, rows, k, d = _sizes(shape)
    return (evaluations * 4.0 * k * nnz,
            evaluations * (8.0 * nnz + (4.0 * k + 12.0) * rows
                           + 4.0 * k * d))


def per_call(shape: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one projection or scatter pass over the design."""
    nnz, rows, k, d = _sizes(shape)
    return 2.0 * k * nnz, 8.0 * nnz + 4.0 * k * rows + 4.0 * k * d
