"""Planted GLMix data from a seed: one sparse fixed-effect shard and,
where the configuration has users, one dense per-user shard.

The shape is the one ``bench_game.py`` and ``chip_smoke.py`` generate
(BASELINE config #4), a SYNTHETIC one that no public dataset bears out
(PERF.md, section 4): every row has exactly ``fe_nnz_per_row`` fixed-effect
features drawn uniformly from ``fe_features`` with N(0,1) values; a user
drawn uniformly; ``re_features`` dense N(0,1) user-shard values; a label
drawn from the planted logistic model.

Every seed gets the same WORK in another order: how many training and
validation rows each user has is one uniform draw made from the shape alone
(not from the seed); the seed then decides which user gets which count and
where its rows lie. So the random-effect buckets, every compiled shape and
the solver's work are the same from seed to seed, as the driver's bounds
need, while values, columns, labels and the planted model all change.

Copied here (vectorised: row sums, never ``np.add.at``) because the
benchmark's inputs may not come from the program. Everything is plain numpy:
the reference reads these arrays, the driver turns them into the program's
dataset.
"""

from __future__ import annotations

import numpy as np


def _row_counts(n: int, n_users: int, salt: int) -> np.ndarray:
    """Rows per user slot: one uniform draw that depends on the shape only."""
    rng = np.random.default_rng([n, n_users, salt])
    return rng.multinomial(n, np.full(n_users, 1.0 / n_users))


def _split(rng, n: int, shape: dict, w_true, wu_true, perm, salt) -> dict:
    d, k = int(shape["fe_features"]), int(shape["fe_nnz_per_row"])
    cols = rng.integers(0, d, size=(n, k), dtype=np.int32)
    vals = rng.standard_normal(size=(n, k), dtype=np.float32)
    logit = np.einsum("ij,ij->i", vals, w_true[cols])
    out = {"cols": cols, "vals": vals, "users": None, "xu": None}
    if wu_true is not None:
        counts = _row_counts(n, len(perm), salt)
        users = np.repeat(perm, counts)[rng.permutation(n)]
        xu = rng.standard_normal(
            size=(n, wu_true.shape[1]), dtype=np.float32)
        logit = logit + np.einsum("ij,ij->i", xu, wu_true[users])
        out["users"], out["xu"] = users, xu
    p = 1.0 / (1.0 + np.exp(-logit.astype(np.float64)))
    out["y"] = (rng.random(n) < p).astype(np.float32)
    return out


def generate(shape: dict, seed: int) -> dict:
    """``{"train": split, "validation": split}``; a split holds ``cols``
    and ``vals`` [n, nnz/row], ``y`` [n] and, with users, ``users`` [n] and
    ``xu`` [n, re_features]."""
    rng = np.random.default_rng(int(seed))
    d = int(shape["fe_features"])
    n_users = int(shape.get("users", 0))
    w_true = (rng.standard_normal(d) * 0.5).astype(np.float32)
    wu_true = perm = None
    if n_users:
        perm = rng.permutation(n_users).astype(np.int32)
        wu_true = (
            rng.standard_normal((n_users, int(shape["re_features"]))) * 0.5
        ).astype(np.float32)
    n = int(shape["rows"])
    n_val = int(shape["validation_rows"])
    return {
        "train": _split(rng, n, shape, w_true, wu_true, perm, 0),
        "validation": _split(rng, n_val, shape, w_true, wu_true, perm, 1),
    }
