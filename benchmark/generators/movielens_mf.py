"""MovieLens-20M's SHAPE from a seed, for GAME's matrix-factorization
coordinate: ``movielens_mixed``'s ratings (the same counts, the same two
rows-per-entity laws, the same incidence, genres, decades and split: its
functions are called, nothing is redrawn another way) with

* one more feature shard, ``movie_onehot``: the rated movie as ONE column of
  27,278 with value 1 (the factored coordinate's shard: ``A x`` of a row is
  the movie's latent vector). The column is the movie's RANK in the shape's
  own draw, the same for every seed: a feature's index is not an entity id,
  and the program's seeded draw of ``A`` then meets the same movie in the
  same column whatever the seed;
* labels from a planted model = the planted fixed effect over the 32 global
  columns + a planted rank-16 term ``a*_movie . c*_user`` (every entry of
  both N(0, ``SIGMA_LATENT``^2), so the term is N(0, 1)-like), no per-user
  or per-movie effect. Over all ratings the two margins' standard
  deviations are 0.441 (fixed effect, less its intercept) and 0.999 (latent
  term) at the full size (``margin_std`` recomputes both).

As in ``movielens_mixed``, model, labels and row order are ONE draw from the
shape alone, so that every seed runs the same computation (PERF.md section
7). Here the seed renames the MOVIES (the ``movieId`` column) and leaves a
user's id at its rank: the factored coordinate lays its rows out user after
user in the order of their ids, so a permutation of the users permutes the
float32 sums of its refit, and the last bits of its scores decide at which
iteration the next fixed-effect L-BFGS (tolerance 0) stops improving: 7, 9
or 10 over seven seeds on the chip, ``train_rows_per_s`` 1.15% apart and
``coef_rel.fixed`` between 2.4e-4 and 4.3e-3 (PERF.md, Findings PR 33).

Returns ``{"train": split, "validation": split}``; a split holds ``y``,
``userId``, ``movieId`` and per shard ``<shard>_cols`` / ``<shard>_vals``
[n, width]: ``global`` (as ``movielens_mixed``) and ``movie_onehot``
(width 1).
"""

from __future__ import annotations

import numpy as np

from benchmark.generators import movielens_mixed as ml

LATENT_DIM = 16
#: a*_movie and c*_user entries: 16 products of two N(0, 0.5^2) draws add up
#: to a margin of standard deviation 16^0.5 * 0.25 = 1
SIGMA_LATENT = 0.5


def _planted(shape: dict):
    """(fixed-effect logits less the intercept [N], latent logits [N],
    user rank [N], movie rank [N], genre index) for the canonical rows."""
    users, rated = int(shape["users"]), int(shape["rated_movies"])
    total = int(shape["rows"]) + int(shape["validation_rows"])
    user, movie, _ = ml.incidence(shape)
    flags, decade = ml.movie_attributes(shape)
    g_items = np.nonzero(flags)[1].astype(np.int64)
    g_indptr = np.concatenate([[0], np.cumsum(flags.sum(axis=1))])
    rng = np.random.default_rng([users, rated, total, 7])
    w_f = (rng.standard_normal(ml.FE_FEATURES) * ml.SIGMA_GLOBAL).astype(
        np.float32)
    a = (rng.standard_normal((rated, LATENT_DIM)) * SIGMA_LATENT).astype(
        np.float32)
    c = (rng.standard_normal((users, LATENT_DIM)) * SIGMA_LATENT).astype(
        np.float32)
    genres, n_genres = ml._expand(g_indptr, g_items, movie)
    flag = ml.decade_flag(decade[movie])
    fixed = (
        np.where(flag >= 0, w_f[flag], 0.0)
        + np.bincount(np.repeat(np.arange(total), n_genres),
                      weights=w_f[1 + genres], minlength=total))
    latent = np.empty(total, np.float64)
    step = 1 << 22  # [step, 16] float32 products, not [N, 16]
    for lo in range(0, total, step):
        u, m = user[lo:lo + step], movie[lo:lo + step]
        latent[lo:lo + step] = np.einsum(
            "nk,nk->n", a[m], c[u], dtype=np.float64)
    return fixed, latent, user, movie, (g_indptr, g_items, decade)


def margin_std(shape: dict) -> tuple[float, float]:
    """Standard deviations of the two planted margins over all ratings."""
    fixed, latent, *_ = _planted(shape)
    return float(np.std(fixed)), float(np.std(latent))


def generate(shape: dict, seed: int) -> dict:
    users, movies = int(shape["users"]), int(shape["movies"])
    rated = int(shape["rated_movies"])
    n, n_val = int(shape["rows"]), int(shape["validation_rows"])
    fixed, latent, user, movie, (g_indptr, g_items, decade) = _planted(shape)
    scale = ml.column_scale(movie, g_indptr, g_items, decade)
    held = np.zeros(n + n_val, bool)
    held[np.random.default_rng([n, n_val, 4]).choice(
        n + n_val, size=n_val, replace=False)] = True

    logit = fixed + latent
    del fixed, latent
    # the intercept that makes half the training rows positive
    train_rows = np.flatnonzero(~held)
    sample = logit[train_rows[::max(1, len(train_rows) >> 20)]]
    lo, hi = -30.0, 30.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        rate = np.mean(1.0 / (1.0 + np.exp(-(sample + mid))))
        lo, hi = (mid, hi) if rate < ml.POSITIVE_RATE else (lo, mid)
    p = 1.0 / (1.0 + np.exp(-(logit + 0.5 * (lo + hi))))
    rng = np.random.default_rng([users, rated, n + n_val, 8])
    label = (rng.random(n + n_val) < p).astype(np.float32)
    del logit, p

    rng = np.random.default_rng([n, n_val, 6])
    order = {"train": rng.permutation(train_rows),
             "validation": rng.permutation(np.flatnonzero(held))}
    rng = np.random.default_rng(int(seed))
    rng.permutation(users)  # ``movielens_mixed`` renames its users here
    user_id = np.arange(users)  # rank -> id: a user keeps its place
    movie_id = rng.permutation(movies)[:rated]
    out = {}
    for name, rows in order.items():
        u, m = user[rows], movie[rows]
        genres, n_genres = ml._expand(g_indptr, g_items, m)
        split = {"y": label[rows], "userId": user_id[u],
                 "movieId": movie_id[m]}
        split["global_cols"], split["global_vals"] = ml._rows(
            1 + genres, n_genres, 2 + ml.MAX_GENRES_A_MOVIE,
            ml.decade_flag(decade[m]), scale)
        split["movie_onehot_cols"] = m.astype(np.int32)[:, None]
        split["movie_onehot_vals"] = np.ones((len(m), 1), np.float32)
        out[name] = split
    return out
