"""MovieLens-20M's SHAPE from a seed, for a GLMix fit with two id columns:
20,000,263 ratings by 138,493 users (at least 20 each) of 27,278 listed
movies (26,744 of them rated), every movie one to eight of 20 genre flags
and a release decade. There is no network here and nothing is fetched.

From the ml-20m README (files.grouplens.org/datasets/movielens/
ml-20m-README.html): the three counts, the floor of 20 ratings a user, the
19 named genres + "(no genres listed)". From the public file's own counts,
written from memory (``from_source`` in the configuration's file): ratings
per user least 20 / median about 68 / mean 144.4 / most 9,254; ratings per
movie median about 18 / mean 748 / most 67,310, several thousand movies
with one rating. FITTED here, and listed under ``assumed``: both laws are a
lognormal that saturates smoothly at its maximum (sigma 1.42 and 3.1, the
scale solved so that the counts add up to the ratings), the genre
frequencies and the decades' shares, who rates what, the split, the
response and the planted model.

What depends on the SHAPE ALONE (so that the random-effect buckets, every
compiled shape and the solver's work are alike from seed to seed):

* ratings per user: one draw of the law, exact;
* who rates what: a user with c ratings takes the movies whose weight
  times c is 1 or more for certain and spreads the rest by systematic
  sampling along the other movies' weights, so no pair occurs twice; the
  weights are the movies' law, refitted a few rounds so that the expected
  ratings per movie follow it where heavy users saturate the popular
  movies (pairs are otherwise independent: the public file's correlations
  cannot be written from memory). Ratings per movie come out of this (at
  full size: most 67,2xx for the law's 67,310, median 17), and a movie
  that drew none takes one rating from a heavy user's most rated movies,
  so that all 26,744 are rated;
* every movie's genres and decade;
* which 2,000,263 ratings are the validation rows (a uniform draw);
* the planted GLMix model (global coefficients over intercept + 20 genres
  + 11 decades, a per-user vector over intercept + genres, a per-movie
  intercept, all normal) and every rating's label: 1 where a rating would
  be 4.0 or more, about half the rows, which the logits' intercept is set
  for;
* the order of the rows (one shuffle).

The SEED renames the users and the movies (a permutation of each id
space) and nothing else: the public file is ONE data set, and every seed
trains on it. ISSUE 30 had the seed draw the model and the labels and
shuffle the rows. Measured on the chip (PERF.md, Findings PR 30): with
model and labels by the seed, how many Newton iterations a bucket's slowest
entity needs moved a fit's length by 1.78% from seed to seed; with those
two from the shape and only the rows' order by the seed, every sum rounds
another way, float32 ends the second sweep's warm-started L-BFGS after 1,
2 or 3 iterations by chance, and a fit's length still moves by 4%. A cell
is admitted at a spread under 0.5%.

Returns ``{"train": split, "validation": split}``; every array of a split
has the rows on its first axis (so that a reader may take every second
row): ``y`` [n], ``userId`` and ``movieId`` [n] int64, and per feature shard
``<shard>_cols`` / ``<shard>_vals`` [n, width], rows padded with value 0 at
column 0: ``global`` (the fixed effect's 32 features: intercept, genre
flags and the release decade's flag, dummy-coded against the 2000s; a set
flag holds 1 over its column's standard deviation; 2 to 10 nonzeros a row,
width 10), ``user`` (intercept + the movie's genre flags, value 1, for the
per-user effect, width 9), ``movie`` (an intercept alone). Plain numpy,
vectorised; a rehearsal's smaller shape keeps the laws' floors and sigmas
(``benchmark/drivers/game_fit_mixed.py`` scales the counts).
"""

from __future__ import annotations

import numpy as np

GENRES = 20  # 19 named + "(no genres listed)", the last
DECADES = 11  # before 1920, the 1920s ... the 2010s
FE_FEATURES = 1 + GENRES + DECADES
USER_FEATURES = 1 + GENRES
MAX_GENRES_A_MOVIE = 8
#: movies carrying each genre among the 27,278 listed (movies.csv, from
#: memory: ``assumed``), "(no genres listed)" last and exclusive
GENRE_MOVIES = (
    13344, 8374, 4178, 4127, 3520, 2939, 2611, 2471, 2329, 1743, 1514, 1412,
    1194, 1139, 1036, 1027, 676, 330, 196, 246)
#: share of the movies by release decade (``assumed``)
DECADE_SHARES = (
    0.005, 0.01, 0.025, 0.035, 0.045, 0.055, 0.07, 0.105, 0.17, 0.31, 0.17)
#: share of the movies whose title carries no year (``assumed``): they set
#: no decade flag
NO_YEAR_SHARE = 0.01
#: the decade that is the release flags' REFERENCE level (the 2000s, the
#: most common): its column exists and is never set, as dummy coding beside
#: an intercept has it. Eleven flags that add up to the intercept in every
#: row would leave a direction that only the L2 term holds, which float32
#: cannot resolve at 18M rows: two float32 L-BFGS runs then end 20% of the
#: coefficient vector apart (PERF.md, Findings PR 30)
REFERENCE_DECADE = 9
SIGMA_GLOBAL, SIGMA_USER, SIGMA_MOVIE = 0.5, 0.5, 0.7
POSITIVE_RATE = 0.5
IPF_ROUNDS = 6


def entity_law(n: int, total: int, floor: int, cap: int, sigma: float,
               salt: int) -> np.ndarray:
    """[n] counts, ascending, that add up to ``total``: ``floor`` + a
    lognormal (``sigma``) that saturates smoothly at ``cap``, the largest
    set to ``cap`` itself. One draw from the shape alone."""
    if not floor * n <= total <= cap * n:
        raise ValueError(
            f"{total} rows cannot be spread over {n} entities of "
            f"{floor} to {cap} rows")
    rng = np.random.default_rng([n, total, salt])
    x = np.exp(sigma * np.sort(rng.standard_normal(n)))
    span = float(cap - floor)

    def counts(scale):
        return np.floor(floor + span * -np.expm1(-scale * x / span))

    lo, hi = 0.0, 1.0
    while counts(hi).sum() < total and hi < 1e18:
        hi *= 2.0
    for _ in range(100):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if counts(mid).sum() < total else (lo, mid)
    c = counts(lo).astype(np.int64)
    c[-1] = cap
    # what the floor() and the forced maximum left over or took too much:
    # one row each from (or to) the entities just under the largest
    rest = int(total - c.sum())
    step = 1 if rest > 0 else -1
    i = n - 2
    while rest != 0:
        if i < 0:
            i = n - 2
        if floor <= c[i] + step <= cap:
            c[i] += step
            rest -= step
        i -= 1
    return np.sort(c)


def _certain(weights_desc: np.ndarray, counts: np.ndarray):
    """For each of ``counts`` (ratings of a user): how many of the heaviest
    movies it takes for certain (s), and the weight left after them. A user
    with c ratings takes movie j for certain while (c - j) * w_j is at least
    the weight from j on; that bound rises with j, so s is a search."""
    tail = np.cumsum(weights_desc[::-1])[::-1]
    bound = np.arange(len(weights_desc)) + tail / weights_desc
    s = np.searchsorted(bound, counts, side="right")
    s = np.minimum(s, len(weights_desc) - 1)
    return s, tail[s]


def _expected(weights_desc, counts, users_of_count):
    """Expected ratings of every movie under the sampling below."""
    s, left = _certain(weights_desc, counts)
    m = len(weights_desc)
    # users for whom movie j is certain: those with s > j
    certain = np.zeros(m + 1)
    np.add.at(certain, s, users_of_count)
    certain = certain.sum() - np.cumsum(certain)[:m]
    # the others draw it with probability (c - s) * w_j / left
    rate = np.zeros(m + 1)
    np.add.at(rate, s, users_of_count * (counts - s) / left)
    return certain + weights_desc * np.cumsum(rate)[:m]


def incidence(shape: dict):
    """(user rank [N], movie rank [N], ratings per user [U] descending):
    who rates what, user after user, from the shape alone. Users are ranked
    by their ratings, movies by their weight."""
    users, rated = int(shape["users"]), int(shape["rated_movies"])
    total = int(shape["rows"]) + int(shape["validation_rows"])
    per_user = entity_law(
        users, total, min(int(shape["min_rows_per_user"]), rated // 2),
        min(int(shape["max_rows_per_user"]), rated // 2),
        float(shape["user_sigma"]), 0)[::-1]
    target = entity_law(
        rated, total, 1, min(int(shape["max_rows_per_movie"]), users),
        float(shape["movie_sigma"]), 1)[::-1].astype(np.float64)
    counts, users_of_count = np.unique(per_user, return_counts=True)
    weights = target / target.sum()
    for _ in range(IPF_ROUNDS):
        got = _expected(weights, counts, users_of_count)
        weights = np.sort(weights * target / np.maximum(got, 1e-12))[::-1]
        weights = weights / weights.sum()
    s_of, _ = _certain(weights, counts)
    rng = np.random.default_rng([users, rated, total, 2])
    movie = np.empty(total, np.int64)
    starts = np.concatenate([[0], np.cumsum(per_user)])
    first = np.searchsorted(-per_user, -counts, side="left")  # by count
    for c, s, lo, k_users in zip(counts, s_of, first, users_of_count):
        k = int(c - s)
        picks = np.empty((k_users, c), np.int64)
        picks[:, :s] = np.arange(s)
        if k:
            cum = np.cumsum(weights[s:])
            cum = cum / cum[-1]
            at = (rng.random((k_users, 1)) + np.arange(k)) / k
            picks[:, s:] = s + np.minimum(
                np.searchsorted(cum, at, side="right"), len(cum) - 1)
        movie[starts[lo]:starts[lo + k_users]] = picks.reshape(-1)
    # a movie of one or two expected ratings may have drawn none: each such
    # movie takes one rating of one of the heaviest users, from one of the
    # most rated movies (which that user holds for certain), so every movie
    # of the law is rated and no user's count moves
    empty = np.flatnonzero(np.bincount(movie, minlength=rated) == 0)
    givers = np.arange(len(empty)) % users
    spread = min(50, int(per_user[givers].min()), int(s_of.max()) or 1)
    movie[starts[givers] + np.arange(len(empty)) % spread] = empty
    user = np.repeat(np.arange(users), per_user)
    return user, movie, per_user


def movie_attributes(shape: dict):
    """(genre flags [rated, 20] bool, decade [rated], -1 where the title
    has no year) by movie rank, from the shape alone."""
    rated = int(shape["rated_movies"])
    rng = np.random.default_rng([rated, int(shape["movies"]), 3])
    p = np.asarray(GENRE_MOVIES[:-1], np.float64) / 27278.0
    flags = rng.random((rated, GENRES - 1)) < p
    # at most eight flags a movie: the rarest of a fuller draw go
    over = flags.sum(axis=1) > MAX_GENRES_A_MOVIE
    for i in np.flatnonzero(over):
        on = np.flatnonzero(flags[i])
        flags[i, on[MAX_GENRES_A_MOVIE:]] = False
    none = ~flags.any(axis=1)
    listed = rng.random(rated) < GENRE_MOVIES[-1] / max(none.mean(), 1e-9) / 27278.0
    flags = np.concatenate([flags, (none & listed)[:, None]], axis=1)
    flags[none & ~listed, 0] = True  # the most common genre
    decade = rng.choice(DECADES, size=rated, p=np.asarray(DECADE_SHARES))
    decade[rng.random(rated) < NO_YEAR_SHARE] = -1
    return flags, decade


def _expand(indptr_of, items_of, keys):
    """For each of ``keys`` the items ``items_of[indptr_of[k]:indptr_of[k+1]]``,
    concatenated; and how many each key gave."""
    n_of = (indptr_of[1:] - indptr_of[:-1])[keys]
    start = np.cumsum(n_of) - n_of
    within = np.arange(int(n_of.sum())) - np.repeat(start, n_of)
    return items_of[np.repeat(indptr_of[:-1][keys], n_of) + within], n_of


def _rows(middle, n_middle, width, last=None, value=None):
    """([n, width] int32 columns, [n, width] float32 values): column 0 (the
    intercept, value 1) first, then each row's ``middle`` columns, then its
    ``last`` column where that is not negative; a set column c holds
    ``value[c]`` (1 without ``value``), the pad slots column 0 with value
    0."""
    n = len(n_middle)
    cols = np.zeros((n, width), np.int32)
    vals = np.zeros((n, width), np.float32)
    vals[:, 0] = 1.0
    row = np.repeat(np.arange(n), n_middle)
    slot = 1 + np.arange(len(middle)) - np.repeat(
        np.cumsum(n_middle) - n_middle, n_middle)
    cols[row, slot] = middle
    vals[row, slot] = 1.0 if value is None else value[middle]
    if last is not None:
        has = np.flatnonzero(last >= 0)
        cols[has, 1 + n_middle[has]] = last[has]
        vals[has, 1 + n_middle[has]] = (
            1.0 if value is None else value[last[has]])
    return cols, vals


def decade_flag(decade: np.ndarray) -> np.ndarray:
    """The fixed effect's column of a release decade, -1 where none is set:
    no year in the title, or the reference level."""
    none = (decade < 0) | (decade == REFERENCE_DECADE)
    return np.where(none, -1, 1 + GENRES + decade)


def column_scale(movie, g_indptr, g_items, decade) -> np.ndarray:
    """[32] the value a set column of the fixed effect's shard holds: 1 over
    the column's standard deviation over ALL the ratings (from the shape
    alone: the same for every seed), the intercept 1. The upstream library's
    SCALE_WITH_STANDARD_DEVIATION, applied when the data is written: the
    plain reference has no normalization of its own, and unscaled flags of
    0.7% to 61% of the rows keep L-BFGS from its float32 end for dozens of
    iterations."""
    n = len(movie)
    count = np.zeros(FE_FEATURES)
    genres, _ = _expand(g_indptr, g_items, movie)
    count[1:1 + GENRES] = np.bincount(genres, minlength=GENRES)
    flag = decade_flag(decade[movie])
    count += np.bincount(flag[flag >= 0], minlength=FE_FEATURES)
    share = count / n
    scale = np.ones(FE_FEATURES)
    on = share > 0
    scale[on] = 1.0 / np.sqrt(np.maximum(share[on] * (1 - share[on]), 1e-12))
    scale[0] = 1.0
    return scale.astype(np.float32)


def _logits(user, movie, g_indptr, g_items, decade, model):
    """[N] float64 logits of the planted model, less its intercept, for the
    canonical rows (user rank, movie rank)."""
    genres, n_genres = _expand(g_indptr, g_items, movie)
    w_f, w_u, w_m = model
    row_of = np.repeat(np.arange(len(movie)), n_genres)
    flag = decade_flag(decade[movie])
    return (
        w_f[0] + np.where(flag >= 0, w_f[flag], 0.0) + w_u[user, 0]
        + w_m[movie]
        + np.bincount(
            row_of, weights=w_f[1 + genres] + w_u[user[row_of], 1 + genres],
            minlength=len(movie))).astype(np.float64)


def generate(shape: dict, seed: int) -> dict:
    users, movies = int(shape["users"]), int(shape["movies"])
    rated = int(shape["rated_movies"])
    n, n_val = int(shape["rows"]), int(shape["validation_rows"])
    user, movie, _ = incidence(shape)
    flags, decade = movie_attributes(shape)
    g_items = np.nonzero(flags)[1].astype(np.int64)
    g_indptr = np.concatenate([[0], np.cumsum(flags.sum(axis=1))])
    scale = column_scale(movie, g_indptr, g_items, decade)
    held = np.zeros(n + n_val, bool)
    held[np.random.default_rng([n, n_val, 4]).choice(
        n + n_val, size=n_val, replace=False)] = True

    # the planted model and the labels: from the shape alone, a draw per
    # canonical row, so that every seed poses the same fit
    rng = np.random.default_rng([users, rated, n + n_val, 5])
    w_f = (rng.standard_normal(FE_FEATURES) * SIGMA_GLOBAL).astype(np.float32)
    w_u = (rng.standard_normal((users, USER_FEATURES)) * SIGMA_USER).astype(
        np.float32)
    w_m = (rng.standard_normal(rated) * SIGMA_MOVIE).astype(np.float32)
    logit = _logits(user, movie, g_indptr, g_items, decade, (w_f, w_u, w_m))
    # the intercept that makes half the training rows positive
    train_rows = np.flatnonzero(~held)
    sample = logit[train_rows[::max(1, len(train_rows) >> 20)]]
    lo, hi = -30.0, 30.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        rate = np.mean(1.0 / (1.0 + np.exp(-(sample + mid))))
        lo, hi = (mid, hi) if rate < POSITIVE_RATE else (lo, mid)
    p = 1.0 / (1.0 + np.exp(-(logit + 0.5 * (lo + hi))))
    label = (rng.random(n + n_val) < p).astype(np.float32)
    del logit, p

    # the rows' order: one shuffle from the shape alone (the canonical
    # rows run user after user, heaviest first)
    rng = np.random.default_rng([n, n_val, 6])
    order = {"train": rng.permutation(train_rows),
             "validation": rng.permutation(np.flatnonzero(held))}
    rng = np.random.default_rng(int(seed))
    user_id = rng.permutation(users)  # rank -> id
    movie_id = rng.permutation(movies)[:rated]
    out = {}
    for name, rows in order.items():
        u, m = user[rows], movie[rows]
        genres, n_genres = _expand(g_indptr, g_items, m)
        split = {"y": label[rows], "userId": user_id[u],
                 "movieId": movie_id[m]}
        split["global_cols"], split["global_vals"] = _rows(
            1 + genres, n_genres, 2 + MAX_GENRES_A_MOVIE,
            decade_flag(decade[m]), scale)
        split["user_cols"], split["user_vals"] = _rows(
            1 + genres, n_genres, 1 + MAX_GENRES_A_MOVIE)
        split["movie_cols"], split["movie_vals"] = _rows(
            genres[:0], np.zeros(len(u), np.int64), 1)
        out[name] = split
    return out
