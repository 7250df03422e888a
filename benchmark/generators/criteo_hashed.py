"""Hashed click-log rows from a seed, in the row format of LIBSVM's
``criteo`` (binary.html: 45,840,617 rows, 1,000,000 hashed features, 39
nonzeros in every row, every value 1/sqrt(39)): 13 numeric fields, bucketed,
and 26 categorical fields whose public cardinalities are those of the Kaggle
Display Advertising Challenge set.

A row draws one id per field; the feature is a FIXED hash of (field, id) mod
``fe_features`` (the same for every seed, so the hot features, the column
histogram and with them the program's layout are alike from seed to seed).
Two fields of a row may hash to the same feature: the row then holds the
column twice, and a design sums them, as the LIBSVM file's reader would.

What no public source bears out is listed under ``assumed`` in the
configuration's file: categorical ids follow a bounded power law with
exponent 1.1 over the field's cardinality (rank = floor of the inverse CDF of
the continuous law: cheap enough for 273M draws), numeric fields one of 40
buckets, geometric with ratio 0.8, labels from a planted logistic model over
the features with an intercept that sets the click rate near 25%.

Returns what ``planted_glmix.generate`` returns, so that the driver and the
plain reference read it unchanged. Plain numpy; the rows are drawn in
blocks, each from its own stream of the seed, on a few threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

NUMERIC_FIELDS = 13
NUMERIC_BUCKETS = 40
BUCKET_RATIO = 0.8
ZIPF_EXPONENT = 1.1
CLICK_RATE = 0.25
# Kaggle Display Advertising Challenge, categorical fields C1..C26
CARDINALITIES = (
    1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683,
    8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18, 15,
    286181, 105, 142572)
FIELDS = NUMERIC_FIELDS + len(CARDINALITIES)
BLOCK_ROWS = 1 << 18
_MASK = np.uint64((1 << 64) - 1)


def hash_feature(field: int, ids: np.ndarray, d: int) -> np.ndarray:
    """(field, id) -> feature in [0, d): splitmix64's finaliser over
    ``id * FIELDS + field``. Fixed: no seed enters."""
    x = ids.astype(np.uint64) * np.uint64(FIELDS) + np.uint64(field)
    x = (x + np.uint64(0x9E3779B97F4A7C15)) & _MASK
    x = ((x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _MASK
    x = ((x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _MASK
    x = x ^ (x >> np.uint64(31))
    return (x % np.uint64(d)).astype(np.int32)


def _field_ids(rng, field: int, n: int) -> np.ndarray:
    u = rng.random(n)
    if field < NUMERIC_FIELDS:
        # geometric over 40 buckets: inverse CDF of the truncated law
        top = 1.0 - BUCKET_RATIO ** NUMERIC_BUCKETS
        b = np.floor(np.log1p(-u * top) / np.log(BUCKET_RATIO))
        return np.minimum(b, NUMERIC_BUCKETS - 1).astype(np.int64)
    card = CARDINALITIES[field - NUMERIC_FIELDS]
    e = 1.0 - ZIPF_EXPONENT
    # x in [1, card + 1) with density ~ x**-1.1; id = floor(x) - 1
    x = (1.0 - u * (1.0 - (card + 1.0) ** e)) ** (1.0 / e)
    return np.minimum(x.astype(np.int64) - 1, card - 1)


def _block(seq, n: int, d: int, w_true: np.ndarray):
    rng = np.random.default_rng(seq)
    cols = np.empty((n, FIELDS), np.int32)
    for f in range(FIELDS):
        cols[:, f] = hash_feature(f, _field_ids(rng, f, n), d)
    value = np.float32(1.0 / np.sqrt(FIELDS))
    logit = w_true[cols].sum(axis=1, dtype=np.float32) * value
    return cols, logit, rng.random(n)


def _blocks(seq, n: int):
    starts = list(range(0, n, BLOCK_ROWS))
    return starts, seq.spawn(len(starts))


def _intercept(seq, n: int, d: int, w_true: np.ndarray) -> float:
    """The logits' intercept that gives the click rate, by bisection on the
    mean click probability of the split's first block."""
    _, seqs = _blocks(seq, n)
    _, logit, _ = _block(seqs[0], min(n, BLOCK_ROWS), d, w_true)
    lo, hi = -30.0, 30.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        rate = np.mean(1.0 / (1.0 + np.exp(-(logit + mid))))
        lo, hi = (mid, hi) if rate < CLICK_RATE else (lo, mid)
    return 0.5 * (lo + hi)


def _split(seq, n: int, d: int, w_true: np.ndarray, intercept) -> dict:
    starts, seqs = _blocks(seq, n)
    cols = np.empty((n, FIELDS), np.int32)
    y = np.empty((n,), np.float32)

    def work(i):
        s = starts[i]
        m = min(BLOCK_ROWS, n - s)
        c, logit, u = _block(seqs[i], m, d, w_true)
        cols[s:s + m] = c
        p = 1.0 / (1.0 + np.exp(-(logit.astype(np.float64) + intercept)))
        y[s:s + m] = u < p

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(work, range(len(starts))))
    vals = np.full((n, FIELDS), 1.0 / np.sqrt(FIELDS), np.float32)
    return {"cols": cols, "vals": vals, "y": y, "users": None, "xu": None}


def generate(shape: dict, seed: int) -> dict:
    """``{"train": split, "validation": split}``; a split holds ``cols``
    and ``vals`` [n, 39], ``y`` [n], and ``users`` / ``xu`` None."""
    if int(shape["fe_nnz_per_row"]) != FIELDS:
        raise ValueError(f"this generator draws {FIELDS} nonzeros a row")
    d = int(shape["fe_features"])
    root = np.random.SeedSequence(int(seed))
    s_model, s_train, s_val = root.spawn(3)
    w_true = np.random.default_rng(s_model).standard_normal(d).astype(
        np.float32)
    n, n_val = int(shape["rows"]), int(shape["validation_rows"])
    intercept = _intercept(s_train, n, d, w_true)
    return {
        "train": _split(s_train, n, d, w_true, intercept),
        "validation": _split(s_val, n_val, d, w_true, intercept),
    }
