"""Data-plane tests: stats vs numpy, normalization contexts + model
back-transform, index maps (incl. mmap store), libsvm reader, validators."""


import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import (
    DataValidationError,
    IndexMap,
    MmapIndexMap,
    NormalizationType,
    ValidationMode,
    build_normalization_context,
    feature_key,
    read_libsvm,
    summarize,
    validate,
)
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.optim import lbfgs_solve, glm_adapter


def test_summary_matches_numpy(rng):
    n, d = 80, 10
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.6)
    batch = SparseBatch.from_dense(X, np.zeros(n))
    s = summarize(batch)
    np.testing.assert_allclose(s.mean, X.mean(0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s.variance, X.var(0, ddof=1), rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(s.max, X.max(0), rtol=1e-5)
    np.testing.assert_allclose(s.min, X.min(0), rtol=1e-5)
    np.testing.assert_allclose(s.num_nonzeros, (X != 0).sum(0), rtol=1e-6)
    np.testing.assert_allclose(s.norm_l1, np.abs(X).sum(0), rtol=1e-4)
    np.testing.assert_allclose(s.norm_l2, np.sqrt((X**2).sum(0)), rtol=1e-4)
    assert int(s.count) == n


def test_summary_ignores_padded_rows(rng):
    X = rng.normal(size=(30, 5))
    batch = SparseBatch.from_dense(X, np.zeros(30)).pad_rows_to(40, 200)
    s = summarize(batch)
    np.testing.assert_allclose(s.mean, X.mean(0), rtol=1e-4, atol=1e-5)
    assert int(s.count) == 30


def test_standardization_context_and_back_transform(rng):
    # train on standardized data, map coefficients back, scores must match
    n, d = 120, 8
    X = rng.normal(size=(n, d)) * 3 + 1.5
    X[:, -1] = 1.0  # intercept column
    y = (rng.random(n) < 0.5).astype(float)
    batch = SparseBatch.from_dense(X, y)
    s = summarize(batch)
    ctx = build_normalization_context(
        NormalizationType.STANDARDIZATION, s, intercept_index=d - 1
    )
    np.testing.assert_allclose(ctx.factors[-1], 1.0)
    np.testing.assert_allclose(ctx.shifts[-1], 0.0)

    obj_norm = make_objective(
        "logistic", l2_weight=0.1, factors=ctx.factors, shifts=ctx.shifts
    )
    res = lbfgs_solve(glm_adapter(obj_norm, batch), jnp.zeros(d, jnp.float32))
    w_orig = ctx.transform_model_coefficients(res.w)

    # margins with original-space coefficients on raw X == normalized-space
    # margins with trained coefficients
    z_norm = obj_norm.margins(res.w, batch)
    z_orig = batch.margins(w_orig, 0.0)
    np.testing.assert_allclose(z_orig, z_norm, rtol=1e-3, atol=1e-3)


def test_normalization_same_optimum_as_unnormalized(rng):
    # NormalizationTest.scala analog: optimizing with standardization then
    # back-transforming reaches the same solution as optimizing raw
    n, d = 150, 6
    X = np.hstack([rng.normal(size=(n, d - 1)) * np.asarray([1, 5, 0.2, 3, 0.7]),
                   np.ones((n, 1))])
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ rng.normal(size=d))))).astype(float)
    batch = SparseBatch.from_dense(X, y)
    raw = lbfgs_solve(
        glm_adapter(make_objective("logistic"), batch), jnp.zeros(d, jnp.float32)
    )
    ctx = build_normalization_context(
        NormalizationType.STANDARDIZATION, summarize(batch), intercept_index=d - 1
    )
    res = lbfgs_solve(
        glm_adapter(
            make_objective("logistic", factors=ctx.factors, shifts=ctx.shifts), batch
        ),
        jnp.zeros(d, jnp.float32),
    )
    w_back = ctx.transform_model_coefficients(res.w)
    np.testing.assert_allclose(w_back, raw.w, rtol=2e-2, atol=2e-2)


def test_scale_variants(rng):
    X = rng.normal(size=(50, 4)) * np.asarray([1.0, 10.0, 0.1, 5.0])
    batch = SparseBatch.from_dense(X, np.zeros(50))
    s = summarize(batch)
    c1 = build_normalization_context(NormalizationType.SCALE_WITH_MAX_MAGNITUDE, s)
    np.testing.assert_allclose(
        c1.factors, 1.0 / np.maximum(np.abs(X.max(0)), np.abs(X.min(0))), rtol=1e-4
    )
    c2 = build_normalization_context(
        NormalizationType.SCALE_WITH_STANDARD_DEVIATION, s
    )
    np.testing.assert_allclose(c2.factors, 1.0 / X.std(0, ddof=1), rtol=1e-3)
    with pytest.raises(ValueError, match="intercept"):
        build_normalization_context(NormalizationType.STANDARDIZATION, s)


def test_index_map_roundtrip(tmp_path):
    keys = [feature_key("age", ""), feature_key("country", "us"),
            feature_key("country", "de"), "plainfeature"]
    im = IndexMap.build(keys * 3, add_intercept=True)
    assert len(im) == 5
    # deterministic: sorted order
    assert im.names == sorted(im.names)
    d = str(tmp_path / "idx")
    im.save(d)
    im2 = IndexMap.load(d)
    assert im2.names == im.names
    mm = MmapIndexMap(d)
    assert len(mm) == len(im)
    for k in im:
        assert mm.get(k) == im[k]
        assert mm.name_of(im[k]) == k
    assert mm.get("missing-key") == -1
    got = mm.get_many(list(im.names) + ["nope"])
    np.testing.assert_array_equal(got[:-1], np.arange(len(im)))
    assert got[-1] == -1


def test_libsvm_reader(tmp_path):
    p = tmp_path / "small.libsvm"
    p.write_text("+1 1:0.5 3:2.0\n-1 2:1.0\n+1 1:1.5\n")
    data = read_libsvm(str(p))
    assert data.num_features == 3
    np.testing.assert_array_equal(data.labels, [1.0, 0.0, 1.0])
    batch = data.to_batch(add_intercept=True)
    assert batch.num_features == 4
    dense = batch.to_dense()[:3]
    np.testing.assert_allclose(
        dense,
        [[0.5, 0, 2.0, 1.0], [0, 1.0, 0, 1.0], [1.5, 0, 0, 1.0]],
    )


def test_validators(rng):
    X = rng.normal(size=(20, 4))
    ok = SparseBatch.from_dense(X, (rng.random(20) > 0.5).astype(float))
    validate(ok, "logistic_regression")

    bad_label = SparseBatch.from_dense(X, rng.normal(size=20) * 5)
    with pytest.raises(DataValidationError, match="binary"):
        validate(bad_label, "logistic_regression")
    validate(bad_label, "linear_regression")

    with pytest.raises(DataValidationError, match="non-negative"):
        validate(SparseBatch.from_dense(X, -np.ones(20)), "poisson_regression")

    nan_feat = X.copy()
    nan_feat[3, 2] = np.nan
    with pytest.raises(DataValidationError, match="feature"):
        validate(SparseBatch.from_dense(nan_feat, np.ones(20)), "linear_regression")

    # disabled mode swallows everything
    validate(bad_label, "logistic_regression", mode=ValidationMode.DISABLED)


def test_validators_collect_all_reports_every_failure(rng):
    """collect_all=True aggregates EVERY failed check into one error — the
    full damage report from one pass, not just the first failure."""
    X = rng.normal(size=(20, 4))
    X[3, 2] = np.nan  # non-finite features
    y = rng.normal(size=20) * 5  # non-binary labels for a logistic task
    weights = np.ones(20)
    weights[5] = -1.0  # negative weight
    batch = SparseBatch.from_dense(X, y, weights=weights)

    # fail-fast mode still stops at the first check
    with pytest.raises(DataValidationError, match="feature"):
        validate(batch, "logistic_regression")

    with pytest.raises(DataValidationError) as ei:
        validate(batch, "logistic_regression", collect_all=True)
    msg = str(ei.value)
    assert "3 validation check(s) failed" in msg
    assert "non-finite feature values" in msg
    assert "negative weights" in msg
    assert "binary task" in msg


def test_summary_maxmin_unaffected_by_nnz_padding():
    """Regression (ADVICE r1-a): when n == n_pad, padding nnz entries alias
    the real last row; their value-0 must not leak into feature 0's max/min."""
    vals = np.array([-2.0, -3.0, -1.0])
    rows = np.array([0, 1, 2])
    cols = np.array([0, 0, 0])
    b = SparseBatch.from_coo(
        vals, rows, cols, np.zeros(3), num_features=2, nnz_pad_multiple=16
    )
    s = summarize(b)
    assert float(s.max[0]) == -1.0
    assert float(s.min[0]) == -3.0
    # feature 1 is all implicit zeros
    assert float(s.max[1]) == 0.0 and float(s.min[1]) == 0.0


def test_from_coo_rejects_out_of_range_indices():
    """Regression (ADVICE r1-b): out-of-range col/row indices must raise,
    not be silently dropped by clamped gathers."""
    with pytest.raises(ValueError):
        SparseBatch.from_coo(
            np.ones(2), np.array([0, 1]), np.array([0, 5]),
            np.zeros(2), num_features=3,
        )
    with pytest.raises(ValueError):
        SparseBatch.from_coo(
            np.ones(2), np.array([0, 7]), np.array([0, 1]),
            np.zeros(2), num_features=3,
        )


def test_index_map_save_detects_hash_collision(tmp_path, monkeypatch):
    """Regression (ADVICE r1-c): a 64-bit hash collision between two keys
    must fail save() loudly — the mmap store resolves by hash alone."""
    from photon_ml_tpu.data import index_map as im_mod

    m = IndexMap(["featA", "featB"])
    monkeypatch.setattr(im_mod, "_hash64", lambda key: 42)
    with pytest.raises(ValueError, match="collision"):
        m.save(str(tmp_path / "idx"))


def test_testing_generators_smoke(rng):
    """Shared generator module (GameTestUtils analog): shapes, ground-truth
    recoverability, and task coverage."""
    from photon_ml_tpu.testing import (
        generate_game_dataset,
        generate_glm_problem,
        generate_low_rank_game_dataset,
    )
    from photon_ml_tpu.optim import OptimizerConfig, solve

    import jax.numpy as jnp

    for task in ("logistic", "squared", "poisson"):
        p = generate_glm_problem(task, n=300, d=8, seed=3)
        assert p.batch.num_features == 8
        res = solve(task, p.batch, OptimizerConfig(),
                    jnp.zeros(8, jnp.float32))
        corr = np.corrcoef(np.asarray(res.w), p.w_true)[0, 1]
        assert corr > 0.8, f"{task}: corr {corr}"

    data, truth = generate_game_dataset("squared", n_users=6, rows_per_user=10)
    assert data.num_rows == 60
    assert set(data.feature_shards) == {"global", "user"}
    assert data.id_columns["userId"].num_entities == 6

    data2, truth2 = generate_low_rank_game_dataset(n_users=8, rows_per_user=5)
    assert truth2["W"].shape == (8, 30)
    assert np.linalg.matrix_rank(truth2["W"]) == 2


def test_native_libsvm_parser_matches_python(rng, tmp_path):
    """The C++ parser (built on demand) must agree exactly with the python
    parser, including comments, blank lines, and {-1,1} label mapping."""
    import pytest as _pytest

    from photon_ml_tpu.data.libsvm import read_libsvm
    from photon_ml_tpu.data.native import load_native

    if load_native() is None:
        _pytest.skip("no native toolchain")

    lines = ["# header comment", ""]
    n, d = 200, 30
    X = (rng.random((n, d)) < 0.3) * rng.normal(size=(n, d))
    y = np.where(rng.random(n) < 0.5, -1, 1)
    for i in range(n):
        feats = " ".join(f"{j + 1}:{X[i, j]:.6f}" for j in np.nonzero(X[i])[0])
        suffix = " # trailing comment" if i % 7 == 0 else ""
        lines.append(f"{y[i]} {feats}{suffix}")
    p = tmp_path / "t.libsvm"
    p.write_text("\n".join(lines) + "\n")

    a = read_libsvm(str(p), engine="python")
    b = read_libsvm(str(p), engine="native")
    np.testing.assert_array_equal(a.labels, b.labels)
    np.testing.assert_array_equal(a.rows, b.rows)
    np.testing.assert_array_equal(a.cols, b.cols)
    np.testing.assert_allclose(a.values, b.values, rtol=0, atol=0)
    assert a.num_features == b.num_features


def test_native_parser_rejects_malformed_input(tmp_path):
    """Malformed tokens must raise (never uninitialized-array garbage):
    the count/parse cross-check plus strict value-token validation."""
    import pytest as _pytest

    from photon_ml_tpu.data.libsvm import read_libsvm
    from photon_ml_tpu.data.native import load_native

    if load_native() is None:
        _pytest.skip("no native toolchain")

    bad_inputs = [
        "1 3: 5\n",  # space after colon: value token missing
        "1 3:\n-1 2:5\n",  # dangling colon would swallow the next label
        "1 3:abc\n",  # non-numeric value
        "x 3:1\n",  # non-numeric label
    ]
    for content in bad_inputs:
        p = tmp_path / "bad.libsvm"
        p.write_text(content)
        with _pytest.raises(ValueError):
            read_libsvm(str(p), engine="native")
        # the python engine rejects the same inputs
        with _pytest.raises(ValueError):
            read_libsvm(str(p), engine="python")


def test_native_parser_edge_semantics_match_python(tmp_path):
    """Divergence regressions: odd whitespace (\\v), labels-only files,
    attached '#', CR line endings — native and python must agree (both
    parse or both raise)."""
    import pytest as _pytest

    from photon_ml_tpu.data.libsvm import read_libsvm
    from photon_ml_tpu.data.native import load_native

    if load_native() is None:
        _pytest.skip("no native toolchain")

    def compare(content: str):
        p = tmp_path / "e.libsvm"
        p.write_text(content)
        try:
            a = read_libsvm(str(p), engine="python")
            py_err = None
        except ValueError as e:
            a, py_err = None, e
        try:
            b = read_libsvm(str(p), engine="native")
            nat_err = None
        except ValueError as e:
            b, nat_err = None, e
        assert (py_err is None) == (nat_err is None), (
            f"engines disagree on {content!r}: python={py_err} native={nat_err}"
        )
        if a is not None:
            np.testing.assert_array_equal(a.labels, b.labels)
            np.testing.assert_array_equal(a.cols, b.cols)
            np.testing.assert_allclose(a.values, b.values, atol=0)
            assert a.num_features == b.num_features

    compare("1 2:3\v4:5\n")       # \v separates tokens (no hang)
    compare("1\n0\n")             # labels-only file: num_features 0
    compare("")                   # empty file
    compare("# only a comment\n")
    compare("1 2:3#comment\n")    # attached '#': both must REJECT
    compare("1 2:3\r-1 4:5\r")    # CR-only line endings: two rows
    compare("+1 1:0.5 # ok\n")    # standalone trailing comment token


def test_native_build_failure_is_a_warning_and_leaves_nothing(
    monkeypatch, caplog, tmp_path
):
    """Losing the native library costs ~60x on Avro decode: it must be
    said at WARNING level, and a failed build must not leave a
    half-written library behind for the next process to load."""
    import logging
    import subprocess

    from photon_ml_tpu.data import native

    target = tmp_path / "libphoton_native.so"
    monkeypatch.setattr(native, "_LIB_PATH", str(target))

    def no_compiler(cmd, **kwargs):
        # what g++ leaves when it dies mid-link
        open(cmd[cmd.index("-o") + 1], "wb").write(b"partial")
        raise subprocess.CalledProcessError(1, cmd, stderr=b"ld: no -lz")

    monkeypatch.setattr(native.subprocess, "run", no_compiler)
    with caplog.at_level(logging.WARNING, "photon_ml_tpu.native"):
        assert native._build() is False
    text = " ".join(r.getMessage() for r in caplog.records)
    assert "native build failed" in text and "no -lz" in text
    assert "falls back to the pure-Python" in text
    assert list(tmp_path.iterdir()) == []
