"""Sparse example batches for TPU GLM training.

TPUs have no native CSR support, so sparse design matrices are stored as
padded COO with static shapes: parallel arrays ``values``/``rows``/``cols``
of length nnz_pad, plus per-row ``labels``/``offsets``/``weights`` of length
n_pad. Margins are computed as gather + multiply + ``segment_sum`` (rows are
sorted, so XLA lowers this to an efficient scan); gradients as a scatter-add
into the feature dimension. This replaces the reference's Breeze sparse-vector
hot loop (ValueAndGradientAggregator.scala:132-153) with fused vector ops.

Padding convention: padded nnz entries have value 0 (so they contribute
nothing to any sum) and point at the LAST row index / col 0 — the last-row
choice keeps ``rows`` non-decreasing, which ``segment_sum`` is promised via
``indices_are_sorted=True`` and may exploit on TPU. Padded rows have weight 0.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.telemetry.trace import span

Array = jax.Array


def _round_up(n: int, multiple: int) -> int:
    if multiple <= 1:
        return max(n, 1)
    return max(((n + multiple - 1) // multiple) * multiple, multiple)


def _pad(a: np.ndarray, total: int, fill=0, dtype=None) -> np.ndarray:
    """Pad a 1-D host array to ``total`` entries with ``fill``, as
    ``dtype`` (the array's own by default): one pass, no wider copy."""
    a = np.asarray(a)
    out = np.full((total,), fill, dtype=a.dtype if dtype is None else dtype)
    out[: a.shape[0]] = a
    return out


def validate_coo_indices(
    rows: np.ndarray, cols: np.ndarray, num_rows: int, num_features: int
) -> None:
    """Reject out-of-range COO indices: silent out-of-range cols would be
    dropped by the clamped device gathers and corrupt the scatter adds.
    Shared by SparseBatch.from_coo and TiledBatch.from_coo."""
    if len(cols) and (cols.min() < 0 or cols.max() >= num_features):
        raise ValueError(
            f"feature indices must be in [0, {num_features}); got "
            f"[{cols.min()}, {cols.max()}]"
        )
    if len(rows) and (rows.min() < 0 or rows.max() >= num_rows):
        raise ValueError(
            f"row indices must be in [0, {num_rows}); got "
            f"[{rows.min()}, {rows.max()}]"
        )


class ContractedRows:
    """The contracted forms of a design's K-table passes, which the
    factored coordinate's refit calls (``LatentRefitBatch``): the K
    projections of a row summed against its latent vector, and K scatters of
    one per-row vector scaled by it. A design that holds its rows in an
    order of its own (``ops/tiled.py::ColumnSortedTiles``) answers them in
    one pass each; here they are ``project_rows`` / ``scatter_rows`` and the
    elementwise product."""

    def contract_rows(self, a: Array, c_rows: Array) -> Array:
        """``a`` [K, F], ``c_rows`` [K, rows] -> [rows]:
        ``sum_l c_rows[l] * dot_rows(a[l])``."""
        return jnp.sum(c_rows * self.project_rows(a), axis=0)

    def scatter_contracted(self, q: Array, c_rows: Array,
                           square: bool = False) -> Array:
        """``q`` [rows], ``c_rows`` [K, rows] -> [K, F]: row l is
        ``scatter_features(q * c_rows[l])``; with ``square`` the design's
        values and ``c_rows`` are squared (a Hessian diagonal)."""
        if square:
            return jax.lax.map(
                self.scatter_features_sq, c_rows * c_rows * q[None, :])
        return self.scatter_rows(c_rows * q[None, :])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseBatch(ContractedRows):
    """A fixed-shape batch of sparse labeled examples.

    The TPU-native analog of the reference's ``RDD[LabeledPoint]`` /
    ``Iterable[LabeledPoint]`` (photon-lib data/LabeledPoint.scala): labels,
    offsets and weights are columnar arrays, and features are one padded COO
    block. ``num_features`` is static so downstream gradient shapes are fixed
    under jit.

    Leaves may be HOST numpy arrays (what the constructors produce) or
    device arrays: host batches make the data plane (grouping, tiling,
    stats) transfer-free, and a solve path uploads once via :meth:`device`
    (or implicitly at a jit boundary).
    """

    values: Array  # f[nnz_pad] feature values (0 in padding)
    rows: Array  # i32[nnz_pad] row index per nnz, non-decreasing
    cols: Array  # i32[nnz_pad] feature index per nnz
    labels: Array  # f[n_pad]
    offsets: Array  # f[n_pad]
    weights: Array  # f[n_pad]; 0 for padded rows
    num_features: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_rows(self) -> int:
        return self.labels.shape[0]

    @property
    def nnz(self) -> int:
        return self.values.shape[0]

    @property
    def dtype(self):
        return self.values.dtype

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_coo(
        values: np.ndarray,
        rows: np.ndarray,
        cols: np.ndarray,
        labels: np.ndarray,
        num_features: int,
        offsets: Optional[np.ndarray] = None,
        weights: Optional[np.ndarray] = None,
        dtype=jnp.float32,
        row_pad_multiple: int = 1,
        nnz_pad_multiple: int = 1,
    ) -> "SparseBatch":
        """Build a batch from host COO arrays, sorting by row and padding.

        Raises on out-of-range row/col indices — a silent out-of-range col
        would be dropped by the clamped device gathers and corrupt the
        scatter adds (TiledBatch.from_coo validates identically).

        Runs under a ``dataset.sparse_batch`` span (children
        ``dataset.validate``, ``dataset.sort`` where the rows arrive out of
        order, ``dataset.pad``): what ``cli train``'s input and a
        benchmark's set-up assemble.
        """
        with span("dataset.sparse_batch", nnz=int(len(values))):
            n = int(len(labels))
            rows = np.asarray(rows)
            cols = np.asarray(cols)
            values = np.asarray(values)
            with span("dataset.validate"):
                validate_coo_indices(rows, cols, n, num_features)
                ordered = not len(rows) or bool(np.all(rows[1:] >= rows[:-1]))
            if not ordered:
                # ingest paths emit row-sorted COO; only re-sort when needed
                with span("dataset.sort"):
                    order = np.argsort(rows, kind="stable")
                    values = values[order]
                    rows = rows[order]
                    cols = cols[order]

            with span("dataset.pad"):
                n_pad = _round_up(n, row_pad_multiple)
                nnz = int(len(values))
                nnz_pad = _round_up(nnz, nnz_pad_multiple)

                labels_p = _pad(np.asarray(labels, dtype=np.float64), n_pad)
                offsets_p = _pad(
                    np.zeros(n) if offsets is None
                    else np.asarray(offsets, np.float64), n_pad
                )
                weights_p = _pad(
                    np.ones(n) if weights is None
                    else np.asarray(weights, np.float64), n_pad
                )

                # leaves stay HOST numpy (dtype applied host-side):
                # construction is transfer-free, and consumers upload exactly
                # once where the batch is actually solved/scored (see
                # .device()). This keeps the host-side data plane (RE
                # grouping, tiling, stats, ingest) off the PCIe link entirely.
                np_dtype = np.dtype(dtype)
                return SparseBatch(
                    values=_pad(values, nnz_pad, dtype=np_dtype),
                    rows=_pad(rows, nnz_pad, fill=n_pad - 1, dtype=np.int32),
                    cols=_pad(cols, nnz_pad, dtype=np.int32),
                    labels=labels_p.astype(np_dtype),
                    offsets=offsets_p.astype(np_dtype),
                    weights=weights_p.astype(np_dtype),
                    num_features=int(num_features),
                )

    def device(self, sharding=None) -> "SparseBatch":
        """Upload every leaf (no-op for leaves already on device)."""
        put = (
            jax.device_put
            if sharding is None
            else (lambda x: jax.device_put(x, sharding))
        )
        return jax.tree.map(put, self)

    @staticmethod
    def from_dense(
        X: np.ndarray,
        labels: np.ndarray,
        offsets: Optional[np.ndarray] = None,
        weights: Optional[np.ndarray] = None,
        dtype=jnp.float32,
    ) -> "SparseBatch":
        X = np.asarray(X)
        rows, cols = np.nonzero(X)
        return SparseBatch.from_coo(
            values=X[rows, cols],
            rows=rows,
            cols=cols,
            labels=labels,
            num_features=X.shape[1],
            offsets=offsets,
            weights=weights,
            dtype=dtype,
        )

    def dense_rows(self) -> Array:
        """DEVICE-side densify [num_rows, num_features] — jit/vmap friendly.
        Intended for small feature dims (per-entity local spaces) where
        explicit-Hessian solvers want the dense design."""
        X = jnp.zeros((self.num_rows, self.num_features), self.dtype)
        return X.at[self.rows, self.cols].add(self.values)

    def to_dense(self) -> np.ndarray:
        """Host-side densify (tests / diagnostics only)."""
        X = np.zeros((self.num_rows, self.num_features), dtype=np.float64)
        np.add.at(
            X,
            (np.asarray(self.rows), np.asarray(self.cols)),
            np.asarray(self.values, dtype=np.float64),
        )
        return X

    # -- device kernels ------------------------------------------------------

    def margins(self, w: Array, shift: Array | float = 0.0) -> Array:
        """Per-row margins z_i = x_i . w + shift + offset_i.

        ``w`` is the (already normalization-scaled) coefficient vector;
        ``shift`` the scalar margin correction -(w*factor).shifts from the
        normalization trick (ValueAndGradientAggregator.scala:35-79 analog).
        """
        contrib = self.values * jnp.take(w, self.cols, fill_value=0)
        dots = jax.ops.segment_sum(
            contrib, self.rows, num_segments=self.num_rows, indices_are_sorted=True
        )
        return dots + self.offsets + shift

    def dot_rows(self, w: Array) -> Array:
        """Per-row raw dot products x_i . w (no offset/shift)."""
        contrib = self.values * jnp.take(w, self.cols, fill_value=0)
        return jax.ops.segment_sum(
            contrib, self.rows, num_segments=self.num_rows, indices_are_sorted=True
        )

    def margins_pair(
        self, w: Array, shift, p: Array, p_shift
    ) -> tuple[Array, Array]:
        """(margins(w, shift), dot_rows(p) + p_shift).

        Layouts that can share one data sweep between the two gathers
        (TiledBatch) override this; here it is the plain composition, so
        call sites need no per-layout dispatch.
        """
        return self.margins(w, shift), self.dot_rows(p) + p_shift

    def fused_value_grad(
        self, w: Array, shift, loss_name: str
    ) -> tuple[Array, Array, Array]:
        """(sum_i wgt_i*l(z_i), raw gradient scatter, sum_i wgt_i*dz_i).

        The raw gradient is sum_i wgt_i*dz_i*x_i with NO normalization
        back-transform or regularization (the objective applies those).
        TiledBatch computes all three in one fused pallas sweep; this is
        the equivalent composition for the padded-COO layout.
        """
        from photon_ml_tpu.ops.losses import get_loss

        z = self.margins(w, shift)
        l, dz = get_loss(loss_name).loss_and_dz(z, self.labels)
        g_row = self.weights * dz
        return (
            jnp.sum(self.weights * l),
            self.scatter_features(g_row),
            jnp.sum(g_row),
        )

    def fused_hessian_vector(
        self, w: Array, shift, v: Array, v_shift, loss_name: str
    ) -> tuple[Array, Array]:
        """(raw Hv scatter sum_i wgt_i*l''(z_i)*(x_i.v)*x_i, sum_i q_i).

        TiledBatch computes this in one fused pallas sweep; this is the
        equivalent composition for the padded-COO layout.
        """
        from photon_ml_tpu.ops.losses import get_loss

        z, xv = self.margins_pair(w, shift, v, v_shift)
        q = self.weights * get_loss(loss_name).d2z(z, self.labels) * xv
        return self.scatter_features(q), jnp.sum(q)

    def fused_hv_at(
        self, d2_row: Array, v_eff: Array, v_shift
    ) -> tuple[Array, Array]:
        """(raw Hv scatter, sum q) with the row curvature d2 = wgt*l''(z)
        precomputed (q = d2 * (x.v + v_shift)). Plain composition here;
        TiledBatch fuses gather + scatter into one pallas pass."""
        u = self.dot_rows(v_eff) + v_shift
        q = d2_row * u
        return self.scatter_features(q), jnp.sum(q)

    def scatter_features(self, per_row: Array) -> Array:
        """Compute sum_i per_row[i] * x_i as a dense feature-space vector.

        A scatter-add over the feature dimension. (No column-sorted CSC
        mirror is kept: a sorted segment_sum lowers to the same scatter
        on TPU.)
        """
        contrib = self.values * jnp.take(per_row, self.rows, fill_value=0)
        return jnp.zeros((self.num_features,), dtype=contrib.dtype).at[self.cols].add(
            contrib
        )

    def scatter_features_sq(self, per_row: Array) -> Array:
        """Compute sum_i per_row[i] * (x_i ** 2) elementwise (Hessian diagonal)."""
        contrib = self.values * self.values * jnp.take(per_row, self.rows, fill_value=0)
        return jnp.zeros((self.num_features,), dtype=contrib.dtype).at[self.cols].add(
            contrib
        )

    # -- K vectors at once (the factored coordinate's [K, d] projection);
    # TiledBatch serves all K in one sweep of its tiles -----------------------

    def project_rows(self, a: Array) -> Array:
        """``a`` [K, F] -> [K, rows]: row l is ``dot_rows(a[l])``."""
        return jax.lax.map(self.dot_rows, a)

    def scatter_rows(self, g: Array) -> Array:
        """``g`` [K, rows] -> [K, F]: row l is ``scatter_features(g[l])``."""
        return jax.lax.map(self.scatter_features, g)

    def feature_moment_sums(self) -> tuple[Array, Array, Array]:
        """Per-feature (sum x, sum x^2, count nonzero) over valid rows."""
        valid = jnp.take(
            (self.weights > 0).astype(self.dtype), self.rows, fill_value=0
        )
        v = self.values * valid
        zeros = jnp.zeros((self.num_features,), dtype=self.dtype)
        s1 = zeros.at[self.cols].add(v)
        s2 = zeros.at[self.cols].add(v * v)
        cnt = zeros.at[self.cols].add((v != 0).astype(self.dtype))
        return s1, s2, cnt

    def with_offsets(self, offsets: Array) -> "SparseBatch":
        return dataclasses.replace(self, offsets=offsets)

    # -- sharding helpers ----------------------------------------------------

    def pad_rows_to(self, n_pad: int, nnz_pad: int) -> "SparseBatch":
        """Pad row-count and nnz to given totals (host-side, numpy)."""
        if n_pad < self.num_rows or nnz_pad < self.nnz:
            raise ValueError("pad target smaller than current size")

        return SparseBatch(
            values=_pad(self.values, nnz_pad),
            rows=_pad(self.rows, nnz_pad, fill=n_pad - 1),
            cols=_pad(self.cols, nnz_pad),
            labels=_pad(self.labels, n_pad),
            offsets=_pad(self.offsets, n_pad),
            weights=_pad(self.weights, n_pad),
            num_features=self.num_features,
        )


def concat_batches(batches: Sequence[SparseBatch]) -> SparseBatch:
    """Host-side concatenation of row-blocks (row indices re-based)."""
    if not batches:
        raise ValueError("no batches")
    num_features = batches[0].num_features
    row_base = 0
    vals, rows, cols, labels, offsets, weights = [], [], [], [], [], []
    for b in batches:
        if b.num_features != num_features:
            raise ValueError("feature-dimension mismatch")
        vals.append(np.asarray(b.values))
        rows.append(np.asarray(b.rows) + row_base)
        cols.append(np.asarray(b.cols))
        labels.append(np.asarray(b.labels))
        offsets.append(np.asarray(b.offsets))
        weights.append(np.asarray(b.weights))
        row_base += b.num_rows
    return SparseBatch(
        values=np.concatenate(vals),
        rows=np.concatenate(rows).astype(np.int32),
        cols=np.concatenate(cols).astype(np.int32),
        labels=np.concatenate(labels),
        offsets=np.concatenate(offsets),
        weights=np.concatenate(weights),
        num_features=num_features,
    )
