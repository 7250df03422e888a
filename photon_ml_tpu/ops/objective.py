"""GLM objective: weighted loss value, gradient, Hessian-vector products and
Hessian diagonal over a :class:`SparseBatch`, with feature normalization
applied algebraically (never densifying) and optional L2 regularization.

This is the TPU-native replacement for the reference's aggregator trio
(photon-lib function/glm/{ValueAndGradient,HessianVector,HessianDiagonal}
Aggregator.scala) and the Distributed/SingleNode GLM loss functions
(photon-api function/glm/). Where the reference streams per-datum ``add``
calls inside ``treeAggregate``, here each quantity is a handful of fused
gather/segment-sum/scatter ops compiled by XLA; under a sharded mesh the
same code yields partial sums that are combined by ``psum``
(see photon_ml_tpu.parallel.distributed).

Normalization trick (ValueAndGradientAggregator.scala:35-79 analog): for
x' = (x - shift) * factor, margins and derivatives are computed against the
raw sparse x via
    z_i       = x_i . (w * factor) - (w * factor) . shift + offset_i
    grad      = factor * scatter(dz) - (factor * shift) * sum(dz)
and similarly for Hv and the Hessian diagonal, so sparsity is preserved.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops.losses import PointwiseLoss, get_loss
from photon_ml_tpu.ops.sparse import SparseBatch

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GLMObjective:
    """Weighted GLM objective  F(w) = sum_i weight_i * l(z_i, y_i) + (l2/2)|w|^2.

    ``l2_weight`` is a traced leaf so lambda sweeps reuse one compiled
    program (the reference mutates l1/l2 weights for warm-started sweeps,
    DistributedOptimizationProblem.scala:60-71).

    ``factors``/``shifts`` implement normalization x' = (x - shift) * factor;
    ``None`` means identity. L1 is NOT part of this objective — it is handled
    by OWLQN's pseudo-gradient, mirroring the reference split.
    """

    loss_name: str = dataclasses.field(metadata=dict(static=True))
    l2_weight: Array = dataclasses.field(default_factory=lambda: jnp.float32(0.0))
    factors: Optional[Array] = None
    shifts: Optional[Array] = None

    @property
    def loss(self) -> PointwiseLoss:
        return get_loss(self.loss_name)

    # -- normalization algebra ----------------------------------------------

    def _effective(self, w: Array) -> tuple[Array, Array]:
        """(w * factor, margin shift constant -(w*factor).shifts)."""
        w_eff = w if self.factors is None else w * self.factors
        if self.shifts is None:
            shift = jnp.zeros((), dtype=w.dtype)
        else:
            shift = -jnp.dot(w_eff, self.shifts)
        return w_eff, shift

    def _back_transform_vec(self, raw: Array, row_total: Array) -> Array:
        """Map a raw feature-space scatter into normalized space:
        factor * raw - (factor * shift) * row_total."""
        out = raw if self.factors is None else raw * self.factors
        if self.shifts is not None:
            fs = self.shifts if self.factors is None else self.factors * self.shifts
            out = out - fs * row_total
        return out

    def margins(self, w: Array, batch: SparseBatch) -> Array:
        w_eff, shift = self._effective(w)
        return batch.margins(w_eff, shift)

    # -- value / gradient ----------------------------------------------------
    #
    # ``axis_name`` enables SPMD data parallelism: when the batch rows are a
    # local shard inside a shard_map over that mesh axis, the per-shard data
    # sums are psum'd over ICI while the regularization terms (functions of
    # the replicated coefficients) stay local. This is the treeAggregate
    # replacement (SURVEY.md §2.a row 1) — the optimizers run unchanged.

    @staticmethod
    def _psum(x, axis_name):
        return x if axis_name is None else jax.lax.psum(x, axis_name)

    def value_and_grad(
        self, w: Array, batch: SparseBatch, axis_name: Optional[str] = None
    ) -> tuple[Array, Array]:
        # One batch-layout-level sweep computes the weighted loss sum, the
        # raw gradient scatter, and sum(w*dz) (needed for the normalization
        # back-transform). TiledBatch fuses all three into one pallas pass.
        w_eff, shift = self._effective(w)
        data_value, raw_grad, row_total = batch.fused_value_grad(
            w_eff, shift, self.loss_name
        )
        value = self._psum(data_value, axis_name)
        grad = self._psum(
            self._back_transform_vec(raw_grad, row_total), axis_name
        )
        l2 = self.l2_weight.astype(w.dtype)
        value = value + 0.5 * l2 * jnp.dot(w, w)
        grad = grad + l2 * w
        return value, grad

    def value_and_grad_at_margins(
        self,
        w: Array,
        z: Array,
        batch: SparseBatch,
        axis_name: Optional[str] = None,
    ) -> tuple[Array, Array]:
        """value_and_grad with the margins z ALREADY known: skips the gather
        half of the fused sweep (one scatter pass). Math identical to
        value_and_grad — the margin-carrying LBFGS fast path."""
        l, dz = self.loss.loss_and_dz(z, batch.labels)
        wdz = batch.weights * dz
        data_value = jnp.sum(batch.weights * l)
        raw_grad = batch.scatter_features(wdz)
        row_total = jnp.sum(wdz)
        value = self._psum(data_value, axis_name)
        grad = self._psum(
            self._back_transform_vec(raw_grad, row_total), axis_name
        )
        l2 = self.l2_weight.astype(w.dtype)
        return value + 0.5 * l2 * jnp.dot(w, w), grad + l2 * w

    def value(
        self, w: Array, batch: SparseBatch, axis_name: Optional[str] = None
    ) -> Array:
        z = self.margins(w, batch)
        l = self.loss.loss(z, batch.labels)
        return self._psum(jnp.sum(batch.weights * l), axis_name) + 0.5 * (
            self.l2_weight.astype(w.dtype)
        ) * jnp.dot(w, w)

    def grad(
        self, w: Array, batch: SparseBatch, axis_name: Optional[str] = None
    ) -> Array:
        return self.value_and_grad(w, batch, axis_name)[1]

    # -- second-order --------------------------------------------------------

    def hessian_vector(
        self, w: Array, v: Array, batch: SparseBatch, axis_name: Optional[str] = None
    ) -> Array:
        """H(w) @ v  =  sum_i weight_i * l''(z_i) * (x'_i . v) * x'_i  + l2*v.

        One layout-level sweep (TiledBatch fuses gather z/u + scatter into a
        single pallas pass — TRON's truncated-CG hot op).
        """
        v_eff, v_shift = self._effective(v)
        w_eff, w_shift = self._effective(w)
        raw_hv, q_total = batch.fused_hessian_vector(
            w_eff, w_shift, v_eff, v_shift, self.loss_name
        )
        hv = self._psum(
            self._back_transform_vec(raw_hv, q_total), axis_name
        )
        return hv + self.l2_weight.astype(w.dtype) * v

    def curvature_at_margins(self, z: Array, batch: SparseBatch) -> Array:
        """Per-row curvature d2 = weight * l''(z) — loop-invariant across a
        TRON truncated-CG inner solve, so compute it ONCE per outer step."""
        return batch.weights * self.loss.d2z(z, batch.labels)

    def hessian_vector_with_curvature(
        self,
        d2: Array,
        v: Array,
        batch: SparseBatch,
        axis_name: Optional[str] = None,
    ) -> Array:
        """H(w) @ v with the per-row curvature d2 = weight*l''(z) ALREADY
        known: one gather (u = X'@v) + one scatter instead of the fused
        kernel's two gathers + scatter, and no per-call elementwise d2z
        pass. TRON's CG uses one fixed z/d2 for its whole inner loop."""
        v_eff, v_shift = self._effective(v)
        raw_hv, q_total = batch.fused_hv_at(d2, v_eff, v_shift)
        hv = self._psum(self._back_transform_vec(raw_hv, q_total), axis_name)
        return hv + self.l2_weight.astype(v.dtype) * v

    def hessian_diagonal(
        self, w: Array, batch: SparseBatch, axis_name: Optional[str] = None
    ) -> Array:
        """diag H(w)_j = sum_i weight_i l''(z_i) x'_ij^2 + l2."""
        z = self.margins(w, batch)
        d2_row = batch.weights * self.loss.d2z(z, batch.labels)
        raw_sq = batch.scatter_features_sq(d2_row)  # sum d2 * x^2
        if self.factors is None and self.shifts is None:
            diag = raw_sq
        else:
            f = (
                jnp.ones((batch.num_features,), dtype=w.dtype)
                if self.factors is None
                else self.factors
            )
            if self.shifts is None:
                diag = f * f * raw_sq
            else:
                raw_lin = batch.scatter_features(d2_row)  # sum d2 * x
                total = jnp.sum(d2_row)
                s = self.shifts
                diag = f * f * (raw_sq - 2.0 * s * raw_lin + s * s * total)
        return self._psum(diag, axis_name) + self.l2_weight.astype(w.dtype)

    def dense_hessian(
        self, w: Array, batch: SparseBatch, axis_name: Optional[str] = None
    ) -> Array:
        """Full H(w) = X'^T diag(wgt*l'') X' + l2 I as a dense [d, d] —
        the explicit-Hessian path for SMALL d (per-entity local spaces;
        batched Newton). Normalization materializes X' = (X - shift)*factor
        on the densified design."""
        z = self.margins(w, batch)
        d2 = batch.weights * self.loss.d2z(z, batch.labels)
        X = batch.dense_rows()
        if self.shifts is not None:
            X = X - self.shifts[None, :]
        if self.factors is not None:
            X = X * self.factors[None, :]
        # float32-grade where it is written (one bfloat16 pass by default on
        # a TPU; ops/dense.py says why no global setting can do this)
        H = jnp.matmul(
            (X * d2[:, None]).T, X, precision=jax.lax.Precision.HIGHEST
        )
        H = self._psum(H, axis_name)
        d = batch.num_features
        return H + self.l2_weight.astype(w.dtype) * jnp.eye(d, dtype=w.dtype)

    # -- plumbing ------------------------------------------------------------

    def with_l2(self, l2_weight) -> "GLMObjective":
        return dataclasses.replace(
            self, l2_weight=jnp.asarray(l2_weight, dtype=jnp.float32)
        )

    def with_normalization(self, factors, shifts) -> "GLMObjective":
        return dataclasses.replace(self, factors=factors, shifts=shifts)


def make_objective(
    loss: str | PointwiseLoss,
    l2_weight: float = 0.0,
    factors: Optional[Array] = None,
    shifts: Optional[Array] = None,
) -> GLMObjective:
    name = loss if isinstance(loss, str) else loss.name
    return GLMObjective(
        loss_name=get_loss(name).name,
        l2_weight=jnp.float32(l2_weight),
        factors=factors,
        shifts=shifts,
    )
