"""Dense local-design batch: the billion-coefficient random-effect layout.

``DenseBatch`` holds one entity's design matrix as a dense [R, K] array and
is duck-type compatible with :class:`~photon_ml_tpu.ops.sparse.SparseBatch`
for everything :class:`~photon_ml_tpu.ops.objective.GLMObjective` and the
optimizer adapters touch, so ``glm_adapter``/``dispatch_solve``/``vmap``
work unchanged.

Why it exists: per-entity problems in index-map-projected local spaces are
SMALL (K ~ 1e2-1e3) and, after the projection squeezed out unobserved
features, fairly dense. At the reference's headline scale ("hundreds of
billions of coefficients", /root/reference/README.md:73; projection
envelope ~1e8 entities x ~1e3 features, projector/README.md:8-12) the solve
throughput is set by how the per-entity sweeps map to hardware: COO
gather/segment ops are random-access bound on TPU (under 1e8 elem/s,
PERF.md section 3), while dense [E, R, K] batched matmuls ride the MXU at
full bandwidth with ZERO random access. A vmapped solve over a [E, R, K]
stack is one ``jnp.einsum`` per sweep.

Used by the streaming 1B-coefficient trainer (photon_ml_tpu.game.streaming)
and anywhere a small dense design is already at hand (diagnostics,
latent-space MF refits).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.ops.losses import get_loss

Array = jax.Array

#: Every product of this layout is float32-grade where it is written: at
#: jax's default precision a float32 matmul is ONE bfloat16 pass on a TPU,
#: and per-entity coefficients then sit 3e-3 from the float32 solve's
#: (PERF.md, Findings PR 30). No global setting can say so for them: Mosaic
#: refuses the tiled kernels' bf16 dots under ``jax_default_matmul_precision``.
_HI = jax.lax.Precision.HIGHEST


def _mm(a: Array, b: Array) -> Array:
    return jnp.matmul(a, b, precision=_HI)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseBatch:
    """Dense labeled examples X [R, K] (+ labels/offsets/weights [R]).

    All sweeps are matmuls/einsums — vmap over a leading entity axis turns
    them into MXU-batched contractions. Weights of 0 mark padded rows.
    """

    x: Array        # f[R, K]
    labels: Array   # f[R]
    offsets: Array  # f[R]
    weights: Array  # f[R]

    @property
    def num_features(self) -> int:
        return self.x.shape[-1]

    @property
    def num_rows(self) -> int:
        return self.x.shape[-2]

    @property
    def dtype(self):
        return self.x.dtype

    @staticmethod
    def from_arrays(x, labels, offsets=None, weights=None) -> "DenseBatch":
        x = jnp.asarray(x, jnp.float32)
        n = x.shape[-2]
        z = jnp.zeros((n,), jnp.float32)
        return DenseBatch(
            x=x,
            labels=jnp.asarray(labels, jnp.float32),
            offsets=z if offsets is None else jnp.asarray(offsets, jnp.float32),
            weights=(
                jnp.ones((n,), jnp.float32)
                if weights is None
                else jnp.asarray(weights, jnp.float32)
            ),
        )

    def dense_rows(self) -> Array:
        return self.x

    def to_dense(self) -> np.ndarray:
        return np.asarray(self.x)

    # -- sweeps (SparseBatch duck-type) --------------------------------------

    def margins(self, w: Array, shift: Array | float = 0.0) -> Array:
        return _mm(self.x, w) + shift + self.offsets

    def dot_rows(self, w: Array) -> Array:
        return _mm(self.x, w)

    def margins_pair(self, w, shift, p, p_shift):
        zu = _mm(self.x, jnp.stack([w, p], axis=1))    # [R, 2]
        return zu[:, 0] + shift + self.offsets, zu[:, 1] + p_shift

    def fused_value_grad(self, w, shift, loss_name: str):
        loss = get_loss(loss_name)
        z = self.margins(w, shift)
        l, dz = loss.loss_and_dz(z, self.labels)
        wdz = self.weights * dz
        return jnp.sum(self.weights * l), _mm(wdz, self.x), jnp.sum(wdz)

    def fused_hessian_vector(self, w, shift, v, v_shift, loss_name: str):
        loss = get_loss(loss_name)
        zu = _mm(self.x, jnp.stack([w, v], axis=1))
        z = zu[:, 0] + shift + self.offsets
        u = zu[:, 1] + v_shift
        q = self.weights * loss.d2z(z, self.labels) * u
        return _mm(q, self.x), jnp.sum(q)

    def fused_hv_at(self, d2_row, v, v_shift):
        q = d2_row * (_mm(self.x, v) + v_shift)
        return _mm(q, self.x), jnp.sum(q)

    def scatter_features(self, per_row: Array) -> Array:
        return _mm(per_row, self.x)

    def scatter_features_sq(self, per_row: Array) -> Array:
        return _mm(per_row, self.x * self.x)

    def with_offsets(self, offsets: Array) -> "DenseBatch":
        return dataclasses.replace(
            self, offsets=jnp.asarray(offsets, self.offsets.dtype)
        )
