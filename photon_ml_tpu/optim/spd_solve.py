"""A hand SPD solve over entity lanes — the Newton step of small problems.

``spd_step(H, g)`` is ``-(H^-1 g)`` for a symmetric positive definite
``H [K, K]`` and ``-g`` where ``H`` is not (a pivot that is not positive and
finite). It is what ``jnp.linalg.cholesky`` + ``cho_solve`` give, written
in plain float32 multiplies and adds with one ``rsqrt`` a column, so that
the compiled program holds no ``Cholesky`` / triangular-solve custom call:
under a bucket's ``vmap`` XLA's batched factorisation took 3.7 us a 32x32
matrix on a v5e, two orders of magnitude over what the work needs.

The routine has its own batching rule: ``vmap`` hands it ``[E, K, K]`` and
it moves the ENTITY axis to the minor (lane) dimension once, ``[K, K, E]``,
and walks the K columns on ``[.., E]`` slabs, every step element-wise over
the lanes. A lane's arithmetic never reads another lane's, so one entity's
failure (or NaN) leaves its neighbours' steps bit for bit what they are
without it, and the code partitions under GSPMD as the rest of a vmapped
solve does.

Left-looking: column j of the factor is column j of ``H`` less the columns
before it. The right-hand side rides along as one more row of the matrix,
which makes the forward substitution part of the factorisation. The column
loop is ROLLED (a ``fori_loop`` over full-size slabs, the columns not yet
computed held at zero): unrolled over K = 32 columns with triangular slabs
the same arithmetic reads a sixth of the bytes and ran 3.1 ms against 15.3
for 41,659 matrices (XLA's 153), but compiles 12-19 s a bucket shape where
this compiles in half a second (PERF.md, Findings PR 31).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

#: the widest problem the hand route takes; above it XLA's blocked routine is
#: the right algorithm
HAND_SOLVE_MAX_DIM = 32


def takes_hand_solve(dim: int) -> bool:
    """Whether a Newton solve over ``dim`` coefficients takes the hand route:
    a static shape, no setting."""
    return dim <= HAND_SOLVE_MAX_DIM


def _step_lanes(H: Array, g: Array) -> Array:
    """``H [K, K, E]``, ``g [K, E]`` -> the step ``[K, E]`` of every lane."""
    K = g.shape[0]
    # A[j] is column j of the augmented matrix [H; g^T], rows on the
    # sublanes: row K's "factor row" is the forward substitution's result
    # y = L^-1 g. L[k] is column k of the factor in the same layout; above
    # its diagonal a column holds leftovers that no pivot ever reads.
    A = jnp.concatenate([jnp.swapaxes(H, 0, 1), g[:, None]], axis=1)

    def factor_column(j, carry):
        L, inv_diag, ok = carry
        row_j = lax.dynamic_index_in_dim(L, j, axis=1, keepdims=True)
        acc = lax.dynamic_index_in_dim(A, j, axis=0, keepdims=False) - jnp.sum(
            L * row_j, axis=0)
        pivot = lax.dynamic_index_in_dim(acc, j, axis=0, keepdims=False)
        inv = lax.rsqrt(pivot)
        return (
            lax.dynamic_update_index_in_dim(L, acc * inv, j, axis=0),
            lax.dynamic_update_index_in_dim(inv_diag, inv, j, axis=0),
            ok & (pivot > 0) & (pivot < jnp.inf),
        )

    L, inv_diag, ok = lax.fori_loop(
        0, K, factor_column,
        (jnp.zeros_like(A), jnp.zeros_like(g), jnp.ones(g.shape[1:], bool)))

    rows = lax.broadcasted_iota(jnp.int32, g.shape, 0)

    def back_substitute(t, x):  # L^T x = y, last unknown first
        j = K - 1 - t
        col = lax.dynamic_index_in_dim(L, j, axis=0, keepdims=False)
        below = jnp.sum(jnp.where(rows > j, col[:K] * x, 0.0), axis=0)
        x_j = (col[K] - below) * lax.dynamic_index_in_dim(
            inv_diag, j, axis=0, keepdims=False)
        return lax.dynamic_update_index_in_dim(x, x_j, j, axis=0)

    x = lax.fori_loop(0, K, back_substitute, jnp.zeros_like(g))
    return -jnp.where(ok, x, g)


@jax.custom_batching.custom_vmap
def spd_step(H: Array, g: Array) -> Array:
    """``-(H^-1 g)`` for SPD ``H [K, K]``, ``-g`` where it is not."""
    return _step_lanes(H[..., None], g[..., None])[..., 0]


@spd_step.def_vmap
def _spd_step_lanes(axis_size, in_batched, H, g):
    if not in_batched[0]:
        H = jnp.broadcast_to(H, (axis_size,) + H.shape)
    if not in_batched[1]:
        g = jnp.broadcast_to(g, (axis_size,) + g.shape)
    step = _step_lanes(jnp.moveaxis(H, 0, -1), jnp.moveaxis(g, 0, -1))
    return jnp.moveaxis(step, -1, 0), True
