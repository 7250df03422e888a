"""Bridge from :class:`GLMObjective` to the optimizer :class:`Objective`
adapter, including the margin-space fast line search.

Along a search direction p, GLM margins are affine: z(a) = z + a*u with
u = X' @ p precomputed once per line search. Each Wolfe trial then costs
O(n) elementwise work instead of a full gather/scatter pass over the nnz —
something the Spark reference cannot express (every Breeze line-search trial
there is a full treeAggregate over the cluster; SURVEY.md §3.4).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.optim.common import Objective

Array = jax.Array


class _LSCarry(NamedTuple):
    z: Array  # margins at w
    u: Array  # directional margins X' @ p
    w: Array
    p: Array
    ww: Array  # w.w
    wp: Array  # w.p
    pp: Array  # p.p


def glm_adapter(
    obj: GLMObjective,
    batch: SparseBatch,
    axis_name: str | None = None,
    row_sharding=None,
) -> Objective:
    """Build the optimizer-facing adapter for a GLM objective over a batch.

    The returned closures capture ``obj`` and ``batch``; under jit they are
    traced with whatever sharding the batch carries, so the same adapter
    serves single-device, vmapped (per-entity) and mesh-sharded training.

    Two mesh modes:
      - GSPMD (the product path, parallel.distributed.gspmd_solve):
        ``row_sharding`` pins the margin-space arrays (z, the directional
        margins u) to the batch rows' ``NamedSharding(mesh, P("batch"))``
        so the compiler keeps every per-row intermediate distributed and
        inserts psums only at the data sums — the treeAggregate ->
        psum-over-ICI mapping of PAPER.md with zero hand-rolled SPMD.
      - explicit SPMD (legacy shard_map callers): ``axis_name`` set means
        the batch is the LOCAL row shard and all data sums are psum'd —
        including the line search's per-trial phi/dphi, one scalar-pair
        all-reduce over ICI per trial.
    """
    loss = obj.loss

    def psum(x):
        return x if axis_name is None else jax.lax.psum(x, axis_name)

    def rows(x):
        # margin-space arrays carry the batch-axis sharding; a missing
        # constraint lets GSPMD replicate [n]-sized intermediates, which
        # is exactly the silent-replication bug class this removes
        if row_sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, row_sharding)

    def value_and_grad(w):
        return obj.value_and_grad(w, batch, axis_name)

    def value(w):
        return obj.value(w, batch, axis_name)

    def ls_prepare(w, p):
        # TiledBatch shares one pass over the nnz slots for both gathers;
        # SparseBatch composes margins + dot_rows.
        p_eff, p_shift = obj._effective(p)
        w_eff, w_shift = obj._effective(w)
        z, u = batch.margins_pair(w_eff, w_shift, p_eff, p_shift)
        z, u = rows(z), rows(u)
        return _LSCarry(
            z=z,
            u=u,
            w=w,
            p=p,
            ww=jnp.dot(w, w),
            wp=jnp.dot(w, p),
            pp=jnp.dot(p, p),
        )

    def ls_eval(carry: _LSCarry, alpha):
        z_a = carry.z + alpha * carry.u
        l, dz = loss.loss_and_dz(z_a, batch.labels)
        l2 = obj.l2_weight.astype(z_a.dtype)
        data_sums = psum(
            jnp.stack(
                [jnp.sum(batch.weights * l), jnp.sum(batch.weights * dz * carry.u)]
            )
        )
        phi = data_sums[0] + 0.5 * l2 * (
            carry.ww + 2.0 * alpha * carry.wp + alpha * alpha * carry.pp
        )
        dphi = data_sums[1] + l2 * (carry.wp + alpha * carry.pp)
        return phi, dphi

    hvp = None
    if loss.has_hessian:
        def hvp(w, v):
            return obj.hessian_vector(w, v, batch, axis_name)

    # margin-carrying protocol: z is threaded through the LBFGS loop so each
    # iteration does one gather (u = X'@p) + one scatter (gradient) instead
    # of two fused gather+scatter sweeps
    def margins(w):
        return rows(obj.margins(w, batch))

    def ls_prepare_z(z, w, p):
        u = dir_margins(p)
        return _LSCarry(
            z=z,
            u=u,
            w=w,
            p=p,
            ww=jnp.dot(w, w),
            wp=jnp.dot(w, p),
            pp=jnp.dot(p, p),
        )

    def ls_advance(carry: _LSCarry, alpha):
        return carry.z + alpha * carry.u

    def value_and_grad_at(w, z):
        return obj.value_and_grad_at_margins(w, z, batch, axis_name)

    def dir_margins(p):
        p_eff, p_shift = obj._effective(p)
        return rows(batch.dot_rows(p_eff) + p_shift)

    hessian = None
    if loss.has_hessian and hasattr(batch, "dense_rows"):
        def hessian(w):
            return obj.dense_hessian(w, batch, axis_name)

    curvature = None
    hvp_at = None
    if loss.has_hessian:
        def curvature(z):
            return obj.curvature_at_margins(z, batch)

        def hvp_at(d2, v):
            return obj.hessian_vector_with_curvature(d2, v, batch, axis_name)

    return Objective(
        value_and_grad=value_and_grad,
        value=value,
        ls_prepare=ls_prepare,
        ls_eval=ls_eval,
        hvp=hvp,
        margins=margins,
        ls_prepare_z=ls_prepare_z,
        ls_advance=ls_advance,
        value_and_grad_at=value_and_grad_at,
        dir_margins=dir_margins,
        curvature=curvature,
        hvp_at=hvp_at,
        hessian=hessian,
        value_scale=lambda: psum(jnp.sum(batch.weights)),
    )
