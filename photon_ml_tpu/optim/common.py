"""Shared optimizer machinery: objective adapters, convergence semantics,
box-constraint projection, and result types.

Convergence reasons and checks mirror the reference's Optimizer
(photon-lib optimization/Optimizer.scala:155-169): an optimizer run stops on
  - MaxIterations:          iter >= max_iterations
  - ObjectiveNotImproving:  the line search failed to make progress
  - FunctionValuesConverged |f_k - f_{k-1}| <= tolerance * f_0
  - GradientConverged       ||g_k|| <= tolerance * ||g_0||
All checks are relative to the *initial* state, so warm-started re-runs may
reuse a stored initial state for consistent convergence behavior
(Optimizer.scala:33-35 semantics; pass ``init_value``/``init_grad_norm``).

Everything here is pure-functional and shape-static: it jits, vmaps (for
per-entity random-effect solves) and shard_maps unchanged.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jax.Array

# ConvergenceReason codes (int32). 0 = still running.
NOT_CONVERGED = 0
MAX_ITERATIONS = 1
OBJECTIVE_NOT_IMPROVING = 2
FUNCTION_VALUES_CONVERGED = 3
GRADIENT_CONVERGED = 4

CONVERGENCE_REASON_NAMES = {
    NOT_CONVERGED: "NotConverged",
    MAX_ITERATIONS: "MaxIterations",
    OBJECTIVE_NOT_IMPROVING: "ObjectiveNotImproving",
    FUNCTION_VALUES_CONVERGED: "FunctionValuesConverged",
    GRADIENT_CONVERGED: "GradientConverged",
}


class Objective(NamedTuple):
    """Adapter the optimizers drive.

    ``ls_prepare``/``ls_eval`` give line searches a cheap directional oracle:
    for GLMs, margins along a search direction are ``z + a*u`` with
    ``u = X @ p`` precomputed once, so each trial is O(n) elementwise instead
    of a full gather/scatter pass (a TPU-side win the Spark reference cannot
    express — every Breeze line-search trial there is a full treeAggregate).
    ``hvp`` is required by TRON only.
    """

    value_and_grad: Callable[[Array], tuple[Array, Array]]
    value: Callable[[Array], Array]
    ls_prepare: Callable[[Array, Array], Any]
    ls_eval: Callable[[Any, Array], tuple[Array, Array]]
    hvp: Optional[Callable[[Array, Array], Array]] = None
    # -- optional margin-carrying protocol (GLM fast path) ------------------
    # When all four are present AND the solve is unconstrained, LBFGS keeps
    # the per-row margins z = X'@w in its loop state: each iteration then
    # costs ONE gather pass (u = X'@p via ls_prepare_z) + ONE scatter pass
    # (gradient via value_and_grad_at) instead of two full gather+scatter
    # sweeps — ~2x fewer one-hot matmuls on the tiled layout.
    margins: Optional[Callable[[Array], Array]] = None  # w -> z
    ls_prepare_z: Optional[Callable[[Array, Array, Array], Any]] = None  # (z,w,p)
    ls_advance: Optional[Callable[[Any, Array], Array]] = None  # (carry,a)->z'
    value_and_grad_at: Optional[
        Callable[[Array, Array], tuple[Array, Array]]
    ] = None  # (w, z) -> (f, g)
    dir_margins: Optional[Callable[[Array], Array]] = None  # p -> X'@p (+shift)
    # TRON CG fast path: ``curvature(z)`` -> per-row d2 = weight*l''(z),
    # computed ONCE per outer iteration; ``hvp_at(d2, v)`` -> Hv with no
    # per-call z gather or d2z pass (one gather + one scatter sweep)
    curvature: Optional[Callable[[Array], Array]] = None  # z -> d2 rows
    hvp_at: Optional[Callable[[Array, Array], Array]] = None  # (d2, v) -> Hv
    # Full dense Hessian (small-d only): the batched-Newton fast path for
    # per-entity solves. None when the layout can't densify (TiledBatch).
    hessian: Optional[Callable[[Array], Array]] = None  # w -> H [d, d]
    # The scale the value is SUMMED at, where that is more than the value:
    # for a GLM the sum of the row weights (a row's loss is computed from
    # numbers of order one, and is no better than eps of THAT however small
    # it comes out: softplus(9) - 9 is 1.2e-4 +- 1e-6). Newton's stop reads
    # it; nothing else does.
    value_scale: Optional[Callable[[], Array]] = None


def from_value_and_grad(
    fn: Callable[[Array], tuple[Array, Array]],
    hvp: Optional[Callable[[Array, Array], Array]] = None,
) -> Objective:
    """Wrap a plain value-and-grad callable (line-search trials do full evals)."""

    def ls_prepare(w, p):
        return (w, p)

    def ls_eval(carry, alpha):
        w, p = carry
        f, g = fn(w + alpha * p)
        return f, jnp.dot(g, p)

    return Objective(
        value_and_grad=fn,
        value=lambda w: fn(w)[0],
        ls_prepare=ls_prepare,
        ls_eval=ls_eval,
        hvp=hvp,
    )


class BoxConstraints(NamedTuple):
    """Per-coefficient box [lower, upper]; +-inf entries are unconstrained.

    The reference projects every iterate into the constraint hypercube
    (LBFGS.scala:72-87 / OptimizerConfig constraintMap).
    """

    lower: Array
    upper: Array

    def project(self, w: Array) -> Array:
        return jnp.clip(w, self.lower, self.upper)


def project_or_identity(constraints: Optional[BoxConstraints], w: Array) -> Array:
    return w if constraints is None else constraints.project(w)


class SolveResult(NamedTuple):
    """Terminal optimizer state plus per-iteration telemetry buffers.

    ``values``/``grad_norms`` are fixed-size (max_iterations + 1) tracking
    buffers — the OptimizationStatesTracker analog — valid up to
    ``iterations`` (inclusive); the rest is padding.
    """

    w: Array
    value: Array
    grad: Array
    iterations: Array  # i32
    reason: Array  # i32 convergence code
    values: Array  # f[max_iter + 1]
    grad_norms: Array  # f[max_iter + 1]
    # i32 count of FULL passes over the training data (value+grad or
    # Hessian-vector evaluations): benches divide rows*data_passes by
    # wall-clock so optimizers with inner data loops (TRON's truncated CG
    # runs one Hv pass per CG step) report throughput comparably with
    # single-pass-per-iteration optimizers.
    data_passes: Array = 0


def convergence_reason(
    iteration: Array,
    value: Array,
    prev_value: Array,
    grad_norm: Array,
    init_value: Array,
    init_grad_norm: Array,
    max_iterations: int,
    tolerance: float,
    ls_failed: Array,
) -> Array:
    """Reference-parity convergence decision (Optimizer.scala:155-169)."""
    tol = jnp.asarray(tolerance, dtype=value.dtype)
    reason = jnp.where(
        iteration >= max_iterations,
        MAX_ITERATIONS,
        jnp.where(
            ls_failed,
            OBJECTIVE_NOT_IMPROVING,
            jnp.where(
                jnp.abs(value - prev_value) <= tol * jnp.abs(init_value),
                FUNCTION_VALUES_CONVERGED,
                jnp.where(
                    grad_norm <= tol * init_grad_norm, GRADIENT_CONVERGED, NOT_CONVERGED
                ),
            ),
        ),
    )
    return reason.astype(jnp.int32)
