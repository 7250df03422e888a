"""Optimizer selection and regularization wiring.

Mirrors the reference's OptimizerFactory (photon-api
optimization/OptimizerFactory.scala:39-77) and RegularizationContext
(optimization/RegularizationContext.scala:41-66): LBFGS handles NONE/L2,
OWLQN handles L1/ELASTIC_NET (l1 = alpha*lambda, l2 = (1-alpha)*lambda),
TRON handles NONE/L2 only and requires a twice-differentiable loss.
"""

from __future__ import annotations

import dataclasses
from enum import Enum
from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.ops.objective import GLMObjective, make_objective
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.optim.adapter import glm_adapter
from photon_ml_tpu.optim.common import BoxConstraints, SolveResult
from photon_ml_tpu.optim.lbfgs import LBFGSConfig, lbfgs_solve
from photon_ml_tpu.optim.newton import (
    NewtonConfig,
    NewtonProblem,
    solve_newton_problem,
)
from photon_ml_tpu.optim.owlqn import owlqn_solve
from photon_ml_tpu.optim.tron import TRONConfig, tron_solve

Array = jax.Array


class OptimizerType(str, Enum):
    LBFGS = "lbfgs"
    TRON = "tron"
    # TPU-first addition (no reference analog): damped Newton with explicit
    # batched [d, d] Hessians — the latency-light fast path for SMALL-d
    # solves (per-entity random effects), where vmapped LBFGS is bound by
    # sequential while_loop depth, not FLOPs
    NEWTON = "newton"


class RegularizationType(str, Enum):
    NONE = "none"
    L1 = "l1"
    L2 = "l2"
    ELASTIC_NET = "elastic_net"


@dataclasses.dataclass(frozen=True)
class RegularizationContext:
    """Splits a single regularization weight into (l1, l2) parts."""

    reg_type: RegularizationType = RegularizationType.NONE
    alpha: float = 1.0  # elastic-net mixing: l1 = alpha*w, l2 = (1-alpha)*w

    def __post_init__(self):
        if self.reg_type == RegularizationType.ELASTIC_NET:
            if not (0.0 <= self.alpha <= 1.0):
                raise ValueError(f"elastic-net alpha must be in [0,1]: {self.alpha}")

    def l1_weight(self, reg_weight: float) -> float:
        if self.reg_type == RegularizationType.L1:
            return reg_weight
        if self.reg_type == RegularizationType.ELASTIC_NET:
            return self.alpha * reg_weight
        return 0.0

    def l2_weight(self, reg_weight: float) -> float:
        if self.reg_type == RegularizationType.L2:
            return reg_weight
        if self.reg_type == RegularizationType.ELASTIC_NET:
            return (1.0 - self.alpha) * reg_weight
        return 0.0


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Typed analog of the reference's OptimizerConfig + GLMOptimizationConfiguration.

    ``box_constraints`` holds (feature_index, lower, upper) triples — the
    constraintMap analog (OptimizerConfig.scala); every optimizer projects
    iterates into the hypercube. Indices address the GLOBAL feature space,
    so constraints apply to fixed-effect / plain-GLM solves only (per-entity
    projected spaces renumber features; matching reference scope).
    """

    optimizer_type: OptimizerType = OptimizerType.LBFGS
    max_iterations: int = 100
    tolerance: float = 1e-7
    regularization: RegularizationContext = RegularizationContext()
    regularization_weight: float = 0.0
    lbfgs_history: int = 10
    down_sampling_rate: float = 1.0
    box_constraints: Optional[tuple[tuple[int, float, float], ...]] = None

    def dense_box_bounds(self, num_features: int, sentinel: bool = False):
        """Validated dense numpy (lower, upper) bounds from the sparse
        (index, lower, upper) triples, or None when unconstrained. With
        ``sentinel`` the arrays carry one extra trailing unbounded slot —
        the gather target for projected spaces' padding id (index-map
        sentinel == num_features)."""
        if not self.box_constraints:
            return None
        import numpy as np

        size = num_features + (1 if sentinel else 0)
        lower = np.full(size, -np.inf, np.float32)
        upper = np.full(size, np.inf, np.float32)
        for idx, lo, hi in self.box_constraints:
            if not 0 <= idx < num_features:
                raise ValueError(
                    f"box constraint index {idx} out of range [0, {num_features})"
                )
            if lo > hi:
                raise ValueError(f"box constraint [{lo}, {hi}] is empty")
            lower[idx], upper[idx] = lo, hi
        return lower, upper

    def build_box_constraints(self, num_features: int) -> Optional[BoxConstraints]:
        """Materialize the sparse (index, lower, upper) triples as dense
        projection bounds for a ``num_features``-dim solve."""
        bounds = self.dense_box_bounds(num_features)
        if bounds is None:
            return None
        lower, upper = bounds
        return BoxConstraints(
            lower=jnp.asarray(lower, jnp.float32),
            upper=jnp.asarray(upper, jnp.float32),
        )

    def validate(self, loss_name: str) -> None:
        uses_l1 = self.regularization.reg_type in (
            RegularizationType.L1,
            RegularizationType.ELASTIC_NET,
        )
        if self.optimizer_type in (OptimizerType.TRON, OptimizerType.NEWTON):
            name = self.optimizer_type.value.upper()
            if uses_l1:
                raise ValueError(
                    f"{name} does not support L1/elastic-net regularization "
                    "(OptimizerFactory parity)"
                )
            if not get_loss(loss_name).has_hessian:
                raise ValueError(
                    f"{name} requires a twice-differentiable loss; "
                    f"'{loss_name}' is not (use LBFGS/OWLQN)"
                )


def split_reg_weights(
    reg: RegularizationContext, weights
) -> tuple[jax.Array, jax.Array]:
    """Vectorized (l2, l1) split of a λ GRID: the per-scalar
    ``RegularizationContext.l1_weight``/``l2_weight`` arithmetic applied to
    a whole [G] array at once, always returning [G] arrays (NONE-type
    regularization broadcasts its 0.0 so the sweep solvers' config axis
    keeps a uniform shape)."""
    lams = jnp.asarray(weights, jnp.float32)
    return (
        jnp.broadcast_to(
            jnp.asarray(reg.l2_weight(lams), jnp.float32), lams.shape
        ),
        jnp.broadcast_to(
            jnp.asarray(reg.l1_weight(lams), jnp.float32), lams.shape
        ),
    )


def build_objective(
    loss_name: str,
    config: OptimizerConfig,
    factors: Optional[Array] = None,
    shifts: Optional[Array] = None,
) -> GLMObjective:
    """GLM objective with the L2 part of the configured regularization."""
    return make_objective(
        loss_name,
        l2_weight=config.regularization.l2_weight(config.regularization_weight),
        factors=factors,
        shifts=shifts,
    )


def newton_config(config: OptimizerConfig) -> NewtonConfig:
    return NewtonConfig(
        max_iterations=config.max_iterations, tolerance=config.tolerance
    )


def newton_problem(
    adapter, constraints: Optional[BoxConstraints] = None
) -> NewtonProblem:
    """The Newton oracles of a prebuilt objective adapter."""
    if adapter.hessian is None:
        raise ValueError(
            "NEWTON needs a dense-Hessian adapter (small-d layouts only; "
            "the tiled layout cannot densify)"
        )
    return NewtonProblem(
        adapter.value_and_grad,
        adapter.hessian,
        constraints,
        adapter.ls_prepare,
        adapter.ls_eval,
        adapter.value_scale,
    )


def dispatch_solve(
    adapter,
    w0: Array,
    config: OptimizerConfig,
    l1,
    constraints: Optional[BoxConstraints] = None,
    init_value: Optional[Array] = None,
    init_grad_norm: Optional[Array] = None,
) -> SolveResult:
    """Route a prebuilt objective adapter to the configured optimizer.

    Shared by the single-device path (solve) and the mesh path
    (parallel.distributed) so dispatch rules live in exactly one place.
    ``l1`` may be a traced scalar — the OWLQN-vs-LBFGS choice depends only
    on the (static) regularization type, so lambda sweeps don't recompile.
    """
    uses_l1 = config.regularization.reg_type in (
        RegularizationType.L1,
        RegularizationType.ELASTIC_NET,
    )
    if config.optimizer_type == OptimizerType.TRON:
        return tron_solve(
            adapter,
            w0,
            TRONConfig(
                max_iterations=config.max_iterations, tolerance=config.tolerance
            ),
            constraints=constraints,
            init_value=init_value,
            init_grad_norm=init_grad_norm,
        )
    if config.optimizer_type == OptimizerType.NEWTON:
        return solve_newton_problem(
            newton_problem(adapter, constraints),
            w0,
            newton_config(config),
            init_value=init_value,
            init_grad_norm=init_grad_norm,
        )

    lcfg = LBFGSConfig(
        max_iterations=config.max_iterations,
        tolerance=config.tolerance,
        history=config.lbfgs_history,
    )
    if uses_l1:
        return owlqn_solve(
            adapter,
            w0,
            l1,
            lcfg,
            constraints=constraints,
            init_value=init_value,
            init_grad_norm=init_grad_norm,
        )
    return lbfgs_solve(
        adapter,
        w0,
        lcfg,
        constraints=constraints,
        init_value=init_value,
        init_grad_norm=init_grad_norm,
    )


def solve(
    loss_name: str,
    batch: SparseBatch,
    config: OptimizerConfig,
    w0: Array,
    constraints: Optional[BoxConstraints] = None,
    factors: Optional[Array] = None,
    shifts: Optional[Array] = None,
    init_value: Optional[Array] = None,
    init_grad_norm: Optional[Array] = None,
) -> SolveResult:
    """One-stop GLM solve: build objective + adapter, dispatch the optimizer.

    Pure and jit-friendly: wrap in jax.jit (static config) or vmap over
    batched problems.
    """
    config.validate(loss_name)
    obj = build_objective(loss_name, config, factors=factors, shifts=shifts)
    adapter = glm_adapter(obj, batch)
    l1 = config.regularization.l1_weight(config.regularization_weight)
    if constraints is None:
        constraints = config.build_box_constraints(batch.num_features)
    return dispatch_solve(
        adapter, w0, config, l1, constraints, init_value, init_grad_norm
    )
