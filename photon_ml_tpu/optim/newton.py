"""Damped Newton with explicit Hessians — the small-dimension fast path.

No reference analog (the reference solves every per-entity problem with the
same serial LBFGS/TRON it uses globally, RandomEffectCoordinate.scala:
101-130); this is a TPU-first addition. Per-entity random-effect problems
are TINY (projected local dims K ~ 16-1000): under ``vmap`` the deep
LBFGS/line-search ``while_loop`` nest is LATENCY-bound — hundreds of
sequential micro-steps — while an explicit-Hessian Newton iteration is a
few big batched ops: build H [E, K, K] via one data sweep on the MXU
(``precision=HIGHEST``), solve ``(H + ridge I) p = -g`` for the step, damp
by fixed step-halving. 5-10x shallower loops for the same optimum on convex
GLMs. The solve is float32 vector arithmetic over ENTITY lanes for K <= 32
(``optim/spd_solve.py``: a hand Cholesky and both substitutions on
``[.., E]`` slabs, no custom call) and XLA's blocked ``cholesky`` +
``cho_solve`` above that; which one is a static shape, not a setting.

Guard rails: requires a twice-differentiable loss (no smoothed hinge), no
L1 (factory rejects), and is intended for small K — H is dense [K, K].
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.common import (
    FUNCTION_VALUES_CONVERGED,
    NOT_CONVERGED,
    BoxConstraints,
    SolveResult,
    convergence_reason,
    project_or_identity,
)
from photon_ml_tpu.optim.spd_solve import spd_step, takes_hand_solve

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class NewtonConfig:
    max_iterations: int = 20
    tolerance: float = 1e-7
    max_halvings: int = 10  # damping: halve the step until f decreases
    ridge: float = 1e-8  # added to H's diagonal before either factorisation


def _newton_step(H: Array, grad: Array) -> Array:
    """``-(H^-1 grad)``, or steepest descent ``-grad`` where ``H`` is not
    SPD. Up to ``HAND_SOLVE_MAX_DIM`` coefficients (a static shape) by the
    hand solve over entity lanes, above it by XLA's blocked routine."""
    if takes_hand_solve(grad.shape[0]):
        return spd_step(H, grad)
    L = jnp.linalg.cholesky(H)
    ok = jnp.all(jnp.isfinite(L))
    eye = jnp.eye(grad.shape[0], dtype=H.dtype)
    return jnp.where(
        ok,
        -jax.scipy.linalg.cho_solve((jnp.where(ok, L, eye), True), grad),
        -grad,
    )


class _NewtonState(NamedTuple):
    w: Array
    value: Array
    grad: Array
    prev_value: Array
    iteration: Array
    reason: Array
    values: Array
    grad_norms: Array


def newton_solve(
    value_and_grad,
    hessian,
    w0: Array,
    config: NewtonConfig = NewtonConfig(),
    constraints: Optional[BoxConstraints] = None,
    init_value: Optional[Array] = None,
    init_grad_norm: Optional[Array] = None,
    ls_prepare=None,
    ls_eval=None,
    value_scale=None,
) -> SolveResult:
    """Minimize a convex twice-differentiable objective.

    ``value_and_grad(w) -> (f, g)``; ``hessian(w) -> H [d, d]``. Under
    ``vmap`` this solves batches of independent problems with converged
    lanes frozen (the RE bucket pattern). With the optional directional
    oracle (``ls_prepare``/``ls_eval``, unconstrained only) the damping
    candidates cost O(n) elementwise each instead of full objective sweeps.
    ``value_scale() -> scalar`` (``Objective.value_scale``) says at what
    scale the value is summed where that is more than the value itself.
    """
    dtype = w0.dtype
    d = w0.shape[0]
    w0 = project_or_identity(constraints, w0)
    f0, g0 = value_and_grad(w0)
    g0n = jnp.linalg.norm(g0)
    anchor_f = f0 if init_value is None else jnp.asarray(init_value, dtype)
    anchor_gn = g0n if init_grad_norm is None else jnp.asarray(init_grad_norm, dtype)

    nvals = config.max_iterations + 1
    values = jnp.full((nvals,), jnp.inf, dtype=dtype).at[0].set(f0)
    gnorms = jnp.full((nvals,), jnp.inf, dtype=dtype).at[0].set(g0n)

    init = _NewtonState(
        w=w0,
        value=f0,
        grad=g0,
        prev_value=f0,
        iteration=jnp.int32(0),
        reason=jnp.int32(NOT_CONVERGED),
        values=values,
        grad_norms=gnorms,
    )

    eye = jnp.eye(d, dtype=dtype)
    unconstrained = constraints is None
    # what the objective must move by to count as moving: the tolerance, or
    # the dtype's own resolution where the tolerance asks for less
    scale = jnp.abs(anchor_f)
    if value_scale is not None:
        scale = jnp.maximum(scale, jnp.asarray(value_scale(), dtype))
    floor = scale * jnp.maximum(
        jnp.asarray(config.tolerance, dtype), jnp.finfo(dtype).eps
    )
    use_oracle = (
        constraints is None and ls_prepare is not None and ls_eval is not None
    )

    def cond(s: _NewtonState):
        return s.reason == NOT_CONVERGED

    def body(s: _NewtonState) -> _NewtonState:
        step = _newton_step(hessian(s.w) + config.ridge * eye, s.grad)

        # the step's own forecast of what it can gain (half the squared
        # Newton decrement). Under ``floor`` the objective cannot show the
        # gain: the comparisons below would be between roundings, and which
        # of a bucket's lanes then stops would be chance (a vmapped bucket
        # runs to its slowest lane). Such a step is taken whole and is the
        # last: the forecast comes from the gradient and the Hessian, whose
        # relative error is small where the objective's differences have none
        # left.
        gain = -0.5 * jnp.dot(s.grad, step)
        last = unconstrained & (gain <= floor)

        # damping: evaluate ALL candidate alphas 1, 1/2, 1/4, ... in ONE
        # vectorized sweep (no sequential halving loop — latency is the
        # enemy for vmapped per-entity solves) and take the first decrease
        alphas = jnp.asarray(0.5, dtype) ** jnp.arange(
            config.max_halvings, dtype=dtype
        )
        if use_oracle:
            # margin-space oracle: each candidate is elementwise, not a
            # full gather/scatter objective sweep
            carry = ls_prepare(s.w, step)
            f_tries = jax.vmap(lambda a: ls_eval(carry, a)[0])(alphas)
        else:
            w_tries = project_or_identity(
                constraints, s.w[None, :] + alphas[:, None] * step[None, :]
            )
            f_tries = jax.vmap(lambda wt: value_and_grad(wt)[0])(w_tries)
        good = f_tries < s.value
        found = jnp.any(good) | last
        best_alpha = jnp.where(
            last, 1.0, jnp.where(found, alphas[jnp.argmax(good)], 0.0)
        )

        w_new = project_or_identity(constraints, s.w + best_alpha * step)
        f_new, g_new = value_and_grad(w_new)
        it = s.iteration + 1
        reason = convergence_reason(
            it,
            f_new,
            s.value,
            jnp.linalg.norm(g_new),
            anchor_f,
            anchor_gn,
            config.max_iterations,
            config.tolerance,
            ~found,  # no decreasing step found = objective not improving
        )
        reason = jnp.where(
            last & (it < config.max_iterations),
            FUNCTION_VALUES_CONVERGED,
            reason,
        ).astype(jnp.int32)
        nxt = _NewtonState(
            w=w_new,
            value=f_new,
            grad=g_new,
            prev_value=s.value,
            iteration=it,
            reason=reason,
            values=s.values.at[it].set(f_new),
            grad_norms=s.grad_norms.at[it].set(jnp.linalg.norm(g_new)),
        )
        return jax.tree.map(
            lambda a, b: jnp.where(s.reason == NOT_CONVERGED, b, a), s, nxt
        )

    final = lax.while_loop(cond, body, init)
    return SolveResult(
        w=final.w,
        value=final.value,
        grad=final.grad,
        iterations=final.iteration,
        reason=final.reason,
        values=final.values,
        grad_norms=final.grad_norms,
        data_passes=final.iteration + 1,
    )
