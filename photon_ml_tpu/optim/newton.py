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
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.common import (
    FUNCTION_VALUES_CONVERGED,
    NOT_CONVERGED,
    BoxConstraints,
    SolveResult,
    convergence_reason,
    plain_lane_counts,
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


class NewtonProblem(NamedTuple):
    """One problem's oracles: ``value_and_grad(w) -> (f, g)``, ``hessian(w)
    -> H [d, d]``, an optional box, the optional directional line-search
    oracle (``ls_prepare`` / ``ls_eval``, unconstrained only) and
    ``value_scale() -> scalar`` (``Objective.value_scale``: at what scale the
    value is summed where that is more than the value itself)."""

    value_and_grad: Callable
    hessian: Callable
    constraints: Optional[BoxConstraints] = None
    ls_prepare: Optional[Callable] = None
    ls_eval: Optional[Callable] = None
    value_scale: Optional[Callable] = None


class _NewtonState(NamedTuple):
    w: Array
    value: Array
    grad: Array
    prev_value: Array
    iteration: Array
    reason: Array
    values: Array
    grad_norms: Array
    # the stopping test's anchors, fixed at the start
    anchor_f: Array
    anchor_gn: Array
    floor: Array


def _start(
    p: NewtonProblem,
    w0: Array,
    config: NewtonConfig,
    init_value: Optional[Array] = None,
    init_grad_norm: Optional[Array] = None,
) -> _NewtonState:
    dtype = w0.dtype
    w0 = project_or_identity(p.constraints, w0)
    f0, g0 = p.value_and_grad(w0)
    g0n = jnp.linalg.norm(g0)
    anchor_f = f0 if init_value is None else jnp.asarray(init_value, dtype)
    anchor_gn = g0n if init_grad_norm is None else jnp.asarray(init_grad_norm, dtype)

    nvals = config.max_iterations + 1
    values = jnp.full((nvals,), jnp.inf, dtype=dtype).at[0].set(f0)
    gnorms = jnp.full((nvals,), jnp.inf, dtype=dtype).at[0].set(g0n)

    # what the objective must move by to count as moving: the tolerance, or
    # the dtype's own resolution where the tolerance asks for less
    scale = jnp.abs(anchor_f)
    if p.value_scale is not None:
        scale = jnp.maximum(scale, jnp.asarray(p.value_scale(), dtype))
    floor = scale * jnp.maximum(
        jnp.asarray(config.tolerance, dtype), jnp.finfo(dtype).eps
    )
    return _NewtonState(
        w=w0,
        value=f0,
        grad=g0,
        prev_value=f0,
        iteration=jnp.int32(0),
        reason=jnp.int32(NOT_CONVERGED),
        values=values,
        grad_norms=gnorms,
        anchor_f=anchor_f,
        anchor_gn=anchor_gn,
        floor=floor,
    )


def _is_open(s: _NewtonState) -> Array:
    return s.reason == NOT_CONVERGED


def _step(p: NewtonProblem, config: NewtonConfig, s: _NewtonState) -> _NewtonState:
    """One damped Newton iteration; a lane that has stopped stays as it is."""
    dtype = s.w.dtype
    constraints = p.constraints
    unconstrained = constraints is None
    eye = jnp.eye(s.w.shape[0], dtype=dtype)
    step = _newton_step(p.hessian(s.w) + config.ridge * eye, s.grad)

    # the step's own forecast of what it can gain (half the squared
    # Newton decrement). Under ``floor`` the objective cannot show the
    # gain: the comparisons below would be between roundings, and which
    # of a bucket's lanes then stops would be chance (a vmapped bucket
    # runs to its slowest lane). Such a step is taken whole and is the
    # last: the forecast comes from the gradient and the Hessian, whose
    # relative error is small where the objective's differences have none
    # left.
    gain = -0.5 * jnp.dot(s.grad, step)
    last = unconstrained & (gain <= s.floor)

    # damping: evaluate ALL candidate alphas 1, 1/2, 1/4, ... in ONE
    # vectorized sweep (no sequential halving loop — latency is the
    # enemy for vmapped per-entity solves) and take the first decrease
    alphas = jnp.asarray(0.5, dtype) ** jnp.arange(
        config.max_halvings, dtype=dtype
    )
    if unconstrained and p.ls_prepare is not None and p.ls_eval is not None:
        # margin-space oracle: each candidate is elementwise, not a
        # full gather/scatter objective sweep
        carry = p.ls_prepare(s.w, step)
        f_tries = jax.vmap(lambda a: p.ls_eval(carry, a)[0])(alphas)
    else:
        w_tries = project_or_identity(
            constraints, s.w[None, :] + alphas[:, None] * step[None, :]
        )
        f_tries = jax.vmap(lambda wt: p.value_and_grad(wt)[0])(w_tries)
    good = f_tries < s.value
    found = jnp.any(good) | last
    best_alpha = jnp.where(
        last, 1.0, jnp.where(found, alphas[jnp.argmax(good)], 0.0)
    )

    w_new = project_or_identity(constraints, s.w + best_alpha * step)
    f_new, g_new = p.value_and_grad(w_new)
    it = s.iteration + 1
    reason = convergence_reason(
        it,
        f_new,
        s.value,
        jnp.linalg.norm(g_new),
        s.anchor_f,
        s.anchor_gn,
        config.max_iterations,
        config.tolerance,
        ~found,  # no decreasing step found = objective not improving
    )
    reason = jnp.where(
        last & (it < config.max_iterations),
        FUNCTION_VALUES_CONVERGED,
        reason,
    ).astype(jnp.int32)
    nxt = s._replace(
        w=w_new,
        value=f_new,
        grad=g_new,
        prev_value=s.value,
        iteration=it,
        reason=reason,
        values=s.values.at[it].set(f_new),
        grad_norms=s.grad_norms.at[it].set(jnp.linalg.norm(g_new)),
    )
    return jax.tree.map(lambda a, b: jnp.where(_is_open(s), b, a), s, nxt)


def _result(s: _NewtonState) -> SolveResult:
    return SolveResult(
        w=s.w,
        value=s.value,
        grad=s.grad,
        iterations=s.iteration,
        reason=s.reason,
        values=s.values,
        grad_norms=s.grad_norms,
        data_passes=s.iteration + 1,
    )


def solve_newton_problem(
    p: NewtonProblem,
    w0: Array,
    config: NewtonConfig = NewtonConfig(),
    init_value: Optional[Array] = None,
    init_grad_norm: Optional[Array] = None,
) -> SolveResult:
    """Minimize one convex twice-differentiable objective. Under ``vmap``
    this solves batches of independent problems with converged lanes frozen
    (the RE bucket pattern); :func:`newton_lanes` solves such a batch with
    its finished lanes left behind."""
    init = _start(p, w0, config, init_value, init_grad_norm)
    return _result(lax.while_loop(_is_open, partial(_step, p, config), init))


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
    """:func:`solve_newton_problem` from the oracles of
    :class:`NewtonProblem` given one by one."""
    return solve_newton_problem(
        NewtonProblem(value_and_grad, hessian, constraints, ls_prepare,
                      ls_eval, value_scale),
        w0, config, init_value, init_grad_norm,
    )


# ---------------------------------------------------------------------------
# A batch of independent problems whose finished lanes leave
# ---------------------------------------------------------------------------

#: the hand SPD solve puts the entities on the vector lanes (``[K, K+1, E]``
#: slabs), 128 of them to a register: a compacted round is whole registers
LANE_TILE = 128
#: a compacted round holds a sixteenth of the batch: the full-width loop
#: runs while more lanes than that are open
COMPACT_SHARE = 16


def compact_width(num_lanes: int) -> int:
    """The lanes a compacted round holds for a batch of ``num_lanes``: a
    sixteenth, rounded up to a multiple of ``LANE_TILE``. Where that is not
    fewer than ``num_lanes`` the batch runs as one plain vmapped loop."""
    tiles = -(-num_lanes // (COMPACT_SHARE * LANE_TILE))
    return max(tiles, 1) * LANE_TILE


def _take(tree, idx: Array):
    return jax.tree.map(lambda x: x[idx], tree)


def _compacted_rounds(step, prepare, lanes, s: _NewtonState, width: int):
    """Run the open lanes of ``s`` to their ends ``width`` at a time: a
    round gathers up to ``width`` open lanes (state and data), runs them
    until all have stopped and scatters them back. A round's fill slots are
    copies of a lane that has stopped, which the step leaves as it is.
    Returns ``(state, lane-iterations run, lanes, rounds)``."""

    def one_round(carry):
        s, run, done, rounds = carry
        is_open = _is_open(s)
        idx = jnp.nonzero(
            is_open, size=width, fill_value=jnp.argmin(is_open)
        )[0]
        part = jax.vmap(prepare)(_take(lanes, idx))
        sub, its = lax.while_loop(
            lambda c: jnp.any(_is_open(c[0])),
            lambda c: (step(part, c[0]), c[1] + 1),
            (_take(s, idx), jnp.int32(0)),
        )
        s = jax.tree.map(lambda x, y: x.at[idx].set(y), s, sub)
        done = done + jnp.minimum(jnp.sum(is_open, dtype=jnp.int32), width)
        return s, run + its * width, done, rounds + 1

    zero = jnp.int32(0)
    return lax.while_loop(
        lambda c: jnp.any(_is_open(c[0])), one_round, (s, zero, zero, zero)
    )


def newton_lanes(
    problem,
    lanes,
    w0: Array,
    config: NewtonConfig = NewtonConfig(),
    prepare: Callable = lambda lane: lane,
) -> SolveResult:
    """Solve ``w0.shape[0]`` independent problems, lane ``e`` being
    ``problem(prepare(lane_e))`` (a :class:`NewtonProblem`) from ``w0[e]``;
    ``lanes`` is a pytree whose leaves hold the lanes on their leading
    axis, and ``prepare`` what is made of a lane's data once, before a loop
    carries it (a packed design's reshape). Every lane runs
    :func:`solve_newton_problem`'s iterations, bit for bit what ``vmap`` of
    it gives; only which lanes a loop carries differs. One loop runs the
    whole batch while more than :func:`compact_width` lanes are open; the
    open lanes then go on in compacted rounds of that width, so a batch no
    longer runs every lane to its slowest one. Where the width is not below
    the batch this is the plain vmapped loop. Returns the batched result,
    its ``lanes`` what the lanes cost."""
    num = w0.shape[0]
    width = compact_width(num)
    if width >= num:
        res = jax.vmap(
            lambda lane, w: solve_newton_problem(
                problem(prepare(lane)), w, config)
        )(lanes, w0)
        return res._replace(lanes=plain_lane_counts(res.iterations))
    step = jax.vmap(lambda lane, s: _step(problem(lane), config, s))
    whole = jax.vmap(prepare)(lanes)
    s = jax.vmap(lambda lane, w: _start(problem(lane), w, config))(whole, w0)
    s, full = lax.while_loop(
        lambda c: jnp.sum(_is_open(c[0]), dtype=jnp.int32) > width,
        lambda c: (step(whole, c[0]), c[1] + 1),
        (s, jnp.int32(0)),
    )
    s, run, done, rounds = _compacted_rounds(step, prepare, lanes, s, width)
    return _result(s)._replace(lanes=jnp.stack([full * num + run, done, rounds]))
