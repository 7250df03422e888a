"""``glmix_plain``, solved on the HOST's CPU backend, its objective summed
on the device the program sums on.

The same plain float32 ``jax.numpy`` fit, file and all: this one only says
where each part runs. At a million features the reference's two passes are
a gather of 273M coefficients and a scatter-add of 273M products; XLA's TPU
gather and scatter-add take 4.4 s and 3.9 s for them (PERF.md, Findings
PR 26), so a solve there would hold the chip for minutes of every run. The
host's caches hold the 4 MB table, and the solve takes a fraction of that
there.

The objective the solve reports is a float32 sum over seven million rows,
and such a sum is only as good as its order: against float64 the host's
reads 3.4e-6 low and the chip's 2e-6 to 1.1e-5 low at the same coefficients
(PERF.md, Findings PR 26, review round). ``glm_fe.lbfgs_fit`` never sees
that, because there program and reference both sum on the chip, in the
same order. So here too: after the solve, the reference's objective is
computed once more at the solve's coefficients by the reference's own
``dot_rows`` and ``_loss`` on the DEFAULT device (the chip in a run, the
CPU in a rehearsal): one gather pass. The comparison that decides
``correct`` is unchanged: float32 against float32, under the cell's limits;
``lower`` is the bfloat16 control as in ``glmix_plain``, and its objective
is computed with its own rounding.
"""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def _objective(d: int, max_iterations: int, lower):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import glmix_plain

    _, dot_rows = glmix_plain._fixed_effect_solver(d, max_iterations, lower)

    def value(cols, vals, y, l2, w):
        loss, _ = glmix_plain._loss(dot_rows(cols, vals, w), y)
        return jnp.sum(loss) + 0.5 * l2 * jnp.dot(w, w)

    return jax.jit(value)


def fit(raw: dict, shape: dict, train_json: dict,
        lower: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark.reference import glmix_plain

    with jax.default_device(jax.devices("cpu")[0]):
        out = glmix_plain.fit(raw, shape, train_json, lower=lower)
    coordinates = train_json["coordinates"]
    (name, coord), = coordinates.items()
    if coord["type"] != "fixed_effect" or len(out["steps"]) != 1:
        raise ValueError("this reference knows one fixed-effect update only")
    opt, tr = coord["optimizer"], raw["train"]
    value = _objective(
        int(shape["fe_features"]), int(opt["max_iterations"]), lower)
    out["steps"][0]["loss"] = float(value(
        jnp.asarray(tr["cols"]), jnp.asarray(tr["vals"]),
        jnp.asarray(tr["y"]), jnp.float32(opt["regularization_weight"]),
        jnp.asarray(out["coefficients"][name], jnp.float32)))
    return out
