"""Plain reference of a GLMix / GLM coordinate-descent fit.

Float32 ``jax.numpy`` at ``highest`` matmul precision: no kernels, no tiles,
no geometry buckets, nothing imported from the program and nothing taken
from it. It reads the generator's arrays and the cell's ``cli train`` JSON
and does what that JSON says: block coordinate descent from zero (a
coordinate's later solves start from its last one, as the program's do), each
fixed effect an L-BFGS solve of the L2-regularised logistic objective
(textbook two-loop recursion, history 10, Armijo backtracking along carried
margins), each random effect an independent damped Newton solve per entity,
residuals passed as offsets, AUC on the validation rows after every update.

The fixed-effect shard is [n, nnz/row] columns and values, so margins are a
gather and a row sum and the gradient a scatter-add; the random-effect
shard is dense [n, K], its rows grouped per entity and padded to the
longest entity (one pad, zero weight) so that every entity's Hessian is one
batched product.

A solve ends at ``max_iterations`` or where float32 ends it: no step along
a descent direction lowers the objective any more, or the objective repeats
exactly. (The program's LBFGS at ``tolerance: 0`` ends the same way, some
iterations before its 20th.) Each solve is one jitted program whose arrays
are arguments, never constants, so that it compiles once and in seconds.

``lower="bfloat16"`` is the CONTROL: the same computation with every
operand of every product (design values, coefficients, directions, per-row
factors) rounded to bfloat16 first and float32 accumulation - the single
pass a later PR would be tempted to put in place of the program's exact
bf16x2 splits. It has to fail the comparison (benchmark/tests).
"""

from __future__ import annotations

import functools

import numpy as np

HISTORY = 10
ARMIJO = 1e-4
MAX_HALVINGS = 30
NEWTON_HALVINGS = 10


def _rounder(lower):
    from jax import lax

    if lower is None:
        return lambda x: x
    if lower == "bfloat16":
        # not astype(bfloat16).astype(float32): on the TPU that pair rounded
        # next to nothing (PR 23's readings; XLA may drop it as excess
        # precision), and the control then passed every number
        return lambda x: lax.reduce_precision(
            x, exponent_bits=8, mantissa_bits=7)
    raise ValueError(f"no control precision '{lower}'")


def _loss(z, y):
    """Logistic loss per row and its derivative in z, labels in {0, 1}."""
    import jax
    import jax.numpy as jnp

    return jnp.logaddexp(0.0, z) - y * z, jax.nn.sigmoid(z) - y


# -- fixed effect -------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _fixed_effect_solver(d: int, max_iterations: int, lower):
    import jax
    import jax.numpy as jnp
    from jax import lax

    rnd = _rounder(lower)

    def dot_rows(cols, vals, w):
        return jnp.sum(rnd(vals) * rnd(w)[cols], axis=1)

    def value_grad(cols, vals, y, l2, w, z):
        l, dz = _loss(z, y)
        contrib = (rnd(vals) * rnd(dz)[:, None]).reshape(-1)
        g = jnp.zeros((d,), jnp.float32).at[cols.reshape(-1)].add(contrib)
        return jnp.sum(l) + 0.5 * l2 * jnp.dot(w, w), g + l2 * w

    def direction(g, S, Y, n_hist):
        """Two-loop recursion over the newest ``n_hist`` of HISTORY pairs
        (slot HISTORY-1 is the newest); returns -H g."""
        def back(i, carry):
            q, alphas = carry
            j = HISTORY - 1 - i
            ok = i < n_hist
            a = jnp.where(ok, jnp.dot(S[j], q) / jnp.where(
                ok, jnp.dot(S[j], Y[j]), 1.0), 0.0)
            return q - a * Y[j], alphas.at[j].set(a)

        q, alphas = lax.fori_loop(
            0, HISTORY, back, (g, jnp.zeros((HISTORY,), jnp.float32)))
        last = HISTORY - 1
        gamma = jnp.where(
            n_hist > 0,
            jnp.dot(S[last], Y[last]) / jnp.where(
                n_hist > 0, jnp.dot(Y[last], Y[last]), 1.0),
            1.0)
        q = q * gamma

        def forth(i, q):
            ok = i < n_hist
            j = jnp.clip(HISTORY - n_hist + i, 0, HISTORY - 1)
            b = jnp.dot(Y[j], q) / jnp.where(ok, jnp.dot(S[j], Y[j]), 1.0)
            return q + jnp.where(ok, alphas[j] - b, 0.0) * S[j]

        return -lax.fori_loop(0, HISTORY, forth, q)

    def solve(cols, vals, y, offsets, l2, w0):
        z0 = offsets + dot_rows(cols, vals, w0)
        f0, g0 = value_grad(cols, vals, y, l2, w0, z0)
        zeros = jnp.zeros((HISTORY, d), jnp.float32)

        def cond(s):
            return (s["it"] < max_iterations) & ~s["stop"]

        def body(s):
            w, z, f, g = s["w"], s["z"], s["f"], s["g"]
            p = direction(g, s["S"], s["Y"], s["n_hist"])
            slope = jnp.dot(g, p)
            bad = slope >= 0
            p = jnp.where(bad, -g, p)
            slope = jnp.where(bad, -jnp.dot(g, g), slope)
            u = dot_rows(cols, vals, p)
            first = jnp.where(
                s["n_hist"] == 0,
                jnp.minimum(1.0, 1.0 / jnp.maximum(jnp.linalg.norm(g),
                                                   1e-12)), 1.0)

            def phi(a):
                l, _ = _loss(z + a * u, y)
                wa = w + a * p
                return jnp.sum(l) + 0.5 * l2 * jnp.dot(wa, wa)

            def ls_cond(c):
                a, k = c
                return (phi(a) > f + ARMIJO * a * slope) & (k < MAX_HALVINGS)

            a, k = lax.while_loop(
                ls_cond, lambda c: (c[0] * 0.5, c[1] + 1),
                (first.astype(jnp.float32), jnp.int32(0)))
            failed = k >= MAX_HALVINGS
            w_new, z_new = w + a * p, z + a * u
            f_new, g_new = value_grad(cols, vals, y, l2, w_new, z_new)
            sv, yv = w_new - w, g_new - g
            keep = (jnp.dot(sv, yv) > 1e-10) & ~failed
            S = jnp.where(keep, jnp.roll(s["S"], -1, 0).at[-1].set(sv), s["S"])
            Y = jnp.where(keep, jnp.roll(s["Y"], -1, 0).at[-1].set(yv), s["Y"])
            take = ~failed
            return {
                "w": jnp.where(take, w_new, w), "z": jnp.where(take, z_new, z),
                "f": jnp.where(take, f_new, f), "g": jnp.where(take, g_new, g),
                "S": S, "Y": Y,
                "n_hist": jnp.where(
                    keep, jnp.minimum(s["n_hist"] + 1, HISTORY), s["n_hist"]),
                "it": s["it"] + 1,
                "stop": failed | (f_new == f),
            }

        s = lax.while_loop(cond, body, {
            "w": w0, "z": z0, "f": f0, "g": g0, "S": zeros, "Y": zeros,
            "n_hist": jnp.int32(0), "it": jnp.int32(0),
            "stop": jnp.bool_(False)})
        return s["w"], s["f"], s["it"], s["z"] - offsets

    return jax.jit(solve), jax.jit(dot_rows)


# -- random effect --------------------------------------------------------------


def group_rows(users: np.ndarray, n_users: int) -> np.ndarray:
    """[U, R] row index of each user's rows, -1 where a user has fewer than
    the longest. One pad to the maximum, no size classes."""
    order = np.argsort(users, kind="stable")
    counts = np.bincount(users, minlength=n_users)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(len(users)) - starts[users[order]]
    index = np.full((n_users, max(int(counts.max()), 1)), -1, np.int64)
    index[users[order], rank] = order
    return index


@functools.lru_cache(maxsize=None)
def _random_effect_solver(max_iterations: int, tolerance: float, lower):
    import jax
    import jax.numpy as jnp
    from jax import lax

    rnd = _rounder(lower)
    hi = lax.Precision.HIGHEST

    def solve(xg, yg, wg, og, l2, W0):
        """``xg`` [U, R, K] grouped rows, labels, 0/1 weights and offsets
        [U, R]; ``W0`` [U, K] the start. Returns (W [U, K], final objective and iterations per
        entity). An entity stops once its objective moves by no more than
        ``tolerance`` x its objective at zero, or no step lowers it."""
        U, R, K = xg.shape
        x = rnd(xg)

        def value(W):
            z = og + jnp.einsum("urk,uk->ur", x, rnd(W), precision=hi)
            l, dz = _loss(z, yg)
            f = jnp.sum(wg * l, axis=1) + 0.5 * l2 * jnp.sum(W * W, axis=1)
            return f, z, dz

        def cond(s):
            return (s["it"] < max_iterations) & jnp.any(s["active"])

        def body(s):
            W, f, active = s["W"], s["f"], s["active"]
            _, z, dz = value(W)
            g = jnp.einsum("urk,ur->uk", x, rnd(wg * dz), precision=hi)
            g = g + l2 * W
            p = jax.nn.sigmoid(z)
            H = jnp.einsum("urk,ur,url->ukl", x, rnd(wg * p * (1.0 - p)), x,
                           precision=hi) + l2 * jnp.eye(K, dtype=jnp.float32)
            step = -jnp.linalg.solve(H, g[..., None])[..., 0]
            alphas = 0.5 ** jnp.arange(NEWTON_HALVINGS, dtype=jnp.float32)
            tries = jax.vmap(lambda a: value(W + a * step)[0])(alphas)
            good = tries < f[None, :]
            alpha = jnp.where(
                jnp.any(good, axis=0), alphas[jnp.argmax(good, axis=0)], 0.0)
            W_new = jnp.where(active[:, None], W + alpha[:, None] * step, W)
            f_new = jnp.where(active, value(W_new)[0], f)
            moved = jnp.abs(f_new - f) > tolerance * jnp.abs(s["f0"])
            return {
                "W": W_new, "f": f_new, "f0": s["f0"], "it": s["it"] + 1,
                "its": s["its"] + active.astype(jnp.int32),
                "active": active & moved & (f_new < f),
            }

        f0 = value(W0)[0]
        s = lax.while_loop(cond, body, {
            "W": W0, "f": f0, "f0": f0, "it": jnp.int32(0),
            "its": jnp.zeros((U,), jnp.int32),
            "active": jnp.ones((U,), bool)})
        return s["W"], s["f"], s["its"]

    def score(x, W, users):
        return jnp.sum(rnd(x) * rnd(W)[users], axis=1)

    return jax.jit(solve), jax.jit(score)


# -- evaluation -------------------------------------------------------------------


def auc(scores: np.ndarray, labels: np.ndarray) -> float:
    """Area under the ROC curve by ranks, ties at their mean rank."""
    order = np.argsort(scores, kind="stable")
    s = scores[order]
    starts = np.flatnonzero(np.concatenate([[True], s[1:] != s[:-1]]))
    ends = np.concatenate([starts[1:], [len(s)]])
    ranks = np.empty(len(s), np.float64)
    ranks[order] = np.repeat((starts + 1 + ends) / 2.0, ends - starts)
    pos = labels > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float(
        (ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg))


# -- the fit ------------------------------------------------------------------------


def fit(raw: dict, shape: dict, train_json: dict,
        lower: str | None = None) -> dict:
    """The whole fit. Returns what the comparison reads: ``coefficients``
    per coordinate (fixed effect [d]; random effect [users, K], a row per
    user id), ``steps`` (loss and validation metrics after every update)
    and ``validation_scores``."""
    import jax
    import jax.numpy as jnp

    if train_json["task"] != "logistic":
        raise ValueError("this reference knows the logistic task only")
    tr, va = raw["train"], raw["validation"]
    n, n_val = len(tr["y"]), len(va["y"])
    d = int(shape["fe_features"])
    y = jnp.asarray(tr["y"])
    cols, vals = jnp.asarray(tr["cols"]), jnp.asarray(tr["vals"])
    vcols, vvals = jnp.asarray(va["cols"]), jnp.asarray(va["vals"])
    add = jax.jit(lambda a, b: a + b)

    names = list(train_json["coordinates"])
    scores = {name: jnp.zeros((n,), jnp.float32) for name in names}
    val_scores = {name: np.zeros((n_val,), np.float64) for name in names}
    coefficients, steps = {}, []
    start = {}  # a coordinate's next solve starts from its last, as CD does
    grouped = None

    for it in range(int(train_json["num_iterations"])):
        for name in names:
            coord = train_json["coordinates"][name]
            opt = coord["optimizer"]
            if opt.get("regularization") != "l2":
                raise ValueError("this reference knows L2 only")
            l2 = jnp.float32(opt["regularization_weight"])
            offsets = jnp.zeros((n,), jnp.float32)
            for other in names:
                if other != name:
                    offsets = add(offsets, scores[other])
            if coord["type"] == "fixed_effect":
                solve, dot_rows = _fixed_effect_solver(
                    d, int(opt["max_iterations"]), lower)
                w, loss, its, scores[name] = solve(
                    cols, vals, y, offsets, l2, start.get(
                        name, jnp.zeros((d,), jnp.float32)))
                start[name] = w
                loss, its = float(loss), float(its)
                val_scores[name] = np.asarray(
                    dot_rows(vcols, vvals, w), np.float64)
                coefficients[name] = np.asarray(w, np.float64)
            elif coord["type"] == "random_effect":
                solve, score = _random_effect_solver(
                    int(opt["max_iterations"]), float(opt["tolerance"]),
                    lower)
                if grouped is None:
                    index = group_rows(tr["users"], int(shape["users"]))
                    pad = index < 0
                    safe = jnp.asarray(np.where(pad, 0, index))
                    grouped = {
                        "safe": safe,
                        "seen": ~pad.all(axis=1),
                        "w": jnp.asarray((~pad).astype(np.float32)),
                        "x": jnp.asarray(tr["xu"])[safe],
                        "y": y[safe],
                        "users": jnp.asarray(tr["users"]),
                        "xu": jnp.asarray(tr["xu"]),
                        "val_users": jnp.asarray(va["users"]),
                        "val_xu": jnp.asarray(va["xu"]),
                    }
                W, f, its_u = solve(
                    grouped["x"], grouped["y"], grouped["w"],
                    offsets[grouped["safe"]], l2, start.get(
                        name, jnp.zeros(
                            (int(shape["users"]), tr["xu"].shape[1]),
                            jnp.float32)))
                start[name] = W
                seen = grouped["seen"]
                loss = float(np.sum(np.asarray(f, np.float64)[seen]))
                its = float(np.mean(np.asarray(its_u)[seen]))
                scores[name] = score(grouped["xu"], W, grouped["users"])
                val_scores[name] = np.asarray(
                    score(grouped["val_xu"], W, grouped["val_users"]),
                    np.float64)
                coefficients[name] = np.asarray(W, np.float64)
            else:
                raise ValueError(f"no reference for a '{coord['type']}'")
            total = functools.reduce(np.add, val_scores.values())
            steps.append({
                "iteration": it, "coordinate": name, "loss": loss,
                "solver_iterations": its,
                "metrics": {"auc": auc(total, va["y"])},
            })
    return {
        "coefficients": coefficients,
        "steps": steps,
        "validation_scores": functools.reduce(np.add, val_scores.values()),
    }
