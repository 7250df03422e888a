"""Plain reference of a GAME fit with a FACTORED random effect (matrix
factorization): fixed effects as ``glmix_plain_ragged`` solves them, and per
factored coordinate the upstream alternation
(FactoredRandomEffectCoordinate.scala:111-147), ``mf_iterations`` times an
update:

1. per-entity damped Newton solves in latent space: an entity's design is
   ``A x`` of its rows, here a plain gather of ``A``'s columns (the shard is
   one-hot: ``<shard>_cols`` [n, 1], value 1), over ``glmix_plain_ragged``'s
   blocks of entities, each block one call of
   ``glmix_plain._random_effect_solver`` unchanged;
2. the refit of ``vec(A)`` as one L2 logistic GLM: margins
   ``sum_k A[k, col_i] * C[entity_i, k]``, gradient by ``segment_sum`` over
   the rows sorted by column, textbook L-BFGS (``glmix_plain``'s two-loop
   recursion, history 10 and ends; the line search is strong Wolfe,
   Nocedal & Wright's Algorithms 3.5 / 3.6, along carried margins: a solve
   cut off at 15 iterations stands where its steps took it), from the
   current ``A``.

Nothing of the program is imported; the program's initial ``A`` (its seeded
Gaussian draw) is DATA: the driver hands it over as
``shape["latent_init"][<coordinate>]`` [K, d]. Float32 ``jax.numpy`` at
``highest`` precision, everything on the default device.

Departures from upstream, each because the program makes it too and the
comparison is of the same fit: latent vectors start at zero and are
warm-started in the second sweep; the refit's L2 term is over ``vec(A)``
alone; an update's ``loss`` is the refit's final objective; what is
compared of the coordinate (``coefficients[<name>]``) is its OWN scores
``(A x) . c_entity``, the one thing a rotation or rescaling of the latent
space leaves alone, over the rows the driver names
(``shape["compared_rows"][<name>]``: entity ids and one-hot columns of ALL
training rows, also where ``raw["train"]`` holds fewer: the half-batch
fault of ``benchmark/tests/readings.py`` fits every second training row and
the comparison needs vectors of one length).

The rows are walked in CHUNKS of ``CHUNK`` (a [rows, K] float32 array pads
its K lanes to 128 on a TPU: 9.2 GB at 18M rows).

``lower="bfloat16"`` is the control of ``glmix_plain``: every operand of
every product rounded to bfloat16 first.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from benchmark.reference import glmix_plain
from benchmark.reference import glmix_plain_ragged as ragged

CHUNK = 1 << 20


def _chunks(x: np.ndarray, fill=0) -> np.ndarray:
    """[n] -> [chunks, CHUNK], padded with ``fill``."""
    n = len(x)
    total = -(-n // CHUNK) * CHUNK
    out = np.full(total, fill, x.dtype)
    out[:n] = x
    return out.reshape(-1, CHUNK)


@functools.lru_cache(maxsize=None)
def _row_ops(lower):
    import jax
    import jax.numpy as jnp
    from jax import lax

    rnd = glmix_plain._rounder(lower)

    def dot_rows(A, C, ent, col):
        """[chunks, CHUNK] margins sum_k A[k, col] C[ent, k]."""
        At, Cr = rnd(A.T), rnd(C)

        def one(args):
            e, c = args
            return jnp.sum(At[c] * Cr[e], axis=1)

        return lax.map(one, (ent, col))

    def scatter(per_row, C, ent, col, d):
        """[K, d]: sum over rows of per_row * C[ent] at the row's column;
        ``col`` ascending, so each chunk is a sorted segment sum."""
        Cr = rnd(C)

        def one(acc, args):
            g, e, c = args
            return acc + jax.ops.segment_sum(
                rnd(g)[:, None] * Cr[e], c, num_segments=d,
                indices_are_sorted=True), None

        out, _ = lax.scan(
            one, jnp.zeros((d, C.shape[1]), jnp.float32),
            (per_row, ent, col))
        return out.T

    return jax.jit(dot_rows), jax.jit(scatter, static_argnums=4)


#: the strong-Wolfe line search's constants (Nocedal & Wright's defaults for
#: quasi-Newton directions)
WOLFE_C1, WOLFE_C2, WOLFE_EVALUATIONS = 1e-4, 0.9, 20


def _strong_wolfe(phi_and_slope, f0, slope0, first):
    """Nocedal & Wright, Algorithms 3.5 (bracketing: a trial that is too
    short for the curvature condition is doubled) and 3.6 (zoom: the next
    trial is the minimiser of the cubic through both ends, kept 5% inside
    the bracket, else the midpoint), at most ``WOLFE_EVALUATIONS`` trials;
    when they run out, the best trial that met the sufficient-decrease
    condition. Why not ``glmix_plain``'s Armijo backtracking: the refit is
    cut off long before it converges, so WHERE the solver stands after 15
    iterations depends on the steps it takes, and a first step of 1 / |g|
    that Armijo accepts is some dozen doublings short of the curvature
    condition (PERF.md section 3). Returns (step, failed)."""
    import jax.numpy as jnp
    from jax import lax

    def enough(a, f):
        return f <= f0 + WOLFE_C1 * a * slope0

    def flat(slope):
        return jnp.abs(slope) <= WOLFE_C2 * jnp.abs(slope0)

    def cubic(a, fa, da, b, fb, db):
        d1 = da + db - 3.0 * (fa - fb) / (a - b)
        rad = d1 * d1 - da * db
        d2 = jnp.sqrt(jnp.maximum(rad, 0.0)) * jnp.sign(b - a)
        den = db - da + 2.0 * d2
        x = b - (b - a) * (db + d2 - d1) / den
        lo, hi = jnp.minimum(a, b), jnp.maximum(a, b)
        inside = ((rad >= 0.0) & jnp.isfinite(x) & (jnp.abs(den) > 1e-20)
                  & (x > lo + 0.05 * (hi - lo)) & (x < hi - 0.05 * (hi - lo)))
        return jnp.where(inside, x, 0.5 * (a + b))

    zero = jnp.float32(0.0)
    start = {
        "phase": jnp.int32(0),  # 0 bracketing, 1 zoom, 2 found, 3 given up
        "a": first.astype(jnp.float32), "n": jnp.int32(0),
        "prev": (zero, f0, slope0), "lo": (zero, f0, slope0),
        "hi": (zero, f0, slope0), "found": zero,
        "fallback": (zero, f0),
    }

    def body(s):
        a = s["a"]
        f, slope = phi_and_slope(a)
        n = s["n"] + 1
        fb_a, fb_f = s["fallback"]
        better = enough(a, f) & (f < fb_f)
        fallback = (jnp.where(better, a, fb_a), jnp.where(better, f, fb_f))
        here = (a, f, slope)

        def pick(c, x, y):
            return tuple(jnp.where(c, p, q) for p, q in zip(x, y))

        def bracketing():
            short = (~enough(a, f)) | ((n > 1) & (f >= s["prev"][1]))
            done = enough(a, f) & flat(slope)
            zoom = short | (~done & (slope >= 0.0))
            lo = pick(short, s["prev"], here)
            hi = pick(short, here, s["prev"])
            phase = jnp.where(done, 2, jnp.where(
                zoom, 1, jnp.where(a >= 1e10, 3, 0))).astype(jnp.int32)
            nxt = jnp.where(zoom, cubic(*lo, *hi), jnp.minimum(2.0 * a, 1e10))
            return (phase, nxt, pick(zoom, lo, s["lo"]),
                    pick(zoom, hi, s["hi"]), jnp.where(done, a, s["found"]))

        def zooming():
            lo, hi = s["lo"], s["hi"]
            worse = (~enough(a, f)) | (f >= lo[1])
            done = (~worse) & flat(slope)
            turn = slope * (hi[0] - lo[0]) >= 0.0
            new_hi = pick(worse, here, pick(turn, lo, hi))
            new_lo = pick(worse, lo, here)
            tiny = jnp.abs(new_hi[0] - new_lo[0]) <= 1e-12 * jnp.maximum(
                1.0, jnp.abs(new_lo[0]))
            phase = jnp.where(done, 2, jnp.where(tiny, 3, 1)).astype(jnp.int32)
            return (phase, cubic(*new_lo, *new_hi), new_lo, new_hi,
                    jnp.where(done, a, s["found"]))

        phase, nxt, lo, hi, found = lax.cond(
            s["phase"] == 0, bracketing, zooming)
        return {"phase": phase, "a": nxt, "n": n, "prev": here, "lo": lo,
                "hi": hi, "found": found, "fallback": fallback}

    s = lax.while_loop(
        lambda s: (s["phase"] < 2) & (s["n"] < WOLFE_EVALUATIONS), body, start)
    ok = s["phase"] == 2
    fb_a, fb_f = s["fallback"]
    usable = (~ok) & (fb_a > 0.0) & (fb_f < f0)
    step = jnp.where(ok, s["found"], jnp.where(usable, fb_a, 0.0))
    return step, ~(ok | usable)



@functools.lru_cache(maxsize=None)
def _refit_solver(max_iterations: int, d: int, lower):
    """``glmix_plain``'s L-BFGS over vec(A) ([K, d] held as a matrix: dots
    are sums over both axes) with a strong-Wolfe line search
    (:func:`_strong_wolfe`) along the carried margins, its passes this
    file's ``dot_rows`` and ``scatter``. Returns solve(A0, C, ent, col, y, w, offsets, l2) ->
    (A, final objective, iterations)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dot_rows, scatter = _row_ops(lower)
    HISTORY = glmix_plain.HISTORY

    def vdot(a, b):
        return jnp.sum(a * b)

    def value_grad(C, ent, col, y, w, l2, A, z):
        l, dz = glmix_plain._loss(z, y)
        g = scatter(w * dz, C, ent, col, d)
        return jnp.sum(w * l) + 0.5 * l2 * vdot(A, A), g + l2 * A

    def direction(g, S, Y, n_hist):
        def back(i, carry):
            q, alphas = carry
            j = HISTORY - 1 - i
            ok = i < n_hist
            a = jnp.where(ok, vdot(S[j], q) / jnp.where(
                ok, vdot(S[j], Y[j]), 1.0), 0.0)
            return q - a * Y[j], alphas.at[j].set(a)

        q, alphas = lax.fori_loop(
            0, HISTORY, back, (g, jnp.zeros((HISTORY,), jnp.float32)))
        last = HISTORY - 1
        gamma = jnp.where(
            n_hist > 0,
            vdot(S[last], Y[last]) / jnp.where(
                n_hist > 0, vdot(Y[last], Y[last]), 1.0),
            1.0)
        q = q * gamma

        def forth(i, q):
            ok = i < n_hist
            j = jnp.clip(HISTORY - n_hist + i, 0, HISTORY - 1)
            b = vdot(Y[j], q) / jnp.where(ok, vdot(S[j], Y[j]), 1.0)
            return q + jnp.where(ok, alphas[j] - b, 0.0) * S[j]

        return -lax.fori_loop(0, HISTORY, forth, q)

    def solve(A0, C, ent, col, y, w, offsets, l2):
        z0 = offsets + dot_rows(A0, C, ent, col)
        f0, g0 = value_grad(C, ent, col, y, w, l2, A0, z0)
        zeros = jnp.zeros((HISTORY,) + A0.shape, jnp.float32)

        def cond(s):
            return (s["it"] < max_iterations) & ~s["stop"]

        def body(s):
            A, z, f, g = s["w"], s["z"], s["f"], s["g"]
            p = direction(g, s["S"], s["Y"], s["n_hist"])
            slope = vdot(g, p)
            bad = slope >= 0
            p = jnp.where(bad, -g, p)
            slope = jnp.where(bad, -vdot(g, g), slope)
            u = dot_rows(p, C, ent, col)
            first = jnp.where(
                s["n_hist"] == 0,
                jnp.minimum(1.0, 1.0 / jnp.maximum(jnp.sqrt(vdot(g, g)),
                                                   1e-12)), 1.0)

            ww, wp, pp = vdot(A, A), vdot(A, p), vdot(p, p)

            def phi_and_slope(a):
                l, dz = glmix_plain._loss(z + a * u, y)
                return (
                    jnp.sum(w * l) + 0.5 * l2 * (
                        ww + 2.0 * a * wp + a * a * pp),
                    jnp.sum(w * dz * u) + l2 * (wp + a * pp))

            a, failed = _strong_wolfe(phi_and_slope, f, slope, first)
            A_new, z_new = A + a * p, z + a * u
            f_new, g_new = value_grad(C, ent, col, y, w, l2, A_new, z_new)
            sv, yv = A_new - A, g_new - g
            keep = (vdot(sv, yv) > 1e-10) & ~failed
            S = jnp.where(keep, jnp.roll(s["S"], -1, 0).at[-1].set(sv), s["S"])
            Y = jnp.where(keep, jnp.roll(s["Y"], -1, 0).at[-1].set(yv), s["Y"])
            take = ~failed
            return {
                "w": jnp.where(take, A_new, A), "z": jnp.where(take, z_new, z),
                "f": jnp.where(take, f_new, f), "g": jnp.where(take, g_new, g),
                "S": S, "Y": Y,
                "n_hist": jnp.where(
                    keep, jnp.minimum(s["n_hist"] + 1, HISTORY), s["n_hist"]),
                "it": s["it"] + 1,
                "stop": failed | (f_new == f),
            }

        s = lax.while_loop(cond, body, {
            "w": A0, "z": z0, "f": f0, "g": g0, "S": zeros, "Y": zeros,
            "n_hist": jnp.int32(0), "it": jnp.int32(0),
            "stop": jnp.bool_(False)})
        return s["w"], s["f"], s["it"]

    return jax.jit(solve)


@functools.lru_cache(maxsize=None)
def _latent_design():
    import jax
    import jax.numpy as jnp

    def design(A, col, pad):
        """[U, R, K]: A's column of every slot's row, 0 on pad slots."""
        return jnp.where(pad[..., None], 0.0, A.T[col])

    return jax.jit(design)


def fit(raw: dict, shape: dict, train_json: dict,
        lower: str | None = None,
        block_cells: int = ragged.BLOCK_CELLS) -> dict:
    """The whole fit. Returns what the comparison reads: ``coefficients``
    per coordinate (fixed effect [d]; factored: its own scores over
    ``shape["compared_rows"]``, the training rows), ``steps`` and
    ``validation_scores``."""
    import jax
    import jax.numpy as jnp

    if train_json["task"] != "logistic":
        raise ValueError("this reference knows the logistic task only")
    tr, va = raw["train"], raw["validation"]
    n, n_val = len(tr["y"]), len(va["y"])
    y_host = np.asarray(tr["y"], np.float32)
    y = jnp.asarray(y_host)
    gather, _, _ = ragged._block_ops(lower)
    dot_rows, _ = _row_ops(lower)
    rounded = jax.jit(glmix_plain._rounder(lower), donate_argnums=0)

    names = list(train_json["coordinates"])
    scores = {name: jnp.zeros((n,), jnp.float32) for name in names}
    val_scores = {name: np.zeros((n_val,), np.float64) for name in names}
    coefficients, steps, start, prepared = {}, [], {}, {}
    seconds, refit_iterations = {}, []

    def clock(stage: str, since: float) -> None:
        seconds[stage] = seconds.get(stage, 0.0) + time.perf_counter() - since

    def prepare(name: str, coord: dict) -> dict:
        shard = coord["shard_name"]
        cols, vals = tr[shard + "_cols"], tr[shard + "_vals"]
        k = int(shape["shards"][shard])
        if coord["type"] == "fixed_effect":
            return {
                "x": rounded(jnp.asarray(np.ascontiguousarray(
                    ragged.dense_rows(cols, vals, k).T))),
                "val_x": rounded(jnp.asarray(np.ascontiguousarray(
                    ragged.dense_rows(
                        va[shard + "_cols"], va[shard + "_vals"], k).T))),
                "d": k,
            }
        if cols.shape[1] != 1 or not np.all(vals == 1):
            raise ValueError("this reference knows a one-hot shard only")
        col = np.asarray(cols[:, 0], np.int32)
        ids = np.asarray(tr[coord["id_name"]], np.int64)
        entities = int(shape[shape["entities"][coord["id_name"]]])
        latent_dim = int(coord["latent_dim"])
        blocks = []
        for b in ragged.entity_blocks(ids, entities, latent_dim, block_cells):
            index = b["index"]
            pad = index < 0
            safe = np.where(pad, 0, index)
            blocks.append({
                "entities": b["entities"],
                "index": jnp.asarray(index.astype(np.int32)),
                "col": jnp.asarray(col[safe]),
                "pad": jnp.asarray(pad),
                "y": jnp.asarray(y_host[safe]),
                "w": jnp.asarray((~pad).astype(np.float32)),
            })
        by_col = np.argsort(col, kind="stable")
        return {
            "blocks": blocks, "entities": entities, "k": latent_dim, "d": k,
            # the refit's rows, sorted by column and cut in chunks
            "by_col": jnp.asarray(_chunks(by_col.astype(np.int32))),
            "col_s": jnp.asarray(_chunks(col[by_col], fill=k - 1)),
            "ent_s": jnp.asarray(_chunks(ids[by_col].astype(np.int32))),
            "y_s": jnp.asarray(_chunks(y_host[by_col])),
            "w_s": jnp.asarray(_chunks(np.ones(n, np.float32))),
            # scoring, in the rows' own order
            "col": jnp.asarray(_chunks(col)),
            "ent": jnp.asarray(_chunks(ids.astype(np.int32))),
            "val_col": jnp.asarray(_chunks(
                np.asarray(va[shard + "_cols"][:, 0], np.int32))),
            "val_ent": jnp.asarray(_chunks(
                np.asarray(va[coord["id_name"]], np.int32))),
            # the rows whose scores are compared
            "own_col": jnp.asarray(_chunks(
                np.asarray(shape["compared_rows"][name]["cols"], np.int32))),
            "own_ent": jnp.asarray(_chunks(
                np.asarray(shape["compared_rows"][name]["ids"], np.int32))),
        }

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
                    offsets = offsets + scores[other]
            if name not in prepared:
                t0 = time.perf_counter()
                prepared[name] = prepare(name, coord)
                clock("prepare:" + name, t0)
            p = prepared[name]
            t0 = time.perf_counter()
            if coord["type"] == "fixed_effect":
                solve, fe_rows = ragged._dense_fixed_effect_solver(
                    int(opt["max_iterations"]), lower)
                w, loss, its, scores[name] = solve(
                    p["x"], y, offsets, l2, start.get(
                        name, jnp.zeros((p["d"],), jnp.float32)))
                start[name] = w
                loss, its = float(loss), float(its)
                val_scores[name] = np.asarray(
                    fe_rows(p["val_x"], w), np.float64)
                coefficients[name] = np.asarray(w, np.float64)
            elif coord["type"] == "factored_random_effect":
                lat = coord["latent_optimizer"]
                if lat.get("regularization") != "l2":
                    raise ValueError("this reference knows L2 only")
                newton, _ = glmix_plain._random_effect_solver(
                    int(opt["max_iterations"]), float(opt["tolerance"]),
                    lower)
                refit = _refit_solver(
                    int(lat["max_iterations"]), p["d"], lower)
                design = _latent_design()
                A, table = start.get(name, (None, None))
                if A is None:
                    A = jnp.asarray(np.asarray(
                        shape["latent_init"][name], np.float32))
                    table = np.zeros((p["entities"], p["k"]), np.float32)
                off_s = None
                for _ in range(int(coord.get("mf_iterations", 1))):
                    new = table.copy()
                    its, solved = 0.0, 0
                    for b in p["blocks"]:
                        real = b["entities"] >= 0
                        W0 = np.zeros((len(real), p["k"]), np.float32)
                        W0[real] = table[b["entities"][real]]
                        W, _, its_u = newton(
                            design(A, b["col"], b["pad"]), b["y"], b["w"],
                            gather(offsets, b["index"]), l2, jnp.asarray(W0))
                        new[b["entities"][real]] = np.asarray(W)[real]
                        its += float(np.sum(np.asarray(its_u)[real]))
                        solved += int(real.sum())
                    table = new
                    its = its / max(solved, 1)
                    C = jnp.asarray(table)
                    if off_s is None:  # pad rows carry weight 0
                        off_s = jnp.where(
                            p["w_s"] > 0, offsets[p["by_col"]], 0.0)
                    A, loss, refit_its = refit(
                        A, C, p["ent_s"], p["col_s"], p["y_s"], p["w_s"],
                        off_s, jnp.float32(lat["regularization_weight"]))
                    refit_iterations.append(int(refit_its))
                start[name] = (A, table)
                loss = float(loss)
                own = dot_rows(A, C, p["ent"], p["col"]).reshape(-1)[:n]
                scores[name] = own
                val_scores[name] = np.asarray(dot_rows(
                    A, C, p["val_ent"], p["val_col"]).reshape(-1)[:n_val],
                    np.float64)
                n_own = len(shape["compared_rows"][name]["ids"])
                coefficients[name] = np.asarray(dot_rows(
                    A, C, p["own_ent"], p["own_col"]).reshape(-1)[:n_own],
                    np.float64)
            else:
                raise ValueError(f"no reference for a '{coord['type']}'")
            clock("solve:" + name, t0)
            t0 = time.perf_counter()
            total = functools.reduce(np.add, val_scores.values())
            metrics = {"auc": glmix_plain.auc(total, va["y"])}
            clock("auc", t0)
            steps.append({
                "iteration": it, "coordinate": name, "loss": loss,
                "solver_iterations": its, "metrics": metrics,
            })
    return {
        "coefficients": coefficients,
        "steps": steps,
        "validation_scores": functools.reduce(np.add, val_scores.values()),
        "seconds": seconds,
        "refit_iterations": refit_iterations,
    }
