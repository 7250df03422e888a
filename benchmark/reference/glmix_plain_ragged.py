"""Plain reference of a GLMix fit with ANY number of random effects over
ragged data: ``glmix_plain``'s coordinate descent, fed splits whose random
effects each have their own id column and their own sparse feature shard
(``generators/movielens_mixed.py``: per shard ``<shard>_cols`` /
``<shard>_vals`` [n, width], rows padded with value 0; the shape names each
shard's width under ``shards`` and each id column's count under
``entities``).

Nothing of the program is imported. The same float32 ``jax.numpy`` at
``highest`` precision as ``glmix_plain``, whose pieces are used as they
are: ``_loss``, ``_random_effect_solver`` (an independent damped Newton
solve per entity over a dense [U, R, K] stack) and ``auc``. What is
new is where the rows of an entity go. ``glmix_plain.group_rows`` pads
every entity to the longest one: 138,493 users x 9,254 rows x 21 features
x 4 bytes = 108 GB here. So the entities are sorted by row count and cut
into BLOCKS: a block's entities are padded to the block's own size class
(the next power of four above its longest entity: five or six classes an
id column, each one compile of the solver) and a block holds as many
entities as fit ``BLOCK_CELLS`` design cells, the last block of a class
padded with empty entities (zero weights; they solve to zero and are
dropped). Each block is one call of the unchanged solver; an entity's
answer does not depend on its block (``benchmark/tests/test_mixed_cell.py``
holds blocks against ``glmix_plain.fit`` itself).

Where each part runs, and why: everything on the default device (the chip
in a run), so every float32 sum that the comparison reads is formed where
the program forms it (PERF.md section 3 has why that matters at 18M rows).
The fixed effect has 32 features, so its design is held DENSE [n, 32] and
its two passes are matrix products at ``highest`` precision:
``glmix_plain``'s gather and scatter-add over the padded slots take XLA's
TPU seconds a pass (PERF.md, Findings PR 26) and its host a second, thirty
times a solve. The L-BFGS around them is ``glmix_plain``'s, line for line
(:func:`_dense_fixed_effect_solver`; ``tests/test_mixed_glmix.py`` holds
this file against ``glmix_plain.fit``, gathers and all). The per-entity solves' sums are short, and the [U, R, K] products
are what a chip is for.

``lower="bfloat16"`` is the control of ``glmix_plain``: every operand of
every product rounded to bfloat16 first.
"""

from __future__ import annotations

import functools
import time

import numpy as np

#: design cells (entities x padded rows x features) of one block
BLOCK_CELLS = 1 << 25
CLASS_BASE = 4


def dense_rows(cols: np.ndarray, vals: np.ndarray, k: int) -> np.ndarray:
    """Padded rows ([n, width] columns and values, pad value 0) -> dense
    [n, k] float32 (a small k only); a column held twice by a row adds."""
    row, slot = np.nonzero(vals)
    x = np.zeros(len(cols) * k, np.float32)
    np.add.at(x, row * k + cols[row, slot], vals[row, slot])
    return x.reshape(len(cols), k)


def size_class(longest: int) -> int:
    """The next power of ``CLASS_BASE`` at or above ``longest``."""
    r = 1
    while r < longest:
        r *= CLASS_BASE
    return r


def entity_blocks(ids: np.ndarray, n_entities: int, features: int,
                  block_cells: int = BLOCK_CELLS) -> list[dict]:
    """Blocks of entities sorted by row count. A block: ``entities`` [U]
    (-1 an empty pad entity) and ``index`` [U, R] the row of each slot
    (-1 a pad slot), R the block's size class, U the same for every block
    of a class."""
    order = np.argsort(ids, kind="stable")
    counts = np.bincount(ids, minlength=n_entities)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    seen = np.flatnonzero(counts)
    seen = seen[np.argsort(counts[seen], kind="stable")]
    classes = np.array([size_class(int(c)) for c in counts[seen]])
    blocks = []
    for r in np.unique(classes):
        members = seen[classes == r]
        per_block = max(block_cells // (int(r) * max(features, 1)), 1)
        per_block = min(per_block, len(members))
        for lo in range(0, len(members), per_block):
            part = members[lo:lo + per_block]
            entities = np.full(per_block, -1, np.int64)
            entities[:len(part)] = part
            index = np.full((per_block, int(r)), -1, np.int64)
            c = counts[part]
            e = np.repeat(np.arange(len(part)), c)
            slot = np.arange(len(e)) - np.repeat(np.cumsum(c) - c, c)
            index[e, slot] = order[np.repeat(starts[part], c) + slot]
            blocks.append({"entities": entities, "index": index})
    return blocks


@functools.lru_cache(maxsize=None)
def _block_ops(lower):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmark.reference import glmix_plain

    rnd = glmix_plain._rounder(lower)

    def gather(per_row, index):
        """[n] -> [U, R] at ``index``, 0 on pad slots."""
        return jnp.where(index >= 0, per_row[jnp.maximum(index, 0)], 0.0)

    def scores(xg, W, index, out):
        """``out`` [n + 1] with every slot's x.w written at its row; pad
        slots go to the spare last entry."""
        z = jnp.einsum("urk,uk->ur", rnd(xg), rnd(W),
                       precision=lax.Precision.HIGHEST)
        at = jnp.where(index >= 0, index, out.shape[0] - 1)
        return out.at[at.reshape(-1)].set(z.reshape(-1))

    def score_rows(x, W, ids):
        return jnp.sum(rnd(x) * rnd(W)[ids], axis=1)

    return jax.jit(gather), jax.jit(scores), jax.jit(score_rows)


@functools.lru_cache(maxsize=None)
def _dense_fixed_effect_solver(max_iterations: int, lower):
    """``glmix_plain._fixed_effect_solver`` over a DENSE design: the same
    L-BFGS (two-loop recursion, history 10, Armijo backtracking along
    carried margins, the same ends), its row sums and its scatter-add
    written as products with X^T [d, n]. Returns (solve, dot_rows)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmark.reference import glmix_plain

    rnd = glmix_plain._rounder(lower)
    hi = lax.Precision.HIGHEST
    HISTORY, ARMIJO = glmix_plain.HISTORY, glmix_plain.ARMIJO
    MAX_HALVINGS = glmix_plain.MAX_HALVINGS

    # the design is held TRANSPOSED, [d, n]: a [n, 32] float32 array pads
    # its 32 lanes to 128 on a TPU, four times its 2.3 GB at 18M rows. The
    # control's rounding of it is done ONCE, where it is placed (`fit`):
    # rounded inside the solve it was a copy a use, 12.9 GB in all
    def dot_rows(xt, w):
        return jnp.matmul(rnd(w), xt, precision=hi)

    def value_grad(xt, y, l2, w, z):
        l, dz = glmix_plain._loss(z, y)
        g = jnp.matmul(xt, rnd(dz), precision=hi)
        return jnp.sum(l) + 0.5 * l2 * jnp.dot(w, w), g + l2 * w

    def direction(g, S, Y, n_hist):
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

    def solve(x, y, offsets, l2, w0):
        d = w0.shape[0]
        z0 = offsets + dot_rows(x, w0)
        f0, g0 = value_grad(x, y, l2, w0, z0)
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
            u = dot_rows(x, p)
            first = jnp.where(
                s["n_hist"] == 0,
                jnp.minimum(1.0, 1.0 / jnp.maximum(jnp.linalg.norm(g),
                                                   1e-12)), 1.0)

            def phi(a):
                l, _ = glmix_plain._loss(z + a * u, y)
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
            f_new, g_new = value_grad(x, y, l2, w_new, z_new)
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


def fit(raw: dict, shape: dict, train_json: dict,
        lower: str | None = None,
        block_cells: int = BLOCK_CELLS) -> dict:
    """The whole fit. Returns what the comparison reads: ``coefficients``
    per coordinate (fixed effect [d]; random effect [entities, K], a row per
    id), ``steps`` (loss and validation metrics after every update) and
    ``validation_scores``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import glmix_plain

    if train_json["task"] != "logistic":
        raise ValueError("this reference knows the logistic task only")
    tr, va = raw["train"], raw["validation"]
    n, n_val = len(tr["y"]), len(va["y"])
    y_host = np.asarray(tr["y"], np.float32)
    y = jnp.asarray(y_host)
    gather, block_scores, score_rows = _block_ops(lower)
    rounded = jax.jit(glmix_plain._rounder(lower), donate_argnums=0)

    names = list(train_json["coordinates"])
    scores = {name: jnp.zeros((n,), jnp.float32) for name in names}
    val_scores = {name: np.zeros((n_val,), np.float64) for name in names}
    coefficients, steps, start, prepared = {}, [], {}, {}
    seconds = {}  # where this reference's own time goes, by stage

    def clock(stage: str, since: float) -> None:
        seconds[stage] = seconds.get(stage, 0.0) + time.perf_counter() - since

    def prepare(name: str, coord: dict) -> dict:
        """A coordinate's arrays, laid out once and kept for both sweeps."""
        shard = coord["shard_name"]
        cols, vals = tr[shard + "_cols"], tr[shard + "_vals"]
        k = int(shape["shards"][shard])
        if coord["type"] == "fixed_effect":
            return {
                "x": rounded(jnp.asarray(np.ascontiguousarray(
                    dense_rows(cols, vals, k).T))),
                "val_x": rounded(jnp.asarray(np.ascontiguousarray(dense_rows(
                    va[shard + "_cols"], va[shard + "_vals"], k).T))),
                "d": k,
            }
        ids = np.asarray(tr[coord["id_name"]], np.int64)
        # the shape names the key that counts each id column's entities
        entities = int(shape[shape["entities"][coord["id_name"]]])
        x = dense_rows(cols, vals, k)
        blocks = []
        for b in entity_blocks(ids, entities, k, block_cells):
            index = b["index"]
            pad = index < 0
            safe = np.where(pad, 0, index)
            blocks.append({
                "entities": b["entities"],
                "index": jnp.asarray(index.astype(np.int32)),
                "x": jnp.asarray(np.where(pad[..., None], 0.0, x[safe])),
                "y": jnp.asarray(y_host[safe]),
                "w": jnp.asarray((~pad).astype(np.float32)),
            })
        val_ids = np.asarray(va[coord["id_name"]], np.int64)
        return {
            "blocks": blocks, "entities": entities, "k": k,
            "val_x": jnp.asarray(dense_rows(
                va[shard + "_cols"], va[shard + "_vals"], k)),
            "val_ids": jnp.asarray(val_ids.astype(np.int32)),
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
                solve, dot_rows = _dense_fixed_effect_solver(
                    int(opt["max_iterations"]), lower)
                w, loss, its, scores[name] = solve(
                    p["x"], y, offsets, l2, start.get(
                        name, jnp.zeros((p["d"],), jnp.float32)))
                start[name] = w
                loss, its = float(loss), float(its)
                val_scores[name] = np.asarray(
                    dot_rows(p["val_x"], w), np.float64)
                coefficients[name] = np.asarray(w, np.float64)
            elif coord["type"] == "random_effect":
                solve, _ = glmix_plain._random_effect_solver(
                    int(opt["max_iterations"]), float(opt["tolerance"]),
                    lower)
                table = start.get(name)
                if table is None:
                    table = np.zeros((p["entities"], p["k"]), np.float32)
                new = table.copy()
                out = jnp.zeros((n + 1,), jnp.float32)
                loss, its, solved = 0.0, 0.0, 0
                for b in p["blocks"]:
                    real = b["entities"] >= 0
                    W0 = np.zeros((len(real), p["k"]), np.float32)
                    W0[real] = table[b["entities"][real]]
                    W, f, its_u = solve(
                        b["x"], b["y"], b["w"], gather(offsets, b["index"]),
                        l2, jnp.asarray(W0))
                    out = block_scores(b["x"], W, b["index"], out)
                    new[b["entities"][real]] = np.asarray(W)[real]
                    loss += float(np.sum(np.asarray(f, np.float64)[real]))
                    its += float(np.sum(np.asarray(its_u)[real]))
                    solved += int(real.sum())
                start[name] = new
                its = its / max(solved, 1)
                scores[name] = out[:n]
                val_scores[name] = np.asarray(score_rows(
                    p["val_x"], jnp.asarray(new), p["val_ids"]), np.float64)
                coefficients[name] = np.asarray(new, np.float64)
            else:
                raise ValueError(f"no reference for a '{coord['type']}'")
            clock("solve:" + name, t0)
            t0 = time.perf_counter()
            total = functools.reduce(np.add, val_scores.values())
            metrics = {"auc": glmix_plain.auc(total, va["y"])}
            clock("auc", t0)
            steps.append({
                "iteration": it, "coordinate": name, "loss": loss,
                "solver_iterations": its,
                "metrics": metrics,
            })
    return {
        "coefficients": coefficients,
        "steps": steps,
        "validation_scores": functools.reduce(np.add, val_scores.values()),
        "seconds": seconds,
    }

