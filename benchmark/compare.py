"""The comparison that decides ``correct``: what the timed path produced
against what the plain reference produced, number by number, each under the
limit its cell's file gives it (PERF.md has the readings each limit was set
from). A number the cell's file gives no limit is not compared."""

from __future__ import annotations

import numpy as np


def numbers(program: dict, reference: dict) -> dict[str, float]:
    """Every number that can be compared, by its short name."""
    out = {}
    for name, ref in reference["coefficients"].items():
        got = program["coefficients"][name]
        out[f"coef_rel.{name}"] = _rel(got, ref)
    out["val_score_rel"] = _rel(
        program["validation_scores"], reference["validation_scores"])
    loss, metric = [], []
    if len(program["steps"]) != len(reference["steps"]):
        raise ValueError("program and reference made different steps")
    for p, r in zip(program["steps"], reference["steps"]):
        if (p["iteration"], p["coordinate"]) != (
                r["iteration"], r["coordinate"]):
            raise ValueError("program and reference made different steps")
        loss.append(abs(p["loss"] - r["loss"]) / abs(r["loss"]))
        for key, value in r["metrics"].items():
            metric.append(abs(p["metrics"][key] - value))
    # the first update starts from zero offsets: no other coordinate's
    # rounding is in it yet, so it reads the same from seed to seed
    out["first_loss_rel"] = float(loss[0])
    out["step_loss_rel"] = float(max(loss))
    out["val_metric_gap"] = float(max(metric))
    return out


def _rel(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise ValueError(f"shapes differ: {got.shape} and {ref.shape}")
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def judge(values: dict[str, float], limits: dict[str, float]):
    """(correct, {name: {"value", "limit"}}) over the limited numbers. A
    number that is not finite, or a limit with no number, is a failure."""
    compared = {}
    correct = bool(limits)
    for name, limit in limits.items():
        value = values.get(name, float("nan"))
        compared[name] = {"value": value, "limit": limit}
        if not (np.isfinite(value) and value <= limit):
            correct = False
    return correct, compared
