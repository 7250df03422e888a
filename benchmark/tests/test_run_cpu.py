"""The command end to end, off the chip, at a few thousand rows
(``--rehearsal-rows`` skips the look for a chip and forces the tiled kernels,
in pallas interpret mode): the last line has the contract's keys, the
program agrees with the plain reference under the cell's own limits, and
each fault a cell can have, planted underneath the harness, comes out as
``correct: false``. Slow (interpret-mode kernels): minutes."""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
ROWS = "20000"


def last_line(capsys, *argv):
    rc = run.main(list(argv))
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_last_line_has_the_contract_keys_and_is_correct(capsys, cell, trace):
    line, err = last_line(
        capsys, "--workload", cell, "--seed", "2147483653", "--seconds",
        "0.1", "--trace", trace, "--rehearsal-rows", ROWS)
    assert list(line)[:5] == [
        "correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    group = "per_layer" if trace == "1" else "end_to_end"
    mine = set(run.cell_metrics(BENCH, cell, group))
    assert set(line["metrics"]) <= mine
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"]), name
    if trace == "0":
        assert set(line["metrics"]) == mine
    else:
        # off the chip there are no peaks: shares of a roofline stay silent
        assert not any("roofline" in n for n in line["metrics"])
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] > line["device"]["busy_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # every number compared, beside its limit, ends standard error
    tail = err.strip().splitlines()[-(len(line["compared"]) + 1):]
    assert tail[-1] == "correct = True"
    for text, (name, c) in zip(tail, line["compared"].items()):
        assert text.startswith(f"compared {name} = ") and "limit" in text
        assert c["value"] <= c["limit"]


# -- faults, planted in the program underneath the harness -------------------------


def unchanged_state(monkeypatch):
    """A step that returns its state unchanged."""
    from photon_ml_tpu.game.coordinates import FixedEffectCoordinate

    real = FixedEffectCoordinate.update_model

    def update(self, model, residual):
        real(self, model, residual)  # trackers and health as usual
        return model

    monkeypatch.setattr(FixedEffectCoordinate, "update_model", update)


def half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest: every
    second training row gets weight 0 and the others weight 2."""
    from benchmark.drivers import game_fit

    real = game_fit.Driver._dataset

    def dataset(self, split):
        ds = real(self, split)
        if split is self.raw["train"]:
            w = np.where(np.arange(ds.num_rows) % 2 == 0, 2.0, 0.0)
            ds = dataclasses.replace(ds, weight=w)
        return ds

    monkeypatch.setattr(game_fit.Driver, "_dataset", dataset)


def altered_answer(monkeypatch):
    """An answer altered where it is produced: one coefficient of every
    fixed-effect update moved by one."""
    from photon_ml_tpu.game.coordinates import FixedEffectCoordinate

    real = FixedEffectCoordinate.update_model

    def update(self, model, residual):
        new = real(self, model, residual)
        return dataclasses.replace(
            new, coefficients=new.coefficients.at[0].add(1.0))

    monkeypatch.setattr(FixedEffectCoordinate, "update_model", update)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize(
    "fault", [unchanged_state, half_batch, altered_answer])
def test_a_planted_fault_comes_out_not_correct(capsys, monkeypatch, cell,
                                               fault):
    fault(monkeypatch)
    line, err = last_line(
        capsys, "--workload", cell, "--seed", "7", "--seconds", "0.1",
        "--trace", "0", "--rehearsal-rows", ROWS)
    assert line["correct"] is False
    over = [n for n, c in line["compared"].items()
            if not c["value"] <= c["limit"]]
    assert over, line["compared"]
    assert err.strip().splitlines()[-1] == "correct = False"


def test_no_chip_and_no_rehearsal_is_an_error(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
