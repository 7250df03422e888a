"""CPU rehearsals of ``chip_smoke.py``: its control flow — children one
after another, the real CLI, the checks on what they report — guarded by
tier-1 at a tiny size. What only the chip can say (Mosaic kernels in the
solve, HBM per device, the transport facts) is not rehearsed: the device
assertion is steered from here, the script has no flag for it.
"""

import json
import os

import pytest

import chip_smoke  # conftest.py puts the repo root on sys.path


@pytest.fixture
def scratch(monkeypatch, tmp_path):
    """Keep the rehearsal's files out of the checkout."""
    monkeypatch.setattr(chip_smoke, "WORKDIR", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "LOG_COPY", str(tmp_path / "logs"))
    return tmp_path


@pytest.fixture
def on_cpu(monkeypatch, scratch):
    """The test-only device assertion: accept the CPU backend, and force
    the tiled layout so that the pallas kernels run (interpreted) in the
    children as they do (compiled) on the chip."""
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "FE_LAYOUT", "tiled")


def _lines(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return [json.loads(ln) for ln in out]


def test_refuses_the_cpu_before_training(scratch, capsys):
    assert chip_smoke.main(["--rows", "2000"]) != 0
    assert capsys.readouterr().out.strip() == ""  # no result line at all
    assert not os.path.exists(os.path.join(chip_smoke.WORKDIR, "train.avro"))
    with open(os.path.join(chip_smoke.LOG_COPY, "probe.err")) as f:
        assert "Traceback" not in f.read()


def test_one_chip_rehearsal(on_cpu, capsys):
    assert chip_smoke.main(["--rows", "2000", "--seed", "3"]) == 0
    lines = _lines(capsys)
    # exactly the contract's keys, nothing added
    assert list(lines[-1]) == ["ok", "device"] and lines[-1]["ok"] is True
    assert sorted(lines[-1]["device"]) == ["count", "kind", "platform"]
    assert lines[-1]["device"]["platform"] == "cpu"
    by_phase = {ln["phase"]: ln for ln in lines[:-1]}
    assert list(by_phase) == [
        "transport", "generate", "train", "score", "serve", "solvers",
        "cache", "total",
    ]
    train = by_phase["train"]
    assert train["fallback_calls"] == 0
    assert train["native_rows"] == 2500 and train["python_rows"] == 0
    assert [s[:2] for s in train["coordinate_steps"]] == [
        [0, "fixed"], [0, "per-user"], [1, "fixed"], [1, "per-user"]]
    assert by_phase["score"]["auc"] == pytest.approx(
        train["validation_auc"], abs=chip_smoke.SCORE_AUC_TOL)
    serve = by_phase["serve"]
    assert serve["known"] != serve["unseen"]
    assert serve["max_abs_diff_vs_cli_score"] <= chip_smoke.SERVE_SCORE_TOL
    assert [t[0] for t in by_phase["solvers"]["trackers"]] == [
        "fe-tron", "fe-owlqn-box"]
    assert by_phase["cache"]["second_process"]["hits"] >= 1
    assert by_phase["cache"]["dir"] == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert not os.path.exists(chip_smoke.WORKDIR)  # nothing left behind


def test_four_chip_rehearsal(on_cpu, monkeypatch, capsys):
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    assert chip_smoke.main(["--rows", "2000", "--chips", "4"]) == 0
    lines = _lines(capsys)
    assert lines[-1]["ok"] is True and lines[-1]["device"]["count"] == 4
    by_phase = {ln["phase"]: ln for ln in lines[:-1]}
    # only the mesh fits and what they are compared with
    assert list(by_phase) == [
        "generate", "one-chip", "batch4", "batch4 vs one-chip",
        "batch2-model2", "batch2-model2 vs one-chip", "total"]
    assert by_phase["one-chip"]["placement_devices"]["fixed.design"] == 1
    for name in ("batch4", "batch2-model2"):
        assert by_phase[name]["placement_devices"]["fixed.design"] == 4
        diff = by_phase[f"{name} vs one-chip"]
        assert diff["fe_max_abs_diff"] <= (
            chip_smoke.MESH_COEF_TOL * diff["fe_max_abs"])
        assert diff["auc_diff"] <= chip_smoke.MESH_AUC_TOL
    assert by_phase["batch2-model2"]["placement_devices"][
        "per-user.coefficients"] == 4


def test_four_chips_need_four_devices(on_cpu, capsys):
    """Under the suite's eight virtual devices ``--chips 4`` must refuse,
    not build a mesh over half of what jax reports."""
    assert chip_smoke.main(["--rows", "2000", "--chips", "4"]) != 0
    assert capsys.readouterr().out.strip() == ""
