"""CLI drivers: config parsing round-trip and a subprocess end-to-end
train -> save -> score pipeline over Avro files."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from photon_ml_tpu.config import (
    game_config_to_json,
    parse_game_config,
    parse_optimizer_config,
)
from photon_ml_tpu.data.avro import TRAINING_EXAMPLE_AVRO, write_avro
from photon_ml_tpu.game.estimator import (
    FactoredRandomEffectConfig,
    FixedEffectConfig,
    RandomEffectConfig,
)
from photon_ml_tpu.optim import OptimizerType, RegularizationType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_optimizer_config():
    cfg = parse_optimizer_config(
        {
            "type": "tron",
            "max_iterations": 15,
            "tolerance": 1e-5,
            "regularization": "l2",
            "regularization_weight": 2.5,
        }
    )
    assert cfg.optimizer_type == OptimizerType.TRON
    assert cfg.max_iterations == 15
    assert cfg.regularization.reg_type == RegularizationType.L2
    assert cfg.regularization_weight == 2.5
    with pytest.raises(ValueError, match="unknown optimizer config keys"):
        parse_optimizer_config({"max_iter": 3})


def test_parse_game_config_round_trip():
    doc = {
        "task": "logistic",
        "num_iterations": 2,
        "evaluators": ["auc", "rmse"],
        "coordinates": {
            "fixed": {
                "type": "fixed_effect",
                "shard_name": "global",
                "normalization": "standardization",
                "intercept_index": 0,
                "optimizer": {"regularization": "l2", "regularization_weight": 1.0},
            },
            "perUser": {
                "type": "random_effect",
                "shard_name": "user",
                "id_name": "userId",
                "active_rows_per_entity": 64,
            },
            "mf": {
                "type": "factored_random_effect",
                "shard_name": "user",
                "id_name": "userId",
                "latent_dim": 4,
                "mf_iterations": 2,
            },
        },
    }
    cfg = parse_game_config(doc)
    assert list(cfg.coordinates) == ["fixed", "perUser", "mf"]  # order kept
    assert isinstance(cfg.coordinates["fixed"], FixedEffectConfig)
    assert isinstance(cfg.coordinates["perUser"], RandomEffectConfig)
    assert isinstance(cfg.coordinates["mf"], FactoredRandomEffectConfig)
    assert cfg.coordinates["mf"].latent_dim == 4
    # JSON metadata re-parses to an equivalent config
    cfg2 = parse_game_config(game_config_to_json(cfg))
    assert cfg2 == cfg


@pytest.fixture(scope="module")
def avro_dataset(tmp_path_factory):
    rng = np.random.default_rng(99)
    tmp = tmp_path_factory.mktemp("cli")
    n, d, n_users = 240, 8, 6
    X = rng.normal(size=(n, d))
    users = rng.integers(0, n_users, n)
    w = rng.normal(size=d)
    u_eff = rng.normal(size=n_users)
    logits = X @ w + u_eff[users]
    y = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(float)

    def recs(lo, hi):
        for i in range(lo, hi):
            yield {
                "uid": str(i),
                "label": float(y[i]),
                "features": [
                    {"name": f"c{j}", "term": "", "value": float(X[i, j])}
                    for j in range(d)
                ],
                "metadataMap": {"userId": str(users[i])},
                "weight": None,
                "offset": None,
            }

    train_path = str(tmp / "train.avro")
    score_path = str(tmp / "holdout.avro")
    write_avro(train_path, TRAINING_EXAMPLE_AVRO, recs(0, 200))
    write_avro(score_path, TRAINING_EXAMPLE_AVRO, recs(200, 240))
    return tmp, train_path, score_path


def _run_cli(args, cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "photon_ml_tpu.cli", *args],
        capture_output=True,
        text=True,
        cwd=str(cwd),
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_cli_train_save_score_end_to_end(avro_dataset):
    tmp, train_path, score_path = avro_dataset
    config = {
        "task": "logistic",
        "input": {
            "format": "avro",
            "paths": [train_path],
            "feature_shards": {"global": ["features"]},
            "id_columns": ["userId"],
        },
        "coordinates": {
            "fixed": {
                "type": "fixed_effect",
                "shard_name": "global",
                "optimizer": {
                    "regularization": "l2",
                    "regularization_weight": 0.1,
                },
            },
            "perUser": {
                "type": "random_effect",
                "shard_name": "global",
                "id_name": "userId",
                "optimizer": {
                    "regularization": "l2",
                    "regularization_weight": 1.0,
                },
            },
        },
        "num_iterations": 1,
        "output_dir": str(tmp / "model"),
    }
    cfg_path = tmp / "train.json"
    cfg_path.write_text(json.dumps(config))

    summary = _run_cli(["train", "--config", str(cfg_path)], cwd=tmp)
    assert summary["num_rows"] == 200
    assert os.path.exists(tmp / "model" / "final" / "model-metadata.json")
    assert os.path.exists(tmp / "model" / "best" / "model-metadata.json")

    # the model dir carries the training feature index maps, so scoring a
    # NEW file reproduces training-time feature ids (prepareFeatureMaps)
    assert os.path.isdir(tmp / "model" / "final" / "feature-indexes" / "global")
    score_cfg = {
        "input": {
            "format": "avro",
            "paths": [score_path],
            "feature_shards": {"global": ["features"]},
            "id_columns": ["userId"],
        }
    }
    score_cfg_path = tmp / "score.json"
    score_cfg_path.write_text(json.dumps(score_cfg))
    out_path = str(tmp / "scores.avro")
    summary = _run_cli(
        [
            "score",
            "--model-dir", str(tmp / "model" / "final"),
            "--config", str(score_cfg_path),
            "--output", out_path,
            "--evaluators", "auc", "logistic_loss",
        ],
        cwd=tmp,
    )
    assert summary["num_rows"] == 40
    assert summary["metrics"]["auc"] > 0.6  # true holdout
    from photon_ml_tpu.data.avro import read_scoring_results

    recs = read_scoring_results(out_path)
    assert len(recs) == 40
    assert all(np.isfinite(r["predictionScore"]) for r in recs)


def test_parse_coordinate_config_rejects_unknown_keys():
    from photon_ml_tpu.config import parse_coordinate_config

    with pytest.raises(ValueError, match="unknown keys"):
        parse_coordinate_config(
            {"type": "fixed_effect", "shard_name": "g", "normalisation": "none"}
        )


@pytest.mark.slow
def test_cli_sigterm_checkpoint_then_resume(avro_dataset):
    """ISSUE 2 acceptance: a train CLI run killed with SIGTERM mid-fit
    writes a final checkpoint and exits gracefully; restarting with
    --resume reproduces the uninterrupted fit's final model."""
    import signal
    import time

    tmp, train_path, _ = avro_dataset
    config = {
        "task": "logistic",
        "input": {
            "format": "avro",
            "paths": [train_path],
            "feature_shards": {"global": ["features"]},
            "id_columns": ["userId"],
        },
        "coordinates": {
            "fixed": {
                "type": "fixed_effect",
                "shard_name": "global",
                "optimizer": {"regularization": "l2",
                              "regularization_weight": 0.1},
            },
            "perUser": {
                "type": "random_effect",
                "shard_name": "global",
                "id_name": "userId",
                "optimizer": {"regularization": "l2",
                              "regularization_weight": 1.0},
            },
        },
        "num_iterations": 4,
        "output_dir": str(tmp / "model"),
    }
    cfg_path = tmp / "train.json"
    cfg_path.write_text(json.dumps(config))

    # reference: the same fit, never interrupted
    ref_cfg = dict(config, output_dir=str(tmp / "model_ref"))
    ref_cfg_path = tmp / "train_ref.json"
    ref_cfg_path.write_text(json.dumps(ref_cfg))
    _run_cli(["train", "--config", str(ref_cfg_path)], cwd=tmp)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    ckpt_dir = tmp / "ckpt"
    proc = subprocess.Popen(
        [sys.executable, "-m", "photon_ml_tpu.cli", "train",
         "--config", str(cfg_path), "--checkpoint-dir", str(ckpt_dir)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=str(tmp), env=env,
    )
    # SIGTERM as soon as the first checkpoint lands (i.e. mid-fit, after
    # the handler is installed); the run must finish its step, write a
    # final checkpoint, and exit 75 with "interrupted": true
    deadline = time.monotonic() + 240
    while time.monotonic() < deadline and proc.poll() is None:
        if ckpt_dir.is_dir() and any(
            n.startswith("step-") for n in os.listdir(ckpt_dir)
        ):
            proc.send_signal(signal.SIGTERM)
            break
        time.sleep(0.005)
    out, err = proc.communicate(timeout=600)
    if proc.returncode == 0:
        pytest.skip("fit completed before SIGTERM landed; timing-dependent")
    assert proc.returncode == 75, err[-3000:]
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["interrupted"] is True
    assert any(n.startswith("step-") for n in os.listdir(ckpt_dir))

    summary = _run_cli(
        ["train", "--config", str(cfg_path),
         "--checkpoint-dir", str(ckpt_dir), "--resume"],
        cwd=tmp,
    )
    assert "interrupted" not in summary

    import numpy as np

    for sub in ("fixed-effect/fixed/coefficients.npz",
                "random-effect/perUser/model.npz"):
        with np.load(tmp / "model" / "final" / sub) as got, \
                np.load(tmp / "model_ref" / "final" / sub) as ref:
            for key in ref.files:
                if ref[key].dtype.kind == "f":
                    np.testing.assert_allclose(
                        got[key], ref[key], rtol=1e-5, atol=1e-6,
                        err_msg=f"{sub}:{key}",
                    )


@pytest.mark.slow
def test_cli_index_job(avro_dataset, tmp_path):
    """FeatureIndexingJob analog: scan avro -> persisted mmap index store."""
    from photon_ml_tpu.cli.index import main as index_main
    from photon_ml_tpu.data.index_map import INTERCEPT_KEY, IndexMap, MmapIndexMap

    tmp, train_path, _ = avro_dataset
    out = str(tmp_path / "idx")
    rc = index_main(
        ["--input", train_path, "--output", out,
         "--shards", "global:features"]
    )
    assert rc == 0
    imap = IndexMap.load(os.path.join(out, "global"))
    assert imap.get(INTERCEPT_KEY) >= 0
    assert len(imap) == 9  # c0..c7 + intercept
    # mmap store loads and answers lookups
    mm = MmapIndexMap(os.path.join(out, "global"))
    assert mm.get("c3") == imap.get("c3")


def test_parse_optimizer_config_string_dsl():
    """Reference mini-DSL: maxIter,tol,lambda,downSample,optType,regType
    (GLMOptimizationConfiguration.parseAndBuildFromString)."""
    from photon_ml_tpu.config import parse_optimizer_config

    cfg = parse_optimizer_config("50, 1e-6, 0.3, 0.8, LBFGS, L2")
    assert cfg.max_iterations == 50
    assert cfg.tolerance == 1e-6
    assert cfg.regularization_weight == 0.3
    assert cfg.down_sampling_rate == 0.8
    assert cfg.optimizer_type == OptimizerType.LBFGS
    assert cfg.regularization.reg_type == RegularizationType.L2
    en = parse_optimizer_config("10,1e-4,1.0,1.0,LBFGS,ELASTIC_NET,0.3")
    assert en.regularization.reg_type == RegularizationType.ELASTIC_NET
    assert en.regularization.alpha == 0.3
    with pytest.raises(ValueError, match="expected"):
        parse_optimizer_config("10,1e-4,1.0")
    with pytest.raises(ValueError, match="unknown optimizer"):
        parse_optimizer_config("10,1e-4,1,1,SGD,L2")


def test_dsl_alpha_only_for_elastic_net():
    from photon_ml_tpu.config import parse_optimizer_config

    with pytest.raises(ValueError, match="elastic_net"):
        parse_optimizer_config("50,1e-6,0.3,0.8,LBFGS,L2,0.5")


def test_load_listener_specs():
    from photon_ml_tpu.utils.events import load_listener, load_listeners

    fn = load_listener("photon_ml_tpu.utils.events:load_listeners")
    assert callable(fn)
    fn2 = load_listener("photon_ml_tpu.utils.events.load_listeners")
    assert callable(fn2)
    with pytest.raises(ValueError, match="dotted path"):
        load_listener("nodots")
    with pytest.raises(ValueError, match="cannot load"):
        load_listener("photon_ml_tpu.utils.events:NoSuchThing")
    with pytest.raises(ValueError, match="cannot load"):
        load_listener("no.such.module:thing")
    assert len(load_listeners([])) == 0


@pytest.mark.slow
def test_cli_train_config_driven_event_listener(avro_dataset):
    """--event-listeners analog: dotted-path listener specs in the train
    config are import-registered at driver startup (Driver.scala:110-118)."""
    tmp, train_path, _ = avro_dataset
    (tmp / "my_listeners.py").write_text(
        "class Recorder:\n"
        "    def __call__(self, event):\n"
        "        with open('events.log', 'a') as f:\n"
        "            f.write(type(event).__name__ + '\\n')\n"
    )
    config = {
        "task": "logistic",
        "input": {
            "format": "avro",
            "paths": [train_path],
            "feature_shards": {"global": ["features"]},
            "id_columns": ["userId"],
        },
        "coordinates": {
            "fixed": {
                "type": "fixed_effect",
                "shard_name": "global",
                "optimizer": {"max_iterations": 5},
            },
        },
        "event_listeners": ["my_listeners:Recorder"],
    }
    cfg_path = tmp / "train_listener.json"
    cfg_path.write_text(json.dumps(config))
    _run_cli(["train", "--config", str(cfg_path)], cwd=tmp)
    log = (tmp / "events.log").read_text().splitlines()
    assert "SetupEvent" in log
    assert "TrainingStartEvent" in log
    assert "OptimizationLogEvent" in log
    assert "TrainingFinishEvent" in log


def test_train_parse_mesh_flag():
    """--mesh 'batch=N,model=M' -> the named GSPMD mesh config dict."""
    from photon_ml_tpu.cli.train import parse_mesh_flag

    assert parse_mesh_flag("batch=8") == {"batch": 8}
    assert parse_mesh_flag("batch=4,model=2") == {"batch": 4, "model": 2}
    assert parse_mesh_flag("model=8") == {"model": 8}
    assert parse_mesh_flag("auto") is True
    assert parse_mesh_flag("off") is False
    with pytest.raises(ValueError, match="axis=N"):
        parse_mesh_flag("batch")
    with pytest.raises(ValueError, match="integer size"):
        parse_mesh_flag("batch=many")
    with pytest.raises(ValueError, match="no axes"):
        parse_mesh_flag(" , ")


# ---------------------------------------------------------------------------
# ISSUE 8: sweep-spec validation + the train --sweep path
# ---------------------------------------------------------------------------


def test_sweep_flag_malformed_grids_are_typed_config_errors():
    """Malformed --sweep grids raise SweepSpecError NAMING the offending
    token — a typo must never silently train the default grid."""
    from photon_ml_tpu.sweep.grid import SweepSpecError, parse_sweep_spec

    for spec, fragment in (
        ("lambda=", "lambda="),
        ("lambda=10:1:log4", "inverted range"),
        ("lambda=1:10:log0", "zero/negative point count"),
        ("lambda=-0.5,1", "negative regularization"),
        ("gamma=1", "unknown key"),
    ):
        with pytest.raises(SweepSpecError) as err:
            parse_sweep_spec(spec)
        assert fragment in str(err.value)
        assert spec in str(err.value)  # the offending token, verbatim


def test_parse_sweep_config_object_and_shorthand():
    from photon_ml_tpu.cli.sweep import parse_sweep_config

    parsed = parse_sweep_config("lambda=1,10")
    assert parsed["grid"].default == (10.0, 1.0)
    assert parsed["policy"] == "best"
    parsed = parse_sweep_config(
        {"grid": ["lambda=1:100:log3", "lambda.perUser=5"],
         "metric": "rmse", "policy": "parsimonious", "rel_tol": 0.05}
    )
    assert parsed["grid"].size == 3
    assert parsed["metric"] == "rmse"
    assert parsed["rel_tol"] == 0.05
    with pytest.raises(ValueError, match="unknown sweep config keys"):
        parse_sweep_config({"grid": "lambda=1", "metrik": "auc"})
    from photon_ml_tpu.sweep.grid import SweepSpecError

    with pytest.raises(SweepSpecError, match="no lambda grid"):
        parse_sweep_config({})
    # the SweepGrid.to_json round-trip form is accepted back
    parsed = parse_sweep_config({"grid": {"lambda": [1.0, 10.0]}})
    assert parsed["grid"].default == (10.0, 1.0)


def test_train_main_sweep_flags_require_grid(tmp_path):
    from photon_ml_tpu.cli.train import main as train_main

    cfg = tmp_path / "c.json"
    cfg.write_text(json.dumps({"task": "logistic", "input": {},
                               "coordinates": {}}))
    with pytest.raises(SystemExit):
        train_main(["--config", str(cfg), "--sweep-metric", "auc"])


def test_sweep_without_validation_split_is_typed(tmp_path):
    from photon_ml_tpu.cli.sweep import run_sweep_fit

    with pytest.raises(ValueError, match="validation split"):
        run_sweep_fit(None, {"grid": "lambda=1"}, None, None, None, None)


@pytest.mark.slow
def test_cli_train_sweep_end_to_end(avro_dataset):
    """ISSUE 8: `cli train --sweep lambda=...` runs the vmapped sweep,
    reports the per-config table, saves the winner under best/, and
    publishes it into a registry a ModelRegistry can serve from."""
    tmp, train_path, holdout_path = avro_dataset
    config = {
        "task": "logistic",
        "input": {
            "format": "avro",
            "paths": [train_path],
            "feature_shards": {"global": ["features"]},
            "id_columns": ["userId"],
        },
        "validation": {"paths": [holdout_path]},
        "coordinates": {
            "fixed": {
                "type": "fixed_effect",
                "shard_name": "global",
                "optimizer": {"regularization": "l2",
                              "max_iterations": 30},
            },
            "perUser": {
                "type": "random_effect",
                "shard_name": "global",
                "id_name": "userId",
                "optimizer": {"regularization": "l2",
                              "max_iterations": 30},
            },
        },
        "num_iterations": 2,
        "output_dir": str(tmp / "sweep_model"),
    }
    cfg_path = tmp / "train_sweep.json"
    cfg_path.write_text(json.dumps(config))
    registry_dir = tmp / "sweep_registry"

    summary = _run_cli(
        ["train", "--config", str(cfg_path),
         "--sweep", "lambda=0.1:10:log4",
         "--sweep-registry-dir", str(registry_dir)],
        cwd=tmp,
    )
    sweep = summary["sweep"]
    assert len(sweep["configs"]) == 4
    assert sweep["metric"] == "auc"
    assert 0 <= sweep["selected_index"] < 4
    lams = [c["lambdas"]["fixed"] for c in sweep["configs"]]
    assert lams == sorted(lams, reverse=True)  # descending path order
    assert summary["best_metric"] == sweep["selected_metric"]
    # winner + feature indexes on disk in the best/ layout
    assert os.path.exists(tmp / "sweep_model" / "best" / "model-metadata.json")
    assert os.path.isdir(
        tmp / "sweep_model" / "best" / "feature-indexes" / "global"
    )
    # registry publish is complete and loadable
    version_dir = sweep["published_version"]
    assert os.path.basename(version_dir) == "v-00000001"
    from photon_ml_tpu.serving import ModelRegistry

    registry = ModelRegistry(str(registry_dir), warm=False,
                             poll_interval=3600)
    assert registry.refresh()
    assert registry.current_version == "v-00000001"
    registry.stop()


@pytest.mark.slow
def test_cli_sweep_subcommand(avro_dataset):
    """`cli sweep` reruns selection over the same config/dataset without
    the single-fit driver outputs."""
    tmp, train_path, holdout_path = avro_dataset
    config = {
        "task": "logistic",
        "input": {
            "format": "avro",
            "paths": [train_path],
            "feature_shards": {"global": ["features"]},
            "id_columns": ["userId"],
        },
        "validation": {"paths": [holdout_path]},
        "coordinates": {
            "fixed": {
                "type": "fixed_effect",
                "shard_name": "global",
                "optimizer": {"regularization": "l2",
                              "max_iterations": 20},
            },
        },
        "num_iterations": 1,
    }
    cfg_path = tmp / "sweep_only.json"
    cfg_path.write_text(json.dumps(config))
    summary = _run_cli(
        ["sweep", "--config", str(cfg_path),
         "--sweep", "lambda=0.1,1,10",
         "--sweep-policy", "parsimonious"],
        cwd=tmp,
    )
    sweep = summary["sweep"]
    assert sweep["policy"] == "parsimonious"
    assert len(sweep["configs"]) == 3
    assert all(c["metric"] is not None for c in sweep["configs"])


def test_parse_sweep_config_mapping_form_is_validated():
    """The JSON round-trip grid form goes through the same validators as
    the string grammar — negative/NaN/empty lists must not sneak in."""
    from photon_ml_tpu.cli.sweep import parse_sweep_config
    from photon_ml_tpu.sweep.grid import SweepSpecError

    with pytest.raises(SweepSpecError, match="negative"):
        parse_sweep_config({"grid": {"lambda": [-1.0, 2.0]}})
    with pytest.raises(SweepSpecError, match="empty grid"):
        parse_sweep_config({"grid": {"lambda": []}})
    with pytest.raises(SweepSpecError, match="not finite"):
        parse_sweep_config({"grid": {"lambda.fixed": [float("nan")]}})
    # valid values dedupe + sort descending like the string path
    parsed = parse_sweep_config({"grid": {"lambda": [1.0, 10.0, 1.0]}})
    assert parsed["grid"].default == (10.0, 1.0)


def test_train_run_refuses_checkpoint_or_mesh_with_sweep(tmp_path):
    """A checkpointed sweep would install GracefulStop (swallowing the
    scheduler's SIGTERM) and then never save anything — refuse upfront."""
    from photon_ml_tpu.cli.train import run

    base = {
        "task": "logistic",
        "input": {"format": "libsvm", "paths": "unused"},
        "coordinates": {"fixed": {"shard_name": "features"}},
        "sweep": {"grid": "lambda=1"},
    }
    with pytest.raises(ValueError, match="checkpointing is not supported"):
        run({**base, "checkpoint": {"dir": str(tmp_path / "ckpt")}})
    with pytest.raises(ValueError, match="mesh training is not supported"):
        run({**base, "mesh": {"batch": 2}})


def test_merge_sweep_flags_shared_helper():
    from photon_ml_tpu.cli.sweep import merge_sweep_flags

    assert merge_sweep_flags({}) is None
    merged = merge_sweep_flags(
        {"sweep": "lambda=1"}, metric="rmse", registry_dir="r/"
    )
    assert merged == {"grid": "lambda=1", "metric": "rmse",
                      "registry_dir": "r/"}
    merged = merge_sweep_flags(
        {"sweep": {"grid": "lambda=1", "policy": "best"}},
        grid=["lambda=2"], policy="parsimonious",
    )
    assert merged["grid"] == ["lambda=2"]
    assert merged["policy"] == "parsimonious"


# -- compile cache: one rule for every entry point ----------------------------


def test_compile_cache_env_places_it_and_code_sets_nothing(monkeypatch):
    import jax

    from photon_ml_tpu.utils import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/placed")
    assert enable_compile_cache() == "/somewhere/placed"
    assert jax.config.jax_compilation_cache_dir == before  # untouched


def test_compile_cache_default_is_fixed_inside_the_checkout(monkeypatch):
    import getpass
    import subprocess
    import tempfile

    import jax

    from photon_ml_tpu.utils import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # a path that never moves: no temp dir, user name or pid in it
    assert compile_cache.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
    assert not compile_cache.DEFAULT_DIR.startswith(tempfile.gettempdir())
    assert getpass.getuser() not in os.path.relpath(
        compile_cache.DEFAULT_DIR, repo)
    if os.path.isdir(os.path.join(repo, ".git")):
        ignored = subprocess.run(
            ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=repo)
        assert ignored.returncode == 0


def test_cli_dispatcher_enables_the_cache_for_every_subcommand(monkeypatch):
    from photon_ml_tpu import utils
    from photon_ml_tpu.cli.__main__ import main

    calls = []
    monkeypatch.setattr(utils, "enable_compile_cache", lambda: calls.append(1))
    assert main(["no-such-command"]) == 2
    assert calls == [1]
