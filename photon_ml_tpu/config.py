"""JSON config parsing: the parse-side of the typed config system.

Reference analog: photon-client's scopt flag parsers — GameParams
(estimators/GameParams.scala:252-492) with its per-coordinate mini-DSL
strings, and the legacy PhotonMLCmdLineParser. One JSON document replaces
both (SURVEY.md §5 "Config / flag system"): it names the input data, the
coordinates (updating-sequence order preserved from the JSON object order),
their optimizers, evaluators, and output. `game_config_to_json` inverts the
parse so saved model metadata can be re-parsed into a runnable config.
"""

from __future__ import annotations

import json
from typing import Mapping, Optional

from photon_ml_tpu.game.estimator import (
    FactoredRandomEffectConfig,
    FixedEffectConfig,
    GameConfig,
    RandomEffectConfig,
    _config_metadata,
)
from photon_ml_tpu.optim.factory import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)


_REG_TYPE_ALIASES = {
    "none": "none",
    "l1": "l1",
    "l2": "l2",
    "elastic_net": "elastic_net",
    "elasticnet": "elastic_net",
}


def parse_optimizer_config_string(spec: str) -> OptimizerConfig:
    """Parse the reference's comma-separated optimizer mini-DSL:
    ``maxIter,tolerance,regWeight,downSamplingRate,optimizerType,regType
    [,alpha]`` (GLMOptimizationConfiguration.parseAndBuildFromString:87-110;
    the trailing alpha extends it for elastic net)."""
    parts = [p.strip() for p in spec.split(",")]
    if len(parts) not in (6, 7):
        raise ValueError(
            f"bad optimizer config string '{spec}': expected "
            "'maxIter,tol,lambda,downSamplingRate,optimizerType,"
            "regularizationType[,alpha]'"
        )
    max_iter, tol, lam, ds_rate = parts[0], parts[1], parts[2], parts[3]
    try:
        opt_type = OptimizerType(parts[4].lower())
    except ValueError:
        raise ValueError(f"unknown optimizer type '{parts[4]}'") from None
    reg_name = parts[5].lower()
    if reg_name not in _REG_TYPE_ALIASES:
        raise ValueError(f"unknown regularization type '{parts[5]}'")
    reg_type = RegularizationType(_REG_TYPE_ALIASES[reg_name])
    if len(parts) == 7 and reg_type != RegularizationType.ELASTIC_NET:
        raise ValueError(
            f"alpha ('{parts[6]}') only applies to elastic_net, not "
            f"'{parts[5]}'"
        )
    alpha = float(parts[6]) if len(parts) == 7 else 1.0
    return OptimizerConfig(
        optimizer_type=opt_type,
        max_iterations=int(max_iter),
        tolerance=float(tol),
        regularization=RegularizationContext(reg_type, alpha=alpha),
        regularization_weight=float(lam),
        down_sampling_rate=float(ds_rate),
    )


def parse_optimizer_config(obj: Optional[Mapping | str]) -> OptimizerConfig:
    """Parse the JSON optimizer spec (GLMOptimizationConfiguration analog);
    a plain string routes through the reference's comma-separated DSL."""
    if isinstance(obj, str):
        return parse_optimizer_config_string(obj)
    obj = dict(obj or {})
    reg_type = RegularizationType(obj.pop("regularization", "none"))
    reg = RegularizationContext(reg_type, alpha=float(obj.pop("alpha", 1.0)))

    def parse_constraints(v):
        # [[index, lower|null, upper|null], ...] (constraintMap analog)
        out = []
        for triple in v:
            idx, lo, hi = triple
            out.append((
                int(idx),
                float("-inf") if lo is None else float(lo),
                float("inf") if hi is None else float(hi),
            ))
        return tuple(out) or None

    known = {
        "type": ("optimizer_type", lambda v: OptimizerType(v)),
        "max_iterations": ("max_iterations", int),
        "tolerance": ("tolerance", float),
        "regularization_weight": ("regularization_weight", float),
        "lbfgs_history": ("lbfgs_history", int),
        "down_sampling_rate": ("down_sampling_rate", float),
        "box_constraints": ("box_constraints", parse_constraints),
    }
    kwargs = {}
    for key, (field, conv) in known.items():
        if key in obj:
            kwargs[field] = conv(obj.pop(key))
    if obj:
        raise ValueError(f"unknown optimizer config keys: {sorted(obj)}")
    return OptimizerConfig(regularization=reg, **kwargs)


def parse_coordinate_config(obj: Mapping):
    obj = dict(obj)
    ctype = obj.pop("type", "fixed_effect")
    if ctype == "fixed_effect":
        out = FixedEffectConfig(
            shard_name=obj.pop("shard_name"),
            optimizer=parse_optimizer_config(obj.pop("optimizer", None)),
            normalization=obj.pop("normalization", "none"),
            intercept_index=obj.pop("intercept_index", None),
            down_sampling_seed=int(obj.pop("down_sampling_seed", 0)),
            layout=obj.pop("layout", "auto"),
        )
    elif ctype == "random_effect":
        ratio = obj.pop("features_to_samples_ratio", None)
        out = RandomEffectConfig(
            shard_name=obj.pop("shard_name"),
            id_name=obj.pop("id_name"),
            optimizer=parse_optimizer_config(obj.pop("optimizer", None)),
            active_rows_per_entity=obj.pop("active_rows_per_entity", None),
            min_rows_per_entity=int(obj.pop("min_rows_per_entity", 1)),
            features_to_samples_ratio=None if ratio is None else float(ratio),
            projector=obj.pop("projector", "index_map"),
            projected_dim=obj.pop("projected_dim", None),
            projection_seed=int(obj.pop("projection_seed", 0)),
            projection_intercept_index=obj.pop("projection_intercept_index", None),
            compute_variances=bool(obj.pop("compute_variances", False)),
        )
    elif ctype == "factored_random_effect":
        out = FactoredRandomEffectConfig(
            shard_name=obj.pop("shard_name"),
            id_name=obj.pop("id_name"),
            latent_dim=int(obj.pop("latent_dim")),
            mf_iterations=int(obj.pop("mf_iterations", 1)),
            re_optimizer=parse_optimizer_config(obj.pop("optimizer", None)),
            latent_optimizer=parse_optimizer_config(
                obj.pop("latent_optimizer", None)
            ),
            active_rows_per_entity=obj.pop("active_rows_per_entity", None),
            min_rows_per_entity=int(obj.pop("min_rows_per_entity", 1)),
            seed=int(obj.pop("seed", 0)),
            layout=obj.pop("layout", "auto"),
        )
    else:
        raise ValueError(f"unknown coordinate type '{ctype}'")
    if obj:  # typos must not silently train with defaults
        raise ValueError(
            f"unknown keys in {ctype} coordinate config: {sorted(obj)}"
        )
    return out


def parse_game_config(obj: Mapping | str) -> GameConfig:
    """Parse a GameConfig from a JSON document (dict or JSON string).

    JSON object order of "coordinates" IS the updating sequence."""
    if isinstance(obj, str):
        obj = json.loads(obj)
    coords = {
        name: parse_coordinate_config(c)
        for name, c in obj.get("coordinates", {}).items()
    }
    return GameConfig(
        task=obj["task"],
        coordinates=coords,
        num_iterations=int(obj.get("num_iterations", 1)),
        evaluators=tuple(obj.get("evaluators", ())),
    )


def game_config_to_json(config: GameConfig) -> dict:
    """Inverse of parse_game_config (round-trips through model metadata)."""
    return _config_metadata(config)
