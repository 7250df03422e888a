"""CLI dispatcher: python -m photon_ml_tpu.cli {train|score|serve} ...

Reference analog: the photon-client spark-submit mains
(cli/game/training/Driver.scala:327, cli/game/scoring/Driver.scala:255)."""

import sys


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m photon_ml_tpu.cli {train|refresh|pipeline|sweep|score|serve|glm|index|report|profile} [options]")
        print("  train --config <json> [--output-dir <dir>] [--sweep lambda=...]   GAME training")
        print("  refresh --config <json> --warm-start <dir> [--delta <avro>...]  incremental warm-start retrain")
        print("  pipeline --config <json> --base <dir> --delta-dir <dir> --registry-dir <dir>  supervised freshness daemon")
        print("  sweep --config <json> --sweep lambda=...     multi-λ sweep + best-model selection")
        print("  score --model-dir <dir> --config <json> [--output <avro>]")
        print("  serve --registry-dir <dir> | --model-dir <dir>  online scoring server")
        print("  glm   --config <json> [--output-dir <dir>]   staged legacy GLM")
        print("  index --input <avro...> --output <dir>       feature index build")
        print("  report --trace <jsonl> [--telemetry <jsonl>] [--compare <json>]")
        print("  profile --profile-dir <dir> -- <command...>  profiler capture around any run")
        return 0 if argv else 2
    from photon_ml_tpu.utils import enable_compile_cache

    enable_compile_cache()  # every subcommand, before its first compile
    cmd, rest = argv[0], argv[1:]
    if cmd == "train":
        from photon_ml_tpu.cli.train import main as train_main

        return train_main(rest)
    if cmd == "refresh":
        from photon_ml_tpu.cli.refresh import main as refresh_main

        return refresh_main(rest)
    if cmd == "pipeline":
        from photon_ml_tpu.cli.pipeline import main as pipeline_main

        return pipeline_main(rest)
    if cmd == "sweep":
        from photon_ml_tpu.cli.sweep import main as sweep_main

        return sweep_main(rest)
    if cmd == "score":
        from photon_ml_tpu.cli.score import main as score_main

        return score_main(rest)
    if cmd == "serve":
        from photon_ml_tpu.cli.serve import main as serve_main

        return serve_main(rest)
    if cmd == "glm":
        from photon_ml_tpu.cli.glm import main as glm_main

        return glm_main(rest)
    if cmd == "index":
        from photon_ml_tpu.cli.index import main as index_main

        return index_main(rest)
    if cmd == "report":
        from photon_ml_tpu.cli.report import main as report_main

        return report_main(rest)
    if cmd == "profile":
        from photon_ml_tpu.cli.profile import main as profile_main

        return profile_main(rest)
    print(
        f"unknown command '{cmd}' (expected train|refresh|pipeline|sweep|score|serve|glm|index|report|profile)",
        file=sys.stderr,
    )
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
