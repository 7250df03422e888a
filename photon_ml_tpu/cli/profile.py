"""Profiler-capture driver: wrap any CLI run in ``jax.profiler.trace``.

    python -m photon_ml_tpu.cli profile --profile-dir prof/ -- \
        train --config train.json --trace-out run.trace.jsonl

Everything after ``--`` is a normal CLI invocation (train, score, glm,
serve, report, ...). The wrapped run executes inside a profiler capture:
``--profile-dir`` receives the xplane/TensorBoard artifacts (open with
TensorBoard's profile plugin or xprof). Every telemetry span mirrors
itself as a ``jax.profiler.TraceAnnotation`` named ``photon:<span>``
(``telemetry/trace.py``, always on), so the capture holds our span tree
(``fit > coordinate_descent > cd_iteration > coordinate:<name> > update``)
on the clock of the XLA executable timeline: which executable ran
inside which phase.

Degrades gracefully: a backend that cannot start the profiler logs a
warning and runs the wrapped command unprofiled (exit code is the wrapped
command's either way).
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional

EXIT_USAGE = 2


def main(argv: Optional[list] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # split at the first bare "--": left = profile flags, right = the
    # wrapped CLI invocation
    if "--" in argv:
        split = argv.index("--")
        own, wrapped = argv[:split], argv[split + 1:]
    else:
        own, wrapped = argv, []
    parser = argparse.ArgumentParser(
        prog="photon_ml_tpu.cli profile",
        description=__doc__.splitlines()[0],
    )
    parser.add_argument(
        "--profile-dir",
        required=True,
        help="directory for the xplane/TensorBoard profiler capture",
    )
    args = parser.parse_args(own)
    if not wrapped:
        parser.error(
            "nothing to profile: pass the wrapped command after `--`, "
            "e.g. `profile --profile-dir prof/ -- train --config t.json`"
        )

    import jax

    from photon_ml_tpu.cli.__main__ import main as cli_main

    started = False
    try:
        jax.profiler.start_trace(args.profile_dir)
        started = True
    except Exception as e:  # noqa: BLE001 — capture is best-effort
        print(
            f"warning: profiler capture unavailable ({e}); running "
            "unprofiled",
            file=sys.stderr,
        )
    try:
        rc = cli_main(wrapped)
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
                print(
                    f"profiler capture written to {args.profile_dir} "
                    "(open with TensorBoard's profile plugin)",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001
                print(
                    f"warning: profiler capture failed to finalize: {e}",
                    file=sys.stderr,
                )
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
