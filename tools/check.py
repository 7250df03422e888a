#!/usr/bin/env python
"""Static-analysis gate: `python tools/check.py`.

Reference analog: the scalastyle + Apache RAT gates of the reference build
(scalastyle-config.xml, build-scripts/rat.gradle) — a zero-setup check that
every source file parses and passes lint before code lands.

The analysis itself lives in the tools/analysis package (see its module
docstrings for the pass-by-pass story):

  1. single parse of every .py under photon_ml_tpu/ tests/ tools/
     (syntax errors are findings of that one parse — no separate
     py_compile phase)
  2. per-file stdlib AST lint, rules L001-L012 (tools/analysis/local.py)
  3. whole-package interprocedural passes over the import-resolved call
     graph (tools/analysis/callgraph.py):
       L013  hot-path propagation — the L010/L011 path lists are seeds;
             syncs/bare jits reachable from ScoringEngine.score_rows or
             the solver loops are flagged WITH the call chain
       L014  jit-purity — functions traced by instrumented_jit/jax.jit/
             lax.while_loop/lax.scan must not touch host state (telemetry,
             logs, wall clock, files, module globals): trace-time effects
             run once and silently never again
       L015  lock discipline — thread-spawning classes (MicroBatcher,
             ModelRegistry, Heartbeat) must guard attributes written from
             both the thread target and public methods with
             `with self._lock/_cv:`
  4. interprocedural DATAFLOW over the same graph (tools/analysis/
     dataflow.py + locks.py): these track VALUES, not names —
       L017  donation safety — borrowed host memory (mmap'd np.load,
             np.frombuffer, staging-ring slots, views of parameters)
             must not reach a donate_argnums slot of instrumented_jit/
             jax.jit without a sanctioned laundering copy
       L018  lock-order cycles — `with self._lock:` acquisition orders
             (incl. calls into other lock-holding methods) must form an
             acyclic cross-class graph
       L019  unsanctioned host transfer — jitted-function results must
             not flow into float()/int()/np.asarray/.tolist()/json.dump/
             branch comparisons outside telemetry.device.sync_fetch
  5. ruff + mypy, IF installed (configs live in pyproject.toml)

Inline suppression: `# photon: noqa[L013]` on the reported line (stale
suppressions are themselves findings, W001). `--baseline accepted.json`
grandfathers existing findings so only NEW ones fail CI;
`--write-baseline` emits that file. `--json` prints the machine-readable
findings document (the schema tests/test_static_gate.py pins).
`--changed GIT_REF` is the fast pre-commit scope: only files touched vs
the ref (plus their call-graph dependents) are linted/reported, while
the interprocedural passes still see the whole graph.

Exit code 0 = clean (no new findings). Otherwise every finding prints as
`path:line: code message [via call -> chain]` and the run exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.analysis import core, driver  # noqa: E402 (path bootstrap above)


def changed_files(root: str, ref: str) -> set:
    """Repo-relative .py paths touched vs ``ref``: committed/staged/
    worktree diffs plus untracked files — everything a pre-commit run
    must re-judge."""
    out: set = set()
    for cmd in (
        ["git", "diff", "--name-only", ref, "--"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        proc = subprocess.run(
            cmd, cwd=root, capture_output=True, text=True
        )
        if proc.returncode != 0:
            raise SystemExit(
                f"--changed: `{' '.join(cmd)}` failed in {root}:\n"
                f"{proc.stderr.strip()}"
            )
        for line in proc.stdout.splitlines():
            line = line.strip()
            if line.endswith(".py"):
                out.add(line.replace("/", os.sep))
    return out


def run_external(quiet: bool) -> list[core.Finding]:
    errs = []
    for tool, args in (
        ("ruff", ["check", "photon_ml_tpu", "tests", "tools"]),
        ("mypy", ["photon_ml_tpu"]),
    ):
        exe = shutil.which(tool)
        if exe is None:
            if not quiet:
                print(
                    f"  - {tool}: not installed, skipped "
                    f"(stdlib gate still ran)"
                )
            continue
        proc = subprocess.run(
            [exe, *args], cwd=REPO, capture_output=True, text=True
        )
        if proc.returncode != 0:
            errs.append(
                core.Finding(
                    path=tool,
                    line=0,
                    code="EXT",
                    message=f"{tool} failed:\n{proc.stdout}\n{proc.stderr}",
                )
            )
        elif not quiet:
            print(f"  - {tool}: clean")
    return errs


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--json",
        action="store_true",
        help="print the findings document as JSON (stdout carries ONLY "
        "the JSON)",
    )
    ap.add_argument(
        "--baseline",
        metavar="PATH",
        help="accepted-findings JSON: matching findings are grandfathered "
        "and only NEW findings fail the gate",
    )
    ap.add_argument(
        "--write-baseline",
        metavar="PATH",
        help="write the current findings as a baseline file and exit 0",
    )
    ap.add_argument(
        "--root",
        default=REPO,
        help="tree to analyze (default: this repo; tests point it at "
        "fixture trees)",
    )
    ap.add_argument(
        "--no-external",
        action="store_true",
        help="skip ruff/mypy even when installed",
    )
    ap.add_argument(
        "--changed",
        metavar="GIT_REF",
        help="fast pre-commit scope: lint/report only files touched vs "
        "GIT_REF (plus their call-graph dependents); the whole tree is "
        "still parsed and the interprocedural passes still see the full "
        "graph. External tools are skipped (they have no changed-scope "
        "mode). Full-tree behavior without this flag is unchanged.",
    )
    args = ap.parse_args(argv)

    baseline = None
    if args.baseline:
        baseline = core.load_baseline(args.baseline)

    root = os.path.abspath(args.root)
    changed = None
    if args.changed and args.write_baseline:
        # a scope-filtered result would write a PARTIAL baseline,
        # silently dropping every out-of-scope accepted entry — the next
        # full-tree run would then fail on all of them
        ap.error("--write-baseline needs the full tree; drop --changed")
    if args.changed:
        changed = changed_files(root, args.changed)
        if not args.json:
            print(
                f"--changed {args.changed}: {len(changed)} touched "
                f"python file(s)"
            )
    # fixture trees are not this repo: their seed classes are whatever the
    # test planted, so the missing-seed config check (W002) stays repo-only
    result = driver.analyze(
        root, baseline=baseline, require_seeds=(root == REPO),
        changed=changed,
    )

    if args.write_baseline:
        # include currently-grandfathered findings: refreshing a baseline
        # with --baseline also on the command line must not silently drop
        # every previously-accepted entry
        accepted = result.findings + result.grandfathered
        doc = {
            "version": 1,
            "findings": [f.to_json() for f in accepted],
        }
        with open(args.write_baseline, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
        print(
            f"wrote {len(accepted)} finding(s) to {args.write_baseline}"
        )
        return 0

    if not args.json:
        print(f"checking {len(result.files)} files")

    external: list[core.Finding] = []
    if not args.no_external and root == REPO and changed is None:
        if not args.json:
            print("external tools:")
        external = run_external(quiet=args.json)
    result.findings.extend(external)

    if args.json:
        print(json.dumps(result.to_json(), indent=2, sort_keys=True))
        return 0 if result.ok else 1

    for f in result.findings:
        print(f.render())
    if result.grandfathered:
        print(
            f"({len(result.grandfathered)} baselined finding(s) "
            f"grandfathered)"
        )
    for key in result.stale_baseline:
        print(f"note: stale baseline entry (fixed — delete it): {key}")
    if result.findings:
        print(f"\n{len(result.findings)} finding(s)")
        return 1
    print("clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
