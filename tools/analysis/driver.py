"""Orchestration: file discovery, the single parse, every pass, then
suppressions and the baseline diff. ``tools/check.py`` is a thin CLI over
:func:`analyze`.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

from tools.analysis import dataflow, faultcov, hotpath, jitpurity, local, locks
from tools.analysis.callgraph import build_graph
from tools.analysis.core import (
    BAD_SEED,
    Finding,
    SourceFile,
    apply_suppressions,
    collect_suppressions,
    dedupe_chain_findings,
    load_source,
    split_baseline,
    syntax_findings,
)

TARGETS = ("photon_ml_tpu", "tests", "tools", "__graft_entry__.py")
PACKAGE_DIR = "photon_ml_tpu"


def source_files(root: str) -> list[str]:
    out = []
    for t in TARGETS:
        path = os.path.join(root, t)
        if os.path.isfile(path):
            out.append(path)
            continue
        if not os.path.isdir(path):
            continue  # --root trees (tests) may carry only the package
        for walk_root, _dirs, files in os.walk(path):
            out.extend(
                os.path.join(walk_root, f)
                for f in files
                if f.endswith(".py")
            )
    return sorted(out)


@dataclasses.dataclass
class Result:
    root: str
    files: list[SourceFile]
    findings: list[Finding]  # NEW findings: these fail the gate
    grandfathered: list[Finding]  # matched --baseline entries
    stale_baseline: list[tuple[str, str, str]]  # baseline keys gone stale
    # call-graph + dataflow coverage (tests assert the interprocedural
    # passes really ran over the whole package, not a silently empty
    # graph: modules/functions/classes, dataflow functions/taint edges,
    # lock-order graph size)
    graph_stats: dict = dataclasses.field(default_factory=dict)
    # --changed mode: the analyzed scope (changed files + call-graph
    # dependents), or None for a full-tree run
    changed_scope: Optional[list[str]] = None

    @property
    def ok(self) -> bool:
        return not self.findings

    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for f in self.findings:
            out[f.code] = out.get(f.code, 0) + 1
        return dict(sorted(out.items()))

    def to_json(self) -> dict:
        out = {
            "version": 1,
            "root": self.root,
            "files": len(self.files),
            "findings": [f.to_json() for f in self.findings],
            "grandfathered": [f.to_json() for f in self.grandfathered],
            "stale_baseline": [list(k) for k in self.stale_baseline],
            "counts": self.counts(),
            "graph": self.graph_stats,
        }
        if self.changed_scope is not None:
            out["changed_scope"] = self.changed_scope
        return out


def changed_scope(
    graph, files: list[SourceFile], changed: set[str]
) -> set[str]:
    """``changed`` rel paths + their transitive call-graph DEPENDENTS:
    every file holding a function that (transitively) calls into a
    changed file. A changed callee's behavior is visible in its callers,
    so a pre-commit run must re-judge them too; files neither changed
    nor depending on a change are out of scope."""
    # file-level reverse edges: callee rel -> {caller rels}
    rdeps: dict[str, set[str]] = {}
    for fn in graph.functions.values():
        for resolved, _call in fn.calls:
            target = graph.resolve_call_target(resolved)
            if target is not None:
                callee_rel = graph.functions[target].rel
                if callee_rel != fn.rel:
                    rdeps.setdefault(callee_rel, set()).add(fn.rel)
    scope = {sf.rel for sf in files if sf.rel in changed}
    frontier = list(scope)
    while frontier:
        rel = frontier.pop()
        for caller in rdeps.get(rel, ()):
            if caller not in scope:
                scope.add(caller)
                frontier.append(caller)
    return scope


def analyze(
    root: str,
    baseline: Optional[dict] = None,  # key -> count, or a set (count 1)
    require_seeds: bool = True,
    changed: Optional[set[str]] = None,
) -> Result:
    """Run the whole gate over ``root``. ``require_seeds=False`` relaxes
    the W002 seed check for reduced test trees that intentionally carry
    only a few modules.

    ``changed`` (rel paths) switches on the fast pre-commit scope: the
    whole tree is still PARSED and the interprocedural passes still run
    over the full graph (a partial graph would silently weaken them),
    but per-file lint runs only on the changed files + their call-graph
    dependents, and findings are filtered to that scope. Full-tree
    behavior (``changed=None``) is unchanged and remains what tier-1
    runs."""
    files = [
        load_source(os.path.relpath(p, root), p) for p in source_files(root)
    ]
    pkg_prefix = PACKAGE_DIR + os.sep
    package_files = [sf for sf in files if sf.rel.startswith(pkg_prefix)]
    graph = build_graph(package_files)
    scope: Optional[set[str]] = None
    if changed is not None:
        scope = changed_scope(graph, files, changed)

    findings = syntax_findings(files)
    for sf in files:
        if sf.tree is None:
            continue
        if os.path.basename(sf.rel) == "__init__.py":
            continue  # re-export surfaces import without using
        if scope is not None and sf.rel not in scope:
            continue  # --changed: out-of-scope files keep their lint
        findings.extend(
            local.lint_file(
                sf.rel, sf.tree, library=sf.rel.startswith(pkg_prefix)
            )
        )

    # interprocedural passes over the library package (incl. __init__
    # trees: re-export bindings are what resolution follows) — ALWAYS
    # the full graph, even under --changed
    findings.extend(hotpath.run(graph, require_seeds=require_seeds))
    findings.extend(jitpurity.run(graph))
    findings.extend(locks.run(graph))
    lock_stats: dict = {}
    findings.extend(locks.run_lock_order(graph, lock_stats))
    df_stats = dataflow.Stats()
    findings.extend(
        dataflow.run(graph, df_stats, require_seeds=require_seeds)
    )
    if require_seeds:
        # L016 fault-point coverage needs the real tests/ tree; reduced
        # fixture trees (require_seeds=False) legitimately carry neither
        findings.extend(faultcov.run(files))
    graph_stats = {
        "modules": len(graph.modules),
        "functions": len(graph.functions),
        "classes": len(graph.classes),
        "dataflow": {
            "functions": df_stats.functions,
            "taint_edges": df_stats.taint_edges,
            "jit_callables": df_stats.jit_callables,
            "donating_callables": df_stats.donating_callables,
        },
        "locks": lock_stats,
    }

    findings = dedupe_chain_findings(findings)
    if scope is not None:
        # W002 (a configured seed/sanitizer that no longer resolves) is
        # pass-config health, reported against tools/analysis/ paths that
        # are never in a package scope — scoping it out would let the
        # exact pre-commit workflow it guards land the disarming rename
        findings = [
            f for f in findings
            if f.path in scope or f.code == BAD_SEED
        ]

    suppressions = {}
    for sf in files:
        if scope is not None and sf.rel not in scope:
            continue  # out-of-scope W001s would be pre-commit noise
        per_file = collect_suppressions(sf)
        if per_file:
            suppressions[sf.rel] = per_file
    kept, unused_warnings = apply_suppressions(findings, suppressions)
    kept.extend(unused_warnings)
    kept.sort(key=lambda f: (f.path, f.line, f.code, f.message))

    if baseline:
        new, grandfathered, stale = split_baseline(kept, baseline)
    else:
        new, grandfathered, stale = kept, [], []
    return Result(
        root=root,
        files=files,
        findings=new,
        grandfathered=grandfathered,
        stale_baseline=stale,
        graph_stats=graph_stats,
        changed_scope=sorted(scope) if scope is not None else None,
    )
