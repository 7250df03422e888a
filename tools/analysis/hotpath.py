"""Pass 2 — L013 hot-path propagation.

The per-file L010/L011 path lists only guard code written *inside* the
listed modules; a helper one call away escaped them entirely. Here those
lists become seeds: hotness propagates transitively along the call graph,
and a ``float(x)`` sync or bare ``jax.jit`` hiding in ``utils/`` that is
reachable from ``ScoringEngine.score_rows`` or a solver loop is flagged
with the full call chain in the message.

Two propagation flavors:

- **sync hotness** from the serving request path (the L010 semantics:
  ``jax.device_get`` / ``np.asarray`` / ``float(non-constant)`` /
  ``block_until_ready`` make the request wait on the device). Seeds are
  the request-path entry points, NOT whole modules — ``ScoringEngine
  .load`` legitimately syncs at model-load time and must not poison the
  walk. The one sanctioned crossing (``telemetry.device.sync_fetch`` —
  its ``np.asarray`` IS the accounted fetch) is excluded by name.
- **jit hotness** from every function defined in the L011 hot scope (the
  training/serving compile surface): any transitively reachable function
  registering a bare ``jax.jit`` escapes the executable registry.
  ``telemetry.xla`` (the instrumented wrapper itself — the one place a
  real ``jax.jit`` must exist) and the L011 cold allowlist are excluded.

A configured seed that no longer resolves (e.g. a rename) is itself a
finding (W002): a silently empty seed list would mean the pass stops
guarding without anyone noticing.
"""

from __future__ import annotations

import ast

from tools.analysis import local
from tools.analysis.callgraph import FunctionInfo, PackageGraph
from tools.analysis.core import BAD_SEED, Finding

#: Serving request-path entry points (qualified names). Keep in sync with
#: photon_ml_tpu/serving/: a rename here surfaces as W002, not silence.
SYNC_SEEDS = (
    "photon_ml_tpu.serving.engine.ScoringEngine.score_rows",
    "photon_ml_tpu.serving.engine.ScoringEngine.warmup",
    "photon_ml_tpu.serving.batcher.MicroBatcher.submit",
    "photon_ml_tpu.serving.batcher.MicroBatcher._loop",
    "photon_ml_tpu.serving.batcher.ContinuousBatcher._collect",
    "photon_ml_tpu.serving.server.ScoringService.score_request",
    "photon_ml_tpu.serving.server.ScoringService.submit_rows",
    # the event-loop request path: a sync here stalls EVERY connection
    "photon_ml_tpu.serving.aio.AsyncScoringServer._route",
    "photon_ml_tpu.serving.aio.AsyncScoringServer._score",
    # fleet observability (ISSUE 13): the supervisor's status thread and
    # its telemetry tail parser are pure-filesystem monitors — a device
    # sync here would couple "is the fleet alive?" to a possibly-wedged
    # device, exactly when the operator needs the answer most
    "photon_ml_tpu.telemetry.progress.tail_heartbeat_fields",
    "photon_ml_tpu.parallel.fleet_status.FleetStatusWriter.snapshot",
    "photon_ml_tpu.parallel.fleet_status.FleetStatusWriter.write_once",
    # executable-level profiler (ISSUE 16): the dispatch sampler wraps
    # EVERY instrumented_jit call — its one honest device sync must stay
    # routed through the sanctioned telemetry.device.sync_fetch crossing
    # (a bare np.asarray/device_get here would re-open the fake-timing
    # trap on the hottest path in the process). A rename surfaces as
    # W002, not silence.
    "photon_ml_tpu.telemetry.profile.profile_dispatch",
    # request-scoped tracing (ISSUE 18): finish() runs on every request
    # (batcher dispatcher thread, router pool threads) and flight_dump()
    # on the SIGTERM drain path — a device sync inside trace bookkeeping
    # would wedge the event loop / block the drain exactly when the
    # process is being told to die
    "photon_ml_tpu.telemetry.requests.RequestTracer.finish",
    "photon_ml_tpu.telemetry.requests.RequestTracer.flight_dump",
)

#: The sanctioned device->host crossing: its body is the accounted fetch.
SANCTIONED_SYNC = {"photon_ml_tpu.telemetry.device.sync_fetch"}

#: Modules whose bare jax.jit is the *implementation* of the instrumented
#: wrapper — the one legitimate jit callsite in the package.
SANCTIONED_JIT_MODULES = {"photon_ml_tpu.telemetry.xla"}


def _short(qname: str) -> str:
    prefix = "photon_ml_tpu."
    return qname[len(prefix):] if qname.startswith(prefix) else qname


def short_chain(chain: tuple[str, ...]) -> tuple[str, ...]:
    return tuple(_short(q) for q in chain)


# ---------------------------------------------------------------------------
# Site detectors (shared with tests; operate on one function's own body)
# ---------------------------------------------------------------------------


def sync_sites(fn: FunctionInfo) -> list[tuple[ast.Call, str]]:
    """(call node, description) for every device->host sync in the body."""
    out = []
    for resolved, call in fn.calls:
        f = call.func
        if resolved == "jax.device_get" or (
            isinstance(f, ast.Attribute) and f.attr == "device_get"
        ) or (isinstance(f, ast.Name) and f.id == "device_get"):
            out.append((call, "jax.device_get"))
        elif resolved == "numpy.asarray" or (
            isinstance(f, ast.Attribute)
            and f.attr == "asarray"
            and isinstance(f.value, ast.Name)
            and f.value.id in ("np", "numpy")
        ):
            out.append((call, "np.asarray (forces a device fetch)"))
        elif isinstance(f, ast.Attribute) and f.attr == "block_until_ready":
            out.append((call, "block_until_ready"))
        elif (
            isinstance(f, ast.Name)
            and f.id == "float"
            and call.args
            and not all(isinstance(a, ast.Constant) for a in call.args)
        ):
            out.append((call, "float() on a non-constant"))
    return out


def jit_sites(fn: FunctionInfo) -> list[tuple[ast.AST, str]]:
    """(node, description) for every bare jax.jit registration."""
    out = []
    for resolved, call in fn.calls:
        if resolved == "jax.jit":
            out.append((call, "jax.jit(...)"))
    for dec in getattr(fn.node, "decorator_list", []):
        if not isinstance(dec, ast.Call):
            if (
                isinstance(dec, ast.Attribute)
                and dec.attr == "jit"
                and isinstance(dec.value, ast.Name)
                and dec.value.id == "jax"
            ):
                out.append((dec, "@jax.jit"))
    return out


# ---------------------------------------------------------------------------
# The pass
# ---------------------------------------------------------------------------


def run(
    graph: PackageGraph,
    sync_seeds: tuple[str, ...] = SYNC_SEEDS,
    require_seeds: bool = True,
) -> list[Finding]:
    findings: list[Finding] = []

    # -- sync propagation from the serving request path ---------------------
    present = [q for q in sync_seeds if q in graph.functions]
    if require_seeds:
        for missing in sorted(set(sync_seeds) - set(present)):
            findings.append(
                Finding(
                    path="tools/analysis/hotpath.py",
                    line=0,
                    code=BAD_SEED,
                    message=(
                        f"hot-path seed `{missing}` no longer resolves — "
                        f"the serving sync pass is not guarding it; update "
                        f"SYNC_SEEDS to the renamed entry point"
                    ),
                )
            )
    reach = graph.reachable(present)
    for qname in sorted(reach):
        fn = graph.functions[qname]
        if fn.rel in local.L010_HOT_PATH:
            continue  # already covered line-by-line by per-file L010
        if qname in SANCTIONED_SYNC or any(
            qname.startswith(s + ".") for s in SANCTIONED_SYNC
        ):
            continue
        chain = short_chain(graph.chain_to(reach, qname))
        for node, desc in sync_sites(fn):
            findings.append(
                Finding(
                    path=fn.rel,
                    line=node.lineno,
                    code="L013",
                    message=(
                        f"{desc} is reachable from serving hot path "
                        f"`{chain[0]}` — every request waits on the "
                        f"device once more; fetch through "
                        f"telemetry.sync_fetch or lift the sync out of the "
                        f"request path"
                    ),
                    chain=chain,
                    site=desc,
                )
            )

    # -- jit propagation from the L011 hot scope ----------------------------
    jit_seeds = sorted(
        q
        for q, fn in graph.functions.items()
        if local.is_l011_hot(fn.rel)
    )
    if require_seeds:
        # same guarantee as SYNC_SEEDS: renaming a hot file/dir must not
        # silently disarm both per-file L011 AND the transitive jit pass
        present_rels = {fn.rel for fn in graph.functions.values()}
        for f in sorted(local.L011_HOT_FILES):
            if f not in present_rels:
                findings.append(
                    Finding(
                        path="tools/analysis/hotpath.py",
                        line=0,
                        code=BAD_SEED,
                        message=(
                            f"L011 hot file `{f}` has no functions in the "
                            f"call graph — renamed? update L011_HOT_FILES "
                            f"or the jit pass stops guarding it"
                        ),
                    )
                )
        for d in local.L011_HOT_DIRS:
            if not any(rel.startswith(d) for rel in present_rels):
                findings.append(
                    Finding(
                        path="tools/analysis/hotpath.py",
                        line=0,
                        code=BAD_SEED,
                        message=(
                            f"L011 hot dir `{d}` matches no modules — "
                            f"renamed? update L011_HOT_DIRS or the jit "
                            f"pass stops guarding it"
                        ),
                    )
                )
    reach = graph.reachable(jit_seeds)
    for qname in sorted(reach):
        fn = graph.functions[qname]
        if local.is_l011_hot(fn.rel):
            continue  # per-file L011 already covers these
        if fn.rel in local.L011_COLD_ALLOWLIST:
            continue
        if fn.module in SANCTIONED_JIT_MODULES:
            continue
        chain = short_chain(graph.chain_to(reach, qname))
        for node, desc in jit_sites(fn):
            findings.append(
                Finding(
                    path=fn.rel,
                    line=node.lineno,
                    code="L013",
                    message=(
                        f"bare {desc} is reachable from hot path "
                        f"`{chain[0]}` — its compiles escape the "
                        f"executable registry (no cost analysis, no "
                        f"recompile attribution); use telemetry.xla"
                        f".instrumented_jit(fn, name=...)"
                    ),
                    chain=chain,
                    site=desc,
                )
            )
    return findings
