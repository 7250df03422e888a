"""Per-file AST lint: rules L001-L012 (the former ``_Lint`` monolith of
tools/check.py, now emitting structured :class:`~tools.analysis.core.Finding`
objects so suppressions/baselines/JSON work uniformly).

Rule summary (rationale lives with each check):

- L001 unused module-scope import
- L002 bare ``except:``
- L003 mutable default argument
- L004 ``== None`` / ``!= None``
- L005 f-string without placeholders
- L006 wall-clock ``time.time()`` in library code (ANY spelling: the
  module-alias table now catches ``import time as t; t.time()`` — the
  blind spot the literal matcher had)
- L007 bare ``block_until_ready()`` statement in library code
- L008 non-atomic persistence outside the blessed atomic writers
- L009 bare ``print()`` in library code (CLI modules exempt)
- L010 device->host syncs in serving hot-path modules
- L011 bare ``jax.jit`` in hot-path library modules
- L012 placement-free ``device_put`` / any ``pmap`` in sharding modules

The L010/L011/L012 path lists below are ALSO the seeds of the
interprocedural hot-path pass (:mod:`tools.analysis.hotpath`): per-file
rules catch syncs written directly in a hot module, L013 catches the same
syncs one or more calls away.
"""

from __future__ import annotations

import ast
import os

from tools.analysis.core import Finding

# Files allowed to call np.savez/json.dump directly: the atomic-write
# primitives and the persistence layers built immediately on top of them.
L008_BLESSED = {
    os.path.join("photon_ml_tpu", "utils", "atomic.py"),
    os.path.join("photon_ml_tpu", "data", "model_store.py"),
    os.path.join("photon_ml_tpu", "game", "checkpoint.py"),
}

# Serving hot-path modules: every score request flows through these, so a
# stray device->host sync (jax.device_get, float() on an array, np.asarray
# on a jax array) is one more wait on the device PER REQUEST, and an
# unaccounted one. The one sanctioned crossing is telemetry.sync_fetch
# (device.py accounts it).
L010_HOT_PATH = {
    os.path.join("photon_ml_tpu", "serving", "engine.py"),
    os.path.join("photon_ml_tpu", "serving", "batcher.py"),
    # the asyncio front end: one blocked event loop stalls EVERY
    # connection, so a stray sync here is worse than in the threading
    # server
    os.path.join("photon_ml_tpu", "serving", "aio.py"),
}

# Hot-path library modules where every jit-compiled program must go
# through telemetry.xla.instrumented_jit (L011): a bare jax.jit hides its
# compile time, cost analysis, and recompile attribution from the
# executable registry (the benchmark's `compile_s`, `eager_compile_s`
# and `eager_programs_per_fit` split programs by that registry). Cold
# paths (one-off summaries, diagnostics) may stay on bare jax.jit via
# the allowlist.
L011_HOT_DIRS = (
    os.path.join("photon_ml_tpu", "parallel") + os.sep,
    os.path.join("photon_ml_tpu", "game") + os.sep,
    os.path.join("photon_ml_tpu", "ops") + os.sep,
    # the sweep runner batches G solver configs into single executables;
    # a bare jax.jit there hides exactly the multi-config warmup the
    # recompile-storm detector (telemetry/xla.py) needs multi_shape
    # attribution for
    os.path.join("photon_ml_tpu", "sweep") + os.sep,
    # the ingest pipeline's assembler writes every chunk through donated
    # device programs, and its uploader feeds every training batch — a
    # bare jax.jit there (and any sync reachable from it, L013) would
    # stall the decode/upload overlap unseen
    os.path.join("photon_ml_tpu", "ingest") + os.sep,
    # incremental warm-start retrains: the masked-lane re-solves and the
    # vocabulary-growth row expansion run on the training hot path — a
    # bare jax.jit there would hide how many solves a refresh dispatched
    os.path.join("photon_ml_tpu", "incremental") + os.sep,
    # the freshness conductor re-runs masked solves (and escalated full
    # fits) every cycle of a long-lived daemon: a bare jax.jit there
    # would hide recompiles that accumulate directly into the
    # event→served staleness the daemon reports
    os.path.join("photon_ml_tpu", "pipeline") + os.sep,
    # the quality layer runs inside every gated publish (gate stats on
    # the candidate model) and inside every score_rows chunk (drift
    # sketches): a bare jax.jit or stray device sync there would tax
    # every publish and every scored chunk unseen
    os.path.join("photon_ml_tpu", "quality") + os.sep,
)
L011_HOT_FILES = {
    os.path.join("photon_ml_tpu", "serving", "engine.py"),
    # the nearline updater re-solves entity rows on a live-serving
    # cadence: a bare jax.jit there would hide exactly the executables
    # whose recompiles land in a live request's latency
    os.path.join("photon_ml_tpu", "serving", "nearline.py"),
    # GLMix bootstrap: B resample lanes ride the sweep solver family on
    # the publish path (and the masked incremental variant); a bare
    # jax.jit there would hide the lane-composition executables that
    # decide what a bootstrap costs over a plain fit
    os.path.join("photon_ml_tpu", "diagnostics", "bootstrap.py"),
    os.path.join("photon_ml_tpu", "training.py"),
    # the executable profiler wraps EVERY instrumented dispatch: a bare
    # jax.jit inside it would both escape its own accounting and put an
    # uninstrumented program on the hottest path in the process; its
    # functions are also L013 jit-walk seeds, so a device sync it
    # introduces is caught on the real dispatch path
    os.path.join("photon_ml_tpu", "telemetry", "profile.py"),
    # the request tracer runs inside every serving request (batcher
    # dispatch, router fan-out, engine folds) — pure-stdlib by contract:
    # a device touch in trace bookkeeping would wedge the event loop
    os.path.join("photon_ml_tpu", "telemetry", "requests.py"),
}
L011_COLD_ALLOWLIST = {
    # gather_to_host: a once-per-summary replicating identity, not a
    # training/serving hot path
    os.path.join("photon_ml_tpu", "parallel", "multihost.py"),
}

# Sharding-discipline modules (L012): in these hot paths every
# `jax.device_put` must name an explicit placement (a Sharding/
# NamedSharding/device second argument or device=/sharding= keyword) — a
# bare `device_put(x)` lands on the default device and is then silently
# replicated/resharded at the next jit boundary, exactly the bug class
# the GSPMD scale-out removed. Bare `pmap` is rejected outright (the
# legacy per-device API; use NamedSharding + jit, parallel/sharding.py).
L012_HOT_DIRS = (
    os.path.join("photon_ml_tpu", "parallel") + os.sep,
)
L012_HOT_FILES = {
    os.path.join("photon_ml_tpu", "game", "coordinates.py"),
    os.path.join("photon_ml_tpu", "game", "streaming.py"),
    os.path.join("photon_ml_tpu", "game", "factored.py"),
    os.path.join("photon_ml_tpu", "serving", "engine.py"),
    os.path.join("photon_ml_tpu", "serving", "registry.py"),
}


def is_l011_hot(rel: str) -> bool:
    return (
        rel in L011_HOT_FILES or rel.startswith(L011_HOT_DIRS)
    ) and rel not in L011_COLD_ALLOWLIST


def is_l012_hot(rel: str) -> bool:
    return rel in L012_HOT_FILES or rel.startswith(L012_HOT_DIRS)


class LocalLint(ast.NodeVisitor):
    """One file's L001-L012 findings (``findings`` after construction)."""

    def __init__(self, path: str, tree: ast.Module, library: bool = False):
        self.path = path
        # library code (photon_ml_tpu/) additionally gets the fake-timing
        # rules L006/L007; scripts and tests may time however they like
        self.library = library
        self._l008_exempt = path in L008_BLESSED
        self._l010_hot = path in L010_HOT_PATH
        self._l011_hot = is_l011_hot(path)
        self._l012_hot = is_l012_hot(path)
        # CLI modules own stdout: bare print() is their user interface
        self._l009_exempt = path.startswith(
            os.path.join("photon_ml_tpu", "cli") + os.sep
        )
        self.findings: list[Finding] = []
        self.imported: dict[str, int] = {}  # name -> lineno (module scope)
        self.used: set[str] = set()
        # local name -> imported module (`import time as t` => t -> time):
        # the L006 blind-spot fix — wall-clock detection resolves through
        # this table instead of matching the literal `time.time()` spelling
        self._module_aliases: dict[str, str] = {}
        # names bound to the wall clock by `from time import time [as x]`
        self._time_aliases: set[str] = set()
        # names bound to the jit transform by `from jax import jit [as x]`
        self._jit_aliases: set[str] = set()
        self._collect(tree)

    def _report(self, node: ast.AST, code: str, msg: str) -> None:
        self.findings.append(
            Finding(path=self.path, line=node.lineno, code=code, message=msg)
        )

    def _collect(self, tree: ast.Module) -> None:
        for node in tree.body:  # module scope only: re-export surfaces stay
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = (a.asname or a.name).split(".")[0]
                    self.imported[name] = node.lineno
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__" or any(
                    a.name == "*" for a in node.names
                ):
                    continue
                for a in node.names:
                    self.imported[a.asname or a.name] = node.lineno
        # alias tables come from EVERY import in the file (function-local
        # `import time as t` must not dodge L006), unlike the module-scope
        # unused-import bookkeeping above
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname is not None:
                        self._module_aliases[a.asname] = a.name
                    else:
                        root = a.name.split(".")[0]
                        self._module_aliases[root] = root
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                for a in node.names:
                    if node.module == "time" and a.name == "time":
                        self._time_aliases.add(a.asname or a.name)
                    if node.module == "jax" and a.name == "jit":
                        self._jit_aliases.add(a.asname or a.name)
        self.visit(tree)

    def visit_Name(self, node: ast.Name) -> None:
        self.used.add(node.id)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        root = node
        while isinstance(root, ast.Attribute):
            root = root.value
        if isinstance(root, ast.Name):
            self.used.add(root.id)
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if node.type is None:
            self._report(node, "L002", "bare `except:` (catch something)")
        self.generic_visit(node)

    def _check_defaults(self, node) -> None:
        for d in list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]:
            if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                self._report(
                    d, "L003", "mutable default argument (use None sentinel)"
                )

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_defaults(node)
        if self._l011_hot:
            # `@jax.jit` decorators without a call are Attribute/Name
            # nodes, invisible to visit_Call
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call) and self._is_bare_jit(dec):
                    self._report_l011(dec)
        self.generic_visit(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        for op, comp in zip(node.ops, node.comparators):
            if isinstance(op, (ast.Eq, ast.NotEq)) and (
                isinstance(comp, ast.Constant) and comp.value is None
            ):
                self._report(node, "L004", "use `is None` / `is not None`")
        self.generic_visit(node)

    def _is_wall_clock_call(self, node: ast.Call) -> bool:
        # `<module-bound-to-time>.time()` (import time / import time as t)
        # or a bare `time()` bound by `from time import time [as x]`
        f = node.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr == "time"
            and isinstance(f.value, ast.Name)
            and self._module_aliases.get(f.value.id) == "time"
        ):
            return True
        return isinstance(f, ast.Name) and f.id in self._time_aliases

    def _is_non_atomic_persist_call(self, node: ast.Call) -> bool:
        # `<anything>.savez(...)` / `<anything>.savez_compressed(...)` and
        # `json.dump(...)` (json.dumps returns a string and is fine)
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in (
            "savez", "savez_compressed",
        ):
            return True
        return (
            isinstance(f, ast.Attribute)
            and f.attr == "dump"
            and isinstance(f.value, ast.Name)
            and f.value.id == "json"
        )

    def _is_bare_jit(self, node: ast.AST) -> bool:
        # `jax.jit(...)` / `@jax.jit` / from-imported `jit(...)`
        f = node.func if isinstance(node, ast.Call) else node
        if (
            isinstance(f, ast.Attribute)
            and f.attr == "jit"
            and isinstance(f.value, ast.Name)
            and f.value.id == "jax"
        ):
            return True
        return isinstance(f, ast.Name) and f.id in self._jit_aliases

    def _report_l011(self, node: ast.AST) -> None:
        self._report(
            node,
            "L011",
            "bare jax.jit in a hot-path library module — compiles escape "
            "the executable registry (no cost analysis, no recompile "
            "attribution); use telemetry.xla.instrumented_jit(fn, "
            "name=...), or add a cold path to L011_COLD_ALLOWLIST",
        )

    def _is_serving_sync_call(self, node: ast.Call) -> bool:
        # device->host crossings in serving hot paths: `jax.device_get`
        # (any spelling), `np.asarray`/`numpy.asarray` (a jax-array arg
        # forces a fetch), and `float(x)` on anything but a literal
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "device_get":
            return True
        if isinstance(f, ast.Name) and f.id == "device_get":
            return True
        if (
            isinstance(f, ast.Attribute)
            and f.attr == "asarray"
            and isinstance(f.value, ast.Name)
            and f.value.id in ("np", "numpy")
        ):
            return True
        return (
            isinstance(f, ast.Name)
            and f.id == "float"
            and not all(isinstance(a, ast.Constant) for a in node.args)
        )

    def _check_l012(self, node: ast.Call) -> None:
        f = node.func
        attr = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None
        )
        if attr == "pmap":
            self._report(
                node,
                "L012",
                "bare pmap in a sharding-discipline module — the legacy "
                "per-device API replicates state and bypasses GSPMD; use "
                "NamedSharding + jit (parallel/sharding.py)",
            )
        if attr == "device_put":
            explicit = len(node.args) >= 2 or any(
                k.arg in ("device", "sharding")
                for k in node.keywords
                if k.arg is not None
            )
            if not explicit:
                self._report(
                    node,
                    "L012",
                    "jax.device_put without an explicit Sharding — an "
                    "unsharded upload lands on the default device and "
                    "silently replicates/reshards at the next jit "
                    "boundary; pass a NamedSharding (parallel/sharding.py "
                    "placement helpers)",
                )

    def visit_Call(self, node: ast.Call) -> None:
        if self._l012_hot:
            self._check_l012(node)
        if self.library and self._is_wall_clock_call(node):
            self._report(
                node,
                "L006",
                "time.time() in library code — wall-clock steps corrupt "
                "phase durations; use time.monotonic() / utils.timing.Timer",
            )
        if (
            self.library
            and not self._l008_exempt
            and self._is_non_atomic_persist_call(node)
        ):
            self._report(
                node,
                "L008",
                "non-atomic persistence (np.savez/json.dump to a final "
                "path) in library code — a crash mid-write leaves a "
                "truncated file; route through utils.atomic / the "
                "model_store//checkpoint writers",
            )
        if self._l011_hot and self._is_bare_jit(node):
            self._report_l011(node)
        if self._l010_hot and self._is_serving_sync_call(node):
            self._report(
                node,
                "L010",
                "device->host sync in a serving hot-path module — every "
                "request waits on the device once more, unaccounted; fetch "
                "results through telemetry.sync_fetch only",
            )
        if (
            self.library
            and not self._l009_exempt
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
        ):
            self._report(
                node,
                "L009",
                "bare print() in library code — stdout belongs to CLI "
                "drivers; route output through logging or telemetry",
            )
        self.generic_visit(node)

    def visit_Expr(self, node: ast.Expr) -> None:
        # a bare `x.block_until_ready()` / `jax.block_until_ready(x)` /
        # from-imported `block_until_ready(x)` STATEMENT is a timing sync
        # that no counter sees; uses whose result feeds real code are fine
        call = node.value
        if (
            self.library
            and isinstance(call, ast.Call)
            and (
                (
                    isinstance(call.func, ast.Attribute)
                    and call.func.attr == "block_until_ready"
                )
                or (
                    isinstance(call.func, ast.Name)
                    and call.func.id == "block_until_ready"
                )
            )
        ):
            self._report(
                node,
                "L007",
                "bare block_until_ready() for timing is a host wait no "
                "counter sees; fetch via telemetry.sync_fetch instead",
            )
        self.generic_visit(node)

    def visit_JoinedStr(self, node: ast.JoinedStr) -> None:
        if not any(isinstance(v, ast.FormattedValue) for v in node.values):
            self._report(node, "L005", "f-string without placeholders")
        self.generic_visit(node)

    def visit_FormattedValue(self, node: ast.FormattedValue) -> None:
        # format specs parse as nested JoinedStrs of constants (e.g. ':.3g');
        # visiting them would false-positive L005 on every formatted field
        self.visit(node.value)

    def unused_imports(self, tree: ast.Module) -> None:
        exported = set()
        for node in tree.body:
            if (
                isinstance(node, ast.Assign)
                and any(
                    isinstance(t, ast.Name) and t.id == "__all__"
                    for t in node.targets
                )
                and isinstance(node.value, (ast.List, ast.Tuple))
            ):
                exported |= {
                    e.value
                    for e in node.value.elts
                    if isinstance(e, ast.Constant)
                }
        for name, lineno in sorted(self.imported.items(), key=lambda kv: kv[1]):
            if name not in self.used and name not in exported:
                self.findings.append(
                    Finding(
                        path=self.path,
                        line=lineno,
                        code="L001",
                        message=f"unused import `{name}`",
                    )
                )


def lint_file(rel: str, tree: ast.Module, library: bool) -> list[Finding]:
    lint = LocalLint(rel, tree, library=library)
    lint.unused_imports(tree)
    return lint.findings
