"""Fixture-driven coverage for the static-analysis gate (tools/analysis).

Every rule L001-L015 gets positive + negative snippets; the suppression,
baseline-diff, and ``--json`` surfaces are pinned; and the ISSUE 7
acceptance demos run the REAL ``tools/check.py`` CLI against miniature
package trees carrying the production seed names (``ScoringEngine
.score_rows``, ``MicroBatcher``), asserting the exit code flips and the
finding names the call chain / attribute.
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.analysis import core, driver, local
from tools.analysis.callgraph import build_graph, module_name_for

CHECK = os.path.join(REPO, "tools", "check.py")


def lint(code: str, rel: str = "photon_ml_tpu/mod.py", library=None):
    tree = ast.parse(textwrap.dedent(code))
    if library is None:
        library = rel.startswith("photon_ml_tpu/")
    return local.lint_file(rel, tree, library=library)


def codes(findings):
    return sorted(f.code for f in findings)


def write_tree(tmp_path, files: dict):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src), encoding="utf-8")
    return tmp_path


def analyze(tmp_path, files: dict, **kw):
    write_tree(tmp_path, files)
    kw.setdefault("require_seeds", False)
    return driver.analyze(str(tmp_path), **kw)


def graph_of(tmp_path, files: dict):
    write_tree(tmp_path, files)
    srcs = []
    for rel in files:
        if rel.startswith("photon_ml_tpu/") and rel.endswith(".py"):
            srcs.append(core.load_source(rel, str(tmp_path / rel)))
    return build_graph(srcs)


# ---------------------------------------------------------------------------
# Per-file rules L001-L012
# ---------------------------------------------------------------------------


class TestLocalRules:
    def test_l001_unused_import(self):
        assert codes(lint("import os\n")) == ["L001"]

    def test_l001_all_export_is_a_use(self):
        assert lint('import os\n__all__ = ["os"]\n') == []

    def test_l001_used_import_clean(self):
        assert lint("import os\nX = os.sep\n") == []

    def test_l002_bare_except(self):
        src = "try:\n    pass\nexcept:\n    pass\n"
        assert codes(lint(src)) == ["L002"]

    def test_l003_mutable_default(self):
        assert codes(lint("def f(a=[]):\n    return a\n")) == ["L003"]
        assert lint("def f(a=None):\n    return a\n") == []

    def test_l004_none_comparison(self):
        assert codes(lint("def f(a):\n    return a == None\n")) == ["L004"]
        assert lint("def f(a):\n    return a is None\n") == []

    def test_l005_fstring_no_placeholder(self):
        assert codes(lint('def f():\n    return f"static"\n')) == ["L005"]
        assert lint('def f(x):\n    return f"{x}"\n') == []

    def test_l006_wall_clock_spellings(self):
        assert codes(
            lint("import time\n\ndef f():\n    return time.time()\n")
        ) == ["L006"]
        assert codes(
            lint("from time import time\n\ndef f():\n    return time()\n")
        ) == ["L006"]

    def test_l006_module_alias_blind_spot_fixed(self):
        # the satellite regression: `import time as t; t.time()` escaped
        # the literal matcher before the module-alias table existed
        assert codes(
            lint("import time as t\n\ndef f():\n    return t.time()\n")
        ) == ["L006"]

    def test_l006_function_local_alias(self):
        src = "def f():\n    import time as clock\n    return clock.time()\n"
        assert codes(lint(src)) == ["L006"]

    def test_l006_monotonic_clean(self):
        assert lint(
            "import time\n\ndef f():\n    return time.monotonic()\n"
        ) == []

    def test_l006_not_in_benches(self):
        src = "import time\n\ndef f():\n    return time.time()\n"
        assert lint(src, rel="chip_smoke.py", library=False) == []

    def test_l007_bare_block_until_ready(self):
        src = "def f(x):\n    x.block_until_ready()\n"
        assert codes(lint(src)) == ["L007"]

    def test_l007_used_result_clean(self):
        assert lint("def f(x):\n    return x.block_until_ready()\n") == []

    def test_l008_non_atomic_persist(self):
        src = "import json\n\ndef f(d, fh):\n    json.dump(d, fh)\n"
        assert codes(lint(src)) == ["L008"]
        src = "import numpy as np\n\ndef f(p, a):\n    np.savez(p, a=a)\n"
        assert codes(lint(src)) == ["L008"]

    def test_l008_blessed_writer_exempt(self):
        src = "import json\n\ndef f(d, fh):\n    json.dump(d, fh)\n"
        assert lint(src, rel="photon_ml_tpu/utils/atomic.py") == []

    def test_l009_print_in_library(self):
        assert codes(lint('def f():\n    print("x")\n')) == ["L009"]

    def test_l009_cli_exempt(self):
        assert lint(
            'def f():\n    print("x")\n', rel="photon_ml_tpu/cli/train.py"
        ) == []

    def test_l010_syncs_in_hot_path(self):
        rel = "photon_ml_tpu/serving/engine.py"
        assert codes(lint("def f(x):\n    return float(x)\n", rel)) == [
            "L010"
        ]
        assert codes(
            lint(
                "import numpy as np\n\ndef f(x):\n    return np.asarray(x)\n",
                rel,
            )
        ) == ["L010"]
        assert codes(
            lint(
                "import jax\n\ndef f(x):\n    return jax.device_get(x)\n",
                rel,
            )
        ) == ["L010"]

    def test_l010_constant_float_and_cold_module_clean(self):
        rel = "photon_ml_tpu/serving/engine.py"
        assert lint('def f():\n    return float("1.5")\n', rel) == []
        assert lint("def f(x):\n    return float(x)\n") == []

    def test_l011_bare_jit_spellings(self):
        rel = "photon_ml_tpu/game/util.py"
        assert codes(
            lint("import jax\n\ndef f(g):\n    return jax.jit(g)\n", rel)
        ) == ["L011"]
        assert codes(
            lint(
                "import jax\n\n@jax.jit\ndef f(x):\n    return x\n", rel
            )
        ) == ["L011"]
        assert codes(
            lint(
                "from jax import jit\n\ndef f(g):\n    return jit(g)\n", rel
            )
        ) == ["L011"]

    def test_l011_allowlist_and_instrumented_clean(self):
        src = "import jax\n\ndef f(g):\n    return jax.jit(g)\n"
        assert lint(src, rel="photon_ml_tpu/parallel/multihost.py") == []
        src = (
            "from photon_ml_tpu.telemetry.xla import instrumented_jit\n\n"
            'def f(g):\n    return instrumented_jit(g, name="f")\n'
        )
        assert lint(src, rel="photon_ml_tpu/game/util.py") == []

    def test_l012_device_put_and_pmap(self):
        rel = "photon_ml_tpu/parallel/x.py"
        assert codes(
            lint(
                "import jax\n\ndef f(x):\n    return jax.device_put(x)\n",
                rel,
            )
        ) == ["L012"]
        assert codes(
            lint("import jax\n\ndef f(g):\n    return jax.pmap(g)\n", rel)
        ) == ["L012"]

    def test_l012_explicit_placement_clean(self):
        rel = "photon_ml_tpu/parallel/x.py"
        assert lint(
            "import jax\n\ndef f(x, s):\n    return jax.device_put(x, s)\n",
            rel,
        ) == []
        assert lint(
            "import jax\n\n"
            "def f(x, s):\n    return jax.device_put(x, device=s)\n",
            rel,
        ) == []


# ---------------------------------------------------------------------------
# Single-parse syntax phase
# ---------------------------------------------------------------------------


class TestSyntaxPhase:
    def test_syntax_error_is_a_finding_and_rest_still_runs(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/bad.py": "def broken(:\n    pass\n",
                "photon_ml_tpu/good.py": "import os\n",
            },
        )
        got = {(f.path, f.code) for f in res.findings}
        assert ("photon_ml_tpu/bad.py", "SYNTAX") in got
        # the other file was linted from the same single parse
        assert ("photon_ml_tpu/good.py", "L001") in got


# ---------------------------------------------------------------------------
# Suppressions + baseline
# ---------------------------------------------------------------------------


class TestSuppressions:
    def test_noqa_suppresses_exact_line_and_code(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/m.py": (
                    'def f():\n    print("x")  # photon: noqa[L009]\n'
                ),
            },
        )
        assert res.findings == []

    def test_wrong_code_does_not_suppress(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/m.py": (
                    'def f():\n    print("x")  # photon: noqa[L008]\n'
                ),
            },
        )
        assert codes(res.findings) == ["L009", "W001"]

    def test_unused_suppression_warns(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/m.py": (
                    "def f():\n    return 1  # photon: noqa[L009]\n"
                ),
            },
        )
        assert codes(res.findings) == ["W001"]
        assert "unused suppression" in res.findings[0].message

    def test_noqa_inside_string_literal_is_inert(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/m.py": (
                    'SNIPPET = "x = 1  # photon: noqa[L009]"\n'
                ),
            },
        )
        assert res.findings == []  # neither suppresses nor warns W001

    def test_multi_code_suppression(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/serving/__init__.py": "",
                "photon_ml_tpu/serving/engine.py": (
                    "def f(x):\n"
                    "    return float(x)  # photon: noqa[L010,L013]\n"
                ),
            },
        )
        # L010 used; L013 never fires on a per-file-covered module -> W001
        assert codes(res.findings) == ["W001"]


class TestBaseline:
    FILES = {
        "photon_ml_tpu/__init__.py": "",
        "photon_ml_tpu/m.py": 'def f():\n    print("x")\n',
    }

    def test_grandfathered_finding_passes(self, tmp_path):
        first = analyze(tmp_path, self.FILES)
        assert codes(first.findings) == ["L009"]
        baseline = {f.key() for f in first.findings}
        again = driver.analyze(
            str(tmp_path), baseline=baseline, require_seeds=False
        )
        assert again.findings == [] and len(again.grandfathered) == 1

    def test_new_finding_still_fails(self, tmp_path):
        first = analyze(tmp_path, self.FILES)
        baseline = {f.key() for f in first.findings}
        write_tree(
            tmp_path,
            {"photon_ml_tpu/m2.py": "import os\n"},
        )
        res = driver.analyze(
            str(tmp_path), baseline=baseline, require_seeds=False
        )
        assert codes(res.findings) == ["L001"]

    def test_stale_baseline_reported(self, tmp_path):
        write_tree(tmp_path, {"photon_ml_tpu/__init__.py": ""})
        baseline = {("photon_ml_tpu/gone.py", "L009", "whatever")}
        res = driver.analyze(
            str(tmp_path), baseline=baseline, require_seeds=False
        )
        assert res.findings == []
        assert res.stale_baseline == [
            ("photon_ml_tpu/gone.py", "L009", "whatever")
        ]

    def test_second_occurrence_of_baselined_rule_still_fails(self, tmp_path):
        # multiset semantics: one grandfathered print() must NOT
        # green-light a second, new print() in the same file — per-file
        # rules have constant messages, so set semantics would
        # (code-review regression)
        first = analyze(tmp_path, self.FILES)
        baseline = {f.key(): 1 for f in first.findings}
        write_tree(
            tmp_path,
            {
                "photon_ml_tpu/m.py": (
                    'def f():\n    print("x")\n\n\n'
                    'def g():\n    print("y")\n'
                ),
            },
        )
        res = driver.analyze(
            str(tmp_path), baseline=baseline, require_seeds=False
        )
        assert codes(res.findings) == ["L009"]
        assert len(res.grandfathered) == 1
        assert res.stale_baseline == []

    def test_baseline_survives_line_drift(self, tmp_path):
        # L015 messages embed write line numbers; Finding.key() normalizes
        # digits so pure line drift cannot resurrect a grandfathered
        # finding (code-review regression)
        files = _batcher_tree(
            "self._pending_rows -= 1", "self._pending_rows += 1"
        )
        first = analyze(tmp_path, files)
        assert codes(first.findings) == ["L015"]
        baseline = {f.key() for f in first.findings}
        mod = tmp_path / "photon_ml_tpu" / "serving" / "batcher.py"
        mod.write_text(
            "# a new leading comment shifts every line\n" + mod.read_text()
        )
        res = driver.analyze(
            str(tmp_path), baseline=baseline, require_seeds=False
        )
        assert res.findings == []
        assert len(res.grandfathered) == 1
        assert res.stale_baseline == []

    def test_write_baseline_keeps_grandfathered_entries(self, tmp_path):
        # refreshing a baseline WITH --baseline on the command line must
        # not drop previously-accepted findings (code-review regression)
        write_tree(tmp_path, self.FILES)
        b1, b2 = tmp_path / "a1.json", tmp_path / "a2.json"
        subprocess.run(
            [sys.executable, CHECK, "--root", str(tmp_path),
             "--write-baseline", str(b1)],
            capture_output=True, text=True, timeout=120, check=True,
        )
        subprocess.run(
            [sys.executable, CHECK, "--root", str(tmp_path),
             "--baseline", str(b1), "--write-baseline", str(b2)],
            capture_output=True, text=True, timeout=120, check=True,
        )
        assert {k[1] for k in core.load_baseline(str(b2))} == {"L009"}

    def test_baseline_file_round_trip(self, tmp_path):
        write_tree(tmp_path, self.FILES)
        base_path = tmp_path / "accepted.json"
        proc = subprocess.run(
            [sys.executable, CHECK, "--root", str(tmp_path),
             "--write-baseline", str(base_path)],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        loaded = core.load_baseline(str(base_path))
        assert {k[1] for k in loaded} == {"L009"}
        proc = subprocess.run(
            [sys.executable, CHECK, "--root", str(tmp_path),
             "--baseline", str(base_path), "--no-external"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# Call graph (pass 1)
# ---------------------------------------------------------------------------


class TestCallGraph:
    def test_module_names(self):
        assert module_name_for("photon_ml_tpu/serving/engine.py") == (
            "photon_ml_tpu.serving.engine", False,
        )
        assert module_name_for("photon_ml_tpu/serving/__init__.py") == (
            "photon_ml_tpu.serving", True,
        )

    def test_reexport_self_method_and_nested_resolution(self, tmp_path):
        g = graph_of(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/impl.py": (
                    "def real(x):\n    return x\n"
                ),
                "photon_ml_tpu/api.py": "from photon_ml_tpu.impl import real\n",
                "photon_ml_tpu/user.py": (
                    "from photon_ml_tpu import api\n\n"
                    "class C:\n"
                    "    def a(self):\n"
                    "        return self.b()\n\n"
                    "    def b(self):\n"
                    "        return api.real(1)\n\n"
                    "def outer():\n"
                    "    def inner():\n"
                    "        return 2\n"
                    "    return inner()\n"
                ),
            },
        )
        a = g.functions["photon_ml_tpu.user.C.a"]
        assert [t for t, _ in g.callees(a.qname)] == [
            "photon_ml_tpu.user.C.b"
        ]
        b_edges = [t for t, _ in g.callees("photon_ml_tpu.user.C.b")]
        assert b_edges == ["photon_ml_tpu.impl.real"]  # through the re-export
        outer_edges = [t for t, _ in g.callees("photon_ml_tpu.user.outer")]
        assert "photon_ml_tpu.user.outer.inner" in outer_edges

    def test_external_names_resolve_dotted(self, tmp_path):
        g = graph_of(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/m.py": (
                    "import time as t\n\n"
                    "def f():\n    return t.monotonic()\n"
                ),
            },
        )
        fn = g.functions["photon_ml_tpu.m.f"]
        assert fn.calls[0][0] == "time.monotonic"


# ---------------------------------------------------------------------------
# L013 hot-path propagation (pass 2)
# ---------------------------------------------------------------------------

_SYNC_TREE = {
    "photon_ml_tpu/__init__.py": "",
    "photon_ml_tpu/serving/__init__.py": "",
    "photon_ml_tpu/serving/engine.py": (
        "from photon_ml_tpu.utils.convert import as_scalar\n\n\n"
        "class ScoringEngine:\n"
        "    def score_rows(self, rows):\n"
        "        return as_scalar(rows)\n"
    ),
    "photon_ml_tpu/utils/__init__.py": "",
    "photon_ml_tpu/utils/convert.py": (
        "def as_scalar(x):\n    return float(x)\n"
    ),
}


class TestHotPathL013:
    def test_transitive_sync_flagged_with_chain(self, tmp_path):
        res = analyze(tmp_path, _SYNC_TREE)
        assert codes(res.findings) == ["L013"]
        f = res.findings[0]
        assert f.path == "photon_ml_tpu/utils/convert.py"
        assert f.chain == (
            "serving.engine.ScoringEngine.score_rows",
            "utils.convert.as_scalar",
        )
        assert "float() on a non-constant" in f.message

    def test_two_hop_chain(self, tmp_path):
        files = dict(_SYNC_TREE)
        files["photon_ml_tpu/utils/convert.py"] = (
            "def as_scalar(x):\n    return _inner(x)\n\n\n"
            "def _inner(x):\n    return float(x)\n"
        )
        res = analyze(tmp_path, files)
        assert codes(res.findings) == ["L013"]
        assert res.findings[0].chain == (
            "serving.engine.ScoringEngine.score_rows",
            "utils.convert.as_scalar",
            "utils.convert._inner",
        )

    def test_sanctioned_sync_fetch_not_flagged(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/serving/__init__.py": "",
                "photon_ml_tpu/serving/engine.py": (
                    "from photon_ml_tpu.telemetry.device import sync_fetch\n"
                    "\n\n"
                    "class ScoringEngine:\n"
                    "    def score_rows(self, rows):\n"
                    "        return sync_fetch(rows)\n"
                ),
                "photon_ml_tpu/telemetry/__init__.py": "",
                "photon_ml_tpu/telemetry/device.py": (
                    "import numpy as np\n\n\n"
                    "def sync_fetch(x, label=None):\n"
                    "    return np.asarray(x)\n"
                ),
            },
        )
        assert res.findings == []

    def test_unreachable_sync_not_flagged(self, tmp_path):
        files = dict(_SYNC_TREE)
        files["photon_ml_tpu/serving/engine.py"] = (
            "class ScoringEngine:\n"
            "    def score_rows(self, rows):\n"
            "        return rows\n"
        )
        res = analyze(tmp_path, files)
        assert res.findings == []

    def test_transitive_bare_jit_flagged(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/game/__init__.py": "",
                "photon_ml_tpu/game/solver.py": (
                    "from photon_ml_tpu.utils.compile import make_fast\n\n\n"
                    "def solve(f):\n    return make_fast(f)\n"
                ),
                "photon_ml_tpu/utils/__init__.py": "",
                "photon_ml_tpu/utils/compile.py": (
                    "import jax\n\n\n"
                    "def make_fast(f):\n    return jax.jit(f)\n"
                ),
            },
        )
        assert codes(res.findings) == ["L013"]
        f = res.findings[0]
        assert f.path == "photon_ml_tpu/utils/compile.py"
        assert f.chain == (
            "game.solver.solve", "utils.compile.make_fast",
        )
        assert "instrumented_jit" in f.message

    def test_missing_seed_is_w002(self, tmp_path):
        write_tree(tmp_path, {"photon_ml_tpu/__init__.py": ""})
        res = driver.analyze(str(tmp_path), require_seeds=True)
        assert "W002" in codes(res.findings)
        assert any("SYNC_SEEDS" in f.message for f in res.findings)
        # the jit scope gets the same rename guard as the sync seeds
        assert any("L011 hot file" in f.message for f in res.findings)
        assert any("L011 hot dir" in f.message for f in res.findings)


# ---------------------------------------------------------------------------
# L014 jit-purity (pass 3)
# ---------------------------------------------------------------------------


class TestJitPurityL014:
    def test_wall_clock_through_chain(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/solver.py": (
                    "import time\n\n"
                    "import jax\n\n\n"
                    "def _scale(x):\n"
                    "    return x * time.monotonic()\n\n\n"
                    "def build():\n"
                    "    def run(x):\n"
                    "        return _scale(x) + 1\n"
                    "    return jax.jit(run)\n"
                ),
            },
        )
        assert codes(res.findings) == ["L014"]
        f = res.findings[0]
        assert f.path == "photon_ml_tpu/solver.py"
        assert "time.monotonic" in f.message
        assert f.chain == ("solver.build.run", "solver._scale")

    def test_telemetry_counter_in_while_loop_body(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": (
                    "from photon_ml_tpu.telemetry.metrics import counter\n"
                ),
                "photon_ml_tpu/telemetry/__init__.py": "",
                "photon_ml_tpu/telemetry/metrics.py": (
                    "def counter(name):\n    return name\n"
                ),
                "photon_ml_tpu/loop.py": (
                    "from jax import lax\n\n"
                    "from photon_ml_tpu.telemetry.metrics import counter\n"
                    "\n\n"
                    "def solve(x):\n"
                    "    def body(s):\n"
                    '        counter("iters")\n'
                    "        return s\n\n"
                    "    def cond(s):\n"
                    "        return s\n\n"
                    "    return lax.while_loop(cond, body, x)\n"
                ),
            },
        )
        assert codes(res.findings) == ["L014"]
        assert "records telemetry (counter)" in res.findings[0].message

    def test_global_mutation_and_decorator_form(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/telemetry/__init__.py": "",
                "photon_ml_tpu/telemetry/xla.py": (
                    "def instrumented_jit(fn=None, **kw):\n"
                    "    return fn\n"
                ),
                "photon_ml_tpu/m.py": (
                    "from photon_ml_tpu.telemetry.xla import "
                    "instrumented_jit\n\n"
                    "_CALLS = 0\n\n\n"
                    '@instrumented_jit(name="m")\n'
                    "def traced(x):\n"
                    "    global _CALLS\n"
                    "    _CALLS += 1\n"
                    "    return x\n"
                ),
            },
        )
        assert codes(res.findings) == ["L014"]
        assert "module global" in res.findings[0].message

    def test_vmap_wrapper_unwrapped(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/telemetry/__init__.py": "",
                "photon_ml_tpu/telemetry/xla.py": (
                    "def instrumented_jit(fn=None, **kw):\n"
                    "    return fn\n"
                ),
                "photon_ml_tpu/v.py": (
                    "import jax\n\n"
                    "from photon_ml_tpu.telemetry.xla import "
                    "instrumented_jit\n\n\n"
                    "def solve_one(x):\n"
                    '    print("solving")\n'
                    "    return x\n\n\n"
                    "def build():\n"
                    "    return instrumented_jit(\n"
                    '        jax.vmap(solve_one), name="v"\n'
                    "    )\n"
                ),
            },
        )
        # print inside the traced function: one L014; the local L009 for
        # bare print in library code also fires — both are correct
        assert codes(res.findings) == ["L009", "L014"]
        l014 = [f for f in res.findings if f.code == "L014"][0]
        assert "prints to stdout" in l014.message

    def test_pure_traced_function_clean(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/pure.py": (
                    "import jax\n\n\n"
                    "def build():\n"
                    "    def run(x):\n"
                    "        return x * 2\n"
                    "    return jax.jit(run)\n"
                ),
            },
        )
        # the bare jit is outside any hot dir, and run is pure
        assert res.findings == []


# ---------------------------------------------------------------------------
# L015 lock discipline (pass 4)
# ---------------------------------------------------------------------------


def _batcher_tree(write_stmt: str, public_stmt: str) -> dict:
    return {
        "photon_ml_tpu/__init__.py": "",
        "photon_ml_tpu/serving/__init__.py": "",
        "photon_ml_tpu/serving/batcher.py": (
            "import threading\n\n\n"
            "class MicroBatcher:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._pending_rows = 0\n"
            "        self._thread = None\n\n"
            "    def start(self):\n"
            "        self._thread = threading.Thread(target=self._loop)\n"
            "        self._thread.start()\n\n"
            "    def submit(self, rows):\n"
            f"        {public_stmt}\n\n"
            "    def _loop(self):\n"
            f"        {write_stmt}\n"
        ),
    }


class TestLockDisciplineL015:
    def test_unlocked_cross_thread_write_flagged(self, tmp_path):
        res = analyze(
            tmp_path,
            _batcher_tree(
                "self._pending_rows -= 1", "self._pending_rows += 1"
            ),
        )
        assert codes(res.findings) == ["L015"]
        f = res.findings[0]
        assert "`self._pending_rows`" in f.message
        assert "MicroBatcher" in f.message

    def test_locked_writes_clean(self, tmp_path):
        res = analyze(
            tmp_path,
            _batcher_tree(
                "with self._lock:\n            self._pending_rows -= 1",
                "with self._lock:\n            self._pending_rows += 1",
            ),
        )
        assert res.findings == []

    def test_condition_variable_counts_as_lock(self, tmp_path):
        res = analyze(
            tmp_path,
            _batcher_tree(
                "with self._cv:\n            self._pending_rows -= 1",
                "with self._cv:\n            self._pending_rows += 1",
            ),
        )
        assert res.findings == []

    def test_one_unlocked_side_still_flagged(self, tmp_path):
        res = analyze(
            tmp_path,
            _batcher_tree(
                "with self._lock:\n            self._pending_rows -= 1",
                "self._pending_rows += 1",
            ),
        )
        assert codes(res.findings) == ["L015"]

    def test_public_only_attr_not_flagged(self, tmp_path):
        # self._thread is written in start()/__init__ but never from the
        # thread side: not a cross-thread attribute
        res = analyze(
            tmp_path,
            _batcher_tree("pass", "self._pending_rows += 1"),
        )
        assert res.findings == []

    def test_tuple_and_subscript_writes_detected(self, tmp_path):
        res = analyze(
            tmp_path,
            _batcher_tree(
                "self._pending_rows, self._x = 0, 1",
                "self._pending_rows[0] = 1",
            ),
        )
        assert codes(res.findings) == ["L015"]
        assert "`self._pending_rows`" in res.findings[0].message

    def test_no_thread_spawn_no_findings(self, tmp_path):
        res = analyze(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/plain.py": (
                    "class Plain:\n"
                    "    def a(self):\n"
                    "        self._x = 1\n\n"
                    "    def _b(self):\n"
                    "        self._x = 2\n"
                ),
            },
        )
        assert res.findings == []


# ---------------------------------------------------------------------------
# Acceptance demos (ISSUE 7): the real CLI flips to exit 1 on the
# demonstration diffs and names the chain / the attribute
# ---------------------------------------------------------------------------


class TestAcceptanceDemos:
    def _run(self, root):
        proc = subprocess.run(
            [sys.executable, CHECK, "--root", str(root), "--json"],
            capture_output=True, text=True, timeout=120,
        )
        return proc, json.loads(proc.stdout)

    def test_sync_in_util_reachable_from_score_rows_fails_gate(
        self, tmp_path
    ):
        write_tree(tmp_path, _SYNC_TREE)
        proc, doc = self._run(tmp_path)
        assert proc.returncode == 1
        (finding,) = doc["findings"]
        assert finding["code"] == "L013"
        assert finding["path"] == "photon_ml_tpu/utils/convert.py"
        assert finding["chain"] == [
            "serving.engine.ScoringEngine.score_rows",
            "utils.convert.as_scalar",
        ]

    def test_unlocked_microbatcher_write_fails_gate(self, tmp_path):
        write_tree(
            tmp_path,
            _batcher_tree(
                "self._pending_rows -= 1", "self._pending_rows += 1"
            ),
        )
        proc, doc = self._run(tmp_path)
        assert proc.returncode == 1
        (finding,) = doc["findings"]
        assert finding["code"] == "L015"
        assert "_pending_rows" in finding["message"]

    def test_clean_tree_exits_zero_with_schema(self, tmp_path):
        write_tree(
            tmp_path,
            {
                "photon_ml_tpu/__init__.py": "",
                "photon_ml_tpu/ok.py": "def f(x):\n    return x\n",
            },
        )
        proc, doc = self._run(tmp_path)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert doc["version"] == 1
        assert doc["findings"] == []
        assert doc["counts"] == {}
        assert doc["files"] == 2
        assert doc["graph"]["modules"] == 2
        assert set(doc) >= {
            "version", "root", "files", "findings", "grandfathered",
            "stale_baseline", "counts", "graph",
        }


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))


# ---------------------------------------------------------------------------
# ISSUE 8: the sweep subsystem is inside the gate
# ---------------------------------------------------------------------------


_SWEEP_TREE = {
    "photon_ml_tpu/__init__.py": "",
    "photon_ml_tpu/telemetry/__init__.py": "",
    "photon_ml_tpu/telemetry/xla.py": (
        "def instrumented_jit(fn, name=None, multi_shape=False):\n"
        "    return fn\n"
    ),
    "photon_ml_tpu/sweep/__init__.py": "",
    # the sweep runner idiom: a closure factory returning
    # instrumented_jit(run) where run vmaps a per-config solve body —
    # with a wall-clock read planted in the traced inner loop
    "photon_ml_tpu/sweep/runner.py": (
        "import time\n\n"
        "import jax\n\n"
        "from photon_ml_tpu.telemetry.xla import instrumented_jit\n\n\n"
        "def _tick(w):\n"
        "    return w * time.time()\n\n\n"
        "def _sweep_solver():\n"
        "    def run(w0, l2s):\n"
        "        def one(w_g, l2_g):\n"
        "            return _tick(w_g) + l2_g\n"
        "        return jax.vmap(one)(w0, l2s)\n"
        "    return instrumented_jit(run, name='sweep_fe_solve',\n"
        "                            multi_shape=True)\n"
    ),
}


class TestSweepGateRegistration:
    def test_sweep_modules_are_l011_hot(self):
        assert local.is_l011_hot("photon_ml_tpu/sweep/runner.py")
        assert local.is_l011_hot("photon_ml_tpu/sweep/select.py")

    def test_bare_jit_in_sweep_runner_is_l011(self):
        src = (
            "import jax\n\n"
            "def solver(fn):\n"
            "    return jax.jit(fn)\n"
        )
        assert "L011" in codes(lint(src, rel="photon_ml_tpu/sweep/runner.py"))

    def test_l014_discovers_vmapped_sweep_solver_as_traced_root(
        self, tmp_path
    ):
        """The closure-factory + vmap idiom the real sweep runner uses
        must be resolvable: instrumented_jit(run) -> run -> one (the
        vmapped per-config body) -> helpers."""
        from tools.analysis import jitpurity

        g = graph_of(tmp_path, _SWEEP_TREE)
        roots = {r[0] for r in jitpurity.trace_roots(g)}
        assert "photon_ml_tpu.sweep.runner._sweep_solver.run" in roots

    def test_planted_wall_clock_in_sweep_inner_loop_fails_gate(
        self, tmp_path
    ):
        """ISSUE 8 satellite acceptance: a time.time() in the sweep inner
        loop fails the REAL CLI with the chain from the traced root."""
        write_tree(tmp_path, _SWEEP_TREE)
        proc = subprocess.run(
            [sys.executable, CHECK, "--root", str(tmp_path), "--json"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 1
        doc = json.loads(proc.stdout)
        l014 = [f for f in doc["findings"] if f["code"] == "L014"]
        assert l014, doc["findings"]
        (finding,) = l014
        assert finding["path"] == "photon_ml_tpu/sweep/runner.py"
        assert "wall clock" in finding["message"]
        assert finding["chain"] == [
            "sweep.runner._sweep_solver.run",
            "sweep.runner._sweep_solver.run.one",
            "sweep.runner._tick",
        ]

    def test_real_sweep_runner_solvers_are_traced_roots(self):
        """On the REAL tree, every sweep executable registers through
        instrumented_jit and is discovered by the purity pass."""
        from tools.analysis import jitpurity
        from tools.analysis.callgraph import build_graph
        from tools.analysis.core import load_source

        srcs = []
        pkg = os.path.join(REPO, "photon_ml_tpu", "sweep")
        for name in sorted(os.listdir(pkg)):
            if name.endswith(".py"):
                rel = os.path.join("photon_ml_tpu", "sweep", name)
                srcs.append(load_source(rel, os.path.join(REPO, rel)))
        # the xla shim so instrumented_jit resolves inside the mini-graph
        srcs.append(
            load_source(
                os.path.join("photon_ml_tpu", "telemetry", "xla.py"),
                os.path.join(REPO, "photon_ml_tpu", "telemetry", "xla.py"),
            )
        )
        g = build_graph(srcs)
        roots = {r[0] for r in jitpurity.trace_roots(g)}
        for expected in (
            "photon_ml_tpu.sweep.runner._fe_sweep_solver.run",
            "photon_ml_tpu.sweep.runner._re_sweep_solver.run",
            "photon_ml_tpu.sweep.select._sweep_evaluator.run",
        ):
            assert expected in roots, sorted(roots)


def _ingest_stream_tree(worker_stmt: str, public_stmt: str) -> dict:
    """A ChunkStream-shaped fixture: decode worker threads + a public
    iterator API sharing pipeline state — the exact class shape the new
    ingest subsystem introduces; L015 must cover it from day one."""
    return {
        "photon_ml_tpu/__init__.py": "",
        "photon_ml_tpu/ingest/__init__.py": "",
        "photon_ml_tpu/ingest/pipeline.py": (
            "import threading\n\n\n"
            "class ChunkStream:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._queue_depth = 0\n"
            "        self._threads = []\n\n"
            "    def start(self):\n"
            "        t = threading.Thread(target=self._decode_loop)\n"
            "        self._threads.append(t)\n"
            "        t.start()\n\n"
            "    def _decode_loop(self):\n"
            f"        {worker_stmt}\n\n"
            "    def __next__(self):\n"
            f"        {public_stmt}\n"
        ),
    }


class TestLockDisciplineIngestL015:
    def test_unlocked_decode_worker_attr_flagged(self, tmp_path):
        """An attribute written by both a decode worker thread and the
        ChunkStream public iterator without a lock is an L015 finding
        naming the attribute and both sides."""
        res = analyze(
            tmp_path,
            _ingest_stream_tree(
                "self._queue_depth += 1", "self._queue_depth -= 1"
            ),
        )
        assert codes(res.findings) == ["L015"]
        f = res.findings[0]
        assert "`self._queue_depth`" in f.message
        assert "ChunkStream" in f.message
        assert "_decode_loop" in f.message

    def test_locked_both_sides_clean(self, tmp_path):
        res = analyze(
            tmp_path,
            _ingest_stream_tree(
                "with self._lock:\n            self._queue_depth += 1",
                "with self._lock:\n            self._queue_depth -= 1",
            ),
        )
        assert res.findings == []

    def test_real_ingest_package_is_in_scope(self):
        """The shipped photon_ml_tpu/ingest/ package must be inside the
        L011 hot scope (which seeds the interprocedural jit pass) so its
        device programs stay accounted."""
        from tools.analysis import local

        rel = os.path.join("photon_ml_tpu", "ingest", "pipeline.py")
        assert local.is_l011_hot(rel)


# ---------------------------------------------------------------------------
# L016 fault-point test coverage (tools/analysis/faultcov.py)
# ---------------------------------------------------------------------------


class TestFaultCoverageL016:
    """Every registered fault point must be named by a test literal —
    an unarmed injection seam is untested recovery code wearing a
    coverage badge."""

    PKG = """
        from photon_ml_tpu import faults

        _FP = faults.register_point("pkg.seam.covered", write_path=True)
        _FP2 = faults.register_point("pkg.seam.orphan")
    """

    def _run(self, tmp_path, files):
        from tools.analysis import faultcov

        write_tree(tmp_path, files)
        srcs = [
            core.load_source(rel, str(tmp_path / rel)) for rel in files
        ]
        return faultcov.run(srcs)

    def test_uncovered_point_flagged_with_its_id(self, tmp_path):
        findings = self._run(tmp_path, {
            "photon_ml_tpu/mod.py": self.PKG,
            "tests/test_mod.py": """
                def test_covered():
                    assert "pkg.seam.covered" in CATALOG
            """,
        })
        assert codes(findings) == ["L016"]
        assert "pkg.seam.orphan" in findings[0].message
        assert findings[0].path == "photon_ml_tpu/mod.py"

    def test_coverage_via_json_plan_literal_counts(self, tmp_path):
        # a substring inside an env-transported JSON plan blob covers too
        findings = self._run(tmp_path, {
            "photon_ml_tpu/mod.py": self.PKG,
            "tests/test_mod.py": """
                PLAN = '{"rules": [{"point": "pkg.seam.covered"}]}'

                def test_orphan_armed():
                    arm('{"rules": [{"point": "pkg.seam.orphan"}]}')
            """,
        })
        assert findings == []

    def test_non_literal_registration_is_flagged(self, tmp_path):
        findings = self._run(tmp_path, {
            "photon_ml_tpu/mod.py": """
                from photon_ml_tpu import faults

                NAME = "dyn" + ".seam"
                _FP = faults.register_point(NAME)
            """,
            "tests/test_mod.py": "LIT = 'dyn.seam'\n",
        })
        assert codes(findings) == ["L016"]
        assert "non-literal" in findings[0].message

    def test_tree_without_tests_is_skipped(self, tmp_path):
        # reduced fixture trees carry no tests/ — the pass must not
        # flag every point as uncovered there
        findings = self._run(tmp_path, {
            "photon_ml_tpu/mod.py": self.PKG,
        })
        assert findings == []

    def test_driver_runs_l016_only_on_real_trees(self, tmp_path):
        # require_seeds=False (reduced fixture tree) skips the pass...
        res = analyze(tmp_path, {
            "photon_ml_tpu/__init__.py": "",
            "photon_ml_tpu/mod.py": self.PKG,
            "tests/test_mod.py": "LIT = 'pkg.seam.covered'\n",
        })
        assert "L016" not in codes(res.findings)

    def test_real_tree_catalog_satisfies_l016(self):
        """The shipped package's own registry passes: every registered
        point is named by at least one test literal (the EXPECTED_POINTS
        catalog in tests/test_faults.py keeps this true by construction)."""
        from tools.analysis import faultcov

        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(driver.__file__))))
        files = [
            core.load_source(os.path.relpath(p, root), p)
            for p in driver.source_files(root)
        ]
        assert faultcov.run(files) == []


# ---------------------------------------------------------------------------
# ISSUE 13: fleet observability joins the analysis scope
# ---------------------------------------------------------------------------


_FLEET_OBS_TREE = {
    "photon_ml_tpu/__init__.py": "",
    "photon_ml_tpu/telemetry/__init__.py": "",
    # the supervisor's tail parser with a PLANTED device sync: the status
    # thread must never touch a device, so the L013 walk seeded at
    # tail_heartbeat_fields has to flag it
    "photon_ml_tpu/telemetry/progress.py": (
        "import json\n\n"
        "import numpy as np\n\n\n"
        "def tail_heartbeat_fields(path, max_bytes=65536,\n"
        "                          expect_proc=None):\n"
        "    with open(path, 'rb') as fh:\n"
        "        tail = fh.read()\n"
        "    rec = json.loads(tail.splitlines()[-1])\n"
        "    rec['rows'] = np.asarray(rec['rows'])\n"
        "    return rec\n"
    ),
}


class TestFleetObservabilityGate:
    def test_status_seeds_are_registered(self):
        from tools.analysis import hotpath

        for seed in (
            "photon_ml_tpu.telemetry.progress.tail_heartbeat_fields",
            "photon_ml_tpu.parallel.fleet_status.FleetStatusWriter"
            ".snapshot",
            "photon_ml_tpu.parallel.fleet_status.FleetStatusWriter"
            ".write_once",
        ):
            assert seed in hotpath.SYNC_SEEDS

    def test_planted_sync_in_tail_parser_flagged(self, tmp_path):
        res = analyze(tmp_path, _FLEET_OBS_TREE)
        assert codes(res.findings) == ["L013"]
        f = res.findings[0]
        assert f.path == "photon_ml_tpu/telemetry/progress.py"
        assert "np.asarray" in f.message
        assert f.chain == ("telemetry.progress.tail_heartbeat_fields",)

    def test_planted_sync_fails_the_real_cli(self, tmp_path):
        write_tree(tmp_path, _FLEET_OBS_TREE)
        proc = subprocess.run(
            [sys.executable, CHECK, "--root", str(tmp_path), "--json"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 1
        doc = json.loads(proc.stdout)
        l013 = [f for f in doc["findings"] if f["code"] == "L013"]
        assert l013, doc["findings"]
        assert l013[0]["path"] == "photon_ml_tpu/telemetry/progress.py"

    def test_real_status_writer_passes_lock_discipline(self):
        """The REAL FleetStatusWriter (a thread-spawning class with
        supervisor-pushed shared state) carries no unlocked cross-thread
        writes (L015), and no sync reachable from its seeds (L013)."""
        from tools.analysis import hotpath, locks
        from tools.analysis.callgraph import build_graph

        rels = (
            os.path.join("photon_ml_tpu", "parallel", "fleet_status.py"),
            os.path.join("photon_ml_tpu", "parallel", "multihost.py"),
            os.path.join("photon_ml_tpu", "telemetry", "progress.py"),
            os.path.join("photon_ml_tpu", "telemetry", "identity.py"),
        )
        srcs = [core.load_source(rel, os.path.join(REPO, rel))
                for rel in rels]
        g = build_graph(srcs)
        assert (
            "photon_ml_tpu.parallel.fleet_status.FleetStatusWriter"
            in g.classes
        )
        assert locks.run(g) == []
        findings = hotpath.run(g, require_seeds=False)
        assert [f for f in findings if f.code == "L013"] == []


# ---------------------------------------------------------------------------
# ISSUE 16: the executable profiler's sampler joins the analysis scope
# ---------------------------------------------------------------------------


def _profiler_tree(fetch_stmt: str) -> dict:
    """A profile.py-shaped fixture: the dispatch sampler with its
    synchronizing fetch spelled ``fetch_stmt`` — bare np.asarray re-opens
    the fake-timing trap; routing through sync_fetch is sanctioned."""
    return {
        "photon_ml_tpu/__init__.py": "",
        "photon_ml_tpu/telemetry/__init__.py": "",
        "photon_ml_tpu/telemetry/device.py": (
            "import numpy as np\n\n\n"
            "def sync_fetch(x, label=None):\n"
            "    return np.asarray(x)\n"
        ),
        "photon_ml_tpu/telemetry/profile.py": (
            ("import numpy as np\n\n" if "np." in fetch_stmt else "")
            + ("from photon_ml_tpu.telemetry.device import "
               "sync_fetch\n\n\n" if "sync_fetch" in fetch_stmt else "")
            + "def profile_dispatch(rec, target, args, kwargs):\n"
            "    out = target(*args, **kwargs)\n"
            f"    {fetch_stmt}\n"
            "    return out\n"
        ),
    }


class TestProfilerGateRegistration:
    def test_sampler_seed_and_hot_file_are_registered(self):
        from tools.analysis import hotpath

        assert (
            "photon_ml_tpu.telemetry.profile.profile_dispatch"
            in hotpath.SYNC_SEEDS
        )
        rel = os.path.join("photon_ml_tpu", "telemetry", "profile.py")
        assert local.is_l011_hot(rel)

    def test_bare_asarray_in_sampler_fails_the_real_cli(self, tmp_path):
        """ISSUE 16 satellite acceptance: a bare np.asarray in the
        dispatch sampler — an unaccounted device sync on the hottest
        path in the process — flips the REAL CLI to exit 1."""
        write_tree(tmp_path, _profiler_tree("np.asarray(out)"))
        proc = subprocess.run(
            [sys.executable, CHECK, "--root", str(tmp_path), "--json"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 1
        doc = json.loads(proc.stdout)
        l013 = [f for f in doc["findings"] if f["code"] == "L013"]
        assert l013, doc["findings"]
        (finding,) = l013
        assert finding["path"] == "photon_ml_tpu/telemetry/profile.py"
        assert "np.asarray" in finding["message"]
        assert finding["chain"] == ["telemetry.profile.profile_dispatch"]

    def test_sanctioned_sync_fetch_route_passes(self, tmp_path):
        write_tree(
            tmp_path,
            _profiler_tree("sync_fetch(out, label=rec.name)"),
        )
        proc = subprocess.run(
            [sys.executable, CHECK, "--root", str(tmp_path), "--json"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert json.loads(proc.stdout)["findings"] == []
