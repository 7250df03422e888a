"""What ``benchmark/`` reads out of the program BY NAME, held in tier-1.

The benchmark's drivers reach into the built coordinates (``_tiled``,
``.parts``, ``.stored``, ``last_tracker``, the identity of the estimator's
cached coordinates) and every file under ``benchmark/metrics/`` hands a
reader the name of a span, a counter, a Mosaic call or a module of the
program. ``benchmark/tests`` is not collected by tier-1 and most readers
return nothing, silently, for a name the program no longer has; so a rename
on the hot path would otherwise surface only in a chip run, as a missing
per-layer metric. Here each cell's own driver (``Driver(config, traffic,
seed, rows=<small>, force_tiled=True)``, the rehearsal of
``benchmark/tests/test_run_cpu.py``) runs its set-up and one more fit on
the CPU in Pallas interpret mode, and every metric file that names
something in the program gets one case per cell that reports it: the name
is looked up in what that run left behind. Nothing under ``benchmark/`` is
edited; no time is asserted."""

from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import math
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import program_trace, run
from benchmark.readers import (
    counter_delta,
    counter_ratio,
    history_seconds,
    kept_counter_delta,
    program_span_seconds,
    setup_span_seconds,
    setup_unattributed,
)
from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry.xla import InstrumentedFunction

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = {}
for _path in sorted(glob.glob(
        os.path.join(ROOT, "benchmark", "metrics", "*.json"))):
    with open(_path) as _f:
        METRICS[os.path.basename(_path)] = json.load(_f)
ROWS = 1024
SEED = 2147483653


#: readers whose parameters name a span, a counter, a module or a history
#: entry of the program; and those that do where ``pattern`` names a Mosaic
#: call (``^%<name>``) and not XLA's own custom-call target
NAMING_READERS = {
    "program_span_seconds", "counter_delta", "counter_ratio", "trace_module",
    "trace_module_roofline", "history_seconds", "kept_counter_delta",
    "setup_span_seconds", "setup_unattributed",
}
KERNEL_READERS = {"trace_kernel_roofline", "trace_kernel_seconds"}


def names_the_program(spec) -> bool:
    return spec["reader"] in NAMING_READERS or (
        spec["reader"] in KERNEL_READERS
        and spec["params"]["pattern"].startswith("^%"))


#: the files that hand their reader no name of the program's: the driver's
#: own clocks and spans, the allocator, XLA's custom-call target, whole
#: device planes
NAMES_NOTHING = {
    "build_coordinates_s.json", "device_idle_pct.json",
    "fe_kernels_roofline.json", "fit_roofline_mfu.json",
    "idle_unattributed_s_per_fit.json", "peak_hbm_gb.json", "setup_s.json",
    "train_rows_per_s.json",
    # XLA's own custom-call targets on a TPU (Cholesky and the triangular
    # solve's block inverse): tests/test_chip_compile.py finds them in the
    # described-v5e compile of the per-entity solver
    "re_factor_device_s_per_fit.json",
}


def cases(*readers):
    """(cell, metric file) for every file of ``readers`` that names
    something in the program, once per cell that reports the metric."""
    out = []
    for cell in CELLS:
        reported = set(run.cell_metrics(BENCH, cell, "per_layer"))
        for name, spec in METRICS.items():
            if (spec["reader"] in readers and names_the_program(spec)
                    and name[:-len(".json")] in reported):
                out.append(pytest.param(cell, name, id=f"{cell}-{name}"))
    return out


def _alternatives(pattern: str) -> list:
    """Every way through ``pattern``'s ``|`` s, each a pattern without one:
    ``a\\.(b|c)|d`` -> ``a\\.(?:b)``, ``a\\.(?:c)``, ``d``. A reader sums
    whatever matches, so a span lost from ONE branch would go unseen."""
    depth, cuts, groups = 0, [], []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == "\\":
            i += 1
        elif ch == "(":
            depth += 1
            if depth == 1:
                groups.append([i, None])
        elif ch == ")":
            depth -= 1
            if depth == 0:
                groups[-1][1] = i
        elif ch == "|" and depth == 0:
            cuts.append(i)
        i += 1
    if cuts:
        edges = [-1, *cuts, len(pattern)]
        return [alt for a, b in zip(edges, edges[1:])
                for alt in _alternatives(pattern[a + 1:b])]
    for start, end in groups:
        inner = _alternatives(pattern[start + 1:end])
        if len(inner) > 1:
            return [alt for one in inner for alt in _alternatives(
                f"{pattern[:start]}(?:{one}){pattern[end + 1:]}")]
    return [pattern]


def _pallas_names(jaxpr) -> set:
    """The ``name`` of every ``pallas_call`` equation in ``jaxpr`` and the
    jaxprs nested in it (what a Mosaic call's events are named after)."""
    names = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.add(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _pallas_names(sub)
    return names


@dataclasses.dataclass
class CellRun:
    """What one cell's rehearsal left behind, taken before the per-test
    telemetry reset: the readers' ``ctx`` (counter marks, the window's
    fits, the driver's shapes and spans, set-up's seconds), the program's
    span trees by root and every span it finished, the registry's
    executables, and the programs the window's fit dispatched."""

    driver: object
    ctx: dict
    roots: dict
    registry: set
    dispatched: dict  # executable name -> (InstrumentedFunction, args, kwargs)
    kernels: set  # Mosaic call names in the dispatched programs
    spans: list  # every finished span of the run (telemetry's own objects)


def _rehearse(cell: str) -> CellRun:
    workload = run.load_json("workloads", cell + ".json")
    config = run.load_json("configs", workload["config"] + ".json")
    traffic = run.load_json("traffic", workload["traffic"] + ".json")
    module = importlib.import_module("benchmark.drivers." + config["driver"])

    def counters():
        return dict(telemetry.snapshot()["counters"])

    telemetry.reset()
    t0 = time.perf_counter()
    driver = module.Driver(config, traffic, SEED, rows=ROWS, force_tiled=True)
    driver.setup()
    marks = {"setup_end": counters(), "window_start": counters()}
    # set-up as run.py counts it, this process's import standing for the
    # interpreter's and the package's start
    setup_s = time.perf_counter() - t0 + marks["setup_end"]["import.seconds"]
    dispatched = {}
    real = InstrumentedFunction.__call__

    def spy(self, *args, **kwargs):
        # shapes, not arrays: a donated buffer is gone after the call
        dispatched.setdefault(self.name, (self, *jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if isinstance(x, jax.Array) else x, (args, kwargs))))
        return real(self, *args, **kwargs)

    first = len(driver.fits)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(InstrumentedFunction, "__call__", spy)
        record = driver.fit()
    assert record["ok"], record
    marks["window_end"] = counters()
    kernels = set()
    for fn, args, kwargs in dispatched.values():
        kernels |= _pallas_names(
            jax.make_jaxpr(fn.__wrapped__)(*args, **kwargs).jaxpr)
    return CellRun(
        driver=driver,
        ctx={"counters": marks, "fits": driver.fits[first:],
             "shapes": driver.shapes(), "setup_s": setup_s,
             "spans": dict(driver.spans)},
        roots={root: program_span_seconds.process_roots(root)
               for root in ("build_coordinates", "coordinate_descent")},
        registry={r.name for r in telemetry.XLA_REGISTRY.executables()},
        dispatched=dispatched,
        kernels=kernels,
        spans=telemetry.finished_spans(),
    )


@pytest.fixture(scope="module")
def rehearsal():
    """cell -> its :class:`CellRun`, made on first use, once."""
    made = {}

    def get(cell: str) -> CellRun:
        if cell not in made:
            made[cell] = _rehearse(cell)
        return made[cell]

    return get


# -- one case per metric file and cell ----------------------------------------


def test_every_metric_file_is_guarded_or_names_nothing():
    """A metric file added later lands in one list or the other."""
    guarded = {name for name, spec in METRICS.items()
               if names_the_program(spec)}
    assert guarded.isdisjoint(NAMES_NOTHING)
    assert guarded | NAMES_NOTHING == set(METRICS)


@pytest.mark.parametrize("cell, metric", cases("program_span_seconds"))
def test_span_named_by_a_metric_is_opened_by_the_program(
        rehearsal, cell, metric):
    params = METRICS[metric]["params"]
    roots = rehearsal(cell).roots[params["root"]]
    assert roots, f"the program opened no root span {params['root']!r}"
    # set-up metrics read the process's FIRST root; per-fit ones every
    # traced fit, which the window's (last) fit stands for here
    roots = roots[:1] if params.get("source") == "process" else roots[-1:]
    for span in _alternatives(params["span"]):
        found = program_trace.select(roots, span, params.get("parent"))
        assert found, (
            f"no span {span!r} under {params.get('parent')!r} of "
            f"{params['root']!r}; it holds "
            f"{sorted({s.name for r in roots for s in r.walk()})}")
        assert all(s.dur >= 0 and math.isfinite(s.dur) for s in found)


@pytest.mark.parametrize("cell, metric", cases("counter_delta", "counter_ratio"))
def test_counter_named_by_a_metric_is_kept_by_the_program(
        rehearsal, cell, metric):
    spec = METRICS[metric]
    params = spec["params"]
    ctx = rehearsal(cell).ctx
    if spec["reader"] == "counter_ratio":
        named = [params["numerator"], params["denominator"]]
        mark = ctx["counters"][params["at"]]
        value = counter_ratio.read(ctx, **params)
        # never fewer slots than nonzeros
        assert value is not None and value >= 1.0
    else:
        named = params["counters"]
        mark = ctx["counters"][params["until"]]
        value = counter_delta.read(ctx, **params)
        if params["since"] == "window_start" and all(
                name.startswith("jit_compile") for name in named):
            assert value == 0  # a repeated fit compiles nothing
        else:
            assert value > 0
    for name in named:
        assert mark.get(name, 0) > 0, (name, sorted(mark))


@pytest.mark.parametrize(
    "cell, metric", cases("trace_kernel_roofline", "trace_kernel_seconds"))
def test_kernel_named_by_a_metric_is_called_by_the_fit(
        rehearsal, cell, metric):
    params = METRICS[metric]["params"]
    cell_run = rehearsal(cell)
    pattern = re.compile(params["pattern"])
    # an `XLA Ops` event of a Mosaic call is named %<name>[.<n>] = ...
    assert any(pattern.search("%" + name) for name in cell_run.kernels), (
        params["pattern"], sorted(cell_run.kernels))
    if "coordinate" in params:
        shape = cell_run.ctx["shapes"]["coordinates"].get(params["coordinate"])
        assert shape is not None and shape["T"] > 0, (
            params["coordinate"], cell_run.ctx["shapes"])
        counts = importlib.import_module(
            "benchmark.counts." + params["counts"])
        flops, nbytes = counts.per_call(shape)
        assert flops > 0 and nbytes > 0


@pytest.mark.parametrize(
    "cell, metric", cases("trace_module", "trace_module_roofline"))
def test_module_named_by_a_metric_is_a_registered_executable(
        rehearsal, cell, metric):
    params = METRICS[metric]["params"]
    cell_run = rehearsal(cell)
    assert cell_run.dispatched
    if params.get("unnamed"):
        # what the reader subtracts: every program the fit dispatched by
        # name is in the registry, and its module is called jit_<name>
        checked = cell_run.dispatched
    else:
        pattern = re.compile(params["pattern"])
        checked = {name: call for name, call in cell_run.dispatched.items()
                   if pattern.search(f"jit_{name}(0)")}
        assert checked, (params["pattern"], sorted(cell_run.dispatched))
    for name, (fn, args, kwargs) in checked.items():
        assert name in cell_run.registry, (name, sorted(cell_run.registry))
        text = fn.lower(*args, **kwargs).as_text()
        assert re.match(rf"module @jit_{re.escape(name)}\b", text), text[:80]


@pytest.mark.parametrize("cell, metric", cases("history_seconds"))
def test_history_read_by_a_metric_has_the_coordinate_and_its_seconds(
        rehearsal, cell, metric):
    params = METRICS[metric]["params"]
    ctx = rehearsal(cell).ctx
    steps = [s for f in ctx["fits"] for s in f["steps"]]
    if "coordinate" in params:
        steps = [s for s in steps if s["coordinate"] == params["coordinate"]]
    assert steps and all(
        math.isfinite(s["seconds"]) and s["seconds"] > 0 for s in steps)
    value = history_seconds.read(ctx, **params)
    assert value is not None and math.isfinite(value) and value > 0


# -- set-up seen from inside the program (PR 35) -------------------------------

#: declared at 0 by the compile hooks; the rehearsal's programs compile
#: under jax's 1 s cache-write threshold, so no load happens in it: the
#: next test shows the counter rising on a real hit
CACHE_LOAD = "jit_cache_load_seconds"


@pytest.mark.parametrize("cell, metric", cases("kept_counter_delta"))
def test_kept_counter_named_by_a_metric_is_kept_by_the_program(
        rehearsal, cell, metric):
    params = METRICS[metric]["params"]
    ctx = rehearsal(cell).ctx
    mark = ctx["counters"][params["until"]]
    for name in params["counters"]:
        assert name in mark, (name, sorted(mark))
        if name != CACHE_LOAD:
            assert mark[name] > 0, name
    value = kept_counter_delta.read(ctx, **params)
    assert value is not None and math.isfinite(value) and value >= 0


def test_a_persistent_cache_hit_raises_the_cache_load_counter(tmp_path):
    """A real hit: a fresh cache directory that keeps every program, the
    in-memory caches cleared, the same program compiled again."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    salt = time.perf_counter()

    def program(x):
        return jnp.tanh(x) * salt + jnp.cos(x)

    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        telemetry.reset()
        x = jnp.ones((4, 13))
        np.asarray(telemetry.instrumented_jit(program, name="cache_probe")(x))
        assert telemetry.snapshot()["counters"][CACHE_LOAD] == 0
        jax.clear_caches()
        np.asarray(telemetry.instrumented_jit(program, name="cache_probe")(x))
        counters = telemetry.snapshot()["counters"]
        assert counters["jit_cache_hits"] >= 1
        assert counters[CACHE_LOAD] > 0
        assert counters["xla.exec.cache_probe.cache_load_seconds"] > 0
        # the backend event holds the load on a hit
        assert counters["jit_compile_seconds"] >= counters[CACHE_LOAD]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("cell, metric", cases("setup_span_seconds"))
def test_setup_span_named_by_a_metric_is_opened_in_set_up(
        rehearsal, cell, metric, monkeypatch):
    params = METRICS[metric]["params"]
    cell_run = rehearsal(cell)
    monkeypatch.setattr(telemetry, "finished_spans", lambda: cell_run.spans)
    value = setup_span_seconds.read(cell_run.ctx, **params)
    assert value is not None and math.isfinite(value) and value > 0
    end = setup_span_seconds.first_root_end(cell_run.spans, params["root"])
    found = {s.name for s in setup_span_seconds.outermost(
        cell_run.spans, params["span"]) if s.ts < end}
    assert {"dataset.sparse_batch", "dataset.game"} <= found, found


@pytest.mark.parametrize("cell, metric", cases("setup_unattributed"))
def test_setup_residual_finds_what_it_subtracts(
        rehearsal, cell, metric, monkeypatch):
    params = METRICS[metric]["params"]
    cell_run = rehearsal(cell)
    ctx = cell_run.ctx
    assert params["counter"] in ctx["counters"]["setup_end"]
    assert all(s in ctx["spans"] for s in params["driver_spans"])
    monkeypatch.setattr(telemetry, "finished_spans", lambda: cell_run.spans)
    value = setup_unattributed.read(ctx, **params)
    assert value is not None and math.isfinite(value)
    assert value < ctx["setup_s"]


@pytest.mark.parametrize("cell", CELLS)
def test_set_up_opens_the_dataset_and_build_spans(rehearsal, cell):
    """The children no metric reads yet: PERF.md section 5's rows."""
    cell_run = rehearsal(cell)
    by_id = {s.span_id: s for s in cell_run.spans}
    under = {}
    for s in cell_run.spans:
        parent = by_id.get(s.parent_id)
        under.setdefault(None if parent is None else parent.name, set()).add(
            s.name)
    assert {"dataset.validate", "dataset.pad"} <= under["dataset.sparse_batch"]
    assert "dataset.pad_rows" in under["dataset.game"]
    with_ids = bool(cell_run.driver.train.id_columns)
    assert ("dataset.ids" in under["dataset.game"]) == with_ids
    kinds = {name: shape["kind"] for name, shape in
             cell_run.ctx["shapes"]["coordinates"].items() if "." not in name}
    for name, kind in kinds.items():
        built = under[f"build:{name}"]
        if kind.startswith("fixed_effect"):
            expected = {"build.normalization", "build.rows", "build.objective"}
        elif name == "user-x-movie":
            expected = {"build.table_estimate", "build.entity_map",
                        "build.objective"}
        else:
            expected = {"build.table_estimate", "build.layout_report",
                        "build.objective"}
        assert expected <= built, (name, sorted(built))


# -- what the drivers read off the program's objects --------------------------


def test_plain_design_gives_the_driver_its_tile_shape(rehearsal):
    """``game_fit.Driver.shapes()``: ``_tiled.num_tiles``, ``.vals.shape[2]``,
    ``.num_blocks``, ``.num_features``."""
    cell_run = rehearsal("glm_fe.lbfgs_fit")
    fixed = cell_run.ctx["shapes"]["coordinates"]["fixed"]
    assert fixed["kind"] == "fixed_effect"  # not "fixed_effect_coo"
    design = cell_run.driver.coordinates["fixed"]._tiled
    assert not hasattr(design, "parts")
    assert (fixed["T"], fixed["S"], fixed["B"], fixed["features"]) == (
        design.num_tiles, design.vals.shape[2], design.num_blocks,
        cell_run.driver.shape["fe_features"])
    assert fixed["T"] * 128 >= ROWS and fixed["S"] % 128 == 0


def test_panel_design_gives_the_driver_its_parts(rehearsal):
    """``game_fit_panels.Driver.shapes()``: ``.parts`` with ``cls.window``
    and ``vals.shape[0]``, ``.stored`` (hot first, then one a part),
    ``.hot``, ``.nnz_slots``, ``.num_tiles``."""
    cell_run = rehearsal("criteo_fe.lbfgs_fit")
    shapes = cell_run.ctx["shapes"]["coordinates"]
    design = cell_run.driver.coordinates["fixed"]._tiled
    assert len(design.stored) == 1 + len(design.parts) and design.parts
    whole, hot, tail = (
        shapes["fixed"], shapes["fixed.hot"], shapes["fixed.tail"])
    assert "T" not in whole  # no reader takes the whole design for a pass
    rows = cell_run.driver.shape["rows"]
    assert whole["nnz"] == rows * cell_run.driver.shape["fe_nnz_per_row"]
    assert whole["nnz"] == hot["nnz"] + tail["nnz"] <= whole["slots"]
    assert whole["features"] == hot["features"] + tail["features"]
    assert hot["T"] * 128 >= rows and hot["B"] * 128 >= hot["features"]
    assert tail["classes"] == len(tail["windows"]) == len(tail["tiles"])
    assert all(w > 0 for w in tail["windows"])
    assert all(t > 0 for t in tail["tiles"]) and tail["T"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_update_leaves_a_tracker_with_value_and_iterations(rehearsal, cell):
    """``game_fit.step_facts`` reads ``last_tracker.final_value`` and
    ``.iterations`` of the coordinate the step updated."""
    cell_run = rehearsal(cell)
    tracker = cell_run.driver.coordinates["fixed"].last_tracker
    step = [s for s in cell_run.ctx["fits"][-1]["steps"]
            if s["coordinate"] == "fixed"][-1]
    assert step["loss"] == float(tracker.final_value)
    assert step["solver_iterations"] == float(tracker.iterations) >= 1
    assert math.isfinite(step["loss"])


MIXED = "ml20m_glmix.cd_fit"
RANDOM_EFFECTS = ["per-user", "per-movie"]


@pytest.mark.parametrize("name", RANDOM_EFFECTS)
def test_random_effect_update_leaves_per_entity_values_and_iterations(
        rehearsal, name):
    """``game_fit.step_facts`` sums ``last_tracker.final_values`` and takes
    the mean of ``.iterations`` (one entry an entity) of a random effect."""
    cell_run = rehearsal(MIXED)
    coordinate = cell_run.driver.coordinates[name]
    tracker = coordinate.last_tracker
    step = [s for s in cell_run.ctx["fits"][-1]["steps"]
            if s["coordinate"] == name][-1]
    entities = sum(b.num_entities for b in coordinate.re_data.buckets)
    assert len(tracker.iterations) == len(tracker.final_values) == entities
    assert step["loss"] == float(
        np.sum(tracker.final_values, dtype=np.float64))
    assert step["solver_iterations"] == float(np.mean(tracker.iterations))
    assert 1 <= tracker.iterations.max() <= 4  # the traffic's cap


@pytest.mark.parametrize("name", RANDOM_EFFECTS)
def test_random_effect_layout_gives_the_driver_its_buckets(rehearsal, name):
    """``game_fit_mixed.Driver.shapes()``: ``re_data.buckets`` (entities,
    rows and local features a bucket, ``values``, ``row_index``,
    ``projection``, ``num_global_features``), ``_dense_x`` beside them, and
    ``random_effect_data.MAX_GEOMETRY_CLASSES`` as the bound."""
    cell_run = rehearsal(MIXED)
    shape = cell_run.ctx["shapes"]["coordinates"][name]
    coordinate = cell_run.driver.coordinates[name]
    assert shape["kind"] == "random_effect"
    assert 1 <= len(shape["buckets"]) <= shape["max_buckets"]
    assert len(shape["buckets"]) == len(coordinate._dense_x)
    rows = cell_run.driver.shape["rows"]
    padded = sum(e * r for e, r, *_ in shape["buckets"])
    assert shape["pass"]["r_sum"] == rows <= padded
    for (e, r, k, nz, dense), b in zip(
            shape["buckets"], coordinate.re_data.buckets):
        assert (e, r, k) == (
            b.num_entities, b.rows_per_entity, b.num_local_features)
        assert e > 0 and r & (r - 1) == 0 and k & (k - 1) == 0
    assert 1 <= shape["pass"]["k_min"] <= shape["features"] <= shape[
        "global_features"]
    assert shape["pass"]["rk"] <= shape["pass"]["rkk"]
    counts = importlib.import_module("benchmark.counts.re_newton_pass")
    flops, nbytes = counts.per_fit(shape, 100.0, 1000.0)
    assert flops > 0 and nbytes == 12000.0


@pytest.mark.parametrize("name", RANDOM_EFFECTS)
def test_random_effect_counters_hold_what_the_layout_and_a_fit_cost(
        rehearsal, name):
    """``re.<coordinate>.*``: the layout's at the end of set-up, the
    stragglers' after every update; ``re.*`` are their sums."""
    ctx = rehearsal(MIXED).ctx
    shape = ctx["shapes"]["coordinates"][name]
    at_setup, at_end = ctx["counters"]["setup_end"], ctx["counters"][
        "window_end"]
    get = lambda mark, key: mark[f"re.{name}.{key}"]  # noqa: E731
    assert get(at_setup, "buckets") == len(shape["buckets"])
    assert get(at_setup, "entities") == shape["entities"]
    assert get(at_setup, "rows") == shape["pass"]["r_sum"]
    assert get(at_setup, "rows_padded") == sum(
        e * r for e, r, *_ in shape["buckets"])
    assert get(at_setup, "nnz") <= get(at_setup, "nnz_padded")
    assert get(at_end, "lane_iterations") <= get(
        at_end, "lane_iterations_run")
    assert get(at_end, "lane_iterations") <= get(at_end, "pass_cells")
    for key in ("rows", "rows_padded", "lane_iterations", "pass_cells"):
        mark = at_end if "iterations" in key or "cells" in key else at_setup
        assert mark[f"re.{key}"] == sum(
            mark[f"re.{other}.{key}"] for other in RANDOM_EFFECTS)


def test_fixed_effect_of_the_mixed_cell_reports_its_own_nonzeros(rehearsal):
    """Ragged rows: ``nnz`` is the shard's own count, and the tile shape
    comes with what the packer chose (``rlo is None``: strided)."""
    cell_run = rehearsal(MIXED)
    fixed = cell_run.ctx["shapes"]["coordinates"]["fixed"]
    design = cell_run.driver.coordinates["fixed"]._tiled
    vals = cell_run.driver.raw["train"]["global_vals"]
    assert fixed["kind"] == "fixed_effect" and fixed["features"] == 32
    assert 2 * len(vals) <= fixed["nnz"] == np.count_nonzero(vals)
    assert (fixed["T"], fixed["S"], fixed["B"]) == (
        design.num_tiles, design.vals.shape[2], design.num_blocks)
    assert fixed["strided"] == (design.rlo is None)

MF = "ml20m_mf.cd_fit"
FACTORED = "user-x-movie"


def test_factored_update_leaves_the_refit_value_and_the_lanes_iterations(
        rehearsal):
    """``game_fit.step_facts`` reads ``last_tracker.final_value`` (the
    refit's final objective) and ``.iterations`` (the latent solves' mean)
    of a factored coordinate."""
    cell_run = rehearsal(MF)
    tracker = cell_run.driver.coordinates[FACTORED].last_tracker
    step = [s for s in cell_run.ctx["fits"][-1]["steps"]
            if s["coordinate"] == FACTORED][-1]
    re_t, fe_t = tracker.steps[-1]
    assert step["loss"] == float(fe_t.final_value) == tracker.final_value
    assert step["solver_iterations"] == float(np.mean(re_t.iterations))
    assert 1 <= fe_t.iterations <= 15  # the traffic's cap


def test_factored_layout_gives_the_driver_its_shape(rehearsal):
    """``game_fit_mf.Driver.shapes()``: ``re_data.buckets``,
    ``latent_dim``, ``_nnz``, ``_design.num_tiles``; the coordinate passes
    for a random effect of ``latent_dim`` features a row, and ``mf`` holds
    what ``counts/mf_refit_pass.py`` prices a pass from."""
    cell_run = rehearsal(MF)
    shape = cell_run.ctx["shapes"]["coordinates"][FACTORED]
    coordinate = cell_run.driver.coordinates[FACTORED]
    rows = cell_run.driver.shape["rows"]
    assert shape["kind"] == "random_effect" and shape["features"] == 16.0
    assert 1 <= len(shape["buckets"]) <= shape["max_buckets"]
    assert [b[:2] for b in shape["buckets"]] == [
        [b.num_entities, b.rows_per_entity]
        for b in coordinate.re_data.buckets]
    # classed by rows alone: no two buckets of one R
    assert len({b[1] for b in shape["buckets"]}) == len(shape["buckets"])
    assert shape["mf"] == {
        "nnz": rows, "rows": rows, "latent_dim": 16,
        "features": cell_run.driver.shape["movies"]}
    assert shape["T"] * 128 >= sum(e * r for e, r, _ in shape["buckets"])
    counts = importlib.import_module("benchmark.counts.mf_refit_pass")
    flops, nbytes = counts.per_fit(shape, 3.0)
    assert flops == 3 * 4 * 16 * rows and nbytes > 3 * 8 * rows
    fit = importlib.import_module("benchmark.counts.glmix_fit")
    steps = cell_run.ctx["fits"][-1]["steps"]
    assert len(list(fit.per_fit(cell_run.ctx["shapes"], steps))) == len(steps)


def test_factored_counters_say_what_was_not_built_and_which_route(rehearsal):
    """``mf.<coordinate>.*``: nothing of Kronecker length, every lane on
    the hand solve, an evaluation more than the refit's iterations."""
    ctx = rehearsal(MF).ctx
    at_setup, at_end = ctx["counters"]["setup_end"], ctx["counters"][
        "window_end"]
    shape = ctx["shapes"]["coordinates"][FACTORED]
    assert at_setup[f"mf.{FACTORED}.kron_nnz_materialised"] == 0
    assert at_setup[f"mf.{FACTORED}.refit_nnz"] == shape["mf"]["nnz"]
    assert at_setup[f"mf.{FACTORED}.latent_dim"] == 16
    assert at_setup[f"re.{FACTORED}.buckets"] == len(shape["buckets"])
    assert at_setup[f"re.{FACTORED}.rows_padded"] == sum(
        e * r for e, r, _ in shape["buckets"])
    assert at_end[f"mf.{FACTORED}.xla_solve_lanes"] == 0
    assert at_end[f"mf.{FACTORED}.hand_solve_lanes"] > 0
    assert at_end[f"mf.{FACTORED}.refit_evaluations"] > at_end[
        f"mf.{FACTORED}.refit_iterations"] > 0
    assert at_end[f"mf.{FACTORED}.lane_iterations"] <= at_end[
        f"mf.{FACTORED}.lane_iterations_run"]
    assert at_end.get("xla.fallback_calls", 0) == 0


def test_factored_outputs_are_its_own_training_scores(rehearsal):
    """``game_fit_mf.Driver.outputs()``: the coordinate's scores over the
    training rows under its name (``coordinate.score``); the projection the
    reference starts from under ``shape['latent_init']`` and the rows it
    scores under ``shape['compared_rows']``."""
    driver = rehearsal(MF).driver
    out = driver.outputs()
    n = driver.shape["rows"]
    assert out["coefficients"][FACTORED].shape == (n,)
    assert out["coefficients"][FACTORED].any()
    assert np.all(np.isfinite(out["coefficients"][FACTORED]))
    assert out["coefficients"]["fixed"].shape == (32,)
    rows = driver.shape["compared_rows"][FACTORED]
    np.testing.assert_array_equal(rows["ids"], driver.raw["train"]["userId"])
    np.testing.assert_array_equal(
        rows["cols"], driver.raw["train"]["movie_onehot_cols"][:, 0])
    init = driver.shape["latent_init"][FACTORED]
    assert init.shape == (16, driver.shape["movies"])
    np.testing.assert_array_equal(
        init, np.asarray(driver.coordinates[FACTORED].initialize_model()
                         .projection.matrix))


def test_mf_driver_refuses_a_program_that_builds_the_kronecker_design(
        monkeypatch):
    """Before it generates a row (the parent of PR 33 on the new cell)."""
    from photon_ml_tpu.game import factored

    workload = run.load_json("workloads", MF + ".json")
    config = run.load_json("configs", workload["config"] + ".json")
    traffic = run.load_json("traffic", workload["traffic"] + ".json")
    module = importlib.import_module("benchmark.drivers." + config["driver"])
    driver = module.Driver(config, traffic, SEED, rows=ROWS, force_tiled=True)
    monkeypatch.delattr(factored, "KRON_FREE_REFIT")
    with pytest.raises(RuntimeError, match="Kronecker"):
        driver.setup()
    assert driver.raw is None


# last: building the coordinates again resets every `last_tracker`
@pytest.mark.parametrize("cell", CELLS)
def test_estimator_hands_back_the_coordinates_it_built(rehearsal, cell):
    """Every fit of the driver asks ``_build_coordinates(data, mesh=None)``
    again and counts on the cached objects."""
    driver = rehearsal(cell).driver
    again = driver.estimator._build_coordinates(driver.train, mesh=None)
    assert list(again) == list(driver.coordinates)
    assert all(again[k] is v for k, v in driver.coordinates.items())
