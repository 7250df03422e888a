"""The main path's kernels compile for the chip — at the real width, for a
TPU v5e that is described, not attached.

Interpret mode (every other tiled test) cannot see what the chip's compiler
refuses: a misaligned slice, too much VMEM, a Mosaic kernel left for GSPMD
to partition. These tests hand the installed TPU compiler the shapes
``chip_smoke.py`` trains at (1M rows x 10K features, 20 nnz/row: T=7813
tiles, S=2560 slots, B=79 column blocks), strided (no ``rlo`` block: what
both benchmark cells' constant-length rows pack to) and, one case a kernel
family, sorted. Nothing runs, so they say nothing about results or speed.

The topology is described inside a fixture of THIS file only: one process
at a time may load libtpu, xdist hands a file to one worker, and a call at
import time (or in ``conftest.py``) would make the other workers fail.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from photon_ml_tpu.ops import tiled
from photon_ml_tpu.ops.tiled import LANE, ROWS_PER_TILE, TiledBatch

N_ROWS, NUM_FEATURES = 1_000_000, 10_000
T = -(-N_ROWS // ROWS_PER_TILE)  # 7813
S = 2560
B = -(-NUM_FEATURES // LANE)  # 79


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip_kernels(monkeypatch):
    """TiledBatch methods ask ``_interpret()``, which sees this process's
    CPU backend: steer it to the Mosaic path for code compiled here."""
    monkeypatch.setattr(tiled, "_interpret", lambda: False)


def _batch(num_tiles, sharding, shard=None, strided=True):
    def leaf(width, dtype):
        return jax.ShapeDtypeStruct(
            (num_tiles, 1, width), dtype, sharding=sharding)

    return TiledBatch(
        vals=leaf(S, jnp.float32), hi=leaf(S, jnp.int32),
        lo=leaf(S, jnp.int32), rlo=None if strided else leaf(S, jnp.int32),
        labels3=leaf(ROWS_PER_TILE, jnp.float32),
        offsets3=leaf(ROWS_PER_TILE, jnp.float32),
        weights3=leaf(ROWS_PER_TILE, jnp.float32),
        num_features=NUM_FEATURES, shard=shard,
    )


# a second shape for every kernel family: S is three lane tiles and the
# coefficient table is narrower than its 16-row bfloat16 tile
S_SMALL, B_SMALL = 384, 3


def _kernel_cases(S=S, B=B, T=T, strided=True):
    """name -> (pallas_call built with interpret=False, argument shapes), at
    the tiles a grid step the design's rule gives. A strided design hands
    the kernels three slot arrays, a sorted one four (``rlo``)."""
    slot = [((T, 1, S), jnp.float32)] + [((T, 1, S), jnp.int32)] * (
        2 if strided else 3)
    row = ((T, 1, ROWS_PER_TILE), jnp.float32)
    w2 = ((tiled._table_rows(B), LANE), jnp.float32)
    sh = ((1, 2), jnp.float32)
    st = (T, tiled.tiles_a_step(T, S, tiled._table_rows(B), strided), S, B,
          strided)
    return {
        "margins": (
            tiled._margins_call(*st, True, False, False),
            slot + [row, w2, sh]),
        "dot_rows": (
            tiled._margins_call(*st, False, False, False),
            slot + [row, w2, sh]),
        "margins_pair": (
            tiled._margins_call(*st, True, True, False),
            slot + [row, w2, w2, sh]),
        "scatter": (
            tiled._scatter_call(*st, False, False), slot + [row]),
        "scatter_sq": (
            tiled._scatter_call(*st, True, False), slot + [row]),
        "value_grad": (
            tiled._value_grad_call(*st, "logistic", True, False),
            slot + [row] * 3 + [w2, sh]),
        "hv": (
            tiled._hv_call(*st, "logistic", True, False),
            slot + [row] * 3 + [w2, w2, sh]),
        "hv_at": (
            tiled._hv_at_call(*st, False), slot + [row, w2, sh]),
    }


def _compiles_as(kernel, call, shapes, one_chip):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the custom call's INSTRUCTION carries the pallas_call's `name`: a
    # device event's name in a profiler trace starts with it
    assert re.search(
        rf"%{kernel}(\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)


@pytest.mark.parametrize("name,kernel", [
    ("margins", "tiled_margins"), ("dot_rows", "tiled_margins"),
    ("margins_pair", "tiled_margins"), ("scatter", "tiled_scatter"),
    ("scatter_sq", "tiled_scatter"), ("value_grad", "tiled_value_grad"),
    ("hv", "tiled_hv"), ("hv_at", "tiled_hv_at"),
])
def test_kernel_compiles_for_v5e(name, kernel, one_chip):
    _compiles_as(kernel, *_kernel_cases()[name], one_chip)


@pytest.mark.parametrize("name,kernel", [
    ("margins_pair", "tiled_margins"), ("scatter_sq", "tiled_scatter"),
    ("value_grad", "tiled_value_grad"), ("hv", "tiled_hv"),
    ("hv_at", "tiled_hv_at"),
])
def test_kernel_family_compiles_at_a_narrow_shape_for_v5e(name, kernel,
                                                         one_chip):
    _compiles_as(kernel, *_kernel_cases(S_SMALL, B_SMALL)[name], one_chip)


@pytest.mark.parametrize("name,kernel", [
    ("margins_pair", "tiled_margins"), ("scatter_sq", "tiled_scatter"),
    ("value_grad", "tiled_value_grad"), ("hv", "tiled_hv"),
    ("hv_at", "tiled_hv_at"),
])
def test_kernel_family_compiles_sorted_for_v5e(name, kernel, one_chip):
    """The arrival-order layout (four slot arrays, the row one-hot ``rt``
    and its MXU pass): what a design of ragged rows still runs."""
    _compiles_as(
        kernel, *_kernel_cases(strided=False)[name], one_chip)


# the benchmark cells' training designs, strided: glm_fe.lbfgs_fit's plain
# design, criteo_fe.lbfgs_fit's hot panel at 39 slots a row and the MovieLens
# cells' fixed effect (ragged rows of 2 to 10 nonzeros): (T, S, B) and the
# tiles a grid step of their calls
CELL_SHAPES = {"glm_fe": (46_875, 2_560, 79, 25),
               "criteo_hot": (54_784, 4_992, 32, 16),
               "ml20m_fe": (140_625, 1_280, 1, 25)}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
@pytest.mark.parametrize("name,kernel", [
    ("margins", "tiled_margins"), ("margins_pair", "tiled_margins"),
    ("scatter", "tiled_scatter"),
])
def test_strided_kernel_compiles_at_the_cells_shapes_for_v5e(
        name, kernel, cell, one_chip):
    """Each at the tiles a grid step the rule picks: its blocks fit the
    chip's VMEM."""
    tiles, slots, blocks, step = CELL_SHAPES[cell]
    assert tiled.tiles_a_step(tiles, slots, tiled._table_rows(blocks)) == step
    call, shapes = _kernel_cases(slots, blocks, tiles)[name]
    assert sum(shape == (tiles, 1, slots) for shape, _ in shapes) == 3
    _compiles_as(kernel, call, shapes, one_chip)


def test_fe_solver_module_and_kernels_are_named_for_v5e(one_chip,
                                                        on_chip_kernels):
    """The FE coordinate's own solver (``instrumented_jit(...,
    name="fe_solve")``): its module is ``jit_fe_solve`` (a trace's `XLA
    Modules` line) and every Mosaic call in it is a named tiled kernel."""
    from photon_ml_tpu.game.coordinates import _fe_solver
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optim import OptimizerConfig, OptimizerType

    cfg = OptimizerConfig(
        optimizer_type=OptimizerType.LBFGS, max_iterations=8, tolerance=0.0)
    obj = make_objective("logistic", l2_weight=1.0)
    w0 = jax.ShapeDtypeStruct((NUM_FEATURES,), jnp.float32, sharding=one_chip)
    text = _fe_solver(cfg, "logistic").lower(
        obj, _batch(T, one_chip), w0, jnp.float32(0.0), None
    ).compile().as_text()
    assert re.match(r"HloModule jit_fe_solve\b", text)
    calls = re.findall(
        r"%([\w\-]+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)
    assert calls and set(calls) <= {"tiled_margins", "tiled_scatter",
                                    "tiled_value_grad"}
    assert "op_name=\"jit(fe_solve)/fe_solve/" in text


def test_whole_lbfgs_solve_compiles_for_v5e(one_chip, on_chip_kernels):
    """The 20-iteration margin-carrying LBFGS while-loop over the full
    design, as ``cli train``'s FE coordinate jits it on one chip."""
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optim import LBFGSConfig, glm_adapter, lbfgs_solve

    obj = make_objective("logistic", l2_weight=1.0)
    cfg = LBFGSConfig(max_iterations=20, tolerance=0.0)

    def solve(batch, w0):
        return lbfgs_solve(glm_adapter(obj, batch), w0, cfg)

    w0 = jax.ShapeDtypeStruct((NUM_FEATURES,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(solve).lower(_batch(T, one_chip), w0).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "while" in text
    mem = compiled.memory_analysis()
    # the tiled design is ~332 MB; the whole program must sit far inside
    # one chip's 16 GB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 << 30


@pytest.mark.parametrize("axes", [
    {"batch": 4}, {"batch": 2, "model": 2},
], ids=["batch4", "batch2_model2"])
def test_sharded_fe_value_grad_compiles_for_v5e_2x2(axes, topo,
                                                    on_chip_kernels):
    """What ``cli train --mesh`` hands ``gspmd_solve`` on four chips: tile
    leaves ``P("batch")``, the kernel per shard under ``shard_map``, its
    accumulators all-reduced. Left to GSPMD the same call is refused
    ("Mosaic kernels cannot be automatically partitioned")."""
    mesh = Mesh(
        np.array(topo.devices).reshape(tuple(axes.values())), tuple(axes))
    n = axes["batch"]
    batch = _batch(
        -(-T // n) * n, NamedSharding(mesh, P("batch")),
        shard=(mesh, "batch"))
    w = jax.ShapeDtypeStruct(
        (NUM_FEATURES,), jnp.float32, sharding=NamedSharding(mesh, P()))

    def value_grad(b, w):
        return b.fused_value_grad(w, 0.0, "logistic")

    compiled = jax.jit(value_grad).lower(batch, w).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    # the tile arrays stay where they were put
    assert "all-gather" not in text
    per_device = compiled.memory_analysis().argument_size_in_bytes
    # three slot arrays a tile: the strided design carries no rlo
    assert per_device < 1.1 * (3 * S + 3 * ROWS_PER_TILE) * 4 * T / n + (1 << 20)


def test_unsharded_kernel_under_a_mesh_is_refused(topo, on_chip_kernels):
    """The fault this file guards against, kept visible: without ``shard``
    the sharded call reaches GSPMD and the Mosaic compiler refuses it."""
    mesh = Mesh(np.array(topo.devices), ("batch",))
    batch = _batch(T + 3, NamedSharding(mesh, P("batch")))
    w = jax.ShapeDtypeStruct(
        (NUM_FEATURES,), jnp.float32, sharding=NamedSharding(mesh, P()))
    with pytest.raises(Exception, match="cannot be automatically partitioned"):
        jax.jit(
            lambda b, w: b.fused_value_grad(w, 0.0, "logistic")
        ).lower(batch, w).compile()


# ---------------------------------------------------------------------------
# column panels (ops/panels.py) at the click-log cell's shape: 7M rows x 1M
# features, the plan the host packer made there (PERF.md, Findings PR 26)
# ---------------------------------------------------------------------------

PANEL_TILES = 54_784
PANEL_HOT_S = 4992  # strided: 128 x the 39 nonzeros of a click-log row
#: (window, first rank block, column windows, tiles) of the four tail classes
PANEL_CLASSES = [(32, 32, 2, 15_308), (64, 96, 3, 11_110),
                 (128, 288, 4, 8_083), (256, 800, 28, 13_603)]
PANEL_FEATURES = 1_000_000


def _panel_batch(sharding, whole, shard=None, shards=1):
    from photon_ml_tpu.ops.panels import (
        HOT_BLOCKS, PANEL_SLOTS, PanelBatch, PanelClass, PanelPart)

    def leaf(shape, dtype, s=sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s)

    tiles = -(-PANEL_TILES // (256 * shards)) * 256 * shards
    hot = TiledBatch(
        vals=leaf((tiles, 1, PANEL_HOT_S), jnp.float32),
        **{k: leaf((tiles, 1, PANEL_HOT_S), jnp.int32) for k in ("hi", "lo")},
        rlo=None,
        **{k: leaf((tiles, 1, ROWS_PER_TILE), jnp.float32)
           for k in ("labels3", "offsets3", "weights3")},
        num_features=HOT_BLOCKS * LANE, shard=shard)
    parts = []
    for W, first, windows, Tt in PANEL_CLASSES:
        Tt = -(-Tt // shards) * shards
        parts.append(PanelPart(
            meta=leaf((Tt,), jnp.int32),
            vals=leaf((Tt, 1, PANEL_SLOTS), jnp.float32),
            **{k: leaf((Tt, 1, PANEL_SLOTS), jnp.int32)
               for k in ("chi", "clo", "rhi", "rlo")},
            cls=PanelClass(W, first, windows),
            bits=max((windows - 1).bit_length(), 1)))
    return PanelBatch(
        hot=hot, parts=tuple(parts),
        order=leaf((PANEL_FEATURES,), jnp.int32, whole),
        rank=leaf((PANEL_FEATURES,), jnp.int32, whole),
        num_features=PANEL_FEATURES, shards=shards, shard=shard)


@pytest.mark.parametrize("W,first,windows,Tt", PANEL_CLASSES)
@pytest.mark.parametrize("kernel", ["panel_margins", "panel_scatter"])
def test_panel_kernel_compiles_for_v5e(kernel, W, first, windows, Tt,
                                       one_chip):
    """Each tail class's two kernels at the cell's shape: the scalar-
    prefetched tile index, the whole coefficient grid of the class in VMEM,
    the [2W, 1024] one-hot intermediates."""
    from photon_ml_tpu.ops import panels

    bits = max((windows - 1).bit_length(), 1)
    blocks = W * windows
    slot = ([((Tt,), jnp.int32), ((Tt, 1, 1024), jnp.float32)]
            + [((Tt, 1, 1024), jnp.int32)] * 4)
    if kernel == "panel_margins":
        call = panels._panel_margins_call(
            Tt, 1024, W, blocks, PANEL_TILES, bits, False)
        shapes = slot + [((blocks, LANE), jnp.float32)]
    else:
        call = panels._panel_scatter_call(
            Tt, 1024, W, blocks, bits, False, False)
        shapes = slot + [((PANEL_TILES, LANE), jnp.float32)]
    _compiles_as(kernel, call, shapes, one_chip)


def test_panel_lbfgs_solve_compiles_for_v5e(one_chip, on_chip_kernels):
    """The FE coordinate's solver over the whole panel design on one chip:
    module ``jit_fe_solve``, and its Mosaic calls are the hot panel's tiled
    kernels and the tail's panel kernels, all named."""
    from photon_ml_tpu.game.coordinates import _fe_solver
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optim import OptimizerConfig, OptimizerType

    cfg = OptimizerConfig(
        optimizer_type=OptimizerType.LBFGS, max_iterations=25, tolerance=0.0)
    obj = make_objective("logistic", l2_weight=10.0)
    w0 = jax.ShapeDtypeStruct(
        (PANEL_FEATURES,), jnp.float32, sharding=one_chip)
    compiled = _fe_solver(cfg, "logistic").lower(
        obj, _panel_batch(one_chip, one_chip), w0, jnp.float32(0.0), None
    ).compile()
    text = compiled.as_text()
    assert re.match(r"HloModule jit_fe_solve\b", text)
    calls = set(re.findall(
        r"%([\w\-]+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text))
    assert calls == {"tiled_margins", "tiled_scatter", "panel_margins",
                     "panel_scatter"}
    mem = compiled.memory_analysis()
    # the design is 4.9 GB; the solve's own state is a few [d] and [n]
    # vectors: the whole program sits inside half the chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30


def test_sharded_panel_value_grad_compiles_for_v5e_2x2(topo, on_chip_kernels):
    """Four chips: every part's tiles ``P("batch")``, the kernels per shard
    under ``shard_map``, the feature-space sums all-reduced, the column
    order whole on every chip."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("batch",))
    batch = _panel_batch(
        NamedSharding(mesh, P("batch")), NamedSharding(mesh, P()),
        shard=(mesh, "batch"), shards=4)
    w = jax.ShapeDtypeStruct(
        (PANEL_FEATURES,), jnp.float32, sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(
        lambda b, w: b.fused_value_grad(w, 0.0, "logistic")
    ).lower(batch, w).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert "all-gather" not in text


@pytest.mark.parametrize("design", ["plain", "panels"])
def test_validation_scorer_calls_have_their_own_names_for_v5e(
        design, one_chip, on_chip_kernels):
    """A validation design (``traced_as("validate")``) through the FE
    coordinate's scorer: module ``jit_fe_score_tiled``, and no Mosaic call in
    it starts with ``tiled_`` or ``panel_``, the names by which a trace's
    reduction finds the TRAINING passes."""
    from photon_ml_tpu.game.coordinates import _tiled_scorer

    if design == "plain":
        batch, d = _batch(T, one_chip), NUM_FEATURES
        want = {"validate_margins"}
    else:
        batch, d = _panel_batch(one_chip, one_chip), PANEL_FEATURES
        want = {"validate_margins", "validate_panel_margins"}
    w = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    text = _tiled_scorer().lower(
        batch.traced_as("validate"), w).compile().as_text()
    assert re.match(r"HloModule jit_fe_score_tiled\b", text)
    calls = set(re.findall(
        r"%([\w\-]+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text))
    assert calls == want


# -- the random-effect path: float32-grade products where they are written ---


def _single_pass_products(text: str) -> list:
    """``dot`` / ``convolution`` instructions of a compiled module that
    multiply float32 operands without ``operand_precision={highest,highest}``:
    on a TPU such a product is ONE bfloat16 pass."""
    found = []
    for line in text.splitlines():
        if re.search(r" (dot|convolution)\(", line) and "f32[" in line:
            if "operand_precision={highest,highest}" not in line:
                found.append(line.strip()[:200])
    return found


def _entity_solver(packed: bool, kmajor: bool = False, sharded: bool = False):
    import dataclasses

    from photon_ml_tpu.config import parse_optimizer_config
    from photon_ml_tpu.game.coordinates import _re_solver

    config = parse_optimizer_config({
        "type": "newton", "max_iterations": 20, "tolerance": 1e-7,
        "regularization": "l2", "regularization_weight": 1.0})
    return _re_solver(
        dataclasses.replace(config, regularization_weight=0.0), "logistic",
        False, False, packed=packed, kmajor=kmajor, sharded=sharded)


def _objective_shapes(one_chip):
    from photon_ml_tpu.ops.objective import make_objective

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        make_objective("logistic", l2_weight=1.0))


_XLA_FACTOR_TARGETS = ("Cholesky", "InvertDiagBlocksLowerTriangular",
                       "TriangularSolve")


# (entities, padded rows, padded local features) of buckets as
# ``ml20m_glmix.cd_fit`` has them: per-user at K = 32 and 16, per-movie
# K = 1; and one wider than the hand solve takes
@pytest.mark.parametrize("E, R, K", [
    (4096, 256, 32), (2048, 64, 16), (8192, 32, 1), (2, 65536, 1),
    (512, 128, 64)])
def test_dense_entity_solve_is_float32_grade_for_v5e(E, R, K, one_chip):
    """``re_solve_dense`` (``optim/newton.py`` over ``ops/dense.py`` and
    ``ops/objective.py::dense_hessian``): margins, gradient, Hessian and the
    line search's directional margins name their precision. Up to K = 32
    the step is ``optim/spd_solve.py``'s float32 vector arithmetic and the
    module holds none of XLA's factorisation custom calls; above it the
    factorisation is XLA's own (what ``re_factor_device_s_per_fit`` sums)."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    packed = (s((E, R * K)), s((E, R)), s((E, R)), s((E, R)))
    text = _entity_solver(True).lower(
        _objective_shapes(one_chip), packed, s((E, K)), s(()), None
    ).compile().as_text()
    assert re.match(r"HloModule jit_re_solve_dense\b", text)
    assert not _single_pass_products(text)
    if K > 1:
        assert "operand_precision={highest,highest}" in text
    found = {t for t in _XLA_FACTOR_TARGETS
             if f'custom_call_target="{t}"' in text}
    if K <= 32:
        assert not found
    else:
        assert {"Cholesky", "InvertDiagBlocksLowerTriangular"} <= found


def test_entity_sharded_dense_solve_compiles_for_v5e_2x2(topo):
    """A bucket's entity axis sharded over the four chips
    (``place_entity_solve``, with the solver a mesh's coordinate builds:
    the plain vmapped loop): the hand solve's ``[.., E]`` slabs partition
    with the rest of the vmapped solve, no chip gathers the entity axis."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices).reshape(4), ("model",))
    split = NamedSharding(mesh, P("model"))
    whole = NamedSharding(mesh, P())
    E, R, K = 4096, 256, 32

    def s(shape, sharding=split):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    packed = (s((E, R * K)), s((E, R)), s((E, R)), s((E, R)))
    text = _entity_solver(True, sharded=True).lower(
        _objective_shapes(whole), packed, s((E, K)), s((), whole), None
    ).compile().as_text()
    assert re.match(r"HloModule jit_re_solve_dense\b", text)
    assert not re.search(r"all-gather|all-to-all|collective-permute", text)
    assert "f32[1024,32,32]" in text and "f32[4096,32,32]" not in text
    assert not any(
        f'custom_call_target="{t}"' in text for t in _XLA_FACTOR_TARGETS)


def test_coo_entity_solve_is_float32_grade_for_v5e(one_chip):
    """``re_solve`` (the COO route): its sweeps are gathers and segment
    sums, and the Hessian's product over the densified rows is pinned."""
    from photon_ml_tpu.ops.sparse import SparseBatch

    E, R, K, NZ = 512, 64, 32, 256

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    batch = SparseBatch(
        values=s((E, NZ)), rows=s((E, NZ), jnp.int32),
        cols=s((E, NZ), jnp.int32), labels=s((E, R)), offsets=s((E, R)),
        weights=s((E, R)), num_features=K)
    text = _entity_solver(False).lower(
        _objective_shapes(one_chip), batch, s((E, K)), s(()), None
    ).compile().as_text()
    assert re.match(r"HloModule jit_re_solve\b", text)
    assert not _single_pass_products(text)
    assert not any(
        f'custom_call_target="{t}"' in text for t in _XLA_FACTOR_TARGETS)


@pytest.mark.parametrize("E, R, K", [(4096, 256, 32), (8192, 32, 1)])
def test_dense_entity_scores_are_float32_grade_for_v5e(E, R, K, one_chip):
    from photon_ml_tpu.game.coordinates import _re_dense_scorer

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _re_dense_scorer().lower(
        s((E, K)), s((E, R * K)), s((E, R), jnp.int32), s((E * R,))
    ).compile().as_text()
    assert re.match(r"HloModule jit_re_score_dense\b", text)
    assert not _single_pass_products(text)


# -- the factored coordinate (ml20m_mf.cd_fit): latent solves, the refit -----

MF_K, MF_D = 16, 27_278
# (entities, padded rows) of the cell's buckets, the first as it stands
MF_BUCKETS = ((41_659, 64), (512, 1024))


def test_latent_solve_of_the_mf_cell_takes_the_hand_solve_for_v5e(one_chip):
    """The latent per-user solve at 41,659 x 64 x 16 on the dense route,
    from the feature-major flat design the projection pass writes
    (``_re_solver(packed=True, kmajor=True)``): float32-grade products, the
    module named ``jit_re_solve_dense``, and no ``Cholesky`` custom call: K = 16
    is the hand SPD solve's."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    E, R = MF_BUCKETS[0]
    packed = (s((E, MF_K * R)), s((E, R)), s((E, R)), s((E, R)))
    text = _entity_solver(True, kmajor=True).lower(
        _objective_shapes(one_chip), packed, s((E, MF_K)), s(()), None
    ).compile().as_text()
    assert re.match(r"HloModule jit_re_solve_dense\b", text)
    assert not _single_pass_products(text)
    assert not any(
        f'custom_call_target="{t}"' in text for t in _XLA_FACTOR_TARGETS)


def _mf_design(one_chip, tiles):
    def leaf(dtype):
        return jax.ShapeDtypeStruct(
            (tiles, 1, LANE), dtype, sharding=one_chip)

    return TiledBatch(
        vals=leaf(jnp.float32), hi=leaf(jnp.int32), lo=leaf(jnp.int32),
        rlo=None, labels3=leaf(jnp.float32), offsets3=leaf(jnp.float32),
        weights3=leaf(jnp.float32), num_features=MF_D,
        margins_name="mf_margins")


def test_mf_refit_holds_no_array_of_kronecker_length_for_v5e(
        one_chip, on_chip_kernels):
    """The refit of vec(A) over the one-hot design at d = 27,278, K = 16
    (B = 214 column blocks): it compiles, its Mosaic calls are the K-wide
    ``mf_margins_k`` / ``mf_scatter_k``, and no buffer of the program has
    the parent's Kronecker length (nonzeros x K) or is wider than the
    [K, rows] sides of a pass."""
    import dataclasses

    from photon_ml_tpu.config import parse_optimizer_config
    from photon_ml_tpu.game import factored

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes, off = [], 0
    for e, r in MF_BUCKETS:
        shapes.append((off, e, r))
        off += e * r
    tiles = -(-off // ROWS_PER_TILE)
    rows = tiles * ROWS_PER_TILE
    nnz = int(off / 1.76)  # one nonzero a real row
    config = parse_optimizer_config({
        "type": "lbfgs", "max_iterations": 15, "tolerance": 0.0,
        "regularization": "l2", "regularization_weight": 1.0})
    solver = factored._latent_fit_solver(
        dataclasses.replace(config, regularization_weight=0.0), "logistic",
        tuple(shapes))
    text = solver.lower(
        _objective_shapes(one_chip), _mf_design(one_chip, tiles),
        s((rows,)), s((rows,)), tuple(s((e, r)) for e, r in MF_BUCKETS),
        tuple(s((e, MF_K)) for e, _ in MF_BUCKETS), s((MF_K * MF_D,)),
        s(())).compile().as_text()
    assert re.match(r"HloModule jit_factored_latent_fit\b", text)
    assert "mf_margins_k" in text and "mf_scatter_k" in text
    sizes = set()
    for shape in re.findall(r"[fsu]\d+\[([\d,]+)\]", text):
        sizes.add(int(np.prod([int(x) for x in shape.split(",")])))
    assert nnz * MF_K not in sizes
    assert max(sizes) <= max(MF_K * rows, 11 * MF_K * MF_D)


def test_mf_projection_pass_compiles_at_the_cells_shape_for_v5e(
        one_chip, on_chip_kernels):
    """``jit_factored_project``: the K-wide gather over 231,905 tiles of
    one-hot rows and the cut into the buckets' feature-major designs."""
    from photon_ml_tpu.game import factored

    shapes, off = [], 0
    for e, r in MF_BUCKETS:
        shapes.append((off, e, r))
        off += e * r
    tiles = -(-off // ROWS_PER_TILE)
    out = factored._latent_design_fn(tuple(shapes)).lower(
        _mf_design(one_chip, tiles),
        jax.ShapeDtypeStruct((MF_K, MF_D), jnp.float32, sharding=one_chip))
    text = out.compile().as_text()
    assert re.match(r"HloModule jit_factored_project\b", text)
    assert "mf_margins_k" in text


# -- the refit's column-sorted second layout (PR 34) -------------------------

# ml20m_mf.cd_fit: 18M one-hot rows in tiles of 4,096 slots (K = 16; 140,625
# tiles of 128 rows, 32 a grid step), one padded tile a window of 16 table
# rows at most (B8 = 224: 14 windows)
MF_SORTED_TILES = -(-18_000_000 // tiled.sorted_slots(16)) + 14
MF_B = -(-MF_D // LANE)  # 214


def _sorted_slot_shapes():
    T, S = MF_SORTED_TILES, tiled.sorted_slots(16)
    return [((T,), jnp.int32), ((T, 1, S), jnp.float32),
            ((T, 1, S), jnp.int32), ((T, 1, S), jnp.int32)]


@pytest.mark.parametrize("kernel", ["mf_margins_k_sorted", "mf_scatter_k_sorted"])
def test_windowed_kernel_compiles_at_the_mf_cells_shape_for_v5e(
        kernel, one_chip):
    """The two kernels over the column-sorted layout at 4,409 tiles x 4,096
    slots, B = 214, K = 16, a window of 16 table rows: the scalar-prefetched
    window of each tile, the aligned dynamic slice of the bf16x2 tables (whole
    in VMEM) and of the float32 accumulator, all inside the VMEM the call
    asks for. Their names keep the prefixes ``%mf_margins_k`` /
    ``%mf_scatter_k`` that ``mf_gather_roofline`` / ``mf_scatter_roofline``
    read."""
    T, S = MF_SORTED_TILES, tiled.sorted_slots(16)
    assert tiled.WINDOW == 16 and T * S // ROWS_PER_TILE >= 140_625
    B8 = tiled._table_rows(MF_B)
    if kernel == "mf_margins_k_sorted":
        call = tiled._contract_window_call(T, S, MF_B, MF_K, False, kernel)
        shapes = _sorted_slot_shapes() + [
            ((MF_K, T * S), jnp.float32),
            ((2 * MF_K, B8, LANE), jnp.bfloat16)]
    else:
        call = tiled._scatter_window_call(
            T, S, MF_B, MF_K, False, False, kernel)
        shapes = _sorted_slot_shapes() + [
            ((T, 1, S), jnp.float32), ((MF_K, T * S), jnp.float32)]
    _compiles_as(kernel, call, shapes, one_chip)
    assert kernel.startswith(("mf_margins_k", "mf_scatter_k"))


def test_mf_refit_over_the_second_layout_compiles_for_v5e(
        one_chip, on_chip_kernels):
    """``jit_factored_latent_fit`` with the column-sorted rows at the cell's
    size: its Mosaic calls are the windowed pair (and the split of the
    tables), the standing K-wide sweeps are not in it, and the [K, total]
    broadcast of the latent table (1.7 GB in the cell) is not formed."""
    import dataclasses

    from photon_ml_tpu.config import parse_optimizer_config
    from photon_ml_tpu.game import factored

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes, off = [], 0
    for e, r in MF_BUCKETS:
        shapes.append((off, e, r))
        off += e * r
    tiles = -(-off // ROWS_PER_TILE)
    rows = tiles * ROWS_PER_TILE
    T, S = MF_SORTED_TILES, tiled.sorted_slots(16)
    by_column = factored.SortedRefitRows(
        design=tiled.ColumnSortedTiles(
            window=s((T,), jnp.int32), vals=s((T, 1, S)),
            hi=s((T, 1, S), jnp.int32), lo=s((T, 1, S), jnp.int32),
            num_features=MF_D, prefix="mf"),
        labels=s((T * S,)), weights=s((T * S,)),
        order=s((T * S,), jnp.int32), entity=s((T * S,), jnp.int32))
    config = parse_optimizer_config({
        "type": "lbfgs", "max_iterations": 15, "tolerance": 0.0,
        "regularization": "l2", "regularization_weight": 1.0})
    solver = factored._latent_fit_solver(
        dataclasses.replace(config, regularization_weight=0.0), "logistic",
        tuple(shapes))
    compiled = solver.lower(
        _objective_shapes(one_chip), _mf_design(one_chip, tiles),
        s((rows,)), s((rows,)), tuple(s((e, r)) for e, r in MF_BUCKETS),
        tuple(s((e, MF_K)) for e, _ in MF_BUCKETS), s((MF_K * MF_D,)),
        s(()), by_column).compile()
    text = compiled.as_text()
    assert re.match(r"HloModule jit_factored_latent_fit\b", text)
    calls = set(re.findall(
        r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text))
    assert calls == {
        "mf_tables_k", "mf_margins_k_sorted", "mf_scatter_k_sorted"}, calls
    sizes = set()
    for shape in re.findall(r"f32\[([\d,]+)\]", text):
        sizes.add(int(np.prod([int(x) for x in shape.split(",")])))
    assert MF_K * rows not in sizes
    # the latent rows [K, slots] (1.15 GB) and a copy of them in the loop
    # that gathers them; the coordinate-order refit holds 8.0 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30
