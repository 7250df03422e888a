"""The main path's kernels compile for the chip — at the real width, for a
TPU v5e that is described, not attached.

Interpret mode (every other tiled test) cannot see what the chip's compiler
refuses: a misaligned slice, too much VMEM, a Mosaic kernel left for GSPMD
to partition. These tests hand the installed TPU compiler the shapes
``chip_smoke.py`` trains at (1M rows x 10K features, 20 nnz/row: T=7813
tiles, S=2560 slots, B=79 column blocks). Nothing runs, so they say nothing
about results or speed.

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


def _batch(num_tiles, sharding, shard=None):
    def leaf(width, dtype):
        return jax.ShapeDtypeStruct(
            (num_tiles, 1, width), dtype, sharding=sharding)

    return TiledBatch(
        vals=leaf(S, jnp.float32), hi=leaf(S, jnp.int32),
        lo=leaf(S, jnp.int32), rlo=leaf(S, jnp.int32),
        labels3=leaf(ROWS_PER_TILE, jnp.float32),
        offsets3=leaf(ROWS_PER_TILE, jnp.float32),
        weights3=leaf(ROWS_PER_TILE, jnp.float32),
        num_features=NUM_FEATURES, shard=shard,
    )


# a second shape for every kernel family: S is three lane tiles and the
# coefficient table is narrower than its 16-row bfloat16 tile
S_SMALL, B_SMALL = 384, 3


def _kernel_cases(S=S, B=B):
    """name -> (pallas_call built with interpret=False, argument shapes)."""
    slot = [((T, 1, S), jnp.float32)] + [((T, 1, S), jnp.int32)] * 3
    row = ((T, 1, ROWS_PER_TILE), jnp.float32)
    w2 = ((tiled._table_rows(B), LANE), jnp.float32)
    sh = ((1, 2), jnp.float32)
    return {
        "margins": (
            tiled._margins_call(T, S, B, True, False, False),
            slot + [row, w2, sh]),
        "dot_rows": (
            tiled._margins_call(T, S, B, False, False, False),
            slot + [row, w2, sh]),
        "margins_pair": (
            tiled._margins_call(T, S, B, True, True, False),
            slot + [row, w2, w2, sh]),
        "scatter": (
            tiled._scatter_call(T, S, B, False, False), slot + [row]),
        "scatter_sq": (
            tiled._scatter_call(T, S, B, True, False), slot + [row]),
        "value_grad": (
            tiled._value_grad_call(T, S, B, "logistic", True, False),
            slot + [row] * 3 + [w2, sh]),
        "hv": (
            tiled._hv_call(T, S, B, "logistic", True, False),
            slot + [row] * 3 + [w2, w2, sh]),
        "hv_at": (
            tiled._hv_at_call(T, S, B, False), slot + [row, w2, sh]),
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
    assert per_device < 1.1 * (4 * S + 3 * ROWS_PER_TILE) * 4 * T / n + (1 << 20)


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
