"""Reusable GSPMD sharding primitives: named axes, placement helpers, and
entity sharding for coefficient tables.

The framework's modern mesh vocabulary (ROADMAP item 1; SNIPPETS [3] shows
the pattern):

  - axis ``batch``: examples sharded for data-parallel fixed-effect
    training — the tiled design and margins carry
    ``NamedSharding(mesh, P("batch", ...))`` and ``jax.jit`` inserts the
    psums (GSPMD), replacing per-solve ``shard_map`` plumbing;
  - axis ``model``: per-entity state (random-effect coefficient tables,
    streamed entity chunks) sharded so table capacity scales with devices.

The legacy 1-D axis names ``data``/``entity`` (parallel.mesh) resolve to
the same roles, so older meshes keep working. This module is a LIBRARY
surface: online serving (ROADMAP item 4) reuses :func:`entity_sharding`
for mesh-spanning model state, so keep it free of training-only concerns.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BATCH_AXIS = "batch"
MODEL_AXIS = "model"

#: Axis names recognized as the example/row (data-parallel) axis, most
#: preferred first. "data" is the legacy 1-D spelling.
_DATA_AXES = (BATCH_AXIS, "data")
#: Axis names recognized as the per-entity (model-parallel) axis.
_MODEL_AXES = (MODEL_AXIS, "entity")


def data_axis(mesh: Mesh) -> Optional[str]:
    """The mesh's example-sharding axis name (``batch``/legacy ``data``),
    or None when the mesh has no such axis (an entity-only mesh)."""
    for name in _DATA_AXES:
        if name in mesh.axis_names:
            return name
    return None


def model_axis(mesh: Mesh) -> Optional[str]:
    """The mesh's entity-sharding axis name (``model``/legacy ``entity``),
    or None when the mesh has no such axis (a batch-only mesh)."""
    for name in _MODEL_AXES:
        if name in mesh.axis_names:
            return name
    return None


def axis_size(mesh: Mesh, axis: str) -> int:
    return int(mesh.shape[axis])


def batch_sharding(mesh: Mesh, axis: Optional[str] = None) -> NamedSharding:
    """Sharding for per-row arrays ([n] labels/offsets/weights, [T, ...]
    tile grids, [nnz] COO slots): leading dim split over the batch axis,
    everything else replicated. ``P(axis)`` is a prefix spec, so one
    sharding serves every rank."""
    axis = axis or data_axis(mesh)
    if axis is None:
        raise ValueError(
            f"mesh {dict(mesh.shape)} has no batch/data axis to shard rows "
            "over"
        )
    return NamedSharding(mesh, P(axis))


def entity_sharding(mesh: Mesh, axis: Optional[str] = None) -> NamedSharding:
    """Sharding for per-entity state ([E, K] coefficient tables, [E, ...]
    chunk batches): the leading entity dim split over the model axis.

    This is the ONE definition of how entity state spans the mesh —
    the streaming coefficient table, the RE bucket solves, and (ROADMAP
    item 4) sharded serving all place through it, so their shards line up
    with no resharding between training and serving."""
    axis = axis or model_axis(mesh)
    if axis is None:
        raise ValueError(
            f"mesh {dict(mesh.shape)} has no model/entity axis to shard "
            "entities over"
        )
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated placement (broadcast analog) on ``mesh``."""
    return NamedSharding(mesh, P())


def pad_count(n: int, shards: int) -> int:
    """Smallest multiple of ``shards`` that is >= ``n``."""
    return -(-int(n) // int(shards)) * int(shards)


def place_entities(tree, mesh: Mesh, axis: Optional[str] = None):
    """Place every leaf of an entity-leading pytree ([E, ...] per leaf)
    with :func:`entity_sharding`. E must be a multiple of the axis size
    (see :func:`pad_count` / game.coordinates._pad_entities)."""
    sharding = entity_sharding(mesh, axis)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def place_replicated(tree, mesh: Mesh):
    """Replicate every leaf of a pytree across the whole mesh."""
    sharding = replicated(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


class ElasticPlacementError(ValueError):
    """The TARGET topology cannot hold this table (entity count does not
    divide over the mesh's model axis) — a configuration error, distinct
    from checkpoint corruption: restore must surface it, never skip past
    valid checkpoints because of it."""


def valid_entity_axis_sizes(num_entities: int) -> list[int]:
    """The axis sizes ``num_entities`` divides over, capped at the device
    count — the LEGAL topologies an operator can actually pick."""
    return [
        d for d in range(1, min(int(num_entities), jax.device_count()) + 1)
        if num_entities % d == 0
    ]


def entity_axis_mismatch(
    num_entities: int, axis: str, size: int, what: str = "re-place elastically"
) -> ElasticPlacementError:
    """The ONE formatting of the indivisible-entity-axis error: an operator
    picking a mesh (elastic restore after host loss, a serving mesh) needs
    the valid sizes listed, not a modulus. Shared by checkpoint restore
    (:func:`place_entity_rows`) and the sharded serving engine."""
    return ElasticPlacementError(
        f"num_entities={num_entities} must divide over the "
        f"{size}-device '{axis}' axis to {what}; valid "
        f"target axis sizes for this table: "
        f"{valid_entity_axis_sizes(num_entities)}"
    )


# ---------------------------------------------------------------------------
# serving-fleet ownership: entity code -> owning member, pure math
# ---------------------------------------------------------------------------

#: An upper bound on how many valid fleet sizes get LISTED in the
#: indivisible-fleet error (the sizes themselves are unbounded).
_FLEET_SIZE_LISTING_CAP = 64


def valid_fleet_sizes(num_entities: int) -> list[int]:
    """Fleet sizes ``num_entities`` divides over — the serving analog of
    :func:`valid_entity_axis_sizes`, deliberately NOT capped at the
    device count: fleet members are processes (often hosts), and the
    whole point of the fleet is holding a table no one device set can."""
    n = int(num_entities)
    return [
        d for d in range(1, min(n, _FLEET_SIZE_LISTING_CAP) + 1)
        if n % d == 0
    ]


def fleet_size_mismatch(
    num_entities: int, num_members: int, what: str = "slice the serving fleet"
) -> ElasticPlacementError:
    """The indivisible-fleet error, formatted like
    :func:`entity_axis_mismatch`: the operator picking a fleet size needs
    the sizes that CAN hold the table, not a modulus."""
    return ElasticPlacementError(
        f"num_entities={num_entities} must divide over a "
        f"{num_members}-member serving fleet to {what}; valid "
        f"fleet sizes for this table: {valid_fleet_sizes(num_entities)}"
    )


def member_row_range(
    num_entities: int, member: int, num_members: int
) -> tuple[int, int]:
    """The contiguous entity-code block ``[lo, hi)`` serving-fleet member
    ``member`` of ``num_members`` owns — a pure function of the fleet
    size alone (the ``plans_for_host`` discipline): every member and the
    router compute the SAME ownership from ``(num_entities,
    num_members)`` with no coordination, and a resize is just re-running
    it at the new size. Contiguous blocks line up with the streamed
    checkpoint's row ranges, so a member restore is one
    ``read_rows(lo, hi)`` over the mmap'd shards."""
    num_entities, num_members = int(num_entities), int(num_members)
    if num_members < 1:
        raise ValueError(f"num_members must be >= 1, got {num_members}")
    if not 0 <= int(member) < num_members:
        raise ValueError(
            f"member {member} outside fleet of {num_members}"
        )
    if num_entities % num_members:
        raise fleet_size_mismatch(num_entities, num_members)
    per = num_entities // num_members
    return int(member) * per, (int(member) + 1) * per


def owner_of_row(num_entities: int, row: int, num_members: int) -> int:
    """The member owning entity code ``row`` — the router-side inverse of
    :func:`member_row_range` (same divisibility contract)."""
    num_entities, num_members = int(num_entities), int(num_members)
    if num_entities % num_members:
        raise fleet_size_mismatch(num_entities, num_members)
    if not 0 <= int(row) < num_entities:
        raise ValueError(
            f"entity code {row} outside table of {num_entities}"
        )
    return int(row) // (num_entities // num_members)


def place_entity_rows(
    read_rows,
    num_entities: int,
    tail_shape: tuple,
    dtype,
    mesh: Optional[Mesh] = None,
    axis: Optional[str] = None,
):
    """Build an entity-sharded ``[E, *tail_shape]`` array from a
    row-range reader WITHOUT materializing the full table on any host.

    ``read_rows(lo, hi)`` returns host rows ``[lo, hi)`` (e.g. slices of
    memory-mapped checkpoint shard files). With a mesh, each device's
    shard is requested independently through
    ``jax.make_array_from_callback`` — peak host residency is one device
    shard, which is what makes ELASTIC checkpoint restore (written on an
    8-device mesh, restored onto 4, or 1) safe for tables that only fit
    sharded. Without a mesh the whole range is read and placed on the
    default device (the caller asserted it fits).

    This is the restore-side complement of :func:`entity_sharding`:
    row ranges re-slice over whatever model axis the TARGET mesh has, so
    a checkpoint's provenance mesh never constrains where it can resume.
    """
    shape = (int(num_entities),) + tuple(int(d) for d in tail_shape)
    # Two aliasing hazards on this path, both host-copy lessons from the
    # ingest uploader. (1) ``read_rows`` serves views of MEMORY-MAPPED
    # checkpoint files, and CPU device_put MAY zero-copy an aligned host
    # array — so every placement gets a fresh owned ndarray, never a
    # mapped view. (2) Even that owned copy is only BORROWED by jax:
    # device_put/make_array_from_callback keep the numpy buffer rather
    # than copying into an XLA-owned allocation. A downstream DONATED
    # update (ShardedCoefficientTable chunk writes) then aliases borrowed
    # memory that is freed when the donated input dies — one device's
    # shard turns into freed-heap garbage, timing-dependent (reproduced
    # under the warm persistent compile cache). ``_owned_copy`` launders
    # the result through a non-donating jitted copy, whose outputs XLA
    # allocates and owns, before anything can donate it.
    if mesh is None:
        import jax.numpy as jnp

        return _owned_copy(
            jnp.asarray(
                np.array(read_rows(0, shape[0]), dtype=dtype, copy=True)
            )
        )
    sharding = entity_sharding(mesh, axis)
    if shape[0] % axis_size(mesh, sharding.spec[0]):
        raise entity_axis_mismatch(
            shape[0], sharding.spec[0],
            axis_size(mesh, sharding.spec[0]),
        )

    def callback(index):
        row_slice = index[0]
        lo = row_slice.start or 0
        hi = shape[0] if row_slice.stop is None else row_slice.stop
        chunk = np.asarray(read_rows(lo, hi))
        return np.array(
            chunk[(slice(None),) + tuple(index[1:])], dtype=dtype,
            copy=True,
        )

    return _owned_copy(
        jax.make_array_from_callback(shape, sharding, callback)
    )


def _owned_copy(array):
    """Copy ``array`` into buffers XLA allocated and owns (sharding
    preserved — the copy is per-device, no cross-device traffic). Without
    donation an executable's outputs can never alias its inputs, so the
    result is safe to hand to donating updates no matter where the input
    buffers came from.

    This function (with :func:`place_entity_rows`) is a registered L017
    SANITIZER: the dataflow gate treats its result as owned and stops
    tracking borrowed host memory through it. Renaming it fails the gate
    with W002 (``tools/analysis/dataflow.py::COPY_SANITIZERS``) rather
    than silently laundering nothing."""
    from photon_ml_tpu import telemetry  # lazy: keep sharding importable solo

    global _OWNED_COPY_JIT
    if _OWNED_COPY_JIT is None:
        import jax.numpy as jnp

        # multi_shape: one executable per (table shape, sharding) by
        # design — placements are once-per-restore, not hot
        _OWNED_COPY_JIT = telemetry.instrumented_jit(
            jnp.copy, name="place_entity_rows_copy", multi_shape=True
        )
    return _OWNED_COPY_JIT(array)


_OWNED_COPY_JIT = None


# ---------------------------------------------------------------------------
# batch placement: flat (non-stacked) designs onto the batch axis
# ---------------------------------------------------------------------------


def pad_batch_rows(batch, shards: int):
    """Host-side: pad a batch's row structure so every leading dim divides
    over ``shards`` — the flat-GSPMD analog of parallel.mesh.shard_rows
    (which additionally re-stacks; GSPMD needs no stacking).

    SparseBatch: pads rows (weight 0 -> inert) and nnz slots (value 0,
    row = last row -> inert). TiledBatch: pads whole tiles (weights 0,
    ``hi`` = num_blocks sentinel so gathers contribute nothing; a tile's
    slots are never cut, so a strided design stays strided).
    """
    import jax.numpy as jnp

    from photon_ml_tpu.ops.sparse import SparseBatch
    from photon_ml_tpu.ops.tiled import TiledBatch

    if isinstance(batch, TiledBatch):
        T = batch.num_tiles
        Tp = pad_count(T, shards)
        if Tp == T:
            return batch

        def pad_tiles(x, fill):
            a = np.asarray(x)
            pad = np.full((Tp - T,) + a.shape[1:], fill, a.dtype)
            return jnp.asarray(np.concatenate([a, pad], axis=0))

        return dataclasses.replace(
            batch,
            vals=pad_tiles(batch.vals, 0.0),
            hi=pad_tiles(batch.hi, batch.num_blocks),
            lo=pad_tiles(batch.lo, 0),
            rlo=None if batch.strided else pad_tiles(batch.rlo, 0),
            labels3=pad_tiles(batch.labels3, 0.0),
            offsets3=pad_tiles(batch.offsets3, 0.0),
            weights3=pad_tiles(batch.weights3, 0.0),
        )
    if isinstance(batch, SparseBatch):
        n, nnz = batch.num_rows, batch.nnz
        n_p, nnz_p = pad_count(n, shards), pad_count(nnz, shards)
        if n_p == n and nnz_p == nnz:
            return batch

        def pad_to(x, total, fill):
            a = np.asarray(x)
            out = np.full((total,) + a.shape[1:], fill, a.dtype)
            out[: a.shape[0]] = a
            return jnp.asarray(out)

        return SparseBatch(
            values=pad_to(batch.values, nnz_p, 0.0),
            rows=pad_to(batch.rows, nnz_p, n_p - 1),
            cols=pad_to(batch.cols, nnz_p, 0),
            labels=pad_to(batch.labels, n_p, 0.0),
            offsets=pad_to(batch.offsets, n_p, 0.0),
            weights=pad_to(batch.weights, n_p, 0.0),
            num_features=batch.num_features,
        )
    raise TypeError(f"cannot pad batch type {type(batch).__name__}")


def place_batch(batch, mesh: Mesh, axis: Optional[str] = None):
    """Pad (:func:`pad_batch_rows`) and upload a flat design so its rows
    live sharded over the batch axis: every leaf gets
    ``NamedSharding(mesh, P(axis))`` on its leading dim. The returned
    batch feeds :func:`photon_ml_tpu.parallel.distributed.gspmd_solve`
    directly — the whole optimizer while-loop then runs under one jit with
    GSPMD-inserted psums (a TiledBatch's or PanelBatch's pallas kernels
    under ``jax.shard_map`` over the same axis)."""
    from photon_ml_tpu.ops.panels import PanelBatch
    from photon_ml_tpu.ops.tiled import TiledBatch

    axis = axis or data_axis(mesh)
    if isinstance(batch, PanelBatch):
        # packed for this many shards already (every shard the same tile
        # counts, row windows local to it); its column order stays whole
        return batch.place(mesh, axis)
    sharding = batch_sharding(mesh, axis)
    padded = pad_batch_rows(batch, axis_size(mesh, axis))
    placed = jax.tree.map(lambda x: jax.device_put(x, sharding), padded)
    if isinstance(placed, TiledBatch):
        # Mosaic kernels cannot be partitioned by GSPMD: the batch carries
        # its mesh so each kernel runs per shard (TiledBatch._run)
        placed = dataclasses.replace(placed, shard=(mesh, axis))
    return placed
