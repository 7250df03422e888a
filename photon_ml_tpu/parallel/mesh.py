"""Device-mesh construction and the stacked-shard batch layout.

The MODERN mesh vocabulary is the named (``batch``, ``model``) GSPMD pair
in ``parallel.sharding`` (flat designs committed with NamedSharding, jit
inserts the collectives). This module keeps:

  - :func:`make_mesh` — mesh construction for any axis names;
  - the legacy 1-D axis names (``data`` for fixed-effect rows, ``entity``
    for per-entity batches, SURVEY.md §2.f), which the sharding helpers
    still resolve;
  - :func:`shard_rows` / :func:`put_sharded` — the stacked shard layout
    ([num_shards, ...] leaves with LOCAL row indices) that multi-host
    workers assemble from process-local rows and feed to
    ``distributed_solve`` (flattened back inside the jit);
  - :func:`shard_map_compat` — ``jax.shard_map`` for callers that
    genuinely need explicit SPMD.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.ops.sparse import SparseBatch, _round_up

DATA_AXIS = "data"
ENTITY_AXIS = "entity"


def shard_map_compat(f, mesh: Mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with this repo's default of ``check_vma=False``."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


def make_mesh(
    axis_sizes: Optional[dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Create a mesh; default is a 1-D data mesh over all devices."""
    devices = list(devices if devices is not None else jax.devices())
    if axis_sizes is None:
        axis_sizes = {DATA_AXIS: len(devices)}
    names = tuple(axis_sizes)
    sizes = tuple(axis_sizes[n] for n in names)
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(
            f"mesh {dict(axis_sizes)} needs {total} devices, have {len(devices)}"
        )
    arr = np.asarray(devices).reshape(sizes)
    # baseline per-device HBM gauges at mesh build (no-op on statless
    # backends): the run report's memory section starts from what the
    # fleet already held before training allocated anything
    from photon_ml_tpu.telemetry import memory as telemetry_memory

    telemetry_memory.record_device_memory(devices)
    return Mesh(arr, names)


def shard_rows(batch: SparseBatch, num_shards: int) -> SparseBatch:
    """Host-side: split a batch into ``num_shards`` equal row blocks with
    LOCAL row indices, stacked on a new leading axis.

    The result's leaves have shape [num_shards, ...]; feed it to shard_map
    with in_specs P(axis) (the leading axis is consumed by the mesh), or
    vmap for testing. Row blocks are contiguous (rows are already sorted),
    nnz is padded to the max shard nnz.
    """
    import jax.numpy as jnp

    n = batch.num_rows
    rows_per = _round_up(n, num_shards) // num_shards
    rows_np = np.asarray(batch.rows)
    vals_np = np.asarray(batch.values)
    cols_np = np.asarray(batch.cols)

    # valid (non-padding) nnz mask: padding points at last row with value 0
    shard_of_nnz = np.minimum(rows_np // rows_per, num_shards - 1)

    shards = []
    for s in range(num_shards):
        sel = (shard_of_nnz == s) & (vals_np != 0)
        local_rows = rows_np[sel] - s * rows_per
        lo, hi = s * rows_per, min((s + 1) * rows_per, n)
        count = max(hi - lo, 0)

        def pad_to(a, total, fill=0.0):
            out = np.full((total,), fill, dtype=np.asarray(a).dtype)
            out[: len(a)] = np.asarray(a)
            return out

        labels = pad_to(np.asarray(batch.labels)[lo:hi], rows_per)
        offsets = pad_to(np.asarray(batch.offsets)[lo:hi], rows_per)
        weights = pad_to(np.asarray(batch.weights)[lo:hi], rows_per)
        shards.append(
            dict(
                values=vals_np[sel],
                rows=local_rows,
                cols=cols_np[sel],
                labels=labels,
                offsets=offsets,
                weights=weights,
            )
        )

    nnz_max = max(len(s["values"]) for s in shards)
    nnz_max = max(nnz_max, 1)

    stacked = {}
    for key, fill in (
        ("values", 0.0),
        ("rows", None),
        ("cols", 0),
        ("labels", 0.0),
        ("offsets", 0.0),
        ("weights", 0.0),
    ):
        parts = []
        for s in shards:
            a = s[key]
            if key in ("values", "rows", "cols"):
                f = rows_per - 1 if key == "rows" else (fill or 0)
                out = np.full((nnz_max,), f, dtype=a.dtype if len(a) else np.int64)
                out[: len(a)] = a
                parts.append(out)
            else:
                parts.append(a)
        stacked[key] = np.stack(parts)

    return SparseBatch(
        values=jnp.asarray(stacked["values"], batch.dtype),
        rows=jnp.asarray(stacked["rows"], jnp.int32),
        cols=jnp.asarray(stacked["cols"], jnp.int32),
        labels=jnp.asarray(stacked["labels"], batch.dtype),
        offsets=jnp.asarray(stacked["offsets"], batch.dtype),
        weights=jnp.asarray(stacked["weights"], batch.dtype),
        num_features=batch.num_features,
    )


def put_sharded(stacked, mesh: Mesh, axis: str = DATA_AXIS):
    """Place a host-stacked batch (any layout pytree with a leading shard
    axis on every leaf) so shard i's block lives on device i."""
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), stacked)
