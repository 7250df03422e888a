"""Tiled one-hot-matmul sparse layout: the TPU fast path for GLM passes.

The padded-COO :class:`~photon_ml_tpu.ops.sparse.SparseBatch` computes
margins/gradients with XLA gather/scatter, which on TPU is random-access
bound (~100-150M elem/s; PERF_NOTES.md). This module reaches HBM/MXU speed
instead by removing ALL random access:

  - Rows are grouped into tiles of R=128 consecutive rows. Each tile's nnz
    become a fixed-length slot list of (value, col_hi, col_lo, row_local)
    where ``col = col_hi * 128 + col_lo`` and ``row_local = row % 128``.
  - The coefficient vector lives as a [B, 128] grid (B = ceil(F/128)).
  - Gathering w[col] per slot = one-hot(col_hi) @ w2, then a masked
    product with one-hot(col_lo) reduced BY MATVEC against a ones vector.
  - Scattering per-slot contributions into feature space = the transposed
    one-hot matmul into a [128, B] accumulator (the [S, B] mask side is
    the smaller elementwise operand).
  - EVERY reduction and row broadcast rides the MXU: these kernels are
    VPU-bound (mask construction + elementwise chains saturate the vector
    unit while the MXU idles at ~3% — PERF_NOTES.md roofline), so lane
    shuffle-reduces and [S, 128] row-mask broadcasts are replaced by
    matmuls against the TRANSPOSED row one-hot mask_rT [R, S]. Measured:
    margins 75 -> 39 ms, fused value+grad 91 -> 62 ms (v5e, config below).
  - f32 exactness comes from bf16x2 splits (x = hi + lo in bfloat16,
    products against 0/1 masks are exact, MXU accumulates in f32). The
    split MUST happen inside the kernel: XLA's
    ``--xla_allow_excess_precision`` folds ``bf16(x - f32(bf16(x)))`` to
    zero, silently degrading the pass to single-bf16 (measured 2e-3
    gradient error; in-kernel split measures ~5e-6). Mosaic's
    precision=HIGHEST f32 matmul measures 5e-3 — not a substitute.

Measured on TPU v5e (1M rows x 10K features, 20 nnz/row): one fused
value+grad pass ~62 ms vs ~650 ms for the XLA gather/scatter path (~10x);
the margin-carrying LBFGS iteration is one dot_rows (~39 ms) plus one
scatter pass.

This replaces the hot loop the reference distributes over a Spark cluster
(ValueAndGradientAggregator.scala:132-153) with on-chip matmuls.

Skew note: the slot-list length S is the max nnz over tiles; heavily skewed
row lengths inflate padding. The layout builder reports waste; callers with
pathological rows should pre-shuffle rows (any order is fine — tiles are
independent).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.ops.sparse import SparseBatch, validate_coo_indices

Array = jax.Array

LANE = 128
ROWS_PER_TILE = 128


def _interpret() -> bool:
    """Pallas TPU kernels run in interpret mode on non-TPU backends (tests)."""
    return jax.default_backend() != "tpu"


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mm2(a, bh, bl):
    """Exact a @ (bh + bl): bf16 one-hot x bf16x2 table, f32 accumulation."""
    x = jax.lax.dot_general(
        a, bh, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return x + jax.lax.dot_general(
        a, bl, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _mmT2(a, bh, bl):
    """Exact a^T @ (bh + bl) (contract slot dim 0)."""
    x = jax.lax.dot_general(
        a, bh, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return x + jax.lax.dot_general(
        a, bl, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _slot_contrib(vals, w_ref, mask_hi, mask_lo):
    """Per-slot vals_s * w[col_s] as an [S, 1] f32 column.

    All reductions ride the MXU: the lane pick + sum is a masked-product
    matvec against a ones vector instead of a 128-lane shuffle reduce
    (measured ~30% kernel time on v5e; the VPU is this kernel family's
    critically saturated unit — see PERF_NOTES roofline)."""
    w = w_ref[:]
    whi, wlo = _split_bf16(w)
    wrow = _mm2(mask_hi, whi, wlo)                    # [S, 128] f32
    e = (wrow * mask_lo) * vals[:, None]              # one lane nonzero
    eh, el = _split_bf16(e)
    ones = jnp.ones((LANE, 1), jnp.bfloat16)
    g = jax.lax.dot_general(
        eh, ones, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return g + jax.lax.dot_general(
        el, ones, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [S, 1]


def _rowsum_mxu(contrib_col, mask_rT):
    """[S, 1] per-slot contributions -> [1, R] per-row sums via the
    TRANSPOSED row one-hot ON THE MXU ([R,S] @ [S,1], bf16x2 exact).
    Both row ops use mask_rT so Mosaic sees only (1,0)-contractions."""
    ch, cl = _split_bf16(contrib_col)
    return _mm2(mask_rT, ch, cl).reshape(1, -1)       # [R, 1] -> [1, R]


def _row_margins(vals, mask_rT, w_ref, mask_hi, mask_lo):
    """Per-row margin sums [1, R] for one tile (shared kernel body)."""
    return _rowsum_mxu(_slot_contrib(vals, w_ref, mask_hi, mask_lo), mask_rT)


def _slots_of_rows(per_row, mask_rT):
    """Broadcast a [1, R] per-row vector to slots ([S, 1]) via the
    transposed row one-hot matvec (exact: per_row splits bf16x2)."""
    ph, plo = _split_bf16(per_row)
    s_row = jax.lax.dot_general(
        ph, mask_rT, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_row = s_row + jax.lax.dot_general(
        plo, mask_rT, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [1, S]
    return s_row.reshape(-1, 1)


def _scatter_accum(out_ref, per_slot, mask_hi, mask_lo):
    """Accumulate sum_s per_slot[s]*onehot(col_s) into the TRANSPOSED
    [LANE, B] accumulator: tmp = per_slot ⊙ mask_hi is [S, B] (the smaller
    mask side), then mask_lo^T @ tmp on the MXU (bf16x2 exact)."""
    tmp = per_slot * mask_hi                          # [S, B]
    th, tl = _split_bf16(tmp)
    out_ref[:] = out_ref[:] + _mmT2(mask_lo, th, tl)  # [LANE, B]


def _masks(hi_ref, lo_ref, rlo_ref, S: int, B: int):
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (S, B), 1)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (S, LANE), 1)
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (LANE, S), 0)
    mask_hi = (hi_ref[0, 0, :][:, None] == iota_b).astype(jnp.bfloat16)
    mask_lo = (lo_ref[0, 0, :][:, None] == iota_l).astype(jnp.bfloat16)
    # row one-hot in TRANSPOSED [R, S] orientation: every use is then a
    # standard (1,0) MXU contraction (Mosaic rejects dim-1 contractions)
    mask_rT = (rlo_ref[0, 0, :][None, :] == iota_r).astype(jnp.bfloat16)
    return mask_hi, mask_lo, mask_rT


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _margins_kernel(use_offsets: bool, pair: bool,
                    *refs):
    """z = per-row sum of vals * w[col] (+offsets +shift).

    With ``pair`` a second table v is gathered in the same sweep (shares all
    masks): used for (margins(w), dot_rows(p)) in one pass per LBFGS line
    search, and for (margins(w), dot_rows(v)) in Hessian-vector products.
    """
    if pair:
        (vals_ref, hi_ref, lo_ref, rlo_ref, off_ref, w_ref, v_ref,
         shift_ref, out_z_ref, out_u_ref) = refs
    else:
        (vals_ref, hi_ref, lo_ref, rlo_ref, off_ref, w_ref,
         shift_ref, out_z_ref) = refs
    S = vals_ref.shape[2]
    B = w_ref.shape[0]
    mask_hi, mask_lo, mask_rT = _masks(hi_ref, lo_ref, rlo_ref, S, B)
    vals = vals_ref[0, 0, :]

    z = _row_margins(vals, mask_rT, w_ref, mask_hi, mask_lo) + shift_ref[0, 0]
    if use_offsets:
        z = z + off_ref[0, :, :]
    out_z_ref[0, :, :] = z

    if pair:
        u = _row_margins(vals, mask_rT, v_ref, mask_hi, mask_lo)
        out_u_ref[0, :, :] = u + shift_ref[0, 1]


def _scatter_kernel(square: bool, *refs):
    """g = sum_i per_row[i] * x_i (or x_i^2): transposed one-hot matmul."""
    (vals_ref, hi_ref, lo_ref, rlo_ref, pr_ref, out_g_ref) = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_g_ref[:] = jnp.zeros_like(out_g_ref)

    S = vals_ref.shape[2]
    B = out_g_ref.shape[1]
    mask_hi, mask_lo, mask_rT = _masks(hi_ref, lo_ref, rlo_ref, S, B)
    vals = vals_ref[0, 0, :]
    if square:
        vals = vals * vals

    per_slot = _slots_of_rows(pr_ref[0, :, :], mask_rT) * vals[:, None]
    _scatter_accum(out_g_ref, per_slot, mask_hi, mask_lo)


def _value_grad_kernel(loss_name: str, use_offsets: bool, *refs):
    """Fused weighted loss value + raw gradient scatter + sum(weights*dz)."""
    (vals_ref, hi_ref, lo_ref, rlo_ref, lab_ref, wgt_ref, off_ref,
     w_ref, shift_ref, out_s_ref, out_g_ref) = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_s_ref[:] = jnp.zeros_like(out_s_ref)
        out_g_ref[:] = jnp.zeros_like(out_g_ref)

    S = vals_ref.shape[2]
    B = w_ref.shape[0]
    mask_hi, mask_lo, mask_rT = _masks(hi_ref, lo_ref, rlo_ref, S, B)
    vals = vals_ref[0, 0, :]

    z = _row_margins(vals, mask_rT, w_ref, mask_hi, mask_lo) + shift_ref[0, 0]
    if use_offsets:
        z = z + off_ref[0, :, :]

    loss = get_loss(loss_name)
    y = lab_ref[0, :, :]
    wgt = wgt_ref[0, :, :]
    l, dz = loss.loss_and_dz(z, y)
    g_row = wgt * dz                                   # [1, R]
    sums = jnp.stack([jnp.sum(wgt * l), jnp.sum(g_row)]).reshape(1, 2)
    out_s_ref[:] = out_s_ref[:] + sums

    per_slot = _slots_of_rows(g_row, mask_rT) * vals[:, None]
    _scatter_accum(out_g_ref, per_slot, mask_hi, mask_lo)


def _hv_kernel(loss_name: str, use_offsets: bool, *refs):
    """Fused Hessian-vector sweep: gather z = margins(w) and u = dot(v) from
    the same masks, form q = weight * l''(z) * u, scatter q into feature
    space and accumulate sum(q) — TRON's CG step in ONE data pass (the
    composed margins_pair + scatter path costs two)."""
    (vals_ref, hi_ref, lo_ref, rlo_ref, lab_ref, wgt_ref, off_ref,
     w_ref, v_ref, shift_ref, out_s_ref, out_g_ref) = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_s_ref[:] = jnp.zeros_like(out_s_ref)
        out_g_ref[:] = jnp.zeros_like(out_g_ref)

    S = vals_ref.shape[2]
    B = w_ref.shape[0]
    mask_hi, mask_lo, mask_rT = _masks(hi_ref, lo_ref, rlo_ref, S, B)
    vals = vals_ref[0, 0, :]

    z = _row_margins(vals, mask_rT, w_ref, mask_hi, mask_lo) + shift_ref[0, 0]
    if use_offsets:
        z = z + off_ref[0, :, :]
    u = _row_margins(vals, mask_rT, v_ref, mask_hi, mask_lo) + shift_ref[0, 1]

    loss = get_loss(loss_name)
    q_row = wgt_ref[0, :, :] * loss.d2z(z, lab_ref[0, :, :]) * u   # [1, R]
    out_s_ref[:] = out_s_ref[:] + jnp.stack(
        [jnp.sum(q_row), jnp.float32(0.0)]).reshape(1, 2)

    per_slot = _slots_of_rows(q_row, mask_rT) * vals[:, None]
    _scatter_accum(out_g_ref, per_slot, mask_hi, mask_lo)


def _hv_at_kernel(*refs):
    """Hessian-vector sweep with the margin-derived row curvature d2 =
    weight * l''(z) PRECOMPUTED: gather u = dot(v), form q = d2 * u,
    scatter q and accumulate sum(q) — one pass, one gather + one scatter
    matmul (vs _hv_kernel's two gathers + scatter; TRON CG holds z fixed
    for its whole inner loop)."""
    (vals_ref, hi_ref, lo_ref, rlo_ref, d2_ref, v_ref, shift_ref,
     out_s_ref, out_g_ref) = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_s_ref[:] = jnp.zeros_like(out_s_ref)
        out_g_ref[:] = jnp.zeros_like(out_g_ref)

    S = vals_ref.shape[2]
    B = v_ref.shape[0]
    mask_hi, mask_lo, mask_rT = _masks(hi_ref, lo_ref, rlo_ref, S, B)
    vals = vals_ref[0, 0, :]

    u = _row_margins(vals, mask_rT, v_ref, mask_hi, mask_lo) + shift_ref[0, 0]
    q_row = d2_ref[0, :, :] * u  # [1, R]
    out_s_ref[:] = out_s_ref[:] + jnp.stack(
        [jnp.sum(q_row), jnp.float32(0.0)]).reshape(1, 2)

    per_slot = _slots_of_rows(q_row, mask_rT) * vals[:, None]
    _scatter_accum(out_g_ref, per_slot, mask_hi, mask_lo)


# Every pallas_call below carries a ``name``: it becomes the custom call's
# HLO instruction name (``%tiled_margins.1 = ... custom-call(...)``), which
# is what a device event's name starts with in a profiler trace — the one
# handle by which a reduction tells these kernels apart.


def _spec_s(S):
    return pl.BlockSpec((1, 1, S), lambda i: (i, 0, 0), memory_space=pltpu.VMEM)


def _spec_r():
    return pl.BlockSpec((1, 1, ROWS_PER_TILE), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)


def _spec_w(B):
    return pl.BlockSpec((B, LANE), lambda i: (0, 0), memory_space=pltpu.VMEM)


def _spec_acc(shape):
    return pl.BlockSpec(shape, lambda i: (0, 0), memory_space=pltpu.VMEM)


@functools.lru_cache(maxsize=None)
def _margins_call(T, S, B, use_offsets, pair, interpret):
    kern = functools.partial(_margins_kernel, use_offsets, pair)
    n_tab = 2 if pair else 1
    out_shape = [jax.ShapeDtypeStruct((T, 1, ROWS_PER_TILE), jnp.float32)]
    out_specs = [_spec_r()]
    if pair:
        out_shape.append(jax.ShapeDtypeStruct((T, 1, ROWS_PER_TILE), jnp.float32))
        out_specs.append(_spec_r())
    return pl.pallas_call(
        kern,
        grid=(T,),
        in_specs=[_spec_s(S)] * 4 + [_spec_r()] + [_spec_w(B)] * n_tab
        + [pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM)],
        out_specs=out_specs if pair else out_specs[0],
        out_shape=out_shape if pair else out_shape[0],
        interpret=interpret,
        name="tiled_margins",
    )


@functools.lru_cache(maxsize=None)
def _scatter_call(T, S, B, square, interpret):
    kern = functools.partial(_scatter_kernel, square)
    return pl.pallas_call(
        kern,
        grid=(T,),
        in_specs=[_spec_s(S)] * 4 + [_spec_r()],
        out_specs=_spec_acc((LANE, B)),
        out_shape=jax.ShapeDtypeStruct((LANE, B), jnp.float32),
        interpret=interpret,
        name="tiled_scatter",
    )


@functools.lru_cache(maxsize=None)
def _hv_call(T, S, B, loss_name, use_offsets, interpret):
    kern = functools.partial(_hv_kernel, loss_name, use_offsets)
    return pl.pallas_call(
        kern,
        grid=(T,),
        in_specs=[_spec_s(S)] * 4 + [_spec_r()] * 3 + [_spec_w(B)] * 2
        + [pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM)],
        out_specs=[_spec_acc((1, 2)), _spec_acc((LANE, B))],
        out_shape=[
            jax.ShapeDtypeStruct((1, 2), jnp.float32),
            jax.ShapeDtypeStruct((LANE, B), jnp.float32),
        ],
        interpret=interpret,
        name="tiled_hv",
    )


@functools.lru_cache(maxsize=None)
def _hv_at_call(T, S, B, interpret):
    return pl.pallas_call(
        _hv_at_kernel,
        grid=(T,),
        in_specs=[_spec_s(S)] * 4 + [_spec_r()] + [_spec_w(B)]
        + [pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM)],
        out_specs=[_spec_acc((1, 2)), _spec_acc((LANE, B))],
        out_shape=[
            jax.ShapeDtypeStruct((1, 2), jnp.float32),
            jax.ShapeDtypeStruct((LANE, B), jnp.float32),
        ],
        interpret=interpret,
        name="tiled_hv_at",
    )


@functools.lru_cache(maxsize=None)
def _value_grad_call(T, S, B, loss_name, use_offsets, interpret):
    kern = functools.partial(_value_grad_kernel, loss_name, use_offsets)
    return pl.pallas_call(
        kern,
        grid=(T,),
        in_specs=[_spec_s(S)] * 4 + [_spec_r()] * 3 + [_spec_w(B)]
        + [pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM)],
        out_specs=[_spec_acc((1, 2)), _spec_acc((LANE, B))],
        out_shape=[
            jax.ShapeDtypeStruct((1, 2), jnp.float32),
            jax.ShapeDtypeStruct((LANE, B), jnp.float32),
        ],
        interpret=interpret,
        name="tiled_value_grad",
    )


# ---------------------------------------------------------------------------
# TiledBatch
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TiledBatch:
    """Sparse labeled examples in the tiled one-hot-matmul layout.

    Duck-type compatible with :class:`SparseBatch` for everything
    :class:`~photon_ml_tpu.ops.objective.GLMObjective` and the optimizer
    adapters use (margins / dot_rows / scatter_features / scatter_features_sq
    / labels / offsets / weights / num_features / num_rows), so it drops into
    every existing solve path unchanged. ``num_rows`` is padded to a multiple
    of 128; padded rows carry weight 0.

    ``shard`` is set by ``parallel.sharding.place_batch`` when the tile
    leaves are committed ``NamedSharding(mesh, P(axis))``: the Mosaic
    compiler refuses to partition a kernel under GSPMD, so every kernel
    below then runs per shard inside ``jax.shard_map`` over ``axis``
    (:meth:`_run`). Everything outside the kernels stays GSPMD.
    """

    vals: Array      # f32[T, 1, S] slot values (0 in padding)
    hi: Array        # i32[T, 1, S] col // 128 (== B sentinel in padding)
    lo: Array        # i32[T, 1, S] col % 128
    rlo: Array       # i32[T, 1, S] row % 128
    labels3: Array   # f32[T, 1, 128]
    offsets3: Array  # f32[T, 1, 128]
    weights3: Array  # f32[T, 1, 128]; 0 for padded rows
    num_features: int = dataclasses.field(metadata=dict(static=True))
    # (mesh, batch axis name) the tile leaves are sharded over, else None
    shard: Optional[tuple[Mesh, str]] = dataclasses.field(
        default=None, metadata=dict(static=True))

    # -- shape views --------------------------------------------------------

    @property
    def num_tiles(self) -> int:
        return self.vals.shape[0]

    @property
    def num_rows(self) -> int:
        return self.num_tiles * ROWS_PER_TILE

    @property
    def nnz_slots(self) -> int:
        return self.vals.shape[0] * self.vals.shape[2]

    @property
    def num_blocks(self) -> int:
        return -(-self.num_features // LANE)

    @property
    def dtype(self):
        return self.vals.dtype

    @property
    def labels(self) -> Array:
        return self.labels3.reshape(-1)

    @property
    def offsets(self) -> Array:
        return self.offsets3.reshape(-1)

    @property
    def weights(self) -> Array:
        return self.weights3.reshape(-1)

    # -- construction -------------------------------------------------------

    @staticmethod
    def pack_coo(
        values: np.ndarray,
        rows: np.ndarray,
        cols: np.ndarray,
        labels: np.ndarray,
        num_features: int,
        offsets: Optional[np.ndarray] = None,
        weights: Optional[np.ndarray] = None,
    ) -> "TiledBatch":
        """Host-side layout build: group nnz by row tile, pad to max. The
        leaves stay HOST numpy arrays; :meth:`device` places them."""
        n = int(len(labels))
        R = ROWS_PER_TILE
        T = max(-(-n // R), 1)
        B = -(-int(num_features) // LANE)
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        values = np.asarray(values, np.float64)
        validate_coo_indices(rows, cols, n, num_features)

        tile = rows // R
        if len(tile) and not np.all(tile[1:] >= tile[:-1]):
            order = np.argsort(tile, kind="stable")
            tile_s = tile[order]
            rows = rows[order]
            cols = cols[order]
            values = values[order]
        else:  # ingest emits row-sorted COO — skip the nnz sort
            tile_s = tile
        starts = np.searchsorted(tile_s, np.arange(T))
        counts = np.diff(np.append(starts, len(tile_s)))
        S = int(max(LANE, -(-int(counts.max(initial=0)) // LANE) * LANE))
        offs = np.arange(len(tile_s)) - starts[tile_s]
        dest = tile_s * S + offs

        vals2 = np.zeros((T * S,), np.float32)
        hi2 = np.full((T * S,), B, np.int32)   # sentinel: one-hot all-zero
        lo2 = np.zeros((T * S,), np.int32)
        rlo2 = np.zeros((T * S,), np.int32)
        vals2[dest] = values
        hi2[dest] = (cols // LANE).astype(np.int32)
        lo2[dest] = (cols % LANE).astype(np.int32)
        rlo2[dest] = (rows % R).astype(np.int32)

        npad = T * R
        lab = np.zeros(npad, np.float32)
        lab[:n] = np.asarray(labels, np.float64)
        off = np.zeros(npad, np.float32)
        if offsets is not None:
            off[:n] = np.asarray(offsets, np.float64)
        wgt = np.zeros(npad, np.float32)
        wgt[:n] = 1.0 if weights is None else np.asarray(weights, np.float64)

        shp = (T, 1, S)
        return TiledBatch(
            vals=vals2.reshape(shp),
            hi=hi2.reshape(shp),
            lo=lo2.reshape(shp),
            rlo=rlo2.reshape(shp),
            labels3=lab.reshape(T, 1, R),
            offsets3=off.reshape(T, 1, R),
            weights3=wgt.reshape(T, 1, R),
            num_features=int(num_features),
        )

    def device(self) -> "TiledBatch":
        """Place the leaves on the default device (host -> device copy of a
        :meth:`pack_coo` / :meth:`pack_batch` layout)."""
        return jax.tree.map(jnp.asarray, self)

    @staticmethod
    def from_coo(*args, **kwargs) -> "TiledBatch":
        """:meth:`pack_coo`, placed on the device."""
        return TiledBatch.pack_coo(*args, **kwargs).device()

    @staticmethod
    def pack_batch(batch: SparseBatch) -> "TiledBatch":
        """Host-side layout of a padded-COO SparseBatch (drops its padding
        slots); leaves stay host numpy arrays."""
        vals = np.asarray(batch.values)
        rows = np.asarray(batch.rows)
        cols = np.asarray(batch.cols)
        keep = vals != 0
        return TiledBatch.pack_coo(
            values=vals[keep],
            rows=rows[keep],
            cols=cols[keep],
            labels=np.asarray(batch.labels),
            num_features=batch.num_features,
            offsets=np.asarray(batch.offsets),
            weights=np.asarray(batch.weights),
        )

    @staticmethod
    def from_batch(batch: SparseBatch) -> "TiledBatch":
        """:meth:`pack_batch`, placed on the device."""
        return TiledBatch.pack_batch(batch).device()

    @staticmethod
    def from_dense(X, labels, offsets=None, weights=None) -> "TiledBatch":
        X = np.asarray(X)
        rows, cols = np.nonzero(X)
        return TiledBatch.from_coo(
            values=X[rows, cols], rows=rows, cols=cols, labels=labels,
            num_features=X.shape[1], offsets=offsets, weights=weights,
        )

    def to_dense(self) -> np.ndarray:
        """Host-side densify (tests / diagnostics only)."""
        T, _, S = self.vals.shape
        X = np.zeros((self.num_rows, self.num_features), np.float64)
        vals = np.asarray(self.vals).reshape(-1)
        hi = np.asarray(self.hi).reshape(-1)
        lo = np.asarray(self.lo).reshape(-1)
        rlo = np.asarray(self.rlo).reshape(-1)
        tiles = np.repeat(np.arange(T), S)
        keep = hi < self.num_blocks
        col = hi[keep] * LANE + lo[keep]
        row = tiles[keep] * ROWS_PER_TILE + rlo[keep]
        np.add.at(X, (row, col), vals[keep])
        return X

    # -- device kernels ------------------------------------------------------

    def _w2(self, w: Array) -> Array:
        """Pad a [F] vector to the [B, 128] coefficient grid."""
        B = self.num_blocks
        pad = B * LANE - self.num_features
        return jnp.pad(w.astype(jnp.float32), (0, pad)).reshape(B, LANE)

    def _slot_args(self):
        return (self.vals, self.hi, self.lo, self.rlo)

    def _run(self, make_call, tile_args, rep_args, reduce: bool):
        """Run ``make_call(T)`` — a pallas_call over T tiles — on this
        batch. ``tile_args`` lead with the tile dim, ``rep_args`` (the
        coefficient grids and shifts) are whole on every device.

        Under ``shard`` each device runs the kernel on its own T/n tiles
        inside ``jax.shard_map``; with ``reduce`` the outputs are feature-
        space accumulators and are ``psum``med over the batch axis,
        otherwise they are per-row and stay sharded like the tiles."""
        if self.shard is None:
            return make_call(self.num_tiles)(*tile_args, *rep_args)
        mesh, axis = self.shard
        call = make_call(self.num_tiles // mesh.shape[axis])

        def local(*args):
            out = call(*args)
            return jax.lax.psum(out, axis) if reduce else out

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis),) * len(tile_args) + (P(),) * len(rep_args),
            out_specs=P() if reduce else P(axis),
            check_vma=False,  # pallas_call carries no vma rule
        )(*tile_args, *rep_args)

    def margins(self, w: Array, shift: Array | float = 0.0) -> Array:
        """Per-row margins z_i = x_i . w + shift + offset_i."""
        S, B = self.vals.shape[2], self.num_blocks
        sh = jnp.stack([jnp.asarray(shift, jnp.float32), jnp.float32(0)])
        z = self._run(
            lambda T: _margins_call(T, S, B, True, False, _interpret()),
            (*self._slot_args(), self.offsets3),
            (self._w2(w), sh.reshape(1, 2)), reduce=False)
        return z.reshape(-1)

    def dot_rows(self, w: Array) -> Array:
        """Per-row raw dot products x_i . w (no offset/shift)."""
        S, B = self.vals.shape[2], self.num_blocks
        z = self._run(
            lambda T: _margins_call(T, S, B, False, False, _interpret()),
            (*self._slot_args(), self.offsets3),
            (self._w2(w), jnp.zeros((1, 2), jnp.float32)), reduce=False)
        return z.reshape(-1)

    def margins_pair(
        self, w: Array, shift, p: Array, p_shift
    ) -> tuple[Array, Array]:
        """(margins(w, shift), dot_rows(p) + p_shift) in one fused sweep."""
        S, B = self.vals.shape[2], self.num_blocks
        sh = jnp.stack([
            jnp.asarray(shift, jnp.float32), jnp.asarray(p_shift, jnp.float32)
        ])
        z, u = self._run(
            lambda T: _margins_call(T, S, B, True, True, _interpret()),
            (*self._slot_args(), self.offsets3),
            (self._w2(w), self._w2(p), sh.reshape(1, 2)), reduce=False)
        return z.reshape(-1), u.reshape(-1)

    def _features(self, g: Array) -> Array:
        """[LANE, B] accumulator -> [F]: feature b*128 + j lives at [j, b]."""
        return g.T.reshape(-1)[: self.num_features]

    def _rows3(self, per_row: Array) -> Array:
        """[n_pad] per-row vector -> the [T, 1, 128] tile grid."""
        return per_row.astype(jnp.float32).reshape(
            self.num_tiles, 1, ROWS_PER_TILE)

    def _scatter(self, per_row: Array, square: bool) -> Array:
        S, B = self.vals.shape[2], self.num_blocks
        g = self._run(
            lambda T: _scatter_call(T, S, B, square, _interpret()),
            (*self._slot_args(), self._rows3(per_row)), (), reduce=True)
        return self._features(g)

    def scatter_features(self, per_row: Array) -> Array:
        """sum_i per_row[i] * x_i as a dense feature-space vector."""
        return self._scatter(per_row, False)

    def scatter_features_sq(self, per_row: Array) -> Array:
        """sum_i per_row[i] * (x_i ** 2) (Hessian diagonal)."""
        return self._scatter(per_row, True)

    def fused_value_grad(
        self, w: Array, shift, loss_name: str
    ) -> tuple[Array, Array, Array]:
        """(sum_i wgt_i*l(z_i), raw feature-space gradient, sum_i wgt_i*dz_i).

        The raw gradient is the un-normalized scatter sum_i wgt_i*dz_i*x_i;
        the caller applies normalization back-transform and regularization
        (GLMObjective.value_and_grad fast path).
        """
        S, B = self.vals.shape[2], self.num_blocks
        sh = jnp.stack([jnp.asarray(shift, jnp.float32), jnp.float32(0)])
        sums, g = self._run(
            lambda T: _value_grad_call(
                T, S, B, loss_name, True, _interpret()),
            (*self._slot_args(), self.labels3, self.weights3, self.offsets3),
            (self._w2(w), sh.reshape(1, 2)), reduce=True)
        return sums[0, 0], self._features(g), sums[0, 1]

    def fused_hessian_vector(
        self, w: Array, shift, v: Array, v_shift, loss_name: str
    ) -> tuple[Array, Array]:
        """(raw Hv scatter sum_i wgt_i*l''(z_i)*(x_i.v)*x_i, sum of the
        per-row q = wgt*l''*u terms) in ONE fused sweep (TRON CG fast path).
        Caller applies normalization back-transform and the L2 term."""
        S, B = self.vals.shape[2], self.num_blocks
        sh = jnp.stack([
            jnp.asarray(shift, jnp.float32), jnp.asarray(v_shift, jnp.float32)
        ])
        sums, g = self._run(
            lambda T: _hv_call(T, S, B, loss_name, True, _interpret()),
            (*self._slot_args(), self.labels3, self.weights3, self.offsets3),
            (self._w2(w), self._w2(v), sh.reshape(1, 2)), reduce=True)
        return self._features(g), sums[0, 0]

    def fused_hv_at(
        self, d2_row: Array, v_eff: Array, v_shift
    ) -> tuple[Array, Array]:
        """(raw Hv scatter, sum q) with the row curvature d2 = wgt*l''(z)
        precomputed: ONE pass doing gather u + scatter q (TRON CG holds z
        fixed across its inner loop)."""
        S, B = self.vals.shape[2], self.num_blocks
        sh = jnp.stack([jnp.asarray(v_shift, jnp.float32), jnp.float32(0)])
        sums, g = self._run(
            lambda T: _hv_at_call(T, S, B, _interpret()),
            (*self._slot_args(), self._rows3(d2_row)),
            (self._w2(v_eff), sh.reshape(1, 2)), reduce=True)
        return self._features(g), sums[0, 0]

    def feature_moment_sums(self) -> tuple[Array, Array, Array]:
        """Per-feature (sum x, sum x^2, count nonzero) over valid rows."""
        valid = (self.weights > 0).astype(jnp.float32)
        s1 = self.scatter_features(valid)
        s2 = self.scatter_features_sq(valid)
        ones = dataclasses.replace(
            self, vals=(self.vals != 0).astype(jnp.float32))
        cnt = ones.scatter_features(valid)
        return s1, s2, cnt

    def with_offsets(self, offsets: Array) -> "TiledBatch":
        return dataclasses.replace(
            self,
            offsets3=offsets.astype(jnp.float32).reshape(
                self.num_tiles, 1, ROWS_PER_TILE),
        )
