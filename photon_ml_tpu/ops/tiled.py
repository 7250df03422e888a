"""Tiled one-hot-matmul sparse layout: the TPU fast path for GLM passes.

The padded-COO :class:`~photon_ml_tpu.ops.sparse.SparseBatch` computes
margins/gradients with XLA gather/scatter, which on TPU is random-access
bound (4.4 s a gather pass over 273M nonzeros; PERF.md section 3). This
module reaches HBM/MXU speed instead by removing ALL random access:

  - Rows are grouped into tiles of R=128 consecutive rows. Each tile's nnz
    become a fixed-length slot list of (value, col_hi, col_lo) where
    ``col = col_hi * 128 + col_lo``: 12 bytes a slot.
  - WHOSE A SLOT IS, the design decides at pack time from its own row
    lengths (:func:`strided_is_cheaper` inside :meth:`TiledBatch.pack_coo`).
    STRIDED: slot ``k*128 + r`` of a tile is the k-th nonzero of row r, so
    S = 128 x the longest row, a per-slot row [1, S] is S/128 lane-aligned
    chunks each already in row order, and the kernels need nothing more:
    a row sum is the chunks added up, a per-row value reaches its slots by
    a lane repeat. SORTED: a tile's nonzeros fill its slots in arrival
    order, S is the fullest tile's count, and a fourth array ``row_local =
    row % 128`` (16 bytes a slot) feeds a row one-hot ``rt`` and one more
    MXU pass. Rows of one length (or near it) go strided; a few very long
    rows among short ones would pad every row to the longest and stay
    sorted.
  - The coefficient vector lives as a [B8, 128] grid (B8 = B rounded up to
    the bfloat16 sublane tile of 16; rows from B on are zero).
  - SLOTS STAY ON LANES. The slot arrays arrive lane-major ([1, S] a tile)
    and every per-slot quantity keeps that shape from the block's load to
    the matmul that consumes it; the one-hot axes go on sublanes. The
    masks are transposed one-hots built by comparing a [1, S] row with an
    iota over dim 0 (a sublane broadcast, no relayout): ``hit`` [B8, S] of
    col_hi, ``lot`` [128, S] of col_lo and, sorted only, ``rt`` [R, S] of
    row_local. A per-slot vector is S/128 vregs as a [1, S] row and S/8 as
    an [S, 1] column; the column-shaped kernels this replaces spent 3.0 of
    their 4.9-6.7 us a tile turning rows into columns and building masks
    from them (PERF.md, Findings PR 25: the stubbed-kernel table).
  - Gathering w[col] per slot = ``[w_hi; w_lo] @ lot`` ([2*B8, S]: every
    column block's w[., lo_s] in slot s's lane), then ``where(hit, ., 0)``
    summed over sublanes: one nonzero a column, so the sum is exact. The
    per-row sum is the [1, S] row's chunks added in float32 (strided), or
    an NT contraction over the lane axis of the per-slot rows and ``rt``
    (sorted).
  - Scattering per-slot contributions into feature space = the per-slot
    product split once as a row, each half placed in its column block by
    ``where(hit, ., 0)`` ([2*B8, S]), contracted over the lane axis
    against ``lot`` into a [B8, 128] accumulator laid out like the grid.
  - ``hit`` only feeds selects and stays boolean; ``lot`` and ``rt`` only
    feed the MXU. Every pass of a one-hot through the MXU is S/128 weight
    tiles, each streamed by the other operand's rows. At the shape below a
    pass that streams the 2*B8 = 160 table rows (a gather, a scatter) is
    25.6 ms a call (pair less margins, either assignment), the sorted
    layout's 16-row pass against ``rt`` (the row sum, the rows-to-slots
    broadcast) 4.5-6.4 ms (each kernel's sorted time less its strided),
    and what a call pays whatever it computes ~10 ms = 0.21 us a tile: a
    kernel that only loads the slot blocks and builds ``hit`` takes 16.2 ms,
    and NO vector stage shows in a call's time (``hit`` left out, ``lot``
    built from half as many compares, the two table halves summed inside
    the MXU: 35.2 -> 35.2, 35.0, 35.0 ms; PERF.md, Findings PR 29). Lane
    chunks of the matmuls, the ``wT @ hit`` contraction order (256 rows
    streamed), a ``where`` + reduce row sum over ``rt`` and the accumulator
    the other way up were all timed and are slower (Findings PR 25).
  - SEVERAL TILES A GRID STEP. Since that ~0.2 us a step does not hide, a
    call runs G tiles a step (:func:`tiles_a_step`: the largest divisor of
    its tile count, a shard's under a mesh, up to ``MAX_TILES_A_STEP``
    whose blocks fit the VMEM budget): the blocks are G tiles deep and the
    kernel runs the tile's body for each of them in tile order, so every
    output is the one-tile-a-step call's to the last bit. The K-table
    sweeps below take the same rule with their own cap.
  - f32 exactness comes from bf16x2 splits (x = hi + lo in bfloat16,
    products against 0/1 masks are exact, MXU accumulates in f32). The
    split MUST happen inside the kernel: XLA's
    ``--xla_allow_excess_precision`` folds ``bf16(x - f32(bf16(x)))`` to
    zero, silently degrading the pass to single-bf16 (2e-3 against 3.4e-6
    measured). Mosaic's precision=HIGHEST f32 matmul is not a substitute.
    Contracting the minor axis of both operands (the ``q @ k.T`` form)
    compiles on the jax this tree runs (0.9.0).

Measured alone on one TPU v5 lite at 6M rows x 10K features, 20 nnz/row
(T = 46,875, S = 2,560, B = 79; PERF.md Findings PR 29, "my chip run"),
strided (sorted): margins 35.5 ms (40.0), scatter 34.1 (39.9), margins_pair
61.1 (65.8), fused value+grad 68.1 (79.5), fused Hv 92.3 (105.1), hv_at 65.4
(76.7). Relative L2 error against float64 at that shape: margins 2.4e-6
(3.4e-6), scatter 3.0e-6 (3.9e-6).

This replaces the hot loop the reference distributes over a Spark cluster
(ValueAndGradientAggregator.scala:132-153) with on-chip matmuls.

K tables at once (``project_rows`` / ``scatter_rows``, the factored
coordinate's refit of a [K, d] projection): a strided design serves all K in
ONE sweep of its tiles, the masks built once a tile, several tiles a grid
step, each table one gather or placement pass (``%<prefix>_margins_k`` /
``%<prefix>_scatter_k``; the tables' bf16x2 halves are made once a call by
``%<prefix>_tables_k``). A pass streams 2*B8 table rows a table and 128
slots, so at K = 16 and B = 214 a tile is MXU-bound at ~1.2 us: 264 / 273 ms
a pass over 212,296 tiles of one-hot rows against 1,265 / 1,152 ms for 16
calls of ``dot_rows`` / ``scatter_features`` (PERF.md, Findings PR 33). A
sorted design makes the K calls under one loop.

Those sweeps stream every row of every table past every slot and keep one
in 224. Where a pass of the K tables is contracted with a per-row [K]
vector on the way (``ContractedRows``: the refit's margins and gradient)
and the design has ONE nonzero a row, :class:`ColumnSortedTiles` holds the
rows a second time, sorted by column: a tile's slots then lie in one window
of ``WINDOW`` = 16 table rows, the window of all K tables is ONE matmul's
left operand (2K x 16 rows against the 2K x 224 above), and only margins
[rows] or the [K, F] gradient leave the kernel
(``%<prefix>_margins_k_sorted`` / ``%<prefix>_scatter_k_sorted``; PERF.md,
Findings PR 34).

Width and skew: a pass costs slots x B (the [2*B8, S] intermediates above),
so this layout is for designs of up to ~128 column blocks; S is the longest
row's or the fullest tile's, so ragged row lengths pad (rows or tiles: the
rule takes the cheaper). ``ops/panels.py::pack_design``
chooses between this layout and the column panels from the design's own
width and column histogram, uses these kernels unchanged for the panels' hot
part, and reports slots against nonzeros (gauge ``layout.padding_ratio``).
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
from photon_ml_tpu.ops.sparse import (
    ContractedRows,
    SparseBatch,
    validate_coo_indices,
)

Array = jax.Array

LANE = 128
ROWS_PER_TILE = 128

# one pass of a one-hot through the MXU, per 128 slots (PERF.md, PR 25): the
# weight loads' floor, or the rows streamed against them
PASS_FLOOR_NS = 15.0
PASS_ROW_NS = 0.167


def _pass_ns(rows: int) -> float:
    return max(PASS_FLOOR_NS, PASS_ROW_NS * rows)


def strided_is_cheaper(s_strided: int, s_sorted: int, num_blocks: int) -> bool:
    """Whether a design's tiles cost less with slot ``k*128 + r`` given to
    row r (``s_strided`` = 128 x the longest row, one pass of the 2*B8 table
    rows a call) than with slots in arrival order (``s_sorted`` = the
    fullest tile's count, that pass and the 16-row pass against the row
    one-hot ``rt``): modelled time a tile, from the design's own row
    lengths. Constant-length rows go strided; a few very long rows among
    short ones would pad every row to the longest and stay sorted."""
    table = _pass_ns(2 * _table_rows(num_blocks))
    return s_strided * table <= s_sorted * (table + _pass_ns(16))


def _bincount(x: np.ndarray, n: int) -> np.ndarray:
    """``np.bincount`` in blocks (it widens its whole input to int64)."""
    out = np.zeros(n, np.int64)
    step = 1 << 24
    for s in range(0, len(x), step):
        out += np.bincount(x[s:s + step], minlength=n)
    return out


def _interpret() -> bool:
    """Pallas TPU kernels run in interpret mode on non-TPU backends (tests)."""
    return jax.default_backend() != "tpu"


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T: contracts the lane axis of both


def _dot(a, b, dims):
    """bf16 x bf16 on the MXU, float32 accumulation."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=dims, preferred_element_type=jnp.float32)


def _table_rows(B: int) -> int:
    """Rows of a coefficient grid inside the kernels: B rounded up to the
    bfloat16 sublane tile, so the stacked halves concatenate aligned."""
    return -(-B // 16) * 16


def _onehot_t(idx_row, n: int):
    """[1, S] int32 -> bool [n, S]: row k is ``idx == k``. The indices stay
    on the lane axis (a sublane broadcast against an iota, no relayout)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, idx_row.shape[1]), 0)
    return idx_row == iota


def _slot_refs(strided: bool, refs):
    """(vals, hi, lo, rlo or None, the other refs) of a kernel's refs: a
    strided design has no ``rlo`` block."""
    if strided:
        return (*refs[:3], None, refs[3:])
    return (*refs[:4], refs[4:])


def _tile_masks(hi_ref, lo_ref, rlo_ref, B8: int, t: int):
    """The transposed one-hots of tile ``t`` of a grid step, slots on lanes.

    ``hit`` [B8, S] only ever feeds a ``where`` and stays boolean. A padding
    slot carries the sentinel ``hi == B``: where B8 > B that is row B of
    ``hit``, which is a zero row of every table (:meth:`TiledBatch._w2`)
    and an accumulator row :meth:`TiledBatch._features` drops; where
    B8 == B it matches no row. ``lot`` [128, S] and ``rt`` [R, S] only
    ever feed the MXU and are converted once. ``rt`` is the sorted
    layout's alone: a strided tile (``rlo_ref`` None) has none, its slot
    ``k*128 + r`` IS row r's."""
    hit = _onehot_t(hi_ref[t], B8)
    lot = _onehot_t(lo_ref[t], LANE).astype(jnp.bfloat16)
    if rlo_ref is None:
        return hit, lot, None
    rt = _onehot_t(rlo_ref[t], ROWS_PER_TILE).astype(jnp.bfloat16)
    return hit, lot, rt


def _table2(w_ref):
    """[B8, 128] f32 grid -> its bf16x2 halves stacked [2*B8, 128], so one
    matmul (one set of weight loads) gathers both."""
    return jnp.concatenate(_split_bf16(w_ref[:]), axis=0)


def _stack16(rows):
    """k <= 8 float32 [1, N] rows -> bf16 [16, N] whose row j is the high
    bf16 half of ``rows[j]`` and row 8 + j its low half: the LHS of one
    MXU pass that is exact for all of them (bf16x2, f32 accumulation).
    Rows past k repeat the last one and are never read."""
    n = rows[0].shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (8, n), 0)
    blocks = []
    for half in zip(*(_split_bf16(r) for r in rows)):
        half = [h.astype(jnp.float32) for h in half]
        blk = jnp.broadcast_to(half[-1], (8, n))
        for j in range(len(rows) - 2, -1, -1):
            blk = jnp.where(iota == j, half[j], blk)
        blocks.append(blk)
    return jnp.concatenate(blocks, axis=0).astype(jnp.bfloat16)


def _gather_slots(tab, hit, lot):
    """table[hi_s, lo_s] of every slot as a [1, S] row. ``tab`` is a
    :func:`_table2` grid [2*N, 128], ``hit`` [N, S] the boolean one-hot of
    the slots' table rows and ``lot`` [128, S] of their lanes:
    ``[t_hi; t_lo] @ lot`` puts t[., lo_s] of every table row in slot s's
    lane, ``where(hit, ., 0)`` keeps the slot's own row, and the sublane
    sum has ONE nonzero, so it is exact."""
    N = hit.shape[0]
    g = _dot(tab, lot, _NN)                            # [2*N, S]
    g = jnp.where(hit, g[:N] + g[N:], 0.0)
    return jnp.sum(g, axis=0, keepdims=True)


def _place_slots(p, hit, lot):
    """sum_s p_s * onehot(hi_s, lo_s) as an [N, 128] grid. The per-slot
    row ``p`` [1, S] is split ONCE, each half is placed in the slot's grid
    row by a select (0/1 mask times a split value IS a select), and one NT
    contraction over the lane axis against ``lot`` lands both halves."""
    N = hit.shape[0]
    halves = [jnp.where(hit, h.astype(jnp.float32), 0.0).astype(jnp.bfloat16)
              for h in _split_bf16(p)]
    d = _dot(jnp.concatenate(halves, axis=0), lot, _NT)   # [2*N, 128]
    return d[:N] + d[N:]


def _chunk_sum(row):
    """[1, S] -> [1, 128]: the sum of the S/128 lane-aligned chunks, added
    pairwise (float32 on the VPU)."""
    chunks = [row[:, k:k + LANE] for k in range(0, row.shape[1], LANE)]
    while len(chunks) > 1:
        chunks = [a + b for a, b in zip(chunks[::2], chunks[1::2])] + (
            chunks[-1:] if len(chunks) % 2 else [])
    return chunks[0]


def _row_sums(tabs, vals, hit, lot, rt):
    """Per-row sums of vals_s * table[col_s]: one [1, R] row per table in
    ``tabs`` (stacked :func:`_table2` grids).

    Gather (:func:`_gather_slots`) times ``vals`` as a [1, S] row. Row sum,
    strided (``rt`` None): chunk k of that row holds the k-th nonzero of
    every row in row order, so the chunks are added up. Sorted: an NT
    contraction over the lane axis of the per-slot rows and ``rt``, all
    tables in one :func:`_stack16` LHS."""
    per_slot = [_gather_slots(tab, hit, lot) * vals for tab in tabs]
    if rt is None:
        return [_chunk_sum(p) for p in per_slot]
    z = _dot(_stack16(per_slot), rt, _NT)              # [16, R]
    z = z[:8] + z[8:]
    return [z[j:j + 1] for j in range(len(tabs))]


def _scatter_accum(out_ref, per_row, vals, hit, lot, rt):
    """out[B8, 128] += sum_s per_row[row_s] * vals_s * onehot(col_s).

    ``per_row`` [1, R] reaches the slots by a lane repeat where the tile is
    strided (``rt`` None) and through ``rt`` on the MXU (exact: bf16x2)
    where it is sorted; :func:`_place_slots` lands the per-slot product in
    the accumulator."""
    if rt is None:
        s = jnp.tile(per_row, (1, vals.shape[1] // LANE))
    else:
        s = _dot(_stack16([per_row]), rt, _NN)         # [16, S]
        s = (s[:8] + s[8:])[0:1]
    out_ref[:] = out_ref[:] + _place_slots(s * vals, hit, lot)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _margins_kernel(use_offsets: bool, pair: bool, strided: bool, *refs):
    """z = per-row sum of vals * w[col] (+offsets +shift), for each of the G
    tiles of a grid step (the blocks' leading axis), one after the other.

    With ``pair`` a second table v is gathered in the same sweep (shares all
    masks and the row-sum matmul): used for (margins(w), dot_rows(p)) in one
    pass per LBFGS line search, and for (margins(w), dot_rows(v)) in
    Hessian-vector products.
    """
    vals_ref, hi_ref, lo_ref, rlo_ref, refs = _slot_refs(strided, refs)
    if pair:
        (off_ref, w_ref, v_ref, shift_ref, out_z_ref, out_u_ref) = refs
        tabs = [_table2(w_ref), _table2(v_ref)]
    else:
        (off_ref, w_ref, shift_ref, out_z_ref) = refs
        tabs = [_table2(w_ref)]
    for t in range(vals_ref.shape[0]):
        hit, lot, rt = _tile_masks(hi_ref, lo_ref, rlo_ref, w_ref.shape[0], t)
        sums = _row_sums(tabs, vals_ref[t], hit, lot, rt)
        z = sums[0] + shift_ref[0, 0]
        if use_offsets:
            z = z + off_ref[t]
        out_z_ref[t] = z
        if pair:
            out_u_ref[t] = sums[1] + shift_ref[0, 1]


def _zero_at_first_step(*out_refs):
    """The accumulators start from zero at the call's first grid step."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        for ref in out_refs:
            ref[:] = jnp.zeros_like(ref)


def _scatter_kernel(square: bool, strided: bool, *refs):
    """g = sum_i per_row[i] * x_i (or x_i^2): transposed one-hot matmul,
    the G tiles of a grid step added in tile order."""
    vals_ref, hi_ref, lo_ref, rlo_ref, refs = _slot_refs(strided, refs)
    (pr_ref, out_g_ref) = refs
    _zero_at_first_step(out_g_ref)
    for t in range(vals_ref.shape[0]):
        hit, lot, rt = _tile_masks(
            hi_ref, lo_ref, rlo_ref, out_g_ref.shape[0], t)
        vals = vals_ref[t]
        if square:
            vals = vals * vals
        _scatter_accum(out_g_ref, pr_ref[t], vals, hit, lot, rt)


def _value_grad_kernel(loss_name: str, use_offsets: bool, strided: bool,
                       *refs):
    """Fused weighted loss value + raw gradient scatter + sum(weights*dz)."""
    vals_ref, hi_ref, lo_ref, rlo_ref, refs = _slot_refs(strided, refs)
    (lab_ref, wgt_ref, off_ref, w_ref, shift_ref, out_s_ref,
     out_g_ref) = refs
    _zero_at_first_step(out_s_ref, out_g_ref)
    tab = _table2(w_ref)
    loss = get_loss(loss_name)
    for t in range(vals_ref.shape[0]):
        hit, lot, rt = _tile_masks(hi_ref, lo_ref, rlo_ref, w_ref.shape[0], t)
        vals = vals_ref[t]

        z = _row_sums([tab], vals, hit, lot, rt)[0] + shift_ref[0, 0]
        if use_offsets:
            z = z + off_ref[t]

        y = lab_ref[t]
        wgt = wgt_ref[t]
        l, dz = loss.loss_and_dz(z, y)
        g_row = wgt * dz                                   # [1, R]
        sums = jnp.stack([jnp.sum(wgt * l), jnp.sum(g_row)]).reshape(1, 2)
        out_s_ref[:] = out_s_ref[:] + sums

        _scatter_accum(out_g_ref, g_row, vals, hit, lot, rt)


def _hv_kernel(loss_name: str, use_offsets: bool, strided: bool, *refs):
    """Fused Hessian-vector sweep: gather z = margins(w) and u = dot(v) from
    the same masks, form q = weight * l''(z) * u, scatter q into feature
    space and accumulate sum(q) — TRON's CG step in ONE data pass (the
    composed margins_pair + scatter path costs two)."""
    vals_ref, hi_ref, lo_ref, rlo_ref, refs = _slot_refs(strided, refs)
    (lab_ref, wgt_ref, off_ref, w_ref, v_ref, shift_ref, out_s_ref,
     out_g_ref) = refs
    _zero_at_first_step(out_s_ref, out_g_ref)
    tabs = [_table2(w_ref), _table2(v_ref)]
    loss = get_loss(loss_name)
    for t in range(vals_ref.shape[0]):
        hit, lot, rt = _tile_masks(hi_ref, lo_ref, rlo_ref, w_ref.shape[0], t)
        vals = vals_ref[t]

        sums = _row_sums(tabs, vals, hit, lot, rt)
        z = sums[0] + shift_ref[0, 0]
        if use_offsets:
            z = z + off_ref[t]
        u = sums[1] + shift_ref[0, 1]

        q_row = wgt_ref[t] * loss.d2z(z, lab_ref[t]) * u   # [1, R]
        out_s_ref[:] = out_s_ref[:] + jnp.stack(
            [jnp.sum(q_row), jnp.float32(0.0)]).reshape(1, 2)

        _scatter_accum(out_g_ref, q_row, vals, hit, lot, rt)


def _hv_at_kernel(strided: bool, *refs):
    """Hessian-vector sweep with the margin-derived row curvature d2 =
    weight * l''(z) PRECOMPUTED: gather u = dot(v), form q = d2 * u,
    scatter q and accumulate sum(q) — one pass, one gather + one scatter
    matmul (vs _hv_kernel's two gathers + scatter; TRON CG holds z fixed
    for its whole inner loop)."""
    vals_ref, hi_ref, lo_ref, rlo_ref, refs = _slot_refs(strided, refs)
    (d2_ref, v_ref, shift_ref, out_s_ref, out_g_ref) = refs
    _zero_at_first_step(out_s_ref, out_g_ref)
    tab = _table2(v_ref)
    for t in range(vals_ref.shape[0]):
        hit, lot, rt = _tile_masks(hi_ref, lo_ref, rlo_ref, v_ref.shape[0], t)
        vals = vals_ref[t]

        u = _row_sums([tab], vals, hit, lot, rt)[0] + shift_ref[0, 0]
        q_row = d2_ref[t] * u  # [1, R]
        out_s_ref[:] = out_s_ref[:] + jnp.stack(
            [jnp.sum(q_row), jnp.float32(0.0)]).reshape(1, 2)

        _scatter_accum(out_g_ref, q_row, vals, hit, lot, rt)


def _split_tables_kernel(a_ref, out_ref):
    """[K, B8, 128] float32 grids -> their bf16x2 halves stacked
    [K, 2*B8, 128], once a call (inside a kernel: XLA would fold the low
    half to zero, see the module's note on splits)."""
    for l in range(a_ref.shape[0]):
        out_ref[l] = jnp.concatenate(_split_bf16(a_ref[l]), axis=0)


def _project_kernel(K: int, vals_ref, hi_ref, lo_ref, tab_ref, out_ref):
    """P[l] = per-row sum of vals * A[l, col] for the K tables of ``tab``
    ([K, 2*B8, 128]: :func:`_split_tables_kernel`'s halves) over the G
    strided tiles of one grid step: a tile's masks are built once, each
    table is one :func:`_gather_slots` pass. Out [G, K, R]."""
    B8 = tab_ref.shape[1] // 2
    for t in range(vals_ref.shape[0]):
        hit, lot, _ = _tile_masks(hi_ref, lo_ref, None, B8, t)
        vals = vals_ref[t]
        for l in range(K):
            out_ref[t, l:l + 1, :] = _chunk_sum(
                _gather_slots(tab_ref[l], hit, lot) * vals)


def _scatter_k_kernel(K: int, vals_ref, hi_ref, lo_ref, g_ref, out_ref):
    """out[l] += sum_s g[l, row_s] * vals_s * onehot(col_s) for K per-row
    vectors ``g`` [G, K, R] over the G strided tiles of one grid step."""
    _zero_at_first_step(out_ref)
    B8 = out_ref.shape[1]
    for t in range(vals_ref.shape[0]):
        hit, lot, _ = _tile_masks(hi_ref, lo_ref, None, B8, t)
        vals = vals_ref[t]
        reps = vals.shape[1] // LANE
        for l in range(K):
            s = jnp.tile(g_ref[t, l:l + 1, :], (1, reps))
            out_ref[l] = out_ref[l] + _place_slots(s * vals, hit, lot)


#: table rows of a column-sorted tile's window: the bfloat16 sublane tile,
#: so a window of the split tables is an aligned dynamic slice
WINDOW = 16


def sorted_slots(K: int) -> int:
    """Slots of a column-sorted tile for K tables: 4,096, halved while the
    float32 product of the tile's one matmul, [2K*WINDOW, S], is over 8 MiB
    (K = 32: 2,048). A grid step costs ~0.35 us whatever it computes: a pass
    over 18M rows at K = 16 takes 16.3 / 14.7 / 13.8 / 13.4 ms at 1,024 /
    2,048 / 4,096 / 8,192 slots (PERF.md, Findings PR 34)."""
    slots = 4096
    while slots > LANE and 2 * K * WINDOW * slots * 4 > 8 << 20:
        slots //= 2
    return slots


def _window_start(w_ref):
    """First table row of this grid step's window (scalar-prefetched)."""
    return pl.multiple_of(w_ref[pl.program_id(0)] * WINDOW, WINDOW)


def _contract_window_kernel(K: int, w_ref, vals_ref, hi_ref, lo_ref, c_ref,
                            tab_ref, out_ref):
    """z_s = vals_s * sum_l c[l, s] * A[l, col_s] over one column-sorted
    tile, whose S slots all lie in ONE window of ``WINDOW`` table rows.
    ``tab`` [2K, B8, 128] holds the K tables' bf16x2 halves (row 2l the
    high half of table l, 2l + 1 its low half) whole in VMEM; the window of
    all of them is ONE matmul's left operand [2K*WINDOW, 128] against
    ``lot``. The contraction with ``c`` [K, S] comes before the select, so
    only a [WINDOW, S] slab meets ``hit`` and only margins [1, S] leave."""
    win = tab_ref[:, pl.ds(_window_start(w_ref), WINDOW), :]
    lot = _onehot_t(lo_ref[0], LANE).astype(jnp.bfloat16)
    g = _dot(win.reshape(2 * K * WINDOW, LANE), lot, _NN)   # [2K*W, S]
    c = c_ref[...]
    acc = None
    for l in range(K):
        lo_half = (2 * l + 1) * WINDOW
        gl = (g[lo_half - WINDOW:lo_half] + g[lo_half:lo_half + WINDOW]
              ) * c[l:l + 1, :]
        acc = gl if acc is None else acc + gl
    hit = _onehot_t(hi_ref[0], WINDOW)
    out_ref[0] = jnp.sum(
        jnp.where(hit, acc, 0.0), axis=0, keepdims=True) * vals_ref[0]


def _scatter_window_kernel(K: int, square: bool, w_ref, vals_ref, hi_ref,
                           lo_ref, q_ref, c_ref, out_ref):
    """out[l, col_s] += q_s * vals_s * c[l, s] over one column-sorted tile:
    the K per-slot rows, split once, are placed in the window's rows by
    selects and land by ONE NT matmul against ``lot``; the [K, B8, 128]
    accumulator stays in VMEM for the whole call and a tile adds to its
    window of it."""
    _zero_at_first_step(out_ref)
    vals = vals_ref[0]
    if square:
        vals = vals * vals
    halves = _split_bf16(c_ref[...] * (q_ref[0] * vals))        # [K, S] x 2
    hit = _onehot_t(hi_ref[0], WINDOW)
    lot = _onehot_t(lo_ref[0], LANE).astype(jnp.bfloat16)
    placed = [
        jnp.where(hit, h[l:l + 1, :].astype(jnp.float32), 0.0)
        .astype(jnp.bfloat16) for l in range(K) for h in halves]
    d = _dot(jnp.concatenate(placed, axis=0), lot, _NT)         # [2K*W, 128]
    d = d.reshape(K, 2, WINDOW, LANE)
    at = pl.ds(_window_start(w_ref), WINDOW)
    out_ref[:, at, :] = out_ref[:, at, :] + d[:, 0] + d[:, 1]


# Every pallas_call below carries a ``name``: it becomes the custom call's
# HLO instruction name (``%tiled_margins.1 = ... custom-call(...)``), which
# is what a device event's name starts with in a profiler trace — the one
# handle by which a reduction tells these kernels apart.


def _spec_g(G, *tail):
    """G tiles of a [T, *tail] array a grid step."""
    zeros = (0,) * len(tail)
    return pl.BlockSpec((G, *tail), lambda i: (i, *zeros),
                        memory_space=pltpu.VMEM)


def _slot_specs(G, S, strided: bool):
    """The slot blocks of G tiles: vals, hi, lo and, sorted only, rlo."""
    return [_spec_g(G, 1, S)] * (3 if strided else 4)


def _spec_r(G):
    """A per-row array of G tiles: [G, 1, 128]."""
    return _spec_g(G, 1, ROWS_PER_TILE)


def _spec_whole(shape):
    return pl.BlockSpec(shape, lambda i: (0, 0), memory_space=pltpu.VMEM)


def _spec_w(B):
    """A coefficient grid or a feature-space accumulator: [B8, 128]."""
    return _spec_whole((_table_rows(B), LANE))


def _spec_shift():
    return pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM)


def _shape_w(B):
    return jax.ShapeDtypeStruct((_table_rows(B), LANE), jnp.float32)


def _shape_rows(T):
    return jax.ShapeDtypeStruct((T, 1, ROWS_PER_TILE), jnp.float32)


_SUMS = jax.ShapeDtypeStruct((1, 2), jnp.float32)


# A call over T tiles runs G of them a grid step (``tiles_a_step``): its
# blocks hold G tiles and the kernel runs the tile's body G times over them,
# so every output is the one-tile-a-step call's to the last bit.


@functools.lru_cache(maxsize=None)
def _margins_call(T, G, S, B, strided, use_offsets, pair, interpret,
                  name="tiled_margins"):
    kern = functools.partial(_margins_kernel, use_offsets, pair, strided)
    n_tab = 2 if pair else 1
    return pl.pallas_call(
        kern,
        grid=(T // G,),
        in_specs=_slot_specs(G, S, strided) + [_spec_r(G)]
        + [_spec_w(B)] * n_tab + [_spec_shift()],
        out_specs=[_spec_r(G)] * 2 if pair else _spec_r(G),
        out_shape=[_shape_rows(T)] * 2 if pair else _shape_rows(T),
        interpret=interpret,
        name=name,
    )


@functools.lru_cache(maxsize=None)
def _scatter_call(T, G, S, B, strided, square, interpret,
                  name="tiled_scatter"):
    kern = functools.partial(_scatter_kernel, square, strided)
    return pl.pallas_call(
        kern,
        grid=(T // G,),
        in_specs=_slot_specs(G, S, strided) + [_spec_r(G)],
        out_specs=_spec_w(B),
        out_shape=_shape_w(B),
        interpret=interpret,
        name=name,
    )


@functools.lru_cache(maxsize=None)
def _hv_call(T, G, S, B, strided, loss_name, use_offsets, interpret):
    kern = functools.partial(_hv_kernel, loss_name, use_offsets, strided)
    return pl.pallas_call(
        kern,
        grid=(T // G,),
        in_specs=_slot_specs(G, S, strided) + [_spec_r(G)] * 3
        + [_spec_w(B)] * 2 + [_spec_shift()],
        out_specs=[_spec_whole((1, 2)), _spec_w(B)],
        out_shape=[_SUMS, _shape_w(B)],
        interpret=interpret,
        name="tiled_hv",
    )


@functools.lru_cache(maxsize=None)
def _hv_at_call(T, G, S, B, strided, interpret):
    return pl.pallas_call(
        functools.partial(_hv_at_kernel, strided),
        grid=(T // G,),
        in_specs=_slot_specs(G, S, strided) + [_spec_r(G)] + [_spec_w(B)]
        + [_spec_shift()],
        out_specs=[_spec_whole((1, 2)), _spec_w(B)],
        out_shape=[_SUMS, _shape_w(B)],
        interpret=interpret,
        name="tiled_hv_at",
    )


@functools.lru_cache(maxsize=None)
def _value_grad_call(T, G, S, B, strided, loss_name, use_offsets, interpret):
    kern = functools.partial(
        _value_grad_kernel, loss_name, use_offsets, strided)
    return pl.pallas_call(
        kern,
        grid=(T // G,),
        in_specs=_slot_specs(G, S, strided) + [_spec_r(G)] * 3 + [_spec_w(B)]
        + [_spec_shift()],
        out_specs=[_spec_whole((1, 2)), _spec_w(B)],
        out_shape=[_SUMS, _shape_w(B)],
        interpret=interpret,
        name="tiled_value_grad",
    )


#: most tiles a grid step of the standing kernels: a step costs ~0.2 us
#: whatever it computes, and every unrolled tile body adds compile time:
#: 25 is the smallest cap within ~1 ms a call of each benchmark design's best
#: (PERF.md, Findings: the kernel-alone table of G against ms)
MAX_TILES_A_STEP = 25
#: most tiles a grid step of the K-table sweeps, whose tile is K passes
#: (1.2 us at K = 16): a step is 2% of 8 of them
K_SWEEP_TILES_A_STEP = 8
#: scoped VMEM a grid step may take: half the 16 MiB a Mosaic kernel gets
#: on a v5e by default
STEP_VMEM_BYTES = 8 << 20


def _step_vmem_bytes(G: int, S: int, B8: int, strided: bool) -> int:
    """Scoped VMEM of a grid step of G tiles of S slots against [B8, 128]
    tables, for the kernel with the most blocks (``tiled_hv``): the slot
    blocks and three per-row blocks of G tiles, double-buffered, and two
    tables and the accumulator, once. Mosaic keeps a tile's temporaries
    (the [2*B8, S] gather, ``hit``, ``lot``) out of it: the described-v5e
    compile's scoped allocation is this to 2% at G = 1 and 8."""
    slot_arrays = 3 if strided else 4
    return 4 * (2 * G * (slot_arrays * S + 3 * ROWS_PER_TILE)
                + 3 * B8 * LANE)


def tiles_a_step(T: int, S: int, B8: int, strided: bool = True,
                 most: int = MAX_TILES_A_STEP) -> int:
    """Tiles a grid step of a call over T tiles of S slots against tables
    of B8 rows: the largest divisor of T up to ``most`` whose step fits
    ``STEP_VMEM_BYTES`` (1 where none above 1 does)."""
    return max(g for g in range(1, min(T, most) + 1) if T % g == 0 and (
        g == 1 or _step_vmem_bytes(g, S, B8, strided) <= STEP_VMEM_BYTES))


def _spec_table_k(K, rows):
    """K whole [rows, 128] grids (``*_``: a scalar-prefetch grid hands the
    index map its prefetched words too)."""
    return pl.BlockSpec((K, rows, LANE), lambda i, *_: (0, 0, 0),
                        memory_space=pltpu.VMEM)


@functools.lru_cache(maxsize=None)
def _split_tables_call(K, B, interpret, name):
    B8 = _table_rows(B)
    return pl.pallas_call(
        _split_tables_kernel,
        grid=(1,),
        in_specs=[_spec_table_k(K, B8)],
        out_specs=_spec_table_k(K, 2 * B8),
        out_shape=jax.ShapeDtypeStruct((K, 2 * B8, LANE), jnp.bfloat16),
        interpret=interpret,
        name=name,
    )


@functools.lru_cache(maxsize=None)
def _project_call(T, S, B, K, G, interpret, name):
    return pl.pallas_call(
        functools.partial(_project_kernel, K),
        grid=(T // G,),
        in_specs=[_spec_g(G, 1, S)] * 3
        + [_spec_table_k(K, 2 * _table_rows(B))],
        out_specs=_spec_g(G, K, ROWS_PER_TILE),
        out_shape=jax.ShapeDtypeStruct((T, K, ROWS_PER_TILE), jnp.float32),
        interpret=interpret,
        name=name,
    )


@functools.lru_cache(maxsize=None)
def _scatter_k_call(T, S, B, K, G, interpret, name):
    return pl.pallas_call(
        functools.partial(_scatter_k_kernel, K),
        grid=(T // G,),
        in_specs=[_spec_g(G, 1, S)] * 3 + [_spec_g(G, K, ROWS_PER_TILE)],
        out_specs=_spec_table_k(K, _table_rows(B)),
        out_shape=jax.ShapeDtypeStruct(
            (K, _table_rows(B), LANE), jnp.float32),
        interpret=interpret,
        name=name,
    )


def _spec_sorted(lead, S):
    """One column-sorted tile of a [T, 1, S] slot array (``lead`` 1) or of
    a [K, T*S] per-slot array (``lead`` K), under the scalar prefetch."""
    if lead == 1:
        return pl.BlockSpec((1, 1, S), lambda i, w: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((lead, S), lambda i, w: (0, i),
                        memory_space=pltpu.VMEM)


@functools.lru_cache(maxsize=None)
def _contract_window_call(T, S, B, K, interpret, name):
    return pl.pallas_call(
        functools.partial(_contract_window_kernel, K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T,),
            in_specs=[_spec_sorted(1, S)] * 3 + [_spec_sorted(K, S)]
            + [_spec_table_k(2 * K, _table_rows(B))],
            out_specs=_spec_sorted(1, S)),
        out_shape=jax.ShapeDtypeStruct((T, 1, S), jnp.float32),
        interpret=interpret, name=name)


@functools.lru_cache(maxsize=None)
def _scatter_window_call(T, S, B, K, square, interpret, name):
    return pl.pallas_call(
        functools.partial(_scatter_window_kernel, K, square),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T,),
            in_specs=[_spec_sorted(1, S)] * 4 + [_spec_sorted(K, S)],
            out_specs=_spec_table_k(K, _table_rows(B))),
        out_shape=jax.ShapeDtypeStruct(
            (K, _table_rows(B), LANE), jnp.float32),
        interpret=interpret, name=name)


def _split_tables(a, num_features: int, name: str):
    """``a`` [K, F] float32 -> the bf16x2 halves of its K coefficient grids,
    [K, 2*B8, 128] (Mosaic call ``name``: :func:`_split_tables_kernel`)."""
    K = a.shape[0]
    B = -(-num_features // LANE)
    B8 = _table_rows(B)
    grid = jnp.pad(
        a.astype(jnp.float32), ((0, 0), (0, B8 * LANE - num_features))
    ).reshape(K, B8, LANE)
    return _split_tables_call(K, B, _interpret(), name)(grid)


def run_tiles(shard, num_tiles, make_call, tile_args, rep_args, reduce: bool):
    """Run ``make_call(T)`` -- a pallas_call over T tiles -- on a tiled
    design. ``tile_args`` lead with the tile dim, ``rep_args`` (the
    coefficient grids and shifts) are whole on every device.

    Under ``shard`` (mesh, batch axis) each device runs the kernel on its
    own T/n tiles inside ``jax.shard_map``; with ``reduce`` the outputs are
    feature-space accumulators and are ``psum``med over the batch axis,
    otherwise they are per-row and stay sharded like the tiles."""
    if shard is None:
        return make_call(num_tiles)(*tile_args, *rep_args)
    mesh, axis = shard
    call = make_call(num_tiles // mesh.shape[axis])

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


# ---------------------------------------------------------------------------
# TiledBatch
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TiledBatch(ContractedRows):
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
    rlo: Optional[Array]  # i32[T, 1, S] row % 128; None: slot % 128 (strided)
    labels3: Array   # f32[T, 1, 128]
    offsets3: Array  # f32[T, 1, 128]
    weights3: Array  # f32[T, 1, 128]; 0 for padded rows
    num_features: int = dataclasses.field(metadata=dict(static=True))
    # (mesh, batch axis name) the tile leaves are sharded over, else None
    shard: Optional[tuple[Mesh, str]] = dataclasses.field(
        default=None, metadata=dict(static=True))
    # what this design's gather calls (margins, dot_rows, pair) are called
    # in a device trace: a reduction that reads the training passes by name
    # must not find a validation design's calls among them
    margins_name: str = dataclasses.field(
        default="tiled_margins", metadata=dict(static=True))

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
        """Host-side layout build: group nnz by row tile, pad. The leaves
        stay HOST numpy arrays; :meth:`device` places them.

        Two row assignments, chosen by :func:`strided_is_cheaper` from the
        design's own row lengths. STRIDED: the k-th nonzero of row r goes to
        slot ``k*128 + r`` of its tile, S = 128 x the longest row, and the
        design has no ``rlo``. SORTED: a tile's nonzeros fill its slots in
        arrival order, S is the fullest tile's count and ``rlo`` says whose
        each slot is. Whoever packs reports slots against nonzeros and the
        tiles packed each way (``ops/panels.py::report_layout``)."""
        n = int(len(labels))
        R = ROWS_PER_TILE
        T = max(-(-n // R), 1)
        B = -(-int(num_features) // LANE)
        # the input's own dtypes throughout: at 1e8 nonzeros an int64 /
        # float64 copy of each array is most of the build
        rows, cols, values = np.asarray(rows), np.asarray(cols), np.asarray(values)
        validate_coo_indices(rows, cols, n, num_features)

        row_counts = _bincount(rows, T * R)
        tile_counts = row_counts.reshape(T, R).sum(axis=1)
        s_sorted = int(max(LANE, -(-int(tile_counts.max()) // LANE) * LANE))
        s_strided = LANE * max(int(row_counts.max()), 1)
        strided = strided_is_cheaper(s_strided, s_sorted, B)
        S = s_strided if strided else s_sorted
        # strided needs a row's nonzeros together, sorted only a tile's;
        # ingest emits row-sorted COO, so neither sorts the nnz then
        key = rows if strided else rows // R
        if len(key) and not np.all(key[1:] >= key[:-1]):
            order = np.argsort(key, kind="stable")
            key, rows, cols, values = (
                a[order] for a in (key, rows, cols, values))
        counts = row_counts if strided else tile_counts
        idx = np.int32 if max(T * S, len(rows)) < 2 ** 31 else np.int64
        key = key.astype(idx, copy=False)
        # a nonzero's rank among those of its row (strided) or tile (sorted)
        dest = np.arange(len(rows), dtype=idx)
        dest -= (np.cumsum(counts) - counts).astype(idx)[key]
        if strided:
            dest *= idx(R)
            dest += key % idx(R)
            dest += (key // idx(R)) * idx(S)
        else:
            dest += key * idx(S)
        del key

        def slots(fill, dtype, per_nnz):
            out = np.full((T * S,), fill, dtype)
            out[dest] = per_nnz
            return out.reshape(T, 1, S)

        npad = T * R
        lab = np.zeros(npad, np.float32)
        lab[:n] = np.asarray(labels, np.float64)
        off = np.zeros(npad, np.float32)
        if offsets is not None:
            off[:n] = np.asarray(offsets, np.float64)
        wgt = np.zeros(npad, np.float32)
        wgt[:n] = 1.0 if weights is None else np.asarray(weights, np.float64)

        return TiledBatch(
            vals=slots(0.0, np.float32, values),
            hi=slots(B, np.int32, cols // LANE),  # sentinel: one-hot all-zero
            lo=slots(0, np.int32, cols % LANE),
            rlo=None if strided else slots(0, np.int32, rows % R),
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
        if self.strided:
            rlo = np.tile(np.arange(S) % ROWS_PER_TILE, T)
        else:
            rlo = np.asarray(self.rlo).reshape(-1)
        tiles = np.repeat(np.arange(T), S)
        keep = hi < self.num_blocks
        col = hi[keep] * LANE + lo[keep]
        row = tiles[keep] * ROWS_PER_TILE + rlo[keep]
        np.add.at(X, (row, col), vals[keep])
        return X

    # -- device kernels ------------------------------------------------------

    def _w2(self, w: Array) -> Array:
        """Pad a [F] vector to the kernels' [B8, 128] coefficient grid
        (feature b*128 + j at [b, j]; rows from B on are zero, which is
        what a padding slot's ``hi == B`` sentinel gathers)."""
        B8 = _table_rows(self.num_blocks)
        pad = B8 * LANE - self.num_features
        return jnp.pad(w.astype(jnp.float32), (0, pad)).reshape(B8, LANE)

    @property
    def strided(self) -> bool:
        """Whether slot ``k*128 + r`` of a tile is row r's (no ``rlo``)."""
        return self.rlo is None

    def _statics(self):
        """(S, B, strided): what the kernels are built for."""
        return self.vals.shape[2], self.num_blocks, self.strided

    def tiles_a_step(self, num_tiles: Optional[int] = None,
                     most: int = MAX_TILES_A_STEP) -> int:
        """Tiles a grid step of this design's calls over ``num_tiles`` (a
        shard's; all of them by default): :func:`tiles_a_step` of the
        shape."""
        S, B, strided = self._statics()
        return tiles_a_step(
            self.num_tiles if num_tiles is None else num_tiles, S,
            _table_rows(B), strided, most)

    def _steps(self, factory, *args):
        """``make_call`` of :func:`run_tiles`: ``factory`` over T tiles at
        this design's shape and its tiles a step."""
        return lambda T: factory(T, self.tiles_a_step(T), *self._statics(),
                                 *args)

    def _slot_args(self):
        if self.strided:
            return (self.vals, self.hi, self.lo)
        return (self.vals, self.hi, self.lo, self.rlo)

    def _run(self, make_call, tile_args, rep_args, reduce: bool):
        return run_tiles(self.shard, self.num_tiles, make_call, tile_args,
                         rep_args, reduce)

    def margins(self, w: Array, shift: Array | float = 0.0) -> Array:
        """Per-row margins z_i = x_i . w + shift + offset_i."""
        sh = jnp.stack([jnp.asarray(shift, jnp.float32), jnp.float32(0)])
        z = self._run(
            self._steps(_margins_call, True, False, _interpret(),
                        self.margins_name),
            (*self._slot_args(), self.offsets3),
            (self._w2(w), sh.reshape(1, 2)), reduce=False)
        return z.reshape(-1)

    def dot_rows(self, w: Array) -> Array:
        """Per-row raw dot products x_i . w (no offset/shift)."""
        z = self._run(
            self._steps(_margins_call, False, False, _interpret(),
                        self.margins_name),
            (*self._slot_args(), self.offsets3),
            (self._w2(w), jnp.zeros((1, 2), jnp.float32)), reduce=False)
        return z.reshape(-1)

    def margins_pair(
        self, w: Array, shift, p: Array, p_shift
    ) -> tuple[Array, Array]:
        """(margins(w, shift), dot_rows(p) + p_shift) in one fused sweep."""
        sh = jnp.stack([
            jnp.asarray(shift, jnp.float32), jnp.asarray(p_shift, jnp.float32)
        ])
        z, u = self._run(
            self._steps(_margins_call, True, True, _interpret(),
                        self.margins_name),
            (*self._slot_args(), self.offsets3),
            (self._w2(w), self._w2(p), sh.reshape(1, 2)), reduce=False)
        return z.reshape(-1), u.reshape(-1)

    def _features(self, g: Array) -> Array:
        """[B8, 128] accumulator -> [F]: feature b*128 + j lives at [b, j]
        (rows from B on hold only what padding slots scattered)."""
        return g.reshape(-1)[: self.num_features]

    def _rows3(self, per_row: Array) -> Array:
        """[n_pad] per-row vector -> the [T, 1, 128] tile grid."""
        return per_row.astype(jnp.float32).reshape(
            self.num_tiles, 1, ROWS_PER_TILE)

    @property
    def _scatter_name(self) -> str:
        """``<prefix>_scatter`` beside ``<prefix>_margins``."""
        return self.margins_name.replace("_margins", "_scatter")

    def _scatter(self, per_row: Array, square: bool) -> Array:
        g = self._run(
            self._steps(_scatter_call, square, _interpret(),
                        self._scatter_name),
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
        sh = jnp.stack([jnp.asarray(shift, jnp.float32), jnp.float32(0)])
        sums, g = self._run(
            self._steps(_value_grad_call, loss_name, True, _interpret()),
            (*self._slot_args(), self.labels3, self.weights3, self.offsets3),
            (self._w2(w), sh.reshape(1, 2)), reduce=True)
        return sums[0, 0], self._features(g), sums[0, 1]

    def fused_hessian_vector(
        self, w: Array, shift, v: Array, v_shift, loss_name: str
    ) -> tuple[Array, Array]:
        """(raw Hv scatter sum_i wgt_i*l''(z_i)*(x_i.v)*x_i, sum of the
        per-row q = wgt*l''*u terms) in ONE fused sweep (TRON CG fast path).
        Caller applies normalization back-transform and the L2 term."""
        sh = jnp.stack([
            jnp.asarray(shift, jnp.float32), jnp.asarray(v_shift, jnp.float32)
        ])
        sums, g = self._run(
            self._steps(_hv_call, loss_name, True, _interpret()),
            (*self._slot_args(), self.labels3, self.weights3, self.offsets3),
            (self._w2(w), self._w2(v), sh.reshape(1, 2)), reduce=True)
        return self._features(g), sums[0, 0]

    def fused_hv_at(
        self, d2_row: Array, v_eff: Array, v_shift
    ) -> tuple[Array, Array]:
        """(raw Hv scatter, sum q) with the row curvature d2 = wgt*l''(z)
        precomputed: ONE pass doing gather u + scatter q (TRON CG holds z
        fixed across its inner loop)."""
        sh = jnp.stack([jnp.asarray(v_shift, jnp.float32), jnp.float32(0)])
        sums, g = self._run(
            self._steps(_hv_at_call, _interpret()),
            (*self._slot_args(), self._rows3(d2_row)),
            (self._w2(v_eff), sh.reshape(1, 2)), reduce=True)
        return self._features(g), sums[0, 0]


    # -- K tables in one sweep (the factored coordinate's refit) ------------

    def project_rows(self, a: Array) -> Array:
        """``a`` [K, F] -> [K, n_pad]: row l is ``dot_rows(a[l])``. A strided
        design gathers all K rows in ONE sweep of its tiles
        (``%<prefix>_margins_k``, the masks built once a tile); a sorted one
        makes K calls of :meth:`dot_rows` under one loop."""
        K = a.shape[0]
        if not self.strided or self.shard is not None:
            return jax.lax.map(self.dot_rows, a)
        S, B, _ = self._statics()
        G = self.tiles_a_step(most=K_SWEEP_TILES_A_STEP)
        tabs = _split_tables(
            a, self.num_features,
            self.margins_name.replace("_margins", "_tables") + "_k")
        p = _project_call(
            self.num_tiles, S, B, K, G, _interpret(),
            self.margins_name + "_k",
        )(*self._slot_args(), tabs)
        return p.transpose(1, 0, 2).reshape(K, -1)

    def scatter_rows(self, g: Array) -> Array:
        """``g`` [K, n_pad] -> [K, F]: row l is ``scatter_features(g[l])``;
        one sweep where the design is strided (``%<prefix>_scatter_k``)."""
        K = g.shape[0]
        if not self.strided or self.shard is not None:
            return jax.lax.map(self.scatter_features, g)
        S, B, _ = self._statics()
        g3 = g.astype(jnp.float32).reshape(
            K, self.num_tiles, ROWS_PER_TILE).transpose(1, 0, 2)
        out = _scatter_k_call(
            self.num_tiles, S, B, K,
            self.tiles_a_step(most=K_SWEEP_TILES_A_STEP),
            _interpret(), self._scatter_name + "_k",
        )(*self._slot_args(), g3)
        return out.reshape(K, -1)[:, : self.num_features]

    def feature_moment_sums(self) -> tuple[Array, Array, Array]:
        """Per-feature (sum x, sum x^2, count nonzero) over valid rows."""
        valid = (self.weights > 0).astype(jnp.float32)
        s1 = self.scatter_features(valid)
        s2 = self.scatter_features_sq(valid)
        ones = dataclasses.replace(
            self, vals=(self.vals != 0).astype(jnp.float32))
        cnt = ones.scatter_features(valid)
        return s1, s2, cnt

    def traced_as(self, prefix: str) -> "TiledBatch":
        """This design with its gather calls named ``<prefix>_margins`` in
        a device trace (:attr:`margins_name`)."""
        return dataclasses.replace(self, margins_name=prefix + "_margins")

    def with_offsets(self, offsets: Array) -> "TiledBatch":
        return dataclasses.replace(self, offsets3=self._rows3(offsets))

    def with_weights(self, weights: Array) -> "TiledBatch":
        return dataclasses.replace(self, weights3=self._rows3(weights))


# ---------------------------------------------------------------------------
# one-hot rows sorted by column: the factored coordinate's refit
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ColumnSortedTiles:
    """A design of ONE nonzero a row with its rows sorted by column, for
    the passes of a [K, F] table that are contracted with a per-row [K]
    vector (the two methods of ``ops/sparse.py::ContractedRows``, each ONE
    sweep here; the refit of a factored coordinate's projection, whose
    per-row arrays are its own and may lie in any order).

    Sorted by column, S consecutive rows touch a handful of adjacent
    columns, so a tile needs a WINDOW of the table, not all of it: the
    sorted rows are cut where ``col // (128 * WINDOW)`` changes and then
    into tiles of S slots, so that every tile lies in one aligned window of
    ``WINDOW`` = 16 table rows (a window's last tile is padded; at most one
    a window, 14 of 4,409 at d = 27,278 and K = 16). A tile's gather or
    placement is then ONE matmul whose left operand is the window of all K
    tables (2K x 16 rows), where :meth:`TiledBatch.project_rows` streams
    all 2 x B8 rows of every table (PERF.md, Findings PR 34). ``num_rows`` is
    the slots': the caller lays its per-row arrays out by the slot of each
    row that :meth:`pack` returns; a padding slot carries value 0 and
    ``hi == WINDOW``, which no window row matches.
    """

    window: Array    # i32[T] a tile's window: table rows from window*WINDOW
    vals: Array      # f32[T, 1, S] slot values (0 in padding)
    hi: Array        # i32[T, 1, S] col // 128 inside the window (padding: WINDOW)
    lo: Array        # i32[T, 1, S] col % 128
    num_features: int = dataclasses.field(metadata=dict(static=True))
    # the Mosaic calls' prefix in a device trace: ``<prefix>_margins_k_sorted``
    # / ``<prefix>_scatter_k_sorted`` / ``<prefix>_tables_k``
    prefix: str = dataclasses.field(default="tiled", metadata=dict(static=True))

    @property
    def num_tiles(self) -> int:
        return self.vals.shape[0]

    @property
    def num_rows(self) -> int:
        return self.vals.shape[0] * self.vals.shape[2]

    @property
    def num_blocks(self) -> int:
        return -(-self.num_features // LANE)

    @staticmethod
    def pack(values: np.ndarray, cols: np.ndarray, num_features: int,
             slots: int) -> tuple["ColumnSortedTiles", np.ndarray]:
        """Host-side layout of rows ALREADY sorted by column (``cols``
        non-decreasing, one entry a row) into tiles of ``slots``
        (:func:`sorted_slots` of the tables it will serve). Returns the
        layout (host numpy leaves) and each row's slot in it."""
        cols = np.asarray(cols)
        if len(cols) and (cols[1:] < cols[:-1]).any():
            raise ValueError("ColumnSortedTiles.pack: cols are not sorted")
        validate_coo_indices(cols[:0], cols[[0, -1][:len(cols)]], 1,
                             num_features)
        windows = _table_rows(-(-int(num_features) // LANE)) // WINDOW
        # a window's rows are consecutive: where each starts, and its tiles
        edges = np.arange(windows + 1, dtype=np.int64) * (WINDOW * LANE)
        first = np.searchsorted(  # needles of cols' own dtype: no copy of it
            cols, np.minimum(edges, int(num_features)).astype(cols.dtype))
        counts = np.diff(first)
        tiles = -(-counts // slots)
        tiles[0] += not tiles.any()  # an empty design is one tile of padding
        T = int(tiles.sum())
        slot = np.arange(len(cols), dtype=np.int64) + np.repeat(
            (np.cumsum(tiles) - tiles) * slots - first[:-1], counts)

        def placed(fill, dtype, per_row):
            out = np.full(T * slots, fill, dtype)
            out[slot] = per_row
            return out.reshape(T, 1, slots)

        return ColumnSortedTiles(
            window=np.repeat(np.arange(windows, dtype=np.int32), tiles),
            vals=placed(0.0, np.float32, values),
            hi=placed(WINDOW, np.int32, cols // LANE % WINDOW),
            lo=placed(0, np.int32, cols % LANE),
            num_features=int(num_features)), slot

    def traced_as(self, prefix: str) -> "ColumnSortedTiles":
        return dataclasses.replace(self, prefix=prefix)

    def to_dense(self) -> np.ndarray:
        """Host-side densify, a row a slot (tests / diagnostics only)."""
        T, _, S = self.vals.shape
        hi = np.asarray(self.hi).reshape(-1)
        col = ((np.repeat(np.asarray(self.window), S) * WINDOW + hi) * LANE
               + np.asarray(self.lo).reshape(-1))
        keep = np.flatnonzero(hi < WINDOW)
        X = np.zeros((T * S, self.num_features), np.float64)
        X[keep, col[keep]] = np.asarray(self.vals).reshape(-1)[keep]
        return X

    def _slot_args(self):
        return (self.window, self.vals, self.hi, self.lo)

    def contract_rows(self, a: Array, c_rows: Array) -> Array:
        """``sum_l c_rows[l] * (X a[l])`` [rows] in one sweep: the [K, rows]
        projection is never written."""
        K = a.shape[0]
        T, _, S = self.vals.shape
        tabs = _split_tables(a, self.num_features, self.prefix + "_tables_k")
        z = _contract_window_call(
            T, S, self.num_blocks, K, _interpret(),
            self.prefix + "_margins_k_sorted",
        )(*self._slot_args(), c_rows.astype(jnp.float32),
          tabs.reshape(2 * K, -1, LANE))
        return z.reshape(-1)

    def scatter_contracted(self, q: Array, c_rows: Array,
                           square: bool = False) -> Array:
        """``X^T (q * c_rows[l])`` for every l, [K, F], in one sweep."""
        K = c_rows.shape[0]
        T, _, S = self.vals.shape
        c = c_rows.astype(jnp.float32)
        out = _scatter_window_call(
            T, S, self.num_blocks, K, square, _interpret(),
            self.prefix + "_scatter_k_sorted",
        )(*self._slot_args(), q.astype(jnp.float32).reshape(T, 1, S),
          c * c if square else c)
        return out.reshape(K, -1)[:, : self.num_features]
