"""Column panels: the tiled layout for fixed effects too wide for one table.

:class:`~photon_ml_tpu.ops.tiled.TiledBatch` gathers a slot's coefficient by
multiplying the WHOLE coefficient grid into the tile's lane one-hot: every
128 slots stream 2*B8 table rows through the MXU, B = d/128. That is the
right trade at B = 79 and no trade at all at B = 7,813 (a [2*B8, S]
intermediate of 312 MB a tile). Here the work of a pass is proportional to
the stored slots, whatever d is:

  - COLUMNS ARE RENUMBERED BY FREQUENCY (:func:`column_order`; rank 0 is the
    most frequent feature). A click log's histogram is steep: the first
    ``HOT_BLOCKS * 128`` = 4,096 ranks hold most of the slots (85% of LIBSVM
    ``criteo``-shaped rows), and beyond them the density falls smoothly, so
    a contiguous range of ranks is a range of like densities.
  - THE HOT PANEL is a plain ``TiledBatch`` over ranks < 4,096 and every
    row, on the one-hot kernels as they are: 2*32 table rows a 128 slots
    stream under the MXU's weight loads, the cheapest a pass gets. Its row
    assignment is ``TiledBatch.pack_coo``'s own: rows of near one length
    (a click log's: 39 a row, 33 of them hot) go strided, 12 bytes a slot
    and ONE pass a call, ragged ones stay sorted with their ``rlo`` and
    the row one-hot's pass.
  - THE TAIL is binned in two dimensions. A class of window W cuts its rank
    range into column windows of W blocks and the rows into row windows of
    W tiles; a bin (row window x column window) is stored as tiles of
    ``PANEL_SLOTS`` slots, and a slot holds (value, block in the window,
    lane, row tile in the window, row in the tile). The kernels are the
    one-hot gather and placement of ``ops/tiled.py`` used on BOTH sides:
    margins gather from the [W, 128] coefficient window and place into the
    [W, 128] row window; scatter gathers from the row window and places
    into the coefficient window. A tile streams 2*W + 2*W rows, so its cost
    is set by W, not by d. Sparser ranks need larger bins to fill a tile
    (slots a bin = W*128 rows x the window's slots a row), so W doubles
    from 16 while a class's windows hold at least two tiles' worth a bin,
    up to ``MAX_WINDOW``; the last class takes what is left.
  - The coefficient windows and the feature-space accumulator of a class
    are whole in VMEM (4 bytes a feature of the class: O(d) once a pass;
    a stretch of one window wider than ``MAX_CLASS_BLOCKS`` is cut into
    several classes, so no width outgrows the kernels' VMEM); the tiles arrive sorted by row window so that a class's per-row output
    block stays resident while its tiles accumulate into it. Which windows
    a tile belongs to comes through scalar prefetch, one packed int a tile.

Whether a design takes this layout is decided from the design alone
(:func:`plan_panels`): from B while the plain kernels' modelled cost is
within twice the floor of any layout, else from the column histogram. The
model is the one PERF.md's PR 25 timings fit (``ops/tiled.py::_pass_ns``):
a pass of a one-hot through the MXU costs max(15 ns, 0.167 ns x rows
streamed) per 128 slots. The plan prices a plain tile and the hot panel
at TWO passes, the sorted row assignment's; whether a tile drops the
second is ``ops/tiled.py::strided_is_cheaper``'s, from the row lengths,
after the plan: it halves at most one term that is compared at a factor
of two.

Everything a :class:`PanelBatch` takes or returns in feature space is in
RANK order. ``game/coordinates.py::FixedEffectCoordinate`` renumbers
coefficients, bounds and normalization at its edge (``order`` / ``rank``),
so nothing outside it sees a rank.
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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.ops.sparse import SparseBatch, validate_coo_indices
from photon_ml_tpu.ops import tiled
from photon_ml_tpu.ops.tiled import (
    LANE,
    PASS_FLOOR_NS,
    ROWS_PER_TILE,
    TiledBatch,
    _bincount,
    _gather_slots,
    _onehot_t,
    _pass_ns,
    _place_slots,
    _split_bf16,
    run_tiles,
)
from photon_ml_tpu.telemetry.metrics import counter, gauge
from photon_ml_tpu.telemetry.trace import span

Array = jax.Array

HOT_BLOCKS = 32      # 2*32 table rows stream under a pass's weight loads
PANEL_SLOTS = 1024   # slots a tail tile
MIN_WINDOW = 16      # the bfloat16 sublane tile: no narrower table stacks
MAX_WINDOW = 256
# a class's coefficient grid and accumulator are whole in VMEM, 512 bytes a
# block and double-buffered: 16 MB of the kernels' 48; a wider stretch of one
# window is cut into several classes
MAX_CLASS_BLOCKS = 16384


def _up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# the plan: which ranks go where
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PanelClass:
    """A tail class: ``num_windows`` column windows of ``window`` blocks
    from rank block ``first_block`` on; rows in windows of ``window``
    tiles."""

    window: int
    first_block: int
    num_windows: int

    @property
    def num_blocks(self) -> int:
        return self.window * self.num_windows


def plain_is_near_floor(num_blocks: int) -> bool:
    """True where the plain kernels' modelled cost a 128 slots is within
    twice what ANY layout has to pay (two passes at the floor): no
    histogram can then make panels worth their second sort."""
    plain = _pass_ns(2 * _up(num_blocks, 16)) + _pass_ns(16)
    return plain <= 2 * 2 * PASS_FLOOR_NS


def plan_panels(block_counts: np.ndarray, num_rows: int):
    """The tail classes for a design whose rank block b holds
    ``block_counts[b]`` slots (non-increasing), or None where the plain
    tiled layout is modelled within twice the panels' cost.

    ``plain`` and the hot panel's ``cost`` carry ``+ _pass_ns(16)``, the row
    one-hot's pass of the SORTED row assignment. A strided design runs
    without it, but which assignment a ``TiledBatch`` gets is decided
    later, from its row lengths, where it is packed
    (``ops/tiled.py::strided_is_cheaper``); the plan sees columns only and
    prices the dearer of the two on both sides of its comparison."""
    B = len(block_counts)
    if B <= HOT_BLOCKS:
        return None
    total = float(block_counts.sum())
    plain = total * (_pass_ns(2 * _up(B, 16)) + _pass_ns(16))
    cost = float(block_counts[:HOT_BLOCKS].sum()) * (
        _pass_ns(2 * HOT_BLOCKS) + _pass_ns(16))
    classes = []
    b, W = HOT_BLOCKS, MIN_WINDOW
    # a row window wider than the design has row tiles only pads rows
    widest = MIN_WINDOW
    while widest < min(MAX_WINDOW, -(-num_rows // ROWS_PER_TILE)):
        widest *= 2
    csum = np.concatenate([[0], np.cumsum(block_counts, dtype=np.int64)])
    while b < B:
        left = -(-(B - b) // W)
        starts = b + W * np.arange(left)
        in_window = csum[np.minimum(starts + W, B)] - csum[starts]
        # slots a bin = the window's slots a row x the row window's rows
        per_bin = in_window * (W * ROWS_PER_TILE) / max(num_rows, 1)
        full = per_bin >= 2 * PANEL_SLOTS
        take = left if W >= widest or full.all() else int(np.argmin(full))
        if take:
            hi = min(b + take * W, B)
            cost += float(csum[hi] - csum[b]) * 2 * _pass_ns(2 * W)
            most = MAX_CLASS_BLOCKS // W
            classes += [PanelClass(W, b + first * W, min(most, take - first))
                        for first in range(0, take, most)]
            b += take * W
        W *= 2
    if 2 * cost >= plain:
        return None
    return tuple(classes)


def column_order(cols: np.ndarray, num_features: int):
    """(order, rank, block_counts): ``order[r]`` is the feature of rank r
    (most frequent first, ties by feature), ``rank`` its inverse, and
    ``block_counts[b]`` the slots of ranks [128 b, 128 b + 128)."""
    counts = _bincount(cols, num_features)
    order = np.argsort(-counts, kind="stable").astype(np.int32)
    rank = np.empty(num_features, np.int32)
    rank[order] = np.arange(num_features, dtype=np.int32)
    sorted_counts = np.zeros(_up(num_features, LANE), np.int64)
    sorted_counts[:num_features] = counts[order]
    return order, rank, sorted_counts.reshape(-1, LANE).sum(axis=1)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _windows(meta_ref, bits: int):
    """(column window, whether this is the first tile of its row window) of
    this grid step, from the packed int (the row window is read by the
    index maps)."""
    m = meta_ref[pl.program_id(0)]
    return (m >> 1) & ((1 << bits) - 1), m & 1


def _table2_rows(ref, start, W: int):
    """The bf16x2 halves of rows [start, start + W) of a float32 grid,
    stacked [2*W, 128]."""
    return jnp.concatenate(_split_bf16(ref[pl.ds(start, W), :]), axis=0)


def _panel_masks(hi_ref, lo_ref, W: int):
    """(bool [W, S], bf16 [128, S]) one-hots of a window's two-level index,
    slots on lanes. A padding slot carries ``hi == W`` and matches no row."""
    return (_onehot_t(hi_ref[0], W),
            _onehot_t(lo_ref[0], LANE).astype(jnp.bfloat16))


def _panel_margins_kernel(bits, meta_ref, vals_ref, chi_ref, clo_ref,
                          rhi_ref, rlo_ref, w_ref, out_ref):
    """out[row window] (+)= per-row sums of vals * w[col] over one tile:
    gather from the tile's coefficient window, place into its row window."""
    W = out_ref.shape[0]
    cw, first = _windows(meta_ref, bits)
    tab = _table2_rows(w_ref, pl.multiple_of(cw * W, W), W)
    p = _gather_slots(tab, *_panel_masks(chi_ref, clo_ref, W)) * vals_ref[0]
    z = _place_slots(p, *_panel_masks(rhi_ref, rlo_ref, W))

    @pl.when(first == 1)
    def _():
        out_ref[:] = z

    @pl.when(first == 0)
    def _():
        out_ref[:] = out_ref[:] + z


def _panel_scatter_kernel(bits, square, meta_ref, vals_ref, chi_ref, clo_ref,
                          rhi_ref, rlo_ref, pr_ref, out_ref):
    """out[column window] += sum_s per_row[row_s] * vals_s (or vals_s^2):
    gather from the tile's row window, place into its coefficient window."""
    W = pr_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    cw, _ = _windows(meta_ref, bits)
    vals = vals_ref[0]
    if square:
        vals = vals * vals
    tab = _table2_rows(pr_ref, 0, W)
    q = _gather_slots(tab, *_panel_masks(rhi_ref, rlo_ref, W)) * vals
    d = _place_slots(q, *_panel_masks(chi_ref, clo_ref, W))
    start = pl.multiple_of(cw * W, W)
    out_ref[pl.ds(start, W), :] = out_ref[pl.ds(start, W), :] + d


_VMEM = pltpu.VMEM
_PARAMS = dict(
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=48 << 20))


def _slot_specs(S):
    return [pl.BlockSpec((1, 1, S), lambda i, m: (i, 0, 0),
                         memory_space=_VMEM)] * 5


def _row_window_spec(W, bits):
    return pl.BlockSpec((W, LANE), lambda i, m: (m[i] >> (bits + 1), 0),
                        memory_space=_VMEM)


def _whole_spec(rows):
    return pl.BlockSpec((rows, LANE), lambda i, m: (0, 0), memory_space=_VMEM)


@functools.lru_cache(maxsize=None)
def _panel_margins_call(Tt, S, W, blocks, tiles, bits, interpret,
                        name="panel_margins"):
    """``blocks``: rows of the class's coefficient grid; ``tiles``: row
    tiles of the design (rows of the [tiles, 128] per-row output)."""
    return pl.pallas_call(
        functools.partial(_panel_margins_kernel, bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Tt,),
            in_specs=_slot_specs(S) + [_whole_spec(blocks)],
            out_specs=_row_window_spec(W, bits)),
        out_shape=jax.ShapeDtypeStruct((tiles, LANE), jnp.float32),
        interpret=interpret, name=name, **_PARAMS)


@functools.lru_cache(maxsize=None)
def _panel_scatter_call(Tt, S, W, blocks, bits, square, interpret):
    return pl.pallas_call(
        functools.partial(_panel_scatter_kernel, bits, square),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Tt,),
            in_specs=_slot_specs(S) + [_row_window_spec(W, bits)],
            out_specs=_whole_spec(blocks)),
        out_shape=jax.ShapeDtypeStruct((blocks, LANE), jnp.float32),
        interpret=interpret, name="panel_scatter", **_PARAMS)


# ---------------------------------------------------------------------------
# the layout
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PanelPart:
    """One tail class's tiles, sorted by (row window, column window)."""

    meta: Array   # i32[Tt] row window << (bits + 1) | column window << 1 | first
    vals: Array   # f32[Tt, 1, S] slot values (0 in padding)
    chi: Array    # i32[Tt, 1, S] column block in its window (== W in padding)
    clo: Array    # i32[Tt, 1, S] col % 128
    rhi: Array    # i32[Tt, 1, S] row tile in its window (== W in padding)
    rlo: Array    # i32[Tt, 1, S] row % 128
    cls: PanelClass = dataclasses.field(metadata=dict(static=True))
    bits: int = dataclasses.field(metadata=dict(static=True))
    # the gather call's name in a device trace (TiledBatch.margins_name)
    margins_name: str = dataclasses.field(
        default="panel_margins", metadata=dict(static=True))

    def _slot_args(self):
        return (self.meta, self.vals, self.chi, self.clo, self.rhi, self.rlo)

    def dot_rows(self, grid: Array, shard, tiles: int) -> Array:
        """[tiles, 128] per-row sums over this class's slots; ``grid`` is
        the class's [blocks, 128] coefficient grid."""
        S, W = self.vals.shape[2], self.cls.window
        n = 1 if shard is None else shard[0].shape[shard[1]]
        return run_tiles(
            shard, self.vals.shape[0],
            lambda Tt: _panel_margins_call(
                Tt, S, W, self.cls.num_blocks, tiles // n, self.bits,
                tiled._interpret(), self.margins_name),
            self._slot_args(), (grid,), reduce=False)

    def scatter(self, rows2: Array, square: bool, shard) -> Array:
        """[blocks, 128] feature-space sums; ``rows2`` is the [tiles, 128]
        per-row grid."""
        S, W = self.vals.shape[2], self.cls.window
        return run_tiles(
            shard, self.vals.shape[0],
            lambda Tt: _panel_scatter_call(
                Tt, S, W, self.cls.num_blocks, self.bits, square,
                tiled._interpret()),
            (*self._slot_args(), rows2), (), reduce=True)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PanelBatch:
    """Sparse labeled examples in the column-panel layout, duck-type
    compatible with :class:`TiledBatch` for the objective, the optimizer
    adapters and :class:`FixedEffectCoordinate`. FEATURE SPACE IS IN RANK
    ORDER: coefficient vectors passed in, and gradients returned, are
    indexed by rank (``order`` / ``rank`` translate)."""

    hot: TiledBatch          # ranks < hot.num_features, every row
    parts: tuple             # PanelPart per tail class, by first_block
    order: Array             # i32[d] feature of each rank
    rank: Array              # i32[d] rank of each feature
    num_features: int = dataclasses.field(metadata=dict(static=True))
    # nonzeros stored in the hot panel and in each part, in that order
    stored: tuple = dataclasses.field(default=(), metadata=dict(static=True))
    shards: int = dataclasses.field(default=1, metadata=dict(static=True))
    shard: Optional[tuple[Mesh, str]] = dataclasses.field(
        default=None, metadata=dict(static=True))

    # -- shape views ----------------------------------------------------------

    num_tiles = property(lambda self: self.hot.num_tiles)
    num_rows = property(lambda self: self.hot.num_rows)
    dtype = property(lambda self: self.hot.dtype)
    labels = property(lambda self: self.hot.labels)
    offsets = property(lambda self: self.hot.offsets)
    weights = property(lambda self: self.hot.weights)

    @property
    def nnz_slots(self) -> int:
        return self.hot.nnz_slots + sum(
            p.vals.shape[0] * p.vals.shape[2] for p in self.parts)

    @property
    def padded_blocks(self) -> int:
        last = self.parts[-1].cls
        return last.first_block + last.num_blocks

    def traced_as(self, prefix: str) -> "PanelBatch":
        """This design with its gather calls named ``<prefix>_margins`` (the
        hot panel) and ``<prefix>_panel_margins`` (the tail) in a device
        trace."""
        return dataclasses.replace(
            self, hot=self.hot.traced_as(prefix),
            parts=tuple(
                dataclasses.replace(
                    p, margins_name=prefix + "_panel_margins")
                for p in self.parts))

    def with_offsets(self, offsets: Array) -> "PanelBatch":
        return dataclasses.replace(self, hot=self.hot.with_offsets(offsets))

    def with_weights(self, weights: Array) -> "PanelBatch":
        return dataclasses.replace(self, hot=self.hot.with_weights(weights))

    # -- placement --------------------------------------------------------------

    def device(self) -> "PanelBatch":
        return jax.tree.map(jnp.asarray, self)

    def place(self, mesh: Mesh, axis: str) -> "PanelBatch":
        """Tile leaves sharded over ``axis`` (this layout was packed for
        that many shards), ``order`` / ``rank`` whole on every device."""
        if mesh.shape[axis] != self.shards:
            raise ValueError(
                f"packed for {self.shards} shards, placed over "
                f"{mesh.shape[axis]}")
        tiles = NamedSharding(mesh, P(axis))
        whole = NamedSharding(mesh, P())
        put = functools.partial(jax.tree.map, lambda x: jax.device_put(x, tiles))
        return dataclasses.replace(
            self,
            hot=dataclasses.replace(put(self.hot), shard=(mesh, axis)),
            parts=tuple(put(p) for p in self.parts),
            order=jax.device_put(self.order, whole),
            rank=jax.device_put(self.rank, whole),
            shard=(mesh, axis))

    # -- device passes ------------------------------------------------------------

    def _grid(self, w: Array) -> Array:
        pad = self.padded_blocks * LANE - self.num_features
        return jnp.pad(w.astype(jnp.float32), (0, pad)).reshape(-1, LANE)

    def dot_rows(self, w: Array) -> Array:
        """Per-row raw dot products x_i . w (no offset/shift)."""
        grid = self._grid(w)
        hot = self.hot.num_blocks
        z = self.hot.dot_rows(grid[:hot].reshape(-1))
        for p in self.parts:
            b = p.cls.first_block
            z = z + p.dot_rows(grid[b:b + p.cls.num_blocks], self.shard,
                               self.num_tiles).reshape(-1)
        return z

    def margins(self, w: Array, shift: Array | float = 0.0) -> Array:
        """Per-row margins z_i = x_i . w + shift + offset_i."""
        return self.dot_rows(w) + self.offsets + jnp.asarray(
            shift, jnp.float32)

    def margins_pair(self, w: Array, shift, p: Array, p_shift):
        """(margins(w, shift), dot_rows(p) + p_shift)."""
        return self.margins(w, shift), self.dot_rows(p) + jnp.asarray(
            p_shift, jnp.float32)

    def _scatter(self, per_row: Array, square: bool) -> Array:
        per_row = per_row.astype(jnp.float32)
        rows2 = per_row.reshape(self.num_tiles, LANE)
        g = [self.hot._scatter(per_row, square)]
        g += [p.scatter(rows2, square, self.shard).reshape(-1)
              for p in self.parts]
        return jnp.concatenate(g)[: self.num_features]

    def scatter_features(self, per_row: Array) -> Array:
        """sum_i per_row[i] * x_i as a dense feature-space vector."""
        return self._scatter(per_row, False)

    def scatter_features_sq(self, per_row: Array) -> Array:
        """sum_i per_row[i] * (x_i ** 2) (Hessian diagonal)."""
        return self._scatter(per_row, True)

    # the fused sweeps of the plain layout need every slot of a row in one
    # tile; here a row's slots lie in several parts, so these are SparseBatch's
    # compositions of the passes above
    fused_value_grad = SparseBatch.fused_value_grad
    fused_hessian_vector = SparseBatch.fused_hessian_vector
    fused_hv_at = SparseBatch.fused_hv_at

    def feature_moment_sums(self) -> tuple[Array, Array, Array]:
        """Per-feature (sum x, sum x^2, count nonzero) over valid rows."""
        valid = (self.weights > 0).astype(jnp.float32)

        def indicator(x):
            return dataclasses.replace(
                x, vals=(x.vals != 0).astype(jnp.float32))

        ones = dataclasses.replace(
            self, hot=indicator(self.hot),
            parts=tuple(indicator(p) for p in self.parts))
        return (self.scatter_features(valid), self.scatter_features_sq(valid),
                ones.scatter_features(valid))

    # -- host views ------------------------------------------------------------------

    def to_dense(self) -> np.ndarray:
        """Host-side densify in the ORIGINAL feature order (tests only)."""
        X = np.zeros((self.num_rows, self.padded_blocks * LANE), np.float64)
        X[:, : self.hot.num_features] = self.hot.to_dense()
        local_tiles = self.num_tiles // self.shards
        for p in self.parts:
            W, S = p.cls.window, p.vals.shape[2]
            meta = np.asarray(p.meta).astype(np.int64)
            per_shard = len(meta) // self.shards
            rw = (meta >> (p.bits + 1)) * W + (
                np.arange(len(meta)) // per_shard) * local_tiles
            cw = ((meta >> 1) & ((1 << p.bits) - 1)) * W + p.cls.first_block
            chi, rhi = (np.asarray(a).reshape(-1, S) for a in (p.chi, p.rhi))
            keep = chi < W
            row = ((rw[:, None] + rhi) * ROWS_PER_TILE
                   + np.asarray(p.rlo).reshape(-1, S))[keep]
            col = ((cw[:, None] + chi) * LANE
                   + np.asarray(p.clo).reshape(-1, S))[keep]
            X += np.bincount(
                row * X.shape[1] + col, np.asarray(p.vals).reshape(-1, S)[keep],
                X.size).reshape(X.shape)
        return X[:, np.asarray(self.rank)]


# ---------------------------------------------------------------------------
# host packing
# ---------------------------------------------------------------------------


def _pack_part(cls: PanelClass, vals, rows, ranks, tiles: int, shards: int):
    """One tail class from the slots whose rank lies in its range: sort by
    bin, cut every bin into tiles of ``PANEL_SLOTS``, pad every shard to the
    same number of tiles."""
    W, S, nW = cls.window, PANEL_SLOTS, cls.num_windows
    bits = max(int(nW - 1).bit_length(), 1)
    first = cls.first_block * LANE
    sel = np.flatnonzero(
        (ranks >= first) & (ranks < first + cls.num_blocks * LANE))
    c = ranks[sel] - np.int32(first)
    r = rows[sel]
    row_windows = tiles // W
    key = (r // np.int32(W * ROWS_PER_TILE)).astype(np.int64) * nW + (
        c // np.int32(W * LANE))
    by_bin = np.argsort(key, kind="stable")
    key, sel, c, r = key[by_bin], sel[by_bin], c[by_bin], r[by_bin]
    in_bin = np.bincount(key, minlength=row_windows * nW).reshape(
        row_windows, nW)
    bin_tiles = -(-in_bin // S)
    # a row window's output block is written by its first tile: it has one
    bin_tiles[:, 0] = np.maximum(bin_tiles[:, 0], 1)
    per_shard = bin_tiles.reshape(shards, -1).sum(axis=1)
    Tt = int(per_shard.max())
    local_windows = row_windows // shards
    if (local_windows << (bits + 1)) >= 2 ** 31:
        raise ValueError("too many row windows for the packed tile index")
    flat_tiles = bin_tiles.reshape(-1)
    bin_shard = np.arange(row_windows * nW) // (local_windows * nW)
    # first tile of every bin: running count inside its shard, on the
    # shard's stretch of Tt tiles
    before = np.cumsum(flat_tiles) - flat_tiles
    shard_start = np.concatenate([[0], np.cumsum(per_shard)[:-1]])
    bin_first = before - shard_start[bin_shard] + bin_shard * Tt
    bin_begin = np.cumsum(in_bin.reshape(-1)) - in_bin.reshape(-1)
    dest = bin_first[key] * S + (np.arange(len(key)) - bin_begin[key])

    def slots(fill, dtype, values):
        out = np.full(shards * Tt * S, fill, dtype)
        out[dest] = values
        return out.reshape(shards * Tt, 1, S)

    # per-tile index: padding tiles of a shard repeat its last row window
    # (never first) and column window 0
    meta = np.zeros((shards, Tt), np.int32)
    meta[:] = ((local_windows - 1) << (bits + 1))
    bin_rw = (np.arange(row_windows * nW) // nW) % local_windows
    bin_cw = np.arange(row_windows * nW) % nW
    tile_bin = np.repeat(np.arange(row_windows * nW), flat_tiles)
    tile_index = np.arange(len(tile_bin)) - shard_start[
        bin_shard[tile_bin]] + bin_shard[tile_bin] * Tt
    is_first = (bin_cw[tile_bin] == 0) & (
        np.arange(len(tile_bin)) == before[tile_bin])
    meta.reshape(-1)[tile_index] = (
        (bin_rw[tile_bin] << (bits + 1)) | (bin_cw[tile_bin] << 1)
        | is_first)
    part = PanelPart(
        meta=meta.reshape(-1),
        vals=slots(0.0, np.float32, vals[sel]),
        chi=slots(W, np.int32, (c // LANE) % W),
        clo=slots(0, np.int32, c % LANE),
        rhi=slots(W, np.int32, (r // ROWS_PER_TILE) % W),
        rlo=slots(0, np.int32, r % ROWS_PER_TILE),
        cls=cls, bits=bits)
    return part, len(sel)


def _nonzeros(batch: SparseBatch):
    """(values, rows, cols) of a padded-COO batch without its padding slots,
    as host arrays in the batch's own dtypes."""
    vals, rows, cols = (np.asarray(a) for a in (
        batch.values, batch.rows, batch.cols))
    keep = vals != 0
    if keep.all():
        return vals, rows, cols
    return vals[keep], rows[keep], cols[keep]


def pack_panels(batch: SparseBatch, nonzeros, classes, order, rank,
                shards: int = 1) -> PanelBatch:
    """Host-side layout build of a padded-COO batch, given its
    :func:`_nonzeros`, on the plan ``classes``; leaves stay host numpy
    arrays."""
    vals, rows, cols = nonzeros
    n = batch.num_rows
    validate_coo_indices(rows, cols, n, batch.num_features)
    widest = max(c.window for c in classes)
    tiles = shards * _up(-(-max(-(-n // ROWS_PER_TILE), 1) // shards), widest)

    def rows_padded(x):
        out = np.zeros(tiles * ROWS_PER_TILE, np.float32)
        out[:n] = np.asarray(x)
        return out

    with span("layout.rank"):
        ranks = rank[cols]
    with span("layout.hot"):
        is_hot = ranks < HOT_BLOCKS * LANE
        hot = TiledBatch.pack_coo(
            vals[is_hot], rows[is_hot], ranks[is_hot],
            rows_padded(batch.labels), HOT_BLOCKS * LANE,
            offsets=rows_padded(batch.offsets),
            weights=rows_padded(batch.weights))
        stored = [int(is_hot.sum())]
        del is_hot
    parts = []
    with span("layout.tail"):
        tail = np.flatnonzero(ranks >= HOT_BLOCKS * LANE)
        vals, rows, ranks = vals[tail], rows[tail], ranks[tail]
        del tail
        for cls in classes:
            part, nnz = _pack_part(cls, vals, rows, ranks, tiles, shards)
            parts.append(part)
            stored.append(nnz)
    return PanelBatch(hot=hot, parts=tuple(parts), order=order, rank=rank,
                      num_features=batch.num_features,
                      stored=tuple(stored), shards=shards)


def report_layout(design, prefix: str = "layout", shards: int = 1) -> None:
    """Counters ``<prefix>.slots`` / ``<prefix>.nnz`` and gauge
    ``<prefix>.padding_ratio`` = slots allocated / nonzeros stored, of a
    design still on the host; a :class:`PanelBatch` splits its nonzeros by
    part besides (``<prefix>.nnz.hot``, ``<prefix>.nnz.w<W>``). Counter
    ``<prefix>.tiles.strided`` or ``<prefix>.tiles.sorted``: the tiles of
    the plain design, or of a panel design's hot part, by the row assignment
    :meth:`TiledBatch.pack_coo` gave them; gauge ``<prefix>.tiles_a_step``:
    the tiles a grid step of their calls on one of ``shards`` devices."""
    if isinstance(design, PanelBatch):
        names = ["hot"] + [f"w{p.cls.window}" for p in design.parts]
        for name, part_nnz in zip(names, design.stored):
            counter(f"{prefix}.nnz.{name}").inc(part_nnz)
        nnz = sum(design.stored)
        tiles = design.hot
    else:
        nnz = int(np.count_nonzero(design.vals))
        tiles = design
    how = "strided" if tiles.strided else "sorted"
    counter(f"{prefix}.tiles.{how}").inc(tiles.num_tiles)
    gauge(f"{prefix}.tiles_a_step").set(
        tiles.tiles_a_step(-(-tiles.num_tiles // shards)))
    counter(f"{prefix}.slots").inc(design.nnz_slots)
    counter(f"{prefix}.nnz").inc(nnz)
    gauge(f"{prefix}.padding_ratio").set(design.nnz_slots / max(nnz, 1))


def pack_design(batch: SparseBatch, shards: int = 1):
    """The TPU layout of a fixed-effect design, chosen from the design
    alone: the plain :class:`TiledBatch` while its modelled cost is within
    twice the column panels', else a :class:`PanelBatch`. Host numpy
    leaves; the caller places them."""
    num_blocks = -(-batch.num_features // LANE)
    classes = None
    if not plain_is_near_floor(num_blocks):
        with span("layout.histogram"):
            nonzeros = _nonzeros(batch)
            order, rank, block_counts = column_order(
                nonzeros[2], batch.num_features)
            classes = plan_panels(block_counts, batch.num_rows)
    if classes is None:
        with span("layout.plain"):
            design = TiledBatch.pack_batch(batch)
    else:
        design = pack_panels(batch, nonzeros, classes, order, rank, shards)
    report_layout(design, shards=shards)
    return design


def pack_rows_like(design, batch: SparseBatch, shards: int = 1):
    """Other rows of ``design``'s feature space (a validation batch) in
    ``design``'s own layout, host numpy leaves. A :class:`PanelBatch`'s rows
    go on ITS plan (order, rank and classes: no histogram is taken), so a
    vector renumbered for ``design`` indexes both; a column ``design`` never
    saw has a rank all the same, in the last class. Nothing is reported:
    the caller says under which counters (:func:`report_layout`)."""
    if not isinstance(design, PanelBatch):
        return TiledBatch.pack_batch(batch)
    return pack_panels(
        batch, _nonzeros(batch), [p.cls for p in design.parts],
        np.asarray(design.order), np.asarray(design.rank), shards)
