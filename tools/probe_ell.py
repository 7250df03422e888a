"""ELL-layout probe: measure the lane-aligned margins kernel against the
current tiled margins kernel at the bench shape, using the K-repetition
slope method from PERF_NOTES (per-pass device time, fixed per-call
overhead excluded). Decides whether the full ELL integration is worth it."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import sys
sys.path.insert(0, "/root/repo")

from photon_ml_tpu.ops.tiled import (
    LANE, ROWS_PER_TILE, TiledBatch, _mm2, _split_bf16, _spec_w,
)

# bench shape: 1M x 10K, 20 nnz/row
N, D, NNZ = 1_000_000, 10_000, 20


def _ell_margins_kernel(S2, *refs):
    """Lane-aligned: slot (s2, j) belongs to ROW j of the tile (lane j).
    The gather runs one UNROLLED step per s2 (Mosaic cannot shape-cast
    [S2,128] vectors to flat slots): each step one-hots 128 slots and
    picks w lanes; per-row margins accumulate elementwise in [1, 128] —
    NO row one-hot, no row matvecs, no transposed-broadcast."""
    (vals_ref, hi_ref, lo_ref, w_ref, out_z_ref) = refs
    B = w_ref.shape[0]
    w = w_ref[:]
    whi, wlo = _split_bf16(w)
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (LANE, B), 1)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1)
    ones = jnp.ones((LANE, 1), jnp.bfloat16)
    z = jnp.zeros((1, LANE), jnp.float32)
    for s2 in range(S2):
        hi = hi_ref[0, s2, :]                    # [128] slot block ids
        lo = lo_ref[0, s2, :]
        vals = vals_ref[0, s2, :]
        mask_hi = (hi[:, None] == iota_b).astype(jnp.bfloat16)  # [128, B]
        mask_lo = (lo[:, None] == iota_l).astype(jnp.bfloat16)  # [128,128]
        wrow = _mm2(mask_hi, whi, wlo)           # [128(slots), 128(lanes)]
        e = (wrow * mask_lo) * vals[:, None]
        eh, el = _split_bf16(e)
        g = jax.lax.dot_general(
            eh, ones, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        g = g + jax.lax.dot_general(
            el, ones, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [128, 1]: slot j = row j
        z = z + g.reshape(1, LANE)
    out_z_ref[0, :, :] = z


@functools.lru_cache(maxsize=None)
def _ell_call(T, S2, B):
    kern = functools.partial(_ell_margins_kernel, S2)
    return pl.pallas_call(
        kern,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, S2, LANE), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ] * 3 + [_spec_w(B)],
        out_specs=pl.BlockSpec((1, 1, ROWS_PER_TILE), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((T, 1, ROWS_PER_TILE), jnp.float32),
        interpret=jax.default_backend() != "tpu",
    )


def main():
    rng = np.random.default_rng(0)
    rows = np.repeat(np.arange(N, dtype=np.int64), NNZ)
    cols = rng.integers(0, D, size=N * NNZ)
    vals = rng.normal(size=N * NNZ)
    y = rng.integers(0, 2, size=N).astype(float)

    tb = TiledBatch.from_coo(values=vals, rows=rows, cols=cols, labels=y,
                             num_features=D)
    T = tb.num_tiles
    B = tb.num_blocks
    S2 = NNZ  # constant nnz/row -> exact ELL occupancy

    # ELL arrays: slot (t, s2, j) = nnz s2 of row t*128+j
    ell_vals = np.zeros((T, S2, LANE), np.float32)
    ell_hi = np.full((T, S2, LANE), B, np.int32)
    ell_lo = np.zeros((T, S2, LANE), np.int32)
    t_idx = (rows // LANE).astype(np.int64)
    j_idx = (rows % LANE).astype(np.int64)
    s_idx = np.tile(np.arange(NNZ, dtype=np.int64), N)
    ell_vals[t_idx, s_idx, j_idx] = vals
    ell_hi[t_idx, s_idx, j_idx] = cols // LANE
    ell_lo[t_idx, s_idx, j_idx] = cols % LANE

    w = jnp.asarray(rng.normal(size=D), jnp.float32)
    w2 = jnp.zeros((B * LANE,), jnp.float32).at[:D].set(w).reshape(B, LANE)
    ev = jnp.asarray(ell_vals)
    eh = jnp.asarray(ell_hi)
    el = jnp.asarray(ell_lo)

    # correctness vs the tiled path
    z_ell = _ell_call(T, S2, B)(ev, eh, el, w2).reshape(-1)[:N]
    z_ref = tb.margins(w)[:N]
    err = float(jnp.max(jnp.abs(z_ell - z_ref)))
    print("max |z_ell - z_tiled| =", err)

    # slope timing: K repetitions inside one jit, with a dependency chain
    # through the weight argument so XLA cannot CSE the repetitions
    def time_slope(fn, w_arg, *rest):
        def rep(k):
            @jax.jit
            def run(ww, *a):
                acc = jnp.float32(0.0)
                for _ in range(k):
                    s = jnp.sum(fn(ww, *a))
                    acc = acc + s
                    ww = ww + s * 1e-30
                return acc
            float(run(w_arg, *rest))  # compile+warm
            t0 = time.perf_counter()
            float(run(w_arg, *rest))
            return time.perf_counter() - t0
        t1, t9 = rep(1), rep(9)
        return (t9 - t1) / 8

    ell_pass = time_slope(
        lambda ww, v, h, lo_: _ell_call(T, S2, B)(v, h, lo_, ww),
        w2, ev, eh, el)
    tiled_pass = time_slope(lambda ww, b: b.margins(ww), w, tb)
    print(f"ELL margins pass:   {ell_pass*1e3:.1f} ms")
    print(f"tiled margins pass: {tiled_pass*1e3:.1f} ms")
    print(f"speedup: {tiled_pass/ell_pass:.3f}x")


if __name__ == "__main__":
    main()
