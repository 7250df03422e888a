"""Operations and bytes one pass of a tiled fixed-effect kernel has to do.

Counted from what the ALGORITHM needs, not from what the layout stores: a
gather pass (margins / dot_rows) or a scatter pass (gradient) over a sparse
design is one multiply-add per stored nonzero and has to read each nonzero's
value and column once (4 + 4 bytes; the tiled layout stores 16 bytes a slot
and pads slots, which is the layout's cost and not the algorithm's), and to
read or write one float32 per row twice (the per-row input and output).
The fused kernels (value+grad, hv) do two or three such passes over one
read of the design; they are counted as one, an undercount. An undercount
keeps a roofline share from ever passing 100%."""


def per_call(shape: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one kernel call over the whole design."""
    nnz, rows = shape["nnz"], shape["T"] * 128
    return 2.0 * nnz, 8.0 * nnz + 8.0 * rows
