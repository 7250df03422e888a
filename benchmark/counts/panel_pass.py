"""Operations and bytes ONE call of a tail panel kernel has to do, on the
average over the ``classes`` calls that make a pass over the tail (every
class is called once a pass, so calls x this = the pass).

Counted from what the algorithm needs over the tail's OWN nonzeros: one
multiply-add per stored nonzero, its value and column read once (8 bytes;
the layout stores 20 bytes a slot and pads tiles, which is the layout's
cost), one float32 per row read or written by every class (4 bytes a row a
call: an undercount of the 8 the plain kernels are given), and each
coefficient of the tail read or written once a pass (4 bytes)."""


def per_call(shape: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one panel kernel call, the mean over classes."""
    nnz, rows, calls = shape["nnz"], shape["T"] * 128, shape["classes"]
    return (2.0 * nnz / calls,
            (8.0 * nnz + 4.0 * shape["features"]) / calls + 4.0 * rows)
