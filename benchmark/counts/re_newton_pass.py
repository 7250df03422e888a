"""Operations and bytes the per-entity Newton passes of ONE random-effect
coordinate require in one fit.

Counted from what the ALGORITHM needs on each entity's OWN r rows and k
local features, not from what the geometry buckets pad them to: per Newton
iteration the margins are 2rk FLOPs, the gradient 2rk, the Hessian 2rk^2
and the factorisation k^3/3, and the [r, k] design is read three times at
4 bytes a value (12rk bytes). The iterations are the program's own, from
its tracker, as two sums it keeps as counters: ``lane_iterations`` (every
entity's iterations, summed) and ``pass_cells`` (each entity's iterations
x its r x its k), so the rk terms are exact. The k^2 and k^3 terms take
every entity's k at the coordinate's least (``k_min``): an undercount, as
are the line search's evaluations, the scores and the residual gathers,
which are left out. An undercount keeps a roofline share under 100%."""


def per_fit(shape: dict, lane_iterations: float,
            pass_cells: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one coordinate's Newton passes in one fit."""
    k = shape["pass"]["k_min"]
    flops = (4.0 + 2.0 * k) * pass_cells + lane_iterations * k ** 3 / 3.0
    return flops, 12.0 * pass_cells
