"""``game_fit``'s driver for a design that the program lays out as column
panels (``photon_ml_tpu/ops/panels.py``): the same set-up, the same unit of
work, and a description of that layout for the counts functions.

``shapes()`` gives the whole coordinate WITHOUT a tile count (so that a
reader which multiplies every Mosaic call by the whole design's count finds
nothing to read) and, beside it, one entry per kernel family with that
family's own nonzeros: ``<name>.hot`` (the tiled kernels over the hot panel)
and ``<name>.tail`` (the panel kernels, ``classes`` calls a pass)."""

from __future__ import annotations

from benchmark.drivers import game_fit


#: a rehearsal keeps the design wide enough for the panel layout (wider
#: than 128 column blocks), whatever its rows are scaled down to
REHEARSAL_FEATURES = 130 * 128


class Driver(game_fit.Driver):
    def __init__(self, config, traffic, seed, rows=None, force_tiled=False):
        super().__init__(config, traffic, seed, rows=rows,
                         force_tiled=force_tiled)
        if rows is not None:
            self.shape["fe_features"] = max(
                self.shape["fe_features"], REHEARSAL_FEATURES)

    def shapes(self) -> dict:
        paneled = {
            name: c._tiled for name, c in self.coordinates.items()
            if hasattr(getattr(c, "_tiled", None), "parts")}
        if not paneled:
            return super().shapes()
        out = {"rows": self.shape["rows"], "coordinates": {}}
        for name, design in paneled.items():
            hot_nnz, *tail_nnz = design.stored
            hot = design.hot
            out["coordinates"][name] = {
                "kind": "fixed_effect", "nnz": int(sum(design.stored)),
                "features": int(design.num_features),
                "slots": int(design.nnz_slots),
            }
            out["coordinates"][name + ".hot"] = {
                "T": int(hot.num_tiles), "S": int(hot.vals.shape[2]),
                "B": int(hot.num_blocks), "nnz": int(hot_nnz),
                "features": int(hot.num_features),
            }
            out["coordinates"][name + ".tail"] = {
                "T": int(design.num_tiles), "nnz": int(sum(tail_nnz)),
                "classes": len(design.parts),
                "features": int(design.num_features - hot.num_features),
                "windows": [int(p.cls.window) for p in design.parts],
                "tiles": [int(p.vals.shape[0]) for p in design.parts],
            }
        return out
