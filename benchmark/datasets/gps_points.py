"""OpenStreetMap GPS Planet points: bare lat/lon, from a seed.

A mixture of city clusters weighted to Europe, then North America and
East Asia, plus a background over the inhabited latitudes. Coordinates
are quantised to the dump's 1e-7 degree grid (int32 fixed point).
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.data import Dataset, mixture_points


def make(config, rows: int, seed: int) -> Dataset:
    rng = np.random.default_rng(seed)
    x, y = mixture_points(rng, rows, config["mixture"])
    width = len(str(max(rows - 1, 1)))
    fids = np.arange(rows, dtype=np.int64).astype(f"U{width}")
    cols = {"__fid__": fids, "geom__x": x, "geom__y": y}
    return Dataset(columns=cols, ref={"x": x, "y": y, "fid": fids}, rows=rows)
