"""WMS heatmaps: a density grid per request.

Traffic parameters: ``grid`` cells a side; with ``envelope`` ``gwc_tile``
(the default) the envelope is a GeoWebCache EPSG:4326 tile at a zoom drawn
from ``zooms`` (zoom z is 180/2^z degrees a side), the tile that holds the
request's centre; with ``viewport`` it is an untiled GetMap viewport of a
side 180/2^z for z uniform over ``zooms``, centred there; with
``window_days`` a ``DURING`` window of that many days (uniform, in ms) at
a start drawn in ms.

The reference snaps with GridSnap: ``col = floor((x - xmin) / ((xmax -
xmin) / width))``, clipped to the last cell; ``cells_off`` counts the
grid cells that differ.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import numpy as np

from benchmark.lib import traffic as tr

CHECK = "cells_off"


def _tile(cx: float, cy: float, zoom: int):
    side = 180.0 / (1 << zoom)
    nx, ny = 2 << zoom, 1 << zoom
    i = min(int(math.floor((cx + 180.0) / side)), nx - 1)
    j = min(int(math.floor((cy + 90.0) / side)), ny - 1)
    return (-180.0 + i * side, -90.0 + j * side,
            -180.0 + (i + 1) * side, -90.0 + (j + 1) * side)


def _viewport(cx: float, cy: float, zoom: float):
    """A square untiled WMS GetMap viewport 180/2^zoom degrees a side,
    centred at (cx, cy) and shifted inside the world, corners rounded to
    1e-6 degree."""
    side = 180.0 / 2.0 ** zoom
    x0 = min(max(cx - side / 2, -180.0), 180.0 - side)
    y0 = min(max(cy - side / 2, -90.0), 90.0 - side)
    x0, y0 = round(x0, 6), round(y0, 6)
    return (x0, y0, round(x0 + side, 6), round(y0 + side, 6))


def draw(rng, config, traffic, n, cx, cy):
    time_attr = (config.get("time") or {}).get("attribute")
    days = traffic.get("window_days")
    grid = int(traffic["grid"])
    z0, z1 = traffic["zooms"]
    viewport = traffic.get("envelope", "gwc_tile") == "viewport"
    zooms = rng.uniform(z0, z1, n) if viewport else rng.integers(z0, z1 + 1, n)
    out = []
    for k in range(n):
        if viewport:
            env = _viewport(float(cx[k]), float(cy[k]), float(zooms[k]))
        else:
            env = _tile(float(cx[k]), float(cy[k]), int(zooms[k]))
        win = tr.window(rng, config, days) if days else None
        out.append(tr.Request(k, tr.cql(time_attr, env, win, None), env, win, None,
                              {"envelope": env, "width": grid, "height": grid},
                              size=env[2] - env[0]))
    return out


def query(req):
    from geomesa_tpu.index.planner import Query

    return Query.cql(req.cql, hints={"density": dict(req.params)})


def read(res):
    grid = np.asarray(res.aggregate["density"])
    return grid, int(grid.sum())


def answer(ref, req) -> np.ndarray:
    env = req.params["envelope"]
    w, h = int(req.params["width"]), int(req.params["height"])
    pos = ref.select(req)
    ft = ref.ft
    x, y = ref.x[pos], ref.y[pos]
    xmin, ymin, xmax, ymax = (ft(v) for v in env)
    dx = ft((xmax - xmin) / ft(w))
    dy = ft((ymax - ymin) / ft(h))
    col = np.clip(np.floor((x - xmin) / dx).astype(np.int64), 0, w - 1)
    row = np.clip(np.floor((y - ymin) / dy).astype(np.int64), 0, h - 1)
    return np.bincount(row * w + col, minlength=w * h).reshape(h, w)


def served(ref, req):
    return SimpleNamespace(aggregate={"density": answer(ref, req)},
                           plan=SimpleNamespace(scan_path="control"))


def off(output, want, req, ref) -> int:
    """Cells of a served grid that differ from the reference's."""
    got = np.asarray(output)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))
