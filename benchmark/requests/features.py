"""WFS GetFeature / OSM API bbox requests: rows with a projection.

Traffic parameters: ``bbox.side_deg`` is the width's log-uniform range and
``bbox.aspect`` width/height, centred at the request's centre;
``window_days`` a ``DURING`` window as in ``density``; ``properties`` the
projection; ``attr_eq`` adds ``<attribute> = '<value>'`` to a ``share`` of
requests, the value drawn by the configuration's frequency.

``rows_off`` counts fids missing from or extra in the served result, and
rows whose projected values differ from the reference's.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Dict

import numpy as np

from benchmark.lib import traffic as tr

CHECK = "rows_off"


def draw(rng, config, traffic, n, cx, cy):
    time_attr = (config.get("time") or {}).get("attribute")
    days = traffic.get("window_days")
    bb = traffic["bbox"]
    lo, hi = math.log(bb["side_deg"][0]), math.log(bb["side_deg"][1])
    eq = traffic.get("attr_eq")
    if eq:
        eq_vals, eq_p = tr.attr_values(config, eq["attribute"])
    props = list(traffic["properties"])
    out = []
    for k in range(n):
        w = math.exp(rng.uniform(lo, hi))
        h = w / float(bb["aspect"])
        x0, y0 = float(cx[k]), float(cy[k])
        box = (round(max(-180.0, x0 - w / 2), 3), round(max(-90.0, y0 - h / 2), 3),
               round(min(180.0, x0 + w / 2), 3), round(min(90.0, y0 + h / 2), 3))
        win = tr.window(rng, config, days) if days else None
        attr = None
        if eq and rng.random() < float(eq["share"]):
            attr = (eq["attribute"], str(eq_vals[rng.choice(len(eq_vals), p=eq_p)]))
        out.append(tr.Request(k, tr.cql(time_attr, box, win, attr), box, win, attr,
                              {"properties": props}, size=w))
    return out


def query(req):
    from geomesa_tpu.index.planner import Query

    return Query.cql(req.cql, properties=list(req.params["properties"]))


def read(res):
    cols = {k: np.asarray(res.columns[k]) for k in list(res.columns.keys())}
    return cols, len(cols["__fid__"]) if "__fid__" in cols else 0


def answer(ref, req) -> np.ndarray:
    """Original row numbers of the matching rows."""
    return ref.rows(req)


def served(ref, req):
    """The reference's rows with their projected values at its precision."""
    rows = answer(ref, req)
    data = ref.ref
    cols: Dict[str, np.ndarray] = {"__fid__": data["fid"][rows]}
    for p in req.params["properties"]:
        if p == "geom":
            cols["geom__x"] = data["x"][rows].astype(ref.ft)
            cols["geom__y"] = data["y"][rows].astype(ref.ft)
        elif p == "dtg":
            cols["dtg"] = data["t"][rows].astype(ref.ft).astype(np.int64)
        elif isinstance(data.get(p), tuple):
            codes, vocab = data[p]
            cols[p] = vocab[codes[rows]]
        else:
            cols[p] = np.asarray(data[p])[rows]
    return SimpleNamespace(columns=cols, plan=SimpleNamespace(scan_path="control"))


def _strings(col) -> np.ndarray:
    return np.asarray(col).astype(str)


def off(output: Dict[str, np.ndarray], rows: np.ndarray, req, ref) -> int:
    """Rows missing from, extra in, or with a projected value unlike the
    reference's. ``output`` is the served columns (``__fid__`` plus the
    projection); ``rows`` the reference's original row numbers."""
    data = ref.ref
    got_fid = _strings(output["__fid__"])
    want_fid = data["fid"][rows].astype(str)
    g_order = np.argsort(got_fid, kind="stable")
    w_order = np.argsort(want_fid, kind="stable")
    common, gi, wi = np.intersect1d(got_fid[g_order], want_fid[w_order],
                                    assume_unique=False, return_indices=True)
    n_off = (len(got_fid) - len(common)) + (len(want_fid) - len(common))
    if not len(common):
        return int(n_off)
    g_idx = g_order[gi]
    w_rows = rows[w_order[wi]]
    bad = np.zeros(len(common), dtype=bool)
    for p in req.params["properties"]:
        if p == "geom":
            for axis in ("x", "y"):
                got = np.asarray(output[f"geom__{axis}"], dtype=np.float64)[g_idx]
                bad |= got != data[axis][w_rows]
        elif p == "dtg":
            got = np.asarray(output["dtg"])
            if np.issubdtype(got.dtype, np.datetime64):
                got = got.astype("datetime64[ms]").astype(np.int64)
            bad |= got.astype(np.int64)[g_idx] != data["t"][w_rows]
        elif isinstance(data.get(p), tuple):
            codes, vocab = data[p]
            bad |= _strings(output[p])[g_idx] != vocab[codes[w_rows]].astype(str)
        else:
            bad |= _strings(output[p])[g_idx] != np.asarray(data[p])[w_rows].astype(str)
    return int(n_off + bad.sum())
