"""The plain reference: which rows each request matches, from the seed's
data alone (``bench.brute_force``, copied and generalised; nothing of
``geomesa_tpu`` is imported). Each request kind
(``benchmark/requests/<kind>.py``) makes its answer from these rows.

Semantics: ``bbox`` keeps ``xmin <= x <= xmax`` and ``ymin <= y <= ymax``;
``DURING`` keeps ``lo < t < hi``; an attribute equality compares the
decoded value.

``precision="f32"`` is the control: the same reference with the data, the
boxes, the windows and the snap arithmetic in float32, the precision below
the configuration's float64 degrees and int64 milliseconds.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


class Reference:
    def __init__(self, ref: Dict, precision: str = "f64"):
        if precision not in ("f64", "f32"):
            raise ValueError(precision)
        self.precision = precision
        self.ref = ref
        # one sort by x: a bbox reads only its slice of x
        self.order = np.argsort(ref["x"], kind="stable")
        ft = np.float32 if precision == "f32" else np.float64
        self.ft = ft
        self.x = ref["x"][self.order].astype(ft, copy=False)
        self.y = ref["y"][self.order].astype(ft, copy=False)
        t = ref.get("t")
        if t is None:
            self.t = None
        elif precision == "f32":
            self.t = t[self.order].astype(np.float32)
        else:
            self.t = t[self.order]

    def _slice(self, box):
        if box is None:
            return 0, len(self.x)
        lo = int(np.searchsorted(self.x, self.ft(box[0]), side="left"))
        hi = int(np.searchsorted(self.x, self.ft(box[2]), side="right"))
        return lo, hi

    def select(self, req) -> np.ndarray:
        """Sorted-order positions of the rows the request matches."""
        lo, hi = self._slice(req.box)
        m = np.ones(hi - lo, dtype=bool)
        if req.box is not None:
            y = self.y[lo:hi]
            m &= (y >= self.ft(req.box[1])) & (y <= self.ft(req.box[3]))
        if req.window is not None:
            t = self.t[lo:hi]
            if self.precision == "f32":
                w0, w1 = np.float32(req.window[0]), np.float32(req.window[1])
            else:
                w0, w1 = req.window
            m &= (t > w0) & (t < w1)
        pos = lo + np.flatnonzero(m)
        if req.attr is not None:
            codes, vocab = self.ref[req.attr[0]]
            k = int(np.searchsorted(vocab, req.attr[1]))
            if k >= len(vocab) or vocab[k] != req.attr[1]:
                return pos[:0]
            pos = pos[codes[self.order[pos]] == k]
        return pos

    def rows(self, req) -> np.ndarray:
        """Original row numbers of the matching rows."""
        return self.order[self.select(req)]

    def count(self, req) -> int:
        return int(len(self.select(req)))
