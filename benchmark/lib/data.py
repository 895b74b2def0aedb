"""Shared pieces of the deployment generators (``benchmark/datasets``)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import numpy as np


@dataclass
class Dataset:
    """What a generator makes from the seed.

    ``columns`` go to ``writer(name).write_columns`` as they are. ``ref``
    holds the plain arrays the reference reads: ``x``, ``y`` (float64
    degrees), ``t`` (int64 epoch ms, absent without a time attribute) and,
    per projected string attribute, ``(codes, vocab)``.
    """

    columns: Dict[str, np.ndarray]
    ref: Dict[str, Any]
    rows: int


_ZIPF_BITS = 20


def zipf_codes(rng, n: int, k: int, s: float) -> np.ndarray:
    """n draws of ranks 0..k-1 with P(rank r) proportional to 1/(r+1)^s,
    through an inverse-CDF table of 2^20 equal-probability buckets."""
    w = 1.0 / np.arange(1, k + 1, dtype=np.float64) ** float(s)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    m = 1 << _ZIPF_BITS
    table = np.searchsorted(cdf, (np.arange(m) + 0.5) / m, side="right")
    table = table.clip(0, k - 1).astype(np.int32)
    return table[rng.integers(0, m, n, dtype=np.uint32)]


def mixture_points(rng, n: int, mixture) -> tuple:
    """Points from a mixture of Gaussian clusters and a uniform background
    (the ``bench.synthesize`` shape, generalised), clipped to the world and
    shuffled. ``mixture``: {"background": {"share", "lon", "lat"},
    "clusters": [{"share", "lon", "lat", "sd": [sd_lon, sd_lat]}],
    "quantum": optional grid step in degrees}."""
    clusters = mixture["clusters"]
    counts = [int(c["share"] * n) for c in clusters]
    n_bg = n - sum(counts)
    bg = mixture["background"]
    xs = [rng.uniform(bg["lon"][0], bg["lon"][1], n_bg)]
    ys = [rng.uniform(bg["lat"][0], bg["lat"][1], n_bg)]
    for c, k in zip(clusters, counts):
        xs.append(rng.normal(c["lon"], c["sd"][0], k))
        ys.append(rng.normal(c["lat"], c["sd"][1], k))
    x = np.clip(np.concatenate(xs), -180.0, 180.0)
    y = np.clip(np.concatenate(ys), -90.0, 90.0)
    q = mixture.get("quantum")
    if q:
        # a fixed-point grid (the GPS dump's int32 at 1e-7 deg)
        inv = round(1.0 / float(q))
        x = np.round(x * inv) / inv
        y = np.round(y * inv) / inv
    order = rng.permutation(n)
    return x[order], y[order]


def mixture_centres(rng, n: int, mixture) -> tuple:
    """n points drawn from the same mixture as the data, unclipped to a
    quantum: where the traffic centres its requests."""
    comps = [mixture["background"]] + list(mixture["clusters"])
    share = np.array([mixture["background"].get("share", 0.0)]
                     + [c["share"] for c in mixture["clusters"]], dtype=np.float64)
    share[0] = max(0.0, 1.0 - share[1:].sum())
    pick = rng.choice(len(comps), size=n, p=share / share.sum())
    x = np.empty(n)
    y = np.empty(n)
    for i, c in enumerate(comps):
        m = pick == i
        k = int(m.sum())
        if i == 0:
            x[m] = rng.uniform(c["lon"][0], c["lon"][1], k)
            y[m] = rng.uniform(c["lat"][0], c["lat"][1], k)
        else:
            x[m] = rng.normal(c["lon"], c["sd"][0], k)
            y[m] = rng.normal(c["lat"], c["sd"][1], k)
    return np.clip(x, -180.0, 180.0), np.clip(y, -90.0, 90.0)


def mixture_share(box, mixture) -> float:
    """The share of the mixture's points inside ``box`` (xmin, ymin, xmax,
    ymax), worked out from its clusters and background (clipping to the
    world ignored): what a request over ``box`` expects to read."""
    from math import erf, sqrt

    def norm(a, b, mu, sd):
        return 0.5 * (erf((b - mu) / (sd * sqrt(2.0))) - erf((a - mu) / (sd * sqrt(2.0))))

    x0, y0, x1, y1 = box
    bg = mixture["background"]
    total = 0.0
    for c in mixture["clusters"]:
        total += c["share"] * norm(x0, x1, c["lon"], c["sd"][0]) * norm(y0, y1, c["lat"], c["sd"][1])
    share_bg = max(0.0, 1.0 - sum(c["share"] for c in mixture["clusters"]))
    fx = max(0.0, min(x1, bg["lon"][1]) - max(x0, bg["lon"][0])) / (bg["lon"][1] - bg["lon"][0])
    fy = max(0.0, min(y1, bg["lat"][1]) - max(y0, bg["lat"][0])) / (bg["lat"][1] - bg["lat"][0])
    return total + share_bg * fx * fy

