"""The one traffic generator: a cell's traffic file of parameters -> the
requests each closed-loop client sends.

A traffic file (``benchmark/traffic/<cell>.json``) names a ``request``
kind; ``benchmark/requests/<kind>.py`` draws that kind's requests from the
file's parameters and knows how to send, read, answer and compare them
(see ``benchmark/requests/__init__.py``). What every kind shares is here:
the fixed pool, its centres drawn from the configuration's own spatial
mixture, the time windows, the CQL, and the dealing to the clients.

Requests come from a fixed pool drawn with the file's ``pool_seed``, the
same for every run: the seed deals the pool to the clients, so every seed
sends requests of the same sizes in another order, and no two clients
send the same request in one pass. Dealing parameters of the file:
``strata_by`` ``size`` (the default: the envelope's or bbox's width) or
``rows`` (the share of the configuration's mixture inside the box, times
the window's share of the time span: the work a request expects), and
``stagger`` (each client sends the strata in one fixed order, starting at
its own, so every seed's clients meet the same strata at the same places;
without it the seed draws the order of each block).
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.lib.data import mixture_centres, mixture_share

DAY_MS = 86_400_000


@dataclass
class Request:
    pool_index: int
    cql: str
    box: Optional[Tuple[float, float, float, float]]
    window: Optional[Tuple[int, int]]  # (lo, hi) epoch ms, both exclusive
    attr: Optional[Tuple[str, str]]  # (attribute, value) equality
    params: Dict = field(default_factory=dict)  # the kind's own
    size: float = 0.0  # the envelope's or bbox's width: the stratum key


def kind(traffic):
    """The request kind's module, found by the traffic file's ``request``."""
    return importlib.import_module(f"benchmark.requests.{traffic['request']}")


def _instant(ms: int) -> str:
    s = np.datetime64(int(ms), "ms").astype(str)
    return s + "Z"


def window(rng, config, days) -> Tuple[int, int]:
    """A window of a length uniform over ``days``, at a start drawn in ms."""
    t0 = int(np.datetime64(config["time"]["start"], "ms").astype(np.int64))
    span = int(config["time"]["span_days"]) * DAY_MS
    length = int(rng.uniform(days[0], days[1]) * DAY_MS)
    start = t0 + int(rng.integers(0, max(1, span - length)))
    return start, start + length


def cql(time_attr, box, win, attr) -> str:
    parts = []
    if box is not None:
        parts.append("bbox(geom, {!r}, {!r}, {!r}, {!r})".format(*box))
    if win is not None:
        parts.append(f"{time_attr} DURING {_instant(win[0])}/{_instant(win[1])}")
    if attr is not None:
        parts.append(f"{attr[0]} = '{attr[1]}'")
    return " AND ".join(parts)


def attr_values(config, attr):
    """(values, probabilities) of an attribute, from the config's generator."""
    mod = importlib.import_module(f"benchmark.datasets.{config['generator']}")
    return mod.frequencies(config, attr)


def pool(config, traffic, warm: bool = False) -> List[Request]:
    """The cell's request pool; ``warm`` draws the warm-up requests from
    another seed, so none of them comes again in the window."""
    rng = np.random.default_rng(int(traffic["pool_seed"]) + (1 if warm else 0))
    n = int(traffic["warmup"] if warm else traffic["pool"])
    cx, cy = mixture_centres(rng, n, config["mixture"])
    return kind(traffic).draw(rng, config, traffic, n, cx, cy)


def _rows_key(config, r: Request) -> float:
    share = mixture_share(r.box, config["mixture"]) if r.box else 1.0
    if r.window is not None:
        share *= (r.window[1] - r.window[0]) / (int(config["time"]["span_days"]) * DAY_MS)
    return share


def streams(requests: List[Request], config, traffic, seed: int,
            strata: int = 8) -> List[List[Request]]:
    """Deal the pool to the clients so that every seed sends the same mix.

    The requests are split by the ``strata_by`` key into ``strata`` equal
    strata; each client's stream is a run of blocks that hold one request
    of each stratum, in an order the seed draws, or with ``stagger`` in
    the fixed order that starts at the client's own stratum. The seed
    picks which requests of each stratum a client gets."""
    clients = int(traffic["clients"])
    if traffic.get("strata_by", "size") == "rows":
        key = lambda r: _rows_key(config, r)  # noqa: E731
    else:
        key = lambda r: r.size  # noqa: E731
    stagger = bool(traffic.get("stagger", False))
    rng = np.random.default_rng(seed)
    ordered = sorted(requests, key=key)
    bounds = np.linspace(0, len(ordered), strata + 1).astype(int)
    dealt = []
    for s in range(strata):
        stratum = ordered[bounds[s]:bounds[s + 1]]
        order = rng.permutation(len(stratum))
        dealt.append([[stratum[i] for i in order[c::clients]] for c in range(clients)])
    out = []
    for c in range(clients):
        blocks = min(len(dealt[s][c]) for s in range(strata))
        seq = []
        for b in range(blocks):
            sequence = ([(c + k) % strata for k in range(strata)] if stagger
                        else rng.permutation(strata))
            seq.extend(dealt[s][c][b] for s in sequence)
        out.append(seq)
    return out
