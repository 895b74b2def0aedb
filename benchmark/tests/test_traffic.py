"""The generator deals every seed the same work: the same strata at the
same places of each client's stream, where the traffic file staggers."""

import json
import os

from benchmark.lib import spec, traffic


def _load(config, name):
    cfg = json.load(open(os.path.join(spec.BENCH_DIR, "configs", f"{config}.json")))
    return cfg, spec.traffic(name)


def test_staggered_rows_strata_are_the_same_for_every_seed():
    cfg, tr = _load("osm-gps-points", "osm-gps.heatmap")
    assert tr.get("stagger") and tr.get("strata_by") == "rows"
    pool = traffic.pool(cfg, tr)
    ranked = sorted(pool, key=lambda r: traffic._rows_key(cfg, r))
    stratum = {r.pool_index: 8 * i // len(ranked) for i, r in enumerate(ranked)}
    seqs = []
    for seed in (1, 2**31 + 7, 987654321987):
        streams = traffic.streams(pool, cfg, tr, seed)
        seqs.append([[stratum[r.pool_index] for r in s[:16]] for s in streams])
        # no request is sent twice
        sent = [r.pool_index for s in streams for r in s]
        assert len(sent) == len(set(sent))
    assert seqs[0] == seqs[1] == seqs[2]
    assert seqs[0][3][:3] == [3, 4, 5]


def test_mixture_share_of_the_world_is_one():
    cfg, _ = _load("osm-gps-points", "osm-gps.heatmap")
    from benchmark.lib.data import mixture_share

    assert abs(mixture_share((-180.0, -90.0, 180.0, 90.0), cfg["mixture"]) - 1.0) < 1e-6
