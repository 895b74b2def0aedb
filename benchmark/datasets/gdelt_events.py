"""GDELT 1.0 event rows for GeoMesa's premade ``gdelt`` schema, from a seed.

Coordinates and times follow ``bench.synthesize`` (copied, not imported):
three city clusters plus a uniform background, over ``time.span_days``.
The string attributes are drawn Zipf-skewed from vocabularies built here
(CAMEO-shaped event codes, country codes, actor names) and handed to the
writer dictionary-encoded: int32 codes into a sorted ``<name>__vocab``,
the at-rest layout the store's own string interning produces
(``store/blocks.intern_string_columns``). Everything is vectorised NumPy.

``make(config, rows, seed)`` returns a ``Dataset``: the columns for
``writer(...).write_columns`` and the plain arrays the reference reads.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.data import Dataset, mixture_points, zipf_codes

# CAMEO root codes 01-20 with their quad class (1 verbal coop, 2 material
# coop, 3 verbal conflict, 4 material conflict) and a Goldstein anchor
_ROOTS = [
    ("01", 1, 0.0), ("02", 1, 3.0), ("03", 1, 4.0), ("04", 1, 1.0),
    ("05", 1, 3.5), ("06", 2, 6.0), ("07", 2, 7.0), ("08", 2, 5.0),
    ("09", 3, -2.0), ("10", 3, -5.0), ("11", 3, -2.0), ("12", 3, -4.0),
    ("13", 3, -6.0), ("14", 4, -6.5), ("15", 4, -7.2), ("16", 4, -4.0),
    ("17", 4, -7.0), ("18", 4, -9.0), ("19", 4, -10.0), ("20", 4, -10.0),
]
# GDELT-like frequency rank of the root codes (04, 01, 05, 03 ... commonest)
_ROOT_RANK = ["04", "01", "05", "03", "02", "19", "11", "08", "17", "07",
              "12", "06", "14", "13", "10", "18", "16", "09", "15", "20"]
_GROUPS = ["", "GOV", "MIL", "REB", "OPP", "BUS", "MED", "EDU", "JUD", "LEG",
           "COP", "CVL", "IGO", "NGO", "REF", "LAB", "HLH", "CRM", "SPY", "ELI"]
_RELIGIONS = ["", "CHR", "MOS", "JEW", "BUD", "HIN", "SHI", "SUN", "CTH", "ITR"]


def _cameo_codes():
    """~300 event codes: each root r, base codes r0..r9 (fewer for the
    small roots), and sub codes base0..base4 under the first bases."""
    codes, base_of, root_of = [], [], []
    for i, (root, _q, _g) in enumerate(_ROOTS):
        n_base = 4 + (i * 7) % 7
        for b in range(n_base):
            base = f"{root}{b}"
            for s in [""] + [str(k) for k in range(1 + (b * 3 + i) % 5)]:
                codes.append(base + s)
                base_of.append(base)
                root_of.append(root)
    return codes, base_of, root_of


def _country_codes(rng):
    heads = ["USA", "GBR", "RUS", "CHN", "FRA", "IND", "ISR", "PAK", "SYR",
             "IRN", "AFG", "IRQ", "UKR", "DEU", "JPN", "TUR", "EGY", "NGA",
             "AUS", "CAN", "BRA", "MEX", "ZAF", "KOR", "PRK", "SAU", "LBY"]
    seen = set(heads)
    out = list(heads)
    letters = np.array(list("ABCDEFGHIJKLMNOPQRSTUVWXYZ"))
    while len(out) < 250:
        c = "".join(letters[rng.integers(0, 26, 3)])
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def _actor_names(rng, n):
    syll = ["AL", "AN", "BA", "BER", "CA", "DA", "EL", "FA", "GA", "HA", "IS",
            "KA", "LA", "MA", "NA", "OR", "PA", "RA", "SA", "TA", "UR", "VA",
            "WA", "YA", "ZA", "MIN", "STAN", "TON", "LIA", "RIA"]
    kinds = ["", " GOVERNMENT", " POLICE", " MILITARY", " PRESIDENT",
             " PROTESTER", " MINISTRY", " PARLIAMENT", " REBEL", " COMPANY"]
    seen = set()
    out = [""]
    while len(out) < n:
        k = rng.integers(2, 5)
        name = "".join(syll[j] for j in rng.integers(0, len(syll), k))
        name += kinds[int(rng.integers(0, len(kinds)))]
        if name not in seen:
            seen.add(name)
            out.append(name)
    return out


def _encode(values, rank_codes):
    """Values listed in frequency-rank order + codes in rank space ->
    (int32 codes into the SORTED vocab, sorted vocab)."""
    vals = np.asarray(values)
    order = np.argsort(vals, kind="stable")
    vocab = vals[order]
    pos = np.empty(len(vals), dtype=np.int32)
    pos[order] = np.arange(len(vals), dtype=np.int32)
    return pos[rank_codes], vocab


def vocabularies(config):
    """The fixed vocabularies (seed-independent: every run sees the same)."""
    vrng = np.random.default_rng(int(config["vocab_seed"]))
    codes, base_of, root_of = _cameo_codes()
    # frequency order of event codes: by their root's rank, then listing order
    rank = {r: i for i, r in enumerate(_ROOT_RANK)}
    order = sorted(range(len(codes)), key=lambda i: (rank[root_of[i]], i))
    return {
        "event": [codes[i] for i in order],
        "base": [base_of[i] for i in order],
        "root": [root_of[i] for i in order],
        "country": _country_codes(vrng),
        "names": _actor_names(vrng, int(config["vocab"]["actor_names"])),
    }


def make(config, rows: int, seed: int) -> Dataset:
    v = config["vocab"]
    voc = vocabularies(config)
    rng = np.random.default_rng(seed)
    x, y = mixture_points(rng, rows, config["mixture"])
    t0 = np.datetime64(config["time"]["start"], "ms").astype(np.int64)
    span_ms = int(config["time"]["span_days"]) * 86_400_000
    t = t0 + rng.integers(0, span_ms, rows)

    cols = {}
    ref = {"x": x, "y": y, "t": t}

    # event codes: one Zipf draw, the base/root/quad/Goldstein derived
    ev_rank = zipf_codes(rng, rows, len(voc["event"]), v["event_zipf"])
    ev, ev_vocab = _encode(voc["event"], ev_rank)
    cols["eventCode"], cols["eventCode__vocab"] = ev, ev_vocab
    base_vals = np.asarray(voc["base"])
    root_vals = np.asarray(voc["root"])
    b_codes, b_vocab = _encode_derived(base_vals, ev_rank)
    r_codes, r_vocab = _encode_derived(root_vals, ev_rank)
    cols["eventBaseCode"], cols["eventBaseCode__vocab"] = b_codes, b_vocab
    cols["eventRootCode"], cols["eventRootCode__vocab"] = r_codes, r_vocab
    ref["eventRootCode"] = (r_codes, r_vocab)
    quad = {r: q for r, q, _g in _ROOTS}
    gold = {r: g for r, _q, g in _ROOTS}
    quad_by_rank = np.array([quad[r] for r in voc["root"]], dtype=np.int32)
    gold_by_rank = np.array([gold[r] for r in voc["root"]], dtype=np.float64)
    cols["quadClass"] = quad_by_rank[ev_rank]
    cols["goldsteinScale"] = gold_by_rank[ev_rank]
    cols["isRootEvent"] = (rng.random(rows, dtype=np.float32) < 0.45).astype(np.int32)

    countries = voc["country"]
    combos = [c + g for c in countries for g in _GROUPS[:8]]
    combo_country = [c for c in countries for _g in _GROUPS[:8]]
    combo_group = [g for _c in countries for g in _GROUPS[:8]]
    for side in ("1", "2"):
        nm_rank = zipf_codes(rng, rows, len(voc["names"]), v["name_zipf"])
        nm, nm_vocab = _encode(voc["names"], nm_rank)
        cols[f"actor{side}Name"], cols[f"actor{side}Name__vocab"] = nm, nm_vocab
        if side == "1":
            ref["actor1Name"] = (nm, nm_vocab)
        c_rank = zipf_codes(rng, rows, len(combos), v["country_zipf"])
        for key, vals in (("Code", combos), ("CountryCode", combo_country),
                          ("GroupCode", combo_group)):
            codes_, vocab_ = _encode_derived(np.asarray(vals), c_rank)
            cols[f"actor{side}{key}"] = codes_
            cols[f"actor{side}{key}__vocab"] = vocab_
        for key, vals, empty in (("EthnicCode", countries[:40], 0.95),
                                 ("Religion1Code", _RELIGIONS, 0.9),
                                 ("Religion2Code", _RELIGIONS, 0.98)):
            draw = zipf_codes(rng, rows, len(vals), 1.2)
            draw[rng.random(rows, dtype=np.float32) < empty] = 0
            values = [""] + [s for s in vals if s]
            codes_, vocab_ = _encode(values, np.minimum(draw, len(values) - 1))
            cols[f"actor{side}{key}"] = codes_
            cols[f"actor{side}{key}__vocab"] = vocab_

    cols["numMentions"] = (rng.geometric(0.35, rows)).astype(np.int32)
    cols["numSources"] = np.minimum(cols["numMentions"],
                                    rng.geometric(0.6, rows)).astype(np.int32)
    cols["numArticles"] = cols["numMentions"] + rng.integers(
        0, 3, rows, dtype=np.int32)
    cols["avgTone"] = rng.normal(-2.0, 3.0, rows)

    first_id = int(config["first_event_id"])
    ids = (np.arange(first_id, first_id + rows, dtype=np.int64)).astype("U10")
    cols["__fid__"] = ids
    cols["globalEventId"] = ids
    cols["dtg"] = t
    cols["geom__x"] = x
    cols["geom__y"] = y
    ref["globalEventId"] = ids
    ref["fid"] = ids
    return Dataset(columns=cols, ref=ref, rows=rows)


def _encode_derived(values_by_rank: np.ndarray, rank_codes: np.ndarray):
    """A column derived from a rank-coded draw through a lookup: codes into
    the sorted vocab of the distinct derived values."""
    vocab, inv = np.unique(values_by_rank, return_inverse=True)
    return inv.astype(np.int32)[rank_codes], vocab


def frequencies(config, attribute: str):
    """(values, P(value)) of ``eventRootCode`` as ``make`` draws it: the
    traffic draws an equality predicate's value by this frequency."""
    if attribute != "eventRootCode":
        raise ValueError(f"no frequency table for {attribute!r}")
    voc = vocabularies(config)
    n = len(voc["event"])
    w = 1.0 / np.arange(1, n + 1) ** float(config["vocab"]["event_zipf"])
    w /= w.sum()
    roots = np.asarray(voc["root"])
    vocab = np.unique(roots)
    return vocab, np.array([w[roots == r].sum() for r in vocab])
