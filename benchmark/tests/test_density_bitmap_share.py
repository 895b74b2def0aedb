"""The ``density.bitmap_share`` reader on a synthetic traced window: the
share of ``density`` spans whose band came back as a bitmap, and None in a
window whose ``density`` spans carry no ``band`` (a program without the
band bitmap)."""

from types import SimpleNamespace

import pytest

from benchmark.lib import spec
from benchmark.lib.tracing import self_ms_by_name
from geomesa_tpu.utils.trace import Span


def _span(name, ms, children=(), **attrs):
    return {"name": name, "duration_ms": ms, "attributes": attrs,
            "children": list(children)}


def _ctx(trees, n_records=4):
    roots = [Span.from_dict(t) for t in trees]
    return SimpleNamespace(records=[object()] * n_records, spans=roots,
                           self_ms=self_ms_by_name(roots))


def test_bitmap_share_counts_the_band_layouts():
    window = [
        _span("query", 10.0, [_span("density", 5.0, outcome="grid", band="indices")]),
        _span("query", 10.0, [_span("density", 5.0, outcome="grid", band="bitmap")]),
        _span("query", 10.0, [_span("density", 5.0, outcome="grid", band="bitmap")]),
        _span("query", 10.0, [_span("density", 5.0, outcome="f32_overflow")]),
    ]
    assert spec.reader("density.bitmap_share")(_ctx(window)) == pytest.approx(50.0)


PARENT_WINDOW = [
    _span("query", 120.0, [_span("density", 50.0, outcome="grid", band_rows=300)]),
    _span("query", 900.0, [
        _span("density", 40.0, outcome="band_overflow"),
        _span("query.assemble", 700.0, [_span("agg.reduce", 200.0, rows=10, kind="density")]),
    ]),
]


@pytest.mark.parametrize("trees", [PARENT_WINDOW, [], [_span("query", 1.0)]])
def test_bitmap_share_silent_without_band_layouts(trees):
    # the parent program: density spans (or none) that carry no ``band``
    assert spec.reader("density.bitmap_share")(_ctx(trees)) is None
