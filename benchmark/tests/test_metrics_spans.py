"""The readers of the coalescer, density push-down and host reducer spans
on a synthetic traced window: a number where the spans are there, None
in a window without them (a program that has no such span)."""

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


# a member that queued, a leader that gathered and ran the device grid with
# a certified band, and a request whose band overflowed to the host reducer
WINDOW = [
    _span("query", 100.0, [_span("batch.wait", 60.0)]),
    _span("query", 120.0, [
        _span("batch.gather", 4.0),
        _span("density", 50.0, [_span("density.certify", 8.0, rows=300)],
              outcome="grid", band_rows=300),
    ]),
    _span("query", 900.0, [
        _span("density", 40.0, outcome="band_overflow"),
        _span("query.assemble", 700.0, [_span("agg.reduce", 200.0, rows=10, kind="density")]),
    ]),
    _span("query", 80.0, [_span("density", 30.0, outcome="f32_overflow")]),
]


@pytest.mark.parametrize("name, want", [
    ("batch.wait_ms", (60.0 + 4.0) / 4),
    ("density.overflow_share", 100.0 / 3),
    ("density.certify_ms", 8.0 / 4),
    ("hostagg.reduce_ms", 200.0 / 4),
])
def test_reader_reads_the_window(name, want):
    assert spec.reader(name)(_ctx(WINDOW)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "batch.wait_ms", "density.overflow_share", "density.certify_ms", "hostagg.reduce_ms",
])
def test_reader_silent_without_its_spans(name):
    # the parent program's window: the same requests, none of the new spans
    bare = [_span("query", 100.0, [_span("query.assemble", 50.0)])]
    assert spec.reader(name)(_ctx(bare)) is None
    assert spec.reader(name)(_ctx([], n_records=0)) is None


def test_overflow_share_counts_every_density_span():
    grid_only = [_span("query", 10.0, [_span("density", 5.0, outcome="grid")])] * 3
    assert spec.reader("density.overflow_share")(_ctx(grid_only)) == 0.0
