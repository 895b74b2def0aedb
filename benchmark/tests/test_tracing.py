"""The trace reductions, on synthetic intervals and span trees and on a
small profiler trace recorded on one TPU v5e (``data/v5e_heatmap.xplane.pb``,
a 1 s ``gdelt.heatmap`` window, kept with the numbers it reduced to)."""

import json
import os

import pytest

from benchmark.lib import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.75), (8, 9)]
    assert tracing.union_seconds(iv) == 5
    assert tracing.gaps_of(iv, 0, 10) == [(3, 5), (6, 8), (9, 10)]
    assert tracing.gaps_of([], 0, 1) == [(0, 1)]
    assert tracing.union_seconds([]) == 0


class _Span:
    def __init__(self, name, start_ms, dur_ms, children=()):
        self.name = name
        self.start_ms = start_ms
        self.duration_ms = dur_ms
        self.children = list(children)

    @property
    def self_time_ms(self):
        return max(0.0, self.duration_ms - sum(c.duration_ms for c in self.children))

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


def test_self_time_and_gap_labels():
    plan = _Span("plan", 0.0, 4.0, [_Span("plan.range_decomposition", 1.0, 1.0)])
    root = _Span("query", 0.0, 10.0, [plan, _Span("scan", 5.0, 5.0)])
    got = tracing.self_ms_by_name([root])
    assert got == {"query": 1.0, "plan": 3.0, "plan.range_decomposition": 1.0, "scan": 5.0}
    # a 2 ms gap inside "scan" (5..10 ms) is labelled by the deepest span
    # that covers half of it; a gap outside every span by no-span
    gaps = [(6_000_000, 8_000_000), (20_000_000, 21_000_000)]
    assert tracing.label_gaps(gaps, [root]) == [["scan", 0.002], ["no-span", 0.001]]


def test_recorded_v5e_trace():
    path = os.path.join(DATA, "v5e_heatmap.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace")
    want = json.load(open(os.path.join(DATA, "v5e_heatmap.json")))
    dev = tracing.reduce_trace(path, want["anchor_wall_ns"])
    assert dev.devices == 1
    assert dev.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert dev.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < dev.busy_s <= dev.window_s
    top = max(dev.module_seconds.items(), key=lambda kv: kv[1])
    assert top[0] == want["top_module"]
    assert top[1] == pytest.approx(want["top_module_s"], rel=1e-9)
