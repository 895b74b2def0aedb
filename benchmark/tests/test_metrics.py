"""Each per-layer reader on a synthetic traced window: a number where it
finds something to read, None where it finds nothing."""

from types import SimpleNamespace

import pytest

from benchmark.counts import density as density_counts
from benchmark.lib import spec
from benchmark.lib.tracing import DeviceTrace


def _ctx(**kw):
    req = SimpleNamespace(params={"width": 256, "height": 256})
    recs = [SimpleNamespace(req=req, scan_path="device-density"),
            SimpleNamespace(req=req, scan_path="host-seek")]
    base = dict(records=recs, spans=[], window_s=2.0,
                reference=SimpleNamespace(count=lambda r: 1_000_000),
                peaks={"hbm_bytes_per_s": 8.19e11},
                self_ms={"plan": 3.0, "plan.range_decomposition": 1.0, "query.plan": 2.0,
                         "device.fetch": 8.0},
                device=DeviceTrace(window_s=2.0, busy_s=1.5,
                                   module_seconds={"jit_step_no_time(7)": 0.25,
                                                   "jit_step(9)": 0.25, "jit_run(1)": 9.0}))
    base.update(kw)
    return SimpleNamespace(**base)


def test_readers():
    ctx = _ctx()
    assert spec.reader("planner.plan_ms")(ctx) == 3.0
    assert spec.reader("hostscan.scan_ms")(ctx) is None
    assert spec.reader("hostscan.scan_ms")(_ctx(self_ms={"scan": 2.0, "scan.block": 4.0})) == 3.0
    assert spec.reader("executor.device_share")(ctx) == 50.0
    assert spec.reader("device.idle_share")(ctx) == pytest.approx(25.0)
    least = density_counts.least_bytes(1_000_000, 256, 256)
    assert least == 8_000_000 + 262_144
    want = 100.0 * (least / 8.19e11) / 0.5
    assert spec.reader("density_roofline")(ctx) == pytest.approx(want)


def test_roofline_silent_without_its_program():
    ctx = _ctx(device=DeviceTrace(window_s=2.0, busy_s=1.5, module_seconds={"jit_run(1)": 1.0}))
    assert spec.reader("density_roofline")(ctx) is None
    assert spec.reader("density_roofline")(_ctx(reference=None)) is None
