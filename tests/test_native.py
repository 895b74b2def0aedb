"""Native zranges parity vs the Python oracle, across dims/budgets."""

import os

import numpy as np
import pytest

from geomesa_tpu.native import load, zranges_native

RNG = np.random.default_rng(17)

pytestmark = pytest.mark.skipif(load() is None, reason="no native toolchain")


def _python_ranges(mins, maxs, bits, dims, max_ranges, precision=64):
    """Run the pure-Python BFS by disabling the native hook."""
    from geomesa_tpu.curve import zorder

    os.environ["GEOMESA_TPU_NO_NATIVE"] = "1"
    try:
        return zorder.zranges(mins, maxs, bits, dims, max_ranges, precision)
    finally:
        del os.environ["GEOMESA_TPU_NO_NATIVE"]


@pytest.mark.parametrize("dims,bits", [(2, 31), (3, 21), (2, 10), (3, 8)])
@pytest.mark.parametrize("max_ranges", [None, 10, 200, 2000])
def test_native_matches_python(dims, bits, max_ranges):
    if max_ranges is None and bits > 10:
        pytest.skip("unbounded full-depth is slow in the Python oracle")
    top = (1 << bits) - 1
    boxes = []
    for _ in range(3):
        lo = RNG.integers(0, top, dims)
        hi = np.minimum(lo + RNG.integers(1, top // 4, dims), top)
        boxes.append((lo, hi))
    mins = [b[0] for b in boxes]
    maxs = [b[1] for b in boxes]
    want = _python_ranges(mins, maxs, bits, dims, max_ranges)
    got = _as_tuples(zranges_native(mins, maxs, bits, dims, max_ranges, 64))
    assert got == [(r.lower, r.upper, r.contained) for r in want]


def _as_tuples(arrays):
    """zranges_native returns (lower[], upper[], contained[]) arrays."""
    lo, hi, cont = arrays
    return list(zip(lo.tolist(), hi.tolist(), cont.tolist()))


def test_native_single_cell():
    got = _as_tuples(zranges_native([[5, 5]], [[5, 5]], 8, 2, None, 64))
    want = _python_ranges([[5, 5]], [[5, 5]], 8, 2, None)
    assert got == [(r.lower, r.upper, r.contained) for r in want]
    assert len(got) == 1 and got[0][2] is True


def test_native_wired_into_sfc():
    """Z2SFC.ranges must give identical results native vs python."""
    from geomesa_tpu.curve.sfc import Z2SFC

    sfc = Z2SFC()
    boxes = [(-10.0, -10.0, 10.0, 10.0), (100.0, 40.0, 120.0, 60.0)]
    a = sfc.ranges(boxes, max_ranges=500)
    os.environ["GEOMESA_TPU_NO_NATIVE"] = "1"
    try:
        b = sfc.ranges(boxes, max_ranges=500)
    finally:
        del os.environ["GEOMESA_TPU_NO_NATIVE"]
    assert [(r.lower, r.upper, r.contained) for r in a] == [
        (r.lower, r.upper, r.contained) for r in b
    ]


def test_xzranges_native_matches_python():
    """The C++ XZ BFS must reproduce the Python walk exactly: same ranges,
    same flags, same budget behavior, across dims/g/windows."""
    import os

    import numpy as np

    from geomesa_tpu.curve.xz import XZ2SFC, XZ3SFC

    rng = np.random.default_rng(5)
    cases = []
    for _ in range(12):
        x0 = rng.uniform(-170, 150); y0 = rng.uniform(-80, 60)
        w = rng.uniform(0.01, 40); h = rng.uniform(0.01, 30)
        cases.append((x0, y0, x0 + w, y0 + h))
    for budget in (None, 50, 500):
        # the unbounded python walk is the slow side (cost ~ box area at
        # g=12): pin the no-budget semantics on the smallest boxes;
        # budgeted walks stay cheap so every box runs them
        small = sorted(cases, key=lambda c: (c[2] - c[0]) * (c[3] - c[1]))[:4]
        for x0, y0, x1, y1 in (small if budget is None else cases):
            sfc = XZ2SFC.for_g(12)
            native = sfc.ranges([(x0, y0, x1, y1)], max_ranges=budget)
            os.environ["GEOMESA_TPU_NO_NATIVE"] = "1"
            try:
                pure = sfc.ranges([(x0, y0, x1, y1)], max_ranges=budget)
            finally:
                del os.environ["GEOMESA_TPU_NO_NATIVE"]
            assert native == pure, (budget, x0, y0, x1, y1)
    # xz3 (octs + time dim)
    sfc3 = XZ3SFC.for_period(12, "week")
    q = [(-20.0, -10.0, 100000.0, 30.0, 25.0, 400000.0)]
    native = sfc3.ranges(q, max_ranges=200)
    os.environ["GEOMESA_TPU_NO_NATIVE"] = "1"
    try:
        pure = sfc3.ranges(q, max_ranges=200)
    finally:
        del os.environ["GEOMESA_TPU_NO_NATIVE"]
    assert native == pure


def test_xzranges_out_of_domain_falls_back_to_python():
    """g > 20 is outside the native kernel's domain: the wrapper must
    decline (None) so the Python walk answers — not return an empty plan."""
    from geomesa_tpu.curve.xz import XZ2SFC
    from geomesa_tpu.native import xzranges_native

    assert xzranges_native([[0.1, 0.1]], [[0.2, 0.2]], 2, 21, 50) is None
    sfc = XZ2SFC.for_g(21)
    assert len(sfc.ranges([(-10.0, -10.0, 10.0, 10.0)], max_ranges=50)) > 0


def test_ranges_nonpositive_budget_parity():
    """A zero/negative budget means 'exhausted' on the Python paths; the
    native wrappers must not map it to the C++ unbounded sentinel."""
    import os

    from geomesa_tpu.curve.xz import XZ2SFC
    from geomesa_tpu.curve.zorder import zranges

    for budget in (0, -1):
        native = zranges([(3, 2)], [(200, 180)], bits=8, dims=2, max_ranges=budget)
        os.environ["GEOMESA_TPU_NO_NATIVE"] = "1"
        try:
            pure = zranges([(3, 2)], [(200, 180)], bits=8, dims=2, max_ranges=budget)
        finally:
            del os.environ["GEOMESA_TPU_NO_NATIVE"]
        assert native == pure, budget
        sfc = XZ2SFC.for_g(12)
        nx = sfc.ranges([(0.0, 0.0, 20.0, 15.0)], max_ranges=budget)
        os.environ["GEOMESA_TPU_NO_NATIVE"] = "1"
        try:
            px = sfc.ranges([(0.0, 0.0, 20.0, 15.0)], max_ranges=budget)
        finally:
            del os.environ["GEOMESA_TPU_NO_NATIVE"]
        assert nx == px, budget


def test_bitmap_rows_native_matches_numpy():
    import numpy as np

    from geomesa_tpu.native import bitmap_rows_native

    rng = np.random.default_rng(3)
    for n_bytes, p in ((1, 0.5), (7, 0.9), (8, 0.0), (1024, 0.02), (100_003, 0.3)):
        bits = (rng.random(n_bytes * 8) < p).astype(np.uint8)
        packed = np.packbits(bits)
        want = 1000 + np.flatnonzero(bits)
        got = bitmap_rows_native(packed, 1000, int(bits.sum()))
        if got is None:
            import pytest

            pytest.skip("bitdecode lib unavailable")
        np.testing.assert_array_equal(got, want)
    # capacity mismatch must be detected LOUDLY (a silent None would let
    # callers fall through to the numpy decode and mask the corruption),
    # and never written past the buffer
    bits = np.ones(64, np.uint8)
    import pytest

    with pytest.raises(ValueError, match="corrupt bitmap"):
        bitmap_rows_native(np.packbits(bits), 0, 63)


_BUILD_AND_LOAD = """
import sys
from geomesa_tpu import native
ref = getattr(native, sys.argv[1])
lib = native._NativeLib(sys.argv[2], sys.argv[3], ref.symbol, ref.restype,
                        ref.argtypes)
sys.exit(0 if lib.load() is not None else 1)
"""


@pytest.mark.parametrize(
    "lib", ["_ZRANGES", "_XZRANGES", "_SEEKSCAN", "_BITDECODE"]
)
def test_native_build_is_race_free(lib, tmp_path):
    """Processes that build one library at once all load it: each compiles
    to a temp file of its own and publishes it atomically, and a process
    whose build loses still loads the current library a peer published."""
    import shutil
    import subprocess
    import sys

    import geomesa_tpu.native as native

    ref = getattr(native, lib)
    src = str(tmp_path / os.path.basename(ref.src))
    shutil.copy(ref.src, src)
    so = str(tmp_path / "_race.so")
    env = {k: v for k, v in os.environ.items() if k != "GEOMESA_TPU_NO_NATIVE"}
    root = os.path.dirname(os.path.dirname(os.path.dirname(native.__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (root, env.get("PYTHONPATH")) if p
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _BUILD_AND_LOAD, lib, src, so],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for _ in range(6)
    ]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    assert [p.returncode for p in procs] == [0] * 6, outs
    assert not list(tmp_path.glob("*.tmp"))
    assert len(list(tmp_path.glob("_race.*.so"))) == 1
