"""Native (C++) host kernels, built lazily and bound via ctypes.

The reference keeps its planning hot loops in tight JVM code (sfcurve
bit-twiddling, SURVEY.md section 2.1); here they are C++ compiled on first
use with the baked-in g++ toolchain. Everything has a pure-Python fallback —
set GEOMESA_TPU_NO_NATIVE=1 to force it (and tests compare the two).

Kernels:
  zranges.cpp   z2/z3 quad/oct-tree range decomposition (+ skip boxes)
  xzranges.cpp  XZ sequence-interval BFS (extent indices)
  seekscan.cpp  one-pass candidate-interval filter (the tserver hot loop)
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()


def _host_tag() -> str:
    """Short fingerprint of this host's CPU (machine + ISA flags): cached
    .so files carry it in their name so a kernel built with -march=native
    on one host is never CDLL-loaded on a different CPU (SIGILL)."""
    import hashlib
    import platform

    sig = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    sig += line
                    break
    except OSError:
        pass
    return hashlib.md5(sig.encode()).hexdigest()[:10]


def _build_so(src: str, so: str) -> bool:
    # lazy JIT compile for THIS host (the host tag in `so` keys the cache):
    # -march=native lets the seek-scan loop vectorize; retry plain -O2 only
    # for compile errors — a missing g++ or a timeout fails the same way.
    # Concurrent processes may build the same library at once: each
    # compiles to a temp file of its own and publishes it with an atomic
    # rename, so no process ever reads or removes another's half-written
    # output.
    tmp = f"{so}.{os.getpid()}.{threading.get_ident()}.tmp"
    try:
        for flags in (["-O3", "-march=native"], ["-O2"]):
            try:
                subprocess.run(
                    ["g++", *flags, "-shared", "-fPIC", "-o", tmp, src],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
                os.replace(tmp, so)
                return True
            except subprocess.CalledProcessError:
                continue
            except Exception:
                return False
        return False
    finally:
        with contextlib.suppress(OSError):
            os.unlink(tmp)


def _current(src: str, so: str) -> bool:
    """True when ``so`` exists and is not older than ``src``."""
    try:
        return os.path.getmtime(so) >= os.path.getmtime(src)
    except OSError:
        return False


class _NativeLib:
    """One lazily-built, cached ctypes kernel: source path, symbol and
    signature in one place (the loader boilerplate used to be copied per
    kernel and drifted)."""

    def __init__(self, src: str, so: str, symbol: str, restype, argtypes):
        self.src = os.path.join(_DIR, src)
        base, ext = os.path.splitext(so)
        self.so = os.path.join(_DIR, f"{base}.{_host_tag()}{ext}")
        self.symbol = symbol
        self.restype = restype
        self.argtypes = argtypes
        self._lib = None
        self._tried = False

    def load(self):
        if os.environ.get("GEOMESA_TPU_NO_NATIVE"):
            return None
        with _lock:
            if self._tried:
                return self._lib
            self._tried = True
            try:
                # a failed build still loads a current library that a
                # concurrent process published in the meantime
                if not _current(self.src, self.so) and not (
                    _build_so(self.src, self.so)
                    or _current(self.src, self.so)
                ):
                    return None
                lib = ctypes.CDLL(self.so)
                fn = getattr(lib, self.symbol)
                fn.restype = self.restype
                fn.argtypes = self.argtypes
                self._lib = lib
            except Exception:
                self._lib = None
            return self._lib


_c_u32p = ctypes.POINTER(ctypes.c_uint32)
_c_u64p = ctypes.POINTER(ctypes.c_uint64)
_c_u8p = ctypes.POINTER(ctypes.c_uint8)
_c_i64p = ctypes.POINTER(ctypes.c_int64)
_c_f64p = ctypes.POINTER(ctypes.c_double)

_ZRANGES = _NativeLib(
    "zranges.cpp",
    "_zranges.so",
    "geomesa_zranges",
    ctypes.c_longlong,
    [
        _c_u32p, _c_u32p,  # mins, maxs
        ctypes.c_int, ctypes.c_int, ctypes.c_int,  # nboxes, bits, dims
        ctypes.c_longlong, ctypes.c_int,  # max_ranges, precision
        _c_u32p, _c_u32p, ctypes.c_int,  # skip_mins, skip_maxs, nskip
        _c_u64p, _c_u64p, _c_u8p, ctypes.c_longlong,  # out lo/hi/cont, cap
    ],
)

_XZRANGES = _NativeLib(
    "xzranges.cpp",
    "_xzranges.so",
    "geomesa_xzranges",
    ctypes.c_longlong,
    [
        _c_f64p, _c_f64p,  # qmins, qmaxs (normalized)
        ctypes.c_int, ctypes.c_int, ctypes.c_int,  # nqueries, dims, g
        ctypes.c_longlong,  # max_ranges
        _c_i64p, _c_i64p, _c_u8p, ctypes.c_longlong,  # out lo/hi/cont, cap
    ],
)

_SEEKSCAN = _NativeLib(
    "seekscan.cpp",
    "_seekscan.so",
    "geomesa_seek_scan",
    ctypes.c_longlong,
    [
        _c_f64p, _c_f64p, _c_i64p,  # x, y, t (t nullable)
        _c_i64p, _c_i64p, _c_u8p, ctypes.c_longlong,  # starts, ends, covered, nruns
        ctypes.c_double, ctypes.c_double, ctypes.c_double, ctypes.c_double,  # box
        ctypes.c_int64, ctypes.c_int64,  # tlo, thi
        _c_i64p, ctypes.c_longlong,  # out_rows, cap
    ],
)

_ENVSCAN = _NativeLib(
    "seekscan.cpp",
    "_seekscan.so",
    "geomesa_env_seek_scan",
    ctypes.c_longlong,
    [
        _c_f64p, _c_f64p, _c_f64p, _c_f64p,  # bxmin, bymin, bxmax, bymax
        _c_u8p,  # isrect flags (nullable)
        _c_i64p, _c_i64p, ctypes.c_longlong,  # starts, ends, nruns
        ctypes.c_double, ctypes.c_double, ctypes.c_double, ctypes.c_double,  # query box
        ctypes.c_int,  # rect_query
        _c_i64p, _c_u8p, ctypes.c_longlong,  # out_rows, out_decided, cap
    ],
)


_BITDECODE = _NativeLib(
    "bitdecode.cpp",
    "_bitdecode.so",
    "bitmap_rows",
    ctypes.c_longlong,
    [_c_u8p, ctypes.c_longlong, ctypes.c_longlong, _c_i64p, ctypes.c_longlong],
)


def load_bitdecode():
    """The bitmap-decode ctypes lib; None when unavailable/disabled."""
    return _BITDECODE.load()


def bitmap_rows_native(bits, base: int, max_out: int):
    """Packed bitmap (np.packbits big bit order) -> int64 row indices
    (bit index + ``base``); None when the lib is unavailable. ``max_out``
    bounds the output (callers know the set-bit count from the wire
    header)."""
    import numpy as np

    lib = load_bitdecode()
    if lib is None:
        return None
    bits = np.ascontiguousarray(bits, dtype=np.uint8)
    out = np.empty(max_out, dtype=np.int64)
    k = lib.bitmap_rows(
        bits.ctypes.data_as(_c_u8p),
        ctypes.c_longlong(len(bits)),
        ctypes.c_longlong(base),
        out.ctypes.data_as(_c_i64p),
        ctypes.c_longlong(max_out),
    )
    if k < 0:
        # popcount exceeded max_out: the wire header and bitmap disagree.
        # Raise rather than return None — None means "lib unavailable"
        # and callers would silently fall through to the numpy decode,
        # masking a wire-format bug instead of surfacing it.
        raise ValueError(
            f"corrupt bitmap wire data: popcount exceeds header count "
            f"{max_out}"
        )
    return out[:k]


def load():
    """The zranges ctypes lib; None when unavailable/disabled."""
    return _ZRANGES.load()


def load_xz():
    """The XZ-ranges ctypes lib; None when unavailable/disabled."""
    return _XZRANGES.load()


def load_seek():
    """The seek-scan ctypes lib; None when unavailable/disabled."""
    return _SEEKSCAN.load()


def load_env_seek():
    """The extent (envelope) seek-scan lib; None when unavailable."""
    return _ENVSCAN.load()


def env_seek_scan_native(
    bxmin, bymin, bxmax, bymax, starts, ends, qenv, rect_query: bool,
    isrect=None,
):
    """Extent candidate filter (see seekscan.cpp geomesa_env_seek_scan);
    returns (rows, decided_bool) or None when the lib is unavailable.
    ``qenv`` = (xmin, ymin, xmax, ymax) of the query geometry's envelope.
    ``isrect``: optional uint8/bool flags — rows whose geometry IS its
    envelope rectangle are decided by the envelope test alone."""
    lib = load_env_seek()
    if lib is None:
        return None
    a = np.ascontiguousarray(bxmin, dtype=np.float64)
    b = np.ascontiguousarray(bymin, dtype=np.float64)
    c = np.ascontiguousarray(bxmax, dtype=np.float64)
    d = np.ascontiguousarray(bymax, dtype=np.float64)
    st = np.ascontiguousarray(starts, dtype=np.int64)
    en = np.ascontiguousarray(ends, dtype=np.int64)
    if isrect is not None:
        ir = np.ascontiguousarray(isrect, dtype=np.uint8)
        ir_p = ir.ctypes.data_as(_c_u8p)
    else:
        ir_p = _c_u8p()
    cap = int(np.maximum(en - st, 0).sum())
    rows = np.empty(max(cap, 1), dtype=np.int64)
    dec = np.empty(max(cap, 1), dtype=np.uint8)
    n = lib.geomesa_env_seek_scan(
        a.ctypes.data_as(_c_f64p),
        b.ctypes.data_as(_c_f64p),
        c.ctypes.data_as(_c_f64p),
        d.ctypes.data_as(_c_f64p),
        ir_p,
        st.ctypes.data_as(_c_i64p),
        en.ctypes.data_as(_c_i64p),
        len(st),
        float(qenv[0]),
        float(qenv[1]),
        float(qenv[2]),
        float(qenv[3]),
        1 if rect_query else 0,
        rows.ctypes.data_as(_c_i64p),
        dec.ctypes.data_as(_c_u8p),
        cap,
    )
    if n < 0:
        return None  # cannot happen with an exact cap; fall back anyway
    return rows[:n], dec[:n].astype(bool)


def zranges_native(
    mins,
    maxs,
    bits: int,
    dims: int,
    max_ranges: Optional[int],
    precision: int,
    skip_mins=None,
    skip_maxs=None,
):
    """Native decomposition as (lower[], upper[], contained[]) uint64/uint64/
    bool arrays; None when the lib is unavailable. The array form skips
    per-range Python tuple construction on the planning hot path."""
    if dims < 1 or dims > 3:
        return None  # fall back rather than silently answering empty
    lib = load()
    if lib is None:
        return None
    m = np.ascontiguousarray(np.asarray(mins, dtype=np.uint32).reshape(-1))
    x = np.ascontiguousarray(np.asarray(maxs, dtype=np.uint32).reshape(-1))
    nboxes = len(m) // dims
    null_u32 = _c_u32p()
    if skip_mins is not None:
        sm = np.ascontiguousarray(np.asarray(skip_mins, dtype=np.uint32).reshape(-1))
        sx = np.ascontiguousarray(np.asarray(skip_maxs, dtype=np.uint32).reshape(-1))
        nskip = len(sm) // dims
        sm_p = sm.ctypes.data_as(_c_u32p)
        sx_p = sx.ctypes.data_as(_c_u32p)
    else:
        nskip = -1  # legacy contained semantics
        sm_p = sx_p = null_u32
    cap = max(4 * (max_ranges or 0), 1 << 16)
    # a NEGATIVE budget must not collide with the C++ 'unbounded' sentinel:
    # the Python walk treats it as an exhausted budget (clamp to 0)
    budget = -1 if max_ranges is None else max(0, int(max_ranges))
    while True:
        lo = np.empty(cap, dtype=np.uint64)
        hi = np.empty(cap, dtype=np.uint64)
        cont = np.empty(cap, dtype=np.uint8)
        n = lib.geomesa_zranges(
            m.ctypes.data_as(_c_u32p),
            x.ctypes.data_as(_c_u32p),
            nboxes,
            bits,
            dims,
            budget,
            precision,
            sm_p,
            sx_p,
            nskip,
            lo.ctypes.data_as(_c_u64p),
            hi.ctypes.data_as(_c_u64p),
            cont.ctypes.data_as(_c_u8p),
            cap,
        )
        if n >= 0:
            # copies: the views' base is the >=64K-entry scratch buffer, and
            # cached plans would otherwise retain ~1MB per query
            return lo[:n].copy(), hi[:n].copy(), cont[:n].astype(bool)
        cap = int(-n) + 16


def xzranges_native(qmins, qmaxs, dims: int, g: int, max_ranges: Optional[int]):
    """Native XZ BFS over normalized [0,1] windows; None when unavailable.
    Output matches _XZSFC.ranges_boxes: [(lower, upper, contained)]."""
    if dims < 2 or dims > 3 or g < 1 or g > 20:
        return None  # out of the kernel's domain: use the Python fallback
    lib = load_xz()
    if lib is None:
        return None
    m = np.ascontiguousarray(np.asarray(qmins, dtype=np.float64).reshape(-1))
    x = np.ascontiguousarray(np.asarray(qmaxs, dtype=np.float64).reshape(-1))
    nq = len(m) // dims
    budget = -1 if max_ranges is None else max(0, int(max_ranges))
    cap = max(4 * (max_ranges or 0) + (1 << dims) * (g + 1), 1 << 16)
    while True:
        lo = np.empty(cap, dtype=np.int64)
        hi = np.empty(cap, dtype=np.int64)
        cont = np.empty(cap, dtype=np.uint8)
        n = lib.geomesa_xzranges(
            m.ctypes.data_as(_c_f64p),
            x.ctypes.data_as(_c_f64p),
            nq,
            dims,
            g,
            budget,
            lo.ctypes.data_as(_c_i64p),
            hi.ctypes.data_as(_c_i64p),
            cont.ctypes.data_as(_c_u8p),
            cap,
        )
        if n >= 0:
            return [(int(lo[i]), int(hi[i]), bool(cont[i])) for i in range(n)]
        cap = int(-n) + 16


def seek_scan_native(
    x: np.ndarray,
    y: np.ndarray,
    t,
    starts: np.ndarray,
    ends: np.ndarray,
    covered: np.ndarray,
    box,
    tlo,
    thi,
):
    """One-pass candidate-interval filter (see seekscan.cpp); returns the
    final row-index array, or None when the lib is unavailable.

    ``box`` = (xmin, ymin, xmax, ymax) inclusive; ``tlo``/``thi`` inclusive
    epoch ms (ignored when ``t`` is None)."""
    lib = load_seek()
    if lib is None:
        return None
    xs = np.ascontiguousarray(x, dtype=np.float64)
    ys = np.ascontiguousarray(y, dtype=np.float64)
    st = np.ascontiguousarray(starts, dtype=np.int64)
    en = np.ascontiguousarray(ends, dtype=np.int64)
    cv = np.ascontiguousarray(covered, dtype=np.uint8)
    if t is not None:
        ts = np.ascontiguousarray(t, dtype=np.int64)
        t_p = ts.ctypes.data_as(_c_i64p)
        lo, hi = int(tlo), int(thi)
    else:
        t_p = _c_i64p()
        lo = hi = 0
    cap = int(np.maximum(en - st, 0).sum())
    out = np.empty(max(cap, 1), dtype=np.int64)
    n = lib.geomesa_seek_scan(
        xs.ctypes.data_as(_c_f64p),
        ys.ctypes.data_as(_c_f64p),
        t_p,
        st.ctypes.data_as(_c_i64p),
        en.ctypes.data_as(_c_i64p),
        cv.ctypes.data_as(_c_u8p),
        len(st),
        float(box[0]),
        float(box[2]),
        float(box[1]),
        float(box[3]),
        lo,
        hi,
        out.ctypes.data_as(_c_i64p),
        cap,
    )
    if n < 0:
        return None  # cannot happen with an exact cap; fall back anyway
    return out[:n]
