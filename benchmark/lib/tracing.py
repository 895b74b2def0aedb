"""Reductions of a traced run: the device's busy time, op and program
time and idle gaps from the JAX profiler's trace, and span self time from
the store's span trees (``geomesa_tpu.utils.trace``), the arithmetic of
``scripts/profile_query.py`` copied here.

The trace and the spans share one clock through an anchor: the window runs
inside ``jax.profiler.TraceAnnotation(ANCHOR)``, whose start in the trace
is the wall time taken as it opened.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANCHOR = "bench.window"


@dataclass
class DeviceTrace:
    window_s: float
    busy_s: float  # union of op intervals, averaged over the devices
    op_seconds: Dict[str, float] = field(default_factory=dict)
    module_seconds: Dict[str, float] = field(default_factory=dict)
    # idle gaps on the first device: (start, end) in wall-clock ns
    gaps: List[Tuple[int, int]] = field(default_factory=list)
    devices: int = 0


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals, in their unit."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals: Sequence[Tuple[float, float]], lo: float, hi: float):
    """The idle stretches of [lo, hi) that no interval covers."""
    out = []
    cur = lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _is_device_plane(plane) -> bool:
    """An accelerator plane that ran XLA ops (not the CPU, and not the
    runtime's line-less planes such as ``/device:CUSTOM:Megascale Trace``)."""
    return (plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU")
            and any(line.name == "XLA Ops" for line in plane.lines))


def find_xspace(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def reduce_trace(path: str, anchor_wall_ns: int) -> Optional[DeviceTrace]:
    """Reduce a profiler trace to the window marked by the ANCHOR
    annotation. None when the trace holds no device plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    anchor = None
    planes = []
    for plane in pd.planes:
        if _is_device_plane(plane):
            planes.append(plane)
            continue
        if anchor is None and plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = (ev.start_ns, ev.start_ns + ev.duration_ns)
                        break
    if anchor is None:
        raise ValueError(f"trace {path} holds no {ANCHOR!r} annotation")
    if not planes:
        return None
    lo, hi = anchor
    shift = anchor_wall_ns - lo  # trace ns -> wall ns
    ops_s: Dict[str, float] = defaultdict(float)
    mods_s: Dict[str, float] = defaultdict(float)
    busy = []
    first_gaps: List[Tuple[int, int]] = []
    for i, plane in enumerate(sorted(planes, key=lambda p: p.name)):
        intervals = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e <= s:
                    continue
                if line.name == "XLA Ops":
                    intervals.append((s, e))
                    ops_s[ev.name] += (e - s) / 1e9
                else:
                    mods_s[ev.name] += (e - s) / 1e9
        busy.append(union_seconds(intervals) / 1e9)
        if i == 0:
            first_gaps = [(int(s + shift), int(e + shift))
                          for s, e in gaps_of(intervals, lo, hi)]
    return DeviceTrace(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy),
        op_seconds=dict(ops_s),
        module_seconds=dict(mods_s),
        gaps=first_gaps,
        devices=len(planes),
    )


def self_ms_by_name(roots) -> Dict[str, float]:
    """Summed self time (duration less direct children's) per span name."""
    out: Dict[str, float] = defaultdict(float)
    for root in roots:
        for sp in root.walk():
            out[sp.name] += sp.self_time_ms
    return dict(out)


def _flat_spans(roots):
    def walk(sp, depth):
        start = sp.start_ms * 1e6
        yield start, start + sp.duration_ms * 1e6, sp.name, depth
        for c in sp.children:
            yield from walk(c, depth + 1)

    for r in roots:
        yield from walk(r, 0)


def label_gaps(gaps: List[Tuple[int, int]], roots, top: int = 10):
    """The ``top`` longest idle gaps, each named by what the host was doing:
    the deepest span that covers at least half of it (else the span that
    covers most of it, else ``no-span``)."""
    spans = list(_flat_spans(roots))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        length = e - s
        best = None
        for ss, se, name, depth in spans:
            ov = min(e, se) - max(s, ss)
            if ov <= 0:
                continue
            key = (ov >= 0.5 * length, depth if ov >= 0.5 * length else 0, ov)
            if best is None or key > best[0]:
                best = (key, name)
        out.append([best[1] if best else "no-span", length / 1e9])
    return out
