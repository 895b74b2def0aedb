"""Glibc arena retention for allocation-churn-heavy paths (bulk ingest).

This environment backs anonymous memory lazily: faulting fresh pages runs
at ~70-140 MB/s (measured; a normal box does GB/s). Glibc's default
behavior — mmap for allocations >128 KB, munmap on free, trim the heap
back to the OS — makes every transient batch buffer re-fault its pages on
the NEXT batch, which collapsed converter ingest from ~600k to ~277k
rec/s as RSS grew (an env-level alloc slowdown seen in round 3).

Measured fix: keep freed memory in the process (M_TRIM_THRESHOLD=max,
M_MMAP_THRESHOLD=max) so batch N+1 reuses batch N's already-faulted
pages. Repeated 512 MB alloc+fault+free cycles: ~550 ms -> ~8 ms.

Deliberately opt-in per path (bulk ingest, benchmarks): a library must
not silently pin every caller's high-water RSS. GEOMESA_MALLOC_RETAIN=0
disables. The reference's JVM runtime makes the same trade by holding its
heap; this is the CPython/glibc equivalent
(tools/ingest/AbstractIngest.scala role: sustained batch throughput).
"""

import ctypes
import os

_done = None

# glibc mallopt parameter numbers (malloc.h)
_M_TRIM_THRESHOLD = -1
_M_MMAP_THRESHOLD = -3


def retain_freed_memory() -> bool:
    """Keep freed memory in-process via glibc heap-trim/mmap thresholds
    (NOT arena management — M_ARENA_MAX is untouched). Idempotent: the
    mallopt pair is applied at most once per process and cannot be undone,
    so GEOMESA_MALLOC_RETAIN=0 only has effect if set before the first
    call. Returns True when the thresholds were (or already are) set."""
    global _done
    if os.environ.get("GEOMESA_MALLOC_RETAIN", "1") == "0" and _done is None:
        return False
    if _done is not None:
        return _done
    try:
        libc = ctypes.CDLL("libc.so.6", use_errno=True)
        ok = bool(libc.mallopt(_M_TRIM_THRESHOLD, 2**31 - 1))
        ok = bool(libc.mallopt(_M_MMAP_THRESHOLD, 2**31 - 1)) and ok
        _done = ok
        if ok:
            # one line so operators can attribute pinned RSS to this knob
            # (irreversible for the process; GEOMESA_MALLOC_RETAIN=0
            # before the first call opts out)
            import sys

            print(
                "[geomesa] malloc retain enabled: freed memory stays "
                "in-process (GEOMESA_MALLOC_RETAIN=0 to disable)",
                file=sys.stderr,
            )
    except Exception:  # noqa: BLE001 - non-glibc platforms: no-op
        _done = False
    return _done

