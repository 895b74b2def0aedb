"""The closed loop: each client thread sends its next request through
``TpuDataStore.query`` when its last one has returned, until the window
closes. A request's latency runs from the call (the CQL parse included)
to the return of a result the client has read whole."""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, List, Optional


@dataclass
class Record:
    client: int
    k: int
    req: Any
    t0: float
    t1: float
    scan_path: str = ""
    hits: int = 0
    error: Optional[str] = None
    output: Any = None  # kept only for the requests the check compares


def run_clients(serve: Callable[[Any], Any], read: Callable[[Any], Any], streams,
                t_close: float, keep: Callable[[Any], bool],
                finite: bool = False) -> List[List[Record]]:
    """Run one thread per stream until ``t_close`` (perf_counter seconds).

    ``serve(req)`` answers a request (``TpuDataStore.query`` in a run, the
    reference in the control's) and ``read(res)`` reads the answer whole.

    Returns each client's records in order. A client keeps the output of
    the requests ``keep`` picks, and of its own request with the most hits
    so far (the longest answer is always among those checked). A client
    that runs out of requests before ``t_close`` is an error unless
    ``finite`` (the warm-up sends each of its requests once)."""
    out: List[List[Record]] = [[] for _ in streams]
    longest: List[Optional[Record]] = [None] * len(streams)
    errors: List[BaseException] = []

    def client(c: int):
        try:
            recs = out[c]
            for k, req in enumerate(streams[c]):
                if time.perf_counter() >= t_close:
                    return
                rec = Record(c, k, req, time.perf_counter(), 0.0)
                try:
                    res = serve(req)
                    output, rec.hits = read(res)
                    rec.scan_path = str(getattr(res.plan, "scan_path", "") or "")
                except Exception as e:  # noqa: BLE001 - counted as failed
                    rec.error = f"{type(e).__name__}: {e}"
                    output = None
                rec.t1 = time.perf_counter()
                if output is not None and keep(req):
                    rec.output = output
                elif output is not None and (
                    longest[c] is None or rec.hits > longest[c].hits
                ):
                    if longest[c] is not None and not keep(longest[c].req):
                        longest[c].output = None
                    rec.output = output
                    longest[c] = rec
                recs.append(rec)
            if not finite:
                errors.append(RuntimeError(f"client {c} ran out of requests"))
        except BaseException as e:  # noqa: BLE001 - re-raised after join
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,), name=f"client-{c}")
               for c in range(len(streams))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return out
