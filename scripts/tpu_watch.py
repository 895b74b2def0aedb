"""Watch a running geomesa-tpu server's telemetry, live or replayed.

Live server watch: pass a server URL and the watcher tails a RUNNING
geomesa-tpu server's telemetry timeline::

    python scripts/tpu_watch.py http://127.0.0.1:8765

One ``GET /debug/timeline?s=<refresh>`` request per refresh
(TPU_WATCH_REFRESH seconds, default 2): the server-side flight recorder
(utils/timeline.py) already holds per-second deltas, so the watcher
renders them directly — no client-side /metrics scraping-and-diffing,
no state between refreshes, and the numbers match what /debug/report
would capture. Ctrl-C exits.

Spool replay: render a PAST window from the durable telemetry spool
(utils/history.py) with the same per-worker rollup rendering, no server
required::

    python scripts/tpu_watch.py --history /data/geomesa --at 1754500000
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _fmt_rate(block: dict) -> str:
    return f"{block['hits']}/{block['hits'] + block['misses']}"


def _fold_snaps(snaps: list) -> dict:
    """Fold a window of flight-recorder snapshots (per-second deltas)
    into one delta block — counters summed, cache hit/miss summed,
    coalesce groups/members summed, LAST breaker/admission states kept.
    Shared by the live server watch and the spool replay so both render
    identically."""
    counters: dict = {}
    caches: dict = {}
    coalesce = {"groups": 0, "members": 0}
    breakers: dict = {}
    admission: dict = {}
    brownout: dict = {}
    tenants: dict = {}
    for s in snaps:
        for k, v in s.get("counters", {}).items():
            counters[k] = counters.get(k, 0) + v
        for row in s.get("tenants", []) or []:
            acc = tenants.setdefault(
                row.get("tenant", "anon"), {"calls": 0, "ms": 0.0, "bad": 0}
            )
            acc["calls"] += row.get("calls", 0)
            acc["ms"] = round(acc["ms"] + row.get("ms", 0.0), 3)
            acc["bad"] += row.get("bad", 0)
        for label, block in s.get("caches", {}).items():
            if label == "coalesce":
                # groups/members, not a hit/miss cache — rendering
                # it as a rate would read healthy coalescing as 0%
                coalesce["groups"] += block.get("groups", 0)
                coalesce["members"] += block.get("members", 0)
                continue
            acc = caches.setdefault(label, {"hits": 0, "misses": 0})
            acc["hits"] += block.get("hits", 0)
            acc["misses"] += block.get("misses", 0)
        breakers = s.get("breakers", breakers)
        admission = s.get("admission", admission)
        brownout = s.get("brownout", brownout)
    return {"counters": counters, "caches": caches, "coalesce": coalesce,
            "breakers": breakers, "admission": admission,
            "brownout": brownout, "tenants": tenants}


def _render_fold(fold: dict, stamp: str) -> None:
    counters = fold["counters"]
    admission = fold["admission"]
    open_breakers = sorted(
        n for n, st in fold["breakers"].items() if st != "closed"
    )
    parts = [
        f"q={counters.get('queries', 0)}",
        f"to={counters.get('queries.timeout', 0) + counters.get('deadline.exceeded', 0)}",
        f"shed={counters.get('shed.overflow', 0)}",
        f"h2d={counters.get('device.h2d.bytes', 0):,}B",
        f"d2h={counters.get('device.d2h.bytes', 0):,}B",
        f"compiles={counters.get('xla.compile.total', 0)}",
    ]
    if admission:
        parts.append(
            f"adm={admission.get('inflight', 0)}+{admission.get('queued', 0)}q"
        )
    for label, block in sorted(fold["caches"].items()):
        if block["hits"] + block["misses"]:
            parts.append(f"{label}={_fmt_rate(block)}")
    if fold["coalesce"]["groups"]:
        parts.append(
            f"coalesce={fold['coalesce']['members']}q/{fold['coalesce']['groups']}grp"
        )
    if open_breakers:
        parts.append(f"breakers={','.join(open_breakers)}")
    # the overload-defense pane: active brownout level (LAST state in
    # the window, the breaker convention) + the window's per-class shed
    # deltas, so "who is being refused" reads off the same line
    bo = fold.get("brownout") or {}
    if bo.get("level"):
        parts.append(f"bo=L{bo['level']}")
    pri_sheds = [
        f"{k[len('shed.priority.'):]}:{v}"
        for k, v in sorted(counters.items())
        if k.startswith("shed.priority.") and v
    ]
    if pri_sheds:
        parts.append(f"shed.pri={','.join(pri_sheds)}")
    print(f"[{stamp}] " + " ".join(parts), flush=True)
    # the tenants pane: who spent the window's device time (utils/
    # tenants.py deltas embedded in the same flight-recorder snapshots,
    # so live watch and --history replay render identically)
    if fold.get("tenants"):
        top = sorted(
            fold["tenants"].items(), key=lambda kv: -kv[1]["ms"]
        )[:5]
        print(
            "  tenants: " + " ".join(
                f"{label}={acc['calls']}q/{acc['ms']:.0f}ms"
                + (f"/{acc['bad']}bad" if acc["bad"] else "")
                for label, acc in top
            ),
            flush=True,
        )


# worker ids are numeric strings: sort as ints so w10 does not
# interleave between w1 and w2
def _by_wid(k) -> int:
    return int(k) if str(k).isdigit() else 0


def _render_fleet(fleet: dict) -> None:
    """Fleet coordinators: the merged per-worker timeline rollup
    (parallel/fleet.py `timeline` RPC) — one sub-line per worker plus
    the fleet fold, so a silently degrading worker (breaker open, host
    scans) is visible from the same watch. Replay mode feeds the SAME
    block, read back off the coordinator's durable spool."""
    roll = fleet.get("rollup", {})
    rparts = [
        f"workers={roll.get('workers', 0)}",
        f"q={roll.get('counters', {}).get('queries', 0)}",
    ]
    scan = roll.get("timers", {}).get("query.scan", {})
    if scan.get("count"):
        rparts.append(
            f"scan={scan['count']}x/{scan.get('sum_ms', 0):.0f}ms"
        )
    if roll.get("unreachable"):
        rparts.append(f"unreachable={','.join(roll['unreachable'])}")
    for wid, names in sorted(
        roll.get("breakers", {}).items(), key=lambda kv: _by_wid(kv[0])
    ):
        rparts.append(f"w{wid}.breakers={','.join(names)}")
    print("  fleet: " + " ".join(rparts), flush=True)
    for wid in sorted(fleet.get("workers", {}), key=_by_wid):
        row = fleet["workers"][wid]
        if row.get("unreachable"):
            print(f"    w{wid}: UNREACHABLE {row.get('error', '')}",
                  flush=True)
            continue
        tick = row.get("tick") or {}
        wc = tick.get("counters") or {}
        adm = row.get("admission") or {}
        wl = [
            f"q={wc.get('queries', 0)}",
            f"adm={adm.get('inflight', 0)}+{adm.get('queued', 0)}q",
            f"parts={row.get('partitions', 0)}",
        ]
        wopen = sorted(
            n for n, st_ in (tick.get("breakers") or {}).items()
            if st_ != "closed"
        )
        if wopen:
            wl.append(f"breakers={','.join(wopen)}")
        print(f"    w{wid}: " + " ".join(wl), flush=True)


def watch_server(url: str) -> None:
    """The live-watch loop: one /debug/timeline request per refresh,
    rendering the window's aggregate deltas as a top-style line. The
    server's ring supplies history and deltas — the client keeps NO
    state and never diffs /metrics itself."""
    import urllib.request

    refresh = float(os.environ.get("TPU_WATCH_REFRESH", 2))
    endpoint = f"{url.rstrip('/')}/debug/timeline?s={refresh:g}"
    print(f"watching {endpoint} every {refresh:g}s (Ctrl-C to exit)", flush=True)
    while True:
        try:
            with urllib.request.urlopen(endpoint, timeout=10) as resp:
                body = json.loads(resp.read())
        except (OSError, ValueError) as e:
            print(f"[{time.strftime('%H:%M:%S')}] fetch failed: {e}", flush=True)
            time.sleep(refresh)
            continue
        if not body.get("enabled", False):
            print("server timeline disabled (geomesa.timeline.enabled=0)")
            return
        snaps = body.get("snapshots", [])
        _render_fold(_fold_snaps(snaps), time.strftime("%H:%M:%S"))
        fleet = snaps[-1].get("fleet") if snaps else None
        if fleet:
            _render_fleet(fleet)
        time.sleep(refresh)


def watch_history(root: str, at: float = None, window: float = 300.0) -> None:
    """Replay mode: render a PAST window off the durable telemetry spool
    (utils/history.py) with the exact rendering the live server watch
    uses — one line per recorded tick (the tick IS the per-second delta
    the live watch would have shown), fleet sub-lines included because
    the coordinator's tick snapshots embed the fleet rollup. ``--at``
    centers the window on a unix timestamp (e.g. a kill instant);
    default is the trailing 5 minutes. No server needed — this works on
    a corpse."""
    from geomesa_tpu.utils import history

    if at is not None:
        lo, hi = float(at) - window / 2, float(at) + window / 2
    else:
        hi = time.time()
        lo = hi - window
    records, truncated = history.read_records(root, s=lo, until=hi)
    ticks = [r for r in records if r.get("kind") == "tick"]
    print(
        f"replaying {root} [{time.strftime('%H:%M:%S', time.localtime(lo))}"
        f" .. {time.strftime('%H:%M:%S', time.localtime(hi))}]"
        f" — {len(ticks)} ticks" + (" (truncated)" if truncated else ""),
        flush=True,
    )
    for rec in ticks:
        snap = rec.get("tick") or {}
        stamp = time.strftime("%H:%M:%S", time.localtime(rec.get("t", 0)))
        _render_fold(_fold_snaps([snap]), stamp)
        fleet = snap.get("fleet")
        if fleet:
            _render_fleet(fleet)
    for rec in records:
        if rec.get("kind") == "sentry":
            stamp = time.strftime("%H:%M:%S", time.localtime(rec.get("t", 0)))
            print(
                f"[{stamp}] sentry {rec.get('state')}: {rec.get('fingerprint')}",
                flush=True,
            )
        elif rec.get("kind") == "brownout":
            # ladder transitions (utils/brownout.py): one line per rung
            # move, with the signals that drove it — a postmortem reads
            # WHEN the defense engaged and why off the same replay
            stamp = time.strftime("%H:%M:%S", time.localtime(rec.get("t", 0)))
            print(
                f"[{stamp}] brownout L{rec.get('from')}->L{rec.get('level')}"
                f" (target L{rec.get('target')},"
                f" queue={rec.get('queue_ratio')},"
                f" slo={','.join(rec.get('slo_violating') or []) or '-'},"
                f" breakers={len(rec.get('open_breakers') or [])})",
                flush=True,
            )


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--history":
        # replay a past window from the durable spool:
        #   tpu_watch.py --history <root> [--at <unix_ts>] [--window <s>]
        if len(sys.argv) < 3:
            print("usage: tpu_watch.py --history <root> [--at <ts>] "
                  "[--window <s>]", file=sys.stderr)
            sys.exit(2)
        hroot = sys.argv[2]
        hat = None
        hwin = 300.0
        rest = sys.argv[3:]
        while rest:
            flag = rest.pop(0)
            if flag == "--at" and rest:
                hat = float(rest.pop(0))
            elif flag == "--window" and rest:
                hwin = float(rest.pop(0))
            else:
                print(f"unknown arg {flag}", file=sys.stderr)
                sys.exit(2)
        watch_history(hroot, at=hat, window=hwin)
    elif len(sys.argv) > 1 and sys.argv[1].startswith(("http://", "https://")):
        try:
            watch_server(sys.argv[1])
        except KeyboardInterrupt:
            pass
    else:
        print(__doc__, file=sys.stderr)
        sys.exit(2)
