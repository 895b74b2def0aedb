"""TCP streaming transport (stream/netlog.py): the LogServer daemon +
RemoteLogBroker client make the durable file log network-transparent —
the Kafka-broker role (kafka/data/KafkaDataStore.scala:44-90) without a
shared filesystem between producers and consumers.
"""

import os
import subprocess
import sys
import textwrap
import threading

import pytest

from geomesa_tpu.geom.base import Point
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.stream.netlog import (
    LogServer,
    RemoteLogBroker,
    RemoteOffsetManager,
)
from geomesa_tpu.stream.store import StreamDataStore

SPEC = "name:String,dtg:Date,*geom:Point:srid=4326"


def test_send_poll_end_offsets_over_tcp(tmp_path):
    with LogServer(str(tmp_path / "log"), partitions=3) as (host, port):
        b = RemoteLogBroker(host, port)
        assert b.partitions == 3  # fetched from the server
        for i in range(50):
            b.send("t", i % 3, f"msg{i}".encode())
        got = b.poll("t", {})
        assert len(got) == 50
        assert {p for p, _o, _b in got} == {0, 1, 2}
        assert got[0][2].startswith(b"msg")
        assert b.end_offsets("t") == {0: 17, 1: 17, 2: 16}
        # offset-bounded poll
        assert len(b.poll("t", {0: 17, 1: 17, 2: 16})) == 0
        assert len(b.poll("t", {0: 10})) == 7 + 17 + 16
        # partition-restricted poll (consumer-group assignment contract)
        assert {p for p, _o, _b in b.poll("t", {}, partitions=[1])} == {1}


def test_remote_offset_manager_commits_server_side(tmp_path):
    root = str(tmp_path / "log")
    with LogServer(root) as (host, port):
        b = RemoteLogBroker(host, port)
        om = RemoteOffsetManager(b, "g1")
        assert om.offsets("t") == {}
        om.commit("t", {0: 5, 2: 9})
        assert om.offsets("t") == {0: 5, 2: 9}
        # a different client (a consumer restarted elsewhere) sees them
        om2 = RemoteOffsetManager(RemoteLogBroker(host, port), "g1")
        assert om2.offsets("t") == {0: 5, 2: 9}
        # groups are isolated
        assert RemoteOffsetManager(b, "g2").offsets("t") == {}
    # offsets were persisted on the SERVER's disk
    assert os.path.exists(os.path.join(root, "offsets", "g1__t.json"))


def test_stream_store_runs_on_remote_broker(tmp_path):
    """The stream tier runs unchanged on the TCP transport: producer in
    ANOTHER OS process reaching the broker only by host:port."""
    with LogServer(str(tmp_path / "log")) as (host, port):
        code = textwrap.dedent(f"""
            import sys
            sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
            from geomesa_tpu.stream.netlog import RemoteLogBroker
            from geomesa_tpu.stream.store import StreamDataStore
            from geomesa_tpu.schema.featuretype import parse_spec
            from geomesa_tpu.geom.base import Point
            s = StreamDataStore(broker=RemoteLogBroker({host!r}, {port}))
            s.create_schema(parse_spec("t", {SPEC!r}))
            for i in range(150):
                s.write("t", [f"n{{i}}", 1760000000000 + i, Point(0.0, 0.0)],
                        fid=f"f{{i}}", ts_ms=1760000000000 + i)
            s.delete("t", "f3")
            print("DONE")
        """)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=120, env=env)
        assert "DONE" in p.stdout, p.stderr[-2000:]
        consumer = StreamDataStore(broker=RemoteLogBroker(host, port))
        consumer.create_schema(parse_spec("t", SPEC))
        res = consumer.query("t", "INCLUDE")
        assert len(res) == 149
        assert "f3" not in set(map(str, res.fids))


def test_client_reconnects_after_server_restart(tmp_path):
    root = str(tmp_path / "log")
    server = LogServer(root, partitions=2)
    host, port = server.start()
    b = RemoteLogBroker(host, port)
    b.send("t", 0, b"before")
    server.close()
    # same root, same port: the durable log carries over
    server2 = LogServer(root, host=host, port=port, partitions=2)
    server2.start()
    try:
        b.send("t", 0, b"after")  # transparent reconnect
        got = [payload for _p, _o, payload in b.poll("t", {})]
        assert got == [b"before", b"after"]
    finally:
        server2.close()


def test_concurrent_producers_interleave_safely(tmp_path):
    with LogServer(str(tmp_path / "log"), partitions=2) as (host, port):
        def produce(tag):
            b = RemoteLogBroker(host, port)
            for i in range(100):
                b.send("t", i % 2, f"{tag}:{i}".encode())

        threads = [threading.Thread(target=produce, args=(t,)) for t in "abc"]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        b = RemoteLogBroker(host, port)
        recs = b.poll("t", {})
        assert len(recs) == 300
        # per-producer order is preserved within each partition
        for tag in "abc":
            for p in (0, 1):
                seq = [int(payload.split(b":")[1]) for part, _o, payload in recs
                       if part == p and payload.startswith(f"{tag}:".encode())]
                assert seq == sorted(seq)


def test_large_backlog_polls_in_bounded_chunks(tmp_path, monkeypatch):
    """A backlog whose payloads exceed the frame budget must stream out
    over several polls, never building a frame the client rejects."""
    from geomesa_tpu.stream import netlog

    monkeypatch.setattr(netlog, "_MAX_MSG", 64 * 1024)  # 32 KiB budget
    with LogServer(str(tmp_path / "log"), partitions=1) as (host, port):
        b = RemoteLogBroker(host, port)
        payload = b"x" * 4096
        for _ in range(40):  # 160 KiB total >> budget
            b.send("t", 0, payload)
        got = []
        offsets = {0: 0}
        rounds = 0
        while True:
            recs = b.poll("t", offsets)
            if not recs:
                break
            rounds += 1
            for p, o, pay in recs:
                got.append((o, pay))
                offsets[p] = o + 1
        assert len(got) == 40
        assert all(pay == payload for _o, pay in got)
        assert rounds > 1  # the bound actually chunked the stream


def test_server_reports_errors_not_disconnects(tmp_path):
    with LogServer(str(tmp_path / "log")) as (host, port):
        b = RemoteLogBroker(host, port)
        with pytest.raises(RuntimeError, match="broker error"):
            b._rpc({"op": "nope"})
        # the connection is still usable afterwards
        b.send("t", 0, b"ok")
        assert len(b.poll("t", {})) == 1


def test_cli_listen_from_beginning_and_tail(tmp_path, capsys):
    """CLI ``listen`` (KafkaListenCommand.scala:22-44 analog) over the TCP
    transport: --from-beginning replays, the default tails only NEW
    events, --group commits offsets so a restart resumes past what it
    already printed."""
    from geomesa_tpu.tools import cli

    with LogServer(str(tmp_path / "log"), partitions=2) as (host, port):
        s = StreamDataStore(broker=RemoteLogBroker(host, port))
        s.create_schema(parse_spec("t", SPEC))
        for i in range(5):
            s.write("t", [f"n{i}", 1760000000000 + i, Point(1.0, 2.0)],
                    fid=f"f{i}", ts_ms=1760000000000 + i)
        s.delete("t", "f3", ts_ms=1760000001000)

        base = ["listen", "--name", "t", "--spec", SPEC,
                "--broker", f"{host}:{port}"]
        # replay: all 5 adds + the delete, formatted like the reference's
        # OutFeatureListener lines
        rc = cli.main(base + ["--from-beginning", "--max-messages", "6"])
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 6
        adds = [l for l in out if "[add/update]" in l]
        assert len(adds) == 5
        assert any("fid=f0" in l and "n0|" in l for l in adds)
        assert sum("[delete]" in l and "fid=f3" in l for l in out) == 1
        assert out[0].startswith("2025-")  # ISO-formatted event time

        # default start = live end: a bounded --duration run sees nothing
        rc = cli.main(base + ["--duration", "0.3", "--poll-interval", "0.05"])
        assert rc == 0
        assert capsys.readouterr().out == ""

        # group resume: first run prints 3 and commits; the restart
        # resumes AFTER them (committed offsets win over --from-beginning)
        g = ["--group", "g1", "--from-beginning"]
        rc = cli.main(base + g + ["--max-messages", "3"])
        assert rc == 0
        first = capsys.readouterr().out.strip().splitlines()
        assert len(first) == 3
        rc = cli.main(base + g + ["--max-messages", "3"])
        assert rc == 0
        second = capsys.readouterr().out.strip().splitlines()
        assert len(second) == 3

        def key(line):
            kind = "delete" if "[delete]" in line else "add"
            fid = next(t for t in line.split() if t.startswith("fid="))
            return (kind, fid)

        # together the two bounded runs cover all 6 events exactly once
        assert sorted(key(l) for l in first + second) == sorted(
            [("add", f"fid=f{i}") for i in range(5)] + [("delete", "fid=f3")]
        )


def test_cli_listen_rejects_bad_transport_args(tmp_path, capsys):
    from geomesa_tpu.tools import cli

    rc = cli.main(["listen", "--name", "t", "--spec", SPEC])
    assert rc == 1
    rc = cli.main(["listen", "--name", "t", "--spec", SPEC,
                   "--broker", "h:1", "--log-root", str(tmp_path)])
    assert rc == 1
    rc = cli.main(["listen", "--name", "t", "--spec", SPEC,
                   "--broker", "nope"])
    assert rc == 1
