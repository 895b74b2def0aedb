"""Durable cross-process streaming (stream/filelog.py): the file-backed
partitioned log + committed offsets must survive a kill -9 of the consumer
mid-stream and replay to the same query result — the crash contract of the
reference's Kafka broker + ZookeeperOffsetManager
(kafka/data/KafkaDataStore.scala:44-90, lambda/stream/ZookeeperOffsetManager.scala)."""

import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from geomesa_tpu.geom.base import Point
from geomesa_tpu.schema.featuretype import parse_spec
from geomesa_tpu.stream.filelog import FileLogBroker, FileOffsetManager
from geomesa_tpu.stream.store import StreamDataStore

SPEC = "name:String,dtg:Date,*geom:Point:srid=4326"


def _write_n(store, n, start=0):
    for i in range(start, start + n):
        store.write("t", [f"n{i}", 1760000000000 + i, Point(i % 360 - 180, i % 170 - 85)],
                    fid=f"f{i}", ts_ms=1760000000000 + i)


def test_filelog_roundtrip_and_torn_tail(tmp_path):
    root = str(tmp_path / "log")
    b = FileLogBroker(root, partitions=3)
    for i in range(50):
        b.send("t", i % 3, f"msg{i}".encode())
    got = b.poll("t", {})
    assert len(got) == 50
    assert b.end_offsets("t") == {0: 17, 1: 17, 2: 16}
    # torn tail: a partial record is invisible until completed
    path = os.path.join(root, "t", "p0.log")
    with open(path, "ab") as f:
        f.write(b"\x20\x00\x00\x00partial")
    b2 = FileLogBroker(root, partitions=3)
    assert len(b2.poll("t", {})) == 50


def test_two_process_producer_consumer(tmp_path):
    """Producer in ANOTHER OS process; this process consumes live."""
    root = str(tmp_path / "log")
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
        from geomesa_tpu.stream.filelog import FileLogBroker
        from geomesa_tpu.stream.store import StreamDataStore
        from geomesa_tpu.schema.featuretype import parse_spec
        from geomesa_tpu.geom.base import Point
        s = StreamDataStore(broker=FileLogBroker({root!r}))
        s.create_schema(parse_spec("t", {SPEC!r}))
        for i in range(200):
            s.write("t", [f"n{{i}}", 1760000000000 + i, Point(0.0, 0.0)],
                    fid=f"f{{i}}", ts_ms=1760000000000 + i)
        print("DONE")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env)
    assert "DONE" in p.stdout, p.stderr[-2000:]
    consumer = StreamDataStore(broker=FileLogBroker(root))
    consumer.create_schema(parse_spec("t", SPEC))
    res = consumer.query("t", "INCLUDE")
    assert len(res) == 200
    assert len(consumer.query("t", "bbox(geom, -1, -1, 1, 1)")) == 200


def test_consumer_kill9_replays_to_same_result(tmp_path):
    """Consumer process is SIGKILLed mid-stream; a fresh consumer replays
    the durable log and answers queries identically to a never-crashed
    oracle consumer."""
    root = str(tmp_path / "log")
    producer = StreamDataStore(broker=FileLogBroker(root))
    producer.create_schema(parse_spec("t", SPEC))
    _write_n(producer, 300)
    producer.delete("t", "f7")
    producer.delete("t", "f250")

    # consumer child: polls, reports, then hangs until killed
    code = textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
        from geomesa_tpu.stream.filelog import FileLogBroker
        from geomesa_tpu.stream.store import StreamDataStore
        from geomesa_tpu.schema.featuretype import parse_spec
        s = StreamDataStore(broker=FileLogBroker({root!r}))
        s.create_schema(parse_spec("t", {SPEC!r}))
        n = s.poll("t")
        print("POLLED", n, flush=True)
        time.sleep(600)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", code], stdout=subprocess.PIPE,
                            text=True, env=env)
    line = proc.stdout.readline()
    assert line.startswith("POLLED")
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait(timeout=30)

    # more writes AFTER the crash
    _write_n(producer, 50, start=300)

    fresh = StreamDataStore(broker=FileLogBroker(root))
    fresh.create_schema(parse_spec("t", SPEC))
    oracle = StreamDataStore(broker=FileLogBroker(root))
    oracle.create_schema(parse_spec("t", SPEC))
    got = sorted(map(str, fresh.query("t", "INCLUDE").fids))
    want = sorted(map(str, oracle.query("t", "INCLUDE").fids))
    assert got == want
    assert len(got) == 348  # 350 written - 2 deleted
    assert "f7" not in got and "f250" not in got


def test_offset_manager_consumer_group_resumes(tmp_path):
    """A consumer-group reader with committed offsets resumes AFTER its
    last commit (no duplicate delivery to listeners across restarts)."""
    root = str(tmp_path / "log")
    producer = StreamDataStore(broker=FileLogBroker(root))
    producer.create_schema(parse_spec("t", SPEC))
    _write_n(producer, 100)

    seen = []
    c1 = StreamDataStore(broker=FileLogBroker(root),
                         offset_manager=FileOffsetManager(root, "g1"))
    c1.create_schema(parse_spec("t", SPEC))
    c1.add_listener("t", lambda m: seen.append(m))
    assert c1.poll("t") == 100
    _write_n(producer, 25, start=100)

    # "restarted" consumer in the same group: resumes from the commit
    c2 = StreamDataStore(broker=FileLogBroker(root),
                         offset_manager=FileOffsetManager(root, "g1"))
    c2.create_schema(parse_spec("t", SPEC))
    seen2 = []
    c2.add_listener("t", lambda m: seen2.append(m))
    assert c2.poll("t") == 25
    assert {m.fid for m in seen2} == {f"f{i}" for i in range(100, 125)}
    # a different group starts from the beginning
    c3 = StreamDataStore(broker=FileLogBroker(root),
                         offset_manager=FileOffsetManager(root, "g2"))
    c3.create_schema(parse_spec("t", SPEC))
    assert c3.poll("t") == 125


def test_lambda_store_survives_kill9_of_consumer(tmp_path):
    """Lambda tier on the durable transport: a SIGKILLed consumer process
    loses nothing — a fresh process re-reads the log, re-ages expired
    features down idempotently, and the union query matches."""
    from geomesa_tpu.stream.lambda_store import LambdaDataStore

    root = str(tmp_path / "log")
    producer = StreamDataStore(broker=FileLogBroker(root))
    producer.create_schema(parse_spec("t", SPEC))
    _write_n(producer, 120)

    # consumer that persisted some then died (simulate by building one,
    # persisting, and discarding it without any clean shutdown)
    lam1 = LambdaDataStore(transient=StreamDataStore(broker=FileLogBroker(root)),
                           age_ms=10)
    lam1.create_schema(parse_spec("t", SPEC))
    lam1.persist_expired("t", now_ms=1760000000000 + 200 + 10)
    del lam1  # kill -9 analog: no flush, no offsets, nothing graceful

    lam2 = LambdaDataStore(transient=StreamDataStore(broker=FileLogBroker(root)),
                           age_ms=10)
    lam2.create_schema(parse_spec("t", SPEC))
    n2 = lam2.persist_expired("t", now_ms=1760000000000 + 200 + 10)
    res = lam2.query("t", "INCLUDE")
    assert len(res) == 120
    assert sorted(map(str, res.fids)) == sorted(f"f{i}" for i in range(120))


def test_producer_crash_torn_tail_repaired_on_next_send(tmp_path):
    """A producer SIGKILLed mid-append leaves a torn record; the NEXT send
    (any process) must truncate it so the partition never misframes."""
    root = str(tmp_path / "log")
    b = FileLogBroker(root, partitions=1)
    b.send("t", 0, b"alpha")
    b.send("t", 0, b"beta")
    path = os.path.join(root, "t", "p0.log")
    with open(path, "ab") as f:
        f.write(b"\x64\x00\x00\x00only-10b")  # len=100, 8 bytes present
    # a FRESH broker (crash wiped in-memory state) appends next
    b2 = FileLogBroker(root, partitions=1)
    b2.send("t", 0, b"gamma")
    got = [p for _, _, p in FileLogBroker(root, partitions=1).poll("t", {})]
    assert got == [b"alpha", b"beta", b"gamma"]
    assert FileLogBroker(root, partitions=1).end_offsets("t") == {0: 3}


def test_partition_assignment_splits_topic_across_consumers(tmp_path):
    """Stream parallelism: two consumers in one group with DISJOINT
    partition assignments collectively consume every record exactly once
    (the Kafka consumer-group assignment shape over the durable log)."""
    root = str(tmp_path / "log")
    producer = StreamDataStore(broker=FileLogBroker(root))
    producer.create_schema(parse_spec("t", SPEC))
    _write_n(producer, 200)

    got = []
    consumers = [
        StreamDataStore(
            broker=FileLogBroker(root),
            offset_manager=FileOffsetManager(root, f"g-p{i}"),
            assigned_partitions=parts,
        )
        for i, parts in enumerate(([0, 1], [2, 3]))
    ]
    for c in consumers:
        c.create_schema(parse_spec("t", SPEC))
        c.add_listener("t", lambda m: got.append(m.fid))
        c.poll("t")
    assert sorted(got) == sorted(f"f{i}" for i in range(200))
    assert len(got) == len(set(got))  # exactly once across the group


def test_lambda_persist_watermark_skips_repersist(tmp_path):
    """With an offset manager, a restarted lambda consumer does NOT
    re-write already-persisted features to the persistent tier — the
    committed watermark (ZookeeperOffsetManager role) marks them done."""
    from geomesa_tpu.store.fs import FsDataStore
    from geomesa_tpu.stream.lambda_store import LambdaDataStore

    root = str(tmp_path / "log")
    pdir = str(tmp_path / "persist")
    producer = StreamDataStore(broker=FileLogBroker(root))
    producer.create_schema(parse_spec("t", SPEC))
    _write_n(producer, 100)

    def make():
        return LambdaDataStore(
            persistent=FsDataStore(pdir),
            transient=StreamDataStore(broker=FileLogBroker(root)),
            age_ms=10,
            offset_manager=FileOffsetManager(root, "lam"),
        )

    lam1 = make()
    lam1.create_schema(parse_spec("t", SPEC))
    n1 = lam1.persist_expired("t", now_ms=1760000000000 + 100 + 10)
    assert n1 == 100
    del lam1  # crash analog

    lam2 = make()
    lam2.create_schema(parse_spec("t", SPEC))
    # replayed cache entries are below the watermark: nothing re-persisted
    n2 = lam2.persist_expired("t", now_ms=1760000000000 + 100 + 10)
    assert n2 == 0
    assert len(lam2.query("t", "INCLUDE")) == 100
    # new writes after the watermark persist normally
    _write_n(producer, 20, start=100)
    n3 = lam2.persist_expired("t", now_ms=1760000000000 + 200 + 10)
    assert n3 == 20
    assert len(lam2.query("t", "INCLUDE")) == 120
    # LATE EVENT TIME: a fresh message whose ts is far below the committed
    # watermark must STILL persist (the watermark is log offsets, not
    # event time — an event-time watermark would silently drop this row)
    producer.write("t", ["late", 1760000000000 - 999, Point(0.0, 0.0)],
                   fid="late1", ts_ms=1760000000000 - 999)
    n4 = lam2.persist_expired("t", now_ms=1760000000000 + 200 + 10)
    assert n4 == 1
    res = lam2.query("t", "IN ('late1')")
    assert len(res) == 1
    assert len(lam2.query("t", "INCLUDE")) == 121


def test_lambda_watermark_out_of_order_event_times(tmp_path):
    """The reproduced data-loss shape: a LOWER-offset message with a
    LATER event time must survive a watermark committed after
    higher-offset, earlier-ts entries were persisted. The min-live-offset
    watermark holds it back until the entry itself is handled."""
    from geomesa_tpu.store.fs import FsDataStore
    from geomesa_tpu.stream.lambda_store import LambdaDataStore

    root = str(tmp_path / "log")
    pdir = str(tmp_path / "persist")
    base = 1760000000000
    producer = StreamDataStore(broker=FileLogBroker(root, partitions=1))
    producer.create_schema(parse_spec("t", SPEC))
    # offset 0: LATE-expiring (fresh event time); offsets 1-2: expire first
    producer.write("t", ["fresh", base + 1000, Point(0.0, 0.0)],
                   fid="f0", ts_ms=base + 1000)
    producer.write("t", ["old", base, Point(1.0, 1.0)], fid="f4", ts_ms=base)
    producer.write("t", ["old", base, Point(2.0, 2.0)], fid="f5", ts_ms=base)

    def make():
        return LambdaDataStore(
            persistent=FsDataStore(pdir),
            transient=StreamDataStore(broker=FileLogBroker(root, partitions=1)),
            age_ms=10,
            offset_manager=FileOffsetManager(root, "lam2"),
        )

    lam = make()
    lam.create_schema(parse_spec("t", SPEC))
    assert lam.persist_expired("t", now_ms=base + 11) == 2  # f4, f5 only
    del lam  # crash analog
    lam2 = make()
    lam2.create_schema(parse_spec("t", SPEC))
    # f0 expires now; a max-offset watermark would classify it done & DROP
    # it. The min-live watermark cannot advance past live offset 0, so f0
    # persists and f4/f5 are re-persisted idempotently (the Kafka
    # contiguous-commit tradeoff: conservative, never lossy).
    assert lam2.persist_expired("t", now_ms=base + 1011) == 3
    res = lam2.query("t", "IN ('f0')")
    assert len(res) == 1, "late-expiring lower-offset feature was lost"
    assert len(lam2.query("t", "INCLUDE")) == 3


def test_lambda_watermark_only_commits_owned_partitions(tmp_path):
    """A consumer assigned a partition subset must not advance OTHER
    partitions' watermarks — another consumer's live entries there are
    invisible to it (review regression)."""
    from geomesa_tpu.store.fs import FsDataStore
    from geomesa_tpu.stream.lambda_store import LambdaDataStore

    root = str(tmp_path / "log")
    base = 1760000000000
    producer = StreamDataStore(broker=FileLogBroker(root, partitions=4))
    producer.create_schema(parse_spec("t", SPEC))
    _write_n(producer, 80)
    om = FileOffsetManager(root, "lamshared")
    lam_b = LambdaDataStore(
        persistent=FsDataStore(str(tmp_path / "pb")),
        transient=StreamDataStore(
            broker=FileLogBroker(root, partitions=4),
            assigned_partitions=[2, 3],
        ),
        age_ms=10,
        offset_manager=om,
    )
    lam_b.create_schema(parse_spec("t", SPEC))
    lam_b.persist_expired("t", now_ms=base + 80 + 10)
    committed = om.offsets("t#persisted")
    assert set(committed) <= {2, 3}, committed  # partitions 0/1 untouched
