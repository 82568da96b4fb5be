"""The fast lane's single flight and the data a request saw on arrival
(concurrency/fast_lane.py `_execute_shared`, PR 38): a follower sent
after an acknowledged write never gets the answer of an execution whose
snapshot predates that write, and in a run that writes nothing every
follower still joins its leader."""

import threading
import time

from greptimedb_tpu.catalog.catalog import Catalog
from greptimedb_tpu.catalog.kv import MemoryKv
from greptimedb_tpu.query.engine import QueryEngine
from greptimedb_tpu.storage.engine import EngineConfig, RegionEngine
from greptimedb_tpu.utils.metrics import FAST_LANE_EVENTS

SQL = "SELECT host, last_value(v ORDER BY ts) FROM cpu GROUP BY host"


def _events() -> dict:
    out: dict = {}
    for key, v in FAST_LANE_EVENTS._snapshot().items():
        e = dict(key)["event"]
        out[e] = out.get(e, 0) + v
    return out


def _db(tmp_path):
    engine = RegionEngine(EngineConfig(data_dir=str(tmp_path / "data"),
                                       maintenance_workers=0))
    qe = QueryEngine(Catalog(MemoryKv()), engine)
    qe.execute_one("CREATE TABLE cpu (host STRING, v DOUBLE, ts "
                   "TIMESTAMP(3) TIME INDEX, PRIMARY KEY(host)) "
                   "WITH (append_mode = 'true')")
    qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES ('a', 1.0, 1000), "
                   "('b', 2.0, 1000)")
    for _ in range(3):  # the template is built at its second sighting
        qe.execute_one(SQL)
    assert len(qe.concurrency.fast_lane) == 1
    return engine, qe


class _HeldLeader:
    """Lets the FIRST execution take its snapshot and compute its answer,
    then holds it before it returns: the flight stays open."""

    def __init__(self, qe):
        self.qe, self.real = qe, qe.executor.execute
        self.started, self.release = threading.Event(), threading.Event()
        self.calls = 0
        qe.executor.execute = self

    def __call__(self, plan):
        self.calls += 1
        first = self.calls == 1
        result = self.real(plan)
        if first:
            self.started.set()
            assert self.release.wait(20)
        return result


def _ask(qe, out: list):
    def run():
        out.append(sorted(qe.execute_one(SQL).rows()))
    t = threading.Thread(target=run)
    t.start()
    return t


def test_a_follower_sent_after_an_acknowledged_write_reads_it(tmp_path):
    engine, qe = _db(tmp_path)
    held = _HeldLeader(qe)
    e0 = _events()
    leader_out, follower_out = [], []
    leader = _ask(qe, leader_out)
    assert held.started.wait(20)
    # acknowledged while the leader's execution, whose snapshot is
    # taken, is still in flight
    qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES ('a', 7.0, 2000)")
    follower = _ask(qe, follower_out)
    follower.join(20)
    assert not follower.is_alive(), "the follower waited for a stale flight"
    assert follower_out == [[["a", 7.0], ["b", 2.0]]]
    held.release.set()
    leader.join(20)
    # the leader was sent before the write: the older answer is its own
    assert leader_out == [[["a", 1.0], ["b", 2.0]]]
    e1 = _events()
    assert e1.get("stale_flight", 0) == e0.get("stale_flight", 0) + 1
    assert e1.get("coalesced", 0) == e0.get("coalesced", 0)
    assert held.calls == 2
    engine.close()


def test_in_a_run_that_writes_nothing_every_follower_joins(tmp_path):
    engine, qe = _db(tmp_path)
    held = _HeldLeader(qe)
    e0 = _events()
    outs = [[] for _ in range(4)]
    leader = _ask(qe, outs[0])
    assert held.started.wait(20)
    followers = [_ask(qe, o) for o in outs[1:]]
    lane = qe.concurrency.fast_lane
    t_end = time.monotonic() + 10
    while time.monotonic() < t_end and len(lane._flights) != 1:
        time.sleep(0.01)
    time.sleep(0.3)  # the followers reach the flight's event
    assert len(lane._flights) == 1 and held.calls == 1
    held.release.set()
    for t in [leader] + followers:
        t.join(20)
    assert all(o == [[["a", 1.0], ["b", 2.0]]] for o in outs)
    e1 = _events()
    assert e1.get("coalesced", 0) == e0.get("coalesced", 0) + 3
    assert e1.get("stale_flight", 0) == e0.get("stale_flight", 0)
    assert held.calls == 1  # one execution answered all four
    engine.close()


def test_readers_beside_a_writer_never_read_behind_an_acknowledgement(
        tmp_path):
    """Readers and a writer on ONE text: every answer holds at least
    what was acknowledged before its request was sent."""
    engine, qe = _db(tmp_path)
    acked = [1.0]
    stop = threading.Event()
    errors: list = []

    def writer():
        for i in range(2, 60):
            qe.execute_one("INSERT INTO cpu (host, v, ts) VALUES "
                           f"('a', {float(i)}, {i * 1000})")
            acked[0] = float(i)
        stop.set()

    def reader():
        while not stop.is_set():
            floor = acked[0]
            got = dict(qe.execute_one(SQL).rows())["a"]
            if got < floor:
                errors.append((got, floor))

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors[:5]
    engine.close()
