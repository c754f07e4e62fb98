"""Hot-path batching: bulk queue ops edge cases and the batched-drain
equivalence guarantee (batched tasklet path == per-item path, §3.2)."""

import pytest

from repro.core import (CollectorSink, JetCluster, JobConfig, Journal,
                        JournalSource, Pipeline, VirtualClock,
                        GUARANTEE_EXACTLY_ONCE, counting, sliding)
from repro.core.backpressure import NetworkLink
from repro.core.clock import VirtualClock as VC
from repro.core.engine import JOB_COMPLETED
from repro.core.events import DONE, Barrier, Event, Watermark
from repro.core.queues import SPSCQueue
from repro.core.tasklet import EdgeCollector
from repro.core.dag import PARTITION_COUNT, Routing
from repro.nexmark.generator import NexmarkGenerator, fill_journal


# ---------------------------------------------------------------------------
# SPSCQueue bulk ops
# ---------------------------------------------------------------------------

def test_offer_many_wraparound_and_partial():
    q = SPSCQueue(8)
    assert q.offer_many([1, 2, 3, 4, 5, 6]) == 6
    assert [q.poll() for _ in range(5)] == [1, 2, 3, 4, 5]
    # head=5, tail=6: a 7-item batch wraps around the ring boundary
    assert q.offer_many([7, 8, 9, 10, 11, 12, 13]) == 7
    assert q.is_full()
    # full queue: backpressure, nothing accepted
    assert q.offer_many([99]) == 0
    assert [q.poll() for _ in range(8)] == [6, 7, 8, 9, 10, 11, 12, 13]
    assert q.poll() is None


def test_offer_many_partial_acceptance_under_backpressure():
    q = SPSCQueue(4)
    assert q.offer_many(list(range(10))) == 4
    assert len(q) == 4
    assert q.poll() == 0
    # start/end slicing: resume the rejected suffix
    assert q.offer_many(list(range(10)), 4, 6) == 1
    assert [q.poll() for _ in range(4)] == [1, 2, 3, 4]


def test_poll_many_wraparound():
    q = SPSCQueue(4)
    q.offer_many([1, 2, 3])
    assert q.poll_many(2) == [1, 2]
    q.offer_many([4, 5, 6])          # wraps
    assert q.poll_many(10) == [3, 4, 5, 6]
    assert q.poll_many(1) == []
    # consumed slots are cleared (no leaks keeping objects alive)
    assert all(s is None for s in q._buf)


def test_poll_prefix_segregates_control_items():
    q = SPSCQueue(16)
    e1, e2, e3 = Event(1, "a", 1), Event(2, "b", 2), Event(3, "c", 3)
    wm = Watermark(5)
    q.offer(e1)
    q.offer(e2)
    q.offer(wm)
    q.offer(e3)
    events, ctrl = q.poll_prefix(16)
    assert events == [e1, e2] and ctrl is wm
    # the event AFTER the watermark stayed behind the control boundary
    events, ctrl = q.poll_prefix(16)
    assert events == [e3] and ctrl is None
    assert q.is_empty()


def test_poll_prefix_leading_control_and_limit():
    q = SPSCQueue(16)
    b = Barrier(1)
    q.offer(b)
    q.offer(Event(1, "a", 1))
    events, ctrl = q.poll_prefix(16)
    assert events == [] or events == ()
    assert ctrl is b
    # limit bounds the data run; control beyond the limit is not consumed
    q2 = SPSCQueue(16)
    evs = [Event(i, "k", i) for i in range(6)]
    for e in evs:
        q2.offer(e)
    q2.offer(DONE)
    got, ctrl = q2.poll_prefix(4)
    assert list(got) == evs[:4] and ctrl is None
    got, ctrl = q2.poll_prefix(4)
    assert list(got) == evs[4:] and ctrl is DONE


def test_network_link_bulk_ops_roundtrip():
    clock = VC()
    link = NetworkLink(clock, latency_s=0.01, initial_window=8)
    items = [Event(i, "k", i) for i in range(6)] + [Watermark(6)]
    assert link.offer_many(items) == 7
    # credit exhausted at window=8 after one more
    assert link.offer_many([Event(9, "k", 9), Event(10, "k", 10)]) == 1
    link.pump()
    assert link.poll_prefix(16) == ((), None), "items still in flight"
    clock.advance(0.02)
    link.pump()
    events, ctrl = link.poll_prefix(16)
    assert [e.ts for e in events] == [0, 1, 2, 3, 4, 5]
    assert isinstance(ctrl, Watermark) and ctrl.ts == 6


# ---------------------------------------------------------------------------
# EdgeCollector: bulk routing == per-item routing
# ---------------------------------------------------------------------------

def _partitioned_pair(n_queues=3):
    queues = [SPSCQueue(1024) for _ in range(n_queues)]
    p2q = [pid % n_queues for pid in range(PARTITION_COUNT)]
    return queues, EdgeCollector(queues, Routing.PARTITIONED, None, p2q)


def test_partitioned_offer_many_matches_per_item():
    items = [Event(i, f"k{i % 17}", i) for i in range(500)]
    qs_bulk, c_bulk = _partitioned_pair()
    qs_item, c_item = _partitioned_pair()
    assert c_bulk.offer_many(items) == 500
    for it in items:
        assert c_item.offer(it)
    for qb, qi in zip(qs_bulk, qs_item):
        assert qb.poll_many(1024) == qi.poll_many(1024)


def test_partitioned_offer_many_stops_at_full_destination():
    queues = [SPSCQueue(4), SPSCQueue(1024)]
    p2q = [pid % 2 for pid in range(PARTITION_COUNT)]
    c = EdgeCollector(queues, Routing.PARTITIONED, lambda ev: ev.key, p2q)
    # keys chosen so every item routes to queue 0 (capacity 4)
    key0 = next(k for k in range(100)
                if p2q[hash(k) % PARTITION_COUNT] == 0)
    items = [Event(i, key0, i) for i in range(10)]
    accepted = c.offer_many(items)
    assert accepted == 4          # prefix semantics: stop at the full queue
    assert len(queues[0]) == 4 and len(queues[1]) == 0


# ---------------------------------------------------------------------------
# Equivalence: batched drain == item-at-a-time drain
# ---------------------------------------------------------------------------

def _run_q5_job(monkeypatch, drain_batch):
    """Deterministic Q5 over a journal on a virtual clock; returns the
    ordered sink output plus snapshot/engine counters."""
    from repro.core import tasklet as tasklet_mod
    monkeypatch.setattr(tasklet_mod, "DRAIN_BATCH", drain_batch)
    journal = Journal(n_partitions=8)
    gen = NexmarkGenerator(rate=5000, n_keys=20)
    fill_journal(journal, gen, 4000)
    clock = VirtualClock()
    cluster = JetCluster(n_nodes=2, cooperative_threads=2, clock=clock)
    out = []
    from repro.nexmark.queries import q5, is_bid
    p = Pipeline.create()
    # paced source: virtual time must pass for snapshot intervals to fire
    (p.read_from(lambda: JournalSource(journal, finite=True, rate=20000),
                 name="bids")
       .filter(is_bid)
       .with_key(lambda b: b.auction)
       .window(sliding(200, 50))
       .aggregate(counting())
       .write_to(lambda: CollectorSink(out)))
    cfg = JobConfig(processing_guarantee=GUARANTEE_EXACTLY_ONCE,
                    snapshot_interval_s=0.05)
    job = cluster.submit(p.to_dag(), cfg)
    cluster.run_until_complete(job)
    results = sorted((ev.ts, ev.key, ev.value.window_end, ev.value.value)
                     for ev in out)
    stats = job.execution.stats()
    return results, job.snapshots_taken, stats["items_out"]


def test_batched_drain_equivalent_to_per_item(monkeypatch):
    batched, snaps_b, _ = _run_q5_job(monkeypatch, 256)
    per_item, snaps_i, _ = _run_q5_job(monkeypatch, 1)
    assert batched == per_item
    assert len(batched) > 0
    # the Chandy-Lamport protocol behaved identically (barrier alignment
    # is unaffected by drain batch size)
    assert snaps_b > 0 and snaps_i > 0


def test_fused_source_fanout_routes_watermarks():
    """A fused source whose chain tail fans out to a keyed edge AND a sink
    must broadcast its watermarks on the keyed edge (regression: the
    multi-collector flush used to hand the Watermark to the partitioned
    data route, which reads .key)."""
    import time
    from repro.core import (PacedGeneratorSource, WallClock)
    cluster = JetCluster(n_nodes=1, cooperative_threads=2, clock=WallClock())
    raw, windows = [], []
    p = Pipeline.create()
    # chain tail (the rekey) fans out: its keyed edge AND a sink both
    # attach to the fused source vertex -> one PARTITIONED collector
    keyed = (p.read_from(lambda: PacedGeneratorSource(
                 lambda s: (s, s % 4, 1), rate=100000, max_events=2000))
               .map(lambda v: v)
               .with_key(lambda v: v % 4))
    (keyed.window(sliding(100, 50))
          .aggregate(counting())
          .write_to(lambda: CollectorSink(windows)))
    keyed.write_to(lambda: CollectorSink(raw))
    dag = p.to_dag()
    # the source vertex must carry the fused chain (fan-out happens at
    # its collectors, which is the path under test)
    assert any("+" in name for name in dag.vertices), dag.vertices
    job = cluster.submit(dag)
    deadline = time.monotonic() + 30
    while job.status != JOB_COMPLETED and time.monotonic() < deadline:
        cluster.step()
    assert job.status == JOB_COMPLETED
    assert len(raw) == 2000
    assert windows, "keyed branch emitted no window results"


def test_fanout_flush_batched_equivalence():
    """The multi-collector (fan-out) flush moves data runs in bulk with
    per-collector progress; every queue must still see exactly the
    per-item protocol's sequence — events in stream order on its route,
    control items broadcast in position — under backpressure/resumption
    (tiny queues force partial acceptance mid-run)."""
    from repro.core.processor import Processor
    from repro.core.tasklet import (GUARANTEE_NONE, ProcessorTasklet,
                                    SnapshotContext)
    from repro.core.events import DoneItem

    items = []
    for i in range(300):
        items.append(Event(i, i % 7, i))
        if i % 31 == 30:
            # a fused source interleaves watermarks into the same outbox
            items.append(Watermark(i))

    class Src(Processor):
        def __init__(self):
            self._i = 0

        def complete(self):
            n = 0
            while self._i < len(items) and n < 16:
                if not self.outbox.offer(items[self._i]):
                    return False
                self._i += 1
                n += 1
            return self._i >= len(items)

    qs_a = [SPSCQueue(8), SPSCQueue(8)]          # keyed branch
    p2q = [pid % 2 for pid in range(PARTITION_COUNT)]
    col_a = EdgeCollector(qs_a, Routing.PARTITIONED, None, p2q)
    q_b = SPSCQueue(4)                           # raw sink branch
    col_b = EdgeCollector([q_b], Routing.ISOLATED, None, None)
    t = ProcessorTasklet("src", Src(), [], [col_a, col_b],
                         SnapshotContext(GUARANTEE_NONE), "src", 0,
                         is_source=True)
    t.processor.init(t.outbox, None)
    got_a, got_b = [[], []], []
    for _ in range(100_000):
        t.call()
        for qi, q in enumerate(qs_a):
            got_a[qi].extend(q.poll_many(64))
        got_b.extend(q_b.poll_many(64))
        if t.is_done:
            break
    assert t.is_done
    for qi, q in enumerate(qs_a):
        got_a[qi].extend(q.poll_many(64))
    got_b.extend(q_b.poll_many(64))

    # per-item oracle: partitioned routes events by key, broadcasts
    # control; isolated takes everything; DONE closes every queue
    exp_a = [[], []]
    for it in items:
        if isinstance(it, Event):
            exp_a[p2q[hash(it.key) % PARTITION_COUNT]].append(it)
        else:
            exp_a[0].append(it)
            exp_a[1].append(it)
    assert [x for x in got_b if not isinstance(x, DoneItem)] == items
    for qi in range(2):
        assert [x for x in got_a[qi]
                if not isinstance(x, DoneItem)] == exp_a[qi]
        assert isinstance(got_a[qi][-1], DoneItem)


def test_flush_zero_collectors_consumes_silently():
    """A terminal vertex (no out-edges) whose processor emits to its outbox
    must consume the items silently, as the per-item loop did (regression:
    the bulk fan-out path crashed on min() of an empty offsets list)."""
    from repro.core.processor import Processor
    from repro.core.tasklet import (GUARANTEE_NONE, ProcessorTasklet,
                                    SnapshotContext)

    class Src(Processor):
        def __init__(self):
            self._emitted = False

        def complete(self):
            if not self._emitted:
                self.outbox.offer(Event(1, "k", 1))
                self._emitted = True
            return True

    t = ProcessorTasklet("s", Src(), [], [], SnapshotContext(GUARANTEE_NONE),
                         "s", 0, is_source=True)
    t.processor.init(t.outbox, None)
    for _ in range(10):
        t.call()
        if t.is_done:
            break
    assert t.is_done
    assert t.items_out == 1


def test_batched_drain_equivalent_without_guarantee(monkeypatch):
    def run(drain):
        from repro.core import tasklet as tasklet_mod
        monkeypatch.setattr(tasklet_mod, "DRAIN_BATCH", drain)
        journal = Journal(n_partitions=4)
        gen = NexmarkGenerator(rate=3000, n_keys=10)
        fill_journal(journal, gen, 1500)
        cluster = JetCluster(n_nodes=1, cooperative_threads=2,
                             clock=VirtualClock())
        out = []
        from repro.nexmark.queries import is_bid
        p = Pipeline.create()
        (p.read_from(lambda: JournalSource(journal, finite=True))
           .filter(is_bid)
           .with_key(lambda b: b.auction)
           .window(sliding(100, 25))
           .aggregate(counting())
           .write_to(lambda: CollectorSink(out)))
        job = cluster.submit(p.to_dag())
        cluster.run_until_complete(job)
        return [(ev.ts, ev.key, ev.value.window_end, ev.value.value)
                for ev in out]

    assert sorted(run(256)) == sorted(run(1))


def test_watermark_waits_for_what_it_closed_on_every_queue():
    """A watermark leaves only after every item its processor emitted for
    it: items stuck behind a full queue may be bound for a queue with room,
    where a broadcast would otherwise put the watermark ahead of them."""
    from repro.core.processor import Processor
    from repro.core.tasklet import (GUARANTEE_NONE, InQueue,
                                    ProcessorTasklet, SnapshotContext)

    p2q = [pid % 2 for pid in range(PARTITION_COUNT)]
    key0, key1 = (next(k for k in range(100)
                       if p2q[hash(k) % PARTITION_COUNT] == q)
                  for q in (0, 1))

    class Closer(Processor):
        def try_process_watermark(self, wm):
            # the results the watermark closes: three for queue 0 (which
            # holds two), then one for queue 1
            for i in range(3):
                self.outbox.offer(Event(wm.ts - 1, key0, i))
            self.outbox.offer(Event(wm.ts - 1, key1, 3))
            return True

    src = SPSCQueue(16)
    out = [SPSCQueue(2), SPSCQueue(1024)]
    t = ProcessorTasklet(
        "w", Closer(), [InQueue(src, 0)],
        [EdgeCollector(out, Routing.PARTITIONED, lambda ev: ev.key, p2q)],
        SnapshotContext(GUARANTEE_NONE), "w", 0)
    t.processor.init(t.outbox, None)
    assert src.offer(Watermark(10))
    t.call()
    # queue 0 is full and nobody reads it yet: queue 1 must not see the
    # watermark before the item that precedes it in the outbox
    got1 = out[1].poll_many(64)
    assert not any(isinstance(x, Watermark) for x in got1)
    got0 = []
    for _ in range(20):
        got0.extend(out[0].poll_many(64))
        got1.extend(out[1].poll_many(64))
        t.call()
    got0.extend(out[0].poll_many(64))
    got1.extend(out[1].poll_many(64))
    assert [x.value for x in got0[:3]] == [0, 1, 2]
    assert isinstance(got0[3], Watermark)
    assert [x.value for x in got1[:1]] == [3]
    assert isinstance(got1[1], Watermark)
