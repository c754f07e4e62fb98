"""Columnar EventBlock datapath: unit coverage for the block primitives
(vectorized hash, block routing, queue explode shim, generator blocks) and
the blocked-vs-per-event equivalence guarantee — the blocked datapath must
be observably identical to the scalar one, including watermark positions,
late-drop counts and exactly-once snapshots through node failure."""

import random
import time

import numpy as np
import pytest

from repro.core import (CollectorSink, EventBlock, JetCluster, JobConfig,
                        PacedGeneratorSource, Pipeline, VirtualClock,
                        WallClock, GUARANTEE_EXACTLY_ONCE, block_form,
                        counting, sliding, summing)
from repro.core.dag import (PARTITION_COUNT, Routing, partition_for_key,
                            partitions_for_keys)
from repro.core.engine import JOB_COMPLETED
from repro.core.events import Event, Watermark
from repro.core.queues import SPSCQueue
from repro.core.tasklet import EdgeCollector
from repro.nexmark import (DisorderedNexmarkGenerator, NexmarkGenerator,
                           queries)


# ---------------------------------------------------------------------------
# EventBlock primitives
# ---------------------------------------------------------------------------

def _block(n=10, payload=False):
    ts = np.arange(n, dtype=np.int64)
    key = (np.arange(n, dtype=np.int64) * 7) % 5
    value = np.arange(n, dtype=np.float64) * 1.5
    pl = [f"v{i}" for i in range(n)] if payload else None
    return EventBlock(ts, key, value, payload=pl,
                      cols={"aux": np.arange(n, dtype=np.int64) + 100})


def test_event_block_explode_and_select():
    blk = _block(10)
    evs = blk.to_events()
    assert [ev.ts for ev in evs] == list(range(10))
    assert all(isinstance(ev.ts, int) and isinstance(ev.key, int)
               for ev in evs)
    assert evs[4].value == 6.0
    sl = blk.slice(2, 5)
    assert len(sl) == 3 and sl.ts.tolist() == [2, 3, 4]
    assert sl.cols["aux"].tolist() == [102, 103, 104]
    tk = blk.take(np.array([5, 1, 3]))
    assert tk.ts.tolist() == [5, 1, 3]
    assert tk.cols["aux"].tolist() == [105, 101, 103]
    cp = blk.compress(blk.key == 0)
    assert cp.ts.tolist() == [0, 5]


def test_event_block_payload_travels_with_rows():
    blk = _block(6, payload=True)
    assert blk.take(np.array([4, 2])).values() == ["v4", "v2"]
    assert blk.slice(1, 3).values() == ["v1", "v2"]
    assert blk.to_events()[3].value == "v3"


def test_event_block_payload_fn_lazy_and_cached():
    calls = []

    def fn(blk, i):
        calls.append(i)
        return blk.cols["aux"][i] * 10

    blk = EventBlock(np.arange(4, dtype=np.int64),
                     np.zeros(4, dtype=np.int64),
                     payload_fn=fn,
                     cols={"aux": np.arange(4, dtype=np.int64)})
    # slicing keeps cols aligned, so the materializer still works after it
    sub = blk.slice(2, 4)
    assert sub.value_at(0) == 20
    assert sub.values() == [20, 30]
    assert blk.values() == [0, 10, 20, 30]
    n_calls = len(calls)
    assert blk.values() == [0, 10, 20, 30]     # cached: no re-derivation
    assert len(calls) == n_calls


def test_from_events_roundtrip():
    evs = [Event(i, i % 3, float(i)) for i in range(8)]
    blk = EventBlock.from_events(evs)
    assert [(e.ts, e.key, e.value) for e in blk.to_events()] == \
        [(e.ts, e.key, e.value) for e in evs]


# ---------------------------------------------------------------------------
# Vectorized partition hash
# ---------------------------------------------------------------------------

def test_partitions_for_keys_matches_python_hash():
    rng = np.random.RandomState(0)
    keys = np.concatenate([
        rng.randint(-(2**62), 2**62, 500).astype(np.int64),
        np.array([0, 1, -1, -2, 270, 271, (1 << 61) - 1, (1 << 61),
                  -(1 << 61) - 1, 2**62, -(2**63), 2**63 - 1],
                 dtype=np.int64),
    ])
    got = partitions_for_keys(keys)
    exp = [partition_for_key(int(k)) for k in keys]
    assert got.tolist() == exp


# ---------------------------------------------------------------------------
# Queue explode shim
# ---------------------------------------------------------------------------

def test_poll_prefix_blocks_as_data_and_explode():
    q = SPSCQueue(16)
    blk = _block(3)
    e0 = Event(99, 0, 0)
    wm = Watermark(5)
    q.offer(e0)
    q.offer(blk)
    q.offer(wm)
    q.offer(_block(2))
    # block-aware consumer: block rides along as one item
    events, ctrl = q.poll_prefix(16)
    assert events[0] is e0 and events[1] is blk and ctrl is wm
    # scalar consumer: the shim explodes the block at the queue boundary
    events, ctrl = q.poll_prefix(16, True)
    assert [ev.ts for ev in events] == [0, 1] and ctrl is None
    assert all(ev.__class__ is Event for ev in events)


def test_network_link_poll_prefix_explodes_blocks():
    from repro.core.backpressure import NetworkLink
    clock = VirtualClock()
    link = NetworkLink(clock, latency_s=0.001)
    link.offer(_block(3))
    link.offer(Watermark(7))
    clock.advance(0.01)
    link.pump()
    events, ctrl = link.poll_prefix(16, True)
    assert [ev.ts for ev in events] == [0, 1, 2]
    assert isinstance(ctrl, Watermark)


# ---------------------------------------------------------------------------
# EdgeCollector: vectorized block routing == per-item routing
# ---------------------------------------------------------------------------

def _partitioned(n_queues=3, cap=1024):
    queues = [SPSCQueue(cap) for _ in range(n_queues)]
    p2q = [pid % n_queues for pid in range(PARTITION_COUNT)]
    return queues, EdgeCollector(queues, Routing.PARTITIONED, None, p2q)


def test_block_routing_matches_per_item():
    n = 500
    ts = np.arange(n, dtype=np.int64)
    key = ((np.arange(n, dtype=np.int64) * 31 + 7) % 17)
    blk = EventBlock(ts, key, np.zeros(n))
    qs_blk, c_blk = _partitioned()
    qs_item, c_item = _partitioned()
    assert c_blk.offer(blk)
    for ev in blk.to_events():
        assert c_item.offer(ev)
    for qb, qi in zip(qs_blk, qs_item):
        got = []
        for item in qb.poll_many(1024):
            got.extend(item.to_events())
        exp = qi.poll_many(1024)
        assert [(e.ts, e.key) for e in got] == [(e.ts, e.key) for e in exp]


def test_block_routing_all_or_nothing_under_backpressure():
    # queue 0 full: NOTHING of the block lands anywhere; the retry after
    # draining delivers the whole block
    queues = [SPSCQueue(1), SPSCQueue(1024)]
    p2q = [pid % 2 for pid in range(PARTITION_COUNT)]
    c = EdgeCollector(queues, Routing.PARTITIONED, None, p2q)
    queues[0].offer(Event(0, 0, 0))        # occupy the only slot
    keys = np.arange(64, dtype=np.int64)
    blk = EventBlock(np.arange(64, dtype=np.int64), keys, np.zeros(64))
    assert not c.offer(blk)
    assert len(queues[1]) == 0, "partial delivery would break the barrier " \
        "ordering contract"
    queues[0].poll()
    assert c.offer(blk)
    assert len(queues[0]) == 1 and len(queues[1]) == 1


def test_offer_many_mixed_events_and_blocks():
    qs, c = _partitioned(2)
    items = [Event(0, 3, 0), _block(20), Event(1, 4, 1), _block(10)]
    assert c.offer_many(items) == 4
    total = 0
    for q in qs:
        for item in q.poll_many(1024):
            total += len(item) if isinstance(item, EventBlock) else 1
    assert total == 32


# ---------------------------------------------------------------------------
# NEXMark generator blocks
# ---------------------------------------------------------------------------

def test_nexmark_gen_block_matches_scalar():
    gen = NexmarkGenerator(rate=7000, n_keys=40)
    seqs = np.arange(300, dtype=np.int64)
    blk = gen.gen_block(seqs)
    for i in range(300):
        ts, key, val = gen(i)
        assert int(blk.ts[i]) == ts
        assert int(blk.key[i]) == key
        assert repr(blk.value_at(i)) == repr(val)
    # bid rows: value column is the price
    bid_rows = np.nonzero(blk.cols["kind"] == 2)[0]
    assert len(bid_rows)
    for i in bid_rows[:20].tolist():
        assert blk.value[i] == gen(i)[2].price


def test_nexmark_seed_draws_another_stream_of_the_same_shape():
    base = NexmarkGenerator(rate=7000, n_keys=40)
    gen = NexmarkGenerator(rate=7000, n_keys=40, seed=7)
    seqs = np.arange(300, dtype=np.int64)
    blk = gen.gen_block(seqs)
    for i in range(300):
        ts, key, val = gen(i)
        assert int(blk.ts[i]) == ts and int(blk.key[i]) == key
        assert repr(blk.value_at(i)) == repr(val)
        assert ts == base(i)[0]
        assert type(val) is type(base(i)[2])
    assert blk.key.tolist() != base.gen_block(seqs).key.tolist()
    # seed 0 is the historical stream
    assert repr(NexmarkGenerator(rate=7000, n_keys=40, seed=0)(5)) == \
        repr(base(5))


@pytest.mark.parametrize("seed", [0, 5])
def test_disordered_gen_block_matches_scalar(seed):
    gen = NexmarkGenerator(rate=10_000, n_keys=25)
    dis = DisorderedNexmarkGenerator(gen, max_skew_ms=40, seed=seed)
    n = 3 * dis.block
    blk = dis.gen_block(np.arange(n, dtype=np.int64))
    for i in range(n):
        ts, key, val = dis(i)
        assert int(blk.ts[i]) == ts and int(blk.key[i]) == key
        assert repr(blk.value_at(i)) == repr(val)
    # still a bounded permutation
    ordered = sorted(repr(gen(i)) for i in range(n))
    assert sorted(repr(dis(i)) for i in range(n)) == ordered
    top = -1 << 60
    for t in blk.ts.tolist():
        assert top - t <= 40
        top = max(top, t)


# ---------------------------------------------------------------------------
# Source: blocked emission == scalar emission (events AND watermarks)
# ---------------------------------------------------------------------------

def _source_sequence(gen, rate, total, block_size, wm_lag=0):
    """Run a lone PacedGeneratorSource tasklet; return the flattened
    (kind, payload) item sequence its out-edge observes."""
    from repro.core.processor import ProcessorContext
    from repro.core.tasklet import (GUARANTEE_NONE, ProcessorTasklet,
                                    SnapshotContext)
    from repro.core.clock import VirtualClock as VC

    clock = VC(auto_step=0.05)
    src = PacedGeneratorSource(gen, rate=rate, max_events=total,
                               wm_lag=wm_lag, block_size=block_size)
    q = SPSCQueue(1 << 14)
    col = EdgeCollector([q], Routing.ISOLATED, None, None)
    t = ProcessorTasklet("src", src, [], [col],
                         SnapshotContext(GUARANTEE_NONE), "src", 0,
                         is_source=True)
    src.init(t.outbox, ProcessorContext(
        vertex_name="src", global_index=0, local_index=0,
        total_parallelism=1, node_id=0, node_count=1, partition_ids=(),
        clock=clock))
    out = []
    for _ in range(200_000):
        if not t.call():
            clock.advance(0.05)
        drained = q.poll_many(1 << 14)
        for item in drained:
            if isinstance(item, EventBlock):
                out.extend(("ev", ev.ts, ev.key, repr(ev.value))
                           for ev in item.to_events())
            elif isinstance(item, Event):
                out.append(("ev", item.ts, item.key, repr(item.value)))
            elif isinstance(item, Watermark):
                out.append(("wm", item.ts))
        if t.is_done:
            break
    assert t.is_done
    for item in q.poll_many(1 << 14):
        if isinstance(item, Watermark):
            out.append(("wm", item.ts))
    return [x for x in out if not isinstance(x, tuple) or x[0] != "done"]


@pytest.mark.parametrize("disorder", [0, 20])
def test_paced_source_block_stream_identical_to_scalar(disorder):
    """The blocked source must emit the exact scalar item sequence:
    same events, same watermark VALUES at the same POSITIONS (blocks split
    at every watermark emission point)."""
    rate, total = 50_000, 6000
    gen = NexmarkGenerator(rate=rate, n_keys=20)
    if disorder:
        gen = DisorderedNexmarkGenerator(gen, max_skew_ms=disorder, seed=3)
    scalar = _source_sequence(gen, rate, total, 0, wm_lag=disorder)
    blocked = _source_sequence(gen, rate, total, None, wm_lag=disorder)
    assert scalar == blocked
    top_ts = max(x[1] for x in blocked if x[0] == "ev")
    assert ("wm", top_ts - disorder) in blocked


# ---------------------------------------------------------------------------
# End-to-end equivalence: blocked == per-event on Q5
# ---------------------------------------------------------------------------

def _run_q5(block_size, disorder=0, n_nodes=1, guarantee="none",
            kill_at_result=None, rate=60_000, total=24_000,
            window_ms=100, slide_ms=20):
    gen = NexmarkGenerator(rate=rate, n_keys=40)
    if disorder:
        gen = DisorderedNexmarkGenerator(gen, max_skew_ms=disorder, seed=9)
        total = (total // gen.block) * gen.block
    cluster = JetCluster(n_nodes=n_nodes, cooperative_threads=2,
                         clock=VirtualClock(auto_step=0.001))
    out = []
    p = queries.q5(
        lambda: PacedGeneratorSource(gen, rate=rate, max_events=total,
                                     wm_lag=disorder,
                                     block_size=block_size),
        lambda: CollectorSink(out), window_ms=window_ms, slide_ms=slide_ms)
    cfg = JobConfig(processing_guarantee=guarantee,
                    snapshot_interval_s=0.02)
    job = cluster.submit(p.to_dag(), cfg)
    killed = False
    for _ in range(4_000_000):
        if job.status == JOB_COMPLETED:
            break
        cluster.step()
        if (kill_at_result is not None and not killed
                and len(out) >= kill_at_result
                and job.snapshots_taken > 0):
            cluster.kill_node(cluster.node_ids[-1])
            killed = True
    assert job.status == JOB_COMPLETED
    if kill_at_result is not None:
        assert killed, "node was never killed — test setup broken"
    drops = sum(getattr(t.processor, "late_dropped", 0)
                for t in job.execution.tasklets)
    return (sorted(set((ev.ts, ev.key, ev.value.window_end,
                        ev.value.value) for ev in out)),
            drops)


def test_q5_blocked_equals_scalar_ordered():
    a, drops_a = _run_q5(0)
    b, drops_b = _run_q5(None)
    assert a == b and len(a) > 0
    assert drops_a == drops_b == 0


def test_q5_blocked_equals_scalar_disordered():
    a, drops_a = _run_q5(0, disorder=40)
    b, drops_b = _run_q5(None, disorder=40)
    assert a == b and len(a) > 0
    assert drops_a == drops_b == 0
    # and the disordered run matches the ordered one (lag covers skew)
    c, _ = _run_q5(None, disorder=0)
    assert {(w, k): v for _t, k, w, v in a} == \
        {(w, k): v for _t, k, w, v in c}


@pytest.mark.slow
def test_q5_blocked_exactly_once_through_kill_node():
    """Acceptance: blocked-vs-per-event equivalence holds through an
    exactly-once snapshot/restore cycle triggered by node failure."""
    base, _ = _run_q5(None, n_nodes=2)
    a, _ = _run_q5(0, n_nodes=2, guarantee=GUARANTEE_EXACTLY_ONCE,
                   kill_at_result=30)
    b, _ = _run_q5(None, n_nodes=2, guarantee=GUARANTEE_EXACTLY_ONCE,
                   kill_at_result=30)
    assert a == b == base and len(base) > 0


# ---------------------------------------------------------------------------
# Randomized equivalence: random map/filter/rekey/window pipelines
# ---------------------------------------------------------------------------

class SyntheticBlockGen:
    """Deterministic generator with scalar and columnar forms guaranteed
    identical; bounded-disorder timestamps, int values."""

    def __init__(self, rate, n_keys=16, skew=0, seed=1):
        self.rate = rate
        self.n_keys = n_keys
        self.skew = skew
        self.seed = seed

    def _rand(self, seqs):
        x = (np.asarray(seqs, dtype=np.uint64)
             + np.uint64((self.seed * 0x9E3779B97F4A7C15)
                         & 0xFFFFFFFFFFFFFFFF))
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))

    def gen_block(self, seqs):
        seqs = np.asarray(seqs, dtype=np.int64)
        r = self._rand(seqs)
        ts = (seqs.astype(np.float64) * 1000.0 / self.rate).astype(np.int64)
        if self.skew:
            ts = ts + (r % np.uint64(self.skew)).astype(np.int64) \
                - self.skew // 2
            ts[ts < 0] = 0
        key = (r % np.uint64(self.n_keys)).astype(np.int64)
        value = ((r >> np.uint64(8)) % np.uint64(1000)).astype(np.float64)
        return EventBlock(ts, key, value,
                          cols={"seq": seqs,
                                "tag": (r % np.uint64(3)).astype(np.int64)})

    def __call__(self, seq):
        blk = self.gen_block(np.array([seq], dtype=np.int64))
        return int(blk.ts[0]), int(blk.key[0]), float(blk.value[0])


def _random_pipeline(rng: random.Random):
    """A random fused chain (every step with a block form) + counting or
    summing window."""
    stages = []
    for _ in range(rng.randint(0, 3)):
        kind = rng.choice(["map", "filter", "rekey"])
        if kind == "map":
            mul = rng.randint(2, 5)
            stages.append(("map", block_form(
                lambda v, m=mul: v * m,
                lambda blk, m=mul: blk.value * m)))
        elif kind == "filter":
            mod, keep = rng.randint(2, 4), rng.randint(0, 1)
            stages.append(("filter", block_form(
                lambda v, m=mod, k=keep: int(v) % m != k,
                lambda blk, m=mod, k=keep:
                    blk.value.astype(np.int64) % m != k)))
        else:
            shift = rng.randint(1, 7)
            stages.append(("rekey", block_form(
                lambda v, s=shift: (int(v) + s) % 11,
                lambda blk, s=shift:
                    (blk.value.astype(np.int64) + s) % 11)))
    op_name = rng.choice(["count", "sum"])
    window = sliding(rng.choice([60, 100]), rng.choice([20, 50][:1]))
    return stages, op_name, window


_int_value = block_form(lambda ev: int(ev.value),
                        lambda blk: blk.value.astype(np.int64))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_randomized_pipeline_blocked_equals_scalar(seed):
    rng = random.Random(seed)
    stages, op_name, window = _random_pipeline(rng)
    skew = rng.choice([0, 30])
    lag = rng.choice([skew, max(0, skew - 20)])   # lag < skew => real drops
    rate, total = 40_000, 12_000
    gen = SyntheticBlockGen(rate, skew=skew, seed=seed + 10)

    def run(block_size):
        from repro.core.pipeline import KeyedStage
        cluster = JetCluster(n_nodes=1, cooperative_threads=2,
                             clock=VirtualClock(auto_step=0.001))
        out = []
        p = Pipeline.create()
        st = p.read_from(lambda: PacedGeneratorSource(
            gen, rate=rate, max_events=total, wm_lag=lag,
            block_size=block_size))
        for kind, fn in stages:
            st = getattr(st, kind)(fn)
        # window over whatever key is current (the generator's, or the
        # last rekey stage's) — KeyedStage without an extra rekey hop
        op = counting() if op_name == "count" else summing(_int_value)
        KeyedStage(p, st.stage).window(window).aggregate(op).write_to(
            lambda: CollectorSink(out))
        job = cluster.submit(p.to_dag())
        cluster.run_until_complete(job, max_steps=4_000_000)
        drops = sum(getattr(t.processor, "late_dropped", 0)
                    for t in job.execution.tasklets)
        return (sorted((ev.ts, ev.key, ev.value.window_end, ev.value.value)
                       for ev in out), drops)

    scalar, drops_s = run(0)
    blocked, drops_b = run(None)
    assert scalar == blocked
    assert drops_s == drops_b
    if lag < skew and skew:
        assert drops_s > 0, "test meant to exercise late drops"


@pytest.mark.parametrize("seed", [0, 1])
def test_randomized_pipeline_blocked_snapshot_restore(seed):
    """Randomized chain + window through exactly-once kill_node: blocked
    and scalar runs restore to identical results."""
    rng = random.Random(100 + seed)
    stages, op_name, window = _random_pipeline(rng)
    rate, total = 40_000, 16_000
    gen = SyntheticBlockGen(rate, seed=seed + 77)

    def run(block_size, kill):
        cluster = JetCluster(n_nodes=2, cooperative_threads=2,
                             clock=VirtualClock(auto_step=0.001))
        out = []
        p = Pipeline.create()
        st = p.read_from(lambda: PacedGeneratorSource(
            gen, rate=rate, max_events=total, block_size=block_size))
        for kind, fn in stages:
            st = getattr(st, kind)(fn)
        from repro.core.pipeline import KeyedStage
        op = counting() if op_name == "count" else summing(_int_value)
        KeyedStage(p, st.stage).window(window).aggregate(op).write_to(
            lambda: CollectorSink(out))
        job = cluster.submit(p.to_dag(), JobConfig(
            processing_guarantee=GUARANTEE_EXACTLY_ONCE,
            snapshot_interval_s=0.02))
        killed = False
        for _ in range(4_000_000):
            if job.status == JOB_COMPLETED:
                break
            cluster.step()
            if kill and not killed and job.snapshots_taken > 0 \
                    and len(out) >= 5:
                cluster.kill_node(cluster.node_ids[-1])
                killed = True
        assert job.status == JOB_COMPLETED
        assert not kill or killed
        return sorted(set((ev.ts, ev.key, ev.value.window_end,
                           ev.value.value) for ev in out))

    base = run(0, kill=False)
    assert run(0, kill=True) == base
    assert run(None, kill=True) == base
    assert len(base) > 0
