"""Event-time completeness (PR 3): session windows, allowed lateness, late
side outputs, and ordered-vs-disordered equivalence on the host engine.

The core property: a bounded-disorder stream produces IDENTICAL window
results to its sorted counterpart whenever the watermark lag covers the
skew — and events later than the allowed lateness are dropped deliberately,
exactly counted, and routed to the late side output when one is wired.
"""

import numpy as np
import pytest

from repro.core import (CollectorSink, GUARANTEE_EXACTLY_ONCE, JetCluster,
                        JobConfig, Journal, JournalSource, LateEvent,
                        PacedGeneratorSource, Pipeline, SessionResult,
                        VirtualClock, counting, session, sliding, summing,
                        tumbling)
from repro.core.engine import JOB_COMPLETED
from repro.core.events import Event, Watermark
from repro.core.processor import Inbox, Outbox, ProcessorContext
from repro.core.watermark import EventTimePolicy
from repro.core.window import (AccumulateByFrameProcessor,
                               SessionWindowProcessor, SessionWindowDef)
from repro.nexmark import DisorderedNexmarkGenerator, NexmarkGenerator, queries
from repro.nexmark.model import Bid


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def run_job(pipeline, n_nodes=1, threads=2):
    cluster = JetCluster(n_nodes=n_nodes, cooperative_threads=threads,
                         clock=VirtualClock())
    job = cluster.submit(pipeline.to_dag())
    cluster.run_until_complete(job)
    return job


def journal_of(events, n_partitions=1):
    """1 partition by default: the journal merge-read picks the min-ts head
    across partitions, which would partially re-sort a disordered stream."""
    j = Journal(n_partitions=n_partitions)
    j.extend(events)
    return j


def session_oracle(events, gap):
    """key -> list of (start, end, count): sort per key, split on gaps."""
    by_key = {}
    for ts, key, _v in events:
        by_key.setdefault(key, []).append(ts)
    out = {}
    for key, tss in by_key.items():
        tss.sort()
        sessions = []
        start, last = tss[0], tss[0]
        n = 1
        for ts in tss[1:]:
            if ts - last < gap:
                last, n = ts, n + 1
            else:
                sessions.append((start, last + gap, n))
                start, last, n = ts, ts, 1
        sessions.append((start, last + gap, n))
        out[key] = sessions
    return out


def sliding_count_oracle(events, size, slide):
    expect = {}
    for ts, key, _v in events:
        fw = (ts // slide + 1) * slide
        for w in range(fw, fw + size, slide):
            expect[(w, key)] = expect.get((w, key), 0) + 1
    return expect


# ---------------------------------------------------------------------------
# session windows: end-to-end correctness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_nodes", [1, 2])
def test_session_windows_match_oracle(n_nodes):
    rng = np.random.RandomState(3)
    events = []
    t = 0
    for _ in range(300):
        t += int(rng.randint(1, 40))
        events.append((t, int(rng.randint(0, 5)), 1))
    gap = 60
    out = []
    p = Pipeline.create()
    keyed = [(ts, k, k) for ts, k, _ in events]
    (p.read_from(lambda: JournalSource(journal_of(keyed, 4)), name="src")
       .with_key(lambda v: v)
       .window(session(gap))
       .aggregate(counting())
       .write_to(lambda: CollectorSink(out)))
    run_job(p, n_nodes)
    got = {}
    for ev in out:
        sr = ev.value
        assert isinstance(sr, SessionResult)
        got.setdefault(sr.key, []).append(
            (sr.window_start, sr.window_end, sr.value))
    oracle = session_oracle([(ts, k, k) for ts, k, _ in events], gap)
    for key in oracle:
        assert sorted(got[key]) == sorted(oracle[key]), key
    assert set(got) == set(oracle)


def test_session_results_emitted_incrementally_by_watermark():
    """A session closes when the watermark passes its end — the result must
    not wait for end-of-stream."""
    proc = SessionWindowProcessor(SessionWindowDef(10), counting())
    outbox = Outbox()
    proc.init(outbox, ProcessorContext("s", 0, 0, 1, 0, 1, ()))
    inbox = Inbox()
    inbox.extend([Event(0, "a", 1), Event(5, "a", 1), Event(40, "a", 1)])
    proc.process(0, inbox)
    assert proc.try_process_watermark(Watermark(30))
    emitted = outbox.drain()
    assert len(emitted) == 1
    sr = emitted[0].value
    assert (sr.window_start, sr.window_end, sr.value) == (0, 15, 2)
    # the open session at ts=40 flushes on complete
    assert proc.complete()
    tail = outbox.drain()
    assert [(e.value.window_start, e.value.window_end, e.value.value)
            for e in tail] == [(40, 50, 1)]


# ---------------------------------------------------------------------------
# the disorder equivalence property (the paper's out-of-order claim)
# ---------------------------------------------------------------------------


def _q5_windows(journal, wm_lag, window_ms=100, slide_ms=20):
    out = []
    p = queries.q5(lambda: JournalSource(journal, wm_lag=wm_lag),
                   lambda: CollectorSink(out),
                   window_ms=window_ms, slide_ms=slide_ms)
    run_job(p, n_nodes=2)
    return {(ev.value.window_end, ev.value.key): ev.value.value
            for ev in out}


@pytest.mark.parametrize("seed", [0, 1, 12345])
def test_q5_disordered_equals_ordered(seed):
    """Acceptance: Q5 on bounded-disorder input (skew <= watermark lag)
    produces identical window results to the ordered input."""
    rate = 10_000
    skew_ms = 40
    gen = NexmarkGenerator(rate=rate, n_keys=30)
    dis = DisorderedNexmarkGenerator(gen, max_skew_ms=skew_ms, seed=seed)
    n = 7 * dis.block              # whole blocks: exact permutation
    ordered = [gen(i) for i in range(n)]
    shuffled = [dis(i) for i in range(n)]
    assert sorted(map(repr, ordered)) == sorted(map(repr, shuffled)), \
        "bounded shuffle must be a permutation"
    assert ordered != shuffled, "disorder mode must actually disorder"
    # skew bound: event at emission slot i carries a timestamp at most
    # max_skew_ms behind the running maximum
    top = -1 << 60
    for ts, _k, _v in shuffled:
        assert top - ts <= skew_ms
        top = max(top, ts)
    got_o = _q5_windows(journal_of(ordered), wm_lag=0)
    got_d = _q5_windows(journal_of(shuffled), wm_lag=skew_ms)
    assert got_o == got_d
    assert got_o == sliding_count_oracle(
        [(ts, k, v) for ts, k, v in ordered if isinstance(v, Bid)], 100, 20)


@pytest.mark.parametrize("seed", [0, 7])
def test_q11_sessions_disordered_equals_ordered(seed):
    rate = 10_000
    skew_ms = 60
    gen = NexmarkGenerator(rate=rate, n_keys=10)
    dis = DisorderedNexmarkGenerator(gen, max_skew_ms=skew_ms, seed=seed)
    n = 4 * dis.block              # whole blocks: exact permutation
    ordered = [gen(i) for i in range(n)]
    shuffled = [dis(i) for i in range(n)]

    def run(events, lag):
        out = []
        p = queries.q11(lambda: JournalSource(journal_of(events),
                                              wm_lag=lag),
                        lambda: CollectorSink(out), gap_ms=25)
        run_job(p, n_nodes=2)
        return sorted((ev.value.key, ev.value.window_start,
                       ev.value.window_end, ev.value.value) for ev in out)

    got_o = run(ordered, 0)
    got_d = run(shuffled, skew_ms)
    assert got_o == got_d
    bids = [(v.ts, v.bidder, 1) for _t, _k, v in ordered
            if isinstance(v, Bid)]
    oracle = session_oracle(bids, 25)
    assert got_o == sorted((k, s, e, c) for k, ss in oracle.items()
                           for s, e, c in ss)


def test_paced_generator_disordered_equals_ordered():
    """Same property through the paced source (the benchmark datapath)."""
    rate = 50_000
    skew_ms = 10
    gen = NexmarkGenerator(rate=rate, n_keys=20)
    dis = DisorderedNexmarkGenerator(gen, max_skew_ms=skew_ms, seed=11)
    n = 3 * dis.block              # whole blocks: exact permutation

    def run(g, lag):
        out = []
        p = queries.q5(
            lambda: PacedGeneratorSource(g, rate=rate, max_events=n,
                                         wm_lag=lag),
            lambda: CollectorSink(out), window_ms=20, slide_ms=5)
        run_job(p)
        return {(ev.value.window_end, ev.value.key): ev.value.value
                for ev in out}

    assert run(gen, 0) == run(dis, skew_ms)


# ---------------------------------------------------------------------------
# allowed lateness: re-fires, deliberate drops, late side output
# ---------------------------------------------------------------------------


def _late_pipeline(events, wm_lag, lateness, late_out, out,
                   size=10, slide=10):
    p = Pipeline.create()
    (p.read_from(lambda: JournalSource(journal_of(events), wm_lag=wm_lag),
                 name="src")
       .with_key(lambda v: v[0])
       .window(sliding(size, slide))
       .allowed_lateness(lateness)
       .late_sink(lambda: CollectorSink(late_out))
       .aggregate(summing(lambda ev: ev.value[1]))
       .write_to(lambda: CollectorSink(out)))
    return p


def test_too_late_events_dropped_and_side_routed_exactly():
    # emission order: ts 5 and 20 open/close frame [0,10); 7 and 3 are then
    # 13+ behind the watermark (20) — too late for lateness 0
    events = [(5, "a", ("a", 1)), (20, "a", ("a", 2)), (7, "a", ("a", 4)),
              (3, "a", ("a", 8)), (25, "a", ("a", 16))]
    out, late_out = [], []
    run_job(_late_pipeline(events, 0, 0, late_out, out))
    got = {(ev.value.window_end, ev.value.key): ev.value.value for ev in out}
    # frame [0,10) fired with only the on-time event; late ones dropped
    assert got[(10, "a")] == 1
    assert got[(30, "a")] == 2 + 16
    late = sorted((ev.ts, ev.value[1]) for ev in late_out)
    assert late == [(3, 8), (7, 4)]
    assert all(isinstance(ev, LateEvent) for ev in late_out)


def test_admissible_late_event_refires_updated_window():
    # lateness 15 keeps frame [0,10) re-firable until wm >= 25
    events = [(5, "a", ("a", 1)), (20, "a", ("a", 2)), (7, "a", ("a", 4)),
              (40, "a", ("a", 8))]
    out, late_out = [], []
    # threads=1: with parallel accumulate instances the combiner's
    # COALESCED watermark lags the data, so the delta may merge before the
    # first firing (correct final value, fewer speculative firings) — a
    # single-instance topology makes the two-firing sequence deterministic
    run_job(_late_pipeline(events, 0, 15, late_out, out), threads=1)
    assert late_out == []
    fires = [ev.value.value for ev in out if ev.value.window_end == 10]
    # first firing without the late event, re-fire with it
    assert fires == [1, 5]
    # final state of every window is exact
    final = {}
    for ev in out:
        final[(ev.value.window_end, ev.value.key)] = ev.value.value
    assert final[(10, "a")] == 5
    assert final[(30, "a")] == 2
    assert final[(50, "a")] == 8


def test_session_late_drop_and_refire():
    gap, lateness = 15, 20
    # session [30,50) fires at wm=55; the late ts=40 (admissible: >= 55-20)
    # merges into the RETAINED emitted session and re-fires it extended to
    # [30,55) with the updated count; ts=5 is behind the lateness horizon
    events = [(30, "a", "a"), (35, "a", "a"), (55, "a", "a"),
              (40, "a", "a"), (90, "a", "a"), (5, "a", "a")]
    out, late_out = [], []
    p = Pipeline.create()
    (p.read_from(lambda: JournalSource(journal_of(events)), name="src")
       .with_key(lambda v: v)
       .window(session(gap))
       .allowed_lateness(lateness)
       .late_sink(lambda: CollectorSink(late_out))
       .aggregate(counting())
       .write_to(lambda: CollectorSink(out)))
    run_job(p)
    assert [(ev.ts, ev.value) for ev in late_out] == [(5, "a")]
    assert all(isinstance(ev, LateEvent) for ev in late_out)
    results = [(ev.value.window_start, ev.value.window_end, ev.value.value)
               for ev in out]
    assert (30, 50, 2) in results         # first firing, on time
    assert (30, 55, 3) in results         # re-fire: merged late event
    assert (55, 70, 1) in results         # 40 vs 55: separation == gap
    assert (90, 105, 1) in results
    assert len(results) == 4


def test_q5_late_drop_counts_exact_under_disorder_seed():
    """Acceptance: with a watermark lag SMALLER than the disorder skew,
    some events arrive behind the watermark — their count and identity
    must match an independent replay of the watermark policy exactly, and
    the window results must equal the oracle over the admitted events."""
    from repro.core.events import MIN_TIME as MINT

    rate, skew_ms, lag = 10_000, 80, 20
    gen = NexmarkGenerator(rate=rate, n_keys=15)
    dis = DisorderedNexmarkGenerator(gen, max_skew_ms=skew_ms, seed=5)
    n = 4 * dis.block
    emission = [dis(i) for i in range(n)]

    # independent oracle: walk the emission order replaying the policy
    # (the source observes EVERY event — the bid filter is fused after)
    policy = EventTimePolicy(lag=lag)
    wm = MINT
    dropped, admitted = [], []
    slide, size = 20, 100
    for ts, key, v in emission:
        if isinstance(v, Bid):
            fts = (ts // slide + 1) * slide
            if fts <= wm:
                dropped.append((ts, v.auction))
            else:
                admitted.append((ts, v.auction, v))
        new = policy.observe(ts)
        if new is not None:
            wm = new
    assert dropped, "scenario must actually produce late events"

    out, late_out = [], []
    p = Pipeline.create()
    (p.read_from(lambda: JournalSource(journal_of(emission),
                                       wm_lag=lag), name="bids")
       .filter(lambda v: isinstance(v, Bid))
       .with_key(lambda b: b.auction)
       .window(sliding(size, slide))
       .late_sink(lambda: CollectorSink(late_out))
       .aggregate(counting())
       .write_to(lambda: CollectorSink(out)))
    # single instance: the oracle's watermark replay assumes ONE source
    # subsequence (instances split the journal's partitions)
    run_job(p, n_nodes=1, threads=1)
    assert sorted((ev.ts, ev.key) for ev in late_out) == sorted(dropped)
    got = {(ev.value.window_end, ev.value.key): ev.value.value for ev in out}
    assert got == sliding_count_oracle(admitted, size, slide)


def test_late_frame_beyond_keys_max_frame_still_fires():
    """A key whose emission front was dragged ahead by OTHER keys'
    activity receives an admissible late frame newer than anything it has
    seen: the window fired empty, so the result must come out as a
    re-fire, not be swallowed by the last_emitted guard."""
    events = [(5, "b", ("b", 1)),     # b: frame [0,10)
              (50, "a", ("a", 1)),    # wm -> 50; windows <= 50 fire
              (35, "b", ("b", 1)),    # admissible (>= 50-30), frame [30,40)
              (70, "a", ("a", 1))]    # wm -> 70; flush the late delta
    out, late_out = [], []
    run_job(_late_pipeline(events, 0, 30, late_out, out), threads=1)
    assert late_out == []
    got = {(ev.value.window_end, ev.value.key): ev.value.value for ev in out}
    assert got[(40, "b")] == 1
    assert got[(10, "b")] == 1


def test_watermark_not_swallowed_by_backpressured_late_event():
    """A watermark arriving while a backpressured LateEvent sits in the
    emit buffer must still close its frames (regression: the old buffer
    guard drained and returned True, forwarding the watermark AHEAD of the
    frames it closes — with lateness 0 those counts were lost)."""
    from repro.core.window import tumbling as _tumbling

    proc = AccumulateByFrameProcessor(_tumbling(10), counting(),
                                      late_output=True)
    outbox = Outbox(batch_limit=1)
    proc.init(outbox, ProcessorContext("a", 0, 0, 1, 0, 1, ()))
    ib = Inbox()
    ib.extend([Event(5, "k", 1)])
    proc.process(0, ib)
    assert proc.try_process_watermark(Watermark(30))
    outbox.drain()
    # two too-late events: the first fills the 1-slot outbox, the second
    # lands in the emit buffer; plus one fresh on-time event
    ib.extend([Event(2, "k", 1), Event(3, "k", 1), Event(35, "k", 1)])
    proc.process(0, ib)
    assert proc.late_dropped == 2
    drained = outbox.drain()
    done = proc.try_process_watermark(Watermark(50))
    drained += outbox.drain()
    while not done:
        done = proc.try_process_watermark(Watermark(50))
        drained += outbox.drain()
    assert proc._last_wm == 50
    closed = [ev.value for ev in drained
              if not isinstance(ev, LateEvent)]
    assert closed == [(40, 1)], closed   # frame [30,40) closed at wm 50
    assert sorted(ev.ts for ev in drained
                  if isinstance(ev, LateEvent)) == [2, 3]


def test_accumulate_processor_counts_late_drops():
    proc = AccumulateByFrameProcessor(tumbling(10), counting())
    proc.init(Outbox(), ProcessorContext("a", 0, 0, 1, 0, 1, ()))
    inbox = Inbox()
    inbox.extend([Event(5, "k", 1)])
    proc.process(0, inbox)
    assert proc.try_process_watermark(Watermark(30))
    inbox.extend([Event(7, "k", 1), Event(3, "k", 1), Event(35, "k", 1)])
    proc.process(0, inbox)
    assert proc.late_dropped == 2


# ---------------------------------------------------------------------------
# sessions x exactly-once: snapshot -> node failure -> restore
# ---------------------------------------------------------------------------


def test_session_windows_exactly_once_after_node_failure():
    rng = np.random.RandomState(9)
    events = []
    t = 0
    for _ in range(400):
        t += int(rng.randint(1, 12))
        events.append((t, int(rng.randint(0, 5)), 1))
    gap = 30
    out = []
    journal = Journal(n_partitions=8)
    journal.extend((ts, k, k) for ts, k, _ in events)
    p = Pipeline.create()
    (p.read_from(lambda: JournalSource(journal, rate=150.0), name="src")
       .with_key(lambda v: v)
       .window(session(gap))
       .aggregate(counting())
       .write_to(lambda: CollectorSink(out)))
    cluster = JetCluster(n_nodes=3, cooperative_threads=2,
                         clock=VirtualClock(auto_step=0.01))
    job = cluster.submit(p.to_dag(),
                         JobConfig(processing_guarantee=GUARANTEE_EXACTLY_ONCE,
                                   snapshot_interval_s=0.05))
    for _ in range(20000):
        cluster.step()
        if job.snapshots_taken >= 1:
            break
    assert job.snapshots_taken >= 1, "no snapshot committed before failure"
    cluster.kill_node(1)
    cluster.run_until_complete(job)
    oracle = session_oracle([(ts, k, k) for ts, k, _ in events], gap)
    expect = {(k, s, e): c for k, ss in oracle.items() for s, e, c in ss}
    got = {}
    for ev in out:
        sr = ev.value
        key = (sr.key, sr.window_start, sr.window_end)
        # exactly-once state: every emission carries the exact count
        # (results between last snapshot and failure re-emit identically)
        assert expect[key] == sr.value, key
        got[key] = sr.value
    assert got == expect


# ---------------------------------------------------------------------------
# Q12: processing-time windows
# ---------------------------------------------------------------------------


def test_q12_processing_time_windows_count_all_bids():
    n = 2000
    gen = NexmarkGenerator(rate=10_000, n_keys=25)
    events = [gen(i) for i in range(n)]
    out = []
    p = queries.q12(lambda: JournalSource(journal_of(events, 8)),
                    lambda: CollectorSink(out), window_ms=50)
    run_job(p, n_nodes=2)
    n_bids = sum(1 for _t, _k, v in events if isinstance(v, Bid))
    per_key = {}
    for ev in out:
        fend, key, count = ev.value
        per_key[key] = per_key.get(key, 0) + count
    # processing-time windows partition arrivals: totals must be exact
    assert sum(per_key.values()) == n_bids
    oracle_keys = {v.bidder for _t, _k, v in events if isinstance(v, Bid)}
    assert set(per_key) == oracle_keys


# ---------------------------------------------------------------------------
# disordered generator unit properties
# ---------------------------------------------------------------------------


def test_disordered_generator_is_deterministic():
    gen = NexmarkGenerator(rate=5000, n_keys=10)
    a = DisorderedNexmarkGenerator(gen, max_skew_ms=50, seed=42)
    b = DisorderedNexmarkGenerator(gen, max_skew_ms=50, seed=42)
    c = DisorderedNexmarkGenerator(gen, max_skew_ms=50, seed=43)
    def key_of(t):
        ts, key, value = t
        return (ts, key, repr(value))  # model values compare by identity

    xs = [key_of(a(i)) for i in range(1000)]
    # random access (replay from an offset) agrees with sequential access
    assert [key_of(b(i)) for i in range(999, -1, -1)][::-1] == xs
    assert [key_of(c(i)) for i in range(1000)] != xs
