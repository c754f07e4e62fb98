"""The benchmark's yardstick on the CPU: histogram, stream, reference,
comparison, trace reduction and roofline arithmetic."""

import numpy as np
import pytest

from bench import check, roofline, trace as tracing
from bench.histogram import LatencyHistogram
from bench.nexmark import KIND_BID, NexmarkStream, bids_between
from bench.queries import q5

BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("samples", [
    np.arange(1, 100_001, dtype=np.float64),
    np.random.default_rng(3).lognormal(9.0, 1.2, 50_000),
    np.full(1000, 77.0),
])
@pytest.mark.parametrize("pct", [0.0, 50.0, 99.0, 99.99, 100.0])
def test_histogram_percentile_matches_numpy(samples, pct):
    h = LatencyHistogram()
    h.record(samples[: len(samples) // 2])
    h.record(samples[len(samples) // 2:])
    assert h.total == len(samples)
    exact = float(np.quantile(np.floor(samples), pct / 100.0,
                              method="inverted_cdf"))
    got = h.percentile(pct)
    # upper-edge biased, within one sub-bucket (1/128) of the value
    assert exact <= got <= exact * (1 + 1 / 64) + 1


def test_histogram_is_exact_below_the_first_bucket():
    h = LatencyHistogram()
    h.record([5, 7, 7, 100])
    assert h.percentile(50) == 7.0 and h.percentile(100) == 100.0


def test_stream_scalar_row_equals_columns():
    from bench.feed import Feed
    stream = NexmarkStream(rate=123_456, n_keys=997, seed=BIG_SEED)
    feed = Feed(stream)
    seqs = np.arange(0, 400)
    blk = feed.gen_block(seqs)
    for s in seqs.tolist():
        ts, key, value = feed.row(s)
        assert ts == blk.ts[s] and key == blk.key[s]
        if blk.cols["kind"][s] == KIND_BID:
            assert value.price == blk.value[s]
            assert value.bidder == blk.cols["bidder"][s]


def test_bids_between_counts_the_mix():
    kind = NexmarkStream(1000, 10, 0).columns(np.arange(0, 1234))["kind"]
    for lo, hi in [(0, 1234), (3, 4), (4, 5), (17, 1001), (50, 100)]:
        assert bids_between(lo, hi) == int((kind[lo:hi] == KIND_BID).sum())


@pytest.mark.parametrize("window_ms,slide_ms", [(100, 20), (60, 60)])
def test_reference_equals_brute_force(window_ms, slide_ms):
    cfg = {"window_ms": window_ms, "slide_ms": slide_ms, "aggregate": "count"}
    stream = NexmarkStream(rate=1000, n_keys=7, seed=BIG_SEED)
    n_ends = 12
    totals = q5.reference(cfg, stream, n_ends)
    c = stream.columns(np.arange(0, 2000))
    for i in range(n_ends):
        end = (i + 1) * slide_ms
        for k in range(7):
            sel = ((c["kind"] == KIND_BID) & (c["key"] == k)
                   & (c["ts"] >= end - window_ms) & (c["ts"] < end))
            assert totals[i, k] == sel.sum()


def test_compare_counts_each_fault():
    totals = np.array([[1, 0, 2], [3, 4, 0]])            # ends 10, 20
    cols = {"end": np.array([10, 10, 20, 20, 20, 15, 10]),
            "key": np.array([0, 2, 0, 1, 1, 0, 1]),
            "value": np.array([1.0, 2.0, 3.0, 5.0, 4.0, 1.0, 9.0])}
    got = check.compare(cols, totals, slide_ms=10, due_end=20)
    # (20,1) holds 5 not 4; (15,..) is off the grid; (10,1) has no total;
    # (20,1) twice
    assert got == {"wrong_values": 1, "missing": 0, "extra": 3}
    assert not check.verdict(got)
    cols = {k: v[[0, 1]] for k, v in cols.items()}
    assert check.compare(cols, totals, 10, 20) == {
        "wrong_values": 0, "missing": 2, "extra": 0}
    assert check.verdict(check.compare(cols, totals, 10, 10))


def test_compare_top_counts_each_fault():
    # ends 10, 20, 30: highest 4 (keys 1, 2 tied), 3 (key 0), none
    totals = np.array([[1, 4, 4], [3, 0, 2], [0, 0, 0]])
    good = {"end": np.array([10, 20]), "key": np.array([2, 0]),
            "value": np.array([4.0, 3.0])}
    assert check.compare_top(good, totals, 10, 30) == {
        "wrong_values": 0, "missing": 0, "extra": 0}
    cols = {"end": np.array([10, 10, 20, 30, 25]),
            "key": np.array([0, 1, 0, 0, 0]),
            "value": np.array([4.0, 4.0, 2.0, 1.0, 3.0])}
    # (10, 0) does not hold the highest; (20, 0) holds 3, not 2; end 10
    # answered twice; end 30 has no bids; 25 is off the grid
    assert check.compare_top(cols, totals, 10, 30) == {
        "wrong_values": 2, "missing": 0, "extra": 3}
    assert check.compare_top({k: v[:0] for k, v in good.items()},
                             totals, 10, 30)["missing"] == 2


@pytest.mark.parametrize("hot_items", [False, True])
def test_answers_of_the_reference_pass_its_own_comparison(hot_items):
    cfg = {"window_ms": 100, "slide_ms": 20, "aggregate": "count",
           "hot_items": hot_items}
    stream = NexmarkStream(rate=1000, n_keys=7, seed=BIG_SEED)
    totals = q5.reference(cfg, stream, 12)
    rows = np.argwhere(totals) if not hot_items else \
        np.stack([np.arange(12), np.zeros(12, int)], axis=1)
    end = (rows[:, 0] + 1) * 20
    key, value = q5.answers(cfg, totals, end, rows[:, 1])
    cols = {"end": end, "key": key, "value": value.astype(np.float64)}
    assert check.verdict(q5.compare(cfg, cols, totals, 12 * 20))
    cols["value"] = cols["value"] + 1
    assert not check.verdict(q5.compare(cfg, cols, totals, 12 * 20))


def test_trace_reduction_on_a_hand_built_trace():
    ops = [(10, 20, "fusion.1"), (15, 30, "copy"), (50, 60, "fusion.1"),
           (95, 130, "fusion.2")]
    assert tracing.merge(ops) == [(10, 30), (50, 60), (95, 130)]
    assert tracing.busy_ns(ops, 0, 100) == 20 + 10 + 5
    assert tracing.idle_gaps(ops, 0, 100) == [(0, 10), (30, 50), (60, 95)]
    assert tracing.op_seconds(ops, 0, 100) == pytest.approx(
        {"fusion.1": 20e-9, "copy": 15e-9, "fusion.2": 5e-9})
    host = [(0, 40, "bids"), (40, 100, "win.device"), (28, 52, "sink")]
    assert tracing.label((30, 50), host) == "sink"
    assert tracing.label((60, 95), host) == "win.device"
    assert tracing.label((200, 210), host) == "host.other"
    mods = [(5, 25, "jit_step1"), (50, 60, "jit_other"), (95, 130,
                                                          "jit_step1")]
    assert tracing.executions(mods, "jit_step", 0, 100) == [
        (5, 25, "jit_step1"), (95, 130, "jit_step1")]


def test_roofline_bytes_and_peaks():
    assert roofline.step_bytes(events=10, window_ends=2, n_keys=5) == \
        10 * 13 + 2 * 5 * 4
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_settle_waits_until_the_sink_catches_up(monkeypatch):
    from bench import harness

    class Clock:
        t = 100.0

    class Cols:
        def frontier(self):
            return int((Clock.t - 100.0) * 1000.0) - lag[0]

    class Feed:
        def anchor(self):
            return 100.0

    class Cluster:
        def step(self):
            Clock.t += 0.001
            lag[0] = max(lag[0] - 5, 20)

    class Job:
        status = "RUNNING"

    lag = [400]
    monkeypatch.setattr(harness.time, "monotonic", lambda: Clock.t)
    harness._settle(Cluster(), Job(), Cols(), Feed(), settle_ms=100)
    assert lag[0] < 100
    assert Clock.t - 100.0 < 1.0
