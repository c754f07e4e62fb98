"""The top-1 emission form: each window end reduced to its hot item on the
device.

The device step's ``"top1"`` form must give, row for row, the maximum and
the lowest ``argmax`` bucket of what the per-key form emits, leave the
per-key form as it was, and be refused where it cannot hold (SPMD steps,
sums).  Through the bridge, device-placed NEXMark Q5 with its hot-items
stage must answer every window end with the host plan's highest count and
an auction that holds it, with one or two window instances, through a
node kill, and count each partial it sends in ``reduced_windows``; the
per-key form counts each result it sends in ``key_results``.
"""

import jax
import numpy as np
import pytest

from repro.core import (CollectorSink, DeviceWindowProcessor, JetCluster,
                        JobConfig, PacedGeneratorSource, VirtualClock,
                        GUARANTEE_EXACTLY_ONCE, counting, summing)
from repro.core.engine import JOB_COMPLETED
from repro.core.window import SlidingWindowDef
from repro.nexmark import NexmarkGenerator, queries
from repro.streaming import (StreamExecutor, StreamJobConfig,
                             VectorWindowSpec)
from repro.streaming.window import emit

# ---------------------------------------------------------------------------
# The device step
# ---------------------------------------------------------------------------

SIZE, SLIDE, K = 40, 10, 16


def _spec(emission):
    # 2 windows a round, 2 rounds a step: a 4-row buffer that a long
    # backlog fills, so emission takes several steps and rounds
    return VectorWindowSpec(size_ms=SIZE, slide_ms=SLIDE, n_key_buckets=K,
                            max_windows_per_step=2, ring_margin=6,
                            emit_rounds=2, frontier_from_data=False,
                            emission=emission)


def _random_state(seed):
    """Panes of small integer counts (ties are common) in a random subset
    of consecutive frames (windows with no live frame occur), with the
    watermark past them all."""
    spec = _spec("per_key")
    R = spec.ring_len
    rng = np.random.default_rng(seed)
    base = int(rng.integers(0, 50))
    frames = np.sort(rng.choice(np.arange(base, base + R),
                                size=int(rng.integers(2, R)), replace=False))
    panes = np.zeros((R, K), np.float32)
    slot_frame = np.full(R, -1, np.int32)
    for f in frames:
        panes[f % R] = rng.integers(0, 3, K)
        slot_frame[f % R] = f
    return {"panes": panes, "slot_frame": slot_frame,
            "watermark": np.int32((base + R + SIZE // SLIDE) * SLIDE),
            "next_emit": np.int32(-1), "dropped_late": np.int32(0),
            "dropped_conflict": np.int32(0)}


def _emit_until_caught_up(emission, state):
    spec = _spec(emission)
    step = jax.jit(lambda st: emit(spec, st))
    outs = []
    for _ in range(40):
        state, out = step(state)
        outs.append({k: np.asarray(v) for k, v in out.items()})
        if not outs[-1]["valid"].any():
            break
    return {k: np.asarray(v) for k, v in state.items()}, outs


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_top1_rows_are_the_max_and_lowest_argmax_of_per_key_rows(seed):
    state = _random_state(seed)
    st_k, per_key = _emit_until_caught_up("per_key", state)
    st_1, top1 = _emit_until_caught_up("top1", state)
    # the state the two forms leave is the same, step for step
    assert len(per_key) == len(top1) > 2
    for k in st_k:
        np.testing.assert_array_equal(st_k[k], st_1[k])
    rows = ties = empty = 0
    for pk, t1 in zip(per_key, top1):
        valid = pk["valid"]
        np.testing.assert_array_equal(valid, t1["valid"])
        assert t1["top"].dtype == np.int32
        assert t1["top"].shape == (3, _spec("top1").emit_buffer_rows)
        ends, bucket, count = t1["top"]
        np.testing.assert_array_equal(ends, np.where(valid,
                                                     pk["window_ends"], -1))
        res = pk["results"][valid]
        np.testing.assert_array_equal(bucket[valid], res.argmax(axis=1))
        np.testing.assert_array_equal(count[valid], res.max(axis=1))
        rows += int(valid.sum())
        ties += int(np.sum((res == res.max(axis=1, keepdims=True))
                           .sum(axis=1) > 1))
        empty += int(np.sum(res.max(axis=1) == 0))
    # the cases the test is for did occur: tied maxima, and window ends
    # with no live frame (all-zero rows, count 0)
    assert rows > 4 and ties > 0 and empty > 0


def test_per_key_form_emits_every_buckets_window_sum():
    """The per-key form (the default) against a numpy reference of the
    window sums: keys, shapes and values as before."""
    state = _random_state(7)
    assert VectorWindowSpec(SIZE, SLIDE).emission == "per_key"
    _st, outs = _emit_until_caught_up("per_key", state)
    F, R = SIZE // SLIDE, _spec("per_key").ring_len
    seen = []
    for out in outs:
        assert set(out) == {"results", "window_ends", "valid"}
        assert out["results"].shape == (_spec("per_key").emit_buffer_rows, K)
        for i in np.nonzero(out["valid"])[0]:
            w_end = int(out["window_ends"][i])
            last = w_end // SLIDE - 1
            want = np.zeros(K, np.float32)
            for s in range(R):
                f = state["slot_frame"][s]
                if f >= 0 and last - F < f <= last:
                    want += state["panes"][s]
            np.testing.assert_array_equal(out["results"][i], want)
            seen.append(w_end)
    assert seen == sorted(set(seen)) and len(seen) > 4


@pytest.mark.parametrize("exchange", ["reduce", "route"])
def test_spmd_builders_refuse_the_top1_form(exchange):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    cfg = StreamJobConfig(window=_spec("top1"), batch_size=64,
                          exchange=exchange)
    with pytest.raises(ValueError, match="single device"):
        StreamExecutor(cfg, mesh=mesh)
    # the per-key form still builds there
    StreamExecutor(StreamJobConfig(window=_spec("per_key"), batch_size=64,
                                   exchange=exchange), mesh=mesh)


def test_summing_and_unknown_forms_are_refused():
    wdef = SlidingWindowDef(100, 10)
    with pytest.raises(ValueError, match="counting"):
        DeviceWindowProcessor(wdef, summing(queries.bid_price),
                              emission="top1")
    DeviceWindowProcessor(wdef, counting(), emission="top1")
    with pytest.raises(ValueError, match="emission form"):
        VectorWindowSpec(SIZE, SLIDE, emission="top2")


# ---------------------------------------------------------------------------
# Through the bridge: NEXMark Q5 with its hot-items stage
# ---------------------------------------------------------------------------

#: (rate, events, window_ms, slide_ms): dense windows of thousands of
#: bids, and sparse ones of a few bids, where one instance may see none
STREAMS = {"dense": (60_000, 24_000, 100, 20),
           "sparse": (300, 900, 20, 10)}


def _run_q5(placement, stream, with_global_max=True, threads=2,
            n_nodes=1, guarantee="none", kill_at_result=None):
    rate, total, window_ms, slide_ms = STREAMS[stream]
    gen = NexmarkGenerator(rate=rate, n_keys=40)
    cluster = JetCluster(n_nodes=n_nodes, cooperative_threads=threads,
                         clock=VirtualClock(auto_step=0.001))
    out = []
    p = queries.q5(
        lambda: PacedGeneratorSource(gen, rate=rate, max_events=total),
        lambda: CollectorSink(out), window_ms=window_ms, slide_ms=slide_ms,
        with_global_max=with_global_max, placement=placement,
        device=dict(n_key_buckets=64, batch_size=256))
    job = cluster.submit(p.to_dag(), JobConfig(
        processing_guarantee=guarantee, snapshot_interval_s=0.02))
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
    assert killed == (kill_at_result is not None)
    return job, out


def _per_key_counts(stream):
    _job, out = _run_q5("host", stream, with_global_max=False)
    return {(ev.value.window_end, ev.value.key): ev.value.value
            for ev in out}


def _check_answers(answers, counts):
    """Every window end with a bid answered with its highest count, by an
    auction that holds it; returns the answered window ends."""
    best = {}
    for (w, _a), c in counts.items():
        best[w] = max(best.get(w, 0), c)
    got = {}
    for w, a, c in answers:
        assert counts.get((w, a)) == c, (w, a, c)
        got.setdefault(w, set()).add(c)
    assert got == {w: {c} for w, c in best.items()}
    return set(got)


def _devices(job):
    return [t.processor for t in job.execution.tasklets
            if isinstance(t.processor, DeviceWindowProcessor)]


@pytest.mark.parametrize("stream,threads", [("dense", 1), ("dense", 2),
                                            ("sparse", 1), ("sparse", 2)])
def test_q5_hot_items_device_equals_host(stream, threads):
    counts = _per_key_counts(stream)
    _hjob, host = _run_q5("host", stream, threads=threads)
    job, dev = _run_q5("device", stream, threads=threads)
    host_ends = _check_answers([ev.value for ev in host], counts)
    dev_answers = [ev.value for ev in dev]
    assert _check_answers(dev_answers, counts) == host_ends
    assert len(dev_answers) == len(host_ends) > 10    # once per window end
    procs = _devices(job)
    assert len(procs) == threads
    assert all(p.emission == "top1" for p in procs)
    # each instance sends one partial per window end it saw bids in
    partials = sum(p.telemetry()["reduced_windows"] for p in procs)
    assert job.execution.stats()["reduced_windows"] == partials
    if threads == 1:
        assert partials == len(host_ends)
    else:
        assert len(host_ends) < partials <= 2 * len(host_ends)
        if stream == "sparse":
            # window ends in which one of the two instances saw no bids
            assert partials < 2 * len(host_ends)


def test_reduced_windows_counts_each_window_end_emitted():
    """One instance: one partial per window end the per-key form emits;
    the per-key form itself reduces nothing and sends every count."""
    job, out = _run_q5("device", "sparse", threads=1)
    p, = _devices(job)
    job_k, out_k = _run_q5("device", "sparse", with_global_max=False,
                           threads=1)
    pk, = _devices(job_k)
    ends = {ev.value.window_end for ev in out_k}
    assert p.telemetry()["reduced_windows"] == len(ends) == len(out) > 20
    assert pk.emission == "per_key"
    assert pk.telemetry()["reduced_windows"] == 0
    assert len(out_k) > len(ends)
    # a step that emitted copied back its mask and the packed (3, rows)
    rows = p._spec.emit_buffer_rows
    tel = p.telemetry()
    assert tel["readback_bytes"] == \
        p.steps * rows + tel["emitting_steps"] * 3 * rows * 4


@pytest.mark.parametrize("threads", [1, 2])
def test_key_results_counts_every_per_key_result_sent(threads):
    """The per-key form: ``key_results`` is what the sink received, one
    result per nonzero (window end, auction) count of the host plan."""
    counts = _per_key_counts("dense")
    job, out = _run_q5("device", "dense", with_global_max=False,
                       threads=threads)
    procs = _devices(job)
    assert len(procs) == threads
    assert all(p.emission == "per_key" for p in procs)
    sent = sum(p.telemetry()["key_results"] for p in procs)
    assert job.execution.stats()["key_results"] == sent == len(out)
    ends = {ev.value.window_end for ev in out}
    due = {wk: c for wk, c in counts.items() if wk[0] in ends}
    got = {(ev.value.window_end, ev.value.key): ev.value.value for ev in out}
    assert got == due and sent == len(due) > 500
    assert all(p.telemetry()["reduced_windows"] == 0 for p in procs)


@pytest.mark.parametrize("threads", [1, 2])
def test_key_results_is_zero_in_the_top1_form(threads):
    job, out = _run_q5("device", "sparse", threads=threads)
    procs = _devices(job)
    assert all(p.emission == "top1" for p in procs) and len(out) > 20
    assert [p.telemetry()["key_results"] for p in procs] == [0] * threads
    assert job.execution.stats()["key_results"] == 0


def test_q5_hot_items_device_through_kill_node():
    """A snapshot taken mid-stream (step-boundary aligned) and restored
    after a node kill gives the same answers as the run without one."""
    counts = _per_key_counts("dense")
    _job, base = _run_q5("device", "dense", n_nodes=2)
    base_ends = _check_answers([ev.value for ev in base], counts)
    _job, out = _run_q5("device", "dense", n_nodes=2,
                        guarantee=GUARANTEE_EXACTLY_ONCE, kill_at_result=3)
    assert _check_answers([ev.value for ev in out], counts) == base_ends
