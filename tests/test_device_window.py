"""Device window semantics + the host→device bridge (PR 5).

Covers the device-tier bugfixes — Pallas kernel padding for
non-tile-multiple shapes, ``wm_lag`` bounded-out-of-orderness on the
vectorized window, emission catch-up across watermark jumps — and the
bridged vertex: NEXMark Q5 through ``aggregate(..., placement="device")``
must be indistinguishable from the host two-stage plan, ordered and
disordered, including exactly-once through ``kill_node`` with the device
state travelling in the snapshot.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CollectorSink, JetCluster, JobConfig,
                        PacedGeneratorSource, Pipeline, VirtualClock,
                        GUARANTEE_EXACTLY_ONCE, counting, session, sliding,
                        summing)
from repro.core.engine import JOB_COMPLETED
from repro.kernels import ops, ref
from repro.nexmark import (DisorderedNexmarkGenerator, NexmarkGenerator,
                           queries)
from repro.streaming import (StreamExecutor, StreamJobConfig,
                             VectorWindowSpec)


# ---------------------------------------------------------------------------
# Bugfix: window_agg kernel pads instead of asserting on non-tile shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k,r", [(1000, 100, 8), (1500, 200, 5),
                                   (3, 7, 4), (1025, 129, 3)])
def test_window_agg_kernel_pads_non_tile_shapes(n, k, r):
    rng = np.random.RandomState(n + k)
    keys = jnp.asarray(rng.randint(0, k, n), jnp.int32)
    slots = jnp.asarray(rng.randint(0, r, n), jnp.int32)
    vals = jnp.asarray(rng.randn(n), jnp.float32)
    valid = jnp.asarray(rng.rand(n) > 0.2)
    got = ops.window_agg(keys, slots, vals, valid, k, r)
    want = ref.window_agg_ref(keys, slots, vals, valid, k, r)
    assert got.shape == (k, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_window_agg_kernel_empty_batch():
    got = ops.window_agg(jnp.zeros((0,), jnp.int32),
                         jnp.zeros((0,), jnp.int32),
                         jnp.zeros((0,), jnp.float32),
                         jnp.zeros((0,), bool), 100, 4)
    assert got.shape == (100, 4) and float(jnp.sum(got)) == 0.0


# ---------------------------------------------------------------------------
# Bugfix: wm_lag on the vectorized window (device-tier disorder equivalence)
# ---------------------------------------------------------------------------


def _drive(ts, keys, vals, spec, B=64, flush_ts=4000):
    ex = StreamExecutor(StreamJobConfig(window=spec, batch_size=B))
    st = ex.init_state()
    got = {}

    def harvest(out):
        v = np.asarray(out["valid"])
        e = np.asarray(out["window_ends"])
        r = np.asarray(out["results"])
        for i in np.nonzero(v)[0]:
            for k in np.nonzero(r[i])[0]:
                got[(int(e[i]), int(k))] = got.get(
                    (int(e[i]), int(k)), 0) + float(r[i][k])

    n = len(ts)
    for i in range(0, n, B):
        sl = slice(i, i + B)
        m = len(ts[sl])
        pad = B - m
        batch = {"ts": jnp.asarray(np.pad(ts[sl], (0, pad))),
                 "key": jnp.asarray(np.pad(keys[sl], (0, pad))),
                 "value": jnp.asarray(np.pad(vals[sl], (0, pad))),
                 "valid": jnp.asarray(np.pad(np.ones(m, bool), (0, pad))),
                 "wm": jnp.asarray(-1, jnp.int32)}
        st, out = ex.step(st, batch)
        harvest(out)
    for _ in range(8):
        batch = {"ts": jnp.zeros((B,), jnp.int32),
                 "key": jnp.zeros((B,), jnp.int32),
                 "value": jnp.zeros((B,), jnp.float32),
                 "valid": jnp.zeros((B,), bool),
                 "wm": jnp.asarray(flush_ts, jnp.int32)}
        st, out = ex.step(st, batch)
        harvest(out)
    return st, got


def _oracle(ts, keys, vals, size, slide):
    out = {}
    for t, k, v in zip(ts.tolist(), keys.tolist(), vals.tolist()):
        f = t // slide
        for L in range(f, f + size // slide):
            out[((L + 1) * slide, k)] = out.get(((L + 1) * slide, k), 0) + v
    return out


def test_device_wm_lag_disorder_equivalence():
    """Ordered vs cross-batch-disordered input with wm_lag >= max skew:
    identical window results, zero drops — the host tier's disorder
    guarantee, now held by the device tier."""
    rng = np.random.RandomState(1)
    n, skew = 800, 50
    ts = np.sort(rng.randint(0, 600, n)).astype(np.int32)
    keys = rng.randint(0, 32, n).astype(np.int32)
    vals = np.ones(n, np.float32)
    order = np.argsort(ts + rng.randint(0, skew, n), kind="stable")
    spec = VectorWindowSpec(size_ms=100, slide_ms=10, n_key_buckets=32,
                            max_windows_per_step=4, ring_margin=8,
                            wm_lag=skew)
    st_o, got_o = _drive(ts, keys, vals, spec)
    st_d, got_d = _drive(ts[order], keys[order], vals[order], spec)
    assert got_o == _oracle(ts, keys, vals, 100, 10)
    assert got_o == got_d
    for st in (st_o, st_d):
        assert int(st["dropped_late"]) == 0
        assert int(st["dropped_conflict"]) == 0


def test_device_without_wm_lag_drops_disordered():
    """Sanity: the same disorder WITHOUT the lag does drop events late —
    the allowance is what provides the guarantee, not accident."""
    rng = np.random.RandomState(1)
    n, skew = 800, 50
    ts = np.sort(rng.randint(0, 600, n)).astype(np.int32)
    keys = rng.randint(0, 32, n).astype(np.int32)
    vals = np.ones(n, np.float32)
    order = np.argsort(ts + rng.randint(0, skew, n), kind="stable")
    spec = VectorWindowSpec(size_ms=100, slide_ms=10, n_key_buckets=32,
                            max_windows_per_step=4, ring_margin=8)
    st_d, got_d = _drive(ts[order], keys[order], vals[order], spec)
    assert (int(st_d["dropped_late"]) > 0
            or got_d != _oracle(ts, keys, vals, 100, 10))


# ---------------------------------------------------------------------------
# Bugfix: emission catches up across watermark jumps (idle then burst)
# ---------------------------------------------------------------------------


def _one_key_batch(ts_list, B=32, wm=-1):
    m = len(ts_list)
    pad = B - m
    return {"ts": jnp.asarray(np.pad(np.asarray(ts_list, np.int32),
                                     (0, pad))),
            "key": jnp.asarray(np.zeros(B, np.int32)),
            "value": jnp.asarray(np.pad(np.ones(m, np.float32), (0, pad))),
            "valid": jnp.asarray(np.pad(np.ones(m, bool), (0, pad))),
            "wm": jnp.asarray(wm, jnp.int32)}


def test_emit_catches_up_after_idle_then_burst():
    """A watermark heartbeat jump of thousands of windows (idle source,
    then a burst) used to leave ``next_emit`` permanently behind and bleed
    every subsequent event into ``dropped_conflict``; the bounded
    emission loop + empty-window fast-forward absorbs it in one step."""
    spec = VectorWindowSpec(size_ms=40, slide_ms=10, n_key_buckets=16,
                            max_windows_per_step=2, ring_margin=2)
    ex = StreamExecutor(StreamJobConfig(window=spec, batch_size=32))
    st = ex.init_state()
    got = {}

    def harvest(out):
        v = np.asarray(out["valid"])
        e = np.asarray(out["window_ends"])
        r = np.asarray(out["results"])
        for i in np.nonzero(v)[0]:
            for k in np.nonzero(r[i])[0]:
                got[(int(e[i]), int(k))] = got.get(
                    (int(e[i]), int(k)), 0) + float(r[i][k])

    st, out = ex.step(st, _one_key_batch([5, 7, 12]))
    harvest(out)
    # idle gap: one heartbeat jumps the watermark 10_000 windows ahead
    st, out = ex.step(st, _one_key_batch([], wm=100_000))
    harvest(out)
    assert int(st["next_emit"]) > 100_000   # front caught up in ONE step
    # burst after the gap: nothing may conflict or drop
    st, out = ex.step(st, _one_key_batch([100_005, 100_013, 100_017]))
    harvest(out)
    st, out = ex.step(st, _one_key_batch([], wm=100_100))
    harvest(out)
    assert int(st["dropped_conflict"]) == 0
    assert int(st["dropped_late"]) == 0
    exp = _oracle(np.asarray([5, 7, 12, 100_005, 100_013, 100_017]),
                  np.zeros(6, np.int64), np.ones(6), 40, 10)
    assert got == exp


def test_emit_output_buffer_bounded_but_progressing():
    """Many non-empty windows at once: emission may take several steps
    (bounded buffer) but never stalls and loses nothing."""
    spec = VectorWindowSpec(size_ms=40, slide_ms=10, n_key_buckets=16,
                            max_windows_per_step=1, ring_margin=20,
                            emit_rounds=2)
    ex = StreamExecutor(StreamJobConfig(window=spec, batch_size=32))
    st = ex.init_state()
    got = {}

    def harvest(out):
        v = np.asarray(out["valid"])
        e = np.asarray(out["window_ends"])
        r = np.asarray(out["results"])
        for i in np.nonzero(v)[0]:
            for k in np.nonzero(r[i])[0]:
                got[(int(e[i]), int(k))] = got.get(
                    (int(e[i]), int(k)), 0) + float(r[i][k])

    ts = list(range(0, 200, 10))        # 20 frames, all live
    st, out = ex.step(st, _one_key_batch(ts))
    harvest(out)
    for _ in range(40):                 # wm jump: all windows close
        st, out = ex.step(st, _one_key_batch([], wm=1000))
        harvest(out)
    assert int(st["dropped_conflict"]) == 0
    assert got == _oracle(np.asarray(ts), np.zeros(len(ts), np.int64),
                          np.ones(len(ts)), 40, 10)


# ---------------------------------------------------------------------------
# Host-vs-device equivalence: NEXMark Q5 through the bridged vertex
# ---------------------------------------------------------------------------


def _run_q5(placement, disorder=0, n_nodes=1, guarantee="none",
            kill_at_result=None, rate=60_000, total=12_000,
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
                                     wm_lag=disorder),
        lambda: CollectorSink(out), window_ms=window_ms, slide_ms=slide_ms,
        placement=placement,
        device=dict(n_key_buckets=64, batch_size=256))
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


def test_q5_device_equals_host_ordered():
    h, drops_h = _run_q5("host")
    d, drops_d = _run_q5("device")
    assert h == d and len(h) > 0
    assert drops_h == drops_d == 0


def test_q5_device_equals_host_disordered():
    """Same NEXMark input under bounded skew with a covering watermark
    lag: identical window totals AND identical late-drop accounting."""
    h, drops_h = _run_q5("host", disorder=40)
    d, drops_d = _run_q5("device", disorder=40)
    assert h == d and len(h) > 0
    assert drops_h == drops_d == 0
    # and the disordered device run matches the ordered host run
    o, _ = _run_q5("host", disorder=0)
    assert {(w, k): v for _t, k, w, v in d} == \
        {(w, k): v for _t, k, w, v in o}


@pytest.mark.slow
def test_q5_device_exactly_once_through_kill_node():
    """Acceptance: the device-placed vertex snapshots its executor state
    through the snapshot store (step-boundary barrier alignment) and a
    node kill + restore reproduces the no-failure run exactly."""
    base, _ = _run_q5("device", n_nodes=2)
    host_base, _ = _run_q5("host", n_nodes=2)
    assert base == host_base and len(base) > 0
    a, _ = _run_q5("device", n_nodes=2, guarantee=GUARANTEE_EXACTLY_ONCE,
                   kill_at_result=30)
    assert a == base


def test_q5_device_summing_variant():
    """The sum aggregate (vectorized price getter) bridges too."""
    rate, total = 60_000, 6_000
    results = {}
    for placement in ("host", "device"):
        gen = NexmarkGenerator(rate=rate, n_keys=30)
        cluster = JetCluster(n_nodes=1, cooperative_threads=2,
                             clock=VirtualClock(auto_step=0.001))
        out = []
        p = Pipeline.create()
        (p.read_from(lambda: PacedGeneratorSource(
                gen, rate=rate, max_events=total), name="bids")
            .filter(queries.is_bid)
            .with_key(queries.bid_auction)
            .window(sliding(100, 20))
            .aggregate(summing(queries.bid_price), placement=placement,
                       device=dict(n_key_buckets=64, batch_size=128))
            .write_to(lambda: CollectorSink(out)))
        job = cluster.submit(p.to_dag(), JobConfig())
        for _ in range(4_000_000):
            if job.status == JOB_COMPLETED:
                break
            cluster.step()
        assert job.status == JOB_COMPLETED
        results[placement] = sorted(
            set((ev.value.window_end, ev.key, ev.value.value)
                for ev in out))
    assert results["host"] == results["device"]
    assert len(results["host"]) > 0


# ---------------------------------------------------------------------------
# Placement API guard rails
# ---------------------------------------------------------------------------


def test_device_placement_rejects_host_only_features():
    p = Pipeline.create()
    keyed = (p.read_from(lambda: CollectorSink([]), name="s")
              .with_key(lambda v: v))
    with pytest.raises(ValueError):
        keyed.window(session(10)).aggregate(counting(), placement="device")
    p2 = Pipeline.create()
    keyed2 = (p2.read_from(lambda: CollectorSink([]), name="s")
               .with_key(lambda v: v))
    with pytest.raises(ValueError):
        (keyed2.window(sliding(100, 10)).allowed_lateness(5)
            .aggregate(counting(), placement="device"))
    from repro.core import DeviceWindowProcessor, to_list
    from repro.core.window import SlidingWindowDef
    with pytest.raises(ValueError):
        DeviceWindowProcessor(SlidingWindowDef(100, 10), to_list())


# ---------------------------------------------------------------------------
# One packed host→device transfer a step
# ---------------------------------------------------------------------------


def _bits(tree):
    """Every leaf as raw bytes of its own dtype: equal means bit for bit."""
    import jax
    return jax.tree.map(lambda x: np.asarray(x).tobytes(), tree)


@pytest.mark.parametrize("emission", ["per_key", "top1"])
@pytest.mark.parametrize("rows", [64, 41])
@pytest.mark.parametrize("wm", [-1, 150, None])
@pytest.mark.parametrize("values", ["ones", "fractions"])
def test_packed_step_equals_the_column_step(emission, rows, wm, values):
    """``step`` on the packed array ``stage_batch`` makes returns the same
    state and outputs as ``step`` on the padded column dict, bit for bit:
    a full and a partial batch, a watermark of -1, a hint and none, counts
    and non-integral f32 sums."""
    import jax
    B = 64
    spec = VectorWindowSpec(size_ms=40, slide_ms=10, n_key_buckets=32,
                            max_windows_per_step=4, ring_margin=8,
                            frontier_from_data=False, emission=emission)
    ex = StreamExecutor(StreamJobConfig(window=spec, batch_size=B))
    rng = np.random.RandomState(rows)

    def columns(lo, n):
        val = (np.ones(n, np.float32) if values == "ones"
               else rng.rand(n).astype(np.float32) * 7.3)
        return {"ts": np.sort(rng.randint(lo, lo + 100, n)).astype(np.int32),
                "key": rng.randint(0, 32, n).astype(np.int32),
                "value": val, "valid": np.ones(n, bool),
                "wm": None if wm is None else np.int32(wm)}

    # a state with live frames, then the step under test
    st, _ = ex.step(ex.init_state(), ex.stage_batch(columns(0, B)))
    batch = columns(100, rows)
    pad = (0, B - rows)
    padded = {k: v if k == "wm" else jnp.asarray(np.pad(v, pad))
              for k, v in batch.items()}
    copy = lambda s: jax.tree.map(jnp.copy, s)    # noqa: E731 (donated)
    sent = ex.host_transfers
    staged = ex.stage_batch(batch)
    assert ex.host_transfers - sent == 1
    assert staged.shape == (4 * B + 1,) and staged.dtype == jnp.int32
    want = ex.step(copy(st), padded)
    got = ex.step(copy(st), staged)
    assert _bits(got) == _bits(want)
    if wm is not None and wm >= 0:
        assert np.asarray(want[1]["valid"]).any()   # the hint emitted


def _q5_device_job(threads=2):
    gen = NexmarkGenerator(rate=60_000, n_keys=40)
    cluster = JetCluster(n_nodes=1, cooperative_threads=threads,
                         clock=VirtualClock(auto_step=0.001))
    out = []
    p = queries.q5(
        lambda: PacedGeneratorSource(gen, rate=60_000, max_events=12_000),
        lambda: CollectorSink(out), window_ms=100, slide_ms=20,
        placement="device", device=dict(n_key_buckets=64, batch_size=256))
    job = cluster.submit(p.to_dag(), JobConfig())
    for _ in range(4_000_000):
        if job.status == JOB_COMPLETED:
            break
        cluster.step()
    assert job.status == JOB_COMPLETED
    return job, {(ev.value.window_end, ev.key): ev.value.value for ev in out}


def test_one_host_transfer_a_step():
    from repro.core import DeviceWindowProcessor
    job, got = _q5_device_job()
    devs = [t.processor for t in job.execution.tasklets
            if isinstance(t.processor, DeviceWindowProcessor)]
    assert len(devs) == 2 and got
    for p in devs:
        assert p.telemetry()["host_transfers"] == p.steps > 0
    assert job.execution.stats()["host_transfers"] == \
        sum(p.steps for p in devs)


def test_packed_batch_honours_the_callers_mask(monkeypatch):
    """Rows whose ``valid`` the caller clears before ``stage_batch`` count
    nowhere on the device: clearing every even bucket leaves exactly the
    odd buckets' counts of the unaltered run."""
    _job, full = _q5_device_job()
    real = StreamExecutor.stage_batch

    def stage(self, batch):
        valid = np.array(batch["valid"])
        valid &= np.asarray(batch["key"]) % 2 == 1
        return real(self, dict(batch, valid=valid))
    monkeypatch.setattr(StreamExecutor, "stage_batch", stage)
    _job, odd = _q5_device_job()
    assert any(k % 64 % 2 == 0 for _w, k in full)
    assert odd == {wk: v for wk, v in full.items() if wk[1] % 64 % 2 == 1}
