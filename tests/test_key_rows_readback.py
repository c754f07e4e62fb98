"""The per-key form's readback: only the emitted prefix of a step's output
comes back to the host.

``DeviceWindowProcessor._convert`` copies ``results`` and ``window_ends``
through the last valid row, rounded up to a power of two (capped at the
step's ``emit_buffer_rows``), sliced on the device.  The events it builds
must equal, in order, those the whole matrix gives; ``readback_bytes``
must count exactly the rows copied; and the slice shapes must stay within
the bucket set, every one compiled in ``init`` so none compiles later.
"""

import numpy as np
import pytest

from repro.core import DeviceWindowProcessor, EventBlock, counting
from repro.core import device_window as dw
from repro.core.window import SlidingWindowDef

SIZE, SLIDE, K, BATCH = 40, 10, 16, 256
#: frames 0..20 hold bids, so window ends 10..240 (24 of them) are live
LIVE_FRAMES = 21
#: key = bucket-injective offset, so conversion must map buckets back
KEY0 = 1000

# (max_windows_per_step, emit_rounds): 24 rows, not a power of two, and 16
SHAPES = {"rows24": (8, 3), "rows16": (4, 4)}


def _processor(shape, monkeypatch=None, asked=None):
    if asked is not None:
        real = dw._slicer()

        def recording(res, ends, rows):
            asked.append(rows)
            return real(res, ends, rows)
        monkeypatch.setattr(dw, "_slicer", lambda: recording)
    per_step, rounds = SHAPES[shape]
    p = DeviceWindowProcessor(SlidingWindowDef(SIZE, SLIDE), counting(),
                              n_key_buckets=K, batch_size=BATCH,
                              max_windows_per_step=per_step,
                              ring_margin=LIVE_FRAMES - 1,
                              emit_rounds=rounds)
    p.init(None, None)
    return p


def _ingest(p, seed):
    """One batch of bids over every live frame, keys mapped onto buckets
    one to one."""
    rng = np.random.default_rng(seed)
    first = np.arange(LIVE_FRAMES) * SLIDE + rng.integers(0, SLIDE,
                                                          LIVE_FRAMES)
    rest = rng.integers(0, LIVE_FRAMES * SLIDE, BATCH - LIVE_FRAMES)
    ts = np.concatenate([first, rest]).astype(np.int64)
    keys = KEY0 + rng.integers(0, K, BATCH).astype(np.int64)
    p._ingest_block(EventBlock(ts, keys))
    assert p._n == 0 and len(p._pending) == 1


def _full_matrix_events(p, out):
    """What the whole ``(emit_buffer_rows, K)`` matrix converts to."""
    valid = np.asarray(out["valid"])
    res = np.asarray(out["results"])
    ends = np.asarray(out["window_ends"])
    events = []
    for i in np.nonzero(valid)[0]:
        w_end = int(ends[i])
        for b in np.nonzero(res[i])[0]:
            key = int(p._bucket_key[b])
            events.append((w_end - 1, key, w_end, key, int(res[i, b])))
    return events


def _as_tuples(buf):
    return [(e.ts, e.key, e.value.window_end, e.value.key, e.value.value)
            for e in buf]


def _rows_bytes(rows):
    return rows * K * 4 + rows * 4


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("closes", [1, 2, 3, 8, 9, "all"])
def test_sliced_readback_converts_like_the_whole_matrix(shape, closes,
                                                         monkeypatch):
    asked = []
    p = _processor(shape, monkeypatch, asked)
    cap = p._spec.emit_buffer_rows
    buckets = dw._row_buckets(cap)
    # init compiled every slice the readback may ask for
    assert sorted(set(asked)) == buckets
    n = cap if closes == "all" else closes
    for seed in (1, 2**31 + 11):
        p = _processor(shape)
        _ingest(p, seed)
        p._harvest_ready(block=True)      # wm -1: the batch closes nothing
        assert not p._emit_buf
        del asked[:]
        before = p.telemetry()["readback_bytes"]
        p._submit(wm_hint=n * SLIDE)      # closes window ends 10..10n
        (_hint, out), = p._pending
        want = _full_matrix_events(p, out)
        p._harvest_ready(block=True)
        valid = np.asarray(out["valid"])
        # the emitted rows are a prefix of the buffer
        assert valid[:n].all() and not valid[n:].any()
        assert _as_tuples(p._emit_buf) == want
        assert {w for _, _, w, _, _ in want} == \
            set(range(SLIDE, (n + 1) * SLIDE, SLIDE))
        s = dw._row_bucket(n, cap)
        assert s >= n and s in buckets and asked == [s]
        assert p.telemetry()["readback_bytes"] - before == \
            valid.nbytes + _rows_bytes(s)
        assert p.telemetry()["key_results"] == len(want)


def test_init_leaves_nothing_to_compile_for_later_slices():
    p = _processor("rows24")
    compiled = dw._slicer()._cache_size()
    for closes in (1, 5, 17, 24):
        p = _processor("rows24")
        _ingest(p, closes)
        p._submit(wm_hint=closes * SLIDE)
        p._harvest_ready(block=True)
        assert p.telemetry()["emitting_steps"] == 1
    assert dw._slicer()._cache_size() == compiled


def test_slice_covers_the_last_valid_row_off_a_prefix():
    """A host output whose valid rows are not a prefix still converts
    every valid row: the slice runs through the last one."""
    p = _processor("rows24")
    p._bucket_key[:] = 3 * K + np.arange(K)
    cap = p._spec.emit_buffer_rows
    rng = np.random.default_rng(7)
    valid = np.zeros(cap, bool)
    valid[[0, 5]] = True
    out = {"valid": valid,
           "window_ends": (np.arange(cap, dtype=np.int32) + 1) * SLIDE,
           "results": rng.integers(0, 3, (cap, K)).astype(np.float32)}
    want = _full_matrix_events(p, out)
    before = p.telemetry()["readback_bytes"]
    p._convert(out)
    assert _as_tuples(p._emit_buf) == want and want
    assert p.telemetry()["readback_bytes"] - before == \
        valid.nbytes + _rows_bytes(8)


@pytest.mark.parametrize("cap,buckets", [
    (1, [1]), (16, [1, 2, 4, 8, 16]), (24, [1, 2, 4, 8, 16, 24]),
    (512, [1, 2, 4, 8, 16, 32, 64, 128, 256, 512])])
def test_row_buckets_are_powers_of_two_then_the_buffer(cap, buckets):
    assert dw._row_buckets(cap) == buckets
    for n in range(1, cap + 1):
        s = dw._row_bucket(n, cap)
        assert s == min(b for b in buckets if b >= n)
