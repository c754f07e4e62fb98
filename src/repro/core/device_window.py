"""The host→device bridge: a window-aggregation vertex offloaded to the
compiled device tier.

``Pipeline.window(w).aggregate(op, placement="device")`` lowers to a
:class:`DeviceWindowProcessor` vertex on a distributed partitioned
in-edge — each parallel instance owns a StreamExecutor over its
key-partition subset (partitioning of device state follows partitioning
of compute) — replacing the host two-stage accumulate/combine plan with
the device tier's fused accumulate+emit step (:mod:`repro.streaming`):

* **Packing** — incoming :class:`~repro.core.events.EventBlock` columns
  (the host hot path) append into fixed-size staging arrays; scalar
  :class:`~repro.core.events.Event`\\ s take the same arrays one row at a
  time.  A full staging buffer (or a partial one, at a watermark) becomes
  one device batch ``{ts, key, value, valid, wm}``, handed to
  ``stage_batch`` as views of the staging arrays: the executor packs
  them into one int32 array, the tail rows of a partial batch padded
  with ``valid=False``, and stages it in ONE host→device transfer
  (``host_transfers`` counts them; the step unpacks the columns on the
  device).  Keys hash-bucket into ``n_key_buckets`` via ``key %
  n_key_buckets`` (injective whenever the key space fits the bucket
  count; wider key spaces aggregate per *bucket* — the documented
  caveat).  The original key of every bucket is remembered host-side so
  emissions convert back.
* **Async drive** — batch *i+1* stages (``stage_batch``: one packed
  transfer) while step *i* executes; step outputs stay on device as
  futures in an ordered pending list and are only materialized once
  ``is_ready()`` (polled from ``poll_async`` / the watermark path), so
  the cooperative tasklet loop NEVER blocks on the device.
* **Watermarks** — the device runs in hint-only frontier mode
  (``frontier_from_data=False``): host watermarks (already lagged at the
  source) are the only event-time authority, so every device instance
  observes the identical watermark sequence.  A watermark that does not
  cross a slide boundary forwards immediately (no window can close); one
  that does submits a wm-hinted step and forwards only after that step's
  emissions are harvested and the device emission front has passed the
  watermark — downstream still sees every result *before* the watermark
  that closed it, exactly the host contract.
* **Unpacking** — every step's ``valid`` mask comes back; only a step
  that emitted copies more.  The output takes one of two forms, fixed at
  construction (``emission``):

  - ``"per_key"`` (the default): only the emitted prefix of
    ``(window_ends, results)`` comes back — the rows up to the last valid
    one, rounded up to a power of two (capped at ``emit_buffer_rows``, so
    a few compiled slice shapes, all warmed in ``init``), sliced on the
    device and copied in one transfer.  Each valid row becomes
    ``Event(w_end - 1, key, WindowResult(w_end, key, value))`` per nonzero
    bucket, the exact shape the host two-stage combiner emits
    (near-integer values collapse to int: counting/integer-sum aggregates
    compare equal to the host path bit-for-bit up to f32's 2**24 integer
    range).
  - ``"top1"``, for a consumer that keeps only the highest count per
    window end (Q5's hot-items stage, which merges partials by max): the
    device reduces each window end to its highest count and the lowest
    bucket holding it, and only that packed ``(3, rows)`` int32 array
    comes back.  Each row with a count above 0 becomes one such
    ``WindowResult`` event, this instance's partial; ``reduced_windows``
    counts them.  Counting only: a sum cannot tell absent from zero.

  ``key_results`` counts the per-key form's results, ``readback_bytes``
  the bytes copied back; together they give the bytes read back per
  result delivered.  Results wait in a buffer until the outbox takes
  them (``_flush_emit``, spanned as ``jet.bridge.flush``).
* **Snapshots** — barriers align to step boundaries: staged rows flush as
  a final step, emission catches up to the last processed watermark
  (identical across instances — the coalesced watermark sequence is), and
  the device state stores per ORIGINAL key as ``("k", key) -> [(frame,
  value), ...]`` entries partitioned like the data keys, so restore after
  a topology change merges shards additively under the standard per-key
  contract.
"""

from __future__ import annotations

import functools
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import spans
from .events import Event, EventBlock, Watermark
from .processor import Inbox, Processor
from .window import AggregateOperation, SlidingWindowDef, WindowResult

def _as_int_if_integral(v: float):
    r = round(v)
    return int(r) if abs(v - r) < 1e-6 else float(v)


def _row_buckets(cap: int) -> List[int]:
    """The row counts a per-key readback rounds up to: the powers of two
    below ``cap`` (a step's ``emit_buffer_rows``), then ``cap`` itself."""
    return sorted({min(1 << i, cap) for i in range(cap.bit_length() + 1)})


def _row_bucket(n: int, cap: int) -> int:
    """The smallest of :func:`_row_buckets` that holds ``n`` rows."""
    return min(1 << (n - 1).bit_length(), cap)


@functools.cache
def _slicer():
    """One jitted slice shared by every instance in the process: ``rows``
    is static, so each row bucket compiles once."""
    import jax
    return jax.jit(lambda res, ends, rows: (res[:rows], ends[:rows]),
                   static_argnums=2)


def _leading_rows(out: Dict, rows: int) -> Tuple[np.ndarray, np.ndarray]:
    """``results[:rows]`` and ``window_ends[:rows]`` of a per-key step
    output on the host: sliced on the device, then copied in one transfer
    (an output already on the host is sliced in place)."""
    res, ends = out["results"], out["window_ends"]
    if isinstance(res, np.ndarray):
        return res[:rows], ends[:rows]
    import jax
    return jax.device_get(_slicer()(res, ends, rows))


class DeviceWindowProcessor(Processor):
    """Block-aware tasklet processor driving a device StreamExecutor.

    ``op`` must be a vectorizable aggregate: ``counting()`` or a
    ``summing(...)`` whose getter carries a block form (the same ops the
    host columnar fast path accepts).  Blocks feed the packer whole; with
    a summing op lacking a block getter the vertex falls back to scalar
    ingestion (``accepts_blocks`` stays False and the tasklet shim
    explodes blocks at the queue boundary).

    ``emission`` picks the output form (module docstring, "Unpacking"):
    ``"per_key"`` sends every key's result; ``"top1"`` sends one partial
    per window end, for a plan whose only consumer keeps the highest
    count (``nexmark.queries.q5`` with its hot-items stage).

    Known divergence from the host plan: the device pane matrix cannot
    distinguish "no events" from "events summing to exactly 0", so a
    summing window whose total is 0 emits nothing here while the host
    combiner emits an explicit ``WindowResult(..., 0)``.  Counting and
    positive-valued sums (the NEXMark shapes) are unaffected; keep
    sign-cancelling sums on the host if the empty-vs-zero distinction
    matters downstream.
    """

    #: Host-side staging and flow state never survives a restart by
    #: design: save_to_snapshot submits staged rows as a final pre-barrier
    #: device step and drains every in-flight output, so the durable
    #: window content lives entirely in the device state (saved as
    #: ("k", key) shards + ("meta", idx) entries and rebuilt by
    #: finish_snapshot_restore).  _emit_buf flushes before the barrier,
    #: watermark cursors re-advance from replayed sources, executor/_spec
    #: are rebuilt lazily by _ensure_executor, and _bucket_collisions,
    #: _readback_bytes, _emitting_steps, _reduced_windows, _key_results
    #: and _host_transfers are telemetry.
    EPHEMERAL_STATE = frozenset({
        "_ts", "_key", "_val", "_n", "_pending", "_emit_buf", "_steps",
        "_progress_hint", "_last_wm", "_wm_submitted", "_closed",
        "_spec", "_bucket_collisions", "_readback_bytes",
        "_emitting_steps", "_reduced_windows", "_key_results",
        "_host_transfers",
    })

    def __init__(self, wdef: SlidingWindowDef, op: AggregateOperation,
                 n_key_buckets: int = 1024, batch_size: int = 1024,
                 max_windows_per_step: int = 8, ring_margin: int = 8,
                 emit_rounds: int = 0, emission: str = "per_key"):
        if op.kind not in ("count", "sum"):
            raise ValueError(
                "device placement supports counting()/summing() aggregates "
                f"(got kind={op.kind!r}); keep other ops on the host")
        if emission == "top1" and op.kind != "count":
            raise ValueError(
                "the top-1 emission form needs counting(): a sum's zero and "
                "an absent key look alike on the device")
        self.wdef = wdef
        self.op = op
        self.n_key_buckets = n_key_buckets
        self.batch_size = batch_size
        self.max_windows_per_step = max_windows_per_step
        self.ring_margin = ring_margin
        self.emit_rounds = emit_rounds
        self.emission = emission
        # blocks are only useful when the value column vectorizes
        self.accepts_blocks = (op.kind == "count"
                               or op.block_get is not None)

        self.executor = None
        self.state = None
        # staging buffers (one device batch)
        B = batch_size
        self._ts = np.zeros(B, np.int32)
        self._key = np.zeros(B, np.int32)
        self._val = np.zeros(B, np.float32)
        self._all_valid = np.ones(B, bool)
        self._n = 0
        #: bucket -> original key (the inverse of the packing hash; first
        #: writer wins on collision — see the module docstring caveat).
        #: An int64 array so block ingestion updates it vectorized.
        self._bkey_sentinel = np.int64(np.iinfo(np.int64).min)
        self._bucket_key = np.full(n_key_buckets, self._bkey_sentinel,
                                   np.int64)
        self._bucket_collisions = 0
        self._closed = False
        #: ordered in-flight step outputs: (wm_hint_or_None, device out)
        self._pending: deque = deque()
        self._emit_buf: deque = deque()
        self._wm_submitted = -1          # highest hint staged to the device
        self._last_wm = -1               # highest watermark fully processed
        self._top_ts = -1                # max event ts seen (host-side)
        self._steps = 0                  # telemetry: device steps driven
        self._readback_bytes = 0         # telemetry: bytes copied to host
        self._emitting_steps = 0         # telemetry: steps that emitted
        self._reduced_windows = 0        # telemetry: top-1 partials sent
        self._key_results = 0            # telemetry: per-key results sent
        self._host_transfers = 0         # telemetry: host->device transfers
        self._progress_hint = False      # last _harvest_ready made progress
        self._snap_entries: Optional[List[Tuple[Any, Any]]] = None
        self._restore_frames: Dict[Any, Dict[int, float]] = {}
        self._restore_meta: List[Dict] = []

    # ------------------------------------------------------------ set-up --
    def init(self, outbox, ctx) -> None:
        super().init(outbox, ctx)
        # build + warm the executor NOW (one dummy step compiles the XLA
        # program) so the one-time compile cost lands at job start, not in
        # the middle of a paced run's latency measurement
        self._ensure_executor()
        staged = self.executor.stage_batch({
            "ts": np.zeros(self.batch_size, np.int32),
            "key": np.zeros(self.batch_size, np.int32),
            "value": np.zeros(self.batch_size, np.float32),
            "valid": np.zeros(self.batch_size, bool),
            "wm": np.asarray(-1, np.int32)})
        self.state, out = self.executor.step(self.state, staged)
        np.asarray(out["valid"])        # block: compilation finished
        if self.emission == "per_key":
            # and every slice the per-key readback may ask for
            for rows in _row_buckets(self._spec.emit_buffer_rows):
                _leading_rows(out, rows)

    def _ensure_executor(self) -> None:
        if self.executor is not None:
            return
        from ..streaming import (StreamExecutor, StreamJobConfig,
                                 VectorWindowSpec)
        spec = VectorWindowSpec(
            size_ms=self.wdef.size, slide_ms=self.wdef.slide,
            n_key_buckets=self.n_key_buckets,
            max_windows_per_step=self.max_windows_per_step,
            ring_margin=self.ring_margin, emit_rounds=self.emit_rounds,
            frontier_from_data=False, emission=self.emission)
        self.executor = StreamExecutor(
            StreamJobConfig(window=spec, batch_size=self.batch_size))
        self.state = self.executor.init_state()
        self._spec = spec

    # ------------------------------------------------------------ ingest --
    def process(self, ordinal: int, inbox: Inbox) -> None:
        self._ensure_executor()
        op = self.op
        for item in inbox:
            if item.__class__ is EventBlock:
                self._ingest_block(item)
            else:
                # scalar fallback: one staged row per event; the op's own
                # accumulate over a fresh accumulator IS the row weight
                # (count -> 1, sum -> get(ev))
                b = int(item.key) % self.n_key_buckets
                prev = self._bucket_key[b]
                if prev == self._bkey_sentinel:
                    self._bucket_key[b] = item.key
                elif prev != item.key:
                    self._bucket_collisions += 1
                n = self._n
                self._ts[n] = item.ts
                self._key[n] = b
                self._val[n] = op.accumulate(op.create(), item)
                if item.ts > self._top_ts:
                    self._top_ts = item.ts
                self._n = n + 1
                if self._n == self.batch_size:
                    self._submit()
        inbox.clear()
        # opportunistically drain finished device steps (non-blocking)
        self._harvest_ready()
        if self._emit_buf:
            self._flush_emit()

    def _ingest_block(self, blk: EventBlock) -> None:
        K, B = self.n_key_buckets, self.batch_size
        ts = blk.ts
        buckets = blk.key % K
        if self.op.kind == "count":
            weights = None
        else:
            weights = np.asarray(self.op.block_get(blk), np.float32)
        # remember the original key per bucket (vectorized; first writer
        # wins) and count collisions — buckets already bound to a
        # DIFFERENT key — for telemetry
        bk = np.asarray(buckets, np.int64)
        kk = np.asarray(blk.key, np.int64)
        bmap = self._bucket_key
        prev = bmap[bk]
        fresh = prev == self._bkey_sentinel
        if fresh.any():
            # first occurrence in this block wins among duplicates: write
            # in reverse row order so the earliest assignment lands last
            idx = np.nonzero(fresh)[0][::-1]
            bmap[bk[idx]] = kk[idx]
            prev = bmap[bk]
        self._bucket_collisions += int(np.count_nonzero(prev != kk))
        top = int(ts.max()) if len(ts) else -1
        if top > np.iinfo(np.int32).max:
            # the device tier computes event time in int32 ms; silently
            # wrapping an int64 host timestamp would corrupt every frame
            # assignment downstream (the scalar path raises naturally)
            raise ValueError(
                f"device window timestamps must fit int32 ms (got {top}); "
                "rebase the stream to a relative time origin")
        if top > self._top_ts:
            self._top_ts = top
        i, n = 0, len(ts)
        while i < n:
            take = min(B - self._n, n - i)
            sl = slice(i, i + take)
            dst = slice(self._n, self._n + take)
            self._ts[dst] = ts[sl]
            self._key[dst] = buckets[sl]
            self._val[dst] = 1.0 if weights is None else weights[sl]
            self._n += take
            i += take
            if self._n == B:
                self._submit()

    # ------------------------------------------------------- device drive --
    def _submit(self, wm_hint: Optional[int] = None,
                window_end: Optional[int] = None) -> None:
        """Stage the staged rows as one device batch and dispatch the step
        asynchronously; the output joins the pending list as a device
        future.  ``window_end``: the highest window end the hint closes,
        given on the first step that carries it (a span attribute only)."""
        tracing = spans.on
        if tracing:
            attrs = {"rows": self._n, "wm": -1 if wm_hint is None
                     else wm_hint}
            if window_end is not None:
                attrs["window_end"] = window_end
        with spans.span("jet.bridge.submit", **attrs) if tracing \
                else spans.OFF as sp:
            n = self._n
            ex = self.executor
            sent = ex.host_transfers
            # views: stage_batch's pack is the one copy (and pads a
            # partial batch), so the staging buffers stay reusable
            staged = ex.stage_batch({
                "ts": self._ts[:n], "key": self._key[:n],
                "value": self._val[:n], "valid": self._all_valid[:n],
                "wm": np.int32(-1 if wm_hint is None else wm_hint)})
            self.state, out = ex.step(self.state, staged)
            sent = ex.host_transfers - sent
            self._host_transfers += sent
            if tracing:
                sp.set_metadata(transfers=sent)
            self._pending.append((wm_hint, out))
            self._steps += 1
            self._n = 0

    @staticmethod
    def _is_ready(arr) -> bool:
        fn = getattr(arr, "is_ready", None)
        return fn() if fn is not None else True

    def _harvest_ready(self, block: bool = False) -> bool:
        """Materialize finished pending outputs in order, converting their
        emissions into WindowResult events.  Stops at the first output
        still executing unless ``block``; returns True when the pending
        list fully drained."""
        pending = self._pending
        progress = False
        while pending:
            _hint, out = pending[0]
            if not block and not self._is_ready(out["valid"]):
                break
            self._convert(out)
            pending.popleft()
            progress = True
        self._progress_hint = progress
        return not pending

    def _convert(self, out: Dict) -> None:
        tracing = spans.on
        top1 = self.emission == "top1"
        with spans.span("jet.bridge.readback") if tracing else spans.OFF \
                as sp:
            valid = np.asarray(out["valid"])
            rows = np.nonzero(valid)[0].tolist()
            nbytes = valid.nbytes
            if rows:
                if top1:
                    top = np.asarray(out["top"])
                    ends = top[0]
                    nbytes += top.nbytes
                else:
                    # through the last valid row, not the count of valid
                    # rows: the slice holds every row even off a prefix
                    res, ends = _leading_rows(out, _row_bucket(
                        rows[-1] + 1, len(valid)))
                    nbytes += ends.nbytes + res.nbytes
            if tracing:
                sp.set_metadata(bytes=nbytes)
        self._readback_bytes += nbytes
        if not rows:
            return
        self._emitting_steps += 1
        bmap, sentinel = self._bucket_key, self._bkey_sentinel
        buf = self._emit_buf
        n0 = len(buf)
        with spans.span("jet.bridge.convert",
                        window_end=int(ends[rows[0]]),
                        last_window_end=int(ends[rows[-1]])) if tracing \
                else spans.OFF as sp:
            if top1:
                # one partial per window end: this instance's hot item
                for w_end, b, count in top[:, rows].T.tolist():
                    if count > 0:
                        k = bmap[b]
                        key = b if k == sentinel else int(k)
                        buf.append(Event(w_end - 1, key,
                                         WindowResult(w_end, key, count)))
                self._reduced_windows += len(buf) - n0
            else:
                for i in rows:
                    row = res[i]
                    w_end = int(ends[i])
                    for b in np.nonzero(row)[0].tolist():
                        k = bmap[b]
                        key = b if k == sentinel else int(k)
                        val = _as_int_if_integral(float(row[b]))
                        buf.append(Event(w_end - 1, key,
                                         WindowResult(w_end, key, val)))
                self._key_results += len(buf) - n0
            if tracing:
                sp.set_metadata(results=len(buf) - n0)

    def _flush_emit(self) -> bool:
        """Move buffered results into the outbox until it refuses one;
        True once the buffer is empty."""
        buf = self._emit_buf
        n = len(buf)
        tracing = spans.on and n > 0
        with spans.span("jet.bridge.flush") if tracing else spans.OFF as sp:
            while buf:
                if not self.outbox.offer(buf[0]):
                    break
                buf.popleft()
            if tracing:
                sp.set_metadata(results=n - len(buf), left=len(buf))
        return not buf

    def poll_async(self) -> bool:
        """Non-blocking pump the tasklet calls every slice: harvest device
        futures that finished since, and move their emissions out."""
        if self.executor is None or not self._pending:
            return False
        self._harvest_ready()
        progress = self._progress_hint
        if self._emit_buf:
            progress |= self._flush_emit()
        return progress

    # --------------------------------------------------------- watermarks --
    def try_process_watermark(self, wm: Watermark) -> bool:
        """Forward the watermark only once every window it closes has been
        emitted downstream (the host ordering contract), without ever
        blocking: not-ready device futures just defer to the next call."""
        self._ensure_executor()
        if not self._flush_emit():
            return False
        slide = self.wdef.slide
        if wm.ts // slide == self._last_wm // slide and wm.ts >= 0 \
                and self._last_wm >= 0:
            # no slide boundary crossed: window closure is slide-granular,
            # so this watermark cannot close anything the previous one did
            # not — forward immediately without a device roundtrip.  The
            # hint itself is NOT sent to the device; that is safe because
            # a later boundary-crossing watermark (or complete()'s
            # close-out) supersedes it before any emission decision needs
            # it.
            self._last_wm = wm.ts
            return True
        if wm.ts > self._wm_submitted:
            # flush staged rows + the hint in ONE wm-carrying step
            self._submit(wm_hint=wm.ts, window_end=wm.ts // slide * slide)
            self._wm_submitted = wm.ts
        # harvest everything up to (and including) the hint step
        if not self._harvest_ready():
            return False
        # the device emission front must have passed the watermark — a
        # bounded emit loop may need another round after a very large jump
        ne = self.state["next_emit"]
        if not self._is_ready(ne):
            return False
        ne_v = int(ne)
        if 0 <= ne_v <= wm.ts:
            self._submit(wm_hint=wm.ts)     # another catch-up round
            return False
        if not self._flush_emit():
            return False
        self._last_wm = wm.ts
        return True

    # ----------------------------------------------------------- complete --
    def complete(self) -> bool:
        if self.executor is None:
            return True
        # close every open window: flush staged rows, then drive wm-hinted
        # steps until no live frame remains (end-of-stream may sync)
        if not self._closed:
            if self._n:
                self._submit()
            close_wm = max(self._top_ts + self.wdef.size + self.wdef.slide,
                           self._last_wm + self.wdef.slide)
            slide = self.wdef.slide
            for i in range(10_000):
                self._submit(wm_hint=close_wm, window_end=None if i
                             else close_wm // slide * slide)
                self._harvest_ready(block=True)
                if not np.any(np.asarray(self.state["slot_frame"]) >= 0):
                    break
            self._harvest_ready(block=True)
            self._closed = True
        return self._flush_emit()

    # ----------------------------------------------------------- snapshot --
    def save_to_snapshot(self) -> bool:
        if self.executor is None:
            return True
        if self._snap_entries is None:
            # step-boundary alignment: staged rows become a final
            # pre-barrier step, emission catches up to the last processed
            # watermark (identical across instances), in-flight outputs
            # drain.  Snapshot time may sync with the device.
            if self._n:
                self._submit(wm_hint=self._wm_submitted
                             if self._wm_submitted >= 0 else None)
            for _ in range(10_000):
                self._harvest_ready(block=True)
                ne_v = int(self.state["next_emit"])
                if not (0 <= ne_v <= self._last_wm):
                    break
                self._submit(wm_hint=self._last_wm)
            self._snap_entries = self._build_snapshot_entries()
        # pre-barrier output (results the catch-up produced) leaves first
        if not self._flush_emit():
            return False
        for skey, val in self._snap_entries:
            self.outbox.offer_to_snapshot(skey, val)
        self._snap_entries = None
        return True

    def _build_snapshot_entries(self) -> List[Tuple[Any, Any]]:
        snap = self.executor.snapshot(self.state)
        host = {k: np.asarray(v) for k, v in snap.items()}
        panes, slot_frame = host["panes"], host["slot_frame"]
        entries: List[Tuple[Any, Any]] = []
        # per ORIGINAL key: [(frame, partial)] — mergeable shards under
        # the standard restore contract, partitioned like the data keys
        per_key: Dict[Any, List[Tuple[int, float]]] = {}
        bmap, sentinel = self._bucket_key, self._bkey_sentinel
        slots, buckets = np.nonzero(panes)
        for s, b in zip(slots.tolist(), buckets.tolist()):
            f = int(slot_frame[s])
            if f < 0:
                continue
            k = bmap[b]
            key = b if k == sentinel else int(k)
            per_key.setdefault(key, []).append((f, float(panes[s, b])))
        for key, frames in per_key.items():
            entries.append((("k", key), frames))
        entries.append((("meta", self.ctx.global_index), {
            "watermark": int(host["watermark"]),
            "next_emit": int(host["next_emit"]),
            "dropped_late": int(host["dropped_late"]),
            "dropped_conflict": int(host["dropped_conflict"]),
            "top_ts": self._top_ts,
        }))
        return entries

    def snapshot_partition(self, skey):
        from .dag import PARTITION_COUNT
        if skey[0] == "k":
            return hash(skey[1]) % PARTITION_COUNT
        return None

    def restore_from_snapshot(self, items) -> None:
        for skey, val in items:
            if skey[0] == "k":
                frames = self._restore_frames.setdefault(skey[1], {})
                for f, v in val:
                    frames[f] = frames.get(f, 0.0) + v
            elif skey[0] == "meta":
                self._restore_meta.append(val)

    def finish_snapshot_restore(self) -> None:
        if not self._restore_frames and not self._restore_meta:
            return
        self._ensure_executor()
        import jax.numpy as jnp
        spec = self._spec
        R, K = spec.ring_len, spec.n_key_buckets
        panes = np.zeros((R, K), np.float32)
        slot_frame = np.full(R, -1, np.int32)
        dropped_conflict = 0
        # older frames win slot conflicts (they emit sooner); a shard pair
        # whose in-flight data diverged by more than the ring span loses
        # the younger frame into dropped_conflict, mirroring accumulate
        for key, frames in sorted(self._restore_frames.items(),
                                  key=lambda kv: str(kv[0])):
            b = int(key) % K
            if self._bucket_key[b] == self._bkey_sentinel:
                self._bucket_key[b] = key
            for f, v in sorted(frames.items()):
                s = f % R
                if slot_frame[s] < 0 or slot_frame[s] == f:
                    slot_frame[s] = f
                    panes[s, b] += v
                elif f < slot_frame[s]:
                    # evict the younger occupant's partials, keep the older
                    panes[s, :] = 0.0
                    slot_frame[s] = f
                    panes[s, b] = v
                    dropped_conflict += 1
                else:
                    dropped_conflict += 1
        meta = self._restore_meta
        state = {
            "panes": jnp.asarray(panes),
            "slot_frame": jnp.asarray(slot_frame),
            "watermark": jnp.asarray(
                max((m["watermark"] for m in meta), default=-1), jnp.int32),
            "next_emit": jnp.asarray(
                max((m["next_emit"] for m in meta), default=-1), jnp.int32),
            "dropped_late": jnp.asarray(
                sum(m["dropped_late"] for m in meta), jnp.int32),
            "dropped_conflict": jnp.asarray(
                sum(m["dropped_conflict"] for m in meta)
                + dropped_conflict, jnp.int32),
        }
        self.state = self.executor._shard_state(state)
        self._top_ts = max((m["top_ts"] for m in meta), default=-1)
        self._restore_frames = {}
        self._restore_meta = []

    # ---------------------------------------------------------- telemetry --
    @property
    def late_dropped(self) -> int:
        """Deliberately dropped late events (device counter, host view)."""
        if self.state is None:
            return 0
        return int(np.asarray(self.state["dropped_late"]))

    @property
    def conflict_dropped(self) -> int:
        if self.state is None:
            return 0
        return int(np.asarray(self.state["dropped_conflict"]))

    @property
    def bucket_collisions(self) -> int:
        return self._bucket_collisions

    @property
    def steps(self) -> int:
        """Device steps this instance has dispatched."""
        return self._steps

    def telemetry(self) -> Dict[str, int]:
        """``readback_bytes``: bytes of step output copied to the host
        (every step's ``valid`` mask; per emitting step, the top-1 form's
        whole ``top`` array or the per-key form's emitted prefix of
        ``window_ends`` and ``results``, rounded up to a power of two);
        ``emitting_steps``: harvested steps whose output held a window;
        ``reduced_windows``: window ends the device reduced to one partial
        (top-1 form; 0 in the per-key form); ``key_results``: per-key
        ``WindowResult`` events sent (per-key form; 0 in the top-1 form);
        ``host_transfers``: host->device transfers the steps' staging
        issued (one a step)."""
        return {"readback_bytes": self._readback_bytes,
                "emitting_steps": self._emitting_steps,
                "reduced_windows": self._reduced_windows,
                "key_results": self._key_results,
                "host_transfers": self._host_transfers}
