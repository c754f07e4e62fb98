"""Vectorized sliding-window aggregation (device tier).

Events arrive as fixed-size array batches ``{ts, key, value, valid}``.
Keys are hashed into ``n_key_buckets``; per (bucket, frame) partial
accumulators live in a ring of ``ring_len`` frame slots — the same
pane-based plan as the host tier (core/window.py), vectorized:

* **accumulate** (Jet stage 1): the batch scatters into the (K, R) pane
  matrix (``segment-sum`` here; the MXU-tiled one-hot-matmul version is
  the Pallas kernel in ``kernels/window_agg`` — DESIGN.md "scatter-add ->
  one-hot matmul"),
* **combine + emit** (Jet stage 2): when the watermark crosses a slide
  boundary, the window result per key is ``panes_ring @ window_mask`` —
  one matvec per emitted window.

Frame/window convention: frame ``f`` covers event time
``[f*slide, (f+1)*slide)``; the window whose LAST frame is ``L`` covers
frames ``[L-F+1, L]`` and its end is ``w_end = (L+1)*slide``; it emits
once the watermark reaches ``w_end``.

All shapes are static; a step emits up to ``max_windows_per_step`` windows
per emission *round* and loops rounds (bounded ``lax.while_loop``) until
the emission front catches the watermark or the per-step output buffer
(``max_windows_per_step * emit_rounds`` rows) fills; empty windows — no
live frame in range — are skipped in O(1) by fast-forwarding the front,
so an idle source followed by a burst (or a large ``wm`` heartbeat jump)
cannot leave emission permanently behind.  Events that arrive after their
last window emitted are dropped and counted (``dropped_late``), events
whose ring slot is still occupied by a live older frame are dropped and
counted (``dropped_conflict`` — bounded by pacing ingestion against
emission, which is the executor's credit-based backpressure job).

``wm_lag`` is the bounded-out-of-orderness allowance (the host tier's
``EventTimePolicy.lag``): the data-driven watermark frontier is
``max(ts) - wm_lag``, so cross-batch disorder up to ``wm_lag`` of event
time is admitted instead of silently dropped as late — ordered and
disordered runs with ``wm_lag >= max_skew_ms`` produce identical results,
the same disorder-equivalence guarantee the host tier gives.
``frontier_from_data=False`` disables the data-driven frontier entirely:
the watermark then advances only on explicit ``wm`` hints, which is how
the host bridge (core/device_window.py) drives emission from the host's
own coalesced watermarks.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

#: sentinel for "no frame / uninitialised emission front" (int32-safe)
_FAR = 2**30


@dataclasses.dataclass(frozen=True)
class VectorWindowSpec:
    size_ms: int
    slide_ms: int
    n_key_buckets: int = 1024
    max_windows_per_step: int = 4
    ring_margin: int = 4
    #: bounded out-of-orderness allowance subtracted from the data-driven
    #: watermark frontier (0 keeps the legacy max(ts) frontier)
    wm_lag: int = 0
    #: False = the watermark advances only on explicit ``wm`` hints (the
    #: host bridge mode: host watermarks are already lagged at the source)
    frontier_from_data: bool = True
    #: max emission rounds per step (0 = auto: ceil(ring_len / E), enough
    #: output rows to retire every live frame's next window in one step)
    emit_rounds: int = 0

    @property
    def frames_per_window(self) -> int:
        assert self.size_ms % self.slide_ms == 0
        return self.size_ms // self.slide_ms

    @property
    def ring_len(self) -> int:
        # the watermark lag keeps frames live for wm_lag/slide extra
        # slides past the emission front: size the ring for it, or the
        # admitted disorder would bleed straight into ring conflicts
        lag_frames = -(-self.wm_lag // self.slide_ms) if self.wm_lag else 0
        return self.frames_per_window + self.ring_margin + lag_frames

    @property
    def emit_rounds_resolved(self) -> int:
        if self.emit_rounds > 0:
            return self.emit_rounds
        return -(-self.ring_len // self.max_windows_per_step)

    @property
    def emit_buffer_rows(self) -> int:
        """Rows in a step's emission output (``results``/``window_ends``/
        ``valid`` leading dimension)."""
        return self.max_windows_per_step * self.emit_rounds_resolved


def window_state_init(spec: VectorWindowSpec, dtype=jnp.float32) -> Dict:
    return {
        # per (frame slot, key bucket) partial aggregate — slot-major so
        # the accumulate scatter lands without a transpose and emission is
        # one (E, R) @ (R, K) matmul
        "panes": jnp.zeros((spec.ring_len, spec.n_key_buckets), dtype),
        # frame id stored in each ring slot (-1 = empty)
        "slot_frame": jnp.full((spec.ring_len,), -1, jnp.int32),
        "watermark": jnp.asarray(-1, jnp.int32),
        # next window end (ms) to emit; -1 = not yet initialised
        "next_emit": jnp.asarray(-1, jnp.int32),
        "dropped_late": jnp.asarray(0, jnp.int32),
        "dropped_conflict": jnp.asarray(0, jnp.int32),
    }


def accumulate(spec: VectorWindowSpec, state: Dict, ts, key_bucket, value,
               valid, wm_hint=None) -> Dict:
    """Jet stage 1, vectorized pane accumulation.

    ``wm_hint``: optional scalar watermark heartbeat (idle-source marker):
    advances event time without carrying data."""
    K, R, F = spec.n_key_buckets, spec.ring_len, spec.frames_per_window
    frame = (ts // spec.slide_ms).astype(jnp.int32)
    slot = frame % R

    # lateness: frames below min_frame have had their last window emitted
    ne = state["next_emit"]
    min_frame = jnp.where(ne < 0, jnp.int32(-(2**30)),
                          ne // spec.slide_ms - F)
    live = valid & (frame >= min_frame)
    n_late = jnp.sum(valid & ~live, dtype=jnp.int32)

    # ring-slot conflicts: slot occupied by a DIFFERENT still-live frame
    slot_frame = state["slot_frame"]
    occupant = slot_frame[slot]
    conflict = live & (occupant >= 0) & (occupant != frame)
    n_conflict = jnp.sum(conflict, dtype=jnp.int32)
    live = live & ~conflict

    combined = slot * K + key_bucket.astype(jnp.int32)
    contrib = jnp.where(live, value, 0.0).astype(state["panes"].dtype)
    panes = state["panes"].reshape(R * K).at[combined].add(
        contrib, mode="drop").reshape(R, K)

    # record which frame now lives in each touched slot (scatter-max;
    # measured 25x faster than the one-hot formulation at R~100)
    slot_frame = slot_frame.at[jnp.where(live, slot, R)].max(
        jnp.where(live, frame, -1), mode="drop")

    wm = state["watermark"]
    if spec.frontier_from_data:
        # bounded out-of-orderness: the frontier trails the running-max
        # timestamp by wm_lag, so cross-batch disorder within the
        # allowance is admitted instead of dropped as late
        frontier = jnp.max(jnp.where(valid, ts, -1)).astype(jnp.int32) \
            - jnp.int32(spec.wm_lag)
        wm = jnp.maximum(wm, frontier)
    if wm_hint is not None:
        wm = jnp.maximum(wm, jnp.asarray(wm_hint, jnp.int32))
    return dict(state, panes=panes, slot_frame=slot_frame, watermark=wm,
                dropped_late=state["dropped_late"] + n_late,
                dropped_conflict=state["dropped_conflict"] + n_conflict)


def emit(spec: VectorWindowSpec, state: Dict
         ) -> Tuple[Dict, Dict[str, jnp.ndarray]]:
    """Jet stage 2, vectorized: emit window results with end <= watermark;
    evict the frame each emission retires.

    Emission runs in rounds of ``max_windows_per_step`` windows (one
    ``(E, R) @ (R, K)`` matmul per round) inside a bounded
    ``lax.while_loop`` that stops when the front passes the watermark or
    the output buffer (``emit_buffer_rows`` rows) fills.  Between rounds
    the front *fast-forwards over empty windows* — window ends no live
    frame participates in — so a watermark jump across an idle gap (idle
    source then burst, or a ``wm`` heartbeat) costs O(1) instead of one
    round per skipped window: emission can no longer fall permanently
    behind and bleed ``dropped_conflict``.
    """
    K, R, F = spec.n_key_buckets, spec.ring_len, spec.frames_per_window
    slide = spec.slide_ms
    E = spec.max_windows_per_step
    EB = spec.emit_buffer_rows

    wm = state["watermark"]
    panes0, slot_frame0 = state["panes"], state["slot_frame"]
    # first window end strictly beyond the watermark: reaching it means
    # emission is fully caught up
    caught = (wm // slide + 1) * slide

    def fast_forward(ne, slot_frame):
        """Smallest window end >= ne containing a live frame; if none is
        at or below the watermark, jump to ``caught`` (every window in
        between is empty — skipping it emits exactly nothing)."""
        live = slot_frame >= 0
        # frame f participates in windows ending (f+1)*slide..(f+F)*slide
        cand = jnp.where(live & ((slot_frame + F) * slide >= ne),
                         jnp.maximum(ne, (slot_frame + 1) * slide), _FAR)
        nxt = jnp.min(cand)
        return jnp.where(ne >= _FAR, ne,
                         jnp.where(nxt <= wm, nxt,
                                   jnp.maximum(ne, caught)))

    # initialise next_emit from the first frame present
    first_frame = jnp.min(jnp.where(slot_frame0 >= 0, slot_frame0, _FAR))
    ne0 = jnp.where(state["next_emit"] < 0,
                    jnp.where(first_frame < _FAR,
                              (first_frame + 1) * slide,
                              jnp.int32(_FAR)),
                    state["next_emit"])
    ne0 = fast_forward(ne0, slot_frame0)

    res0 = jnp.zeros((EB, panes0.shape[1]), panes0.dtype)
    ends0 = jnp.zeros((EB,), jnp.int32)
    val0 = jnp.zeros((EB,), bool)

    def cond(carry):
        ne, _panes, _sf, _res, _ends, _val, count = carry
        return (ne <= wm) & (ne < _FAR) & (count + E <= EB)

    def body(carry):
        ne, panes, slot_frame, res, ends, val, count = carry
        # E candidate windows in ONE matmul: masks (E, R) @ panes (R, K)
        w_ends = ne + jnp.arange(E, dtype=jnp.int32) * slide
        ready = w_ends <= wm                                    # (E,)
        L = w_ends // slide - 1                                 # (E,)
        ring_f = slot_frame                                     # (R,)
        in_win = ((ring_f[None, :] > (L - F)[:, None])
                  & (ring_f[None, :] <= L[:, None])
                  & (ring_f[None, :] >= 0) & ready[:, None])
        masks = jnp.where(in_win, 1.0, 0.0).astype(panes.dtype)  # (E, R)
        # HIGHEST: at DEFAULT precision the TPU multiplies f32 operands in
        # bfloat16, which rounds a window sum above 256 (a summing Q5's
        # price totals) — the results must equal the host's exactly
        results = jnp.matmul(masks, panes,
                             precision=jax.lax.Precision.HIGHEST)  # (E, K)
        # evict every frame retired by an emitted window (single pass)
        evict = jnp.any((ring_f[None, :] == (L - F + 1)[:, None])
                        & ready[:, None], axis=0) & (ring_f >= 0)
        panes = jnp.where(evict[:, None], 0.0, panes)
        slot_frame = jnp.where(evict, -1, slot_frame)
        n_emitted = jnp.sum(ready, dtype=jnp.int32)
        # the ready rows are a prefix of the E candidates (w_ends are
        # ascending), so advancing the cursor by n_emitted lets the next
        # round overwrite only the not-ready tail
        res = jax.lax.dynamic_update_slice(res, results, (count, 0))
        ends = jax.lax.dynamic_update_slice(ends, w_ends, (count,))
        val = jax.lax.dynamic_update_slice(val, ready, (count,))
        count = count + n_emitted
        ne = fast_forward(ne + n_emitted * slide, slot_frame)
        return ne, panes, slot_frame, res, ends, val, count

    ne_f, panes, slot_frame, res, ends, val, _count = jax.lax.while_loop(
        cond, body,
        (ne0, panes0, slot_frame0, res0, ends0, val0, jnp.int32(0)))

    new_next = jnp.where(ne_f < _FAR, ne_f, state["next_emit"])
    out_state = dict(state, panes=panes, slot_frame=slot_frame,
                     next_emit=new_next)
    return out_state, {"results": res, "window_ends": ends, "valid": val}


def step(spec: VectorWindowSpec, state: Dict, batch: Dict
         ) -> Tuple[Dict, Dict]:
    """One fused accumulate+emit step (the whole-DAG-per-chip tasklet)."""
    state = accumulate(spec, state, batch["ts"], batch["key"],
                       batch["value"], batch["valid"], batch.get("wm"))
    return emit(spec, state)
