"""Unit coverage: the adaptive receive window protocol (paper §3.3),
watermark coalescing, and the sharding rule table."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.backpressure import (ACK_INTERVAL_S, MIN_RECEIVE_WINDOW,
                                     NetworkLink, WINDOW_FILL_FACTOR)
from repro.core.clock import VirtualClock
from repro.core.watermark import WatermarkCoalescer


# ---------------------------------------------------------------------------
# NetworkLink / adaptive receive window
# ---------------------------------------------------------------------------

def test_link_credit_exhaustion_backpressures():
    clock = VirtualClock()
    link = NetworkLink(clock, latency_s=0.0, initial_window=4)
    assert all(link.offer(i) for i in range(4))
    assert not link.offer(99), "credits exhausted -> remote backpressure"
    link.pump()
    # consumer drains, ack not due yet -> still no credit
    assert link.poll() == 0
    assert not link.offer(99)
    clock.advance(ACK_INTERVAL_S + 0.01)
    link.pump()                          # ack: acked_seq advances
    assert link.offer(99)


def test_link_window_adapts_to_processing_rate():
    clock = VirtualClock()
    link = NetworkLink(clock, latency_s=0.0, initial_window=16)
    # consumer processes ~100 items per ack interval
    for _ in range(6):
        for _ in range(min(100, link.remaining_capacity())):
            link.offer("x")
        link.pump()
        while link.poll() is not None:
            pass
        clock.advance(ACK_INTERVAL_S + 0.001)
        link.pump()
    # steady state: window ~ WINDOW_FILL_FACTOR x per-interval rate
    assert link.receive_window >= MIN_RECEIVE_WINDOW
    assert link.receive_window <= 100 * WINDOW_FILL_FACTOR * 2


def test_link_preserves_fifo_through_latency():
    clock = VirtualClock()
    link = NetworkLink(clock, latency_s=0.01)
    for i in range(10):
        assert link.offer(i)
    link.pump()
    assert link.poll() is None, "items still in flight"
    clock.advance(0.02)
    link.pump()
    assert [link.poll() for _ in range(10)] == list(range(10))


# ---------------------------------------------------------------------------
# Watermark coalescing
# ---------------------------------------------------------------------------

def test_coalescer_min_rule_and_done_exclusion():
    c = WatermarkCoalescer(3)
    assert c.observe(0, 10) is None          # others still at MIN
    assert c.observe(1, 20) is None
    assert c.observe(2, 15) == 10            # min(10, 20, 15)
    assert c.observe(0, 30) == 15            # min(30, 20, 15)
    assert c.queue_done(2) == 20             # 15 leaves; min(30, 20)
    assert c.queue_done(1) == 30
    assert c.queue_done(0) is None           # nothing live


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh():
    # rule logic only reads mesh.shape / axis_names; build an abstract mesh
    import jax
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def test_param_rules_train_vs_serve(mesh):
    from repro.sharding.rules import _param_spec
    # attention projection: FSDP+TP in training, TP-only in serving
    assert _param_spec(mesh, ("groups", "b0", "mixer", "wq"),
                       (4, 1024, 2048)) == P(None, "data", "model")
    assert _param_spec(mesh, ("groups", "b0", "mixer", "wq"),
                       (4, 1024, 2048), fsdp=False) == P(None, None, "model")
    # embed: vocab-only sharding in BOTH modes (batch-replication hazard)
    assert _param_spec(mesh, ("embed",), (92544, 6144)) == P("model", None)
    # MoE experts: EP when E % 16 == 0, TP-in-expert otherwise
    assert _param_spec(mesh, ("groups", "b0", "ffn", "w_gate"),
                       (2, 16, 4096, 6400)) == P(None, "model", "data", None)
    assert _param_spec(mesh, ("groups", "b0", "ffn", "w_gate"),
                       (2, 8, 4096, 14336)) == P(None, None, "data", "model")
    # non-dividing dims are dropped, never invalid
    assert _param_spec(mesh, ("groups", "b0", "mixer", "wk"),
                       (4, 1536, 100)) == P(None, "data", None)


def test_cache_rules_sequence_sharding(mesh):
    from repro.sharding.rules import _cache_spec
    # decode cache: sequence over model (B shards on data)
    spec = _cache_spec(mesh, ("b0", "k"), (48, 128, 32768, 8, 128))
    assert spec == P(None, "data", "model", None, None)
    # long-context B=1: sequence takes both axes
    spec = _cache_spec(mesh, ("b0", "k"), (4, 1, 524288, 8, 128))
    assert spec == P(None, None, ("data", "model"), None, None)


def test_batch_spec_fallbacks(mesh):
    from repro.sharding.rules import batch_spec
    assert batch_spec(mesh, (256, 4096)) == P("data", None)
    # B=1 cannot shard; with a seq dim hint it shards the sequence
    assert batch_spec(mesh, (1, 524288), seq_dim=1) == P(None, "data")
