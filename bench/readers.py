"""Readers shared by metric files under ``bench/metrics/``.

Each takes the run's :class:`bench.harness.Observation` and returns the
metric, or None where the run holds nothing to read it from.  A share of
a roofline is never returned as 0 for want of data.
"""

from __future__ import annotations

from .histogram import LatencyHistogram
from .roofline import peaks, step_bytes


def latency_percentile(obs, pct: float):
    if obs.latency_ms is None or not len(obs.latency_ms):
        return None
    hist = LatencyHistogram()
    hist.record(obs.latency_ms * 1000.0)
    return hist.percentile(pct) / 1000.0


def latency_p50_ms(obs):
    return latency_percentile(obs, 50.0)


def events_per_s(obs):
    if obs.events <= 0:
        return None
    return obs.events / obs.seconds


def setup_s(obs):
    return obs.setup_s


def gen_lag_ms(obs):
    """Highest lag of the generator behind its schedule in the window."""
    if not obs.gen_lag.shape[1]:
        return None
    return float(obs.gen_lag[1].max())


def window_vertex_share(obs):
    """% of sampled worker time spent in the device window vertex."""
    total = sum(obs.vertex_seconds.values())
    if total <= 0:
        return None
    dev = sum(s for v, s in obs.vertex_seconds.items()
              if v.endswith(".device"))
    return 100.0 * dev / total


def batch_fill(obs):
    """% of the device batches' rows that held an admitted bid."""
    if obs.steps <= 0:
        return None
    batch = obs.cell.config["device"]["batch_size"]
    return 100.0 * obs.bids / (obs.steps * batch)


def device_idle_share(obs):
    t = obs.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def step_device_ms(obs):
    """Mean device time of one execution of the step program."""
    t = obs.trace
    if t is None or not t.steps:
        return None
    return sum(e - s for s, e, _ in t.steps) / len(t.steps) / 1e6


def step_roofline(obs):
    """% of the HBM roofline the step programs reach: the least bytes the
    window's work moves, over peak bandwidth, over the steps' device
    time."""
    t = obs.trace
    if t is None or not t.steps or obs.bids <= 0:
        return None
    device_s = sum(e - s for s, e, _ in t.steps) / 1e9
    need = step_bytes(obs.bids, obs.window_ends,
                      obs.cell.config["n_auctions"])
    return 100.0 * need / peaks(obs.device_kind)["hbm_bytes_per_s"] \
        / device_s
