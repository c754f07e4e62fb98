"""Engine layer, per-key cell: % of sampled worker time in the sink vertex, the cost of taking every per-key result downstream of the bridge (moves events_per_s)."""


def read(obs):
    total = sum(obs.vertex_seconds.values())
    if total <= 0:
        return None
    sink = sum(s for v, s in obs.vertex_seconds.items()
               if v.rsplit("-", 1)[0] == "sink")
    return 100.0 * sink / total
