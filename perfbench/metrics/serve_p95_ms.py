"""95th percentile of every answered request's latency, timed from when
it was due to be sent (nearest rank)."""
import math


def read(run):
    lat = sorted(run.latencies_ms or ())
    if not lat:
        return None
    return lat[math.ceil(0.95 * len(lat)) - 1]
