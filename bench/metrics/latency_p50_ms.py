"""latency_p50_ms: median latency of every request due in the window, from
its due time to its answer (see ``harness.measure.latencies_ms``)."""
import numpy as np


def read(ctx):
    lat = ctx.latencies_ms()
    return float(np.percentile(lat, 50)) if lat.size else None
