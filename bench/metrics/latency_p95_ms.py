"""latency_p95_ms: 95th percentile latency of every request due in the
window, from its due time to its answer (see
``harness.measure.latencies_ms``)."""
import numpy as np


def read(ctx):
    lat = ctx.latencies_ms()
    return float(np.percentile(lat, 95)) if lat.size else None
