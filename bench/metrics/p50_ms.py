"""p50_ms: median latency of the interactions due in the window, from the
moment each was due to the collect that routed its last answer."""
import numpy as np


def read(run):
    lat = [r.done - r.due for r in run.inters if r.done is not None]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
