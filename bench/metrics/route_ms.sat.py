"""route_ms.sat: host time per beat in routing (the engine's
``t_collect_s``: result assembly and routing to the tickets, after the
device finished)."""
import numpy as np


def read(run):
    v = [b.stats["t_collect_s"] for b in run.beats]
    return float(np.mean(v)) * 1e3 if v else None
