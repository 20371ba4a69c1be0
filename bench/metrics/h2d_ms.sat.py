"""h2d_ms.sat: host time per beat in admission's host-to-device copies
(the engine's ``h2d_s``: its ``beat.h2d`` span over every staged query
buffer and update batch), part of ``admit_ms.sat``."""
import numpy as np


def read(run):
    v = [b.stats["h2d_s"] for b in run.beats if "h2d_s" in b.stats]
    return float(np.mean(v)) * 1e3 if v else None
