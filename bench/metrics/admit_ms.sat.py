"""admit_ms.sat: host time per beat in admission (the engine's
``t_stage_s``: queue drain, staging-buffer fill, host-to-device copy)."""
import numpy as np


def read(run):
    v = [b.stats["t_stage_s"] for b in run.beats]
    return float(np.mean(v)) * 1e3 if v else None
