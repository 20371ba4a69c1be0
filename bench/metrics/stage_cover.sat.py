"""stage_cover.sat: share of the device's self time in the whole
``jit_beat_*`` executions of the traced window that the engine's
``trace_export()`` maps to a heartbeat stage, in percent: how much of the
beat the four ``*_dev_ms.sat`` metrics account for."""
from program_trace import stage_group


def read(run):
    return stage_group(run, "stage_cover")
