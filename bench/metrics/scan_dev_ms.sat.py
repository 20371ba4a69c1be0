"""scan_dev_ms.sat: device time per beat in the heartbeat's shared scans
(scopes ``scan/*`` and ``fused_delta/*``), ms, over the whole
``jit_beat_*`` executions in the traced window
(``program_trace.stage_split`` with the engine's ``trace_export()``)."""
from program_trace import stage_group


def read(run):
    return stage_group(run, "scan_dev_ms")
