"""beat_ms.sat: the achieved cycle time under load, the window's length
over the beats collected in it (host clock)."""


def read(run):
    return run.seconds / len(run.beats) * 1e3 if run.beats else None
