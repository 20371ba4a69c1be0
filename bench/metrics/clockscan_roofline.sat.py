"""clockscan_roofline.sat: the ClockScan kernel's share of the HBM
roofline over the traced window: the bytes its launches must move
(``roofline.clockscan_bytes`` of each launch's operand shapes) over their
device time at the chip's HBM bandwidth, in percent.

A launch is recognised by its operands as the trace's HLO text gives
them: ``custom-call(s32[C,T] cols, s32[C,Q,1] lo, s32[C,Q,1] hi,
s32[1,T] valid)`` with result ``s32[Q/32,T]``.  Ops cut by the window's
edges are left out."""
from roofline import clockscan_bytes, roofline_share
from trace_reduce import hlo_shapes


def launch(text: str):
    """(columns, rows, query slots) of a ClockScan launch, else None."""
    head, sep, args = text.partition(" custom-call(")
    if not sep or "tpu_custom_call" not in args:
        return None
    out = hlo_shapes(head.partition(" = ")[2])
    ops = hlo_shapes(args)[:4]
    if len(out) != 1 or len(ops) != 4:
        return None
    (_, res), (_, cols), (_, lo), (_, hi), (_, valid) = out[0], *ops
    if len(cols) != 2 or len(res) != 2 or lo != hi or len(lo) != 3:
        return None
    C, T = cols
    Q = lo[1]
    if lo != (C, Q, 1) or valid != (1, T) or res != (Q // 32, T):
        return None
    return C, T, Q


def read(run):
    if run.trace is None:
        return None
    n_bytes = seconds = 0.0
    for ev in run.trace.ops:
        g = launch(ev.name) if ev.whole else None
        if g is not None:
            n_bytes += clockscan_bytes(*g)
            seconds += ev.dur_ns * 1e-9
    if seconds <= 0:
        return None
    return roofline_share(n_bytes, seconds, run.device_kind)
