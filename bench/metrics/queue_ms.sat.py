"""queue_ms.sat: how long an admitted query waited in its template's
admission queue, from its submission to the start of the beat that
admitted it (the engine's ``queue_wait_s_sum`` over ``admitted``), mean
over the window's beats, in ms."""


def read(run):
    stats = [b.stats for b in run.beats if "queue_wait_s_sum" in b.stats]
    n = sum(s["admitted"] for s in stats)
    return 1e3 * sum(s["queue_wait_s_sum"] for s in stats) / n if n \
        else None
