"""wips: web interactions per second, those due in the window and
completed before it closed, over the window's length."""


def read(run):
    done = sum(1 for r in run.inters if r.done is not None
               and r.done <= run.t_close)
    return done / run.seconds
