"""setup_s: seconds from the process's start to the window's opening:
data generation, the engine's build, compilation or cache loads, warm-up."""


def read(run):
    return run.setup_s
