"""Engine step: window over the engine steps the program counted in it, ms."""


def read(run):
    return 1e3 * run.window_s / run.engine_steps if run.engine_steps else None
