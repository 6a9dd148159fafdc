"""Everything before the window: process start, data, the index build,
compiles and the warm-up (host clock)."""


def read(run):
    return run.setup_s
