"""Set-up: from the process's start to the window's start (imports,
data from the seed, compile_multi, pool and scheduler, warm-up of every
gang width)."""
NAME = "setup_s"
UNIT = "s"
SOURCE = "host_clock"


def read(run):
    return run.setup["setup_s"]
