"""Program JIT time: the host clock around ``compile_multi`` of the
configuration's programs during set-up."""
NAME = "jit.compile_s"
UNIT = "s"
LAYER = "program JIT"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(run):
    return run.setup.get("compile_s")
