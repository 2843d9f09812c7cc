"""Engine launch time per image: the sum over an image's calls of
``RunStats.launch_s``, the engine's host seconds inside its kernel calls
until they return (dispatch, with any re-trace of a vmapped launch),
timed at its ``vta.engine.launch`` spans; a gang's time shared out over
the gang, averaged over finished images.  Left out where the program
records no phase seconds."""
NAME = "engine.launch_ms_per_img"
UNIT = "ms/img"
LAYER = "engine"
MOVES = "img_per_s"
SOURCE = "program_span"


def read(run):
    stats = [st for r in run.finished for call in r.stats for st in call]
    if not stats or not all(hasattr(st, "launch_s") for st in stats):
        return None
    per = [sum(st.launch_s / st.gang_size for call in r.stats for st in call)
           for r in run.finished]
    return 1e3 * sum(per) / len(per)
