"""Engine wall time per image: the sum over an image's calls of
``RunStats.wall_time_s`` (the engine's own clock around each segment;
a gang's window shared out over the gang), averaged over finished
images."""
NAME = "engine.wall_ms_per_img"
UNIT = "ms/img"
LAYER = "engine"
MOVES = "img_per_s"
SOURCE = "program_span"


def read(run):
    per = [sum(st.wall_time_s / st.gang_size for call in r.stats
               for st in call) for r in run.finished]
    return 1e3 * sum(per) / len(per) if per else None
