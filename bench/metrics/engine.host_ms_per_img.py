"""Engine host self time per image: the sum over an image's calls of
``RunStats.wall_time_s`` less its ``stage_s``, ``launch_s`` and
``sync_s``, that is the ``vta.engine.gang`` span's time outside its
phase spans: stream decode (with its cache key), the Python walk of the
decoded stream, tile planning, scatter and write-back.  A gang's time is
shared out over the gang; averaged over finished images.  Left out where
the program records no phase seconds."""
NAME = "engine.host_ms_per_img"
UNIT = "ms/img"
LAYER = "engine"
MOVES = "img_per_s"
SOURCE = "program_span"


def _self_s(st):
    return st.wall_time_s - st.stage_s - st.launch_s - st.sync_s


def read(run):
    stats = [st for r in run.finished for call in r.stats for st in call]
    if not stats or not all(hasattr(st, "sync_s") for st in stats):
        return None
    per = [sum(_self_s(st) / st.gang_size for call in r.stats for st in call)
           for r in run.finished]
    return 1e3 * sum(per) / len(per)
