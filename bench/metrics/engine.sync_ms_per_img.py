"""Engine sync time per image: the sum over an image's calls of
``RunStats.sync_s``, the engine's host seconds waiting for its kernels'
results and reading them back (``np.asarray``), timed at its
``vta.engine.sync`` spans; a gang's time shared out over the gang,
averaged over finished images.  Left out where the program records no
phase seconds."""
NAME = "engine.sync_ms_per_img"
UNIT = "ms/img"
LAYER = "engine"
MOVES = "img_per_s"
SOURCE = "program_span"


def read(run):
    stats = [st for r in run.finished for call in r.stats for st in call]
    if not stats or not all(hasattr(st, "sync_s") for st in stats):
        return None
    per = [sum(st.sync_s / st.gang_size for call in r.stats for st in call)
           for r in run.finished]
    return 1e3 * sum(per) / len(per)
