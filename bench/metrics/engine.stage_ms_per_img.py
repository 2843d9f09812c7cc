"""Engine staging time per image: the sum over an image's calls of
``RunStats.stage_s``, the engine's host seconds building and uploading
kernel operands (``np.zeros``, copies, stacks, ``jnp.asarray``), timed
at its ``vta.engine.stage`` spans; a gang's time shared out over the
gang, averaged over finished images.  Left out where the program
records no phase seconds."""
NAME = "engine.stage_ms_per_img"
UNIT = "ms/img"
LAYER = "engine"
MOVES = "img_per_s"
SOURCE = "program_span"


def read(run):
    stats = [st for r in run.finished for call in r.stats for st in call]
    if not stats or not all(hasattr(st, "stage_s") for st in stats):
        return None
    per = [sum(st.stage_s / st.gang_size for call in r.stats for st in call)
           for r in run.finished]
    return 1e3 * sum(per) / len(per)
