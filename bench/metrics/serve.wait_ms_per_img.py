"""Serving-plane wait per image: the sum over an image's calls and their
segments of ``RunStats.park_s`` (parked in the Scheduler's admission
queue until release to the pool) and ``RunStats.queue_s`` (queued in the
pool until the segment's engine run began), averaged over finished
images.  Each request's own wait, not shared out over its gang.  Left
out where the program records no waits."""
NAME = "serve.wait_ms_per_img"
UNIT = "ms/img"
LAYER = "serving plane"
MOVES = "img_p75_ms"
SOURCE = "program_counter"


def read(run):
    stats = [st for r in run.finished for call in r.stats for st in call]
    if not stats or not all(hasattr(st, "queue_s") for st in stats):
        return None
    per = [sum(st.park_s + st.queue_s for call in r.stats for st in call)
           for r in run.finished]
    return 1e3 * sum(per) / len(per)
