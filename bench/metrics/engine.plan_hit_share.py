"""Share of the engine's segments that replayed their stream's plan:
``RunStats.plan_hit`` (1 when ``PallasBackend`` replayed, in full, the
interpretation plan its first run of the stream recorded; 0 when it
analysed the stream afresh) averaged over every accelerator segment the
window's calls ran.  After warm-up every stream has a plan, so a reading
below 1 means plans were missed, evicted or fell back.  Left out where
the program keeps no such counter."""
NAME = "engine.plan_hit_share"
UNIT = "hits/segment"
LAYER = "engine"
MOVES = "img_per_s"
SOURCE = "program_counter"


def read(run):
    stats = [st for r in run.requests for call in r.stats for st in call]
    if not stats or not all(hasattr(st, "plan_hit") for st in stats):
        return None
    return sum(st.plan_hit for st in stats) / len(stats)
