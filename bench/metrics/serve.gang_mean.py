"""Mean gang width: ``RunStats.gang_size`` averaged over every
accelerator segment the window's calls ran (one entry per request per
segment)."""
NAME = "serve.gang_mean"
UNIT = "requests"
LAYER = "serving plane"
MOVES = "img_per_s"
SOURCE = "program_counter"


def read(run):
    g = [st.gang_size for r in run.requests for call in r.stats
         for st in call]
    return sum(g) / len(g) if g else None
