"""Kernel launches per image: the sum over an image's calls of
``RunStats.tile_batches``, each gang launch shared out over the gang
(divided by ``gang_size``), averaged over finished images."""
NAME = "engine.launches_per_img"
UNIT = "launches/img"
LAYER = "engine"
MOVES = "img_per_s"
SOURCE = "program_counter"


def read(run):
    per = [sum(st.tile_batches / st.gang_size for call in r.stats
               for st in call) for r in run.finished]
    return sum(per) / len(per) if per else None
