"""The whole step's share of the chip's int8 peak: the useful operations
of one image (2 * MACs of its calls) times the traced run's images per
second, over the peak of the chips used."""
NAME = "mfu.img"
UNIT = "%"
LAYER = "whole step"
MOVES = "img_per_s"
SOURCE = "host_clock"


def read(run):
    if run.peaks is None:
        return None
    ops = sum(w.ops for w in run.work)
    return 100.0 * ops * run.img_per_s / (run.chips
                                          * run.peaks["int8_ops_per_s"])
