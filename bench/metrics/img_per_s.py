"""Images per second: images' worth of conv calls finished inside the
measured window (calls finished / calls per image), over the window's
length.  Counting calls rather than whole images gives the rate a
granularity of one call, where a closed loop of lockstep clients
finishes its images in groups."""
NAME = "img_per_s"
UNIT = "img/s"
SOURCE = "host_clock"


def read(run):
    return run.img_per_s
