"""75th percentile of image latency over every image finished inside
the window: from its first call's submit (its scheduled arrival, in an
open loop) to its last call's return.  A window holds some 30 to 60
images, so the 75th is the highest percentile with about ten images
beyond it; a 95th would rest on the two or three slowest."""
import numpy as np

NAME = "img_p75_ms"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    lat = [r.latency_s for r in run.in_window]
    if not lat:
        return None
    return float(np.percentile(np.asarray(lat) * 1e3, 75))
