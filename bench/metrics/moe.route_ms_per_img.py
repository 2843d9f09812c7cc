"""Expert-layer host time per image (one decode step of every layer):
the sum over an image's calls of ``RunStats.host_span_s`` under
``moe.route`` (scores, top-k, gates and the gather of each held expert's
rows) and ``moe.combine`` (the gate-weighted sum and requant), timed at
those ``vta.moe.*`` spans, averaged over finished images.  Left out
where no call ran such a span."""
NAME = "moe.route_ms_per_img"
UNIT = "ms/img"
LAYER = "expert layer"
MOVES = "img_per_s"
SOURCE = "program_span"

SPANS = ("moe.route", "moe.combine")


def read(run):
    stats = [st for r in run.finished for call in r.stats for st in call]
    if not any(k in getattr(st, "host_span_s", {}) for st in stats
               for k in SPANS):
        return None
    per = [sum(st.host_span_s.get(k, 0.0) for call in r.stats
               for st in call for k in SPANS) for r in run.finished]
    return 1e3 * sum(per) / len(per)
