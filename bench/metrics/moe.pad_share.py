"""Share of the rows launched for routed experts that carried no token:
1 - sum of ``RunStats.moe_rows`` (tokens routed to a held expert) over
the sum of ``RunStats.moe_rows_padded`` (rows its stream ran: the tokens
rounded up to the template's batch), over every routed expert's segment
the window's calls ran, in percent.  Left out where no call ran one."""
NAME = "moe.pad_share"
UNIT = "%"
LAYER = "expert layer"
MOVES = "img_per_s"
SOURCE = "program_counter"


def read(run):
    stats = [st for r in run.requests for call in r.stats for st in call]
    padded = sum(getattr(st, "moe_rows_padded", 0) for st in stats)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(st.moe_rows for st in stats) / padded)
