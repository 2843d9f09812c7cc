"""Share of the bytes the engine's WGT LOADs name that the host never
copied out of DRAM: Σ ``RunStats.wgt_skip_bytes`` ÷ Σ
``RunStats.wgt_load_bytes`` over every accelerator segment the window's
calls ran, in %.  ``PallasBackend`` defers a WGT LOAD to the DRAM range
and digest it names, and copies its bytes only where an operand built
from it was not on the device, or where something read the WGT SRAM.
Left out where the program keeps no such counter, or ran no WGT LOAD."""
NAME = "engine.wgt_load_skip_share"
UNIT = "%"
LAYER = "engine"
MOVES = "img_per_s"
SOURCE = "program_counter"


def read(run):
    stats = [st for r in run.requests for call in r.stats for st in call]
    if not stats or not all(hasattr(st, "wgt_load_bytes") for st in stats):
        return None
    named = sum(st.wgt_load_bytes for st in stats)
    if named == 0:
        return None
    return 100.0 * sum(st.wgt_skip_bytes for st in stats) / named
