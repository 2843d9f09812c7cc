"""Share of the GEMM weight-operand bytes that the engine's launches
needed and found on the device: Σ ``RunStats.wgt_hit_bytes`` ÷ Σ
``RunStats.wgt_bytes`` over every accelerator segment the window's calls
ran, in %.  A hit is a padded weight tile that ``PallasBackend``'s
content-keyed weight cache already held, so the launch uploaded only its
activations.  Left out where the program keeps no such counter, or ran
no GEMM."""
NAME = "engine.wgt_resident_share"
UNIT = "%"
LAYER = "engine"
MOVES = "img_per_s"
SOURCE = "program_counter"


def read(run):
    stats = [st for r in run.requests for call in r.stats for st in call]
    if not stats or not all(hasattr(st, "wgt_bytes") for st in stats):
        return None
    need = sum(st.wgt_bytes for st in stats)
    if need == 0:
        return None
    return 100.0 * sum(st.wgt_hit_bytes for st in stats) / need
