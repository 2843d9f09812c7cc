"""Bytes the engine hands to the device per image: the sum over an
image's calls of ``RunStats.upload_bytes`` (kernel operands built on the
host and uploaded in the engine's stage phase: GEMM activations and
weights, ALU inputs and tensor operands; a launch's bytes on every run
that took part in it), each shared out over the gang (divided by
``gang_size``), averaged over finished images, in MB (1e6 bytes).  Left
out where the program keeps no such counter."""
NAME = "engine.upload_mb_per_img"
UNIT = "MB/img"
LAYER = "engine"
MOVES = "img_per_s"
SOURCE = "program_counter"


def read(run):
    stats = [st for r in run.finished for call in r.stats for st in call]
    if not stats or not all(hasattr(st, "upload_bytes") for st in stats):
        return None
    per = [sum(st.upload_bytes / st.gang_size for call in r.stats
               for st in call) for r in run.finished]
    return sum(per) / len(per) / 1e6
