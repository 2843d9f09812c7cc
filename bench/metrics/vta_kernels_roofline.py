"""Share of the roofline reached by the engine's kernels (``vta_gemm``,
``tensor_alu``, ``lut_gemm``) together: the least time the chip needs
for the useful work of every call the traced window ran, over those
kernels' summed device time in the trace.

Least time is max(ops / int8 peak, bytes / HBM rate), with ops = 2 *
MACs from the conv shapes and bytes = the call's int8 weights once per
gang (shared out over the gang) plus its int8 input and output.  The
same work is counted whatever the kernels do: padding, repeated weight
loads and re-tiling all show as a lower share."""
NAME = "vta_kernels_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "img_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    kernel_s = sum(run.trace.kernel_ns.values()) * 1e-9
    if kernel_s <= 0:
        return None
    ops = nbytes = 0.0
    for r in run.requests:
        for i, call in enumerate(r.stats):
            w = run.work[i]
            gang = call[0].gang_size if call else 1
            ops += w.ops
            nbytes += w.gang_bytes(gang) / gang
    least = max(ops / run.peaks["int8_ops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
