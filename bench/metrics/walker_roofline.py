"""walker_roofline: the work-list walker's share of its roofline. For the
walker kernels that start in the traced window: the least time the chip
could take for their conv layers' required work (per layer the larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth, at the calls' batch
of images per chip) over their summed device time."""
import work

WALKER = "_worklist_spmm_pallas"       # HLO name of the walker's kernel


def read(w):
    if w.trace is None or w.peaks is None or w.work is None:
        return None
    calls, seconds = w.trace.started(lambda n: n.startswith(WALKER))
    if calls == 0 or seconds <= 0:
        return None
    per_forward = work.roofline_seconds(
        w.work, w.slots // w.chips, w.peaks["bf16_flops"],
        w.peaks["hbm_bytes_s"])
    return 100.0 * (calls / len(w.work)) * per_forward / seconds
