"""non_walker_ms_per_img: device time outside the walker kernels (im2col,
pools, pads, copies), summed over the chips, per image answered in the
window."""
WALKER = "_worklist_spmm_pallas"


def read(w):
    if w.trace is None or w.images_in_window == 0:
        return None
    busy = w.trace.busy_s * len(w.trace.devices)
    walker = w.trace.op_seconds(lambda n: n.startswith(WALKER))
    return 1e3 * (busy - walker) / w.images_in_window
