"""step_mfu_pct: the whole forward's share of the chips' bf16 peak: the
pruned network's required FLOPs (2 x nonzero-weight MACs) per image, times
the images answered in the window, over the window, the chips and the
peak."""
import work


def read(w):
    if w.trace is None or w.peaks is None or w.work is None:
        return None
    flops = work.flops_per_image(w.work) * w.images_in_window
    return 100.0 * flops / (w.seconds * w.chips * w.peaks["bf16_flops"])
