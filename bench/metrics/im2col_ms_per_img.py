"""im2col_ms_per_img: device time of the operations under a layer's
``im2col`` scope, summed over the chips, per image answered in the
window."""
import programread


def read(w):
    return programread.im2col_ms_per_img(w.scope_s, w.images_in_window)
