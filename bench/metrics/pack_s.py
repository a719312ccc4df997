"""pack_s: the offline packing chain's time before the window, from the
program's ``setup.pack`` span."""
import programread


def read(w):
    if w.program is None:
        return None
    return programread.pack_s(w.program, w.window_ns[0])
