"""warmup_s: the server's warm-up of its buckets before the window, from
the program's ``serve.warmup`` spans (whole-net jit compile or cache
load, and the first calls)."""
import programread


def read(w):
    if w.program is None:
        return None
    return programread.warmup_s(w.program, w.window_ns[0])
