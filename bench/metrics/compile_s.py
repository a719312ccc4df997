"""compile_s: XLA compile proper before the window: the program's
``jax.compile`` spans less their ``jax.cache_load`` children."""
import programread


def read(w):
    if w.program is None:
        return None
    return programread.compile_s(w.program, w.window_ns[0])
