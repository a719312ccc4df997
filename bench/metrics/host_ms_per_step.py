"""host_ms_per_step: the host's share of a serving step: ``serve.step``
less its ``serve.wait``, mean over the steps that start in the window
(the program's spans)."""
import programread


def read(w):
    if w.program is None:
        return None
    return programread.host_ms_per_step(w.program, w.window_ns)
