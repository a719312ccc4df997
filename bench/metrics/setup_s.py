"""setup_s: process start to the first timed request: start-up, weights,
packing, server construction, warm-up (compiles or cache loads) and the
traffic's images."""


def read(w):
    return w.setup_s
