"""img_per_s: images answered in the window over the window's seconds."""


def read(w):
    return w.images_in_window / w.seconds
