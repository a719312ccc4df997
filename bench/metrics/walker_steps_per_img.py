"""walker_steps_per_img: the walker's scheduled grid steps per image, from
the server's schedule counters (all chips) over its slots."""


def read(w):
    if not w.schedule:
        return None
    return w.schedule["scheduled_steps"] / w.slots
