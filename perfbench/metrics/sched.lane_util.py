"""Mean active lanes over lanes at the window's tick boundaries (the
scheduler's own timeline), in %."""


def read(run):
    al = run["active_lanes"]
    return sum(al) / (len(al) * run["lanes"]) * 100 if al else None
