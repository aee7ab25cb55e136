"""Examples completed per second over the window: all steps between the
first and the last completion timestamp, all chips of the cell together."""


def read(ctx):
    stamps = ctx["obs"]["stamps"]
    if len(stamps) < 2:
        return None
    return ctx["obs"]["batch"] * (len(stamps) - 1) / (stamps[-1] - stamps[0])
