"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / (window), averaged over the
cell's chips.  Moves ``samples_per_s``: every idle gap between or inside
rounds lengthens the window's wall time."""


def read(run):
    if run.trace.window_s <= 0 or not run.trace.ops:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
