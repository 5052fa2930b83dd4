"""How unevenly the cell's devices were busy over the traced window
(profiler trace): the largest device's busy seconds (the union of its
device-op intervals) over the mean of every device's. 1.0 when every
chip works alike; the device count when one chip does all the work.
None where the trace holds fewer than two devices or no busy time."""


def read(run):
    busy = run.trace["busy_s"] if run.trace else []
    if len(busy) < 2 or not sum(busy):
        return None
    return max(busy) / (sum(busy) / len(busy))
