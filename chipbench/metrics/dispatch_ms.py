"""Mean wall of the scheduler's dispatch stage per batch over the
window: the program's own ``dispatch_busy_s`` counter (the same walls
as its CostBook ``mb_dispatch_s`` series) over the batches formed."""


def read(win):
    n = win.counters["batches"]
    return win.counters["dispatch_s"] / n * 1e3 if n else None
