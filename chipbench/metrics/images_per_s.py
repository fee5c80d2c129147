"""Requests completed inside the window over the window's seconds."""


def read(win):
    end = win.t0 + win.seconds
    n = sum(1 for t in win.reqs.done.values() if win.t0 <= t <= end)
    return n / win.seconds
