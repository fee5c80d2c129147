"""Set-up: process start to the first due request (weights made from
the seed, the image pool, the cell's engines loaded and warmed)."""


def read(win):
    return win.setup_s
