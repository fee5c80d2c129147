"""Share of the engine step's device time spent in the connected
components tail (label propagation over the score and link maps): ops
under the program's ``cc_tail`` scope over the complete steps' device
time (chipbench/stages)."""
from chipbench.stages import scope_share


def read(win):
    return scope_share(win, "cc_tail")
