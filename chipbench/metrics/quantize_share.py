"""Share of the engine step's device time spent in the BFP round trips
of activations and weights (Algorithm 1 on both operands of each conv
the XLA path runs): ops under the program's ``bfp_roundtrip`` scope
over the complete steps' device time (chipbench/stages)."""
from chipbench.stages import scope_share


def read(win):
    return scope_share(win, "bfp_roundtrip")
