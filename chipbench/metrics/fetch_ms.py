"""Mean wall of the completion stage's fetch per batch: the program's
``std.complete.fetch`` spans (device-to-host copy of the step's outputs,
the non-convergence count, the split into per-image payloads) over the
traced window's batches."""
from chipbench import stages


def read(win):
    st = stages.of(win)
    if not st or not st["fetch"]["count"]:
        return None
    return st["fetch"]["seconds"] / st["fetch"]["count"] * 1e3
