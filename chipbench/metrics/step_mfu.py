"""Model FLOP utilisation of the engine step: the credited model FLOPs
of the live images in the traced steps (chipbench/yardstick's rule:
3x3 stride-1 convolutions and the upsample at a quarter of dense) over
the summed device time of those steps times the chip's bf16 peak."""
from chipbench import yardstick


def read(win):
    steps = (win.trace or {}).get("steps") or []
    if not steps:
        return None
    live = sum(s["live"] for s in steps)
    busy = sum(s["seconds"] for s in steps)
    flops = 2 * yardstick.model_macs(win.convs) * live
    return 100.0 * flops / (busy * win.peak["bf16_flops"])
