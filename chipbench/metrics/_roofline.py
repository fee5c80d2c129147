"""Shared arithmetic of the kernel roofline readers: the least time of
the kernel's calls in the traced steps (yardstick) over the summed
durations of its events."""
from chipbench import yardstick


def share(win, kernel):
    t = win.trace or {}
    steps = t.get("steps") or []
    spent = t.get("kernels", {}).get(kernel, 0.0)
    if not steps or spent <= 0:
        return None
    bfp = win.fields.get("bfp") or {}
    least = sum(yardstick.kernel_least_seconds(
        win.convs, kernel, s["batch"], win.peak,
        mantissa_bits=int(bfp.get("mantissa_bits", 10)),
        block=int(bfp.get("block_size", 32))) for s in steps)
    return 100.0 * least / spent
