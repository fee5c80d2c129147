"""The benchmark's fixed arithmetic: chip peaks, and the operations and
bytes of the model and of each Pallas kernel call, all computed from
the plain reference's layer list (references/), never from the program.

Crediting rule for the model's work (``credited_macs``): a 3x3 stride-1
convolution is credited at the Winograd F(4x4, 3x3) multiply count, 36
per 4x4 output tile where direct convolution needs 144 (a quarter of
dense), and the merge's 2x upsample convolution at its non-zero taps
(9 per 4 outputs, a quarter of the zero-inserted dense count).  Every
other convolution is credited at its dense count.  A dense credit for
work the served path provably skips would let a share of peak pass
100%.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

#: Published per-chip peaks, by ``jax.Device.device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e" (Cloud TPU system architecture):
#: 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GiB HBM.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for a device kind; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add a sourced row to "
                       f"chipbench/yardstick.PEAKS") from None


def dense_macs(cv) -> int:
    """Multiply-accumulates of one convolution as written (for the
    upsample: the 3x3 convolution over the zero-inserted plane)."""
    oh, ow = cv.out_hw
    return oh * ow * cv.k * cv.k * cv.cin * cv.cout


def credited_macs(cv) -> int:
    """Multiply-accumulates the served algorithm needs (module doc)."""
    if cv.kind == "upsample" or (cv.k == 3 and cv.stride == 1):
        return dense_macs(cv) // 4
    return dense_macs(cv)


def model_macs(convs, *, credited: bool = True) -> int:
    f = credited_macs if credited else dense_macs
    return sum(f(cv) for cv in convs)


def is_bfp_matmul(cv) -> bool:
    """A 1x1 stride-1 convolution is a matrix product: the BFP path
    runs it through the ``bfp_matmul_quantized`` kernel."""
    return cv.kind == "conv" and cv.k == 1 and cv.stride == 1


def is_winograd(cv) -> bool:
    """A 3x3 stride-1 convolution: the ``winograd_tile_matmul`` kernel."""
    return cv.kind == "conv" and cv.k == 3 and cv.stride == 1


def kernel_calls(convs) -> Dict[str, int]:
    """Kernel launches per model step, from the layer list."""
    return {"bfp_matmul_quantized": sum(map(is_bfp_matmul, convs)),
            "winograd_tile_matmul": sum(map(is_winograd, convs))}


def bfp_matmul_cost(cv, batch: int, mantissa_bits: int,
                    block: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of one BFP matmul call: C (M, N) = A (M, K) @
    B (K, N), M = batch * H * W.  Bytes are the operands as the kernel
    reads them (integer mantissas, one int32 exponent per block of
    ``block`` along K) and the float32 result."""
    m = batch * cv.out_hw[0] * cv.out_hw[1]
    k, n = cv.cin, cv.cout
    mbytes = 1 if mantissa_bits <= 7 else (2 if mantissa_bits <= 15 else 4)
    kb = -(-k // min(block, k))
    nbytes = (m * k + k * n) * mbytes + (m + n) * kb * 4 + m * n * 4
    return 2 * m * k * n, nbytes


def winograd_cost(cv, batch: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of one Winograd tile-matmul call: 36 products of
    (P, Cin) @ (Cin, Cout) over P = batch * ceil(H/4) * ceil(W/4) tiles;
    bytes are the transformed input V (36, P, Cin), the transformed
    filter U (36, Cin, Cout), the bias and the (16, P, Cout) output,
    all float32."""
    h, w = cv.out_hw
    p = batch * (-(-h // 4)) * (-(-w // 4))
    flops = 2 * 36 * p * cv.cin * cv.cout
    nbytes = 4 * (36 * p * cv.cin + 36 * cv.cin * cv.cout + cv.cout
                  + 16 * p * cv.cout)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]
                  ) -> float:
    """The roofline: the larger of compute time at the bf16 peak and
    memory time at the HBM bandwidth."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def kernel_least_seconds(convs: List, kernel: str, batch: int,
                         peak: Dict[str, float], *, mantissa_bits: int,
                         block: int) -> float:
    """Least time of all of one kernel's calls in one step at ``batch``."""
    total = 0.0
    for cv in convs:
        if kernel == "bfp_matmul_quantized" and is_bfp_matmul(cv):
            total += least_seconds(
                *bfp_matmul_cost(cv, batch, mantissa_bits, block), peak)
        elif kernel == "winograd_tile_matmul" and is_winograd(cv):
            total += least_seconds(*winograd_cost(cv, batch), peak)
    return total
