"""Public BFP matmul op: quantize (Algorithm 1) then run the Pallas kernel.

The quantization step is the paper's "model weight normalization" /
activation normalization module (Fig. 6); in production weights are
quantized once at load time (see ``models/lm`` BFP mode) while activations
are quantized on the fly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bfp as bfp_lib
import repro.kernels

from .kernel import bfp_matmul_quantized


def _mantissa_dtype(mantissa_bits: int):
    if mantissa_bits <= 7:
        return jnp.int8
    if mantissa_bits <= 15:
        return jnp.int16
    return jnp.int32


def _pad_to(x: jax.Array, m: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % m
    if not pad:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_size", "mantissa_bits", "rounding", "bm", "bn", "bk",
        "interpret",
    ),
)
def bfp_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_size: int = bfp_lib.DEFAULT_BLOCK,
    mantissa_bits: int = bfp_lib.DEFAULT_MANTISSA,
    rounding: str = "trunc",
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """C = A @ B through shared-exponent BFP (A:(M,K), B:(K,N)).
    ``interpret=None`` derives from the backend (compiled on TPU,
    interpreted elsewhere — see repro.kernels.default_interpret)."""
    if interpret is None:
        interpret = repro.kernels.default_interpret()
    M, K = a.shape
    _, N = b.shape
    qa = bfp_lib.quantize(
        a, block_size=block_size, mantissa_bits=mantissa_bits, axis=-1,
        rounding=rounding,
    )
    qb = bfp_lib.quantize(
        b, block_size=block_size, mantissa_bits=mantissa_bits, axis=0,
        rounding=rounding,
    )
    mdt = _mantissa_dtype(mantissa_bits)
    # pad every dim to tile multiples (zero mantissa == exact zero value)
    bm_ = min(bm, max(8, M))
    bn_ = min(bn, max(128, N)) if N >= 128 else N
    # K tile must stay a multiple of the BFP block so exponent tiles align
    k_blocks = -(-K // block_size)
    bk_ = min(bk, k_blocks * block_size)
    bk_ = (bk_ // block_size) * block_size
    ma = _pad_to(_pad_to(qa.mantissa.astype(mdt), bm_, 0), bk_, 1)
    ea = _pad_to(_pad_to(qa.exponent, bm_, 0), bk_ // block_size, 1)
    mb = _pad_to(_pad_to(qb.mantissa.astype(mdt), bk_, 0), bn_, 1)
    eb = _pad_to(_pad_to(qb.exponent, bn_, 0), bk_ // block_size, 1)
    out = bfp_matmul_quantized(
        ma, ea, mb, eb,
        block_size=block_size, mantissa_bits=mantissa_bits,
        bm=bm_, bn=bn_, bk=bk_, interpret=interpret,
    )
    return out[:M, :N]
