"""Public BFP matmul op: C = A @ B through shared-exponent BFP.

The quantization is the paper's "model weight normalization" /
activation normalization module (Fig. 6): weights are normalized once
(here per call, in production at load time by
``FCNEngine.normalize_weights``), activations on the fly inside the
kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bfp as bfp_lib

from .kernel import bfp_matmul_quantized


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "mantissa_bits", "rounding", "interpret"),
)
def bfp_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_size: int = bfp_lib.DEFAULT_BLOCK,
    mantissa_bits: int = bfp_lib.DEFAULT_MANTISSA,
    rounding: str = "trunc",
    interpret: bool | None = None,
) -> jax.Array:
    """C = A @ B with both operands BFP-quantized along K (A:(M,K),
    B:(K,N)).  ``interpret=None`` derives from the backend (compiled on
    TPU, interpreted elsewhere — see repro.kernels.default_interpret)."""
    wq = bfp_lib.roundtrip(
        b.astype(jnp.float32), block_size=block_size,
        mantissa_bits=mantissa_bits, axis=0, rounding=rounding,
    )
    return bfp_matmul_quantized(
        a, wq, block_size=block_size, mantissa_bits=mantissa_bits,
        rounding=rounding, interpret=interpret,
    )
