"""Block floating-point (BFP) — paper Algorithm 1 + §III.E / §IV.C.

The paper stores activations/weights in FP16 and computes MACs on
block-floating-point mantissas: every block of N numbers shares the block's
maximum exponent; mantissas are right-shifted by the exponent difference
(Algorithm 1) so the MAC array runs pure fixed-point.  Partial sums use a
widened 15-bit mantissa and are truncated back to storage precision only at
the end (§IV.C "accuracy maintenance") — i.e. *quantize the inputs, never
narrow the accumulator*.

TPU adaptation (see DESIGN.md §2): the MXU natively accumulates in f32, so
the wide-accumulator discipline is expressed as int/f32 accumulation over
shared-exponent integer mantissas.  What BFP buys on TPU is *bandwidth*
(an 8-bit mantissa block with one exponent per 32 values is ~4x smaller
than f32 and ~2x smaller than bf16), so the same quantizer here feeds

  * the BFP matmul kernels (forward compute, kernels/bfp_matmul),
  * compressed gradient all-reduce (optim/grad_utils),
  * 8-bit Adam moments (optim/optimizers).

All functions are pure and jit-friendly.  ``quantize`` is bit-exact to
Algorithm 1 (integer mantissas, arithmetic right shift == hardware
truncation); tests cross-check against a numpy oracle.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BLOCK = 32          # values per shared exponent (paper: norm block)
DEFAULT_MANTISSA = 10       # FP16 mantissa width used by the paper
WIDE_MANTISSA = 15          # paper's widened accumulator mantissa


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BFPTensor:
    """A block-floating-point tensor.

    ``mantissa`` is a signed integer tensor with the original shape;
    ``exponent`` holds one power-of-two exponent per block along ``axis``,
    laid out as ``moveaxis(x, axis, -1).shape[:-1] + (n_blocks,)``.  The
    represented value is ``mantissa * 2**(exponent - mantissa_bits)``.
    """

    mantissa: jax.Array          # int-valued (stored int8/int16/int32)
    exponent: jax.Array          # int32, per block
    mantissa_bits: int
    block_size: int
    axis: int

    def tree_flatten(self):
        return (self.mantissa, self.exponent), (
            self.mantissa_bits,
            self.block_size,
            self.axis,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        m, e = children
        return cls(m, e, *aux)

    @property
    def shape(self):
        return self.mantissa.shape

    def nbytes_model(self) -> int:
        """Modelled storage cost (what HBM/ICI would carry on TPU)."""
        mbytes = 1 if self.mantissa_bits <= 7 else (2 if self.mantissa_bits <= 15 else 4)
        return int(
            np.prod(self.mantissa.shape) * mbytes
            + np.prod(self.exponent.shape)  # 1 byte/exponent
        )


def exp2i(e: jax.Array) -> jax.Array:
    """EXACT 2**e for integer e — jnp.exp2 is exp(x*ln2) on some backends
    and is off by an ulp, which breaks bit-exactness vs Algorithm 1.
    Builds the f32 exponent field directly; e clamped to normal range
    (out-of-range only happens for all-zero blocks, where mantissas are 0)."""
    e = jnp.clip(e.astype(jnp.int32), -126, 127)
    bits = ((e + 127) << 23).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _blockify(x: jax.Array, block_size: int, axis: int) -> Tuple[jax.Array, tuple]:
    """Reshape so blocks are contiguous on a new trailing axis."""
    axis = axis % x.ndim
    x = jnp.moveaxis(x, axis, -1)
    orig = x.shape
    n = orig[-1]
    # an axis shorter than one block is one block: padding it (3 image
    # channels to 32) gives the same exponents, since zeros never win the
    # block max, but multiplies every intermediate's size
    block_size = min(block_size, n)
    pad = (-n) % block_size
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    x = x.reshape(*x.shape[:-1], (n + pad) // block_size, block_size)
    return x, orig


def _unblockify(x: jax.Array, orig: tuple, axis: int, ndim: int) -> jax.Array:
    x = x.reshape(*x.shape[:-2], -1)[..., : orig[-1]]
    return jnp.moveaxis(x, -1, axis % ndim)


@partial(jax.jit, static_argnames=("block_size", "mantissa_bits", "axis", "rounding"))
def quantize(
    x: jax.Array,
    *,
    block_size: int = DEFAULT_BLOCK,
    mantissa_bits: int = DEFAULT_MANTISSA,
    axis: int = -1,
    rounding: str = "trunc",
) -> BFPTensor:
    """Algorithm 1 — BFP normalization.

    For block X = (m_1 2^{e_1}, ..., m_N 2^{e_N}):
        xi = max_i e_i;  d_i = xi - e_i;  m_bi = m_i >> d_i
    ``rounding='trunc'`` matches the hardware right-shift; ``'nearest'``
    adds half-ulp before shifting (the software toolchain option).
    """
    if rounding not in ("trunc", "nearest"):
        raise ValueError(rounding)
    xb, orig = _blockify(x.astype(jnp.float32), block_size, axis)
    m, e = jnp.frexp(xb)                      # x = m * 2**e, |m| in [0.5, 1)
    e = jnp.where(xb == 0, -(2**30), e)       # zeros never win the max
    xi = jnp.max(e, axis=-1, keepdims=True)   # block max exponent
    xi = jnp.maximum(xi, -(2**29))            # all-zero block -> harmless exp
    d = xi - e                                # shift distances >= 0
    # integer mantissa with `mantissa_bits` fractional bits of |m| < 1:
    mi = m * (1 << mantissa_bits)
    mi = jnp.trunc(mi).astype(jnp.int32)      # frexp mantissa is exact in f32
    d = jnp.minimum(d, 31)
    if rounding == "nearest":
        # add +/- half of the soon-to-be-dropped ulp before shifting
        half = jnp.where(d > 0, (1 << jnp.maximum(d - 1, 0)), 0)
        mi = mi + jnp.sign(mi).astype(jnp.int32) * half
    mb = mi >> d                              # arithmetic shift == truncation
    mb = _unblockify(mb, orig, axis, x.ndim)
    exponent = jnp.squeeze(xi, -1).astype(jnp.int32)
    # store the axis in NEGATIVE form: BFPTensor leaves get sliced along
    # leading (layer-stack) dims by lax.scan, and a last-relative axis
    # stays valid under that slicing
    axis_store = axis if axis < 0 else axis - x.ndim
    return BFPTensor(mb, exponent, mantissa_bits, block_size, axis_store)


@jax.jit
def dequantize(t: BFPTensor) -> jax.Array:
    mb, orig = _blockify(t.mantissa.astype(jnp.float32), t.block_size, t.axis)
    scale = exp2i(t.exponent - t.mantissa_bits)
    out = mb * scale[..., None]
    return _unblockify(out, orig, t.axis, t.mantissa.ndim)


def roundtrip(
    x: jax.Array,
    *,
    block_size: int = DEFAULT_BLOCK,
    mantissa_bits: int = DEFAULT_MANTISSA,
    axis: int = -1,
    rounding: str = "trunc",
) -> jax.Array:
    """Quantize-dequantize: the numerical effect of running through BFP."""
    return dequantize(
        quantize(
            x,
            block_size=block_size,
            mantissa_bits=mantissa_bits,
            axis=axis,
            rounding=rounding,
        )
    ).astype(x.dtype)


def quantization_error(x: jax.Array, **kw) -> jax.Array:
    """Mean relative error introduced by BFP — used by precision benches."""
    y = roundtrip(x, **kw)
    denom = jnp.maximum(jnp.abs(x), 1e-12)
    return jnp.mean(jnp.abs(x - y) / denom)


# ---------------------------------------------------------------------------
# BFP matmul semantics (the oracle mirrored by kernels/bfp_matmul).
# ---------------------------------------------------------------------------

def bfp_matmul_reference(
    a: jax.Array,
    b: jax.Array,
    *,
    block_size: int = DEFAULT_BLOCK,
    mantissa_bits: int = DEFAULT_MANTISSA,
    rounding: str = "trunc",
    wide_accum: bool = True,
) -> jax.Array:
    """C = A @ B with both operands BFP-quantized along the contraction dim.

    A: (M, K) blocked along K; B: (K, N) blocked along K.  Within a block
    the mantissa dot is exact integer arithmetic (the paper's fixed-point
    MAC); across blocks partial sums accumulate in f32 — the widened
    accumulator of §IV.C.  ``wide_accum=False`` truncates every partial sum
    back to `mantissa_bits` (the failure mode the paper's Fig. 7 fixes),
    used by the Table VI precision benchmark.
    """
    qa = quantize(a, block_size=block_size, mantissa_bits=mantissa_bits,
                  axis=-1, rounding=rounding)
    qb = quantize(b, block_size=block_size, mantissa_bits=mantissa_bits,
                  axis=0, rounding=rounding)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    nb = -(-K // block_size)
    pad = nb * block_size - K
    ma = jnp.pad(qa.mantissa, ((0, 0), (0, pad))).reshape(M, nb, block_size)
    mb = jnp.pad(qb.mantissa, ((0, pad), (0, 0))).reshape(nb, block_size, N)
    # exponent layout: quantization axis moved last then blocked, so
    # qa.exponent is (M, nb) and qb.exponent (axis=0) is (N, nb).
    ea = qa.exponent                                   # (M, nb)
    eb = qb.exponent.T                                 # (nb, N)
    # exact int32 dot per block (mantissas fit in mantissa_bits each, block
    # sums fit easily in f32's 24-bit exact-integer range for mb<=11, and in
    # int32 generally; use f32 einsum over ints for MXU-shaped math):
    partial = jnp.einsum(
        "mkb,kbn->kmn",
        ma.astype(jnp.float32),
        mb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )                                                   # (nb, M, N)
    scale = exp2i(
        ea.T[:, :, None] + eb[:, None, :] - 2 * mantissa_bits
    )                                                   # (nb, M, N)
    contrib = partial * scale
    if wide_accum:
        return jnp.sum(contrib, axis=0)
    # narrow accumulator: truncate each running partial sum to mantissa_bits
    def body(carry, c):
        s = carry + c
        s = roundtrip(s, block_size=s.shape[-1], mantissa_bits=mantissa_bits,
                      axis=-1, rounding="trunc")
        return s, None
    out, _ = jax.lax.scan(body, jnp.zeros((M, N), jnp.float32), contrib)
    return out
