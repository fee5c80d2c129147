"""BFP matmul Pallas kernel — paper C2 adapted to TPU (DESIGN.md §2).

The FPGA runs fixed-point MACs on shared-exponent mantissas because DSPs
are cheap and FP is expensive.  On TPU the MXU is already fixed-function;
what BFP buys is *HBM/ICI bandwidth*: the kernel streams int8 mantissas
(one int8 exponent per `block_size` values) from HBM — a 4x reduction
versus f32 and 2x versus bf16 — dequantizes in VMEM on the VPU, and runs
the MXU in f32 with full-width accumulation (the §IV.C wide-accumulator
discipline: inputs are quantized, the accumulator never is).

Tiling: grid (M/bm, N/bn, K/bk), K innermost so the f32 accumulator tile
lives in a VMEM scratch across the K sweep.  `bk` must be a multiple of
the BFP block size so exponent tiles align with mantissa tiles.

Exponent layout: one slab per K tile, ``(K/bk, rows, bk/bs)``.  A block
``(1, bm, bk/bs)`` then spans the slab's full last dimension, which the
TPU compiler requires of any block narrower than 128 lanes (the flat
``(rows, K/bs)`` layout is refused as soon as K spans more than one tile).

VMEM budget per step (defaults bm=bn=256, bk=512, bs=32):
    A mantissa  256*512   int8   = 128 KiB     (x2 for pipeline ping-pong)
    B mantissa  512*256   int8   = 128 KiB
    exponents   256*16*2  int8   =   8 KiB
    accumulator 256*256   f32    = 256 KiB
  ~0.9 MiB with double buffering — far under the ~16 MiB/core class
  budget, leaving room for the compiler to widen tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bfp_matmul_kernel(
    ma_ref,      # (bm, bk)   int8/int16 mantissas of A
    ea_ref,      # (1, bm, bk//bs) int32 block exponents of A
    mb_ref,      # (bk, bn)   mantissas of B
    eb_ref,      # (1, bn, bk//bs) int32 block exponents of B (N-major)
    o_ref,       # (bm, bn)   f32 out
    acc_ref,     # (bm, bn)   f32 VMEM scratch
    *,
    block_size: int,
    mantissa_bits: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequantize tiles in VMEM (VPU work): value = m * 2^(e - mantissa_bits)
    # (exact power-of-two via exponent-field bitcast — see core.bfp.exp2i)
    from repro.core.bfp import exp2i

    ea = jnp.repeat(ea_ref[0], block_size, axis=1)              # (bm, bk)
    a = ma_ref[...].astype(jnp.float32) * exp2i(ea - mantissa_bits)
    eb = jnp.repeat(eb_ref[0], block_size, axis=1)              # (bn, bk)
    b = mb_ref[...].astype(jnp.float32) * exp2i(eb - mantissa_bits).T
    # MXU contraction with f32 (wide) accumulation; HIGHEST keeps the
    # 11-bit signed mantissas exact (a bf16 pass would round them)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_size", "mantissa_bits", "bm", "bn", "bk", "interpret",
    ),
)
def bfp_matmul_quantized(
    ma: jax.Array,   # (M, K) int mantissas
    ea: jax.Array,   # (M, K//bs) int32 exponents
    mb: jax.Array,   # (K, N) int mantissas
    eb: jax.Array,   # (N, K//bs) int32 exponents
    *,
    block_size: int,
    mantissa_bits: int,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, K = ma.shape
    K2, N = mb.shape
    assert K == K2 and K % block_size == 0
    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk % block_size == 0
    ebk = bk // block_size
    # (rows, K/bs) -> (K/bk, rows, bk/bs): one exponent slab per K tile
    slabs = lambda e: e.reshape(e.shape[0], K // bk, ebk).transpose(1, 0, 2)

    return pl.pallas_call(
        functools.partial(
            _bfp_matmul_kernel,
            block_size=block_size,
            mantissa_bits=mantissa_bits,
        ),
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, bm, ebk), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn, ebk), lambda i, j, k: (k, j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(ma, slabs(ea), mb, slabs(eb))
