"""Winograd F(4x4,3x3) Pallas kernel — paper C3 on the MXU.

Split of work (DESIGN.md §2): the input transform BᵀXB is a small
data-layout computation done in XLA (ops.py); this kernel runs the part
the paper puts on its DSP supertile arrays — the 36 per-position
(tiles × Cin) · (Cin × Cout) contractions — on the MXU, and *fuses the
output transform AᵀYA in-kernel*.  Fusing the output transform matters on
TPU: the intermediate M tensor is 36/16 = 2.25x the output size, so
writing it to HBM would more than double the kernel's write traffic.

The conv's bias add and ReLU ride the same flush (paper Fig. 5: the
microcode's per-layer ReLU flag drives a datapath epilogue, not a
separate pass) — one launch covers contraction, output transform, bias,
and activation, so the optimized engine issues a single dispatch per
fused conv+bias+ReLU microcode word.

Layout: position-major.  V is (36, P, Cin) and the output (16, P, Cout),
so every per-position slice ``v_ref[t]`` / ``o_ref[r]`` is a plain 2-D
(sublane, lane) tile: no in-kernel transpose or reshape, which the TPU
compiler refuses for these shapes.

Grid: (P/bp, Cout/bn, Cin/bk) with Cin innermost; the (36, bp, bn) f32
accumulator lives in VMEM scratch across the Cin sweep.  The flush is the
output transform written out as static sums over the 36 accumulator
slices with Aᵀ's coefficients (zeros skipped, ±1 as add/sub).

VMEM per step (bp=128, bn=128, bk=128):
    V tile   36*128*128*4  = 2.25 MiB   (x2 ping-pong)
    U tile   36*128*128*4  = 2.25 MiB   (x2 ping-pong)
    acc      36*128*128*4  = 2.25 MiB
    out      16*128*128*4  = 1.00 MiB   (x2 ping-pong)
  ~13.3 MiB plus the flush's 24 row sums (1.5 MiB): inside the v5e
  default scoped VMEM limit (16 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.winograd import AT
import repro.kernels


def _lincomb(coeffs, terms):
    """sum(c * t) with static coefficients: zeros skipped, +-1 as add/sub."""
    out = None
    for c, t in zip(coeffs, terms):
        c = float(c)
        if c == 0.0:
            continue
        if c == -1.0:
            out = -t if out is None else out - t
            continue
        term = t if c == 1.0 else t * c
        out = term if out is None else out + term
    return out


def _winograd_mm_kernel(v_ref, u_ref, b_ref, o_ref, acc_ref, *, relu: bool):
    """v: (36, bp, bk); u: (36, bk, bn); b: (1, bn); o: (16, bp, bn)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # 36 independent MXU contractions, one per tile position
    for t in range(36):
        acc_ref[t] += jnp.dot(
            v_ref[t].astype(jnp.float32), u_ref[t].astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        # Y = Aᵀ M A with M[j, c] = acc[6j + c]: rows first (R = Aᵀ M),
        # then columns (Y = R A), all static VPU sums
        rows = [[_lincomb(AT[i], [acc_ref[6 * j + c] for j in range(6)])
                 for c in range(6)] for i in range(4)]
        bias = b_ref[...]                 # (1, bn)
        for i in range(4):
            for l in range(4):
                y = _lincomb(AT[l], rows[i]) + bias
                if relu:
                    y = jnp.maximum(y, 0.0)
                o_ref[4 * i + l] = y


@functools.partial(
    jax.jit, static_argnames=("bp", "bn", "bk", "relu", "interpret")
)
def winograd_tile_matmul(
    v: jax.Array,          # (36, P, Cin)  transformed input tiles
    u: jax.Array,          # (36, Cin, Cout) transformed weights (G W Gᵀ)
    b: jax.Array | None = None,            # (Cout,) fused bias
    *,
    bp: int = 128,
    bn: int = 128,
    bk: int = 128,
    relu: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (16, P, Cout) output tiles (4x4 row-major per tile), with
    the bias add and optional ReLU fused into the output-transform flush
    (``interpret=None`` derives from the backend — see
    repro.kernels.default_interpret)."""
    if interpret is None:
        interpret = repro.kernels.default_interpret()
    t36, P, K = v.shape
    _, _, N = u.shape
    assert t36 == 36
    bp = min(bp, P)
    bn = min(bn, N)
    bk = min(bk, K)
    assert P % bp == 0 and N % bn == 0 and K % bk == 0, (P, N, K, bp, bn, bk)
    bias = (jnp.zeros((1, N), jnp.float32) if b is None
            else b.astype(jnp.float32).reshape(1, N))
    return pl.pallas_call(
        functools.partial(_winograd_mm_kernel, relu=relu),
        grid=(P // bp, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((36, bp, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((36, bk, bn), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((16, bp, bn), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((16, P, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((36, bp, bn), jnp.float32)],
        interpret=interpret,
    )(v, u, bias)
