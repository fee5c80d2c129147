"""Pallas TPU kernels for the paper's compute hot-spots.

Each package ships three layers:
  kernel.py  pl.pallas_call body + BlockSpec VMEM tiling (TPU target)
  ops.py     jit'd public wrapper (layout, quantization, padding)
  ref.py     pure-jnp oracle used by tests and as the interpreter fallback

  bfp_matmul/       paper C2 — a 1x1 BFP conv in one launch: the f16
                    activation quantized in VMEM, load-time BFP weights,
                    f32 wide accumulation (§IV.C), bias/ReLU flush; and
                    the same quantization as one round-trip pass over the
                    activation of every other conv
  winograd_conv/    paper C3 — F(4x4,3x3), 36 MXU contractions per tile,
                    output transform fused in-kernel
  flash_attention/  blockwise online-softmax GQA attention (prefill path)
  ssd_scan/         Mamba2 state-space-dual intra-chunk quadratic kernel
  cc_label/         paper §III.A — PixelLink CC labeling, tile-local
                    VMEM convergence + global log-hop merge rounds

Every public op takes ``interpret`` (default ``None`` = derive from the
backend via :func:`default_interpret`): the kernel bodies target the TPU
Mosaic compiler (``pltpu.VMEM`` scratch, MXU dot shapes), so everywhere
else they execute through the Pallas interpreter — which makes opting
into the kernels (``use_pallas=True``) safe on any backend, just not
fast off-TPU.
"""
from __future__ import annotations


def default_interpret() -> bool:
    """Whether Pallas calls should run interpreted on this backend.

    The kernels here compile with the TPU Mosaic backend only; on cpu/gpu
    the interpreter is the working path.  Resolved at trace time so the
    decision follows the backend the enclosing jit actually lowers for.
    """
    import jax

    return jax.default_backend() != "tpu"
