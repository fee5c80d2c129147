"""Public Winograd conv op: XLA-side tiling/input transform + Pallas MXU
contraction with fused output transform + bias/ReLU epilogue."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import winograd as wg

from .kernel import winograd_tile_matmul


def _pad_axis(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


@functools.partial(
    jax.jit,
    static_argnames=("padding", "bp", "bn", "bk", "relu", "interpret"),
)
def winograd_conv2d(
    x: jax.Array,              # (N, H, W, Cin) NHWC
    w: jax.Array,              # (3, 3, Cin, Cout)
    b: jax.Array | None = None,
    *,
    padding: str = "SAME",
    bp: int = 128,
    bn: int = 128,
    bk: int = 128,
    relu: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """F(4x4,3x3) convolution with the bias add and optional ReLU fused
    into the kernel's output-transform flush — one launch per conv+bias+
    ReLU microcode sequence.  ``interpret=None`` derives from the backend
    (compiled on TPU, interpreted elsewhere — see
    repro.kernels.default_interpret); pass an explicit bool to override.
    """
    n, h, wd, cin = x.shape
    kh, kw, cin2, cout = w.shape
    assert (kh, kw) == (3, 3) and cin2 == cin
    if padding == "SAME":
        ph, out_h, out_w = 1, h, wd
    elif padding == "VALID":
        ph, out_h, out_w = 0, h - 2, wd - 2
    else:
        raise ValueError(padding)
    th = -(-out_h // wg.TILE_OUT)
    tw = -(-out_w // wg.TILE_OUT)
    need_h = th * wg.TILE_OUT + 2
    need_w = tw * wg.TILE_OUT + 2
    P = n * th * tw
    bp_ = min(bp, P)
    bn_ = min(bn, cout)
    bk_ = min(bk, cin)
    # everything around the kernel call is layout work in XLA: scope
    # winograd_io (what the benchmark's winograd_io_share reads)
    with jax.named_scope("winograd_io"):
        xp = jnp.pad(
            x.astype(jnp.float32),
            ((0, 0), (ph, need_h - h - ph), (ph, need_w - wd - ph), (0, 0)),
        )
        # tile extraction + input transform
        idx_h = (jnp.arange(th) * wg.TILE_OUT)[:, None] + jnp.arange(
            wg.TILE_IN)
        idx_w = (jnp.arange(tw) * wg.TILE_OUT)[:, None] + jnp.arange(
            wg.TILE_IN)
        tiles = xp[:, idx_h][:, :, :, idx_w]          # (N, th, 6, tw, 6, C)
        tiles = jnp.moveaxis(tiles, 2, 3)             # (N, th, tw, 6, 6, C)
        v = wg.transform_input(jnp.moveaxis(tiles, -1, -3))  # (N,th,tw,C,6,6)
        v = v.reshape(P, cin, 36).transpose(2, 0, 1)  # (36, P, Cin)
        u = wg.transform_weights(w.astype(jnp.float32))      # (6,6,Cin,Cout)
        u = u.reshape(36, cin, cout)
        # pad P/Cin/Cout to tile multiples for the kernel grid
        vp = _pad_axis(_pad_axis(v, bp_, 1), bk_, 2)
        up = _pad_axis(_pad_axis(u, bk_, 1), bn_, 2)
        bias = None if b is None else _pad_axis(b.astype(jnp.float32), bn_, 0)
    y = winograd_tile_matmul(
        vp, up, bias, bp=bp_, bn=bn_, bk=bk_, relu=relu,
        interpret=interpret,
    )
    with jax.named_scope("winograd_io"):
        y = y[:, :P, :cout]                           # (16, P, Cout)
        y = y.reshape(wg.TILE_OUT, wg.TILE_OUT, n, th, tw, cout)
        return y.transpose(2, 3, 0, 4, 1, 5).reshape(
            n, th * wg.TILE_OUT, tw * wg.TILE_OUT, cout
        )[:, :out_h, :out_w, :]
