"""Block-local CC propagation kernel — phase 1 of the Pallas CCL path.

Each grid step owns one (th, tw) tile of one image and iterates the
PixelLink one-hop max-label spread entirely in VMEM until the tile stops
changing.  Label values are opaque here (just monotone max propagation),
so tiles converge independently; the cross-tile merge is phase 2 in
ops.py (global log-hop rounds).  The payoff is HBM traffic: the naive
while_loop re-reads and re-writes the full plane every hop, while this
kernel touches HBM once per tile no matter how many local hops the tile
needs.

Grid: (N, H/th, W/tw); blocks are (1, th, tw) label/positive planes and
(1, 8, th, tw) link stacks (direction-major, so each direction is a plain
2-D tile), int32 throughout (TPU-friendly — the bool masks are rebuilt
in-register).  The TPU compiler takes a block whose last dim is a
multiple of 128 or the whole plane width, and whose second-to-last is a
multiple of 8 or the whole plane height; ops.py picks and pads to such
tiles.  Shifts are ``pltpu.roll`` (the TPU lane/sublane rotate); iota
row/col masks zero the wrapped rows/cols, so a tile edge behaves exactly
like an image edge, which is what makes the phase block-local.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import repro.kernels
from repro.models.fcn.postprocess import NEIGHBORS


def _local_cc_kernel(lab_ref, pos_ref, lnk_ref, out_ref, *, th: int,
                     tw: int):
    """lab/pos: (1, th, tw) int32; lnk: (1, 8, th, tw) int32."""
    lab = lab_ref[0]
    pos = pos_ref[0] != 0
    lnk = [(lnk_ref[0, d] != 0) & pos for d in range(len(NEIGHBORS))]
    rows = jax.lax.broadcasted_iota(jnp.int32, (th, tw), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (th, tw), 1)

    def spread(l):
        out = l
        for d, (dy, dx) in enumerate(NEIGHBORS):
            # sh[y, x] = l[y + dy, x + dx]  (jnp.roll by (-dy, -dx))
            sh = l
            if dy:
                sh = pltpu.roll(sh, (-dy) % th, 0)
            if dx:
                sh = pltpu.roll(sh, (-dx) % tw, 1)
            # zero the wrapped rows/cols: the tile edge acts as an image
            # edge, keeping the propagation strictly block-local
            if dy == 1:
                sh = jnp.where(rows < th - 1, sh, 0)
            elif dy == -1:
                sh = jnp.where(rows > 0, sh, 0)
            if dx == 1:
                sh = jnp.where(cols < tw - 1, sh, 0)
            elif dx == -1:
                sh = jnp.where(cols > 0, sh, 0)
            out = jnp.where(lnk[d], jnp.maximum(out, sh), out)
        return jnp.where(pos, out, 0)

    def cond(state):
        _, changed, it = state
        # local fixpoint is reached in <= tile pixel-count hops (a label
        # value strictly grows somewhere every non-final iteration)
        return changed & (it < th * tw)

    def body(state):
        l, _, it = state
        new = spread(l)
        changed = jnp.max(jnp.where(new != l, 1, 0)) > 0
        return new, changed, it + 1

    lab, _, _ = jax.lax.while_loop(
        cond, body, (lab, jnp.bool_(True), jnp.int32(0))
    )
    out_ref[0] = lab


@functools.partial(jax.jit, static_argnames=("th", "tw", "interpret"))
def local_spread_converge(
    labels: jax.Array,         # (N, H, W) int32 initial label map
    pos: jax.Array,            # (N, H, W) int32 (0/1 positive mask)
    lnk: jax.Array,            # (N, 8, H, W) int32 (0/1 symmetrized links)
    *,
    th: int = 32,
    tw: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Run every (th, tw) tile to its local spread fixpoint in VMEM.

    Returns the (N, H, W) int32 label map with all within-tile
    propagation complete; cross-tile merging is the caller's phase 2
    (``interpret=None`` derives from the backend — see
    repro.kernels.default_interpret)."""
    if interpret is None:
        interpret = repro.kernels.default_interpret()
    N, H, W = labels.shape
    assert H % th == 0 and W % tw == 0, (H, W, th, tw)
    return pl.pallas_call(
        functools.partial(_local_cc_kernel, th=th, tw=tw),
        grid=(N, H // th, W // tw),
        in_specs=[
            pl.BlockSpec((1, th, tw), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, th, tw), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, 8, th, tw), lambda b, i, j: (b, 0, i, j)),
        ],
        out_specs=pl.BlockSpec((1, th, tw), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((N, H, W), jnp.int32),
        interpret=interpret,
    )(labels, pos, lnk)
