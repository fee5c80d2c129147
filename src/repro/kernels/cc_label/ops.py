"""Public Pallas CCL op: tile-local VMEM convergence + global log-hop
merge rounds.

``cc_label_pallas`` matches the semantics of
``repro.models.fcn.postprocess.cc_label_batched(hop="log")`` — same
thresholds, same valid-mask padding rule, same label values (component
max linear index + 1) — but restructures the iteration for HBM
economy:

  phase 1  one Pallas launch runs every (th, tw) tile to its local
           spread fixpoint entirely in VMEM (kernel.py), so the many
           short-range hops that dominate real text maps never touch
           HBM per-iteration;
  phase 2  global merge rounds (one-hop spread + pointer jump, the
           exact ops the postprocess module exports) stitch tiles —
           only components that CROSS tile boundaries still pay
           full-plane traffic, and the pointer jumps keep those rounds
           sublinear in component diameter.

Both phases are monotone toward the same fixpoint as the plain spread,
so labels are exactly ``cc_label_batched``'s (property-pinned against
the union-find oracle in tests/test_postprocess_device.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.cc_label.kernel import local_spread_converge
from repro.models.fcn import postprocess as pp


@functools.partial(
    jax.jit,
    static_argnames=("score_thr", "link_thr", "max_iters", "th", "tw",
                     "interpret", "return_stats"),
)
def cc_label_pallas(
    score: jax.Array,          # (N, H, W) or (H, W) probabilities
    links: jax.Array,          # (N, H, W, 8) or (H, W, 8)
    score_thr: float = 0.5,
    link_thr: float = 0.5,
    max_iters: int = 256,
    valid_mask: Optional[jax.Array] = None,
    *,
    th: int = 32,
    tw: int = 128,
    interpret: bool | None = None,
    return_stats: bool = False,
):
    """Pallas-accelerated CC labeling -> (N, H, W) int32 label map
    (0 = background, labels = component max linear index + 1 — identical
    values to ``cc_label_batched``).

    ``max_iters`` bounds the PHASE-2 merge rounds per image (phase 1
    always reaches the tile-local fixpoint); with ``return_stats`` the
    result is ``(labels, iters, converged)`` where ``iters`` counts
    merge rounds and ``converged`` is per-image.  A plane no larger than
    a tile is one tile (whole-plane blocks are always TPU-legal); larger
    planes are zero-padded to (th, tw) multiples for phase 1 only — keep
    ``th`` a multiple of 8 and ``tw`` of 128 on TPU.  Label values always
    index the ORIGINAL plane, and padding can never grow or merge
    components (padded pixels are background)."""
    unbatched = score.ndim == 2
    if unbatched:
        score = score[None]
        links = links[None]
        if valid_mask is not None:
            valid_mask = valid_mask[None]
    if valid_mask is not None:
        score = jnp.where(valid_mask, score, 0.0)
    N, H, W = score.shape
    pos = score > score_thr
    lnk = pp.link_symmetrize(links) > link_thr
    init = jax.vmap(pp.cc_init_labels)(pos)

    # -- phase 1: tile-local fixpoint in VMEM ------------------------------
    bh, bw = min(th, H), min(tw, W)
    ph, pw = (-H) % bh, (-W) % bw
    pad = lambda a, lead=1: (jnp.pad(a, ((0, 0),) * lead + ((0, ph), (0, pw)))
                             if ph or pw else a)
    local = local_spread_converge(
        pad(init), pad(pos.astype(jnp.int32)),
        # direction-major (N, 8, H, W): each direction is one 2-D tile
        pad(jnp.moveaxis(lnk.astype(jnp.int32), -1, 1), 2),
        th=bh, tw=bw, interpret=interpret,
    )[:, :H, :W]

    # -- phase 2: global log-hop merge rounds ------------------------------
    def gcond(state):
        _, changed, it = state
        return jnp.any(changed & (it < max_iters))

    def gbody(state):
        lab, changed, it = state
        active = changed & (it < max_iters)
        new = jax.vmap(pp.cc_spread)(lab, pos, lnk)
        new = jax.vmap(pp.cc_pointer_jump)(new, pos)
        new = jnp.where(active[:, None, None], new, lab)
        delta = jnp.any(new != lab, axis=(1, 2))
        return (new, jnp.where(active, delta, changed),
                it + active.astype(jnp.int32))

    labels, changed, iters = jax.lax.while_loop(
        gcond, gbody,
        (local, jnp.ones((N,), jnp.bool_), jnp.zeros((N,), jnp.int32)),
    )
    converged = ~changed
    if unbatched:
        labels, iters, converged = labels[0], iters[0], converged[0]
    if return_stats:
        return labels, iters, converged
    return labels
