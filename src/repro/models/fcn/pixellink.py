"""PixelLink-style STD model: backbone + fusion assembled to ONE microcode
program (paper Fig. 1 + §III), plus the segmentation losses.

The model's outputs are pixel-wise at 1/4 input scale:
    score (1 ch)  — text / non-text probability
    links (8 ch)  — 8-neighbor same-instance probabilities
Connected components over positive links recover text boxes without any
box regression (postprocess.py).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import BFPConfig

from .heads import DetectionModel, PixelLinkHead

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class STDConfig:
    name: str = "pixellink_resnet50"
    backbone: str = "resnet50"
    width: float = 1.0
    image_size: Tuple[int, int] = (512, 512)     # (H, W); W <= 4096 (paper)
    merge_ch: Tuple[int, int, int] = (128, 64, 32)
    upsample_mode: str = "fused"
    mode: str = "optimized"                      # reference|optimized
    bfp: Optional[BFPConfig] = None
    storage_fp16: bool = True                    # paper's data-pool format
    use_pallas: bool = False                     # Pallas kernels in the
                                                 # optimized datapath


class PixelLinkModel(DetectionModel):
    """The zoo's ``head=PixelLinkHead()`` special case, kept as a named
    class for back-compat: apply() returns {score (N,h,w), links
    (N,h,w,8), logits} exactly as before the DetectionHead refactor."""

    def __init__(self, cfg: STDConfig):
        super().__init__(cfg, PixelLinkHead())


class STDLoss:
    """Class-balanced BCE on score + link BCE masked to positive pixels
    (PixelLink's loss structure, simplified: no instance-balancing)."""

    def __init__(self, neg_ratio: float = 3.0, link_weight: float = 1.0):
        self.neg_ratio = neg_ratio
        self.link_weight = link_weight

    def __call__(self, outputs, score_gt, link_gt) -> Dict[str, jax.Array]:
        logits = outputs["logits"]
        s_logit = logits[..., 0]
        l_logit = logits[..., 1:]
        pos = (score_gt > 0.5).astype(F32)
        neg = 1.0 - pos
        bce = lambda lg, y: jnp.maximum(lg, 0) - lg * y + jnp.log1p(
            jnp.exp(-jnp.abs(lg))
        )
        s_l = bce(s_logit, score_gt)
        n_pos = jnp.maximum(jnp.sum(pos), 1.0)
        # hard negative count = neg_ratio * n_pos (OHEM-lite: weight all
        # negatives by the ratio of the budget to the negative count)
        n_neg = jnp.minimum(self.neg_ratio * n_pos, jnp.sum(neg))
        w = pos + neg * (n_neg / jnp.maximum(jnp.sum(neg), 1.0))
        score_loss = jnp.sum(s_l * w) / jnp.maximum(jnp.sum(w), 1.0)

        l_l = bce(l_logit, link_gt)
        link_mask = pos[..., None]
        # masked mean over ELEMENTS: the sum covers all n_links channels
        # of every positive pixel, so the denominator is positive pixels
        # x n_links (dividing by positive pixels alone inflates the link
        # term n_links-fold vs the documented BCE mean)
        link_loss = jnp.sum(l_l * link_mask) / jnp.maximum(
            jnp.sum(link_mask) * l_logit.shape[-1], 1.0
        )
        total = score_loss + self.link_weight * link_loss
        return {"loss": total, "score_loss": score_loss,
                "link_loss": link_loss}
