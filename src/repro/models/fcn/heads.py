"""Detection-head zoo through the microcode seam (paper §II/Fig. 4).

The paper's headline claim is versatility: *different FCN models run on
one fixed datapath, reconfigured by microcodes*.  This module is that
claim's software seam — a :class:`DetectionHead` describes everything
model-specific about a scene-text detector:

  * the head's LayerSpecs appended after the shared backbone + U-merge
    (the general model description the Assembler resolves to microcode —
    Fig. 4 left branch),
  * how raw engine outputs become named probability/geometry maps,
  * the on-device serving tail (CC labeling for segmentation heads,
    valid-region masking for regression heads),
  * the per-image host decode and an independent NumPy reference decode
    the serve_bench parity gates compare against.

Three heads ship:

  * :class:`PixelLinkHead` — the paper's own model: 1 score + 8 link
    channels, connected components over positive links (PixelLink [6]).
  * :class:`EASTHead` — direct geometry regression (EAST, arXiv
    1704.03155): 1 score + 4 axis-aligned edge distances per pixel,
    decoded host-side with greedy NMS.  No CC tail at all — which is
    exactly why the engine payload had to stop being hardcoded to
    ``(labels, converged)``.
  * :class:`DBHead` — a DB/FAST-style minimalist shrink-mask head
    (FaSTExt, arXiv 1908.08994): a residual 3x3/1x1 merge through the
    binary ``add`` microcode op, one sigmoid mask channel, plain
    8-connected CC, and the DB unclip expansion at decode time.

:class:`DetectionModel` composes backbone + U-merge + head into ONE
assembled program; ``MODEL_ZOO``/:func:`build_head` are the registry the
engine factory, the serving layer, and serve_bench route by.  The N-th
model is a head subclass: specs + decode, ~50 lines.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Assembler, FCNEngine, LayerSpec
from repro.core.assembler import Program

from . import backbones as bb
from . import fusion

F32 = jnp.float32

#: the model axis every engine/param cache and telemetry series keys on
DEFAULT_MODEL = "pixellink"


def _valid_mask(score: jax.Array, valid_q: jax.Array) -> jax.Array:
    """(N, h, w) bool mask of the per-image valid region (quarter-res
    heights/widths in ``valid_q`` (N, 2)) — the same arithmetic the CC
    tail uses, shared so regression heads mask identically."""
    h, w = score.shape[1:]
    return (
        (jnp.arange(h)[None, :, None] < valid_q[:, 0, None, None])
        & (jnp.arange(w)[None, None, :] < valid_q[:, 1, None, None])
    )


def _iou(a: Tuple[int, int, int, int], b: Tuple[int, int, int, int]) -> float:
    """Inclusive-pixel IoU of two (x0, y0, x1, y1) boxes."""
    ix = min(a[2], b[2]) - max(a[0], b[0]) + 1
    iy = min(a[3], b[3]) - max(a[1], b[1]) + 1
    if ix <= 0 or iy <= 0:
        return 0.0
    inter = ix * iy
    aa = (a[2] - a[0] + 1) * (a[3] - a[1] + 1)
    bb = (b[2] - b[0] + 1) * (b[3] - b[1] + 1)
    return inter / float(aa + bb - inter)


def db_unclip_box(box: Dict, valid_hw_q: Tuple[int, int],
                  ratio: float) -> Dict:
    """DB's unclip expansion on one tight component box: the shrink-mask
    training target contracts text regions, so detection grows each box
    back by ``delta = area * ratio / perimeter`` (the polygon offset
    formula specialized to axis-aligned rectangles), clipped to the
    valid quarter-res plane."""
    x0, y0, x1, y1 = box["box"]
    w, h = x1 - x0 + 1, y1 - y0 + 1
    d = int(round(w * h * ratio / (2.0 * (w + h))))
    vh, vw = valid_hw_q
    out = dict(box)
    out["box"] = (max(0, x0 - d), max(0, y0 - d),
                  min(vw - 1, x1 + d), min(vh - 1, y1 + d))
    return out


class DetectionHead:
    """One detection model's head: specs, maps, tail, decode.

    Class attributes every subclass pins down:

    ``maps``
        ``((name, rank), ...)`` — the named maps :meth:`model_outputs`
        produces (rank includes the batch dim; 3 = per-pixel scalar,
        4 = per-pixel vector).  The row-banded engines shard exactly
        these maps out of the shard body.
    ``payload_ranks``
        Ranks of the device arrays :meth:`tail` returns before the
        trailing ``converged`` flag — the data-parallel engines build
        their out_specs from this.
    ``n_payload``
        ``len(payload_ranks)`` — how many payload arrays precede
        ``converged`` in an engine's return tuple.
    ``supports_device_postprocess``
        Whether the label-map → compact-boxes device tail applies
        (only single-label-map payloads can ride it).
    """

    name: str = "base"
    maps: Tuple[Tuple[str, int], ...] = ()
    payload_ranks: Tuple[int, ...] = (3,)
    n_payload: int = 1
    supports_device_postprocess: bool = False

    def __init__(self, score_thr: float = 0.5, link_thr: float = 0.5):
        self.score_thr = float(score_thr)
        self.link_thr = float(link_thr)

    # -- graph side -----------------------------------------------------------
    def head_specs(self, feat: str) -> Tuple[List[LayerSpec], List[str]]:
        """LayerSpecs appended after the fusion output ``feat`` plus the
        program output names (Fig. 4: the model-specific tail of the
        general model description)."""
        raise NotImplementedError

    def model_outputs(self, raw: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """Raw engine outputs -> ``{"logits", <named maps...>}``."""
        raise NotImplementedError

    # -- device tail ----------------------------------------------------------
    def tail(self, factory, out: Dict[str, jax.Array],
             valid_q: jax.Array) -> Tuple[jax.Array, ...]:
        """Named maps -> ``(*payload, converged)`` on device.  Runs
        inside the compiled engine; ``factory`` supplies the shared CC
        machinery (EngineFactory.label_tail)."""
        raise NotImplementedError

    # -- host decode ----------------------------------------------------------
    def payload_plane(self, payload: Any) -> Optional[Tuple[int, int]]:
        """Quarter-res (h, w) plane of a per-image payload, or None when
        the payload carries no plane (device-compact rows)."""
        if isinstance(payload, tuple):
            return None
        return tuple(np.asarray(payload).shape[:2])

    def decode(self, payload: Any,
               valid_hw: Tuple[int, int]) -> Tuple[List[Dict], str]:
        """One image's materialized payload -> (boxes, kind) where kind
        labels the postprocess telemetry series ("host"/"device")."""
        raise NotImplementedError

    def reference_decode(self, out: Dict[str, np.ndarray],
                         valid_hw: Tuple[int, int]) -> List[Dict]:
        """Independent NumPy oracle: per-image maps (no batch dim) ->
        boxes.  serve_bench's per-model parity gate compares this
        against the serving tail + :meth:`decode` on the same maps."""
        raise NotImplementedError

    @staticmethod
    def _crop_q(arr: np.ndarray, valid_hw: Tuple[int, int]) -> np.ndarray:
        vh, vw = valid_hw[0] // 4, valid_hw[1] // 4
        return np.asarray(arr)[:vh, :vw]


class PixelLinkHead(DetectionHead):
    """The paper's model: 1 score + 8 neighbor-link channels, CC over
    positive links (PixelLink).  Supports the device-compact box tail."""

    name = "pixellink"
    maps = (("score", 3), ("links", 4))
    payload_ranks = (3,)
    n_payload = 1
    supports_device_postprocess = True

    def head_specs(self, feat):
        return fusion.pixellink_head(feat)

    def model_outputs(self, raw):
        prob = raw["head_prob"].astype(F32)
        return {
            "logits": raw["head_logits"].astype(F32),
            "score": prob[..., 0],
            "links": prob[..., 1:],
        }

    def tail(self, factory, out, valid_q):
        return factory.label_tail(out["score"], out["links"], valid_q)

    def decode(self, payload, valid_hw):
        from . import postprocess as pp

        if isinstance(payload, tuple):          # device-compact rows
            return pp.boxes_from_compact(payload[0]), "device"
        return pp.boxes_from_labels(self._crop_q(payload, valid_hw)), "host"

    def reference_decode(self, out, valid_hw):
        from . import postprocess as pp

        score = self._crop_q(out["score"], valid_hw)
        links = self._crop_q(out["links"], valid_hw)
        labels = pp.cc_label_numpy(score, links,
                                   self.score_thr, self.link_thr)
        return pp.boxes_from_labels_reference(labels)


class EASTHead(DetectionHead):
    """EAST-style direct regression: per-pixel score + 4 edge distances
    (top, right, bottom, left, in quarter-res pixels), decoded host-side
    with greedy NMS.  No CC tail — the engine payload is the masked
    score map plus the geometry map."""

    name = "east"
    maps = (("score", 3), ("geo", 4))
    payload_ranks = (3, 4)
    n_payload = 2
    supports_device_postprocess = False

    #: sigmoid output x scale = edge distance in quarter-res pixels (the
    #: regression range; EAST's text regions rarely exceed this radius
    #: at 1/4 scale for bucket-sized planes)
    GEO_SCALE = 8.0
    #: greedy-NMS suppression threshold
    NMS_IOU = 0.5

    def __init__(self, score_thr: float = 0.5, link_thr: float = 0.5, *,
                 geo_scale: float = GEO_SCALE, nms_iou: float = NMS_IOU):
        super().__init__(score_thr, link_thr)
        self.geo_scale = float(geo_scale)
        self.nms_iou = float(nms_iou)

    def head_specs(self, feat):
        specs = [
            LayerSpec("head_logits", "conv", [feat], out_ch=5, kernel=1),
            LayerSpec("head_prob", "sigmoid", ["head_logits"]),
        ]
        return specs, ["head_logits", "head_prob"]

    def model_outputs(self, raw):
        prob = raw["head_prob"].astype(F32)
        return {
            "logits": raw["head_logits"].astype(F32),
            "score": prob[..., 0],
            "geo": prob[..., 1:] * self.geo_scale,
        }

    def tail(self, factory, out, valid_q):
        score = out["score"]
        masked = jnp.where(_valid_mask(score, valid_q), score, 0.0)
        converged = jnp.ones((score.shape[0],), bool)
        return masked, out["geo"].astype(F32), converged

    def payload_plane(self, payload):
        return tuple(np.asarray(payload[0]).shape[:2])

    def _candidates(self, score: np.ndarray, geo: np.ndarray):
        """Thresholded pixels -> clipped integer candidate boxes, in
        (-score, y, x) order.  Vectorized; the reference decode redoes
        this per pixel in pure Python."""
        vh, vw = score.shape
        ys, xs = np.nonzero(score > self.score_thr)
        if ys.size == 0:
            return [], []
        d = geo[ys, xs]                      # (n, 4) order (t, r, b, l)
        x0 = np.clip(np.rint(xs - d[:, 3]), 0, vw - 1).astype(np.int64)
        y0 = np.clip(np.rint(ys - d[:, 0]), 0, vh - 1).astype(np.int64)
        x1 = np.clip(np.rint(xs + d[:, 1]), 0, vw - 1).astype(np.int64)
        y1 = np.clip(np.rint(ys + d[:, 2]), 0, vh - 1).astype(np.int64)
        sc = score[ys, xs]
        order = np.lexsort((xs, ys, -sc))    # primary -score, then y, x
        boxes = [(int(x0[i]), int(y0[i]), int(x1[i]), int(y1[i]))
                 for i in order]
        return boxes, [float(sc[i]) for i in order]

    @staticmethod
    def _nms(boxes, scores, iou_thr: float) -> List[Dict]:
        kept: List[Dict] = []
        for box, sc in zip(boxes, scores):
            if all(_iou(box, k["box"]) <= iou_thr for k in kept):
                kept.append({
                    "label": len(kept) + 1,
                    "box": box,
                    "area": (box[2] - box[0] + 1) * (box[3] - box[1] + 1),
                    "score": sc,
                })
        return kept

    def decode(self, payload, valid_hw):
        score, geo = payload
        vh, vw = valid_hw[0] // 4, valid_hw[1] // 4
        score = np.asarray(score)[:vh, :vw]
        geo = np.asarray(geo)[:vh, :vw]
        boxes, scores = self._candidates(score, geo)
        return self._nms(boxes, scores, self.nms_iou), "host"

    def reference_decode(self, out, valid_hw):
        score = self._crop_q(out["score"], valid_hw)
        geo = self._crop_q(out["geo"], valid_hw)
        vh, vw = score.shape
        cands = []
        for y in range(vh):                   # pure-Python oracle
            for x in range(vw):
                if not score[y, x] > self.score_thr:
                    continue
                t, r, b, l = (geo[y, x, 0], geo[y, x, 1],
                              geo[y, x, 2], geo[y, x, 3])
                box = (
                    int(min(max(np.rint(x - l), 0), vw - 1)),
                    int(min(max(np.rint(y - t), 0), vh - 1)),
                    int(min(max(np.rint(x + r), 0), vw - 1)),
                    int(min(max(np.rint(y + b), 0), vh - 1)),
                )
                cands.append((-float(score[y, x]), y, x, box))
        cands.sort(key=lambda c: c[:3])
        kept: List[Dict] = []
        for neg_sc, _, _, box in cands:
            if all(_iou(box, k["box"]) <= self.nms_iou for k in kept):
                kept.append({
                    "label": len(kept) + 1,
                    "box": box,
                    "area": (box[2] - box[0] + 1) * (box[3] - box[1] + 1),
                    "score": -neg_sc,
                })
        return kept


class DBHead(DetectionHead):
    """DB/FAST-style minimalist head: a residual 3x3/1x1 merge through
    the binary ``add`` microcode op (the residual read via ext_addr2 —
    the op the assembler's concat path used to double-count), ONE
    sigmoid shrink-mask channel, plain 8-connected CC over the mask, and
    the DB unclip expansion at decode time.  Supports the device-compact
    box tail (its payload is a single label map, like PixelLink's)."""

    name = "db"
    maps = (("score", 3),)
    payload_ranks = (3,)
    n_payload = 1
    supports_device_postprocess = True

    #: unclip growth factor (DB's r; the shrink target contracts text
    #: regions, decode grows them back)
    UNCLIP_RATIO = 1.5
    #: residual-merge width
    HEAD_CH = 16

    def __init__(self, score_thr: float = 0.5, link_thr: float = 0.5, *,
                 unclip_ratio: float = UNCLIP_RATIO, head_ch: int = HEAD_CH):
        super().__init__(score_thr, link_thr)
        self.unclip_ratio = float(unclip_ratio)
        self.head_ch = int(head_ch)

    def head_specs(self, feat):
        ch = self.head_ch
        specs = [
            LayerSpec("db_c3", "conv", [feat], out_ch=ch, kernel=3,
                      relu=True, bn=True, bias=False),
            LayerSpec("db_r1", "conv", ["db_c3"], out_ch=ch, kernel=1,
                      bn=True, bias=False),
            # the residual merge: reads db_r1 at in_addr and db_c3 via
            # ext_addr2 — channels must MATCH (never sum like a concat)
            LayerSpec("db_add", "add", ["db_r1", "db_c3"], relu=True),
            LayerSpec("head_logits", "conv", ["db_add"], out_ch=1,
                      kernel=1),
            LayerSpec("head_prob", "sigmoid", ["head_logits"]),
        ]
        return specs, ["head_logits", "head_prob"]

    def model_outputs(self, raw):
        prob = raw["head_prob"].astype(F32)
        return {
            "logits": raw["head_logits"].astype(F32),
            "score": prob[..., 0],
        }

    def tail(self, factory, out, valid_q):
        score = out["score"]
        # all-positive links turn the CC tail into plain 8-connected
        # labeling of the thresholded mask (link_thr < 1 always passes)
        links = jnp.ones(score.shape + (8,), score.dtype)
        return factory.label_tail(score, links, valid_q)

    def _unclip(self, boxes: List[Dict],
                valid_hw: Tuple[int, int]) -> List[Dict]:
        vq = (valid_hw[0] // 4, valid_hw[1] // 4)
        return [db_unclip_box(b, vq, self.unclip_ratio) for b in boxes]

    def decode(self, payload, valid_hw):
        from . import postprocess as pp

        if isinstance(payload, tuple):          # device-compact rows
            return self._unclip(pp.boxes_from_compact(payload[0]),
                                valid_hw), "device"
        boxes = pp.boxes_from_labels(self._crop_q(payload, valid_hw))
        return self._unclip(boxes, valid_hw), "host"

    def reference_decode(self, out, valid_hw):
        from . import postprocess as pp

        score = self._crop_q(out["score"], valid_hw)
        links = np.ones(score.shape + (8,), np.float32)
        labels = pp.cc_label_numpy(score, links,
                                   self.score_thr, self.link_thr)
        return self._unclip(pp.boxes_from_labels_reference(labels),
                            valid_hw)


#: name -> head class; the engine factory, serving layer, serve_bench
#: --model sweep, and the golden disassembly snapshots all route by it
MODEL_ZOO: Dict[str, type] = {
    "pixellink": PixelLinkHead,
    "east": EASTHead,
    "db": DBHead,
}


def check_model(model: str) -> str:
    if model not in MODEL_ZOO:
        raise ValueError(
            f"unknown model {model!r}; expected one of "
            f"{tuple(sorted(MODEL_ZOO))}"
        )
    return model


def build_head(model: str, *, score_thr: float = 0.5,
               link_thr: float = 0.5, **kw) -> DetectionHead:
    """One configured head instance from the zoo registry."""
    return MODEL_ZOO[check_model(model)](score_thr=score_thr,
                                         link_thr=link_thr, **kw)


class DetectionModel:
    """Backbone + EAST-style U-merge + one :class:`DetectionHead`,
    assembled to ONE microcode program and executed by FCNEngine — the
    generic model the whole zoo compiles through (PixelLinkModel is the
    ``head=PixelLinkHead()`` special case).

    ``cfg`` is duck-typed to the STDConfig fields (backbone, width,
    image_size, merge_ch, upsample_mode, mode, bfp, storage_fp16,
    use_pallas)."""

    def __init__(self, cfg, head: DetectionHead):
        self.cfg = cfg
        self.head = head
        h, w = cfg.image_size
        specs, taps = bb.BACKBONES[cfg.backbone](cfg.width)
        fspecs, fout = fusion.east_merge(
            taps, cfg.merge_ch, cfg.upsample_mode
        )
        hspecs, outs = head.head_specs(fout)
        self.program: Program = Assembler((h, w, 3)).assemble(
            specs + fspecs + hspecs, outputs=outs
        )
        self.engine = FCNEngine(
            self.program,
            mode=cfg.mode,
            bfp=cfg.bfp,
            storage_dtype=jnp.float16 if cfg.storage_fp16 else jnp.float32,
            use_pallas=cfg.use_pallas,
        )

    def init_params(self, key):
        return self.engine.init_params(key)

    def for_plane(self, image_size: Tuple[int, int]) -> "DetectionModel":
        """The same architecture reassembled for another input plane
        (fully convolutional — parameters transfer 1:1; this is how the
        row-band ExecutionPlan builds its band-plane program)."""
        return DetectionModel(
            dataclasses.replace(self.cfg, image_size=tuple(image_size)),
            self.head,
        )

    def normalize_weights(self, params):
        """Paper Fig. 4 right branch (BN fold + BFP weight
        normalization)."""
        return self.engine.normalize_weights(params)

    def apply(self, params, images, *, transposed: bool = False,
              band_ctx=None) -> Dict[str, jax.Array]:
        """images (N, H, W, 3) -> the head's named maps + logits.

        Any leading batch size runs through ONE assembled program;
        ``transposed``/``band_ctx`` are the paper's §IV.B over-wide and
        row-band modes, threaded down to the engine unchanged."""
        if images.ndim != 4:
            raise ValueError(
                f"images must be (N, H, W, 3), got shape {images.shape}"
            )
        raw = self.engine(params, images, transposed=transposed,
                          band_ctx=band_ctx)
        return self.head.model_outputs(raw)

    def microcode_bytes(self):
        from repro.core.microcode import pack_program

        return pack_program(self.program.words)
