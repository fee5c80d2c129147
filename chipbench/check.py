"""What decides ``correct``: the served answers against the plain
reference's maps.

A served request's answer is its label map (the CC tail's output, 1/4
scale) and the boxes decoded from it.  Two comparisons:

* ``decode_boxes`` re-derives the boxes from the served label map; the
  served boxes must equal them exactly (the host decode).
* ``decision_gap`` judges the label map against the reference's score
  and link probabilities.  Every decision the served path took (a pixel
  positive or not, two neighbours joined or not) is allowed to disagree
  with the reference only where the reference itself lies within
  ``gap`` of the threshold; the number is the smallest such ``gap``.
  A pixel decided against the reference costs ``|score - thr|``; two
  positive neighbours split apart while the reference links them cost
  ``link - thr``; a served component costs ``thr - b``, where ``b`` is
  the weakest reference link it must have used: the bottleneck of the
  maximum spanning tree of its own links.  A positive pixel outside the
  image's valid region, or a component not connected through adjacent
  pixels, costs 1.

The gap is the analogue of "how far below the reference's best does a
served token's logit lie": a sound path at lower precision moves
decisions only near the threshold, a wrong one moves them anywhere.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components, minimum_spanning_tree

#: (dy, dx) per link channel, the head's order (references/*: NEIGHBORS)
NEIGHBORS = ((-1, -1), (-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1),
             (1, 0), (1, 1))
#: each undirected neighbour pair once: E, SW, S, SE
FORWARD = ((0, 1), (1, -1), (1, 0), (1, 1))


def decode_boxes(labels: np.ndarray, valid_q: Tuple[int, int]) -> List[Dict]:
    """Boxes of a label map, cropped to the valid region: one dict per
    component, ascending label, with the tight (x0, y0, x1, y1) box in
    label-map pixels and the pixel count."""
    lab = np.asarray(labels)[:valid_q[0], :valid_q[1]]
    out = []
    for v in np.unique(lab):
        if v == 0:
            continue
        ys, xs = np.nonzero(lab == v)
        out.append({"label": int(v),
                    "box": (int(xs.min()), int(ys.min()), int(xs.max()),
                            int(ys.max())),
                    "area": int(ys.size)})
    return out


def decision_gap(score: np.ndarray, links: np.ndarray, labels: np.ndarray,
                 valid_q: Tuple[int, int], score_thr: float = 0.5,
                 link_thr: float = 0.5) -> float:
    """The smallest threshold margin that explains the served label map
    (module doc).  ``score`` (h, w), ``links`` (h, w, 8) and ``labels``
    (h, w) cover the whole padded plane at 1/4 scale."""
    s = np.asarray(score, np.float64)
    lk = np.asarray(links, np.float64)
    lab = np.asarray(labels)
    h, w = lab.shape
    valid = np.zeros((h, w), bool)
    valid[:valid_q[0], :valid_q[1]] = True
    pos = lab > 0
    if (pos & ~valid).any():
        return 1.0
    flip = valid & (pos != (s > score_thr))
    gap = float(np.abs(s - score_thr)[flip].max(initial=0.0))

    idx = np.arange(h * w).reshape(h, w)
    ep, eq, em = [], [], []
    for dy, dx in FORWARD:
        d = NEIGHBORS.index((dy, dx))
        r = NEIGHBORS.index((-dy, -dx))
        x0, x1 = max(0, -dx), w - max(0, dx)
        p = (slice(0, h - dy), slice(x0, x1))
        q = (slice(dy, h), slice(x0 + dx, x1 + dx))
        m = np.maximum(lk[p][..., d], lk[q][..., r])
        both = pos[p] & pos[q]
        same = lab[p] == lab[q]
        split = both & ~same & (m > link_thr)
        gap = max(gap, float((m - link_thr)[split].max(initial=0.0)))
        joined = both & same
        ep.append(idx[p][joined])
        eq.append(idx[q][joined])
        em.append(m[joined])
    ep, eq, em = map(np.concatenate, (ep, eq, em))
    # weights 2 - m lie in [1, 2]: never 0, which scipy reads as no edge
    graph = coo_matrix((2.0 - em, (ep, eq)), shape=(h * w, h * w)).tocsr()
    _, cc = connected_components(graph, directed=False)
    pairs = np.unique(np.stack([lab[pos], cc[idx[pos]]]), axis=1)
    if pairs.shape[1] != np.unique(lab[pos]).size:
        return 1.0
    tree = minimum_spanning_tree(graph).tocoo()
    if tree.nnz:
        gap = max(gap, float(np.max(link_thr - (2.0 - tree.data))))
    return max(gap, 0.0)
