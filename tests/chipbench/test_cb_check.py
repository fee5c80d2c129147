"""The comparison that decides ``correct``: the decision gap of a label
map against reference maps, and the box decode."""
import numpy as np

import _cb_paths  # noqa: F401
from chipbench import check


def labels_of(score, links, thr=0.5):
    """Union-find CC of a map pair (either link direction joins)."""
    h, w = score.shape
    pos = score > thr
    parent = np.arange(h * w)

    def find(a):
        while parent[a] != a:
            a = parent[a]
        return a

    for y in range(h):
        for x in range(w):
            for d, (dy, dx) in enumerate(check.NEIGHBORS):
                ny, nx = y + dy, x + dx
                if not (0 <= ny < h and 0 <= nx < w):
                    continue
                r = check.NEIGHBORS.index((-dy, -dx))
                if pos[y, x] and pos[ny, nx] and max(
                        links[y, x, d], links[ny, nx, r]) > thr:
                    a, b = find(y * w + x), find(ny * w + nx)
                    parent[max(a, b)] = min(a, b)
    out = np.zeros((h, w), np.int32)
    for i in range(h * w):
        if pos.flat[i]:
            out.flat[i] = find(i) + 1
    return out


def maps(seed, h=12, w=12):
    rng = np.random.default_rng(seed)
    return (rng.uniform(0.2, 0.8, (h, w)), rng.uniform(0.2, 0.8, (h, w, 8)))


def test_exact_labels_have_zero_gap():
    for seed in range(5):
        s, l = maps(seed)
        assert check.decision_gap(s, l, labels_of(s, l), (12, 12)) == 0.0


def test_near_threshold_noise_reads_its_margin():
    s, l = maps(1)
    eps = 0.01
    noisy = np.clip(s + np.random.default_rng(2).uniform(-eps, eps, s.shape),
                    0, 1)
    lab = labels_of(noisy, l)
    gap = check.decision_gap(s, l, lab, (12, 12))
    assert 0 < gap <= eps


def test_a_wrong_answer_reads_far_from_the_threshold():
    s, l = maps(3)
    good = labels_of(s, l)
    assert check.decision_gap(s, l, np.zeros_like(good), (12, 12)) > 0.2
    swapped = labels_of(*maps(4))
    assert check.decision_gap(s, l, swapped, (12, 12)) > 0.2


def test_split_and_merged_components_cost_their_link_margin():
    s = np.full((1, 2), 0.9)
    l = np.full((1, 2, 8), 0.1)
    l[0, 0, check.NEIGHBORS.index((0, 1))] = 0.7      # strong link
    split = np.array([[1, 2]])
    assert abs(check.decision_gap(s, l, split, (1, 2)) - 0.2) < 1e-12
    l[0, 0, check.NEIGHBORS.index((0, 1))] = 0.45     # weak link
    merged = np.array([[2, 2]])
    assert abs(check.decision_gap(s, l, merged, (1, 2)) - 0.05) < 1e-12


def test_disconnected_or_outside_answers_read_one():
    s = np.full((1, 3), 0.9)
    l = np.full((1, 3, 8), 0.9)
    s[0, 1] = 0.1
    # one label over two pixels that are not adjacent once 0,1 is off
    assert check.decision_gap(s, l, np.array([[3, 0, 3]]), (1, 3)) == 1.0
    assert check.decision_gap(s, l, np.array([[1, 0, 3]]), (1, 2)) == 1.0


def test_decode_boxes_crops_to_the_valid_region():
    lab = np.zeros((6, 6), np.int32)
    lab[1:3, 2:5] = 7
    lab[4, 0] = 9
    lab[5, 5] = 11
    assert check.decode_boxes(lab, (5, 5)) == [
        {"label": 7, "box": (2, 1, 4, 2), "area": 6},
        {"label": 9, "box": (0, 4, 0, 4), "area": 1},
    ]
