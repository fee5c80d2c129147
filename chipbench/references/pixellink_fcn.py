"""Plain float32 reference of the PixelLink detector (arXiv:1801.01315):
a ResNet-50 (arXiv:1512.03385, stride on the 3x3, "v1.5") or VGG-16
(arXiv:1409.1556, 13 convs, no FC) backbone, the EAST-style U-merge
(arXiv:1704.03155) and the 1 score + 8 link head, written out as
straight ``jax.numpy``/``lax`` code.

It imports nothing of the program under test.  The architecture is
written once, in :func:`network`, against an ``ops`` object: the forward
pass hands it real convolutions, :func:`layer_list` a recorder that
lists every convolution with its shapes (the yardstick's FLOP and byte
counts and the weight initialiser read that list).

Conventions, where the papers leave a choice:

* convolutions and pools pad "SAME" (TensorFlow's rule: the extra row
  or column of an odd padding goes to the bottom/right);
* the 2x upsample of the merge is zero insertion followed by a 3x3
  convolution (a learnable transposed convolution), with no bias;
* batch norm uses eps = 1e-5 and is applied as written, not folded;
* PixelLink joins two positive 8-neighbours when EITHER of the two
  link predictions between them is positive.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BN_EPS = 1e-5
N_LINKS = 8
#: the backbone's total stride: the merge takes planes that are
#: multiples of it
STRIDE = 32
#: (dy, dx) of the 8 link directions, in the head's channel order
NEIGHBORS = ((-1, -1), (-1, 0), (-1, 1), (0, -1), (0, 1), (1, -1),
             (1, 0), (1, 1))


@dataclasses.dataclass(frozen=True)
class Conv:
    """One convolution of the network, at a given input plane."""

    name: str
    kind: str            # "conv" or "upsample" (zero-insert 2x + 3x3)
    k: int
    stride: int
    cin: int
    cout: int
    in_hw: Tuple[int, int]
    out_hw: Tuple[int, int]
    bn: bool
    bias: bool
    residual: bool       # last conv of a residual branch


def _same_out(n: int, stride: int) -> int:
    return -(-n // stride)


def network(ops, x, arch: Dict):
    """The detector, once.  ``arch``: backbone, width, merge_ch.
    Returns the head's logits (N, H/4, W/4, 9)."""
    width = float(arch["width"])

    def c(ch):
        return max(int(ch * width), 8)

    if arch["backbone"] == "resnet50":
        y = ops.conv("stem", x, c(64), 7, 2, relu=True)
        y = ops.maxpool(y, 3, 2)
        taps = []
        for si, (n, base) in enumerate(zip((3, 4, 6, 3),
                                           (64, 128, 256, 512))):
            mid = c(base)
            for bi in range(n):
                s = 2 if si > 0 and bi == 0 else 1
                name = f"s{si + 1}b{bi + 1}"
                short = (ops.conv(f"{name}_proj", y, mid * 4, 1, s)
                         if bi == 0 else y)
                z = ops.conv(f"{name}_c1", y, mid, 1, 1, relu=True)
                z = ops.conv(f"{name}_c2", z, mid, 3, s, relu=True)
                z = ops.conv(f"{name}_c3", z, mid * 4, 1, 1, residual=True)
                y = jax.nn.relu(z + short)
            taps.append(y)
    elif arch["backbone"] == "vgg16":
        y, taps = x, []
        for si, (n, ch) in enumerate(((2, 64), (2, 128), (3, 256),
                                      (3, 512), (3, 512))):
            for bi in range(n):
                y = ops.conv(f"conv{si + 1}_{bi + 1}", y, c(ch), 3, 1,
                             relu=True)
            y = ops.maxpool(y, 2, 2)
            if si >= 1:
                taps.append(y)
    else:
        raise ValueError(f"unknown backbone {arch['backbone']!r}")

    # U-merge, deepest first: squeeze, upsample x2, concat the lateral
    # tap after the upsampled map, 1x1 then 3x3
    h = taps[-1]
    for i, lateral in enumerate(reversed(taps[:-1])):
        ch = int(arch["merge_ch"][i])
        h = ops.conv(f"merge{i + 1}_sq", h, ch, 1, 1, relu=True)
        h = ops.upsample(f"merge{i + 1}_up", h)
        h = jnp.concatenate([h, lateral], axis=-1)
        h = ops.conv(f"merge{i + 1}_c1", h, ch, 1, 1, relu=True)
        h = ops.conv(f"merge{i + 1}_c3", h, ch, 3, 1, relu=True)
    h = ops.conv("fuse_out", h, int(arch["merge_ch"][-1]), 3, 1, relu=True)
    return ops.conv("head_logits", h, 1 + N_LINKS, 1, 1, bn=False,
                    bias=True)


def _maxpool(x, k, s):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                             (1, s, s, 1), "SAME")


class _Forward:
    """Real convolutions over a parameter dict."""

    def __init__(self, params):
        self.params = params

    def conv(self, name, x, cout, k, stride, *, relu=False, bn=True,
             bias=False, residual=False):
        p = self.params[name]
        y = lax.conv_general_dilated(
            x, p["w"], (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if bias:
            y = y + p["b"]
        if bn:
            y = (y - p["mean"]) * lax.rsqrt(p["var"] + BN_EPS) * p["gamma"] \
                + p["beta"]
        return jax.nn.relu(y) if relu else y

    def upsample(self, name, x):
        n, h, w, c = x.shape
        z = jnp.zeros((n, 2 * h, 2 * w, c), x.dtype).at[:, ::2, ::2].set(x)
        return lax.conv_general_dilated(
            z, self.params[name]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    maxpool = staticmethod(_maxpool)


class _Recorder:
    """Lists every convolution with its shapes; computes nothing."""

    def __init__(self):
        self.convs: List[Conv] = []

    def conv(self, name, x, cout, k, stride, *, relu=False, bn=True,
             bias=False, residual=False):
        n, h, w, cin = x.shape
        out = (_same_out(h, stride), _same_out(w, stride))
        self.convs.append(Conv(name, "conv", k, stride, cin, cout, (h, w),
                               out, bn, bias, residual))
        return jnp.zeros((n, *out, cout), x.dtype)

    def upsample(self, name, x):
        n, h, w, c = x.shape
        self.convs.append(Conv(name, "upsample", 3, 1, c, c, (h, w),
                               (2 * h, 2 * w), False, False, False))
        return jnp.zeros((n, 2 * h, 2 * w, c), x.dtype)

    maxpool = staticmethod(_maxpool)


def layer_list(arch: Dict, hw: Tuple[int, int]) -> List[Conv]:
    """Every convolution of the network at input plane ``hw``."""
    rec = _Recorder()
    jax.eval_shape(lambda x: network(rec, x, arch),
                   jax.ShapeDtypeStruct((1, hw[0], hw[1], 3), jnp.float32))
    return rec.convs


def init_params(key, arch: Dict) -> Dict[str, Dict[str, jax.Array]]:
    """Seeded weights: He-normal convolutions, the last conv of each
    residual branch scaled by 1/sqrt(#branches) (Fixup, so a random
    ResNet's residual stream stays bounded), and batch-norm statistics
    drawn near identity so that the comparison exercises them.  One
    normal and one uniform draw cover every layer (a small program)."""
    convs = layer_list(arch, (64, 64))
    n_res = sum(cv.residual for cv in convs)
    sizes = [cv.k * cv.k * cv.cin * cv.cout for cv in convs]
    kn, ku = jax.random.split(key)
    normal = jax.random.normal(kn, (sum(sizes) + 3 * sum(
        cv.cout for cv in convs),), jnp.float32)
    uniform = jax.random.uniform(ku, (2 * sum(cv.cout for cv in convs),),
                                 jnp.float32, 0.8, 1.2)
    params, at, ut = {}, 0, 0

    def take(n):
        nonlocal at
        at += n
        return normal[at - n:at]

    def take_u(n):
        nonlocal ut
        ut += n
        return uniform[ut - n:ut]

    for cv, size in zip(convs, sizes):
        w = take(size).reshape(cv.k, cv.k, cv.cin, cv.cout)
        w = w * np.sqrt(2.0 / (cv.k * cv.k * cv.cin))
        if cv.residual:
            w = w / np.sqrt(n_res)
        p = {"w": w}
        beta, mean, bias = (0.02 * take(cv.cout) for _ in range(3))
        gamma, var = take_u(cv.cout), take_u(cv.cout)
        if cv.bias:
            p["b"] = bias
        if cv.bn:
            p.update(gamma=gamma, beta=beta, mean=mean, var=var)
        params[cv.name] = p
    return params


def calibrate(params, images, arch: Dict, text_share: float,
              link_share: float):
    """Shift the head's biases so that on ``images`` a share
    ``text_share`` of the pixels scores above the threshold (logit 0)
    and ``link_share`` of each link direction does.  A random network's logits sit
    mostly on one side of 0, so its maps would read all text or none,
    and how long the connected-component tail runs would depend on the
    seed; a trained detector marks a minority of the pixels."""
    with jax.default_matmul_precision("highest"):
        logits = network(_Forward(params), images.astype(jnp.float32),
                         arch)
    shift = jnp.concatenate([
        jnp.quantile(logits[..., 0], 1.0 - text_share)[None],
        jnp.quantile(logits[..., 1:].reshape(-1, N_LINKS), 1.0 - link_share,
                     axis=0),
    ])
    head = dict(params["head_logits"], b=params["head_logits"]["b"] - shift)
    return dict(params, head_logits=head)


def to_program(params):
    """The one mapping from these weights to the served model's
    parameter tree: the same layer names, kernels in HWIO, batch-norm
    statistics unfolded (the service folds them itself)."""
    return {name: dict(p) for name, p in params.items()}


def forward(params, images, arch: Dict):
    """(N, H, W, 3) float32 -> (score (N, h, w), links (N, h, w, 8)),
    the head's sigmoid probabilities at 1/4 scale, at float32 matmul
    precision ("highest": a TPU otherwise rounds f32 operands to bf16)."""
    with jax.default_matmul_precision("highest"):
        logits = network(_Forward(params), images.astype(jnp.float32), arch)
    prob = jax.nn.sigmoid(logits)
    return prob[..., 0], prob[..., 1:]
