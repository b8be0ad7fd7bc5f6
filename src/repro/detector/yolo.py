"""YOLOv3 (Redmon & Farhadi, arXiv:1804.02767; darknet ``cfg/yolov3.cfg``)
in pure JAX, at its published widths by default.

Layers (every backbone and neck conv is conv without bias -> batch norm
in inference form -> leaky ReLU of slope ``leaky``):

* **Darknet-53** (scope ``backbone``): a 3x3 stem conv, then five
  stages, each a 3x3 stride-2 conv to ``stage_widths[i]`` channels and
  ``stage_blocks[i]`` residual blocks (1x1 to C/2, 3x3 to C, plus the
  block's input).
* **FPN neck** (scope ``neck``): at stride 32 five convs alternating
  1x1 C/2 and 3x3 C, a 3x3 C conv and a linear 1x1 head of
  3 * (5 + n_classes) channels with bias.  Route: the fifth conv's
  output through a 1x1 C/4 conv, nearest x2 upsample, concatenated
  with the previous stage's output, and the same five, 3x3 and head
  one level down; twice, so heads sit at strides 32, 16 and 8.
* **Decode** (scope ``decode``): anchor priors in pixels of the input,
  ``masks[i]`` at head ``i``; x = (sigmoid(tx) + cx) / G,
  w = p_w * exp(tw) / image_size; a pair's score is
  sigmoid(obj) * sigmoid(cls_c), the classes independent sigmoids.
* **Suppression** (scope ``nms``): greedy NMS per class over every
  (anchor, class) pair scored at least ``score_thr``, merged in
  descending score (``kernels.class_nms``).

Batch norm uses darknet's ``normalize_cpu`` form, (x - mean) /
(sqrt(var) + bn_eps) with bn_eps = 1e-6 (most ports use
sqrt(var + eps) instead), then gamma and beta.  It is applied as a
per-channel scale and shift after each conv, on every call; it is not
folded into the conv weights.  Padding is darknet's: k // 2 on every
side, so a stride-2 3x3 conv pads (1, 1) where XLA's ``SAME`` would pad
(0, 1).  Convs run in float32 at ``Precision.HIGHEST``, as
``ssd._conv`` does.

Anchor order: heads at strides 32, 16, 8 in turn; within a head, cells
row-major and the head's three priors per cell.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.class_nms import class_nms

ANCHORS = ((10, 13), (16, 30), (33, 23), (30, 61), (62, 45), (59, 119),
           (116, 90), (156, 198), (373, 326))
MASKS = ((6, 7, 8), (3, 4, 5), (0, 1, 2))


@dataclass(frozen=True)
class YOLOv3Config:
    image_size: int = 416
    n_classes: int = 80
    stem: int = 32
    stage_widths: Tuple[int, ...] = (64, 128, 256, 512, 1024)
    stage_blocks: Tuple[int, ...] = (1, 2, 8, 8, 4)
    anchors: Tuple[Tuple[int, int], ...] = ANCHORS     # (w, h) in px
    masks: Tuple[Tuple[int, ...], ...] = MASKS         # per head
    leaky: float = 0.1
    bn_eps: float = 1e-6

    def __post_init__(self):
        if len(self.stage_widths) != 5 or len(self.stage_blocks) != 5:
            raise ValueError("Darknet-53 has five stages")
        if self.image_size % 32:
            raise ValueError("image_size must be a multiple of 32")
        if len(self.masks) != 3 or sorted(sum(self.masks, ())) != list(
                range(len(self.anchors))):
            raise ValueError("three heads whose masks share out the anchors")
        if any(w % 4 for w in self.stage_widths[2:]):
            raise ValueError("the neck's widths are C/2 and C/4 of stages "
                             "3 to 5")

    @property
    def grids(self) -> Tuple[int, ...]:
        """Cells per side of the heads, at strides 32, 16 and 8."""
        return tuple(self.image_size // s for s in (32, 16, 8))

    @property
    def n_anchors(self) -> int:
        return sum(g * g * len(m) for g, m in zip(self.grids, self.masks))


def conv_shapes(cfg: YOLOv3Config):
    """``{"backbone", "neck", "heads"}``: (k, c_in, c_out) of every conv
    in the order it runs; the neck's three levels each hold (for levels
    1 and 2) the 1x1 route conv, the five convs and the 3x3 conv."""
    bb, c = [(3, 3, cfg.stem)], cfg.stem
    for w, n in zip(cfg.stage_widths, cfg.stage_blocks):
        bb.append((3, c, w))
        bb += [(1, w, w // 2), (3, w // 2, w)] * n
        c = w
    neck, heads = [], []
    out = len(cfg.masks[0]) * (5 + cfg.n_classes)
    c_in = cfg.stage_widths[-1]
    for level, w in enumerate(cfg.stage_widths[:1:-1]):
        if level:
            neck.append((1, w, w // 2))             # route, from the 1x1
            c_in = w // 2 + w                       # upsampled + skip
        neck += [(1, c_in, w // 2), (3, w // 2, w), (1, w, w // 2),
                 (3, w // 2, w), (1, w, w // 2), (3, w // 2, w)]
        heads.append((1, w, out))
    return {"backbone": bb, "neck": neck, "heads": heads}


def init_yolov3(cfg: YOLOv3Config, key):
    """Seeded weights in the program's layout: ``backbone`` and ``neck``
    lists of ``{"w", "gamma", "beta", "mean", "var"}`` per conv, and
    ``heads`` of ``{"w", "b"}``.  He-scaled convs, unit batch norm and
    the last batch norm of each residual branch at gamma 0.1, so that
    activations stay O(1) through the 23 residual adds."""
    shapes = conv_shapes(cfg)
    keys = iter(jax.random.split(key, sum(map(len, shapes.values()))))

    def w(k, ci, co):
        return jax.random.normal(next(keys), (k, k, ci, co),
                                 jnp.float32) * np.sqrt(2.0 / (k * k * ci))

    def bn(k, ci, co, gamma=1.0):
        return {"w": w(k, ci, co),
                "gamma": jnp.full((co,), gamma, jnp.float32),
                "beta": jnp.zeros((co,), jnp.float32),
                "mean": jnp.zeros((co,), jnp.float32),
                "var": jnp.ones((co,), jnp.float32)}

    branch_end = _branch_ends(cfg)
    return {"backbone": [bn(*s, gamma=0.1 if i in branch_end else 1.0)
                         for i, s in enumerate(shapes["backbone"])],
            "neck": [bn(*s) for s in shapes["neck"]],
            "heads": [{"w": w(*s) * 0.1,
                       "b": jnp.zeros((s[2],), jnp.float32)}
                      for s in shapes["heads"]]}


def _branch_ends(cfg: YOLOv3Config):
    """Indices in ``backbone`` of each residual branch's last conv."""
    out, i = set(), 0                   # i: the stage's stride-2 conv
    for n in cfg.stage_blocks:
        i += 1
        out |= {i + 2 * b + 2 for b in range(n)}
        i += 2 * n
    return out


def _conv(x, w, stride=1):
    p = w.shape[0] // 2
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((p, p), (p, p)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _cbl(leaf, x, cfg: YOLOv3Config, stride=1):
    """conv -> batch norm (inference form) -> leaky ReLU."""
    scale = leaf["gamma"] / (jnp.sqrt(leaf["var"]) + cfg.bn_eps)
    y = _conv(x, leaf["w"], stride) * scale + (leaf["beta"]
                                               - leaf["mean"] * scale)
    return jnp.where(y > 0, y, cfg.leaky * y)


def _upsample(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def yolov3_forward(params, cfg: YOLOv3Config, images):
    """images (B, S, S, 3) -> the three head outputs, (B, G, G,
    3 * (5 + n_classes)) at strides 32, 16 and 8."""
    with jax.named_scope("backbone"):
        bb = iter(params["backbone"])
        x = _cbl(next(bb), images, cfg)
        stages = []
        for n in cfg.stage_blocks:
            x = _cbl(next(bb), x, cfg, stride=2)
            for _ in range(n):
                h = _cbl(next(bb), x, cfg)
                x = x + _cbl(next(bb), h, cfg)
            stages.append(x)
    with jax.named_scope("neck"):
        nk = iter(params["neck"])
        outs, x = [], stages[-1]
        for level, head in enumerate(params["heads"]):
            if level:
                x = _upsample(_cbl(next(nk), route, cfg))
                x = jnp.concatenate([x, stages[-1 - level]], -1)
            for _ in range(5):
                x = _cbl(next(nk), x, cfg)
            route = x
            y = _cbl(next(nk), x, cfg)
            outs.append(_conv(y, head["w"]) + head["b"])
    return outs


def decode(cfg: YOLOv3Config, outs):
    """Head outputs -> boxes (B, A, 4) xyxy in [0, 1] image units and the
    class-major score plane (B, C, A) of sigmoid(obj) * sigmoid(cls)."""
    boxes, scores = [], []
    for y, mask in zip(outs, cfg.masks):
        B, G = y.shape[0], y.shape[1]
        y = y.reshape(B, G, G, len(mask), 5 + cfg.n_classes)
        cy, cx = np.meshgrid(np.arange(G), np.arange(G), indexing="ij")
        cell = np.stack([cx, cy], -1)[:, :, None, :].astype(np.float32)
        prior = np.asarray([cfg.anchors[m] for m in mask], np.float32)
        c = (jax.nn.sigmoid(y[..., :2]) + cell) / G
        wh = prior * jnp.exp(y[..., 2:4]) / cfg.image_size
        boxes.append(jnp.concatenate([c - wh / 2, c + wh / 2], -1)
                     .reshape(B, -1, 4))
        s = jax.nn.sigmoid(y[..., 4:5]) * jax.nn.sigmoid(y[..., 5:])
        scores.append(s.reshape(B, -1, cfg.n_classes).transpose(0, 2, 1))
    return jnp.concatenate(boxes, 1), jnp.concatenate(scores, 2)


def yolov3_detect(params, cfg: YOLOv3Config, images, score_thr=0.5,
                  iou_thr=0.45, max_out=32):
    """Full inference: forward, decode and exact class-aware
    suppression.  Returns per image ``(boxes, scores, classes, valid)``
    with ``max_out`` rows, as ``ssd.decode_detections`` does, and last
    ``nms_candidates`` (B,): the (anchor, class) pairs scored at least
    ``score_thr``.  The parts run under the named scopes ``backbone``,
    ``neck``, ``decode`` and ``nms``."""
    outs = yolov3_forward(params, cfg, images)
    with jax.named_scope("decode"):
        boxes, scores = decode(cfg, outs)
    with jax.named_scope("nms"):
        anchor, cls, score, valid = class_nms(
            boxes, scores, score_thr=score_thr, iou_thr=iou_thr,
            max_out=max_out)
        bxk = jnp.take_along_axis(boxes, anchor[..., None], axis=1)
        n_cand = jnp.sum(scores >= score_thr, axis=(1, 2), dtype=jnp.int32)
    return bxk, score, cls, valid, n_cand
