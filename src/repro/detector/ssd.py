"""Mini single-shot detector in pure JAX — the paper's executor payload
class (SSD300/YOLOv3 stand-in; pretrained weights are not available
offline, so examples train this on the synthetic benchmark video).

Conv backbone (stride-2 blocks) -> two feature maps -> per-anchor box
regression + objectness + class logits; decode + greedy NMS through the
fused batched Pallas NMS kernel (repro.kernels.nms) — the whole
micro-batch is suppressed in one launch.  Input: (B, 64, 64, 3).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..models.layers import truncated_normal


@dataclass(frozen=True)
class SSDConfig:
    image_size: int = 64
    n_classes: int = 3
    channels: Tuple[int, ...] = (16, 32, 64, 64)   # stride-2 conv blocks
    anchor_scales: Tuple[float, ...] = (0.15, 0.35)
    feature_strides: Tuple[int, ...] = (8, 16)     # maps at 8x8 and 4x4


def _conv_init(key, k, c_in, c_out):
    return {
        "w": truncated_normal(key, (k, k, c_in, c_out), jnp.float32,
                              1.0 / np.sqrt(k * k * c_in)),
        "b": jnp.zeros((c_out,), jnp.float32),
    }


def _conv(p, x, stride=1):
    # float32 on every backend: the TPU's default f32 conv is one bf16
    # pass, which reorders near-equal scores and changes which boxes
    # survive NMS against the float32 reference
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    return y + p["b"]


def make_anchors(cfg: SSDConfig) -> np.ndarray:
    """(A_total, 4) xyxy in [0,1] image coords."""
    out = []
    for stride, scale in zip(cfg.feature_strides, cfg.anchor_scales):
        g = cfg.image_size // stride
        cs = (np.arange(g) + 0.5) / g
        cx, cy = np.meshgrid(cs, cs)
        for ar in (1.0, 2.0):
            w = scale * np.sqrt(ar)
            h = scale / np.sqrt(ar)
            out.append(np.stack([cx - w / 2, cy - h / 2,
                                 cx + w / 2, cy + h / 2], -1).reshape(-1, 4))
    return np.concatenate(out, 0).astype(np.float32)


def init_ssd(cfg: SSDConfig, key):
    ks = jax.random.split(key, len(cfg.channels) + 2)
    p = {"backbone": []}
    c_in = 3
    for i, c in enumerate(cfg.channels):
        p["backbone"].append(_conv_init(ks[i], 3, c_in, c))
        c_in = c
    n_anchor_kinds = 2
    out_dim = n_anchor_kinds * (4 + 1 + cfg.n_classes)
    p["head8"] = _conv_init(ks[-2], 3, cfg.channels[-2], out_dim)
    p["head16"] = _conv_init(ks[-1], 3, cfg.channels[-1], out_dim)
    return p


def ssd_forward(p, cfg: SSDConfig, images):
    """images: (B, S, S, 3) -> (boxes_delta (B,A,4), obj (B,A),
    cls_logits (B,A,C))."""
    x = images
    feats = []
    for i, blk in enumerate(p["backbone"]):
        x = jax.nn.relu(_conv(blk, x, stride=2))
        feats.append(x)
    f8, f16 = feats[-2], feats[-1]           # (B,8,8,C), (B,4,4,C)
    outs = []
    for f, head in ((f8, p["head8"]), (f16, p["head16"])):
        y = _conv(head, f)                   # (B,g,g,2*(5+C))
        B, g, _, _ = y.shape
        outs.append(y.reshape(B, g * g * 2, 5 + cfg.n_classes))
    y = jnp.concatenate(outs, 1)             # (B, A, 5+C)
    return y[..., :4], y[..., 4], y[..., 5:]


def detector_loss(p, cfg: SSDConfig, images, gt_boxes, gt_classes, gt_mask,
                  anchors):
    """gt_boxes: (B,K,4) in [0,1]; gt_mask: (B,K) valid flags."""
    deltas, obj, cls_logits = ssd_forward(p, cfg, images)
    B, A = obj.shape
    anc = jnp.asarray(anchors)               # (A,4)

    def per_image(gtb, gtc, gtm):
        iou = _iou(anc, gtb)                 # (A,K)
        iou = iou * gtm[None, :]
        best_gt = jnp.argmax(iou, 1)         # (A,)
        best_iou = jnp.max(iou, 1)
        pos = best_iou >= 0.45
        tgt_box = gtb[best_gt]               # (A,4)
        tgt_cls = gtc[best_gt]
        return pos, tgt_box, tgt_cls

    pos, tgt_box, tgt_cls = jax.vmap(per_image)(gt_boxes, gt_classes,
                                                gt_mask)
    anc_wh = anc[:, 2:] - anc[:, :2]
    anc_c = (anc[:, :2] + anc[:, 2:]) / 2
    tgt_c = (tgt_box[..., :2] + tgt_box[..., 2:]) / 2
    tgt_wh = jnp.maximum(tgt_box[..., 2:] - tgt_box[..., :2], 1e-4)
    tgt_delta = jnp.concatenate(
        [(tgt_c - anc_c) / anc_wh, jnp.log(tgt_wh / anc_wh)], -1)

    posf = pos.astype(jnp.float32)
    n_pos = jnp.maximum(jnp.sum(posf), 1.0)
    box_l = jnp.sum(jnp.abs(deltas - tgt_delta).sum(-1) * posf) / n_pos
    obj_t = posf
    obj_l = jnp.mean(
        jnp.maximum(obj, 0) - obj * obj_t + jnp.log1p(jnp.exp(-jnp.abs(obj))))
    logz = jax.scipy.special.logsumexp(cls_logits, -1)
    gold = jnp.take_along_axis(cls_logits, tgt_cls[..., None], -1)[..., 0]
    cls_l = jnp.sum((logz - gold) * posf) / n_pos
    return box_l + obj_l + cls_l, {"box": box_l, "obj": obj_l, "cls": cls_l}


def _iou(a, b):
    tl = jnp.maximum(a[:, None, :2], b[None, :, :2])
    br = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    inter = jnp.prod(jnp.clip(br - tl, 0.0), -1)
    aa = jnp.prod(a[:, 2:] - a[:, :2], -1)
    ab = jnp.prod(b[:, 2:] - b[:, :2], -1)
    return inter / jnp.maximum(aa[:, None] + ab[None] - inter, 1e-9)


def decode_detections(p, cfg: SSDConfig, images, anchors, score_thr=0.4,
                      iou_thr=0.5, max_out=32, use_pallas=False):
    """Full inference: forward + box decode + fused batched NMS (one
    suppression launch for the whole micro-batch; Pallas kernel when
    use_pallas=True, its XLA twin otherwise).  Returns per-image
    (boxes, scores, classes, valid).  The three parts run under the
    named scopes ``backbone``, ``decode`` and ``nms``, which prefix the
    names of their ops in the compiled program."""
    with jax.named_scope("backbone"):
        deltas, obj, cls_logits = ssd_forward(p, cfg, images)
    with jax.named_scope("decode"):
        anc = jnp.asarray(anchors)
        anc_wh = anc[:, 2:] - anc[:, :2]
        anc_c = (anc[:, :2] + anc[:, 2:]) / 2
        c = anc_c + deltas[..., :2] * anc_wh
        wh = anc_wh * jnp.exp(jnp.clip(deltas[..., 2:], -4, 4))
        boxes = jnp.concatenate([c - wh / 2, c + wh / 2], -1)  # (B,A,4)
        scores = jax.nn.sigmoid(obj)
        classes = jnp.argmax(cls_logits, -1)
    with jax.named_scope("nms"):
        # score-thresholding and suppression are fused into the batched
        # NMS; stop_at_zero skips the zero-score tail, whose survivors
        # the seed path enumerated only to mask them back out of ``valid``
        keep, valid = kops.batched_nms(boxes, scores, iou_thr=iou_thr,
                                       score_thr=score_thr, max_out=max_out,
                                       stop_at_zero=True,
                                       use_pallas=use_pallas)
        sc = jnp.where(scores >= score_thr, scores, 0.0)
        bxk = jnp.take_along_axis(boxes, keep[..., None], axis=1)
        sck = jnp.take_along_axis(sc, keep, axis=1)
        clk = jnp.take_along_axis(classes, keep, axis=1)
        valid = valid & (sck > 0)
    return bxk, sck, clk, valid
