"""Jit'd dispatch layer over the Pallas kernels.

``_interpret()`` is the one place that decides how a Pallas kernel
runs: compiled by Mosaic on a TPU backend, interpreted anywhere else
(the kernel body runs as traced JAX ops, which checks results, not
speed).  ``use_pallas=False`` falls back to
the pure-jnp oracles in ref.py (the default inside model code, where XLA
fusion already does well; tests compare both paths).

NMS is the one exception to the "False means oracle" rule: the fused
batched NMS has an XLA twin of the *same* tiled algorithm
(``nms.batched_nms_xla``) which is the production path on hosts where
Pallas runs interpreted, so ``batched_nms(use_pallas=False)`` routes
there.  The slow oracles stay available as ``ref.nms_ref`` /
``ref.batched_nms_ref`` (tests assert bit-compatibility against them)
and the seed's per-image serial path survives as ``nms_serial``
(``tests/test_nms_fused.py`` checks the batched path against it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .association import greedy_assign_pallas as _assoc_pallas
from .association import greedy_assign_xla as _assoc_xla
from .decode_attention import decode_attention as _decode_pallas
from .flash_attention import flash_attention as _flash_pallas
from .iou import iou_matrix as _iou_pallas
from .nms import batched_nms_pallas as _nms_pallas
from .nms import batched_nms_xla as _nms_xla
from .roi import crop_resize_pallas as _crop_pallas
from .roi import crop_resize_xla as _crop_xla
from .roi import uncrop_boxes_pallas as _uncrop_pallas
from .roi import uncrop_boxes_xla as _uncrop_xla


def _interpret() -> bool:
    """True unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, scale=None, use_pallas=True):
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    return _flash_pallas(q, k, v, causal=causal, scale=scale,
                         interpret=_interpret())


def decode_attention(q, k, v, *, scale=None, use_pallas=True):
    if not use_pallas:
        return ref.decode_attention_ref(q, k, v, scale=scale)
    return _decode_pallas(q, k, v, scale=scale, interpret=_interpret())


def iou_matrix(a, b, *, use_pallas=True):
    if not use_pallas:
        return ref.iou_matrix_ref(a, b)
    return _iou_pallas(a, b, interpret=_interpret())


def batched_nms(boxes, scores, *, iou_thr=0.5, score_thr=None, max_out=64,
                tile=None, num_candidates=None, stop_at_zero=False,
                use_pallas=True):
    """Fused batched greedy NMS over a micro-batch of frames.

    boxes (B, A, 4) xyxy, scores (B, A) -> (keep (B, max_out) int32,
    valid (B, max_out) bool).  Exact (bit-compatible with
    ``ref.batched_nms_ref``) when ``num_candidates`` covers all boxes and
    ``stop_at_zero=False``; with ``score_thr`` + ``stop_at_zero=True``
    the valid-masked outputs still match the seed decode path exactly —
    zero-score survivors are simply not enumerated.
    """
    kw = dict(iou_thr=iou_thr, score_thr=score_thr, max_out=max_out,
              num_candidates=num_candidates, stop_at_zero=stop_at_zero)
    if tile is not None:
        kw["tile"] = tile
    if use_pallas:
        return _nms_pallas(boxes, scores, interpret=_interpret(), **kw)
    return _nms_xla(boxes, scores, **kw)


def greedy_assign(t_boxes, d_boxes, *, t_mask=None, d_mask=None,
                  t_cls=None, d_cls=None, iou_thr=0.3, use_pallas=True):
    """Fused IoU cost-matrix + greedy assignment over a frame batch
    (the tracker's association step).

    t_boxes (B, T, 4) xyxy predicted track boxes, d_boxes (B, D, 4)
    detections -> match (B, T) int32 (detection index per track slot or
    -1).  Masks default to all-true, class ids to all-zero (no class
    gate).  Like NMS, the fused batched path has an XLA twin of the
    same algorithm for non-TPU hosts; ``ref.greedy_assign_ref`` is the
    bit-compatibility oracle.
    """
    B, T, _ = t_boxes.shape
    D = d_boxes.shape[1]
    if T == 0 or D == 0:
        return jnp.full((B, T), -1, jnp.int32)
    t_mask = (jnp.ones((B, T), bool) if t_mask is None
              else t_mask.astype(bool))
    d_mask = (jnp.ones((B, D), bool) if d_mask is None
              else d_mask.astype(bool))
    t_cls = (jnp.zeros((B, T), jnp.int32) if t_cls is None
             else t_cls.astype(jnp.int32))
    d_cls = (jnp.zeros((B, D), jnp.int32) if d_cls is None
             else d_cls.astype(jnp.int32))
    if use_pallas:
        return _assoc_pallas(t_boxes, d_boxes, t_mask, d_mask, t_cls,
                             d_cls, iou_thr=iou_thr,
                             interpret=_interpret())
    return _assoc_xla(t_boxes, d_boxes, t_mask, d_mask, t_cls, d_cls,
                      iou_thr=iou_thr)


def crop_resize(images, rois, *, out_size, use_pallas=True):
    """ROI crop+resize for the cascade's hierarchical second pass:
    images (B, H, W, ch), rois (B, R, 4) normalized xyxy ->
    crops (B, R, C, C, ch) float32.  Like NMS, ``use_pallas=False``
    routes to the XLA twin of the same float32 index math (the
    production path on non-TPU hosts); ``ref.crop_resize_ref`` is the
    bit-compatibility oracle."""
    if not use_pallas:
        return _crop_xla(images, rois, out_size=out_size)
    return _crop_pallas(images, rois, out_size=out_size,
                        interpret=_interpret())


def uncrop_boxes(boxes, rois, *, bounds, crop_size, use_pallas=True):
    """Map second-pass detections from crop pixel coordinates back into
    the parent frame.  boxes (..., 4) in [0, crop_size], rois (..., 4)
    normalized windows (broadcast), bounds = (W, H).  XLA twin on
    ``use_pallas=False``; ``ref.uncrop_boxes_ref`` is the oracle."""
    if not use_pallas:
        return _uncrop_xla(boxes, rois, bounds=tuple(bounds),
                           crop_size=crop_size)
    return _uncrop_pallas(boxes, rois, bounds=tuple(bounds),
                          crop_size=crop_size, interpret=_interpret())


def nms(boxes, scores, iou_thr=0.5, max_out=64, use_pallas=True):
    """Single-frame greedy NMS: routed through the fused batched kernel
    (B=1).  Returns (keep_idx (max_out,), valid mask), identical to
    ``ref.nms_ref``."""
    keep, valid = batched_nms(boxes[None], scores[None], iou_thr=iou_thr,
                              max_out=max_out, use_pallas=use_pallas)
    return keep[0], valid[0]


def nms_serial(boxes, scores, iou_thr=0.5, max_out=64, use_pallas=True):
    """The seed's per-image NMS: IoU matrix (Pallas kernel when
    ``use_pallas``) + an A-step sequential suppress loop.  Kept as the
    benchmark baseline for the fused batched path."""
    iou = iou_matrix(boxes, boxes, use_pallas=use_pallas)
    order = jnp.argsort(-scores)

    def body(i, state):
        keep, kcount, alive = state
        idx = order[i]
        ok = alive[idx]
        keep = keep.at[kcount].set(jnp.where(ok, idx, keep[kcount]))
        kcount = kcount + ok.astype(jnp.int32)
        alive = alive & ~((iou[idx] >= iou_thr) & ok)
        return keep, kcount, alive

    keep0 = jnp.zeros((max_out,), jnp.int32)
    alive0 = jnp.ones((boxes.shape[0],), bool)
    keep, kcount, _ = jax.lax.fori_loop(0, boxes.shape[0], body,
                                        (keep0, 0, alive0))
    valid = jnp.arange(max_out) < kcount
    return keep, valid
