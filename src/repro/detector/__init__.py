"""Detectors, and the one dispatch that picks a detector by the type of
its configuration (``detector_for``)."""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

from .ssd import (SSDConfig, decode_detections, detector_loss, init_ssd,
                  make_anchors, ssd_forward)
from .yolo import YOLOv3Config, init_yolov3, yolov3_detect, yolov3_forward


class Detector(NamedTuple):
    """What the serving engine needs of a detector."""
    init: Callable          # (cfg, key) -> params
    # (params, images) -> (boxes, scores, classes, valid[, counter]), the
    # configuration and serving thresholds bound
    infer: Callable
    counter: Optional[str]   # name of the (B,) output after ``valid``


def detector_for(cfg, *, score_thr, iou_thr, max_out,
                 use_pallas=False) -> Detector:
    """The detector ``cfg``'s type names: ``SSDConfig`` -> the mini-SSD
    with class-agnostic NMS (``decode_detections``), ``YOLOv3Config`` ->
    YOLOv3 with exact class-aware NMS (``yolov3_detect``), which counts
    ``nms_candidates`` and has no Pallas kernel."""
    if isinstance(cfg, YOLOv3Config):
        if use_pallas:
            raise ValueError("YOLOv3's class-aware suppression has no "
                             "Pallas kernel: use_pallas=True serves only "
                             "an SSDConfig")
        return Detector(init_yolov3, lambda p, x: yolov3_detect(
            p, cfg, x, score_thr=score_thr, iou_thr=iou_thr,
            max_out=max_out), "nms_candidates")
    if isinstance(cfg, SSDConfig):
        anchors = make_anchors(cfg)
        return Detector(init_ssd, lambda p, x: decode_detections(
            p, cfg, x, anchors, score_thr=score_thr, iou_thr=iou_thr,
            max_out=max_out, use_pallas=use_pallas), None)
    raise TypeError(f"no detector for a {type(cfg).__name__}")


__all__ = ["Detector", "SSDConfig", "YOLOv3Config", "decode_detections",
           "detector_for", "detector_loss", "init_ssd", "init_yolov3",
           "make_anchors", "ssd_forward", "yolov3_detect", "yolov3_forward"]
