"""Pallas kernels for the detection fast path and the LLM substrate.

Fast path
---------
The detection hot path is ``nms.batched_nms_pallas``: fused batched
greedy NMS with a leading batch grid dimension (one program per frame,
one launch per micro-batch).  Layout and tiling choices:

* Boxes are carried transposed as (4, A) coordinate planes per frame —
  the candidate index lands on the 128-wide lane dimension (the natural
  (A, 4) layout would waste 124/128 lanes per vector op), mirroring
  ``iou.py``.
* Candidates are sorted by (thresholded) score once in the wrapper,
  then suppressed in tiles of 32: each tile computes its IoU strip
  against all later candidates on the fly in VMEM, so the full (A, A)
  IoU matrix never exists in HBM.
* Within a tile, greedy NMS is solved by a suppression *fixpoint*
  (3-5 vectorized sweeps) instead of a serial per-box loop; the tile
  loop exits early once ``max_out`` survivors exist.
* Survivor -> output-slot assignment counts survivors tile by tile
  (a (tile, A) masked sum) — never a dense (A, A) triangular product,
  which would put the quadratic operand back into VMEM.

Every ``*_pallas`` entry takes ``interpret`` with no default:
``ops`` passes ``ops._interpret()``, the one place that picks Mosaic
(TPU) or the interpreter (any other backend), and a direct caller must
say which.  ``tests/test_tpu_compile.py`` compiles the frame-path
kernels for a TPU v5e on every test run.

``nms.batched_nms_xla`` is the same algorithm as batched XLA ops and is
the production path on hosts where Pallas runs interpreted;
``ops.batched_nms`` dispatches between the two, and ``ref.nms_ref`` /
``ref.batched_nms_ref`` remain the bit-compatibility oracles.

``association.greedy_assign_pallas`` follows the same three-tier
pattern for the tracking subsystem's data-association step (IoU cost
matrix + greedy assignment fused into one launch per frame batch, XLA
twin ``greedy_assign_xla``, oracle ``ref.greedy_assign_ref``,
dispatch ``ops.greedy_assign``).

``roi.crop_resize_pallas`` / ``roi.uncrop_boxes_pallas`` carry the
cascade's hierarchical second pass (cheap first-pass boxes -> ROI crops
batched into the heavy model -> detections mapped back to the parent
frame), again with XLA twins and ``ref`` oracles; the nearest-neighbor
gather is expressed as two one-hot matmuls so it runs on the MXU.
"""
from . import ops, ref
from .association import greedy_assign_pallas, greedy_assign_xla
from .decode_attention import decode_attention
from .flash_attention import flash_attention
from .iou import iou_matrix
from .nms import batched_nms_pallas, batched_nms_xla
from .roi import (crop_resize_pallas, crop_resize_xla,
                  uncrop_boxes_pallas, uncrop_boxes_xla)

__all__ = ["ops", "ref", "decode_attention", "flash_attention",
           "iou_matrix", "batched_nms_pallas", "batched_nms_xla",
           "greedy_assign_pallas", "greedy_assign_xla",
           "crop_resize_pallas", "crop_resize_xla",
           "uncrop_boxes_pallas", "uncrop_boxes_xla"]
