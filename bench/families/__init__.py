"""Detector families: one module per architecture, found by name.

A configuration file names its family (``"family": "ssd"``), and the
harness loads ``bench/families/<family>.py`` by path, as it loads the
metric readers, so a new architecture comes as a new file.  A family
module gives the harness everything that depends on the architecture:

* ``check(cfg)``: raises ``ValueError`` where the family's own keys of
  the configuration are missing or inconsistent;
* ``image_size(cfg)``: the square input size in pixels;
* ``program_config(cfg)``: the program's detector configuration, for
  ``DetectionEngine(cfg=...)``; the only place the program is imported;
* ``make_params(cfg, seed)``: the weights from the seed, made on the
  device in the layout the program's detector takes;
* ``candidates(cfg, params, images, precision)``: the plain reference,
  importing nothing of the program: per frame a ``Candidates`` over all
  of its anchors; ``precision`` is ``"highest"`` (float32) or ``"high"``
  (the lower-precision control);
* ``survivors(cand, serve)``: what the reference's suppression serves
  from one frame's candidates, as ``Rows`` in output order.  A family
  that suppresses per class may serve one anchor once for each class;
* ``flops_per_frame(cfg)``: the detector's operations per frame as the
  ``mfu`` metric counts them;
* ``flops_by_scope(cfg)`` and ``bytes_by_scope(cfg, frames_per_call)``:
  per named scope of the detect program, operations per frame and
  bytes per call, each a lower bound of what the chip must do there
  (weights counted once per call).  A scope without a count is absent.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np


class Candidates(NamedTuple):
    """One frame's reference outputs over all ``A`` anchors, before
    suppression."""
    boxes: np.ndarray       # (A, 4) xyxy
    # (A,): one detection per anchor, served with this score whatever
    # its class; (A, C): one per anchor and class, with that class's
    # score
    scores: np.ndarray
    # (A, C) where scores are per anchor: the anchor's class is the
    # argmax of these (``cls_gap`` reads how far the served class lies
    # below it); None where scores are per class, each class keyed on
    # its own
    class_scores: Optional[np.ndarray]


class Rows(NamedTuple):
    """Detections the reference serves for one frame, in output order."""
    anchor: np.ndarray      # (K,) int
    cls: np.ndarray         # (K,) int
    boxes: np.ndarray       # (K, 4)
    scores: np.ndarray      # (K,)
