"""The numbers that decide ``correct``: what the timed path produced,
against the plain reference (``reference.py``).

Detector, on a seeded sample of the frames the window detected, against
each frame's ``Candidates`` and the ``Rows`` its family's ``survivors``
serves (``bench/families/__init__.py``).  A detection is keyed on its
anchor where the family serves one detection per anchor, and on its
anchor and class where it serves one per anchor and class:

* ``det_gap`` -- each served detection is paired with the reference
  candidate (any anchor) nearest to it in box and score, the score of
  its class where scores are per class; the largest such distance (max
  of the box coordinates' and the score's absolute differences, image
  units).
* ``cls_gap`` -- at that anchor, how far the reference's class score
  of the served class lies below the reference's best; the largest.  A
  family that serves any class has no such score and reads 0: its
  classes are held by the keys.
* ``nms_miss`` -- the keys of the reference's survivors and of the
  served detections, as sets per frame: the size of their symmetric
  difference, plus the served detections of a class the detector does
  not have, over the reference's survivor count.

Tracker, on a seeded sample of the cameras, over every frame the window
served for them (detected frames step the reference tracker with the
served detections, interpolated frames coast it):

* ``track_miss`` -- share of compared items that disagree: the track id
  of each served detection, and the emitted mask and track id of every
  slot of the table an interpolated frame carries.
  The track table the path holds once the window is over is compared
  too: ids, activity, hit and coast counters and classes of every slot.
* ``track_gap`` -- largest difference, relative to the reference's
  value where that is above 1 and absolute below, of a box or score of
  an interpolated frame's table, and of a position, velocity,
  covariance or score of the final table, over the slots that hold the
  same track on both sides.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from . import reference as ref

DET_NUMBERS = ("det_gap", "cls_gap", "nms_miss")
TRACK_NUMBERS = ("track_miss", "track_gap")


def detector_numbers(served: Sequence[tuple], cands: Sequence,
                     serve: dict, survivors: Callable) -> Dict[str, float]:
    """``served``: per frame ``(boxes, scores, classes, valid)`` as the
    path emitted them; ``cands``: per frame the reference's
    ``Candidates``; ``survivors``: the family's suppression rule,
    ``(cand, serve) -> Rows``."""
    det_gap = cls_gap = 0.0
    miss = total = 0
    for (bx, sc, cl, va), cand in zip(served, cands):
        per_class = np.ndim(cand.scores) == 2
        rows = survivors(cand, serve)
        want = set(zip(rows.anchor.tolist(), rows.cls.tolist())
                   if per_class else rows.anchor.tolist())
        n_cls = np.shape(cand.scores if per_class else
                         cand.class_scores)[-1]
        picked = set()
        for j in np.flatnonzero(np.asarray(va, bool)):
            c = int(cl[j])
            if not 0 <= c < n_cls:
                miss += 1       # a class the detector does not have
                continue
            rs = cand.scores[:, c] if per_class else cand.scores
            d = np.maximum(np.abs(cand.boxes - np.asarray(bx[j], np.float64))
                           .max(-1), np.abs(rs - float(sc[j])))
            a = int(np.argmin(d))
            picked.add((a, c) if per_class else a)
            det_gap = max(det_gap, float(d[a]))
            if cand.class_scores is not None:
                lg_a = np.asarray(cand.class_scores[a], np.float64)
                cls_gap = max(cls_gap, float(lg_a.max() - lg_a[c]))
        miss += len(picked.symmetric_difference(want))
        total += len(want)
    return {"det_gap": det_gap, "cls_gap": cls_gap,
            "nms_miss": miss / max(total, 1)}


ROW_EXACT = ("track_id", "active", "hits", "tsu", "cls", "next_id")
ROW_REAL = ("pos", "vel", "cov", "score")


def _rel_gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size == 0:
        return 0.0
    return float((np.abs(got - want) / np.maximum(np.abs(want), 1.0)).max())


def tracker_numbers(streams: Dict[int, List], finals: Dict[int, dict],
                    prm: ref.TrackerParams) -> Tuple[Dict[str, float], int]:
    """``streams``: camera -> its served responses in arrival order, each
    with ``interpolated``, ``boxes``, ``scores``, ``classes``, ``valid``
    and ``track_ids``; ``finals``: camera -> the track table the path
    holds after the window (the fields of a portable track row).
    Returns the numbers and how many served detections the reference
    associated with an existing track: where that is 0 the comparison
    covers births, coasting and eviction but not the Kalman update."""
    bad = items = matched = 0
    gap = 0.0
    for sid, rs in streams.items():
        trk = ref.Track(prm)
        for r in rs:
            if not r.interpolated:
                tid = trk.step(r.boxes, r.scores, r.classes, r.valid)
                v = np.asarray(r.valid, bool)
                got = np.asarray(r.track_ids)
                bad += int((got[v] != tid[v]).sum())
                items += int(v.sum())
                continue
            trk.coast()
            tb, ts, tc, tid, emit = trk.output()
            got_emit = np.asarray(r.valid, bool)
            got_tid = np.asarray(r.track_ids)
            bad += int((emit != got_emit).sum() + (got_tid != tid).sum())
            items += 2 * len(emit)
            used = (tid >= 0) & (got_tid == tid)
            gap = max(gap, _rel_gap(np.asarray(r.boxes)[used], tb[used]),
                      _rel_gap(np.asarray(r.scores)[used], ts[used]))
        matched += trk.matched
        row = finals.get(sid)
        if row is None:
            continue
        want = trk.row()
        for f in ROW_EXACT:
            g, w = np.asarray(row[f]), np.asarray(want[f])
            bad += int((g != w).sum())
            items += int(w.size)
        used = (want["track_id"] >= 0) & (np.asarray(row["track_id"])
                                          == want["track_id"])
        for f in ROW_REAL:
            gap = max(gap, _rel_gap(np.asarray(row[f])[used],
                                    want[f][used]))
    return {"track_miss": bad / max(items, 1), "track_gap": gap}, matched


class Replay:
    """What a control puts in the program's place on the tracker: the
    reference at a lower precision, fed the same detections and drop
    schedule, its outputs in the served responses' shape."""

    def __init__(self, interpolated, boxes, scores, classes, valid,
                 track_ids):
        self.interpolated = interpolated
        self.boxes, self.scores, self.classes = boxes, scores, classes
        self.valid, self.track_ids = valid, track_ids


def replay_tracker(streams: Dict[int, List], prm: ref.TrackerParams,
                   dtype):
    """The reference tracker at ``dtype`` fed each camera's detections
    and drop schedule: ``(responses, final tables)`` in the shape
    ``tracker_numbers`` compares."""
    out, finals = {}, {}
    for sid, rs in streams.items():
        trk = ref.Track(prm, dtype)
        out[sid] = []
        for r in rs:
            if not r.interpolated:
                tid = trk.step(r.boxes, r.scores, r.classes, r.valid)
                out[sid].append(Replay(False, r.boxes, r.scores, r.classes,
                                       r.valid, tid))
            else:
                trk.coast()
                tb, ts, tc, tid, emit = trk.output()
                out[sid].append(Replay(True, tb, ts, tc, emit, tid))
        finals[sid] = trk.row()
    return out, finals
