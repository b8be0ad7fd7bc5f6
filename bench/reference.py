"""Plain reference of what every detector family shares, and of the
tracker.

Nothing here imports the program.  Each family module
(``bench/families/``) builds its detector's weights from the seed and
recomputes its candidates; it draws its key from ``params_key``, and a
family that suppresses class-agnostically takes ``nms``:

* ``nms``: greedy NMS over the score-sorted thresholded candidates
  (``iou >= iou_thr`` suppresses, at most ``max_out`` survivors, zero
  scores never kept);
* the tracker, per camera: constant-velocity Kalman predict, greedy
  class-gated IoU association (globally best pair first, row-major
  ties), measurement update, coast bookkeeping, births into free slots
  in rank order with lowest-score coasting tracks evicted on overflow,
  and the confirmed-track output (w and h floored at 1).

The tracker reference runs in float64 numpy; ``dtype=bfloat16`` gives
the control, one precision step below.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import numpy as np


# ----------------------------------------------------------------- shared
def params_key(seed: int):
    """A JAX key from any whole-number seed (also above 2**31)."""
    import jax
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0] >> 1)),
                              int(words[1] >> 1))


def iou_one(box, boxes) -> np.ndarray:
    tl = np.maximum(box[:2], boxes[:, :2])
    br = np.minimum(box[2:], boxes[:, 2:])
    inter = np.prod(np.clip(br - tl, 0.0, None), -1)
    a = np.prod(box[2:] - box[:2])
    b = np.prod(boxes[:, 2:] - boxes[:, :2], -1)
    return inter / np.maximum(a + b - inter, 1e-9)


def nms(boxes, scores, *, score_thr: float, iou_thr: float,
        max_out: int) -> np.ndarray:
    """Anchor indices of the greedy-NMS survivors, in output order."""
    s = np.where(scores >= score_thr, scores, 0.0)
    order = np.argsort(-s, kind="stable")
    alive = np.ones(len(s), bool)
    keep = []
    for i in order:
        if s[i] <= 0.0 or len(keep) == max_out:
            break
        if not alive[i]:
            continue
        keep.append(int(i))
        alive &= ~(iou_one(boxes[i], boxes) >= iou_thr)
    return np.asarray(keep, np.int64)


# ----------------------------------------------------------------- tracker
@dataclass(frozen=True)
class TrackerParams:
    """The tracker's documented constants (``TrackerConfig`` defaults)."""
    capacity: int = 64
    iou_thr: float = 0.3
    min_hits: int = 2
    max_coast: int = 12
    score_decay: float = 0.95
    birth_score_thr: float = 0.0
    q: float = 1.0
    r: float = 9.0
    p0_vel: float = 25.0


def _xyxy(pos):
    wh = np.maximum(pos[:, 2:], 1.0)
    return np.concatenate([pos[:, :2] - wh / 2.0, pos[:, :2] + wh / 2.0], -1)


def _cxcywh(boxes):
    return np.concatenate([(boxes[:, :2] + boxes[:, 2:]) / 2.0,
                           boxes[:, 2:] - boxes[:, :2]], -1)


class Track:
    """One camera's track table, stepped frame by frame."""

    def __init__(self, prm: TrackerParams, dtype=np.float64):
        T, self.prm, self.dt = prm.capacity, prm, dtype
        self.pos = np.zeros((T, 4), dtype)
        self.vel = np.zeros((T, 4), dtype)
        self.cov = np.zeros((T, 4, 3), dtype)
        self.score = np.zeros(T, dtype)
        self.cls = np.zeros(T, np.int64)
        self.tid = np.full(T, -1, np.int64)
        self.hits = np.zeros(T, np.int64)
        self.tsu = np.zeros(T, np.int64)
        self.active = np.zeros(T, bool)
        self.next_id = 0
        self.matched = 0          # detections associated with a track

    def coast(self):
        p, dt = self.prm, self.dt
        pxx, pxv, pvv = (self.cov[..., i] for i in range(3))
        self.pos = (self.pos + self.vel).astype(dt)
        self.cov = np.stack([pxx + (2.0 * pxv + pvv) + p.q / 4.0,
                             pxv + pvv + p.q / 2.0,
                             pvv + p.q], -1).astype(dt)
        self.tsu = self.tsu + self.active
        self.score = np.where(self.active, self.score * p.score_decay,
                              self.score).astype(dt)
        self.active = self.active & (self.tsu <= p.max_coast)

    def _associate(self, boxes, valid, classes) -> np.ndarray:
        T, D = len(self.pos), len(boxes)
        tb = _xyxy(self.pos)
        cost = np.stack([iou_one(b, boxes) for b in tb])
        ok = (self.active[:, None] & valid[None, :]
              & (self.cls[:, None] == classes[None, :]))
        cost = np.where(ok, cost, -1.0)
        match = np.full(T, -1, np.int64)
        for _ in range(min(T, D)):
            i, j = divmod(int(np.argmax(cost)), D)
            if cost[i, j] < self.prm.iou_thr:
                break
            match[i] = j
            cost[i, :] = -1.0
            cost[:, j] = -1.0
        return match

    def step(self, boxes, scores, classes, valid) -> np.ndarray:
        """One detection frame; returns the track id of each detection
        (-1 for invalid rows)."""
        p, dt = self.prm, self.dt
        boxes = np.asarray(boxes).astype(dt)
        scores = np.asarray(scores).astype(dt)
        classes = np.asarray(classes).astype(np.int64)
        valid = np.asarray(valid, bool)
        D = len(boxes)
        self.coast()
        match = self._associate(boxes, valid, classes)
        matched = match >= 0
        self.matched += int(matched.sum())
        mi = np.maximum(match, 0)
        z = _cxcywh(boxes[mi])
        pxx, pxv, pvv = (self.cov[..., i] for i in range(3))
        s = pxx + p.r
        k1, k2 = pxx / s, pxv / s
        y = z - self.pos
        g = matched[:, None]
        self.pos = np.where(g, self.pos + k1 * y, self.pos).astype(dt)
        self.vel = np.where(g, self.vel + k2 * y, self.vel).astype(dt)
        cov_u = np.stack([(1.0 - k1) * pxx, (1.0 - k1) * pxv,
                          pvv - k2 * pxv], -1)
        self.cov = np.where(g[..., None], cov_u, self.cov).astype(dt)
        self.score = np.where(matched, scores[mi], self.score).astype(dt)
        self.hits = self.hits + matched
        self.tsu = np.where(matched, 0, self.tsu)

        taken = np.zeros(D, bool)
        taken[match[matched]] = True
        unmatched = valid & ~taken & (scores >= p.birth_score_thr)
        free = ~self.active
        need = max(int(unmatched.sum()) - int(free.sum()), 0)
        evictable = self.active & ~matched
        key = np.where(evictable, self.score, np.inf)
        rank = np.argsort(np.argsort(key, kind="stable"), kind="stable")
        evict = evictable & (rank < need)
        free = free | evict
        d_rank = np.cumsum(unmatched) - unmatched
        t_rank = np.cumsum(free) - free
        pair = (free[:, None] & unmatched[None, :]
                & (t_rank[:, None] == d_rank[None, :]))
        birth = pair.any(-1)
        bidx = np.argmax(pair, -1)
        bz = _cxcywh(boxes[bidx])
        self.pos = np.where(birth[:, None], bz, self.pos).astype(dt)
        self.vel = np.where(birth[:, None], 0.0, self.vel).astype(dt)
        cov0 = np.zeros((4, 3), dt)
        cov0[:, 0], cov0[:, 2] = p.r, p.p0_vel
        self.cov = np.where(birth[:, None, None], cov0, self.cov).astype(dt)
        self.score = np.where(birth, scores[bidx], self.score).astype(dt)
        self.cls = np.where(birth, classes[bidx], self.cls)
        self.tid = np.where(birth, self.next_id + t_rank, self.tid)
        self.next_id += int(birth.sum())
        self.hits = np.where(birth, 1, self.hits)
        self.tsu = np.where(birth, 0, self.tsu)
        self.active = (self.active & ~evict) | birth
        hit = (match[:, None] == np.arange(D)[None, :]) & matched[:, None]
        det_tid = np.where(hit | pair, self.tid[:, None], -1).max(0)
        return np.where(valid, det_tid, -1)

    def row(self) -> Dict[str, np.ndarray]:
        """The table in the fields of the program's portable track row."""
        return {"pos": self.pos, "vel": self.vel, "cov": self.cov,
                "score": self.score, "cls": self.cls, "track_id": self.tid,
                "hits": self.hits, "tsu": self.tsu, "active": self.active,
                "next_id": np.asarray(self.next_id)}

    def output(self):
        """(boxes (T, 4) xyxy, scores, classes, ids, emitted mask)."""
        emit = self.active & (self.hits >= self.prm.min_hits)
        return _xyxy(self.pos), self.score, self.cls, self.tid, emit
