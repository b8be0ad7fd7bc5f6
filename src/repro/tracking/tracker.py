"""Batched multi-object tracker: fixed-capacity track table + masked
lifecycle updates, one fused launch per frame batch.

The track table is a struct-of-arrays ``TrackerState`` with a leading
batch axis (B independent streams tracked in lockstep — the serving
engine uses B=1, a multi-camera NVR deployment raises it).  No Python
object per track ever exists: birth, confirmation, coasting and death
are all masked array updates inside one jitted ``step``:

  predict  — constant-velocity Kalman predict on every slot, age +=1,
             score decay while coasting, kill after ``max_coast``
             frames without a matched detection (the slot's ``active``
             bit drops; its storage is reused by the next birth).
  associate— fused IoU cost + greedy assignment kernel
             (``kernels/association.py``), class-gated.
  update   — Kalman measurement update on matched slots; hit counters
             drive confirmation (``min_hits``).
  birth    — unmatched detections land in free slots via the same
             exclusive-cumsum rank trick the NMS kernel uses for slot
             assignment (k-th unmatched detection -> k-th free slot),
             so birth is O(T·D) vectorized, not a Python scan.  When
             unmatched detections outnumber free slots, the
             lowest-score COASTING tracks are evicted to make room
             (overflow eviction); only a table whose every slot
             matched a detection this frame — nothing safe to evict —
             still drops the overflow birth with ``det_tid = -1``.

``output`` emits the confirmed, alive slots — the boxes a dropped frame
gets instead of nothing.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .association import associate, cxcywh_to_xyxy, xyxy_to_cxcywh
from .kalman import init_cov, kf_predict, kf_update


@dataclass(frozen=True)
class TrackerConfig:
    capacity: int = 64         # track-table slots per stream
    iou_thr: float = 0.3       # association gate
    min_hits: int = 2          # matches before a track is emitted
    max_coast: int = 12        # frames without a match before death
    score_decay: float = 0.95  # per-coasted-frame score multiplier
    birth_score_thr: float = 0.0   # detections below never seed tracks
    q: float = 1.0             # process noise intensity (px^2/frame^4)
    r: float = 9.0             # measurement noise variance (px^2)
    p0_vel: float = 25.0       # fresh-track velocity variance


class TrackerState(NamedTuple):
    pos: jnp.ndarray        # (B, T, 4) cx, cy, w, h
    vel: jnp.ndarray        # (B, T, 4)
    cov: jnp.ndarray        # (B, T, 4, 3) [p_xx, p_xv, p_vv] per coord
    score: jnp.ndarray      # (B, T) last matched detection score, decayed
    cls: jnp.ndarray        # (B, T) int32
    track_id: jnp.ndarray   # (B, T) int32 (globally unique per stream)
    hits: jnp.ndarray       # (B, T) int32 total matches
    tsu: jnp.ndarray        # (B, T) int32 frames since last match
    active: jnp.ndarray     # (B, T) bool
    next_id: jnp.ndarray    # (B,) int32


def init_state(batch: int, cfg: TrackerConfig) -> TrackerState:
    B, T = batch, cfg.capacity
    return TrackerState(
        pos=jnp.zeros((B, T, 4), jnp.float32),
        vel=jnp.zeros((B, T, 4), jnp.float32),
        cov=jnp.zeros((B, T, 4, 3), jnp.float32),
        score=jnp.zeros((B, T), jnp.float32),
        cls=jnp.zeros((B, T), jnp.int32),
        track_id=jnp.full((B, T), -1, jnp.int32),
        hits=jnp.zeros((B, T), jnp.int32),
        tsu=jnp.zeros((B, T), jnp.int32),
        active=jnp.zeros((B, T), bool),
        next_id=jnp.zeros((B,), jnp.int32),
    )


def _tick(state: TrackerState, cfg: TrackerConfig) -> TrackerState:
    """One frame of time passing: Kalman predict + coast bookkeeping."""
    pos, vel, cov = kf_predict(state.pos, state.vel, state.cov, cfg.q)
    tsu = state.tsu + state.active
    score = jnp.where(state.active, state.score * cfg.score_decay,
                      state.score)
    active = state.active & (tsu <= cfg.max_coast)
    return state._replace(pos=pos, vel=vel, cov=cov, tsu=tsu,
                          score=score, active=active)


@functools.partial(jax.jit, static_argnames=("cfg",))
def coast(state: TrackerState, cfg: TrackerConfig) -> TrackerState:
    """Advance the table over a frame with no detections (a frame the
    executors never saw).  Not a miss: lifecycle is clocked in frames,
    so ``max_coast`` bounds the total interpolation span either way."""
    return _tick(state, cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas"))
def step(state: TrackerState, boxes, scores, classes, valid,
         cfg: TrackerConfig, use_pallas: bool = False):
    """One detection frame per stream: predict, associate, update,
    birth — all masked array updates, one launch per frame batch.

    boxes (B, D, 4) xyxy, scores (B, D), classes (B, D), valid (B, D).
    Returns (new_state, det_track_id (B, D) int32): the track id each
    detection landed on (matched or newborn), -1 for unused slots.
    """
    B, T = state.active.shape
    D = boxes.shape[1]
    boxes = boxes.astype(jnp.float32)
    scores = scores.astype(jnp.float32)
    classes = classes.astype(jnp.int32)
    valid = valid.astype(bool)

    state = _tick(state, cfg)

    # -------------------------------------------------------- associate
    match = associate(state.pos, state.active, state.cls, boxes, valid,
                      classes, cfg.iou_thr, use_pallas)      # (B, T)
    matched = match >= 0
    mi = jnp.maximum(match, 0)
    z = xyxy_to_cxcywh(jnp.take_along_axis(boxes, mi[..., None], axis=1))

    # ----------------------------------------------------------- update
    pos, vel, cov = kf_update(state.pos, state.vel, state.cov, z, cfg.r,
                              matched[..., None])
    score = jnp.where(matched, jnp.take_along_axis(scores, mi, axis=1),
                      state.score)
    hits = state.hits + matched
    tsu = jnp.where(matched, 0, state.tsu)

    # ------------------------------------------------------------ birth
    darange = jnp.arange(D, dtype=jnp.int32)
    taken = jnp.any((match[..., None] == darange[None, None]) &
                    matched[..., None], axis=1)              # (B, D)
    unmatched = valid & ~taken & (scores >= cfg.birth_score_thr)
    free = ~state.active

    # ---------------------------------------------- overflow eviction
    # When unmatched detections outnumber free slots, births used to be
    # silently dropped (det_tid stayed -1 with no signal).  Instead the
    # lowest-score COASTING tracks (active but unmatched this frame)
    # give up exactly the missing slots; every evicted slot is
    # guaranteed to be reborn below, because the eviction count never
    # exceeds n_unmatched - n_free.  With no overflow ``need`` is 0 and
    # this whole block is the identity.
    need = jnp.maximum(jnp.sum(unmatched, -1) - jnp.sum(free, -1),
                       0)[:, None]                           # (B, 1)
    evictable = state.active & ~matched
    # ascending-score rank among evictable slots (ties -> lower index
    # first): double stable argsort = rank, O(T log T) — non-evictable
    # slots sort last behind +inf keys and are masked out anyway
    key = jnp.where(evictable, state.score, jnp.inf)
    rank = jnp.argsort(jnp.argsort(key, axis=-1), axis=-1)   # (B, T)
    evict = evictable & (rank < need)
    free = free | evict
    d_rank = jnp.cumsum(unmatched, -1) - unmatched           # excl. rank
    t_rank = jnp.cumsum(free, -1) - free
    pair = (free[:, :, None] & unmatched[:, None, :] &
            (t_rank[:, :, None] == d_rank[:, None, :]))      # (B, T, D)
    birth = jnp.any(pair, -1)                                # (B, T)
    bidx = jnp.argmax(pair, -1)                              # det index
    bz = xyxy_to_cxcywh(jnp.take_along_axis(boxes, bidx[..., None],
                                            axis=1))
    b3 = birth[..., None]
    pos = jnp.where(b3, bz, pos)
    vel = jnp.where(b3, 0.0, vel)
    cov = jnp.where(b3[..., None],
                    init_cov((B, T, 4), cfg.r, cfg.p0_vel), cov)
    score = jnp.where(birth, jnp.take_along_axis(scores, bidx, axis=1),
                      score)
    cls = jnp.where(birth, jnp.take_along_axis(classes, bidx, axis=1),
                    state.cls)
    new_id = state.next_id[:, None] + t_rank
    track_id = jnp.where(birth, new_id, state.track_id)
    next_id = state.next_id + jnp.sum(birth, -1, dtype=jnp.int32)
    hits = jnp.where(birth, 1, hits)
    tsu = jnp.where(birth, 0, tsu)
    active = (state.active & ~evict) | birth

    # which track id each detection landed on (matched or newborn)
    m_onehot = (match[..., None] == darange[None, None]) & matched[..., None]
    det_tid = jnp.max(jnp.where(m_onehot | pair, track_id[..., None], -1),
                      axis=1)                                # (B, D)
    det_tid = jnp.where(valid, det_tid, -1)

    return state._replace(pos=pos, vel=vel, cov=cov, score=score,
                          cls=cls, track_id=track_id, hits=hits,
                          tsu=tsu, active=active,
                          next_id=next_id), det_tid


def export_rows(state: TrackerState) -> list:
    """Split the (B, T) table into B portable per-stream rows: plain
    dicts of numpy copies (one entry per ``TrackerState`` field, the
    batch axis stripped).  Rows are serializable and shard-agnostic —
    the currency track identities travel in across segment boundaries,
    stream migration and evacuation.  ``rows_to_state`` rebuilds a
    table from any subset/reordering of them bit-identically.  The
    whole table comes off the device in one ``jax.device_get``."""
    host = jax.device_get(state)
    B = host.active.shape[0]
    return [{f: np.asarray(getattr(host, f))[b].copy()
             for f in TrackerState._fields} for b in range(B)]


def rows_to_state(rows, cfg: TrackerConfig) -> TrackerState:
    """Rebuild a (B, T) table from ``len(rows)`` portable rows; a None
    entry seeds that batch row fresh (== ``init_state``).  All-None
    input returns ``init_state`` itself, so a cold start is
    bit-identical to the pre-portability behavior."""
    fresh = init_state(len(rows), cfg)
    if all(r is None for r in rows):
        return fresh
    cols = {f: np.asarray(getattr(fresh, f)).copy()
            for f in TrackerState._fields}
    for b, r in enumerate(rows):
        if r is None:
            continue
        for f in TrackerState._fields:
            cols[f][b] = r[f]
    return TrackerState(**{f: jnp.asarray(v) for f, v in cols.items()})


@functools.partial(jax.jit, static_argnames=("cfg",))
def output(state: TrackerState, cfg: TrackerConfig):
    """Emit the confirmed, alive tracks: (boxes (B, T, 4) xyxy, scores,
    classes, track ids, valid).  Unconfirmed births (e.g. single-frame
    false positives that never re-matched) stay silent."""
    emit = state.active & (state.hits >= cfg.min_hits)
    return (cxcywh_to_xyxy(state.pos), state.score, state.cls,
            state.track_id, emit)
