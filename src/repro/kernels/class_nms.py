"""Exact class-aware greedy NMS over a micro-batch, as batched XLA ops.

Semantics: per class, greedy NMS over that class's (anchor, class)
pairs scored at least ``score_thr``, a pair suppressed by a kept pair of
its class whose box it overlaps with IoU >= ``iou_thr`` (the
repository's convention; darknet's ``do_nms_sort`` suppresses on
IoU > thresh).  The survivors of every class are merged in descending
score, ties going to the lower flat index ``a * C + c``, and the first
``max_out`` are served.  ``ref.class_nms_ref`` is the oracle.

The algorithm takes the best remaining pair ``max_out`` times.  Each
class keeps its best remaining score and the anchor that holds it; a
step picks the class whose best is highest (lowest flat index on ties),
serves that pair, zeroes its class's row where the IoU with the served
box reaches ``iou_thr`` (and the served pair itself), and refreshes that
class's best.  A served pair is a survivor of its class, since every
pair of its class scored above it was taken or suppressed before it,
and pairs come out in the merge order, so the first ``max_out`` steps
serve exactly the first ``max_out`` merged survivors.  The score plane
is read once for the first bests; a step then touches one class's row
of ``A`` scores.  The program is a loop of ``max_out`` steps whose body
is the same whatever ``A`` and ``C`` are: its size does not grow with
the number of pairs.

Scores come class-major, (B, C, A), so that a class's row is contiguous
and anchors lie along the lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _iou_row(box, boxes, areas):
    """IoU of one box (4,) against boxes (A, 4) -> (A,)."""
    tl = jnp.maximum(box[:2], boxes[:, :2])
    br = jnp.minimum(box[2:], boxes[:, 2:])
    inter = jnp.prod(jnp.clip(br - tl, 0.0), -1)
    area = jnp.prod(box[2:] - box[:2])
    return inter / jnp.maximum(area + areas - inter, 1e-9)


def _one_frame(boxes, scores, *, score_thr, iou_thr, max_out):
    C, A = scores.shape
    s = jnp.where(scores >= score_thr, scores, 0.0)
    best, arg = s.max(-1), jnp.argmax(s, -1).astype(jnp.int32)
    areas = jnp.prod(boxes[:, 2:] - boxes[:, :2], -1)
    flat_of = jnp.arange(C, dtype=jnp.int32)
    out = (jnp.zeros((max_out,), jnp.int32), jnp.zeros((max_out,), jnp.int32),
           jnp.zeros((max_out,), scores.dtype))

    def more(st):
        i, _, best, _, _ = st
        return (i < max_out) & (best.max() > 0.0)

    def step(st):
        i, s, best, arg, (o_a, o_c, o_s) = st
        top = best.max()
        c = jnp.argmin(jnp.where(best == top, arg * C + flat_of, A * C))
        a = arg[c]
        row = jnp.where(_iou_row(boxes[a], boxes, areas) >= iou_thr, 0.0,
                        s[c]).at[a].set(0.0)
        s = s.at[c].set(row)
        best = best.at[c].set(row.max())
        arg = arg.at[c].set(jnp.argmax(row).astype(jnp.int32))
        out = (o_a.at[i].set(a), o_c.at[i].set(c.astype(jnp.int32)),
               o_s.at[i].set(top))
        return i + 1, s, best, arg, out

    _, _, _, _, (o_a, o_c, o_s) = jax.lax.while_loop(
        more, step, (0, s, best, arg, out))
    return o_a, o_c, o_s, o_s > 0.0


def class_nms(boxes, scores, *, score_thr, iou_thr, max_out):
    """boxes (B, A, 4) xyxy, scores (B, C, A) class-major ->
    (anchor (B, max_out) int32, cls (B, max_out) int32, score
    (B, max_out), valid (B, max_out) bool), rows in merge order; rows
    past a frame's survivors have score 0 and are not valid."""
    return jax.vmap(functools.partial(
        _one_frame, score_thr=score_thr, iou_thr=iou_thr,
        max_out=max_out))(boxes, scores)
