"""Batched track↔detection association — IoU cost matrix + greedy
assignment as one fused kernel (Pallas TPU kernel + an XLA twin).

The tracking subsystem (``repro/tracking``) needs, per frame batch, the
classic data-association step: score every (track, detection) pair by
IoU, then greedily commit the best-scoring pairs until nothing clears
the threshold.  Done naively this is a host-side Hungarian/greedy loop
per frame; here it is one launch per frame batch:

 1. **Cost matrix**: the (T, D) IoU matrix of predicted track boxes vs
    detection boxes is computed on the fly in VMEM from (T, 4) track
    rows and (4, D) detection planes: tracks run down the sublanes,
    detections along the 128-wide lanes, with no transpose in the
    kernel.
    Pairs that are masked out (dead track slot, padding detection) or
    class-mismatched are set to cost -1 so they can never win.
 2. **Greedy assignment**: at most ``min(T, D)`` serial steps; each
    step takes the argmax of the remaining cost matrix (row-major tie
    break, exactly like the oracle), commits the pair, and retires its
    row and column with one vectorized mask.  The loop exits as soon as
    the best remaining pair falls below ``iou_thr``, so the serial step
    count is the number of *matches*, not T·D.

Greedy (not Hungarian) is the standard choice for edge trackers — it
is within a fraction of a percent of optimal at IoU-gated costs and is
embarrassingly vectorizable; the oracle in ``ref.greedy_assign_ref``
pins the exact semantics and both paths are bit-compatible with it.

On TPU the ``pallas_call`` compiles to Mosaic (grid = batch, one
program per frame; ``chip_smoke.py`` checks it against the XLA twin on
the chip); on the CPU host it runs in interpret mode.
``greedy_assign_xla`` is the same algorithm as batched XLA ops with a
per-frame active gate and is the production path on non-TPU hosts —
see ``ops.greedy_assign`` for the dispatch.  TPU tile tuning (lane-
width padding of T/D, VMEM residency) is a ROADMAP follow-up.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .nms import _pair_iou


def _plane_cost(tb, db, t_ok, d_ok, t_cls, d_cls):
    """IoU of (T, 4) track rows vs (4, D) detection planes -> (T, D),
    masked to -1 where either side is dead/padding or the classes
    differ.  ``t_ok``/``t_cls`` are (T, 1) columns, ``d_ok``/``d_cls``
    (1, D) lanes."""
    tx0, ty0, tx1, ty1 = (tb[:, k:k + 1] for k in range(4))
    dx0, dy0, dx1, dy1 = (db[k:k + 1] for k in range(4))
    ix0 = jnp.maximum(tx0, dx0)
    iy0 = jnp.maximum(ty0, dy0)
    ix1 = jnp.minimum(tx1, dx1)
    iy1 = jnp.minimum(ty1, dy1)
    inter = jnp.clip(ix1 - ix0, 0.0) * jnp.clip(iy1 - iy0, 0.0)
    t_area = (tx1 - tx0) * (ty1 - ty0)
    d_area = (dx1 - dx0) * (dy1 - dy0)
    union = t_area + d_area - inter
    iou = inter / jnp.maximum(union, 1e-9)
    ok = (t_ok > 0) & (d_ok > 0) & (t_cls == d_cls)
    return jnp.where(ok, iou, -1.0)


def _greedy_body(n_pairs, iou_thr, cost0):
    """Greedy loop (runs inside the Pallas kernel): commit the best
    remaining pair per step (lowest row-major index among ties, like
    ``argmax``), retire its row+column.  Returns the (T, 1) match
    column."""
    Tp, Dp = cost0.shape
    rowm = jax.lax.broadcasted_iota(jnp.int32, (Tp, Dp), 0)
    colm = jax.lax.broadcasted_iota(jnp.int32, (Tp, Dp), 1)
    flat_idx = rowm * Dp + colm
    row = jax.lax.broadcasted_iota(jnp.int32, (Tp, 1), 0)

    def cond(state):
        it, cost, _ = state
        return (it < n_pairs) & (jnp.max(cost) >= iou_thr)

    def body(state):
        it, cost, match = state
        best = jnp.max(cost)
        flat = jnp.min(jnp.where(cost == best, flat_idx, Tp * Dp))
        i = jax.lax.div(flat, Dp)
        j = flat - i * Dp
        match = jnp.where(row == i, j, match)
        cost = jnp.where((rowm == i) | (colm == j), -1.0, cost)
        return it + 1, cost, match

    _, _, match = jax.lax.while_loop(
        cond, body, (jnp.int32(0), cost0,
                     jnp.full((Tp, 1), -1, jnp.int32)))
    return match


def _assoc_kernel(tb_ref, tm_ref, tc_ref, db_ref, dm_ref, dc_ref,
                  match_ref, *, n_pairs, iou_thr):
    """One grid program = one frame of the batch."""
    cost = _plane_cost(tb_ref[0].astype(jnp.float32),
                       db_ref[0].astype(jnp.float32),
                       tm_ref[0], dm_ref[0], tc_ref[0], dc_ref[0])
    match_ref[0] = _greedy_body(n_pairs, iou_thr, cost)


@functools.partial(jax.jit, static_argnames=("iou_thr", "interpret"))
def greedy_assign_pallas(t_boxes, d_boxes, t_mask, d_mask, t_cls, d_cls,
                         *, interpret, iou_thr=0.3):
    """t_boxes (B, T, 4) xyxy, d_boxes (B, D, 4) xyxy (+ per-slot masks
    and int class ids) -> match (B, T) int32: the detection index
    assigned to each track slot, or -1.  One launch per frame batch.
    ``interpret`` has no default: the caller (``ops.greedy_assign``)
    decides from the platform."""
    B, T, _ = t_boxes.shape
    D = d_boxes.shape[1]
    t_pad = -T % 8
    d_pad = -D % 8
    tb = jnp.pad(t_boxes.astype(jnp.float32), ((0, 0), (0, t_pad), (0, 0)))
    db = jnp.pad(d_boxes.astype(jnp.float32), ((0, 0), (0, d_pad), (0, 0)))
    # tracks go down the sublanes as (Tp, 1) columns, detections along
    # the lanes as (1, Dp) rows, so the (Tp, Dp) cost plane needs no
    # transpose inside the kernel
    tm = jnp.pad(t_mask.astype(jnp.int32), ((0, 0), (0, t_pad)))[..., None]
    tc = jnp.pad(t_cls.astype(jnp.int32), ((0, 0), (0, t_pad)))[..., None]
    dm = jnp.pad(d_mask.astype(jnp.int32), ((0, 0), (0, d_pad)))[:, None]
    dc = jnp.pad(d_cls.astype(jnp.int32), ((0, 0), (0, d_pad)))[:, None]
    Tp, Dp = T + t_pad, D + d_pad
    dbt = db.transpose(0, 2, 1)                  # (B, 4, Dp) planes

    kernel = functools.partial(_assoc_kernel, n_pairs=min(T, D),
                               iou_thr=iou_thr)
    match = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Tp, 4), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Tp, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Tp, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 4, Dp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, Dp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, Dp), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Tp, 1), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Tp, 1), jnp.int32),
        interpret=interpret,
    )(tb, tm, tc, dbt, dm, dc)
    return match[:, :T, 0]


@functools.partial(jax.jit, static_argnames=("iou_thr",))
def greedy_assign_xla(t_boxes, d_boxes, t_mask, d_mask, t_cls, d_cls,
                      *, iou_thr=0.3):
    """XLA twin of the Pallas kernel — identical algorithm and outputs,
    batched over frames with a per-frame active gate (a frame whose
    best remaining pair falls below ``iou_thr`` stops committing while
    the other frames keep going)."""
    B, T, _ = t_boxes.shape
    D = d_boxes.shape[1]
    iou = _pair_iou(t_boxes.astype(jnp.float32),
                    d_boxes.astype(jnp.float32))        # (B, T, D)
    ok = (t_mask[:, :, None] & d_mask[:, None, :] &
          (t_cls[:, :, None] == d_cls[:, None, :]))
    cost0 = jnp.where(ok, iou, -1.0)
    match0 = jnp.full((B, T), -1, jnp.int32)
    row = jnp.arange(T, dtype=jnp.int32)[None, :]

    def cond(state):
        it, cost, _ = state
        return (it < min(T, D)) & jnp.any(jnp.max(cost, (1, 2)) >= iou_thr)

    def body(state):
        it, cost, match = state
        flat = jnp.argmax(cost.reshape(B, T * D), -1).astype(jnp.int32)
        best = jnp.take_along_axis(cost.reshape(B, T * D), flat[:, None],
                                   -1)[:, 0]
        act = best >= iou_thr                                # (B,)
        i = flat // D
        j = flat - i * D
        match = jnp.where(act[:, None] & (row == i[:, None]),
                          j[:, None], match)
        kill = (act[:, None, None] &
                ((jnp.arange(T)[None, :, None] == i[:, None, None]) |
                 (jnp.arange(D)[None, None, :] == j[:, None, None])))
        cost = jnp.where(kill, -1.0, cost)
        return it + 1, cost, match

    _, _, match = jax.lax.while_loop(cond, body,
                                     (jnp.int32(0), cost0, match0))
    return match
