"""Fused batched NMS — Pallas TPU kernel + an XLA twin of the same
algorithm.

The seed decode path ran greedy NMS as a per-image ``vmap`` of
(full IoU matrix + A-step serial ``fori_loop``): A sequential steps per
frame and an (A, A) IoU matrix materialized in HBM.  This module replaces
it with one launch per micro-batch that is exact (bit-compatible with
``ref.nms_ref``) but does only a handful of serial steps:

 1. **Score threshold** (optional, fused): scores below ``score_thr``
    are zeroed — the same semantics the detector decode applied before
    calling NMS.
 2. **Candidate selection**: a stable descending sort by thresholded
    score; only the top ``num_candidates`` sorted boxes enter
    suppression (default: all of them, which keeps the op exact).
 3. **Tiled suppression**: sorted candidates are processed in tiles of
    ``tile``.  For each tile the IoU of the tile's boxes against all
    later candidates is computed on the fly in VMEM — the full (A, A)
    IoU matrix never exists in HBM.  Inside a tile, greedy NMS is solved
    by a *suppression fixpoint*: ``alive[j] = pre[j] and not any(alive[i]
    and iou[i, j] >= thr for i < j)`` iterated to convergence, which
    takes at most the longest suppression-chain depth (3-5 iterations in
    practice) instead of ``tile`` serial steps.  One vectorized pass then
    suppresses all later candidates.
 4. **Early exit**: once ``max_out`` survivors exist, remaining tiles
    cannot change the output — extra survivors only bump the count past
    the point where ``valid`` saturates and their keep-slots are dropped
    (matching the reference's out-of-bounds-scatter semantics) — so the
    tile loop stops.  With ``stop_at_zero`` the loop also stops at the
    first tile whose best (thresholded) score is 0: zero-score survivors
    can never suppress a positive-score box (they sort after all of
    them) and the detector masks them out of ``valid`` anyway.
 5. **Slot assignment**: survivor i lands in output slot
    ``#survivors-before-i``, counted tile by tile as the survivors
    found before the tile plus the tile's own survivors ranked inside
    it (a (tile, A) masked sum — no (A, A) operand in VMEM, and no
    ``cumsum``, which Mosaic does not lower).

Greedy-equivalence of the fixpoint: ``alive[j]`` depends only on
``alive[i]`` for candidates i that precede j in score order, so by
induction each lane stabilizes one Jacobi sweep after its predecessors —
the iteration converges to the unique greedy solution in at most
chain-depth sweeps, and the convergence check makes the result exact.

Layout: boxes are carried transposed as (4, A) coordinate planes per
frame (same trick as ``iou.py``) so the candidate index lands on the
128-wide lane dimension; the grid has a leading batch dimension, one
program per frame, so a whole micro-batch is suppressed in one launch.
Per-frame blocks keep a singleton middle dimension so their last two
dimensions equal the array's, as Mosaic's tiling rule requires.

On TPU the ``pallas_call`` compiles to Mosaic (``chip_smoke.py`` checks
it against the XLA twin on the chip); on the CPU host it runs in
interpret mode, which validates numerics but interprets the kernel
body per grid step.  ``batched_nms_xla`` is the same algorithm written
as batched XLA ops (tiles unrolled, per-tile early exit via
``lax.cond``) and is the fast path on non-TPU hosts — see
``ops.batched_nms`` for the dispatch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE = 32


def _plane_iou(tx0, ty0, tx1, ty1, tarea, x0, y0, x1, y1, area):
    """IoU of a (T, 1) column tile of boxes against (1, A) lane planes
    -> (T, A)."""
    ix0 = jnp.maximum(tx0, x0)
    iy0 = jnp.maximum(ty0, y0)
    ix1 = jnp.minimum(tx1, x1)
    iy1 = jnp.minimum(ty1, y1)
    inter = jnp.clip(ix1 - ix0, 0.0) * jnp.clip(iy1 - iy0, 0.0)
    union = tarea + area - inter
    # degenerate zero-area boxes (e.g. padding rows): union == inter == 0
    # -> IoU 0, never NaN
    return inter / jnp.maximum(union, 1e-9)


def _nms_kernel(boxes_ref, rows_ref, scores_ref, oidx_ref, keep_ref,
                valid_ref, *, n_real, iou_thr, score_thr, max_out, tile,
                num_candidates, stop_at_zero):
    """One grid program = one frame of the micro-batch.

    Everything stays 2-D and lane-major, which is what Mosaic lowers:
    per-candidate state is a (1, Ap) lane vector holding 0.0 / 1.0, and
    the current tile's boxes are (tile, 1) columns read from the (Ap, 4)
    row copy of the boxes with ``pl.ds``.  The alive state of a tile is
    turned into a column by a masked lane reduction against an iota
    plane, not a dynamic slice."""
    f32 = jnp.float32
    b = boxes_ref[0].astype(f32)                     # (4, Ap) planes
    x0, y0, x1, y1 = b[0:1], b[1:2], b[2:3], b[3:4]  # (1, Ap) each
    area = (x1 - x0) * (y1 - y0)
    s = scores_ref[0].astype(f32)                    # (1, Ap) sorted desc
    if score_thr is not None:
        s = jnp.where(s >= score_thr, s, 0.0)
    Ap = s.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Ap), 1)
    lane_t = jax.lax.broadcasted_iota(jnp.int32, (tile, Ap), 1)
    row_t = jax.lax.broadcasted_iota(jnp.int32, (tile, Ap), 0)

    def column(v, c0):
        """Lanes [c0, c0 + tile) of the (1, Ap) vector ``v`` as a
        (tile, 1) column (exactly one term of each sum is non-zero)."""
        return jnp.sum(jnp.where(lane_t == c0 + row_t, v, 0.0), axis=1,
                       keepdims=True)

    n_cand = min(n_real, num_candidates)
    n_tiles = pl.cdiv(n_cand, tile)
    alive0 = jnp.where(lane < n_cand, 1.0, 0.0)      # padding never alive
    slot0 = jnp.full((1, Ap), -1.0, f32)             # output slot per lane

    def tile_cond(state):
        t, _, _, found = state
        more = (t < n_tiles) & (found < max_out)
        if stop_at_zero:
            tile_best = jnp.sum(jnp.where(lane == t * tile, s, 0.0))
            more &= tile_best > 0.0
        return more

    def tile_body(state):
        t, alive, slot, found = state
        c0 = t * tile
        tb = rows_ref[0, pl.ds(pl.multiple_of(c0, tile), tile), :]
        tb = tb.astype(f32)                                    # (T, 4)
        tx0, ty0, tx1, ty1 = (tb[:, k:k + 1] for k in range(4))
        ta = (tx1 - tx0) * (ty1 - ty0)
        sup = _plane_iou(tx0, ty0, tx1, ty1, ta,
                         x0, y0, x1, y1, area) >= iou_thr      # (T, Ap)
        in_tile = (lane >= c0) & (lane < c0 + tile)
        # strictly-later candidates of the same tile, in score order
        intra = sup & (lane_t > c0 + row_t) & (lane_t < c0 + tile)
        pre = jnp.where(in_tile, alive, 0.0)

        def fix_cond(st):
            a, prev, it = st
            return (it == 0) | (jnp.max(jnp.abs(a - prev)) > 0.0)

        def fix_body(st):
            a, _, it = st
            hit = jnp.max(jnp.where(intra & (column(a, c0) > 0.0), 1.0,
                                    0.0), axis=0, keepdims=True)
            return pre * (1.0 - hit), a, it + 1

        a_c, _, _ = jax.lax.while_loop(fix_cond, fix_body, (pre, pre, 0))
        a_col = column(a_c, c0) > 0.0                          # (T, 1)
        # one vectorized pass suppresses every later candidate
        dead = jnp.max(jnp.where(sup & (lane_t >= c0 + tile) & a_col, 1.0,
                                 0.0), axis=0, keepdims=True)
        alive = jnp.where(in_tile, a_c, alive * (1.0 - dead))
        # survivor j of this tile lands in slot found + #survivors of the
        # tile before it
        rank = jnp.sum(jnp.where((lane_t > c0 + row_t) & a_col, 1.0, 0.0),
                       axis=0, keepdims=True)
        slot = jnp.where(in_tile & (a_c > 0.0), found + rank, slot)
        return t + 1, alive, slot, found + jnp.sum(a_c)

    _, _, slot, _ = jax.lax.while_loop(
        tile_cond, tile_body, (0, alive0, slot0, jnp.float32(0.0)))

    # slots past max_out are dropped (the reference's out-of-bounds
    # scatter); an unfilled slot reads index 0 and is not valid
    k = jax.lax.broadcasted_iota(jnp.int32, (max_out, Ap), 0).astype(f32)
    onehot = slot == k                                     # (max_out, Ap)
    keep_ref[0] = jnp.sum(jnp.where(onehot, oidx_ref[0], 0), axis=1,
                          keepdims=True)
    valid_ref[0] = jnp.max(jnp.where(onehot, 1, 0), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=(
    "iou_thr", "score_thr", "max_out", "tile", "num_candidates",
    "stop_at_zero", "interpret"))
def batched_nms_pallas(boxes, scores, *, interpret, iou_thr=0.5,
                       score_thr=None, max_out=64, tile=DEFAULT_TILE,
                       num_candidates=None, stop_at_zero=False):
    """boxes (B, A, 4) xyxy, scores (B, A) -> keep (B, max_out) int32,
    valid (B, max_out) bool.  Exact greedy NMS per frame, one launch for
    the whole micro-batch.  ``interpret`` has no default: the caller
    (``ops.batched_nms``) decides from the platform."""
    B, A = scores.shape
    if num_candidates is None:
        num_candidates = A
    s_key = scores.astype(jnp.float32)
    if score_thr is not None:
        s_key = jnp.where(s_key >= score_thr, s_key, 0.0)
    order = jnp.argsort(-s_key, axis=-1, stable=True)
    bs = jnp.take_along_axis(boxes.astype(jnp.float32),
                             order[..., None], axis=1)
    ss = jnp.take_along_axis(scores.astype(jnp.float32), order, axis=1)

    # pad to a common multiple of the tile and the 8-sublane minimum so
    # every tile lies inside the block
    pad = -A % math.lcm(tile, 8)
    if pad:
        bs = jnp.pad(bs, ((0, 0), (0, pad), (0, 0)))
        ss = jnp.pad(ss, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        order = jnp.pad(order, ((0, 0), (0, pad)))
    Ap = A + pad
    # (4, Ap) planes put the candidates on the lanes; the (Ap, 4) rows
    # hand the kernel each tile's boxes as sublane columns
    bt = bs.transpose(0, 2, 1)

    kernel = functools.partial(
        _nms_kernel, n_real=A, iou_thr=iou_thr, score_thr=score_thr,
        max_out=max_out, tile=tile, num_candidates=num_candidates,
        stop_at_zero=stop_at_zero)
    # per-frame blocks keep a singleton middle dim, so the last two
    # block dims equal the array's (Mosaic's (8, 128) tiling rule)
    keep, valid = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 4, Ap), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Ap, 4), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, Ap), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, Ap), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, max_out, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, max_out, 1), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, max_out, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, max_out, 1), jnp.int32),
        ],
        interpret=interpret,
    )(bt, bs, ss[:, None, :], order.astype(jnp.int32)[:, None, :])
    return keep[..., 0], valid[..., 0] > 0


def _pair_iou(a, b):
    """a (B, T, 4) vs b (B, M, 4) -> (B, T, M)."""
    tl = jnp.maximum(a[:, :, None, :2], b[:, None, :, :2])
    br = jnp.minimum(a[:, :, None, 2:], b[:, None, :, 2:])
    inter = (jnp.clip(br[..., 0] - tl[..., 0], 0.0) *
             jnp.clip(br[..., 1] - tl[..., 1], 0.0))
    aa = (a[:, :, 2] - a[:, :, 0]) * (a[:, :, 3] - a[:, :, 1])
    ab = (b[:, :, 2] - b[:, :, 0]) * (b[:, :, 3] - b[:, :, 1])
    return inter / jnp.maximum(aa[:, :, None] + ab[:, None, :] - inter, 1e-9)


@functools.partial(jax.jit, static_argnames=(
    "iou_thr", "score_thr", "max_out", "tile", "num_candidates",
    "stop_at_zero"))
def batched_nms_xla(boxes, scores, *, iou_thr=0.5, score_thr=None,
                    max_out=64, tile=DEFAULT_TILE, num_candidates=None,
                    stop_at_zero=False):
    """XLA twin of the Pallas kernel — identical algorithm and outputs,
    tiles unrolled with a batch-global ``lax.cond`` early exit.  This is
    the production path on hosts where Pallas runs interpreted."""
    B, A = scores.shape
    K = A if num_candidates is None else min(num_candidates, A)
    s_key = scores.astype(jnp.float32)
    if score_thr is not None:
        s_key = jnp.where(s_key >= score_thr, s_key, 0.0)
    order = jnp.argsort(-s_key, axis=-1, stable=True)[:, :K]
    bs = jnp.take_along_axis(boxes.astype(jnp.float32),
                             order[..., None], axis=1)
    ss = jnp.take_along_axis(s_key, order, axis=1)

    tri = jnp.arange(tile)[:, None] < jnp.arange(tile)[None, :]
    alive_parts = []
    alive_rest = jnp.ones((B, K), bool)
    # per-frame gate, exactly like the kernel's tile_cond: a frame stops
    # contributing survivors once its next tile opens with a zero score
    # (a batch-global gate would let one long frame drag zero-score
    # survivors into the other frames' counts)
    active = jnp.ones((B,), bool)
    if stop_at_zero and K > 0:
        active = ss[:, 0] > 0.0
    found = jnp.zeros((B,), jnp.int32)
    for c0 in range(0, K, tile):
        T = min(tile, K - c0)
        pre = alive_rest[:, c0:c0 + T] & active[:, None]
        rest = alive_rest[:, c0 + T:]
        done = ~jnp.any(active) | jnp.all(found >= max_out)

        def do_tile(args, c0=c0, T=T):
            pre, rest, found = args
            iou = _pair_iou(bs[:, c0:c0 + T], bs[:, c0:])
            sup = iou >= iou_thr
            intra = sup[:, :, :T] & tri[:T, :T][None]

            def cond(st):
                return (st[2] == 0) | jnp.any(st[0] != st[1])

            def body(st):
                a, _, it = st
                return pre & ~jnp.any(intra & a[:, :, None], 1), a, it + 1

            a_c, _, _ = jax.lax.while_loop(cond, body, (pre, pre, 0))
            dead = jnp.any(sup[:, :, T:] & a_c[:, :, None], 1)
            return a_c, rest & ~dead, found + jnp.sum(a_c, -1,
                                                      dtype=jnp.int32)

        a_c, rest, found = jax.lax.cond(
            done, lambda args: (jnp.zeros_like(args[0]),) + args[1:],
            do_tile, (pre, rest, found))
        alive_parts.append(a_c)
        if c0 + T < K:
            alive_rest = jnp.concatenate(
                [jnp.zeros((B, c0 + T), bool), rest], axis=-1)
            if stop_at_zero:
                active = active & (ss[:, c0 + T] > 0.0)

    alive = jnp.concatenate(alive_parts, axis=-1)
    count = jnp.minimum(found, max_out)
    # survivor i -> slot (#survivors before i); dead/overflow slots land in
    # a per-frame spill column that is sliced away (the reference's
    # dropped-out-of-bounds-scatter semantics)
    slot = jnp.where(alive, jnp.cumsum(alive, axis=-1) - 1, max_out)
    slot = jnp.minimum(slot, max_out)
    flat = (jnp.arange(B)[:, None] * (max_out + 1) + slot).reshape(-1)
    keep = jnp.zeros((B * (max_out + 1),), jnp.int32).at[flat].set(
        order.reshape(-1).astype(jnp.int32)
    ).reshape(B, max_out + 1)[:, :max_out]
    valid = jnp.arange(max_out)[None, :] < count[:, None]
    return keep, valid
