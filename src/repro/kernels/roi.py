"""ROI crop / uncrop kernels for the hierarchical detection second pass.

The transprecise cascade (``serving/cascade.py``) runs a cheap first
pass over the full frame, then batches the detected regions through the
heavy model (SNIPPETS.md §3, ``inference-region=roi-list``).  The two
halves of that data movement live here:

* ``crop_resize_pallas`` — nearest-neighbor crop+resize of R normalized
  xyxy windows per frame into fixed (C, C) tiles, so ROI crops slot
  straight into the existing micro-batch path.  The gather is expressed
  as two one-hot matmuls per channel plane (rows then columns): the
  source-index comparison against a ``broadcasted_iota`` builds a
  (C, H) / (W, C) selection matrix, and the contraction runs on the MXU
  at ``Precision.HIGHEST`` (exact for one-hot operands) — no serial
  per-pixel gather loop in the kernel body.  Grid (B, R): one program
  per window.
* ``uncrop_boxes_pallas`` — maps second-pass detections from crop pixel
  coordinates back into the parent frame; boxes are carried transposed
  as (4, N) coordinate planes like ``iou.py`` so the box index lands on
  the lane dimension.

Both have an XLA twin (``*_xla``) of the same index math and a pure
oracle in ``ref.py``; the source-pixel formula

    src = clip(floor((r0 + (i + 0.5) / C * (r1 - r0)) * S), 0, S - 1)

is evaluated in float32 with the same operation order in every tier.
The crop is bit-compatible across all three tiers (the floor/clip
quantizes to integer indices, absorbing any excess precision); for the
uncrop, Pallas and the XLA twin are bit-identical to each other, and
both match the numpy oracle to within one float32 ULP of the parent
frame scale — XLA contracts the ``r0 + t * (r1 - r0)`` pattern into an
FMA inside jit, which eager numpy cannot express.  Validated on CPU
in interpret mode against ``ref.crop_resize_ref`` /
``ref.uncrop_boxes_ref``, and on the chip by ``chip_smoke.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

UNCROP_BLOCK = 128


def _crop_kernel(img_ref, roi_ref, o_ref, *, H, W, ch, C):
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST   # one-hot selection must stay exact
    roi = roi_ref[0, 0].astype(f32)              # (1, 4)
    x0, y0, x1, y1 = (roi[:, k:k + 1] for k in range(4))   # (1, 1) each
    # rows: out row i reads src row floor((y0 + (i+.5)/C*(y1-y0)) * H)
    ii = jax.lax.broadcasted_iota(jnp.int32, (C, H), 0).astype(f32)
    hh = jax.lax.broadcasted_iota(jnp.int32, (C, H), 1).astype(f32)
    fy = (ii + 0.5) / C
    ys = jnp.clip(jnp.floor((y0 + fy * (y1 - y0)) * H), 0.0, H - 1.0)
    row_oh = (hh == ys).astype(f32)              # (C, H) one-hot
    # columns: out column j reads src column xs[j], as a (W, C) one-hot
    ww = jax.lax.broadcasted_iota(jnp.int32, (W, C), 0).astype(f32)
    jj = jax.lax.broadcasted_iota(jnp.int32, (W, C), 1).astype(f32)
    fx = (jj + 0.5) / C
    xs = jnp.clip(jnp.floor((x0 + fx * (x1 - x0)) * W), 0.0, W - 1.0)
    col_oh = (ww == xs).astype(f32)              # (W, C) one-hot
    for k in range(ch):                          # one (H, W) plane each
        rows = jnp.dot(row_oh, img_ref[0, k].astype(f32), precision=hi,
                       preferred_element_type=f32)          # (C, W)
        o_ref[0, 0, k] = jnp.dot(rows, col_oh, precision=hi,
                                 preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("out_size", "interpret"))
def crop_resize_pallas(images, rois, *, out_size: int, interpret: bool):
    """images (B, H, W, ch), rois (B, R, 4) normalized xyxy in [0, 1]
    -> crops (B, R, C, C, ch) float32, C = out_size.  Degenerate
    (zero-area) windows produce a constant tile of source pixel (0, 0);
    callers mask invalid windows downstream.  ``interpret`` has no
    default: the caller (``ops.crop_resize``) decides from the
    platform."""
    B, H, W, ch = images.shape
    R = rois.shape[1]
    C = out_size
    planes = images.transpose(0, 3, 1, 2)        # (B, ch, H, W)
    out = pl.pallas_call(
        functools.partial(_crop_kernel, H=H, W=W, ch=ch, C=C),
        grid=(B, R),
        in_specs=[
            pl.BlockSpec((1, ch, H, W), lambda b, r: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, 4), lambda b, r: (b, r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, ch, C, C),
                               lambda b, r: (b, r, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, R, ch, C, C), jnp.float32),
        interpret=interpret,
    )(planes, rois.astype(jnp.float32)[:, :, None, :])
    return out.transpose(0, 1, 3, 4, 2)


@functools.partial(jax.jit, static_argnames=("out_size",))
def crop_resize_xla(images, rois, *, out_size: int):
    """XLA twin of ``crop_resize_pallas``: same float32 index math as a
    vmapped double gather — the production path on non-TPU hosts."""
    B, H, W, ch = images.shape
    C = out_size
    f = (jnp.arange(C, dtype=jnp.float32) + 0.5) / C

    def one(img, roi):
        roi = roi.astype(jnp.float32)
        x0, y0, x1, y1 = roi[0], roi[1], roi[2], roi[3]
        ys = jnp.clip(jnp.floor((y0 + f * (y1 - y0)) * H),
                      0.0, H - 1.0).astype(jnp.int32)
        xs = jnp.clip(jnp.floor((x0 + f * (x1 - x0)) * W),
                      0.0, W - 1.0).astype(jnp.int32)
        return img.astype(jnp.float32)[ys][:, xs]

    return jax.vmap(lambda img, rs:
                    jax.vmap(lambda r: one(img, r))(rs))(images, rois)


def _uncrop_kernel(b_ref, r_ref, o_ref, *, W, H, C):
    b = b_ref[...].astype(jnp.float32)           # (4, BN) crop-space boxes
    r = r_ref[...].astype(jnp.float32)           # (4, BN) normalized rois
    x0, y0, x1, y1 = r[0], r[1], r[2], r[3]
    o_ref[...] = jnp.stack([
        (x0 + b[0] / C * (x1 - x0)) * W,
        (y0 + b[1] / C * (y1 - y0)) * H,
        (x0 + b[2] / C * (x1 - x0)) * W,
        (y0 + b[3] / C * (y1 - y0)) * H,
    ])


@functools.partial(jax.jit, static_argnames=("bounds", "crop_size",
                                             "interpret", "block"))
def uncrop_boxes_pallas(boxes, rois, *, bounds, crop_size: int,
                        interpret: bool, block: int = UNCROP_BLOCK):
    """boxes (..., 4) xyxy in crop pixel coordinates [0, crop_size],
    rois (..., 4) normalized parent windows (broadcast against the
    boxes' leading shape) -> boxes in parent-frame pixel coordinates,
    bounds = (W, H)."""
    W, H = bounds
    boxes = jnp.asarray(boxes, jnp.float32)
    rois = jnp.broadcast_to(jnp.asarray(rois, jnp.float32), boxes.shape)
    lead = boxes.shape[:-1]
    N = 1
    for d in lead:
        N *= d
    pad = -N % block
    bt = jnp.pad(boxes.reshape(N, 4), ((0, pad), (0, 0))).T   # (4, Np)
    rt = jnp.pad(rois.reshape(N, 4), ((0, pad), (0, 0))).T
    Np = N + pad
    out = pl.pallas_call(
        functools.partial(_uncrop_kernel, W=float(W), H=float(H),
                          C=crop_size),
        grid=(Np // block,),
        in_specs=[
            pl.BlockSpec((4, block), lambda i: (0, i)),
            pl.BlockSpec((4, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((4, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((4, Np), jnp.float32),
        interpret=interpret,
    )(bt, rt)
    return out.T[:N].reshape(lead + (4,))


@functools.partial(jax.jit, static_argnames=("bounds", "crop_size"))
def uncrop_boxes_xla(boxes, rois, *, bounds, crop_size: int):
    """XLA twin of ``uncrop_boxes_pallas`` (same float32 math,
    elementwise)."""
    W, H = float(bounds[0]), float(bounds[1])
    b = jnp.asarray(boxes, jnp.float32)
    r = jnp.broadcast_to(jnp.asarray(rois, jnp.float32), b.shape)
    scale = jnp.stack([r[..., 2] - r[..., 0], r[..., 3] - r[..., 1],
                       r[..., 2] - r[..., 0], r[..., 3] - r[..., 1]], -1)
    base = jnp.stack([r[..., 0], r[..., 1], r[..., 0], r[..., 1]], -1)
    px = jnp.asarray([W, H, W, H], jnp.float32)
    return (base + b / crop_size * scale) * px
