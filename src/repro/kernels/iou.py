"""Pairwise-IoU matrix — Pallas TPU kernel for the paper's NMS
post-processing hot-spot.

Layout adaptation for TPU: boxes are carried TRANSPOSED as (4, N) planes
(x0, y0, x1, y1) so the box index lands on the 128-wide lane dimension —
the natural (N, 4) layout would waste 124/128 lanes per vector op.
Tiling: grid (N/BN, M/BM); each program computes a (BN, BM) IoU tile from
one (4, BN) and one (4, BM) strip held in VMEM.

Validated on CPU in interpret mode against ref.iou_matrix_ref; not yet compiled
for a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 128
BLOCK_M = 128


def _iou_kernel(a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)      # (4, BN)
    b = b_ref[...].astype(jnp.float32)      # (4, BM)
    ax0, ay0, ax1, ay1 = a[0], a[1], a[2], a[3]
    bx0, by0, bx1, by1 = b[0], b[1], b[2], b[3]
    ix0 = jnp.maximum(ax0[:, None], bx0[None, :])
    iy0 = jnp.maximum(ay0[:, None], by0[None, :])
    ix1 = jnp.minimum(ax1[:, None], bx1[None, :])
    iy1 = jnp.minimum(ay1[:, None], by1[None, :])
    inter = jnp.clip(ix1 - ix0, 0.0) * jnp.clip(iy1 - iy0, 0.0)
    area_a = (ax1 - ax0) * (ay1 - ay0)
    area_b = (bx1 - bx0) * (by1 - by0)
    union = area_a[:, None] + area_b[None, :] - inter
    o_ref[...] = (inter / jnp.maximum(union, 1e-9)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n",
                                             "block_m"))
def iou_matrix(a, b, *, interpret: bool, block_n: int = BLOCK_N,
               block_m: int = BLOCK_M):
    """a:(N,4) b:(M,4) xyxy -> (N,M) f32 IoU (N, M padded internally)."""
    N, M = a.shape[0], b.shape[0]
    n_pad = -N % block_n
    m_pad = -M % block_m
    at = jnp.pad(a, ((0, n_pad), (0, 0))).T          # (4, Np)
    bt = jnp.pad(b, ((0, m_pad), (0, 0))).T          # (4, Mp)
    Np, Mp = at.shape[1], bt.shape[1]
    out = pl.pallas_call(
        _iou_kernel,
        grid=(Np // block_n, Mp // block_m),
        in_specs=[
            pl.BlockSpec((4, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((4, block_m), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Mp), jnp.float32),
        interpret=interpret,
    )(at, bt)
    return out[:N, :M]
