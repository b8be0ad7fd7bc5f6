"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        scale: float | None = None):
    """q:(B,H,T,D) k/v:(B,H,S,D) -> (B,H,T,D)  (full softmax attention)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    scale = D ** -0.5 if scale is None else scale
    s = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.arange(S)[None, :] <= jnp.arange(T)[:, None] + (S - T)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p.astype(q.dtype), v)


def decode_attention_ref(q, k, v, *, scale: float | None = None):
    """GQA flash-decode oracle.
    q:(B,H,D) one token; k/v:(B,S,KV,D) full cache -> (B,H,D)."""
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D)
    scale = D ** -0.5 if scale is None else scale
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k).astype(jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype), v)
    return out.reshape(B, H, D)


def iou_matrix_ref(a, b):
    """a:(N,4) b:(M,4) xyxy -> (N,M) IoU in f32."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    tl = jnp.maximum(a[:, None, :2], b[None, :, :2])
    br = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    inter = jnp.prod(jnp.clip(br - tl, 0.0), -1)
    area_a = jnp.prod(a[:, 2:] - a[:, :2], -1)
    area_b = jnp.prod(b[:, 2:] - b[:, :2], -1)
    union = area_a[:, None] + area_b[None, :] - inter
    return inter / jnp.maximum(union, 1e-9)


def nms_ref(boxes, scores, iou_thr: float = 0.5, max_out: int = 64):
    """Greedy NMS oracle. Returns (keep_idx (max_out,), valid mask)."""
    n = boxes.shape[0]
    iou = iou_matrix_ref(boxes, boxes)
    order = jnp.argsort(-scores)

    def body(i, state):
        keep, kcount, alive = state
        idx = order[i]
        ok = alive[idx]
        keep = keep.at[kcount].set(jnp.where(ok, idx, keep[kcount]))
        kcount = kcount + ok.astype(jnp.int32)
        # suppress everything overlapping idx
        sup = (iou[idx] >= iou_thr) & ok
        alive = alive & ~sup
        return keep, kcount, alive

    keep0 = jnp.zeros((max_out,), jnp.int32)
    alive0 = jnp.ones((n,), bool)
    keep, kcount, _ = jax.lax.fori_loop(0, n, body, (keep0, 0, alive0))
    valid = jnp.arange(max_out) < kcount
    return keep, valid


def batched_nms_ref(boxes, scores, iou_thr: float = 0.5,
                    max_out: int = 64, score_thr: float | None = None):
    """Batched greedy-NMS oracle: ``nms_ref`` vmapped over the leading
    frame axis, with the detector's score-threshold semantics (scores
    below ``score_thr`` are zeroed but still iterated, exactly like the
    seed decode path).  boxes (B, A, 4), scores (B, A)."""
    if score_thr is not None:
        scores = jnp.where(scores >= score_thr, scores, 0.0)
    return jax.vmap(
        lambda b, s: nms_ref(b, s, iou_thr, max_out))(boxes, scores)


def class_nms_ref(boxes, scores, *, score_thr: float, iou_thr: float,
                  max_out: int):
    """Class-aware greedy-NMS oracle (numpy loops) for
    ``class_nms.class_nms``: per frame and per class, greedy NMS over the
    pairs scored at least ``score_thr`` in descending score (lower anchor
    first on ties), suppressing IoU >= ``iou_thr``; the survivors of all
    classes merged in descending score, ties to the lower flat index
    ``a * C + c``, the first ``max_out`` served.

    boxes (B, A, 4), scores (B, C, A) -> (anchor, cls, score, valid),
    each (B, max_out), rows past the survivors zero and not valid."""
    import numpy as np
    boxes = np.asarray(boxes, np.float32)
    scores = np.asarray(scores, np.float32)
    B, C, A = scores.shape
    out = [np.zeros((B, max_out), np.int32), np.zeros((B, max_out), np.int32),
           np.zeros((B, max_out), np.float32), np.zeros((B, max_out), bool)]
    for b in range(B):
        bx = boxes[b]
        area = np.prod(bx[:, 2:] - bx[:, :2], -1)
        found = []
        for c in range(C):
            s = np.where(scores[b, c] >= score_thr, scores[b, c],
                         np.float32(0))
            alive = s > 0
            kept = 0
            for a in np.argsort(-s, kind="stable"):
                if not alive[a] or kept == max_out:
                    continue
                kept += 1
                found.append((-s[a], a * C + c, a, c))
                tl = np.maximum(bx[a, :2], bx[:, :2])
                br = np.minimum(bx[a, 2:], bx[:, 2:])
                inter = np.prod(np.clip(br - tl, 0, None), -1)
                iou = inter / np.maximum(area[a] + area - inter,
                                         np.float32(1e-9))
                alive &= ~(iou >= iou_thr)
        for k, (neg, _, a, c) in enumerate(sorted(found)[:max_out]):
            out[0][b, k], out[1][b, k] = a, c
            out[2][b, k], out[3][b, k] = -neg, True
    return tuple(jnp.asarray(o) for o in out)


def greedy_assign_ref(t_boxes, d_boxes, t_mask, d_mask, t_cls=None,
                      d_cls=None, iou_thr: float = 0.3):
    """Greedy IoU-association oracle for the tracking subsystem.

    t_boxes (B, T, 4) xyxy predicted track boxes, d_boxes (B, D, 4)
    detections, boolean slot masks, optional int class ids (class
    mismatch forbids a pair) -> match (B, T) int32: detection index per
    track slot or -1.  Per step the globally best remaining pair is
    committed (row-major tie break) and its row+column retired, until
    the best pair falls below ``iou_thr``.
    """
    import numpy as np
    t_boxes = jnp.asarray(t_boxes)
    d_boxes = jnp.asarray(d_boxes)
    B, T = t_boxes.shape[0], t_boxes.shape[1]
    D = d_boxes.shape[1]
    match = np.full((B, T), -1, np.int32)
    for b in range(B):
        ok = (np.asarray(t_mask[b], bool)[:, None] &
              np.asarray(d_mask[b], bool)[None, :])
        if t_cls is not None:
            ok &= (np.asarray(t_cls[b])[:, None] ==
                   np.asarray(d_cls[b])[None, :])
        cost = np.where(ok, np.asarray(iou_matrix_ref(t_boxes[b],
                                                      d_boxes[b])), -1.0)
        for _ in range(min(T, D)):
            flat = int(np.argmax(cost))
            i, j = divmod(flat, D)
            if cost[i, j] < iou_thr:
                break
            match[b, i] = j
            cost[i, :] = -1.0
            cost[:, j] = -1.0
    return jnp.asarray(match)


def crop_resize_ref(images, rois, *, out_size: int):
    """Nearest-neighbor ROI crop oracle (numpy loops, float32 index
    math — the bit-compatibility reference for ``roi.py``).

    images (B, H, W, ch), rois (B, R, 4) normalized xyxy ->
    crops (B, R, C, C, ch) float32."""
    import numpy as np
    images = np.asarray(images)
    rois = np.asarray(rois, np.float32)
    B, H, W, ch = images.shape
    R = rois.shape[1]
    C = out_size
    f = (np.arange(C, dtype=np.float32) + np.float32(0.5)) / np.float32(C)
    out = np.zeros((B, R, C, C, ch), np.float32)
    for b in range(B):
        for r in range(R):
            x0, y0, x1, y1 = rois[b, r]
            ys = np.clip(np.floor((y0 + f * (y1 - y0)) * np.float32(H)),
                         0, H - 1).astype(np.int64)
            xs = np.clip(np.floor((x0 + f * (x1 - x0)) * np.float32(W)),
                         0, W - 1).astype(np.int64)
            out[b, r] = images[b].astype(np.float32)[ys][:, xs]
    return jnp.asarray(out)


def uncrop_boxes_ref(boxes, rois, *, bounds, crop_size: int):
    """Crop-space -> parent-frame box mapping oracle for ``roi.py``.

    boxes (..., 4) xyxy in [0, crop_size] pixels, rois (..., 4)
    normalized parent windows (broadcast), bounds = (W, H)."""
    import numpy as np
    W, H = np.float32(bounds[0]), np.float32(bounds[1])
    b = np.asarray(boxes, np.float32)
    r = np.broadcast_to(np.asarray(rois, np.float32), b.shape)
    C = np.float32(crop_size)
    out = np.stack([
        (r[..., 0] + b[..., 0] / C * (r[..., 2] - r[..., 0])) * W,
        (r[..., 1] + b[..., 1] / C * (r[..., 3] - r[..., 1])) * H,
        (r[..., 0] + b[..., 2] / C * (r[..., 2] - r[..., 0])) * W,
        (r[..., 1] + b[..., 3] / C * (r[..., 3] - r[..., 1])) * H,
    ], axis=-1)
    return jnp.asarray(out)


def rwkv_scan_ref(r, k, v, w, u, s0):
    """Stepwise oracle for the RWKV-6 recurrence kernel.
    r/k/v/w: (B,H,T,hs); u: (H,hs); s0: (B,H,hs,hs)."""
    def step(S, inp):
        r_t, k_t, v_t, w_t = inp                       # (B,H,hs)
        kv = k_t[..., :, None] * v_t[..., None, :]
        out = jnp.einsum("bhk,bhkv->bhv", r_t, S + u[None, :, :, None] * kv)
        S = w_t[..., :, None] * S + kv
        return S, out
    xs = tuple(a.transpose(2, 0, 1, 3) for a in (r, k, v, w))
    S, outs = jax.lax.scan(step, s0.astype(jnp.float32), xs)
    return outs.transpose(1, 2, 0, 3).astype(r.dtype), S
