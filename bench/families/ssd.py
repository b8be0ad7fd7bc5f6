"""The ``ssd`` family: the program's single-shot detector
(``repro.detector.SSDConfig``), read from a configuration's ``"ssd"``
keys.

Stride-2 3x3 conv blocks with ReLU, two 3x3 heads on the last two
feature maps, two anchor kinds (aspect 1 and 2) per cell, box decode,
sigmoid objectness and class argmax, then class-agnostic greedy NMS
(``reference.nms``).  The reference runs in float32 with
``Precision.HIGHEST``, what the configurations state; the control in
three bfloat16 passes.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

from bench import reference
from bench.families import Candidates, Rows

N_ANCHOR_KINDS = 2
ASPECTS = (1.0, 2.0)
BLOCK = 16               # frames the reference computes in one call
KEYS = {"image_size", "n_classes", "channels", "feature_strides",
        "anchor_scales"}


def check(cfg: dict) -> None:
    ssd = cfg.get("ssd")
    if not isinstance(ssd, dict) or set(ssd) != KEYS:
        raise ValueError(f"an ssd configuration has exactly the keys "
                         f"{sorted(KEYS)} under 'ssd'")
    n = len(ssd["channels"])
    if list(ssd["feature_strides"]) != [2 ** (n - 1), 2 ** n]:
        raise ValueError("the two heads sit on the last two stride-2 maps: "
                         f"feature_strides must be {[2 ** (n - 1), 2 ** n]}")
    if len(ssd["anchor_scales"]) != 2:
        raise ValueError("one anchor scale per head")
    if ssd["image_size"] % 2 ** n:
        raise ValueError(f"image_size must be a multiple of {2 ** n}")


def image_size(cfg: dict) -> int:
    return cfg["ssd"]["image_size"]


def program_config(cfg: dict):
    from repro.detector import SSDConfig
    ssd = cfg["ssd"]
    return SSDConfig(image_size=ssd["image_size"], n_classes=ssd["n_classes"],
                     channels=tuple(ssd["channels"]),
                     anchor_scales=tuple(ssd["anchor_scales"]),
                     feature_strides=tuple(ssd["feature_strides"]))


# ----------------------------------------------------------------- weights
def _shapes(ssd: dict) -> List[Tuple[str, int, int, int]]:
    """(name, k, c_in, c_out) of every conv, in parameter order."""
    out, c_in = [], 3
    for i, c in enumerate(ssd["channels"]):
        out.append((f"backbone.{i}", 3, c_in, c))
        c_in = c
    head = N_ANCHOR_KINDS * (4 + 1 + ssd["n_classes"])
    out.append(("head8", 3, ssd["channels"][-2], head))
    out.append(("head16", 3, ssd["channels"][-1], head))
    return out


def make_params(cfg: dict, seed: int):
    """Detector weights from the seed, made on the device in one jitted
    call: He-scaled truncated normals, zero biases, float32, in the
    program's layout (``{"backbone": [{"w", "b"}, ...], "head8",
    "head16"}``)."""
    import jax
    import jax.numpy as jnp
    shapes = tuple(_shapes(cfg["ssd"]))

    @functools.partial(jax.jit, static_argnums=0)
    def build(shapes, key):
        keys = jax.random.split(key, len(shapes))
        p = {"backbone": []}
        for (name, k, ci, co), kk in zip(shapes, keys):
            w = jax.random.truncated_normal(kk, -2.0, 2.0, (k, k, ci, co),
                                            jnp.float32) / np.sqrt(k * k * ci)
            leaf = {"w": w, "b": jnp.zeros((co,), jnp.float32)}
            if name.startswith("backbone"):
                p["backbone"].append(leaf)
            else:
                p[name] = leaf
        return p

    return build(shapes, reference.params_key(seed))


# --------------------------------------------------------------- reference
def anchors(ssd: dict) -> np.ndarray:
    """(A, 4) xyxy anchors in [0, 1] image units: per feature map, per
    aspect ratio, its cells in row-major order."""
    out = []
    for stride, scale in zip(ssd["feature_strides"], ssd["anchor_scales"]):
        g = ssd["image_size"] // stride
        cs = (np.arange(g) + 0.5) / g
        cx, cy = np.meshgrid(cs, cs)
        for ar in ASPECTS:
            w, h = scale * np.sqrt(ar), scale / np.sqrt(ar)
            out.append(np.stack([cx - w / 2, cy - h / 2,
                                 cx + w / 2, cy + h / 2], -1).reshape(-1, 4))
    return np.concatenate(out, 0).astype(np.float32)


def forward_fn(ssd: dict, precision: str = "highest"):
    """Jitted ``(params, images) -> (deltas, obj, cls_logits)``; the head
    output of cell ``c`` and anchor kind ``k`` lands at row ``2c + k``.
    ``precision`` is ``"highest"`` (float32) or ``"high"`` (the control:
    three bfloat16 passes)."""
    import jax
    import jax.numpy as jnp
    n_cls = ssd["n_classes"]

    def conv1(x, w, stride, prec, out=None):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=out)

    def conv(leaf, x, stride):
        if precision == "highest":
            y = conv1(x, leaf["w"], stride, jax.lax.Precision.HIGHEST)
        else:
            # three bfloat16 passes (hi*hi + hi*lo + lo*hi) with float32
            # sums: what Precision.HIGH does on a TPU, spelled out so
            # that every backend computes it
            bf = jnp.bfloat16
            xh = x.astype(bf)
            wh = leaf["w"].astype(bf)
            xl = (x - xh.astype(x.dtype)).astype(bf)
            wl = (leaf["w"] - wh.astype(x.dtype)).astype(bf)
            y = sum(conv1(a, b, stride, jax.lax.Precision.DEFAULT,
                          jnp.float32)
                    for a, b in ((xh, wh), (xh, wl), (xl, wh)))
        return y + leaf["b"]

    def fwd(params, images):
        x, feats = images, []
        for leaf in params["backbone"]:
            x = jnp.maximum(conv(leaf, x, 2), 0.0)
            feats.append(x)
        outs = []
        for f, name in ((feats[-2], "head8"), (feats[-1], "head16")):
            y = conv(params[name], f, 1)
            b, g = y.shape[0], y.shape[1]
            outs.append(y.reshape(b, g * g * N_ANCHOR_KINDS, 5 + n_cls))
        y = jnp.concatenate(outs, 1)
        return y[..., :4], y[..., 4], y[..., 5:]

    return jax.jit(fwd)


def decode(deltas, obj, anc):
    """Boxes (A, 4) and scores (A,) of one frame's anchors."""
    deltas = np.asarray(deltas, np.float64)
    anc = np.asarray(anc, np.float64)
    wh0 = anc[:, 2:] - anc[:, :2]
    c0 = (anc[:, :2] + anc[:, 2:]) / 2
    c = c0 + deltas[:, :2] * wh0
    wh = wh0 * np.exp(np.clip(deltas[:, 2:], -4, 4))
    boxes = np.concatenate([c - wh / 2, c + wh / 2], -1)
    return boxes, 1.0 / (1.0 + np.exp(-np.asarray(obj, np.float64)))


def candidates(cfg: dict, params, images, precision: str):
    """Per frame the ``Candidates`` of every anchor: one detection each,
    its class the argmax of the class logits; computed ``BLOCK`` frames
    at a time."""
    import jax
    ssd = cfg["ssd"]
    fwd = forward_fn(ssd, precision)
    anc = anchors(ssd)
    out = []
    for a in range(0, len(images), BLOCK):
        x = images[a:a + BLOCK]
        with jax.default_matmul_precision("highest"):
            dl, ob, lg = (np.asarray(v) for v in fwd(params, x))
        for f in range(len(x)):
            out.append(Candidates(*decode(dl[f], ob[f], anc), lg[f]))
    return out


def survivors(cand: Candidates, serve: dict) -> Rows:
    """Class-agnostic greedy NMS over all of a frame's anchors, each
    served with its argmax class."""
    keep = reference.nms(cand.boxes, cand.scores,
                         score_thr=serve["score_thr"],
                         iou_thr=serve["iou_thr"], max_out=serve["max_out"])
    return Rows(keep, np.argmax(cand.class_scores[keep], -1),
                cand.boxes[keep], cand.scores[keep])


# ------------------------------------------------------------ operations
def _convs(ssd: dict) -> List[Tuple[int, int, int, int, int]]:
    """(input size, k, stride, c_in, c_out) of every conv, in parameter
    order."""
    shapes, size, out, maps = _shapes(ssd), ssd["image_size"], [], []
    for _, k, ci, co in shapes[:-2]:
        out.append((size, k, 2, ci, co))
        size = -(-size // 2)
        maps.append(size)
    for size, (_, k, ci, co) in zip(maps[-2:], shapes[-2:]):
        out.append((size, k, 1, ci, co))
    return out


def flops_per_frame(cfg: dict) -> int:
    """2 * H_out * W_out * k * k * C_in * C_out summed over the stride-2
    3x3 backbone convs and the two 3x3 heads, for one frame."""
    return sum(2 * (-(-n // s)) ** 2 * k * k * ci * co
               for n, k, s, ci, co in _convs(cfg["ssd"]))


def _taps(n: int, k: int, s: int) -> int:
    """Kernel taps that fall inside an ``n``-wide input, summed over the
    outputs of a SAME-padded 1-D conv of width ``k`` and stride ``s``."""
    m = -(-n // s)
    lo = max((m - 1) * s + k - n, 0) // 2
    return sum(max(min(i * s - lo + k, n) - max(i * s - lo, 0), 0)
               for i in range(m))


def flops_by_scope(cfg: dict) -> Dict[str, int]:
    """``backbone``: the multiply-adds of every conv, padding taps left
    out (what XLA's cost analysis counts for a conv), times 2."""
    return {"backbone": sum(2 * _taps(n, k, s) ** 2 * ci * co
                            for n, k, s, ci, co in _convs(cfg["ssd"]))}


def bytes_by_scope(cfg: dict, frames_per_call: float) -> Dict[str, float]:
    """``backbone``: the float32 frames it reads and its weights and
    biases, read once per call; activations are not counted."""
    ssd = cfg["ssd"]
    weights = sum(k * k * ci * co + co for _, k, ci, co in _shapes(ssd))
    return {"backbone": 4.0 * (frames_per_call * ssd["image_size"] ** 2 * 3
                               + weights)}
