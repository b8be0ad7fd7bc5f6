"""The ``yolov3`` family: YOLOv3 (arXiv:1804.02767; darknet
``cfg/yolov3.cfg``), read from a configuration's ``"yolov3"`` keys, for
the program's ``repro.detector.YOLOv3Config``.

The plain reference, importing nothing of the program:

* Darknet-53: a 3x3 stem conv, then five stages of a 3x3 stride-2 conv
  and residual blocks (1x1 to C/2, 3x3 to C, plus the input); every conv
  is conv without bias, batch norm as darknet's ``normalize_cpu`` writes
  it, (x - mean) / (sqrt(var) + bn_eps) * gamma + beta, and leaky ReLU;
  darknet's padding, k // 2 on every side;
* the FPN neck: at stride 32 five convs alternating 1x1 C/2 and 3x3 C,
  a 3x3 C conv and a linear 1x1 head with bias; the fifth conv through
  a 1x1 C/4 conv, nearest x2 upsample, concatenated with the previous
  stage's output, and the same one level down, twice;
* decode: x = (sigmoid(tx) + cx) / G, w = p_w * exp(tw) / image_size,
  a pair's score sigmoid(obj) * sigmoid(cls_c); anchors per head at
  strides 32, 16, 8, cells row-major, the head's priors per cell;
* suppression: greedy NMS per class (``reference.nms`` on each class's
  scores), the survivors of all classes merged in descending score,
  ties to the lower flat index a * C + c, the first ``max_out`` served.

The reference runs in float32 with ``Precision.HIGHEST``, what the
configuration states; the control in three bfloat16 passes.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

from bench import reference
from bench.families import Candidates, Rows

BLOCK = 8                # frames the reference computes in one call
KEYS = {"image_size", "n_classes", "stem", "stage_widths", "stage_blocks",
        "anchors", "masks", "leaky", "bn_eps"}

# Random weights kept realistic (``make_params``): He-scaled convs; batch
# norm statistics drawn around what a He-scaled conv gives; the last
# batch norm of every residual branch at a small gamma, so that the 23
# residual adds keep activations O(1).  The heads' objectness and class
# channels are then set from one forward pass over calibration frames
# made from the seed (a black frame and frames of filled boxes drawn as
# the traffic's renderer draws them): each channel is centred on what
# it reads at the black frame's centre cell and scaled to unit spread
# over the calibration frames, times ``HEAD_GAIN``, with the bias at a
# prior, as RetinaNet's prior init does.  A black background then
# scores sigmoid(logit(OBJ_PRIOR)) * sigmoid(logit(CLS_PRIOR)), far
# below any threshold, and pairs pass it only where the frame's content
# moves both logits.
BN_MEAN_STD = 0.1
BN_VAR = (0.75, 1.25)
BN_BETA_STD = 0.1
BRANCH_GAMMA = 0.2
HEAD_GAIN = 1.5
OBJ_PRIOR = 0.01
CLS_PRIOR = 0.01
CALIBRATION_BOXES = 12   # filled boxes per calibration frame


def check(cfg: dict) -> None:
    y = cfg.get("yolov3")
    if not isinstance(y, dict) or set(y) != KEYS:
        raise ValueError(f"a yolov3 configuration has exactly the keys "
                         f"{sorted(KEYS)} under 'yolov3'")
    if len(y["stage_widths"]) != 5 or len(y["stage_blocks"]) != 5:
        raise ValueError("Darknet-53 has five stages")
    if y["image_size"] % 32:
        raise ValueError("image_size must be a multiple of 32")
    if sorted(sum(map(list, y["masks"]), [])) != list(range(len(y["anchors"]))):
        raise ValueError("the three heads' masks share out the anchors")
    program_config(cfg)      # and the program takes it


def image_size(cfg: dict) -> int:
    return cfg["yolov3"]["image_size"]


def program_config(cfg: dict):
    from repro.detector import YOLOv3Config
    y = cfg["yolov3"]
    return YOLOv3Config(
        image_size=y["image_size"], n_classes=y["n_classes"], stem=y["stem"],
        stage_widths=tuple(y["stage_widths"]),
        stage_blocks=tuple(y["stage_blocks"]),
        anchors=tuple(tuple(a) for a in y["anchors"]),
        masks=tuple(tuple(m) for m in y["masks"]), leaky=y["leaky"],
        bn_eps=y["bn_eps"])


# ------------------------------------------------------------------ shapes
def _convs(y: dict) -> List[Tuple[str, int, int, int, int, int]]:
    """(scope, input size, k, stride, c_in, c_out) of every conv in the
    order it runs; the parameter lists follow this order, the heads last
    in ``heads``."""
    n, c = y["image_size"], y["stem"]
    out = [("backbone", n, 3, 1, 3, c)]
    for w, blocks in zip(y["stage_widths"], y["stage_blocks"]):
        out.append(("backbone", n, 3, 2, c, w))
        n, c = n // 2, w
        out += [("backbone", n, 1, 1, w, w // 2),
                ("backbone", n, 3, 1, w // 2, w)] * blocks
    head = len(y["masks"][0]) * (5 + y["n_classes"])
    c_in = y["stage_widths"][-1]
    for level, w in enumerate(y["stage_widths"][:1:-1]):
        if level:
            out.append(("neck", n, 1, 1, w, w // 2))
            n, c_in = 2 * n, w // 2 + w
        out += [("neck", n, 1, 1, c_in, w // 2), ("neck", n, 3, 1, w // 2, w),
                ("neck", n, 1, 1, w, w // 2), ("neck", n, 3, 1, w // 2, w),
                ("neck", n, 1, 1, w, w // 2), ("neck", n, 3, 1, w // 2, w),
                ("head", n, 1, 1, w, head)]
    return out


def _branch_ends(y: dict) -> set:
    """Indices in the backbone list of each residual branch's last
    conv."""
    out, i = set(), 0
    for n in y["stage_blocks"]:
        i += 1
        out |= {i + 2 * b + 2 for b in range(n)}
        i += 2 * n
    return out


def n_anchors(y: dict) -> int:
    return sum((y["image_size"] // s) ** 2 * len(m)
               for s, m in zip((32, 16, 8), y["masks"]))


# ----------------------------------------------------------------- weights
def make_params(cfg: dict, seed: int):
    """Detector weights from the seed, made on the device in one jitted
    call, float32, in the program's layout (``{"backbone": [...],
    "neck": [...]}`` of ``{"w", "gamma", "beta", "mean", "var"}`` and
    ``"heads"`` of ``{"w", "b"}``), the heads calibrated on
    ``calibration_frames``: see the constants above."""
    import jax
    import jax.numpy as jnp
    y = cfg["yolov3"]
    convs = tuple(_convs(y))
    ends = frozenset(_branch_ends(y))
    n_cls, per = y["n_classes"], 5 + y["n_classes"]
    obj_b = float(np.log(OBJ_PRIOR / (1 - OBJ_PRIOR)))
    cls_b = float(np.log(CLS_PRIOR / (1 - CLS_PRIOR)))

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def build(convs, ends, key):
        keys = iter(jax.random.split(key, 5 * len(convs)))
        p = {"backbone": [], "neck": [], "heads": []}
        for scope, _, k, _, ci, co in convs:
            w = jax.random.truncated_normal(next(keys), -2.0, 2.0,
                                            (k, k, ci, co), jnp.float32)
            if scope == "head":
                p["heads"].append({"w": w / np.sqrt(ci),
                                   "b": jnp.zeros((co,), jnp.float32)})
                continue
            gamma = (BRANCH_GAMMA if scope == "backbone"
                     and len(p["backbone"]) in ends else 1.0)
            p[scope].append({
                "w": w * np.sqrt(2.0 / (k * k * ci)),
                "gamma": jnp.full((co,), gamma, jnp.float32),
                "beta": BN_BETA_STD * jax.random.normal(next(keys), (co,)),
                "mean": BN_MEAN_STD * jax.random.normal(next(keys), (co,)),
                "var": jax.random.uniform(next(keys), (co,), jnp.float32,
                                          *BN_VAR)})
        return p

    @jax.jit
    def calibrate(leaf, out):
        g = out.shape[1]
        ref = out[0, g // 2, g // 2]
        sd = jnp.maximum(jnp.std((out[1:] - ref).reshape(-1, len(ref)), 0),
                         1e-6)
        chan = jnp.arange(len(ref)) % per
        scored = chan >= 4
        scale = jnp.where(scored, HEAD_GAIN / sd, 1.0)
        prior = jnp.where(chan == 4, obj_b, cls_b)
        return {"w": leaf["w"] * scale,
                "b": jnp.where(scored, prior - ref * scale, 0.0)}

    p = build(convs, ends, reference.params_key(seed))
    with jax.default_matmul_precision("highest"):
        outs = forward_fn(y)(p, calibration_frames(y["image_size"], seed))
    p["heads"] = [calibrate(leaf, out)
                  for leaf, out in zip(p["heads"], outs)]
    return p


def calibration_frames(size: int, seed: int) -> np.ndarray:
    """(BLOCK, size, size, 3) float32: a black frame, then frames of
    ``CALIBRATION_BOXES`` filled boxes each, 4-12% of the width by
    10-25% of the height, each in one colour channel, from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 5]))
    out = np.zeros((BLOCK, size, size, 3), np.float32)
    for f in range(1, BLOCK):
        for _ in range(CALIBRATION_BOXES):
            w, h = rng.uniform(0.04, 0.12) * size, rng.uniform(0.1, 0.25) * size
            x0, y0 = rng.uniform(0, size - w), rng.uniform(0, size - h)
            out[f, int(y0):int(y0 + h) + 1, int(x0):int(x0 + w) + 1,
                rng.integers(3)] = 1.0
    return out


# --------------------------------------------------------------- reference
def forward_fn(y: dict, precision: str = "highest"):
    """Jitted ``(params, images) -> [head outputs]``, (B, G, G, 3 * (5 +
    C)) at strides 32, 16 and 8.  ``precision`` is ``"highest"`` (float32)
    or ``"high"`` (the control: three bfloat16 passes)."""
    import jax
    import jax.numpy as jnp

    def conv1(x, w, stride, prec, out=None):
        p = w.shape[0] // 2
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), ((p, p), (p, p)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=out)

    def conv(x, w, stride=1):
        if precision == "highest":
            return conv1(x, w, stride, jax.lax.Precision.HIGHEST)
        # three bfloat16 passes (hi*hi + hi*lo + lo*hi) with float32
        # sums, as ``ssd.forward_fn`` spells them out
        bf = jnp.bfloat16
        xh, wh = x.astype(bf), w.astype(bf)
        xl = (x - xh.astype(x.dtype)).astype(bf)
        wl = (w - wh.astype(x.dtype)).astype(bf)
        return sum(conv1(a, b, stride, jax.lax.Precision.DEFAULT,
                         jnp.float32)
                   for a, b in ((xh, wh), (xh, wl), (xl, wh)))

    def cbl(leaf, x, stride=1):
        v = conv(x, leaf["w"], stride)
        v = ((v - leaf["mean"]) / (jnp.sqrt(leaf["var"]) + y["bn_eps"])
             * leaf["gamma"] + leaf["beta"])
        return jnp.where(v > 0, v, y["leaky"] * v)

    def up(x):
        return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)

    def fwd(params, images):
        bb, nk = iter(params["backbone"]), iter(params["neck"])
        x = cbl(next(bb), images)
        stages = []
        for blocks in y["stage_blocks"]:
            x = cbl(next(bb), x, 2)
            for _ in range(blocks):
                h = cbl(next(bb), x)
                x = x + cbl(next(bb), h)
            stages.append(x)
        outs, x = [], stages[-1]
        for level, head in enumerate(params["heads"]):
            if level:
                x = jnp.concatenate([up(cbl(next(nk), route)),
                                     stages[-1 - level]], -1)
            for _ in range(5):
                x = cbl(next(nk), x)
            route = x
            outs.append(conv(cbl(next(nk), x), head["w"]) + head["b"])
        return outs

    return jax.jit(fwd)


def decode(y: dict, outs) -> Tuple[np.ndarray, np.ndarray]:
    """One frame's head outputs -> boxes (A, 4) xyxy in image units and
    scores (A, C), in float64."""
    boxes, scores = [], []
    n_cls = y["n_classes"]
    for o, mask in zip(outs, y["masks"]):
        o = np.asarray(o, np.float64)
        g = o.shape[0]
        o = o.reshape(g, g, len(mask), 5 + n_cls)
        sig = 1.0 / (1.0 + np.exp(-o))
        cy, cx = np.meshgrid(np.arange(g), np.arange(g), indexing="ij")
        cx, cy = cx[:, :, None], cy[:, :, None]
        pw = np.array([y["anchors"][m][0] for m in mask], np.float64)
        ph = np.array([y["anchors"][m][1] for m in mask], np.float64)
        bx = (sig[..., 0] + cx) / g
        by = (sig[..., 1] + cy) / g
        bw = pw * np.exp(o[..., 2]) / y["image_size"]
        bh = ph * np.exp(o[..., 3]) / y["image_size"]
        boxes.append(np.stack([bx - bw / 2, by - bh / 2, bx + bw / 2,
                               by + bh / 2], -1).reshape(-1, 4))
        scores.append((sig[..., 4:5] * sig[..., 5:]).reshape(-1, n_cls))
    return np.concatenate(boxes), np.concatenate(scores)


def candidates(cfg: dict, params, images, precision: str):
    """Per frame the ``Candidates`` of every (anchor, class) pair, scores
    (A, C); computed ``BLOCK`` frames at a time."""
    import jax
    y = cfg["yolov3"]
    fwd = forward_fn(y, precision)
    out = []
    for a in range(0, len(images), BLOCK):
        with jax.default_matmul_precision("highest"):
            heads = [np.asarray(h) for h in fwd(params, images[a:a + BLOCK])]
        for f in range(len(heads[0])):
            out.append(Candidates(*decode(y, [h[f] for h in heads]), None))
    return out


def survivors(cand: Candidates, serve: dict) -> Rows:
    """Greedy NMS per class, the survivors merged in descending score,
    ties to the lower flat index a * C + c; the first ``max_out``."""
    n_cls = cand.scores.shape[1]
    found = []
    for c in range(n_cls):
        keep = reference.nms(cand.boxes, cand.scores[:, c],
                             score_thr=serve["score_thr"],
                             iou_thr=serve["iou_thr"],
                             max_out=serve["max_out"])
        found += [(-cand.scores[a, c], int(a) * n_cls + c) for a in keep]
    found = sorted(found)[:serve["max_out"]]
    flat = np.array([f for _, f in found], np.int64)
    a, c = flat // n_cls, flat % n_cls
    return Rows(a, c, cand.boxes[a].reshape(-1, 4), cand.scores[a, c])


# ------------------------------------------------------------ operations
def _taps(n: int, k: int, s: int) -> int:
    """Kernel taps inside an ``n``-wide input, summed over the outputs of
    a 1-D conv of width ``k``, stride ``s`` and padding k // 2 on each
    side."""
    p, m = k // 2, (n + 2 * (k // 2) - k) // s + 1
    return sum(min(i * s - p + k, n) - max(i * s - p, 0) for i in range(m))


def flops_per_frame(cfg: dict) -> int:
    """2 * H_out * W_out * k * k * C_in * C_out over the 75 convs, heads
    included, for one frame."""
    return sum(2 * (n // s) ** 2 * k * k * ci * co
               for _, n, k, s, ci, co in _convs(cfg["yolov3"]))


def flops_by_scope(cfg: dict) -> Dict[str, int]:
    """``backbone`` (Darknet-53) and ``neck`` (every conv after it, the
    heads among them): the multiply-adds of every conv, padding taps
    left out, times 2."""
    out = {"backbone": 0, "neck": 0}
    for scope, n, k, s, ci, co in _convs(cfg["yolov3"]):
        out["backbone" if scope == "backbone" else "neck"] += (
            2 * _taps(n, k, s) ** 2 * ci * co)
    return out


def bytes_by_scope(cfg: dict, frames_per_call: float) -> Dict[str, float]:
    """``backbone``: the float32 frames it reads and its weights (conv
    weights and the four batch-norm vectors), once per call; ``neck``:
    its weights and the heads' weights and biases, once per call;
    activations are not counted.  ``nms``: one read of the score plane
    and the boxes, (A * C + A * 4) float32 values a frame, whatever
    implements the suppression."""
    y = cfg["yolov3"]
    w = {"backbone": 0, "neck": 0}
    for scope, _, k, _, ci, co in _convs(y):
        per_ch = 1 if scope == "head" else 4
        w["backbone" if scope == "backbone" else "neck"] += (
            k * k * ci * co + per_ch * co)
    a = n_anchors(y)
    return {"backbone": 4.0 * (frames_per_call * y["image_size"] ** 2 * 3
                               + w["backbone"]),
            "neck": 4.0 * w["neck"],
            "nms": 4.0 * frames_per_call * a * (y["n_classes"] + 4)}
