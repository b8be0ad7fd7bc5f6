"""The one traffic generator: open-loop camera fleets from a mix file.

A mix (``bench/traffic/<name>.json``) gives the scene (``video``, the
spec of a synthetic benchmark clip), ``fps`` per camera, ``cameras``,
``emit_period_s`` and ``pool_frames``.  Frame ``k`` of camera ``s`` is
due at ``(k + s / cameras) / fps`` seconds after the window opens, the
phase stagger of ``repro.serving.nvr.make_nvr_streams``.  Frames are
rendered once into a pool; camera ``s`` starts at an offset into the
pool drawn from the seed, so every seed sends the same sizes and the
same arrivals and only the pixels differ.

``SyntheticVideo`` is a copy of ``repro.core.stream.SyntheticVideo``
(ground-truth motion and the box renderer), kept here so that the
yardstick does not move with the program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass(frozen=True)
class VideoSpec:
    name: str
    fps: float
    n_frames: int
    width: int
    height: int
    moving_camera: bool
    n_objects: int = 8
    seed: int = 0
    obj_speed: float = 0.002
    cam_speed: float = 0.0025


class SyntheticVideo:
    """Objects moving at constant velocity, bouncing off the frame
    edges, under an optional camera pan; three classes."""

    N_CLASSES = 3

    def __init__(self, spec: VideoSpec):
        self.spec = spec
        rng = np.random.default_rng(spec.seed)
        W, H, K = spec.width, spec.height, spec.n_objects
        self.sizes = np.stack([rng.uniform(0.04, 0.12, K) * W,
                               rng.uniform(0.10, 0.25, K) * H], -1)
        self.pos0 = np.stack([rng.uniform(0.1, 0.9, K) * W,
                              rng.uniform(0.2, 0.8, K) * H], -1)
        speed = spec.obj_speed * W
        ang = rng.uniform(0, 2 * np.pi, K)
        self.vel = np.stack([np.cos(ang), np.sin(ang)], -1) * \
            rng.uniform(0.5, 1.5, (K, 1)) * speed
        self.cam_vel = np.array([spec.cam_speed * W, 0.0])
        self.classes = rng.integers(0, self.N_CLASSES, K)

    def boxes_at(self, i: int) -> np.ndarray:
        W, H = self.spec.width, self.spec.height
        centers = self.pos0 + i * (self.vel + self.cam_vel)
        span = np.array([W, H], float)
        centers = np.abs(np.mod(centers, 2 * span) - span)
        half = self.sizes / 2
        return np.concatenate([centers - half, centers + half], -1)

    def pixels(self, i: int, size: int) -> np.ndarray:
        """Frame ``i`` as a (size, size, 3) float32 image: each object a
        filled box in the channel of its class."""
        img = np.zeros((size, size, 3), np.float32)
        sx, sy = size / self.spec.width, size / self.spec.height
        for b, c in zip(self.boxes_at(i), self.classes):
            x0, y0 = int(b[0] * sx), int(b[1] * sy)
            x1, y1 = max(int(b[2] * sx), x0 + 1), max(int(b[3] * sy), y0 + 1)
            img[max(y0, 0):y1, max(x0, 0):x1, c % 3] = 1.0
        return img


@dataclass(frozen=True)
class Mix:
    """One traffic mix, as read from its file."""
    name: str
    video: VideoSpec
    fps: float
    cameras: int
    emit_period_s: float
    pool_frames: int

    @classmethod
    def from_dict(cls, name: str, d: dict) -> "Mix":
        return cls(name, VideoSpec(**d["video"]), float(d["fps"]),
                   int(d["cameras"]), float(d["emit_period_s"]),
                   int(d["pool_frames"]))


def render_pool(mix: Mix, size: int) -> np.ndarray:
    """(pool_frames, size, size, 3) float32: the clip's first frames."""
    video = SyntheticVideo(mix.video)
    return np.stack([video.pixels(i, size) for i in range(mix.pool_frames)])


def camera_offsets(rng: np.random.Generator, cameras: int,
                   pool_frames: int) -> np.ndarray:
    """Where in the pool each camera starts (drawn from the seed)."""
    return rng.integers(0, pool_frames, cameras)


def due_times(fps: float, cameras: int, seconds: float):
    """Frames due in ``[0, seconds)``, in due order: arrays ``(due,
    stream, k)`` with frame ``k`` of camera ``s`` due at ``(k + s /
    cameras) / fps``.  Ties cannot occur, so the order is k-major,
    s-minor and the position in it is the frame's request id."""
    n_k = int(np.ceil(seconds * fps)) + 1
    k = np.repeat(np.arange(n_k), cameras)
    s = np.tile(np.arange(cameras), n_k)
    due = (k + s / cameras) / fps
    keep = due < seconds
    return due[keep], s[keep], k[keep]


def pool_index(offsets: np.ndarray, stream: np.ndarray, k: np.ndarray,
               pool_frames: int) -> np.ndarray:
    """Pool frame shown by frame ``k`` of camera ``stream``."""
    return (offsets[stream] + k) % pool_frames


def boundary_of(due: np.ndarray, period: float) -> np.ndarray:
    """Index of the emit boundary that flushes each frame: boundary
    ``j`` runs at ``j * period`` and takes the frames due in
    ``[(j - 1) * period, j * period)``."""
    return np.floor(due / period + 1e-9).astype(np.int64) + 1


def split_by_boundary(due: np.ndarray, period: float) -> List[slice]:
    """Slices of the due-ordered frames, one per boundary ``1..n``."""
    b = boundary_of(due, period)
    edges = np.searchsorted(b, np.arange(1, b.max() + 2)) if len(b) else [0]
    return [slice(int(edges[j]), int(edges[j + 1]))
            for j in range(len(edges) - 1)]
