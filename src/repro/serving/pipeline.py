"""Composable per-tick stage pipeline: ONE implementation of the
serving data plane shared by every engine.

The per-tick chain — detect -> decode -> NMS -> [ROI second pass] ->
associate -> Kalman — used to be duplicated across
``serving/engine.py`` (``_detect_batch`` / ``_interpolate``),
``serving/runtime.py`` (``_DetectionCore._process_next_batch`` /
``_roi_pass``) and the sharded cores.  This module makes each stage a
function of one typed ``TickState`` pytree, and the engines thin
drivers over it:

* ``TickState``      — the value threaded through the stages: the
  micro-batch ``images``, the decoded/suppressed detections
  (``boxes``/``scores``/``classes``/``valid`` — the detect+NMS stages
  already run as ONE fused jit launch, ``DetectionEngine._infer``),
  the cascade ``model`` that produced them, the lockstep
  ``tracker`` table and the per-detection ``det_tid`` assignment.
* ``roi_second_pass`` — the cascade's hierarchical ROI stage as a pure
  function of ``TickState`` (previously a bespoke ``_roi_pass`` method
  buried in the incremental core).
* ``TickPipeline``   — the tracker tick driver: staged mode launches
  ``trk.step``/``trk.coast`` exactly like the pre-refactor engines
  (bit-identical, and monkeypatch-observable per launch); fused mode
  compiles associate -> Kalman -> output as ONE ``jax.jit`` program
  with the track-table buffers donated, so a serve tick is a single
  launch instead of a kernel chain.
* ``export_track_rows`` / ``build_tracker_state`` — the portable
  track-state contract: the (B, T) table splits into per-stream rows
  keyed by ``stream_id`` and rebuilds with any stream subset/order, so
  track identities survive segment boundaries, ``rebalance_streams``
  migration and watchdog evacuation.
* ``TrackTable``     — a segment's final table left on the device,
  read as those rows only when something reads them; the next
  segment's tracker starts from the table itself when it serves the
  same streams.
* ``sorted_chunk`` / ``chunk_size`` / ``bucket`` / ``dispatch_time`` —
  the chunking/ordering helpers that were copied between the batch
  engine and the incremental core.

Fusion/donation rules
---------------------
The fused tick program traces the SAME jitted ``trk.step`` and
``trk.output`` the staged chain launches, so the op sequence is
identical and the outputs are bit-identical (validated by
``tests/test_pipeline.py`` / ``benchmarks/tick_bench.py``); only the
launch count changes.  The incoming ``TrackerState`` is donated
(``donate_argnums=(0,)``): callers must thread the returned state and
never reuse the argument.  On backends without donation support
(XLA-CPU) the donation is a no-op — JAX keeps the input buffers valid
and would warn per call; that warning is filtered here.  A tick with an
all-invalid detection row is bit-identical to ``trk.coast`` (every
lifecycle write is masked by match/birth bits an invalid row can never
set), which is what lets fused mode run ONE uniform program every tick.

``fused_window`` takes the fusion one step further where the tick
schedule is known before the tracker runs (the engines' interpolation
replay: micro-batch detection results are all collected first): a
``lax.scan`` of the same tick body turns a K-tick window — 2K launches
staged — into ONE launch, amortizing the whole dispatch chain.  Same
trace, same bits; only the launch count changes.
"""
from __future__ import annotations

import functools
import warnings
from collections.abc import Mapping
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.spans import span

# donation is best-effort: XLA-CPU cannot honor donated buffers and
# would warn once per fused launch; the program is correct either way
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


# --------------------------------------------------------------- chunking
def sorted_chunk(frames) -> List:
    """Normalize an ingest argument to a list of ``FrameRequest``
    sorted stably by arrival (a single frame passes through as
    ``[frame]``) — the shared front door of every ingest path."""
    from .engine import FrameRequest   # lazy: avoids import cycles
    if isinstance(frames, FrameRequest):
        return [frames]
    return sorted(frames, key=lambda f: f.t_arrival)


def dispatch_time(frames, i: int, replicas) -> float:
    """Virtual 'now' when the micro-batch headed by ``frames[i]``
    forms: the later of the head frame's arrival and the earliest
    replica free-up — the clock every dispatch-point decision (batch
    sizing, cascade model selection, load sampling) is evaluated at."""
    return max(frames[i].t_arrival,
               min(r.busy_until for r in replicas))


def chunk_size(frames, i: int, *, micro_batch: Optional[int],
               max_micro_batch: int, replicas) -> int:
    """Queue depth at dispatch time: how many frames have arrived by
    the moment the earliest replica frees up (at least one — the head
    frame defines 'now' when the pipeline is idle).  A fixed
    ``micro_batch`` short-circuits the adaptive rule."""
    if micro_batch is not None:
        return micro_batch
    t_now = dispatch_time(frames, i, replicas)
    q = 1
    while (i + q < len(frames) and q < max_micro_batch
           and frames[i + q].t_arrival <= t_now):
        q += 1
    return q


def bucket(k: int) -> int:
    """Pad adaptive batches to power-of-two buckets: O(log mb) jit
    traces instead of one per distinct queue depth.

    >>> [bucket(k) for k in (1, 2, 3, 5, 8)]
    [1, 2, 4, 8, 8]
    """
    b = 1
    while b < k:
        b <<= 1
    return b


# -------------------------------------------------------------- TickState
class TickState(NamedTuple):
    """The value threaded through the per-tick stage chain.

    Detection-side fields hold one micro-batch (leading axis = frames
    in the batch); tracker-side fields hold the lockstep table (leading
    axis = streams).  Every stage is a function ``TickState ->
    TickState`` that fills or rewrites the fields it owns and leaves
    the rest untouched, so stages compose in any gated combination:

    * ``images``  — the stacked (padded) micro-batch input frames.
    * ``boxes`` / ``scores`` / ``classes`` / ``valid`` — the decoded,
      NMS-suppressed detections (fixed ``max_out`` rows, ``valid``
      masking the real ones).
    * ``model``   — the cascade model name that produced them (None on
      catalog-less engines); the post-processor hook composes on it.
    * ``tracker`` — the ``tracking.TrackerState`` (B, T) table.
    * ``det_tid`` — per-detection track-id assignment from the last
      associate/Kalman stage ((B, D) int32, -1 for unused rows).
    """
    boxes: Optional[np.ndarray] = None
    scores: Optional[np.ndarray] = None
    classes: Optional[np.ndarray] = None
    valid: Optional[np.ndarray] = None
    images: Optional[np.ndarray] = None
    model: Optional[str] = None
    tracker: Optional[object] = None
    det_tid: Optional[np.ndarray] = None


# ---------------------------------------------------- portable track rows
def export_track_rows(state, sids) -> Dict[int, dict]:
    """Split the (B, T) track table into per-stream portable rows keyed
    by ``stream_id`` (batch row ``b`` belongs to ``sids[b]``).  Rows
    are plain numpy dicts — serializable, shard-agnostic — and round
    trip bit-identically through ``build_tracker_state``."""
    from ..tracking import export_rows    # lazy: avoids import cycles
    rows = export_rows(state)
    return {s: rows[b] for b, s in enumerate(sids)}


class TrackTable(Mapping):
    """A segment's final (B, T) track table, left on the device, read as
    the mapping ``stream_id -> portable row`` that ``export_track_rows``
    makes (batch row ``b`` belongs to ``sids[b]``).

    Nothing leaves the device until a row is read: the first read
    pulls the whole table with one ``jax.device_get`` and keeps the
    rows.  The next segment's tracker starts from the device table
    itself when it serves the same streams in the same order
    (``TickPipeline.seed``); ``take`` hands the table over, and a fused
    tick then donates it, so rows not read before ``take`` can no
    longer be read."""

    def __init__(self, state, sids):
        self.sids = tuple(sids)
        self._state = state
        self._rows: Optional[Dict[int, dict]] = None

    @property
    def taken(self) -> bool:
        """Whether a later segment's tracker took the device table."""
        return self._state is None

    def take(self):
        """The device table, for a tracker that may donate it."""
        state, self._state = self._state, None
        return state

    def pull(self) -> int:
        """Read the rows off the device now; returns the bytes read (0
        when they were read already)."""
        if self._rows is not None:
            return 0
        if self._state is None:
            raise RuntimeError("the track table was handed to a later "
                               "segment before its rows were read")
        self._rows = export_track_rows(self._state, self.sids)
        return _nbytes(self._state)

    def __getitem__(self, sid) -> dict:
        if self._rows is None:
            with span("serve.track.export") as sp:
                sp.set_metadata(d2h_bytes=self.pull())
        return self._rows[sid]

    def __contains__(self, sid) -> bool:
        return sid in self.sids

    def __iter__(self):
        return iter(self.sids)

    def __len__(self) -> int:
        return len(self.sids)


def build_tracker_state(rows0: Optional[Dict[int, dict]], sids, cfg):
    """Tracker table for streams ``sids`` (batch row ``b`` =
    ``sids[b]``), seeding each stream from its carried row in ``rows0``
    when present and a fresh row otherwise.  With no carried rows the
    result is bit-identical to ``tracking.init_state`` — the
    pre-portability behavior."""
    from ..tracking import init_state, rows_to_state
    if not rows0:
        return init_state(len(sids), cfg)
    return rows_to_state([rows0.get(s) for s in sids], cfg)


def confirmed_ids(row: dict, cfg) -> List[int]:
    """Sorted ids of the confirmed, alive tracks in one portable row —
    the identity set the continuity audit compares across an
    export/import (migration) boundary."""
    m = np.asarray(row["active"]) & (np.asarray(row["hits"])
                                     >= cfg.min_hits)
    return sorted(int(t) for t in np.asarray(row["track_id"])[m])


# ------------------------------------------------------- fused tick program
@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas"),
                   donate_argnums=(0,))
def _fused_tick(state, boxes, scores, classes, valid, cfg, use_pallas):
    """ONE launch per tick: associate -> Kalman update/birth -> output,
    with the incoming track table donated.  Traces the same jitted
    ``trk.step`` / ``trk.output`` the staged chain calls (nested jits
    inline), so the op graph — and the bits — match the two-launch
    chain exactly."""
    from .. import tracking as trk       # lazy: avoids import cycles
    state, det_tid = trk.step(state, boxes, scores, classes, valid,
                              cfg, use_pallas)
    return state, det_tid, trk.output(state, cfg)


def make_fused_tick(cfg, use_pallas: bool = False):
    """The one-jit tick program as a plain callable
    ``(state, boxes, scores, classes, valid) -> (state, det_tid,
    (boxes, scores, classes, track_ids, emit))`` with ``cfg`` /
    ``use_pallas`` closed over (compiled once per (B, D) shape).  The
    input ``state`` is donated — thread the returned one."""
    return lambda state, b, s, c, v: _fused_tick(state, b, s, c, v,
                                                 cfg, use_pallas)


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas"),
                   donate_argnums=(0,))
def _fused_window(state, boxes, scores, classes, valid, cfg, use_pallas):
    """ONE launch per K-tick WINDOW: ``lax.scan`` of the fused tick
    body over stacked detection rows (leading axis = ticks).  The
    interpolation replay knows every tick's detections before the
    tracker runs (micro-batch results are collected first), so the
    whole dispatch chain — 2K launches staged, K fused — collapses to
    a single program.  The scan body is the same ``trk.step`` /
    ``trk.output`` trace as ``_fused_tick``, so the stacked outputs
    and the final table are bit-identical to the per-tick chain;
    detection-free ticks ride along as all-invalid rows."""
    from .. import tracking as trk       # lazy: avoids import cycles

    def body(s, tick):
        b, sc, c, v = tick
        s, det_tid = trk.step(s, b, sc, c, v, cfg, use_pallas)
        return s, (det_tid, trk.output(s, cfg))

    state, (det_tid, out) = jax.lax.scan(
        body, state, (boxes, scores, classes, valid))
    return state, det_tid, out


def fused_window(state, boxes, scores, classes, valid, cfg,
                 use_pallas: bool = False):
    """Run a K-tick window as ONE launch.  ``boxes`` (K, B, D, 4),
    ``scores``/``classes``/``valid`` (K, B, D) are the window's stacked
    detection rows (all-invalid rows for detection-free ticks); returns
    ``(state, det_tid (K, B, D), out)`` with every output stacked along
    the tick axis.  The input ``state`` is donated — thread the
    returned one.  Compiled once per (K, B, D) shape: callers with
    variable-length windows should bucket K."""
    return _fused_window(state, jnp.asarray(boxes), jnp.asarray(scores),
                         jnp.asarray(classes), jnp.asarray(valid),
                         cfg, use_pallas)


class TickPipeline:
    """Driver for the tracker end of the tick chain.

    ``fused=False`` (the default) launches the staged chain —
    ``trk.step`` / ``trk.coast`` per tick, ``trk.output`` on demand —
    through the ``tracking`` module attributes, exactly like the
    pre-refactor engines (the launch spies in ``benchmarks/nvr_bench``
    keep working).  ``fused=True`` runs the one-jit donated-buffer
    program every tick, detections or not (an all-invalid row is
    bit-identical to coasting), and returns the tick's outputs for
    free.  ``launches`` counts tracker launches either way — one per
    tick; ``resident`` is 1 when ``seed`` took the last segment's
    device table."""

    def __init__(self, cfg, *, use_pallas: bool = False,
                 fused: bool = False):
        self.cfg = cfg
        self.use_pallas = use_pallas
        self.fused = fused
        self.launches = 0
        self.resident = 0

    def seed(self, sids, rows0: Optional[Dict[int, dict]] = None,
             resident: Optional[TrackTable] = None):
        """Initial table for streams ``sids``.  ``resident``, the last
        segment's ``TrackTable``, is taken as it stands when it holds
        exactly these streams in this order (``self.resident`` becomes
        1).  Otherwise its rows join the carried ``rows0`` and the
        table is built from rows: carried where a stream has one, fresh
        (== ``init_state``, bit-identical) where none has."""
        with span("serve.track.seed") as sp:
            if resident is not None and resident.sids == tuple(sids):
                self.resident = 1
                sp.set_metadata(resident=1, h2d_bytes=0)
                return resident.take()
            rows = {**(rows0 or {}), **(resident or {})}
            state = build_tracker_state(rows, sids, self.cfg)
            carried = any(s in rows for s in sids)
            sp.set_metadata(resident=0,
                            h2d_bytes=_nbytes(state) if carried else 0)
        return state

    def tick(self, state, boxes, scores, classes, valid):
        """One detection tick.  Returns ``(state, det_tid, out)`` where
        ``out`` is the tick's confirmed-track output tuple in fused
        mode and None in staged mode (ask ``output`` lazily)."""
        from .. import tracking as trk   # module attr: spy-patchable
        self.launches += 1
        host = (boxes, scores, classes, valid)
        with span("serve.track.tick", h2d_bytes=_nbytes(host)):
            args = tuple(jnp.asarray(a) for a in host)
            if self.fused:
                state, det_tid, out = _fused_tick(
                    state, *args, self.cfg, self.use_pallas)
                return state, np.asarray(det_tid), out
            state, det_tid = trk.step(state, *args, self.cfg,
                                      self.use_pallas)
            return state, np.asarray(det_tid), None

    def coast(self, state, det_width: int = 1):
        """One detection-free tick.  Staged mode launches
        ``trk.coast``; fused mode feeds the one program an all-invalid
        (B, det_width) row — bit-identical state, uniform launch —
        and returns the output tuple.  ``det_width`` should match the
        segment's detection width so ONE compiled program covers every
        tick."""
        from .. import tracking as trk   # module attr: spy-patchable
        self.launches += 1
        with span("serve.track.tick", h2d_bytes=0):
            if self.fused:
                B = state.active.shape[0]
                D = det_width
                state, _, out = _fused_tick(
                    state, jnp.zeros((B, D, 4), jnp.float32),
                    jnp.zeros((B, D), jnp.float32),
                    jnp.zeros((B, D), jnp.int32),
                    jnp.zeros((B, D), bool), self.cfg, self.use_pallas)
                return state, out
            return trk.coast(state, self.cfg), None

    def output(self, state):
        """Confirmed-track output of the current table (staged mode's
        lazy path — fused mode already returned it from the tick)."""
        from .. import tracking as trk
        return trk.output(state, self.cfg)

    def export(self, state, sids, pull: bool = False) -> TrackTable:
        """The final table as a ``TrackTable``: it stays on the device
        and becomes portable rows when something reads them; ``pull``
        reads them now."""
        with span("serve.track.export") as sp:
            table = TrackTable(state, sids)
            sp.set_metadata(d2h_bytes=table.pull() if pull else 0)
        return table


def _nbytes(arrays) -> int:
    """Bytes of the arrays of a tuple or pytree (a span's byte count)."""
    return sum(a.nbytes for a in jax.tree.leaves(arrays))


# ------------------------------------------------------------- ROI stage
def roi_second_pass(eng, tick: TickState, kept, pad_b: int, rec):
    """Hierarchical second pass over one micro-batch as a pipeline
    stage: the selected light model's detections (``tick.boxes``...)
    become ROI windows (top ``roi_max`` by score, padded, clamped),
    the heavy model answers only inside them, and its detections —
    clipped to their covering window — REPLACE the first pass's fields
    in the returned ``TickState``.  Also returns the fraction of
    full-frame pixels the second pass read, its measured wall seconds,
    and the pixel tallies ``{"full", "roi", "passes"}`` for the
    caller's accounting (the stage itself mutates nothing).

    The crop always runs through the ``kernels.roi`` pair (Pallas /
    XLA twin per the engine's ``use_pallas``), so the serving hot
    path exercises the kernel tier; with a built-in SSD the crops
    are detected directly, with a cascade oracle the ROI windows
    are forwarded for the oracle's containment filter."""
    import time as _time
    from ..kernels import ops as _kops
    from .cascade import roi_pixels, rois_from_boxes
    images = tick.images
    boxes, scores = tick.boxes, tick.scores
    classes, valid = tick.classes, tick.valid
    heavy = eng.cascade.heaviest
    n = len(kept)
    R = eng.roi_max
    if eng.roi_bounds is not None:
        W, H = eng.roi_bounds
    else:
        W, H = images.shape[2], images.shape[1]
    rois = np.zeros((n, R, 4), np.float32)
    n_rois = np.zeros(n, np.int64)
    px = np.zeros(n)
    for j in range(n):
        rois[j], n_rois[j] = rois_from_boxes(
            boxes[j], scores[j], valid[j], bounds=(W, H),
            roi_max=R, pad=eng.roi_pad)
        px[j] = roi_pixels(rois[j], int(n_rois[j]), (W, H))
    px_full = float(n) * W * H
    px_roi = float(px.sum())
    t0 = _time.perf_counter()
    C = eng.roi_crop or images.shape[1]
    norm = rois / np.array([W, H, W, H], np.float32)
    crops = _kops.crop_resize(images[:n], norm, out_size=C,
                              use_pallas=eng._use_pallas)
    if eng._detect_fn is not None:
        roi_arg = {f.rid: rois[j][:n_rois[j]]
                   for j, f in enumerate(kept)}
        out2, _ = eng._detect_batch(
            images, rids=[f.rid for f in kept] + [-1] * (pad_b - n),
            model=heavy, rois=roi_arg)
        boxes, scores, classes, valid = out2
    else:
        # built-in SSD: detect the crop tiles, map boxes back into
        # the parent frame, keep the top detections per frame
        flat = np.asarray(crops).reshape((n * R,) + crops.shape[2:])
        bb = bucket(n * R)
        if len(flat) < bb:
            flat = np.concatenate(
                [flat, np.zeros((bb - len(flat),) + flat.shape[1:],
                                flat.dtype)], 0)
        out2, _ = eng._detect_batch(flat)
        cb, cs, cc, cv = out2
        M = cb.shape[1]
        cb = np.asarray(_kops.uncrop_boxes(
            cb[:n * R].reshape(n, R, M, 4), norm[:, :, None, :],
            bounds=(W, H), crop_size=C,
            use_pallas=eng._use_pallas))
        cs = cs[:n * R].reshape(n, R, M)
        cc = cc[:n * R].reshape(n, R, M)
        cv = (cv[:n * R].reshape(n, R, M)
              & (np.arange(R)[None, :, None] < n_rois[:, None, None]))
        K = boxes.shape[1]
        # jitted outputs can be read-only views — replace in copies
        boxes, scores = boxes.copy(), scores.copy()
        classes, valid = classes.copy(), valid.copy()
        for j in range(n):
            fb = cb[j].reshape(-1, 4)
            fs = np.where(cv[j].reshape(-1), cs[j].reshape(-1),
                          -np.inf)
            top = np.argsort(-fs, kind="stable")[:K]
            keep = top[np.isfinite(fs[top])]
            boxes[j] = 0.0
            scores[j] = 0.0
            classes[j] = 0
            valid[j] = False
            boxes[j, :len(keep)] = fb[keep]
            scores[j, :len(keep)] = fs[keep]
            classes[j, :len(keep)] = cc[j].reshape(-1)[keep]
            valid[j, :len(keep)] = True
    roi_wall = _time.perf_counter() - t0
    if rec.enabled:
        for j, f in enumerate(kept):
            v = np.asarray(valid[j], bool)
            fb = np.asarray(boxes[j])[v]
            ext = ([float(fb[:, 0].min()), float(fb[:, 1].min()),
                    float(fb[:, 2].max()), float(fb[:, 3].max())]
                   if len(fb) else None)
            rec.record(
                "roi_pass", f.t_arrival, rid=f.rid,
                stream=f.stream_id, model=heavy,
                n_rois=int(n_rois[j]), px_full=float(W) * float(H),
                px_roi=float(px[j]),
                rois=[[float(x) for x in row]
                      for row in rois[j][:n_rois[j]]],
                bounds=[float(W), float(H)], det_extent=ext)
    new_tick = tick._replace(boxes=boxes, scores=scores,
                             classes=classes, valid=valid, model=heavy)
    return new_tick, (px_roi / px_full if px_full else 0.0), roi_wall, \
        {"full": px_full, "roi": px_roi, "passes": n}
