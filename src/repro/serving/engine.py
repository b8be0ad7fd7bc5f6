"""NVR detection serving engines: the paper's multi-model parallelism
as parallel replica executors behind one scheduler.

The paper's "n detection models on n accelerator sticks" becomes n model
replicas (replica groups of the mesh; on this CPU host, n logical replicas
sharing the device).  Frames stream in, the paper's schedulers (FCFS /
RR / weighted / proportional) pick a replica, the real jitted detect+NMS
fast path runs in micro-batches, measured wall times drive the same
virtual timeline as the edge simulator, and the sequence synchronizer
returns responses in arrival order.  ``DetectionEngine`` is the primary
(video-frame) payload path; ``ServingEngine`` carries the same replica
machinery for token (LLM prefill+decode) payloads.  Both engines'
``serve()`` are thin one-shot drivers over the incremental core in
``repro.serving.runtime`` — ``ServingRuntime`` accepts the same trace
frame-by-frame for always-on serving, bit-identical to the batch call.

Multi-camera (NVR) contract
---------------------------
``FrameRequest.stream_id`` tags which camera a frame belongs to
(default 0 — the single-stream case).  ``rid`` stays globally unique
across streams; a frame's position WITHIN its camera's stream (its
per-stream arrival index) is derived by the engine and returned as
``DetectionResponse.seq``.  All cameras share the same replicas,
micro-batches and — under ``track_and_interpolate`` — ONE batched
tracker with batch dim B = number of streams: frames from different
cameras are interleaved into shared micro-batches (one fused detect +
one fused NMS launch covers frames from several cameras), and the
track table advances all streams in lockstep, one launch per tick.
Ordering, drop accounting, coverage and FPS are all reported both
globally (unchanged keys) and per stream (``per_stream`` /
``streams``); per-stream emit clocks guarantee a camera's frames are
released in that camera's arrival order, independent of the other
cameras.  With a single stream the engine's outputs are bit-identical
to the scalar-stream implementation.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.scheduler import make_scheduler
from ..models import init_model
from ..models.config import ModelConfig
from ..obs.metrics import detection_latency_keys
from ..obs.spans import span
from ..obs.trace import NULL_RECORDER
from ..runtime.steps import make_decode_step, make_prefill_step
from .pipeline import (TickPipeline, TrackTable, bucket, chunk_size,
                       confirmed_ids)


@dataclass
class Request:
    """Token-payload request for ``ServingEngine``: a prompt of
    ``tokens`` (``(prompt_len,)`` int32) arriving at virtual time
    ``t_arrival``, asking for ``max_new_tokens`` of greedy decode.
    ``rid`` is the caller-assigned unique request id that responses
    are matched and ordered by."""
    rid: int
    tokens: np.ndarray            # (prompt_len,)
    max_new_tokens: int = 8
    t_arrival: float = 0.0


@dataclass
class FrameRequest:
    """Video-frame request for ``DetectionEngine``: one camera frame
    (``image``: ``(S, S, 3)`` float32) arriving at virtual time
    ``t_arrival``.

    ``stream_id`` names the camera the frame belongs to (default 0,
    the single-stream case); ``rid`` must stay globally unique ACROSS
    cameras — the engine derives the frame's position within its own
    camera's stream and returns it as ``DetectionResponse.seq``."""
    rid: int
    image: np.ndarray             # (S, S, 3) float32
    t_arrival: float = 0.0
    stream_id: int = 0            # which camera this frame belongs to


@dataclass
class DetectionResponse:
    """Per-frame detection result from ``DetectionEngine.serve``.

    ``boxes``/``scores``/``classes`` are fixed-width ``max_out`` rows
    with ``valid`` masking the real detections.  ``replica`` is the
    executor that processed the frame, or ``-1`` for a frame the
    scheduler dropped and the tracker re-emitted (``interpolated=True``
    — boxes are the tracker's coasted prediction, ``track_ids`` carries
    the persistent track identities).  ``t_start``/``t_done`` are
    virtual-clock processing bounds and ``service_s`` the per-frame
    service share of the micro-batch.  ``stream_id``/``seq`` locate the
    frame in its camera's stream: ``seq`` is the per-stream arrival
    index the per-camera reorder/quality accounting keys on."""
    rid: int
    boxes: np.ndarray             # (max_out, 4)
    scores: np.ndarray            # (max_out,)
    classes: np.ndarray           # (max_out,)
    valid: np.ndarray             # (max_out,) bool
    replica: int                  # -1 for tracker-interpolated frames
    t_start: float
    t_done: float
    service_s: float
    interpolated: bool = False    # True: boxes coasted by the tracker
    track_ids: Optional[np.ndarray] = None
    stream_id: int = 0            # camera this frame belongs to
    seq: int = -1                 # per-stream arrival index of the frame


@dataclass
class Response:
    """Token-payload response from ``ServingEngine.serve``: the greedy
    decode ``tokens`` for request ``rid``, the ``replica`` that served
    it, its virtual-clock ``t_start``/``t_done`` window and the
    measured wall ``service_s``."""
    rid: int
    tokens: np.ndarray            # generated ids
    replica: int
    t_start: float
    t_done: float
    service_s: float


class ReplicaExecutor:
    """Scheduler-compatible executor backed by a real jitted model call."""

    def __init__(self, idx: int, speed: float = 1.0):
        self.idx = idx
        self.speed = speed            # heterogeneity: service multiplier
        self.busy_until = 0.0
        self.n_processed = 0
        self.ewma_service = None
        self._last_wall = 0.1
        self.faults = None            # optional faults.ReplicaFaultView
        # loadable-model catalog (serving.models.ModelCatalog) — attached
        # by the owning engine.  It travels WITH the executor: replica
        # lending moves the object into the borrower's pool, so a guest
        # keeps its home catalog, and a dead replica's catalog leaves the
        # capacity pool with it.
        self.catalog = None

    @property
    def mu_effective(self) -> float:
        # explicit None check: a measured EWMA of exactly 0.0 (zero-cost
        # oracle detectors in tests) is data, not absence of data — the
        # old `ewma or fallback` silently fell back to the wall estimate
        t = (self._last_wall * self.speed if self.ewma_service is None
             else self.ewma_service)
        return 1.0 / max(t, 1e-6)

    def service_time(self, frame=None, t=None) -> float:
        """Virtual service seconds for one frame.  ``t`` is the virtual
        dispatch time the scheduler evaluates the work at; it only
        matters when a fault view is attached — an injected slowdown
        multiplies the base estimate and a dead replica reports
        infinity, which the scheduler's timeout rule turns into a
        suspect + retry (``core.scheduler``)."""
        s = self._last_wall * self.speed
        if self.faults is not None and t is not None:
            if not self.faults.alive(t):
                return float("inf")
            s *= self.faults.factor(t)
        return s

    def record(self, t_service: float):
        self.n_processed += 1
        a = 0.3
        self.ewma_service = (t_service if self.ewma_service is None
                             else (1 - a) * self.ewma_service + a * t_service)

    def reset(self):
        """Clear per-serve virtual-clock state.  ``_last_wall`` (the warm
        service estimate from warmup / the last measured batch) survives,
        so a reset replica starts a new serve exactly like a
        freshly-warmed one."""
        self.busy_until = 0.0
        self.n_processed = 0
        self.ewma_service = None


def _per_replica_counts(replicas, responses) -> Dict[int, int]:
    """Per-CALL placement counts (``replica == -1`` tracker-interpolated
    frames excluded): identical to the executors' cumulative
    ``n_processed`` on a fresh or reset engine, but stays per-call when
    virtual-clock state is carried across calls (the sharded epoch
    loop), so report merges can sum counts without double counting."""
    counts = {r.idx: 0 for r in replicas}
    for resp in responses:
        if resp.replica >= 0:
            counts[resp.replica] += 1
    return counts


class ServingEngine:
    """Token-payload serving: the paper's parallel-replica scheduling
    applied to an LLM decode loop.

    ``n_replicas`` logical replicas share one set of jitted
    prefill/decode programs; each request's REAL measured wall time,
    scaled by the replica's ``replica_speeds`` multiplier
    (heterogeneous pools), drives the same virtual-clock schedulers as
    the edge simulator (``scheduler`` in fcfs/rr/wrr/proportional).
    ``drop_when_busy=True`` reproduces the paper's load shedding: a
    request arriving with every replica busy is dropped instead of
    queued.  ``serve`` returns responses in arrival order plus
    throughput/latency/per-replica accounting."""

    def __init__(self, cfg: ModelConfig, params=None, n_replicas: int = 4,
                 scheduler: str = "fcfs", cache_len: int = 128,
                 replica_speeds: Optional[Sequence[float]] = None,
                 drop_when_busy: bool = False, seed: int = 0,
                 recorder=None):
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}: "
                             "an empty replica pool can never serve")
        self.cfg = cfg
        self.params = params if params is not None else init_model(
            cfg, jax.random.PRNGKey(seed))
        self.cache_len = cache_len
        self.prefill = jax.jit(make_prefill_step(cfg, cache_len=cache_len))
        self.decode = jax.jit(make_decode_step(cfg))
        speeds = list(replica_speeds or [1.0] * n_replicas)
        self.replicas = [ReplicaExecutor(i, s) for i, s in enumerate(speeds)]
        self.scheduler = make_scheduler(scheduler, self.replicas,
                                        host_overhead=1e-4)
        # observability (repro.obs): None -> the shared no-op recorder,
        # so the untraced engine stays bit-identical to the pre-tracing
        # one; the scheduler shares the same recorder for dispatch events
        self.recorder = recorder if recorder is not None else NULL_RECORDER
        self.scheduler.recorder = self.recorder
        self.drop_when_busy = drop_when_busy
        self._warm = False

    # ------------------------------------------------------------- compute
    def _generate(self, req: Request) -> tuple[np.ndarray, float]:
        t0 = time.perf_counter()
        toks = jnp.asarray(req.tokens, jnp.int32)[None]
        logits, cache = self.prefill(self.params, {"tokens": toks})
        out = []
        pos = toks.shape[1]
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        for _ in range(req.max_new_tokens):
            out.append(int(nxt[0, 0]))
            logits, cache = self.decode(self.params, {
                "tokens": nxt, "cache": cache,
                "decode_pos": jnp.asarray(pos, jnp.int32)})
            nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            pos += 1
        jax.block_until_ready(logits)
        return np.array(out, np.int32), time.perf_counter() - t0

    def warmup(self, prompt_len: int = 16):
        req = Request(-1, np.zeros(prompt_len, np.int32), 2)
        _, wall = self._generate(req)
        for r in self.replicas:
            r._last_wall = wall
        self._warm = True

    def reset(self):
        """Clear per-serve virtual-clock state (replica ``busy_until`` /
        processed counts / EWMAs and the scheduler's round bookkeeping)
        so repeated ``serve()`` calls are independent: the second call
        sees idle replicas at t=0, exactly like the first.  Delegates to
        ``ServingRuntime.reset_engines`` — the ONE reset semantic every
        engine shares."""
        from .runtime import ServingRuntime
        ServingRuntime.reset_engines(self)

    # ------------------------------------------------------------- serving
    def serve(self, requests: Sequence[Request]) -> Dict:
        """Run a batch of requests through the parallel-replica pipeline.
        Returns responses (arrival order), dropped ids, and FPS metrics.

        Each call is independent: per-serve virtual-clock state is reset
        on entry, and ``per_replica`` counts THIS call's placements (not
        a lifetime cumulative), so two identical back-to-back calls
        return identical reports.

        Latency keys (same names as ``DetectionEngine.serve``, present
        in the empty-trace early return too): ``p50_latency`` (exact
        median of ``t_done - t_start``), ``p95_latency`` /
        ``p99_latency`` (quantiles of the log-bucketed
        ``latency_hist`` — see ``repro.obs.metrics``)."""
        if not requests:                  # empty report, like DetectionEngine
            empty = detection_latency_keys([])
            return {"responses": [], "dropped": [], "throughput_rps": 0.0,
                    "p50_latency": 0.0, "p95_latency": 0.0,
                    "p99_latency": 0.0, "latency_hist": empty["latency_hist"],
                    "per_replica": {r.idx: 0 for r in self.replicas}}
        if not self._warm:
            self.warmup(max(len(r.tokens) for r in requests))
        self.reset()
        rec = self.recorder
        responses: List[Response] = []
        dropped: List[int] = []
        for req in sorted(requests, key=lambda r: r.t_arrival):
            if rec.enabled:
                rec.record("arrive", req.t_arrival, rid=req.rid,
                           stream=0, seq=req.rid)
            gen, wall = self._generate(req)       # real compute, measured
            for r in self.replicas:               # this request would cost
                r._last_wall = wall               # wall x speed on replica r
            if self.drop_when_busy:
                a = self.scheduler.assign(req.rid, req.t_arrival)
                if a is None:
                    dropped.append(req.rid)
                    if rec.enabled:
                        rec.record("drop", req.t_arrival, rid=req.rid,
                                   stream=0, seq=req.rid)
                    continue
            else:
                # raises NoHealthyExecutorError when nothing can ever
                # take the request (fail fast, never spin); returns None
                # only when a fault kills the bounded retry chain
                a = self.scheduler.blocking_assign(req.rid, req.t_arrival)
                if a is None:
                    dropped.append(req.rid)
                    if rec.enabled:
                        rec.record("drop", req.t_arrival, rid=req.rid,
                                   stream=0, seq=req.rid)
                    continue
            responses.append(Response(req.rid, gen, a.executor_idx,
                                      a.t_start, a.t_done, wall))
        responses.sort(key=lambda r: r.rid)       # sequence synchronizer
        if rec.enabled:
            clk = 0.0                   # rid-order release clock (one lane)
            for r in responses:
                clk = max(clk, r.t_done)
                rec.record("emit", clk, rid=r.rid, stream=0, seq=r.rid)
        makespan = max((r.t_done for r in responses), default=0.0)
        lk = detection_latency_keys(responses)
        return {
            "responses": responses,
            "dropped": dropped,
            "throughput_rps": len(responses) / max(makespan, 1e-9),
            "p50_latency": lk["p50_latency"],
            "p95_latency": lk["p95_latency"],
            "p99_latency": lk["p99_latency"],
            "latency_hist": lk["latency_hist"],
            "per_replica": _per_replica_counts(self.replicas, responses),
        }


class BoundProgram:
    """A jitted ``fn(params, images)`` with the weights bound: callable
    and lowerable with the images alone, so the weights stay arguments
    of the compiled program and never become its constants."""

    def __init__(self, jitted, params):
        self.jitted, self.params = jitted, params

    def __call__(self, images):
        return self.jitted(self.params, images)

    def lower(self, images):
        return self.jitted.lower(self.params, images)


class DetectionEngine:
    """Video-frame payload path: the paper's "n detection models" served
    from the same scheduler/replica machinery as the token path, with
    frames routed through the detector in micro-batches so the whole
    batch is decoded and suppressed by ONE fused batched-NMS launch
    (repro.kernels.nms) instead of a per-frame kernel + serial loop.

    * ``micro_batch=None`` (the default) sizes each micro-batch by the
      queue depth at dispatch time — the frames that arrived while the
      replicas were busy — capped at ``max_micro_batch``; an explicit
      int keeps the fixed-size behaviour.
    * ``drop_when_busy=True`` reproduces the paper's frame dropping on
      this path: a frame arriving with every replica slot taken gets no
      detection.
    * ``track_and_interpolate=True`` closes that gap with the batched
      tracker (``repro.tracking``): dropped frames are emitted in
      arrival order with tracker-coasted boxes, tagged
      ``interpolated`` — the sequence synchronizer's stale-reuse fill
      upgraded to motion-compensated prediction.
    * ``detect_fn`` swaps the mini-SSD for any ``(images, rids) ->
      (boxes, scores, classes, valid)`` callable (oracle detectors in
      tests/benchmarks); ``service_time`` pins the virtual per-frame
      service time so paced runs are deterministic.
    * Multi-camera (NVR): tag requests with ``stream_id`` and the SAME
      engine multiplexes every camera onto the shared replicas —
      interleaved micro-batches, one batched tracker with B = number
      of streams stepping all cameras in lockstep, and per-stream
      coverage/FPS/drop accounting in the report (``per_stream``,
      ``streams``).  B=1 results are bit-identical to the
      single-stream engine.
    * ``faults=`` takes a ``serving.faults.FaultSchedule`` of
      virtual-time replica slowdowns/deaths/revivals (``fault_shard``
      picks which shard's events apply — 0 standalone).  The scheduler
      detects failures by timeout (``timeout_k`` x expected service),
      retries the in-flight frame up to ``max_retries`` times on a
      healthy replica, and the report's ``retries`` / ``failovers`` /
      ``frames_lost`` keys count the outcomes per replica.  An empty
      schedule (or ``None``) leaves every path bit-identical to the
      pre-fault engine.
    * ``catalog=`` gives every replica a ``serving.models.ModelCatalog``
      of loadable model profiles and turns on per-micro-batch model
      selection (``serving.cascade.ModelSelector``): the heaviest model
      whose pooled ``mu`` sustains the arrival-rate estimate, degrade
      under backlog pressure, hysteretic upgrade when slack returns.
      ``roi=True`` additionally runs the hierarchical second pass
      whenever a lighter model was selected: the first pass's boxes
      become ROI windows (``roi_max`` top-scored, padded ``roi_pad``,
      clamped to ``roi_bounds``) batched through the heavy model, with
      per-frame pixel-reduction accounting.  A single-entry catalog
      never switches and never triggers ROI — bit-identical to pinning
      ``service_time`` to that profile.  Reports gain ``models`` /
      ``model_of_frame`` / ``model_map_est`` / ``model_switches`` /
      ``map_estimate`` / ``roi_pixels`` / ``roi_pixel_reduction``
      (present, empty, without a catalog).
    * Tick pipeline (``serving.pipeline``): the per-tick data plane —
      detect -> decode -> NMS -> [ROI second pass] -> associate ->
      Kalman — is composed from shared stages over a ``TickState``
      pytree.  ``fused_tick=True`` runs the tracker tick as ONE jitted
      program with donated track-table buffers (bit-identical to the
      staged chain); ``post_process=`` installs a pure ``TickState ->
      TickState`` stage between NMS/ROI and the tracker (composes with
      cascade model selection — the state carries the batch's model);
      ``carry_tracks=False`` opts out of seeding the tracker from
      carried portable rows (``serve(stream_tracks=...)``), restoring
      the re-seed-per-segment behaviour.
    """

    def __init__(self, cfg=None, params=None, n_replicas: int = 4,
                 scheduler: str = "fcfs", micro_batch: Optional[int] = None,
                 max_micro_batch: int = 8,
                 replica_speeds: Optional[Sequence[float]] = None,
                 use_pallas: bool = False, score_thr: float = 0.4,
                 iou_thr: float = 0.5, max_out: int = 32, seed: int = 0,
                 drop_when_busy: bool = False,
                 track_and_interpolate: bool = False,
                 tracker_cfg=None, detect_fn=None,
                 service_time: Optional[float] = None,
                 faults=None, fault_shard: int = 0,
                 timeout_k: float = 4.0, max_retries: int = 1,
                 recorder=None, catalog=None, selector_kw=None,
                 roi: bool = False, roi_bounds=None, roi_max: int = 4,
                 roi_pad: float = 0.1, roi_crop: Optional[int] = None,
                 fused_tick: bool = False, post_process=None,
                 carry_tracks: bool = True):
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}: "
                             "an empty replica pool can never serve")
        self.micro_batch = micro_batch
        self.max_micro_batch = micro_batch or max_micro_batch
        self.drop_when_busy = drop_when_busy or track_and_interpolate
        self.track_and_interpolate = track_and_interpolate
        self.service_time = service_time
        self._detect_fn = detect_fn
        if track_and_interpolate:
            from ..tracking import TrackerConfig   # lazy: avoids cycles
            self.tracker_cfg = tracker_cfg or TrackerConfig()
        self._counter = None
        if detect_fn is None:
            from ..detector import SSDConfig, detector_for
            self.cfg = cfg or SSDConfig()
            det = detector_for(self.cfg, score_thr=score_thr,
                               iou_thr=iou_thr, max_out=max_out,
                               use_pallas=use_pallas)
            self.params = params if params is not None else det.init(
                self.cfg, jax.random.PRNGKey(seed))
            self._counter = det.counter

            def infer(params, imgs):
                return det.infer(params, imgs)

            # a named function: its XLA module is ``jit_infer``; the
            # weights are its arguments, so no seed's weights are
            # constants of the program
            self._infer = BoundProgram(jax.jit(infer), self.params)
        else:
            self.cfg = cfg
        speeds = list(replica_speeds or [1.0] * n_replicas)
        self.replicas = [ReplicaExecutor(i, s) for i, s in enumerate(speeds)]
        # fault injection: an EMPTY schedule normalizes to None, so the
        # no-fault path attaches no views and stays bit-identical to the
        # pre-fault engine (the no_fault_bit_identical regression bar)
        self.faults = faults if faults else None
        if self.faults is not None:
            for r in self.replicas:
                r.faults = self.faults.view(fault_shard, r.idx)
        self.scheduler = make_scheduler(scheduler, self.replicas,
                                        host_overhead=1e-4,
                                        timeout_k=timeout_k,
                                        max_retries=max_retries)
        # observability (repro.obs): None -> the shared no-op recorder —
        # the disabled path skips every event and stays bit-identical.
        # The sharded engine passes each shard a recorder.shard_view(h)
        # so this engine's events carry their failure domain.
        self.recorder = recorder if recorder is not None else NULL_RECORDER
        self.scheduler.recorder = self.recorder
        # transprecise cascade (serving.models / serving.cascade): a
        # missing or empty catalog normalizes to None and leaves every
        # existing path untouched.  The selector lives on the ENGINE so
        # scheduler health probes / pool resizes never reset its
        # hysteresis state; each replica carries the catalog object so
        # lending and deaths move per-model capacity with the executor.
        from .models import as_catalog
        self.catalog = as_catalog(catalog)
        self.cascade = None
        if self.catalog is not None:
            from .cascade import ModelSelector
            self.cascade = ModelSelector(self.catalog,
                                         **(selector_kw or {}))
        for r in self.replicas:
            r.catalog = self.catalog
        self.roi = bool(roi)
        self.roi_bounds = tuple(roi_bounds) if roi_bounds is not None else None
        self.roi_max = roi_max
        self.roi_pad = roi_pad
        self.roi_crop = roi_crop
        # tick-pipeline knobs (serving.pipeline): ``fused_tick`` runs
        # the tracker tick as ONE jitted program with donated
        # track-table buffers (bit-identical to the staged chain);
        # ``post_process`` is a pure ``TickState -> TickState`` stage
        # applied after detect/NMS/ROI, before responses and the
        # tracker (None = identity, bit-identical); ``carry_tracks``
        # seeds each segment's tracker from the previous segment's
        # exported rows so identities survive epoch boundaries and
        # stream migration (False restores the old re-seed behavior).
        self.fused_tick = bool(fused_tick)
        self.post_process = post_process
        self.carry_tracks = bool(carry_tracks)
        self._exported_tracks: Mapping[int, dict] = {}
        self._use_pallas = use_pallas
        # capability probe: does a custom detect_fn accept the cascade's
        # model= / rois= keywords?  A plain oracle keeps its exact
        # 2-argument call, so the no-catalog path is bit-identical.
        self._fn_takes_model = self._fn_takes_rois = False
        if detect_fn is not None:
            try:
                import inspect
                ps = inspect.signature(detect_fn).parameters
                self._fn_takes_model = "model" in ps
                self._fn_takes_rois = "rois" in ps
            except (TypeError, ValueError):
                pass
        self._warm = False

    def _detect_batch(self, images: np.ndarray, rids=None, model=None,
                      rois=None):
        """One fused launch for a full micro-batch; returns numpy
        results + measured wall seconds.  ``model``/``rois`` are the
        cascade hooks, forwarded only to detect_fns that declare them."""
        t0 = time.perf_counter()
        frames = (len(images) if rids is None
                  else sum(r >= 0 for r in rids))
        with span("serve.detect", frames=frames,
                  padded=len(images) - frames) as det:
            if self._detect_fn is not None:
                kw = {}
                if model is not None and self._fn_takes_model:
                    kw["model"] = model
                if rois is not None and self._fn_takes_rois:
                    kw["rois"] = rois
                out = tuple(np.asarray(o) for o in
                            self._detect_fn(images, rids, **kw))
            else:
                with span("serve.detect.put", h2d_bytes=images.nbytes):
                    x = jnp.asarray(images)
                with span("serve.detect.run"):
                    out = jax.block_until_ready(self._infer(x))
                with span("serve.detect.pull") as sp:
                    if self._counter:
                        # one pull for the detections and the counter
                        out = tuple(jax.device_get(out))
                    else:
                        out = tuple(np.asarray(o) for o in out)
                    sp.set_metadata(d2h_bytes=sum(o.nbytes for o in out))
                if self._counter:
                    real = (np.arange(len(images)) < frames if rids is None
                            else np.asarray(rids) >= 0)
                    det.set_metadata(
                        **{self._counter: int(out[4][real].sum())})
                    out = out[:4]
        return out, time.perf_counter() - t0

    def _model_caps(self) -> Dict[str, float]:
        """Summed healthy-pool service rate (frames/s) per model name —
        the feasibility signal ``ModelSelector.decide`` consumes.  Each
        replica contributes from ITS OWN catalog (a lent guest carries
        its home catalog; a model a guest cannot load adds nothing), and
        unhealthy replicas contribute nothing at all, so a death
        removes its catalog's capacity the moment the scheduler marks
        it."""
        caps: Dict[str, float] = {}
        for r, ok in zip(self.replicas, self.scheduler.healthy):
            if not ok:
                continue
            cat = r.catalog if r.catalog is not None else self.catalog
            if cat is None:
                continue
            for p in cat:
                caps[p.name] = caps.get(p.name, 0.0) + p.mu / r.speed
        return caps

    def _apply_model(self, model: str, extra_s: float = 0.0):
        """Pin each replica's service estimate to the selected model's
        profile (plus the ROI second-pass surcharge).  Replicas whose
        own catalog pins a different ``service_s`` for the same model
        name use theirs (heterogeneous pools); profiles without
        ``service_s`` leave the measured-wall estimate in charge."""
        for r in self.replicas:
            cat = r.catalog if r.catalog is not None else self.catalog
            prof = cat.get(model) if cat is not None else None
            if prof is not None and prof.service_s is not None:
                r._last_wall = prof.service_s + extra_s

    def warmup(self):
        mb = self.max_micro_batch
        if self._detect_fn is None:
            size = self.cfg.image_size
            imgs = np.zeros((mb, size, size, 3), np.float32)
            _, wall = self._detect_batch(imgs, rids=[-1] * mb)
            per_frame = wall / mb
        else:
            per_frame = 1e-3
        # explicit None check: a pinned ``service_time=0.0`` (zero-cost
        # oracle) must pin the virtual clock to zero, not fall back to
        # the measured wall the way `service_time or wall` did
        if self.service_time is not None:
            per_frame = self.service_time
        for r in self.replicas:
            r._last_wall = per_frame
        self._warm = True

    def reset(self):
        """Clear per-serve virtual-clock state: replica ``busy_until`` /
        processed counts / EWMAs and the scheduler's round bookkeeping.
        Warm service estimates (``_last_wall``) and compiled programs
        survive, so a reset engine starts the next ``serve`` exactly
        like a freshly-warmed one.  Delegates to
        ``ServingRuntime.reset_engines`` — the ONE reset semantic every
        engine shares."""
        from .runtime import ServingRuntime
        ServingRuntime.reset_engines(self)

    def backlog_snapshot(self, t: float) -> Dict:
        """Virtual-clock load observation at time ``t``, the signal the
        sharded serving layer's work-stealing policy consumes:
        ``busy_until`` per replica, ``backlog_s`` (summed committed
        service extending past ``t`` — ``scheduler.backlog``) and
        ``horizon_s`` (how far the busiest replica's commitment reaches
        beyond ``t``).  Pure observation: reading it never perturbs the
        clock."""
        busy = [r.busy_until for r in self.replicas]
        return {"t": t,
                "busy_until": busy,
                "horizon_s": max(max(busy, default=0.0) - t, 0.0),
                "backlog_s": self.scheduler.backlog(t)}

    def _chunk_size(self, frames, i: int) -> int:
        """Queue depth at dispatch time: how many frames have arrived by
        the moment the earliest replica frees up (at least one — the
        head frame defines 'now' when the pipeline is idle).  Shared
        implementation: ``pipeline.chunk_size``."""
        return chunk_size(frames, i, micro_batch=self.micro_batch,
                          max_micro_batch=self.max_micro_batch,
                          replicas=self.replicas)

    @staticmethod
    def _bucket(k: int) -> int:
        """Pad adaptive batches to power-of-two buckets: O(log mb) jit
        traces instead of one per distinct queue depth.  Shared
        implementation: ``pipeline.bucket``.

        >>> [DetectionEngine._bucket(k) for k in (1, 2, 3, 5, 8)]
        [1, 2, 4, 8, 8]
        """
        return bucket(k)

    def serve(self, frames: Sequence[FrameRequest], *, reset: bool = True,
              stream_seq0: Optional[Dict[int, int]] = None,
              stream_emit0: Optional[Dict[int, float]] = None,
              stream_tracks: Optional[Dict[int, dict]] = None) -> Dict:
        """Micro-batched detection serving: frames are grouped in arrival
        order into micro-batches (queue-depth-sized unless a fixed
        ``micro_batch`` was given), each batch runs through the batched
        fast path once, and the per-frame share of the measured wall time
        drives the virtual-clock scheduler.  With ``drop_when_busy``,
        frames arriving into a full pipeline are dropped — and, with
        ``track_and_interpolate``, re-emitted with tracker-predicted
        boxes so the output stream covers every arrival frame.

        Frames from several cameras (distinct ``stream_id``) interleave
        into the SAME micro-batches and replicas; the report carries
        per-stream coverage/FPS/drop accounting next to the global keys
        (see the module docstring for the multi-camera contract).

        Each call is independent by default: per-serve virtual-clock
        state (replica ``busy_until`` / counts / EWMAs, scheduler round
        bookkeeping) is reset on entry and ``per_replica`` counts THIS
        call's placements, so two identical back-to-back calls return
        identical reports.  The keyword-only warm-start hooks exist for
        callers that slice ONE logical trace into several calls (the
        sharded epoch loop):

        * ``reset=False`` carries the virtual clock and scheduler state
          from the previous call instead of clearing them;
        * ``stream_seq0`` maps ``stream_id -> first per-stream arrival
          index of this call`` — its key set is the warm-start stream
          set: every key appears in the report's per-stream maps even
          with zero frames this call, and ``seq`` continues from the
          given floor instead of restarting at 0;
        * ``stream_emit0`` maps ``stream_id -> emit-clock floor``:
          tracker-interpolated frames of that stream are never released
          before it (per-stream emit monotonicity across calls);
        * ``stream_tracks`` maps ``stream_id -> portable track row``
          (``tracking.export_rows``; the engine's own exports land in
          ``_exported_tracks`` after each serve): the lockstep tracker
          seeds those streams from their carried rows instead of fresh
          tables, so track identities survive the call boundary —
          including a ``rebalance_streams`` migration to a different
          shard's engine.  Ignored when ``carry_tracks=False``.

        Report keys: ``responses`` (rid order), ``dropped`` (rids, in
        arrival order), ``coverage`` = responses/frames,
        ``interpolated`` (count of tracker-filled frames),
        ``throughput_fps``, ``per_replica`` (frames per executor, this
        call), ``n_streams``, ``streams`` ({stream_id: responses in
        per-stream ``seq`` order}), ``emit_t`` ({stream_id: monotonic
        release clocks, same length as the stream's responses}),
        ``per_stream`` ({stream_id: frames / dropped / interpolated /
        coverage / throughput_fps}), ``tracker_launches`` /
        ``tracker_ticks`` (lockstep-tracker accounting; 0 unless
        ``track_and_interpolate``), ``track_table_resident`` (segments
        whose tracker started from the last segment's device table
        rather than from rows), and ``retries`` / ``failovers`` /
        ``frames_lost`` (this call's failure-detection counts, sparse
        per replica — all empty on the fault-free path).

        Latency keys (``repro.obs.metrics``): ``p50_latency`` (exact
        median of detection ``t_done - t_start``), ``p95_latency`` /
        ``p99_latency`` (quantiles of the log-bucketed
        ``latency_hist`` — mergeable: shard merges sum buckets and
        recompute, never average), ``interp_latency`` (re-emission
        delay of tracker-interpolated frames, kept OUT of the
        detection histogram), and ``latency_by_stream`` /
        ``latency_by_replica`` histogram rollups.  With a
        ``recorder=`` attached, the engine additionally records the
        full frame lifecycle (arrive/enqueue/dispatch/complete/drop/
        emit events — see ``repro.obs.trace``) and samples queue depth
        and scheduler backlog at each micro-batch dispatch; the
        default no-op recorder keeps this path bit-identical."""
        from .runtime import ServingRuntime
        rt = ServingRuntime(self, reset=reset, stream_seq0=stream_seq0,
                            stream_emit0=stream_emit0,
                            stream_tracks=stream_tracks)
        rt.ingest(frames)
        return rt.drain()

    def _interpolate(self, frames, responses, seq_of, emit0,
                     tracks0: Optional[Dict[int, dict]] = None,
                     rec=None, resident: Optional[TrackTable] = None,
                     ) -> List[DetectionResponse]:
        """ONE batched tracker over every camera stream, advanced in
        lockstep by the shared tick pipeline (``serving.pipeline``):
        tick k covers each stream's k-th arrival frame, and the whole
        (B, T) track table moves with a single tracker launch per tick
        (the staged ``trk.step``/``trk.coast`` chain by default; the
        one-jit donated-buffer program under ``fused_tick`` —
        bit-identical).  Streams whose tick-k frame was processed feed
        the associate/update/birth path; streams whose frame was
        dropped — or that have no frame left — are passed an
        all-invalid detection row, which is bit-identical to coasting
        (every lifecycle write is masked by match/birth bits that an
        invalid row can never set).  Dropped frames are re-emitted with
        the coasted prediction, tagged ``interpolated``, ready no
        earlier than the newest detection of the SAME stream they
        extrapolate from (per-stream emit clocks: one slow camera never
        delays another's output).

        ``tracks0`` seeds streams from carried portable rows (see
        ``serve``'s ``stream_tracks``); ``resident``, the last
        segment's ``TrackTable``, carries newer rows for its streams
        and is itself the starting table when the segment serves
        exactly its streams (``TickPipeline.seed``).  The final table
        lands in ``self._exported_tracks`` as a ``TrackTable`` either
        way: it stays on the device until something reads its rows.
        With a ``rec`` attached, seeding records a ``track_import`` per
        carried stream, the export records a ``track_export`` per
        stream (both carrying ``next_id`` + confirmed ``tids`` — the
        identity-continuity audit's evidence), so both read rows."""
        per: Dict[int, List[FrameRequest]] = {}
        for f in frames:                    # frames sorted by arrival
            per.setdefault(f.stream_id, []).append(f)
        ticks = max(len(v) for v in per.values())
        with span("serve.track", ticks=ticks, streams=len(per)):
            rec = NULL_RECORDER if rec is None else rec
            cfg = self.tracker_cfg
            sids = sorted(per)
            row = {s: b for b, s in enumerate(sids)}
            B = len(sids)
            pipe = TickPipeline(cfg, fused=self.fused_tick)
            rows0 = dict(tracks0) if (self.carry_tracks and tracks0) else {}
            resident = resident if self.carry_tracks else None
            if rec.enabled and resident is not None:
                # the import events read the carried rows: pull them
                # before a fused tick donates the table
                rows0.update(resident)
            state = pipe.seed(sids, rows0, resident)
            if rec.enabled:
                for s in sids:
                    r0 = rows0.get(s)
                    if r0 is not None:
                        rec.record("track_import", per[s][0].t_arrival,
                                   stream=s, next_id=int(r0["next_id"]),
                                   tids=confirmed_ids(r0, cfg))
            by_rid = {r.rid: r for r in responses}
            D = responses[0].boxes.shape[0] if responses else 1
            # warm-start emit floor: when this call continues a sliced trace
            # (epoch loop), a stream's interpolated frames are never released
            # before anything the PREVIOUS call already emitted for it
            emit_t = {s: emit0.get(s, 0.0) for s in sids}
            out: List[DetectionResponse] = []
            for k in range(ticks):
                tick = [(s, per[s][k] if k < len(per[s]) else None)
                        for s in sids]
                resp = {s: by_rid.get(f.rid) if f is not None else None
                        for s, f in tick}
                det_tid = None
                if any(r is not None for r in resp.values()):
                    boxes = np.zeros((B, D, 4), np.float32)
                    scores = np.zeros((B, D), np.float32)
                    classes = np.zeros((B, D), np.int32)
                    valid = np.zeros((B, D), bool)
                    for s, r in resp.items():
                        if r is not None:
                            b = row[s]
                            boxes[b], scores[b] = r.boxes, r.scores
                            classes[b], valid[b] = r.classes, r.valid
                    state, det_tid, fout = pipe.tick(state, boxes, scores,
                                                     classes, valid)
                else:                           # no stream saw a detection
                    state, fout = pipe.coast(state, det_width=D)
                # fused mode returns the tick's output for free; the staged
                # chain materializes it lazily, only if a drop needs it
                coasted = (tuple(np.asarray(a) for a in fout)
                           if fout is not None else None)
                for s, f in tick:
                    if f is None:
                        continue
                    r, b = resp[s], row[s]
                    if r is not None:
                        r.track_ids = det_tid[b]
                        emit_t[s] = max(emit_t[s], r.t_done)
                        out.append(r)
                    else:
                        if coasted is None:
                            coasted = tuple(np.asarray(a) for a in
                                            pipe.output(state))
                        tb, ts, tc, tid, emit = coasted
                        t_ready = max(emit_t[s], f.t_arrival)
                        out.append(DetectionResponse(
                            f.rid, tb[b], ts[b], tc[b], emit[b], -1, t_ready,
                            t_ready, 0.0, interpolated=True,
                            track_ids=tid[b], stream_id=s, seq=seq_of[f.rid]))
            self._tracker_launches = pipe.launches
            self._tracker_ticks = ticks
            self._track_table_resident = pipe.resident
            self._exported_tracks = pipe.export(state, sids,
                                                pull=rec.enabled)
            if rec.enabled:
                for s in sids:
                    rowd = self._exported_tracks[s]
                    rec.record("track_export", per[s][-1].t_arrival,
                               stream=s, next_id=int(rowd["next_id"]),
                               tids=confirmed_ids(rowd, cfg))
            return out
