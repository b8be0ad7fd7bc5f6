"""Sharded multi-host NVR serving on the replica mesh.

``DetectionEngine`` multiplexes every camera of an NVR deployment onto
one host's replica pool.  This layer carries the same serving contract
across a *device mesh*: the camera set is partitioned over mesh shards
(``sharding.serving_rules.shard_streams`` — deterministic, so every
host agrees without communicating), each shard runs its own
``DetectionEngine`` — its own scheduler, interleaved micro-batches and
lockstep ``B = cameras-per-shard`` tracker — and the per-shard reports
are merged into ONE global engine report with the exact key set
``DetectionEngine.serve`` produces (so ``core.quality.evaluate_streams``
consumes it unchanged).

Two detection paths
-------------------
* **SPMD fast path** (``mesh=`` given): the batched detect+NMS launch
  is ONE ``jax.jit`` program whose micro-batch dim carries the
  ``replica`` logical axis (``constrain_frames`` /
  ``constrain_detections``), compiled once and shared by every shard —
  the mesh, not a Python loop, spreads frames over devices.  This is
  the paper's "n parallel detection models" as a single compiled
  program spanning the mesh.
* **Scheduler fallback** (``mesh=None``): each shard's engine keeps its
  own per-host jitted program (or the caller's ``detect_fn`` oracle) —
  the path for heterogeneous device pools, which one SPMD program
  cannot model, and for numpy oracles, which cannot be jitted.

Single-shard regression bar: ``ShardedDetectionEngine(n_shards=1,
**kw).serve(trace)`` is bit-identical to
``DetectionEngine(**kw).serve(trace)`` — the sharded layer adds keys
(``n_shards``, ``per_shard``, ``shard_of_stream``) but never changes
the base report.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.synchronizer import SequenceSynchronizer
from ..obs.metrics import merge_hist_dicts, quantile_of_dict
from ..obs.trace import NULL_RECORDER
from ..sharding.context import mesh_context
from ..sharding.serving_rules import constrain_detections, constrain_frames
from .engine import DetectionEngine, FrameRequest
from .models import cascade_report_keys


def _require_ssd(cfg):
    """The mesh paths build only the mini-SSD: refuse any other detector
    rather than serve an SSD in its place."""
    from ..detector import SSDConfig
    if cfg is not None and not isinstance(cfg, SSDConfig):
        raise ValueError(f"the sharded detection paths serve only an "
                         f"SSDConfig detector, not a {type(cfg).__name__}: "
                         "use DetectionEngine on one device")


def make_spmd_detect(cfg, params, mesh, *, score_thr: float = 0.4,
                     iou_thr: float = 0.5, max_out: int = 32,
                     use_pallas: bool = False):
    """ONE jitted detect+NMS program spanning every replica of ``mesh``.

    Wraps the unchanged ``detector.decode_detections`` with replica-axis
    sharding constraints on its input images and output detections, so
    a micro-batch of B frames is computed by the mesh's ``data`` axis
    shards in a single compiled program — the SPMD replacement for the
    Python-side per-replica executor loop.  On a 1-device mesh the
    constraints are no-ops and the outputs are bit-identical to
    ``DetectionEngine``'s own jitted path.

    Returns a ``(images, rids=None) -> (boxes, scores, classes, valid)``
    callable matching the ``DetectionEngine.detect_fn`` interface
    (blocking, so the engine's wall-time measurement brackets real
    device work)."""
    from ..detector import decode_detections, make_anchors
    _require_ssd(cfg)
    anchors = jnp.asarray(make_anchors(cfg))

    def infer(imgs):
        imgs = constrain_frames(imgs)
        out = decode_detections(params, cfg, imgs, anchors,
                                score_thr=score_thr, iou_thr=iou_thr,
                                max_out=max_out, use_pallas=use_pallas)
        return constrain_detections(*out)

    jitted = jax.jit(infer)

    def detect(images, rids=None):
        with mesh_context(mesh):
            return jax.block_until_ready(jitted(jnp.asarray(images)))

    return detect


def _renumber_and_collect(frames: Sequence[FrameRequest],
                          reports: Sequence[Dict],
                          report_shard: Sequence[int],
                          pool_sizes: Sequence[int]):
    """Shared merge scaffolding for ``merge_shard_reports`` (one report
    per shard) and ``merge_epoch_shard_reports`` (one per epoch x
    shard): renumber replica ids by the owning shard's pool offset (on
    COPIES — never the caller's responses; offset 0 reuses the original
    objects so single-shard reports stay bit-identical), collect
    responses in rid order and dropped rids in global arrival order
    (stable on ties, like the engine's own sort), sum the per-call
    ``per_replica`` counts into the globally-renumbered map, and
    rebuild the per-stream view from the merged responses with the
    engine's own reorder helper — so ``streams`` holds the SAME objects
    as ``responses``, the DetectionEngine contract.

    Returns ``(responses, dropped, makespan, per_replica, streams,
    emit_t)``."""
    n_shards = len(pool_sizes)
    offsets = [0] * n_shards
    for h in range(1, n_shards):
        offsets[h] = offsets[h - 1] + pool_sizes[h - 1]
    per_replica: Dict[int, int] = {
        offsets[h] + i: 0 for h in range(n_shards)
        for i in range(pool_sizes[h])}
    responses = []
    for rep, h in zip(reports, report_shard):
        off = offsets[h]
        for idx, count in rep["per_replica"].items():
            per_replica[off + idx] += count
        for r in rep["responses"]:
            if off and r.replica >= 0:
                r = replace(r, replica=r.replica + off)
            responses.append(r)
    responses.sort(key=lambda r: r.rid)
    pos = {f.rid: i for i, f in
           enumerate(sorted(frames, key=lambda f: f.t_arrival))}
    dropped = sorted((rid for rep in reports for rid in rep["dropped"]),
                     key=pos.__getitem__)
    makespan = max((r.t_done for r in responses), default=0.0)
    ordered = SequenceSynchronizer.order_per_stream(responses)
    streams = {sid: rs for sid, (rs, _) in ordered.items()}
    emit_t = {sid: em for sid, (_, em) in ordered.items()}
    return responses, dropped, makespan, per_replica, streams, emit_t


def _merged_fault_counts(reports: Sequence[Dict],
                         report_shard: Sequence[int],
                         pool_sizes: Sequence[int]) -> Dict[str, Dict]:
    """Sum the per-replica failure counters (``retries`` / ``failovers``
    / ``frames_lost``) across shard reports, renumbering replica ids by
    the owning shard's pool offset exactly like ``per_replica``.  The
    keys stay sparse (all-empty on the fault-free path), mirroring the
    single-engine report."""
    offsets = [0] * len(pool_sizes)
    for h in range(1, len(pool_sizes)):
        offsets[h] = offsets[h - 1] + pool_sizes[h - 1]
    out: Dict[str, Dict] = {"retries": {}, "failovers": {},
                            "frames_lost": {}}
    for rep, h in zip(reports, report_shard):
        for key, agg in out.items():
            for idx, c in rep.get(key, {}).items():
                g = offsets[h] + idx
                agg[g] = agg.get(g, 0) + c
    return out


def _merged_latency_keys(responses, reports: Sequence[Dict],
                         report_shard: Sequence[int],
                         pool_sizes: Sequence[int]) -> Dict:
    """Rebuild the latency block of a merged report (``repro.obs``
    contract): histograms SUM bucket-wise across shard reports and the
    quantiles are recomputed from the merged buckets — never averaged
    (an average of per-shard p99s is not a p99).  ``p50_latency`` is
    recomputed exactly (median over the merged detection latencies,
    the same formula the engine uses), so a single-shard merge is
    bit-identical to the shard's own report.  ``latency_by_replica``
    keys renumber by the owning shard's pool offset like
    ``per_replica``."""
    det = merge_hist_dicts(rep.get("latency_hist") for rep in reports)
    interp = merge_hist_dicts(rep.get("interp_latency")
                              for rep in reports)
    by_stream: Dict[int, List] = {}
    by_replica: Dict[int, List] = {}
    offsets = [0] * len(pool_sizes)
    for h in range(1, len(pool_sizes)):
        offsets[h] = offsets[h - 1] + pool_sizes[h - 1]
    for rep, h in zip(reports, report_shard):
        for sid, d in rep.get("latency_by_stream", {}).items():
            by_stream.setdefault(sid, []).append(d)
        for idx, d in rep.get("latency_by_replica", {}).items():
            by_replica.setdefault(offsets[h] + idx, []).append(d)
    lat = [r.t_done - r.t_start for r in responses if not r.interpolated]
    return {
        "p50_latency": float(np.median(lat)) if lat else 0.0,
        "p95_latency": quantile_of_dict(det, 0.95),
        "p99_latency": quantile_of_dict(det, 0.99),
        "latency_hist": det,
        "interp_latency": interp,
        "latency_by_stream": {sid: merge_hist_dicts(ds)
                              for sid, ds in sorted(by_stream.items())},
        "latency_by_replica": {g: merge_hist_dicts(ds)
                               for g, ds in sorted(by_replica.items())},
    }


def _epoch_rollup(reports: Sequence[Dict]) -> Dict:
    """One epoch's latency/volume rollup for the ``per_epoch`` key."""
    det = merge_hist_dicts(rep.get("latency_hist") for rep in reports)
    return {
        "responses": sum(len(rep["responses"]) for rep in reports),
        "dropped": sum(len(rep["dropped"]) for rep in reports),
        "interpolated": sum(rep["interpolated"] for rep in reports),
        "latency_hist": det,
        "p95_latency": quantile_of_dict(det, 0.95),
        "p99_latency": quantile_of_dict(det, 0.99),
    }


def _merged_cascade_keys(reports: Sequence[Dict], n_frames: int) -> Dict:
    """Merge the transprecise-cascade block: raw counters sum (model
    counts, switches, roi pixels) or union (``model_of_frame`` /
    ``model_map_est`` — rids are globally unique, catalogs agree on
    names), then the derived scalars (``map_estimate``,
    ``roi_pixel_reduction``) are RECOMPUTED by the same
    ``cascade_report_keys`` the engines use — never averaged — so a
    single-shard merge is bit-identical to the shard's own report."""
    counts: Dict[str, int] = {}
    model_of: Dict[int, str] = {}
    map_est: Dict[str, float] = {}
    switches = 0
    roi_px = {"full": 0.0, "roi": 0.0, "passes": 0}
    for rep in reports:
        for m, c in rep.get("models", {}).items():
            counts[m] = counts.get(m, 0) + c
        model_of.update(rep.get("model_of_frame", {}))
        map_est.update(rep.get("model_map_est", {}))
        switches += rep.get("model_switches", 0)
        rp = rep.get("roi_pixels", {})
        roi_px["full"] += rp.get("full", 0.0)
        roi_px["roi"] += rp.get("roi", 0.0)
        roi_px["passes"] += rp.get("passes", 0)
    return cascade_report_keys(counts, model_of, map_est, switches,
                               roi_px, n_frames)


def merge_shard_reports(frames: Sequence[FrameRequest],
                        reports: Sequence[Dict],
                        pool_sizes: Sequence[int]) -> Dict:
    """Merge per-shard ``DetectionEngine.serve`` reports into one global
    engine report.

    Streams are disjoint across shards, so the per-stream maps
    (``streams`` / ``emit_t`` / ``per_stream``) merge by union; global
    scalars (``coverage``, ``throughput_fps``) are recomputed from the
    merged responses with the same formulas ``DetectionEngine`` uses;
    replica ids are renumbered globally (shard ``h``'s replica ``i``
    becomes ``offset(h) + i`` with ``offset = cumsum(pool_sizes)``) —
    both the ``per_replica`` map and every ``DetectionResponse.replica``
    field (the ``-1`` tracker-interpolated sentinel excepted), so
    grouping responses by replica stays consistent with the map.  With
    a single shard every merged key is bit-identical to the shard's own
    report.

    Adds the shard-level view on top: ``n_shards`` and ``per_shard``
    (per-shard frame/response/drop/tracker counts).  The caller attaches
    ``shard_of_stream``.

    Tracker accounting across shards: each shard runs its OWN lockstep
    tracker, so the merged ``tracker_launches`` SUMS over shards while
    ``tracker_ticks`` is the MAX (the shards tick in parallel, not in
    series).  The single-engine invariant "one launch per tick" thus
    reads globally as ``launches == n_shards x ticks`` — exact when
    every shard saw the same tick count (balanced frames-per-stream),
    an upper bound on ``ticks`` otherwise.  ``track_table_resident``
    sums too."""
    # renumber replica ids on COPIES (never mutate the caller's shard
    # reports), keeping the -1 tracker-interpolated sentinel; offset 0
    # (first shard / single shard) reuses the original objects so the
    # shards=1 report stays bit-identical
    responses, dropped, makespan, per_replica, streams, emit_t = \
        _renumber_and_collect(frames, reports, range(len(reports)),
                              pool_sizes)
    per_stream: Dict[int, Dict] = {}
    for rep in reports:
        per_stream.update(rep["per_stream"])
        for sid in rep["streams"]:
            streams.setdefault(sid, [])      # streams with 0 responses
            emit_t.setdefault(sid, [])
    return {
        "responses": responses,
        "dropped": dropped,
        "coverage": len(responses) / max(len(frames), 1),
        "interpolated": sum(rep["interpolated"] for rep in reports),
        "throughput_fps": len(responses) / max(makespan, 1e-9),
        "per_replica": per_replica,
        "n_streams": sum(rep["n_streams"] for rep in reports),
        "streams": streams,
        "emit_t": emit_t,
        "per_stream": per_stream,
        "tracker_launches": sum(rep["tracker_launches"]
                                for rep in reports),
        "tracker_ticks": max((rep["tracker_ticks"] for rep in reports),
                             default=0),
        "track_table_resident": sum(rep["track_table_resident"]
                                    for rep in reports),
        **_merged_fault_counts(reports, range(len(reports)), pool_sizes),
        **_merged_latency_keys(responses, reports, range(len(reports)),
                               pool_sizes),
        **_merged_cascade_keys(reports, len(frames)),
        "per_epoch": {0: _epoch_rollup(reports)},
        "n_shards": len(reports),
        "per_shard": [{
            "streams": sorted(rep["per_stream"]),
            "frames": sum(v["frames"] for v in rep["per_stream"].values()),
            "responses": len(rep["responses"]),
            "dropped": len(rep["dropped"]),
            "interpolated": rep["interpolated"],
            "tracker_launches": rep["tracker_launches"],
            "tracker_ticks": rep["tracker_ticks"],
            "latency_hist": merge_hist_dicts([rep.get("latency_hist")]),
        } for rep in reports],
    }


def merge_epoch_shard_reports(frames: Sequence[FrameRequest],
                              reports: Sequence[Dict],
                              report_shard: Sequence[int],
                              pool_sizes: Sequence[int],
                              report_epoch: Optional[Sequence[int]] = None,
                              ) -> Dict:
    """Merge per-(epoch, shard) ``DetectionEngine.serve`` reports into
    one global engine report — the epoch-loop generalization of
    ``merge_shard_reports``.

    Unlike the single-epoch merge, a stream may appear in SEVERAL
    reports (later epochs, and — after a migration — a different
    shard), so per-stream stats are SUMMED across reports instead of
    unioned, and the per-stream response order / emit clocks are
    rebuilt globally from the merged responses (``rid`` stays globally
    unique and ``seq`` is the global per-stream arrival index thanks to
    the engines' warm-start floors, so the rebuild is exact).  Replica
    ids renumber by shard exactly as in ``merge_shard_reports``; per-
    call ``per_replica`` counts sum across epochs.  ``per_shard``
    aggregates each shard over its epochs (its ``streams`` list names
    every stream the shard served at least one frame for — a migrated
    stream legitimately shows up on two shards).  Global
    ``tracker_launches`` sums over shards AND epochs; global
    ``tracker_ticks`` is the max over shards of each shard's summed
    epoch ticks (shards tick in parallel, epochs in series);
    ``track_table_resident`` sums like ``tracker_launches``.  The
    caller attaches ``shard_of_stream`` / ``migrations`` /
    ``n_epochs``.

    Latency merging (``repro.obs.metrics``): histograms sum bucket-wise
    across every (epoch, shard) report, quantiles are recomputed from
    the merged buckets (never averaged), and ``p50_latency`` is the
    exact median over the merged responses.  ``report_epoch`` (the raw
    epoch index of each report, parallel to ``report_shard``) buckets
    the ``per_epoch`` rollup; when omitted every report lands in epoch
    0."""
    n_shards = len(pool_sizes)
    epochs_of = (list(report_epoch) if report_epoch is not None
                 else [0] * len(reports))
    responses, dropped, makespan, per_replica, streams, emit_t = \
        _renumber_and_collect(frames, reports, report_shard, pool_sizes)
    per_stream: Dict[int, Dict] = {}
    per_shard = [{"streams": set(), "frames": 0, "responses": 0,
                  "dropped": 0, "interpolated": 0, "tracker_launches": 0,
                  "tracker_ticks": 0, "_hists": []}
                 for _ in range(n_shards)]
    for rep, h in zip(reports, report_shard):
        for sid, v in rep["per_stream"].items():
            agg = per_stream.setdefault(
                sid, {"frames": 0, "dropped": 0, "interpolated": 0})
            agg["frames"] += v["frames"]
            agg["dropped"] += v["dropped"]
            agg["interpolated"] += v["interpolated"]
            if v["frames"]:
                per_shard[h]["streams"].add(sid)
            per_shard[h]["frames"] += v["frames"]
        per_shard[h]["responses"] += len(rep["responses"])
        per_shard[h]["dropped"] += len(rep["dropped"])
        per_shard[h]["interpolated"] += rep["interpolated"]
        per_shard[h]["tracker_launches"] += rep["tracker_launches"]
        per_shard[h]["tracker_ticks"] += rep["tracker_ticks"]
        per_shard[h]["_hists"].append(rep.get("latency_hist"))
    for sh in per_shard:
        sh["streams"] = sorted(sh["streams"])
        sh["latency_hist"] = merge_hist_dicts(sh.pop("_hists"))
    for sid, agg in per_stream.items():
        rs = streams.setdefault(sid, [])
        em = emit_t.setdefault(sid, [])
        agg["coverage"] = len(rs) / max(agg["frames"], 1)
        agg["throughput_fps"] = len(rs) / max(em[-1] if em else 0.0, 1e-9)
    return {
        "responses": responses,
        "dropped": dropped,
        "coverage": len(responses) / max(len(frames), 1),
        "interpolated": sum(rep["interpolated"] for rep in reports),
        "throughput_fps": len(responses) / max(makespan, 1e-9),
        "per_replica": per_replica,
        "n_streams": len(per_stream),
        "streams": streams,
        "emit_t": emit_t,
        "per_stream": per_stream,
        "tracker_launches": sum(rep["tracker_launches"]
                                for rep in reports),
        "tracker_ticks": max((sh["tracker_ticks"] for sh in per_shard),
                             default=0),
        "track_table_resident": sum(rep["track_table_resident"]
                                    for rep in reports),
        **_merged_fault_counts(reports, report_shard, pool_sizes),
        **_merged_latency_keys(responses, reports, report_shard,
                               pool_sizes),
        **_merged_cascade_keys(reports, len(frames)),
        "per_epoch": {
            e: _epoch_rollup([rep for rep, re_ in zip(reports, epochs_of)
                              if re_ == e])
            for e in sorted(set(epochs_of))},
        "n_shards": n_shards,
        "per_shard": per_shard,
    }


class ShardedDetectionEngine:
    """NVR detection serving partitioned over mesh shards.

    ``n_shards`` Python-level shards each own a full ``DetectionEngine``
    (replica pool, scheduler, micro-batching, lockstep tracker with
    ``B = cameras assigned to the shard``); the camera set is split by
    the deterministic ``shard_streams`` partition and the per-shard
    reports merge into one global report (``merge_shard_reports``).
    Every ``DetectionEngine`` keyword is accepted and forwarded
    verbatim to the shard engines, so ``n_shards=1`` is a transparent
    wrapper: same trace in, bit-identical report out (plus the
    ``n_shards`` / ``per_shard`` / ``shard_of_stream`` extras).

    ``mesh`` switches the detection compute to the SPMD fast path: one
    ``make_spmd_detect`` program shared by all shards, its micro-batch
    dim constrained to the mesh's replica (``data``) axis.  Requires
    the built-in mini-SSD path (a numpy ``detect_fn`` oracle cannot be
    jitted — passing both is an error); heterogeneous
    ``replica_speeds`` keep working because speeds scale the *virtual*
    service clock, not the compiled program.  Off-mesh (``mesh=None``)
    the engines keep today's per-host scheduler path.

    Cross-shard work stealing (``rebalance=True``): the static
    ``shard_streams`` partition drops frames on a shard whose cameras
    go bursty while a neighboring shard idles — the paper's §III rate
    mismatch, recreated between shards.  With rebalancing on, ``serve``
    splits the trace into ``epoch_s``-second virtual-time epochs; after
    each epoch every shard's backlog/drop pressure is observed
    (``DetectionEngine.backlog_snapshot`` + the epoch report) and
    ``sharding.serving_rules.rebalance_streams`` — a pure deterministic
    function of those observations, so replicated dispatchers agree
    without coordinating — migrates up to ``max_moves_per_epoch`` whole
    camera streams from the most pressured shard to the least pressured
    one.  Migration happens ONLY at epoch boundaries: within an epoch
    no tracker state moves; at the boundary every stream's portable
    track rows (``tracking.export_rows``, handed between shards through
    the engines' ``stream_tracks`` warm start) and its per-stream
    ``seq`` and emit clock all carry to its new shard alongside the
    ``stream_seq0`` / ``stream_emit0`` floors — so track identities,
    per-stream ordering and emit monotonicity survive migration, and
    nothing is silently reset mid-epoch.  ``rebalance=False`` (the default) and
    ``n_shards=1`` (no peer to steal from) keep the static single-pass
    path, bit-identical to the pre-stealing engine.

    Example::

        mesh = make_serving_mesh(4)            # 4-shard host mesh
        eng = ShardedDetectionEngine(n_shards=4, mesh=mesh,
                                     n_replicas=2,
                                     track_and_interpolate=True)
        report = eng.serve(frames)             # same keys as the
                                               # single-host engine
    """

    def __init__(self, n_shards: int = 1, mesh=None, cfg=None, params=None,
                 seed: int = 0, detect_fn=None, use_pallas: bool = False,
                 score_thr: float = 0.4, iou_thr: float = 0.5,
                 max_out: int = 32, rebalance: bool = False,
                 epoch_s: float = 4.0, max_moves_per_epoch: int = 1,
                 faults=None, supervisor=None, recorder=None,
                 **engine_kwargs):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if epoch_s <= 0:
            raise ValueError(f"epoch_s must be > 0, got {epoch_s}")
        self.rebalance = rebalance
        self.epoch_s = epoch_s
        self.max_moves_per_epoch = max_moves_per_epoch
        # fault injection + supervision: an empty schedule normalizes to
        # None so the fault-free paths stay bit-identical
        self.faults = faults if faults else None
        self.supervisor = supervisor
        if self.faults is not None and self.faults.has_shard_events and (
                not rebalance or n_shards < 2):
            raise ValueError(
                "shard-level fault events are folded into the epoch "
                "loop: they require rebalance=True and n_shards >= 2 "
                "(replica-level events work on any configuration)")
        if supervisor is not None and (not rebalance or n_shards < 2):
            raise ValueError(
                "the watchdog supervises epoch boundaries: supervisor= "
                "requires rebalance=True and n_shards >= 2")
        if mesh is not None and detect_fn is not None:
            raise ValueError(
                "mesh= (SPMD detect) and detect_fn= (host-side oracle) "
                "are mutually exclusive: an arbitrary Python callable "
                "cannot be compiled across mesh shards — drop mesh= to "
                "use the scheduler fallback path")
        if detect_fn is None:
            _require_ssd(cfg)
        self.n_shards = n_shards
        self.mesh = mesh
        self._shared_detect = None
        self._spmd_warm = False
        if mesh is not None:
            from ..detector import SSDConfig, init_ssd
            cfg = cfg or SSDConfig()
            if params is None:
                params = init_ssd(cfg, jax.random.PRNGKey(seed))
            self._shared_detect = make_spmd_detect(
                cfg, params, mesh, score_thr=score_thr, iou_thr=iou_thr,
                max_out=max_out, use_pallas=use_pallas)
            self.cfg = cfg
            shard_detect_kw = dict(detect_fn=self._shared_detect, cfg=cfg)
        else:
            if detect_fn is None:
                # meshless mini-SSD: init the params ONCE — the shards
                # are replicas of the same model, not n different ones
                from ..detector import SSDConfig, init_ssd
                cfg = cfg or SSDConfig()
                if params is None:
                    params = init_ssd(cfg, jax.random.PRNGKey(seed))
            shard_detect_kw = dict(detect_fn=detect_fn, cfg=cfg,
                                   params=params, seed=seed,
                                   use_pallas=use_pallas,
                                   score_thr=score_thr, iou_thr=iou_thr,
                                   max_out=max_out)
            self.cfg = cfg
        # observability: each shard engine gets a shard_view(h) of the
        # one recorder, so its frame/replica events carry their failure
        # domain; the watchdog shares the recorder for loan/restart
        # events.  None -> the no-op recorder (bit-identical default).
        self.recorder = recorder if recorder is not None else NULL_RECORDER
        if supervisor is not None:
            supervisor.recorder = self.recorder
        self.engines = [DetectionEngine(**shard_detect_kw, **engine_kwargs,
                                        faults=self.faults, fault_shard=h,
                                        recorder=self.recorder.shard_view(h))
                        for h in range(n_shards)]
        if mesh is None and detect_fn is None:
            # one jitted program for all shards (identical closures
            # would otherwise re-trace/compile per shard)
            for eng in self.engines[1:]:
                eng._infer = self.engines[0]._infer

    # ------------------------------------------------------------- warmup
    def warmup(self):
        """Warm every shard engine, plus — on the SPMD path — compile the
        shared mesh program at every power-of-two micro-batch bucket
        the engines can emit, so no served batch's measured wall time
        (which drives the schedulers' service estimates) includes XLA
        compilation."""
        for eng in self.engines:
            if not eng._warm:
                eng.warmup()
        if self._shared_detect is not None and not self._spmd_warm:
            size = self.cfg.image_size
            eng = self.engines[0]
            if eng.micro_batch is not None:
                # fixed mode pads every batch to exactly micro_batch
                shapes = [eng.micro_batch]
            else:
                # adaptive mode buckets to powers of two, up to the
                # bucket that COVERS max_micro_batch (e.g. max 6 -> 8)
                shapes, b = [], 1
                while b < DetectionEngine._bucket(eng.max_micro_batch):
                    shapes.append(b)
                    b <<= 1
                shapes.append(b)
            for b in shapes:
                self._shared_detect(
                    np.zeros((b, size, size, 3), np.float32))
            self._spmd_warm = True

    # ------------------------------------------------------------- serving
    def serve(self, frames: Sequence[FrameRequest]) -> Dict:
        """Partition the trace's cameras over the shards, serve each
        shard's sub-trace through its own engine, and merge the
        per-shard reports into one global report (same keys as
        ``DetectionEngine.serve`` plus ``n_shards`` / ``per_shard`` /
        ``shard_of_stream``).

        ``rid`` stays globally unique and ``seq`` is per-stream, so
        responses and quality accounting are unaffected by WHICH shard
        served a camera; only drop/latency behaviour depends on the
        per-shard pools.

        With ``rebalance=True`` (and more than one shard) the trace is
        served in ``epoch_s`` virtual-second epochs with cross-shard
        work stealing between them (see the class docstring); the
        report gains ``migrations`` (one ``{"epoch", "stream", "src",
        "dst"}`` record per executed move) and ``n_epochs``, and
        ``shard_of_stream`` reflects the FINAL partition.

        With ``faults=`` (or ``supervisor=``) active, the report also
        gains ``faults`` (``{"n_events", "frames_lost_shard",
        "restarts", "loans"}`` — the injected schedule's size and the
        recovery actions taken) and ``recovered_coverage`` (the minimum
        per-stream coverage over frames arriving after the last fault /
        recovery action took effect — 1.0 means every stream fully
        recovered).

        The merged report carries the engine's latency block
        (``p50_latency`` / ``p95_latency`` / ``p99_latency`` /
        ``latency_hist`` / ``interp_latency`` / ``latency_by_stream``
        / ``latency_by_replica`` — histograms summed across shards,
        quantiles recomputed from the merged buckets) plus
        ``per_epoch`` ({raw epoch index: responses / dropped /
        latency rollup}; a single ``0`` entry on the static path) and
        a ``latency_hist`` per ``per_shard`` entry.  With a
        ``recorder=`` attached, every shard engine traces through a
        ``shard_view`` of it and the epoch loop adds
        epoch/migrate/shard_down/shard_lost control events (the
        watchdog adds loan/restart events) — see ``repro.obs``."""
        from .runtime import ServingRuntime
        rt = ServingRuntime(self)
        rt.ingest(frames)
        return rt.drain()

    def reset(self):
        """Clear per-serve virtual-clock state on EVERY shard engine
        (replica ``busy_until`` / counts / EWMAs and each shard
        scheduler's round bookkeeping) so repeated ``serve()`` calls
        are independent.  Delegates to
        ``ServingRuntime.reset_engines`` — the ONE reset semantic every
        engine shares (warm service estimates and compiled programs
        survive, like ``DetectionEngine.reset``)."""
        from .runtime import ServingRuntime
        ServingRuntime.reset_engines(self)

    # -------------------------------------------------------- fault report
    def _attach_fault_keys(self, out: Dict, frames, lost, restarts,
                           loans, t_rec):
        """Attach the fault-scenario keys: ``faults`` (what happened and
        what the supervision did about it) and ``recovered_coverage``
        (did every stream come back after the dust settled)."""
        out["faults"] = {
            "n_events": len(self.faults) if self.faults is not None else 0,
            "frames_lost_shard": len(lost),
            "restarts": restarts,
            "loans": loans,
        }
        out["recovered_coverage"] = self._recovered_coverage(
            out, frames, t_rec)

    @staticmethod
    def _recovered_coverage(out: Dict, frames, t_rec) -> float:
        """Minimum per-stream coverage over frames arriving at or after
        ``t_rec`` (the first epoch boundary after the last fault or
        recovery action).  1.0 = every stream fully served once the
        system settled; 0.0 = some stream never came back.  ``None``
        (no fault ever fired) reads 1.0 by definition."""
        if t_rec is None:
            return 1.0
        total: Dict[int, int] = {}
        by_rid: Dict[int, FrameRequest] = {}
        for f in frames:
            by_rid[f.rid] = f
            if f.t_arrival >= t_rec:
                total[f.stream_id] = total.get(f.stream_id, 0) + 1
        if not total:
            return 1.0            # the trace ended before recovery did
        got: Dict[int, int] = {}
        for r in out["responses"]:
            f = by_rid.get(r.rid)
            if f is not None and f.t_arrival >= t_rec:
                got[f.stream_id] = got.get(f.stream_id, 0) + 1
        return min(got.get(sid, 0) / n for sid, n in sorted(total.items()))
