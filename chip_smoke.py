#!/usr/bin/env python3
"""Chip smoke test: the tracked multi-camera detection path, once, on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

One process owns the chip and starts no other.  A phase that fails
raises, nothing is caught, and the script exits non-zero without a
result line.  Only when every phase passed is the last line of standard
output the JSON object ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``; every other report comes before it.

Phases with no arguments:

1. device check -- ``jax.devices()[0].platform`` must be ``tpu``; there
   is no CPU fallback.
2. served trace -- ``DetectionEngine(track_and_interpolate=True,
   fused_tick=True)`` with the mini-SSD at ``SSDConfig()`` widths
   (64 px, channels (16, 32, 64, 64), 160 anchors; random weights from
   ``--seed``) serves 8 cameras x 32 frames of ``make_nvr_streams``
   rendered by ``SyntheticVideo.pixels``: detect, decode, fused NMS,
   associate, Kalman, emit.  Coverage must be 1.0 and the recorded
   trace must pass ``obs.audit``.
3. reference check -- the served detect program's outputs for a few
   micro-batches against ``decode_detections`` run in float32 on the
   host CPU at ``jax.default_matmul_precision("highest")``.  The
   detector's convolutions ask for ``Precision.HIGHEST``, so the chip
   computes them in float32 too; what is left is summation order.
   ``valid`` and ``classes`` must agree exactly, boxes (normalized image
   units) and scores within ``ATOL``.  Detections are matched as sets
   per frame, since two near-equal scores may trade output slots.
4. kernels on the chip -- the Pallas NMS, association, crop and uncrop
   kernels, compiled by Mosaic at the served shapes, against their XLA
   twins and the ``kernels.ref`` oracles, equal as the CPU tests
   require.

With ``--chips 4``: the device check, then ``ShardedDetectionEngine``
on ``make_serving_mesh(4)`` over the same trace against the one-shard
``DetectionEngine``: frames fresh in both runs must make the same
detections within ``ATOL``, and the SPMD program's outputs must sit on
four distinct devices.

Wall times printed here are smoke figures, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
# tolerance of the chip-vs-reference comparisons (normalized box units
# and sigmoid scores): 1e-4 of a 64 px frame is 0.0064 px
ATOL = 1e-4
SCORE_THR, IOU_THR, MAX_OUT = 0.4, 0.5, 32
# the served trace: 8 cameras x 32 frames at ETH-Sunnyday's 14 fps
CAMERAS, FRAMES, RATE = 8, 32, 14.0


def log(msg: str):
    print(msg, flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events.  A compile served from the cache is
    counted with its (short) retrieval time."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def match_detections(a, b, label: str):
    """Compare two ``(boxes, scores, classes, valid)`` batches frame by
    frame.  The valid counts must be equal and every valid detection of
    ``a`` must pair with a distinct valid detection of ``b`` of the same
    class, box and score within ``ATOL``.  Returns the largest
    difference seen and how many detections sat in another slot."""
    import numpy as np
    boxes_a, scores_a, cls_a, valid_a = (np.asarray(x) for x in a)
    boxes_b, scores_b, cls_b, valid_b = (np.asarray(x) for x in b)
    worst, moved = 0.0, 0
    for f in range(valid_a.shape[0]):
        ia = np.flatnonzero(valid_a[f])
        free = list(np.flatnonzero(valid_b[f]))
        if len(ia) != len(free):
            raise AssertionError(f"{label}: frame {f} has {len(ia)} valid "
                                 f"detections vs {len(free)}")
        for i in ia:
            diff = {j: max(np.abs(boxes_a[f, i] - boxes_b[f, j]).max(),
                           abs(scores_a[f, i] - scores_b[f, j]))
                    for j in free if cls_a[f, i] == cls_b[f, j]}
            j = min(diff, key=diff.get, default=None)
            if j is None or diff[j] > ATOL:
                raise AssertionError(
                    f"{label}: frame {f} slot {i} (class {cls_a[f, i]}) "
                    f"has no partner within {ATOL} "
                    f"(closest {None if j is None else diff[j]})")
            worst = max(worst, float(diff[j]))
            moved += int(j != i)
            free.remove(j)
    return worst, moved


def make_trace(size: int):
    """The NVR trace with real rendered frames: camera ``s`` shows the
    benchmark scene from frame ``s * FRAMES`` on."""
    from repro.serving import make_nvr_streams
    trace, frame_of, videos, _ = make_nvr_streams(CAMERAS, FRAMES, RATE)
    for f in trace:
        s, k = frame_of[f.rid]
        f.image = videos[s].pixels(s * FRAMES + k, size)
    return trace


def served_trace(cfg, params, clog):
    from repro.obs import TraceRecorder, audit_recorder
    from repro.serving import DetectionEngine
    trace = make_trace(cfg.image_size)
    rec = TraceRecorder()
    eng = DetectionEngine(cfg=cfg, params=params, max_micro_batch=8,
                          score_thr=SCORE_THR, iou_thr=IOU_THR,
                          max_out=MAX_OUT, track_and_interpolate=True,
                          fused_tick=True, recorder=rec)
    c0, t0 = clog.seconds, time.perf_counter()
    out = eng.serve(trace)
    cold_wall, cold_compile = time.perf_counter() - t0, clog.seconds - c0
    audit = audit_recorder(rec)
    c0, t0 = clog.seconds, time.perf_counter()
    again = eng.serve(trace)
    warm_wall, warm_compile = time.perf_counter() - t0, clog.seconds - c0

    import numpy as np
    fresh = [r for r in out["responses"] if not r.interpolated]
    n_det = sum(int(np.asarray(r.valid).sum()) for r in fresh)
    log(f"served: frames={len(trace)} cameras={out['n_streams']} "
        f"responses={len(out['responses'])} "
        f"interpolated={out['interpolated']} detections={n_det} "
        f"coverage={out['coverage']} tracker_launches="
        f"{out['tracker_launches']} ticks={out['tracker_ticks']}")
    log(f"served: cold wall_s={cold_wall} compile_s={cold_compile} | "
        f"warm wall_s={warm_wall} compile_s={warm_compile} "
        f"(smoke timings, not a benchmark)")
    log(f"served: trace events={len(rec.events)} audit_ok={audit.ok}")
    if out["coverage"] != 1.0 or again["coverage"] != 1.0:
        raise AssertionError(f"coverage {out['coverage']} / "
                             f"{again['coverage']}, expected 1.0")
    if not audit.ok:
        raise AssertionError(f"trace audit failed: {audit.violations[:5]}")
    if out["tracker_launches"] != out["tracker_ticks"]:
        raise AssertionError("fused tick: expected one launch per tick")
    if n_det == 0:
        raise AssertionError("the detector made no detection at all")
    for r in out["responses"]:
        v = np.asarray(r.valid, bool)
        if not (np.isfinite(r.boxes[v]).all()
                and np.isfinite(r.scores[v]).all()):
            raise AssertionError(f"rid {r.rid}: non-finite detections")
    return eng, trace


def reference_check(eng, trace, cfg, n_batches: int = 3):
    import jax
    import numpy as np
    from repro.detector import decode_detections
    cpu = jax.devices("cpu")[0]
    params = jax.device_put(eng.params, cpu)
    anchors = jax.device_put(eng.anchors, cpu)
    ref_fn = jax.jit(lambda p, a, x: decode_detections(
        p, cfg, x, a, score_thr=SCORE_THR, iou_thr=IOU_THR,
        max_out=MAX_OUT, use_pallas=False))
    images = np.stack([f.image for f in trace[:8 * n_batches]])
    worst, moved, n_valid = 0.0, 0, 0
    for i in range(n_batches):
        x = images[8 * i:8 * (i + 1)]
        chip = jax.block_until_ready(eng._infer(x))
        if chip[0].devices() != {jax.devices()[0]}:
            raise AssertionError(f"served program ran on {chip[0].devices()}")
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(params, anchors, jax.device_put(x, cpu))
        w, m = match_detections(chip, ref, f"reference batch {i}")
        worst, moved = max(worst, w), moved + m
        n_valid += int(np.asarray(ref[3]).sum())
    log(f"reference: batches={n_batches} valid={n_valid} "
        f"max_abs_diff={worst} atol={ATOL} moved_slots={moved} "
        f"(cpu float32, matmul precision highest)")


def kernel_check(seed: int):
    """Mosaic-compiled kernels, never interpreted, against their twins."""
    import jax
    from repro.kernels import ops
    if ops._interpret():
        raise AssertionError("Pallas would run interpreted on this backend")
    boxes, scores = nms_inputs(seed)
    hlo = jax.jit(lambda b, s: ops.batched_nms(
        b, s, iou_thr=IOU_THR, max_out=MAX_OUT, use_pallas=True)).lower(
        boxes, scores).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("the NMS program holds no Mosaic kernel")
    results = compare_kernels(seed)
    log("kernels: " + " ".join(f"{k}={v}" for k, v in results.items()))
    failed = [k for k, v in results.items() if not v]
    if failed:
        raise AssertionError(f"kernel checks failed: {failed}")


def nms_inputs(seed: int):
    """A (8, 160) micro-batch of random boxes and scores (the served
    NMS shape)."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    tl = rng.uniform(0, 1, (8, 160, 2))
    boxes = np.concatenate([tl, tl + rng.uniform(0.01, 0.35, (8, 160, 2))],
                           -1)
    return (jnp.asarray(boxes, jnp.float32),
            jnp.asarray(rng.random((8, 160)), jnp.float32))


def compare_kernels(seed: int) -> dict:
    """Each frame-path Pallas kernel (through ``kernels.ops``, which
    picks Mosaic or the interpreter from the platform) against its XLA
    twin and its ``kernels.ref`` oracle: ``{check: passed}``."""
    import numpy as np
    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed + 1)
    results = {}

    def same(name, got, want):
        got = [np.asarray(g) for g in got]
        want = [np.asarray(w) for w in want]
        results[name] = all(g.shape == w.shape and np.array_equal(g, w)
                            for g, w in zip(got, want))

    # NMS at the served shape: a (8, 160) micro-batch, max_out 32
    boxes, scores = nms_inputs(seed)
    got = ops.batched_nms(boxes, scores, iou_thr=IOU_THR, max_out=MAX_OUT,
                          use_pallas=True)
    same("nms_vs_xla", got, ops.batched_nms(
        boxes, scores, iou_thr=IOU_THR, max_out=MAX_OUT, use_pallas=False))
    same("nms_vs_ref", got, ref.batched_nms_ref(boxes, scores, IOU_THR,
                                                MAX_OUT))
    # the detector's configuration: score threshold + zero-score exit
    kw = dict(iou_thr=IOU_THR, score_thr=SCORE_THR, max_out=MAX_OUT,
              stop_at_zero=True)
    kp, vp = ops.batched_nms(boxes, scores, use_pallas=True, **kw)
    kx, vx = ops.batched_nms(boxes, scores, use_pallas=False, **kw)
    same("nms_thresholded_vs_xla", (vp, np.where(vp, kp, 0)),
         (vx, np.where(vx, kx, 0)))

    # association at the tracker's shape: B=8 streams, T=64, D=32
    B, T, D = 8, 64, 32
    t_tl = rng.uniform(0, 600, (B, T, 2))
    t_boxes = np.concatenate([t_tl, t_tl + rng.uniform(10, 80, (B, T, 2))],
                             -1).astype(np.float32)
    pick = rng.integers(0, T, (B, D))
    d_boxes = (np.take_along_axis(t_boxes, pick[..., None], 1)
               + rng.normal(0, 4, (B, D, 4))).astype(np.float32)
    t_mask, d_mask = rng.random((B, T)) < 0.7, rng.random((B, D)) < 0.8
    t_cls, d_cls = rng.integers(0, 3, (B, T)), rng.integers(0, 3, (B, D))
    akw = dict(t_mask=t_mask, d_mask=d_mask, t_cls=t_cls, d_cls=d_cls,
               iou_thr=0.3)
    match = ops.greedy_assign(t_boxes, d_boxes, use_pallas=True, **akw)
    same("assoc_vs_xla", [match], [ops.greedy_assign(
        t_boxes, d_boxes, use_pallas=False, **akw)])
    same("assoc_vs_ref", [match], [ref.greedy_assign_ref(
        t_boxes, d_boxes, t_mask, d_mask, t_cls, d_cls, iou_thr=0.3)])
    results["assoc_matches"] = int((np.asarray(match) >= 0).sum()) > 0

    # ROI crop + uncrop at the second pass's shape: 8 frames of 64 px,
    # 4 windows each, 64 px crops
    images = rng.random((8, 64, 64, 3)).astype(np.float32)
    lo = rng.uniform(0, 0.6, (8, 4, 2))
    rois = np.concatenate([lo, np.minimum(
        lo + rng.uniform(0.05, 0.5, (8, 4, 2)), 1.0)], -1).astype(np.float32)
    crops = ops.crop_resize(images, rois, out_size=64, use_pallas=True)
    same("crop_vs_xla", [crops], [ops.crop_resize(images, rois, out_size=64,
                                                  use_pallas=False)])
    same("crop_vs_ref", [crops], [ref.crop_resize_ref(images, rois,
                                                      out_size=64)])
    cboxes = rng.uniform(0, 64, (8, 4, 32, 4)).astype(np.float32)
    ukw = dict(bounds=(640, 480), crop_size=64)
    same("uncrop_vs_xla",
         [ops.uncrop_boxes(cboxes, rois[:, :, None], use_pallas=True, **ukw)],
         [ops.uncrop_boxes(cboxes, rois[:, :, None], use_pallas=False,
                           **ukw)])
    return results


def sharded_check(cfg, params, clog):
    import jax
    import numpy as np
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import DetectionEngine, ShardedDetectionEngine
    trace = make_trace(cfg.image_size)
    # a pinned service time makes the virtual schedule -- which frames
    # are detected and which interpolated -- independent of wall time
    kw = dict(cfg=cfg, params=params, n_replicas=2, max_micro_batch=8,
              service_time=0.005, score_thr=SCORE_THR, iou_thr=IOU_THR,
              max_out=MAX_OUT, track_and_interpolate=True, fused_tick=True)
    mesh = make_serving_mesh(4)
    sharded = ShardedDetectionEngine(n_shards=4, mesh=mesh, **kw)
    t0 = time.perf_counter()
    out = sharded.serve(trace)
    wall = time.perf_counter() - t0
    base = DetectionEngine(**kw).serve(trace)
    if [r.rid for r in out["responses"]] != \
            [r.rid for r in base["responses"]]:
        raise AssertionError("sharded and one-shard runs answer other rids")
    fresh = [(a, b) for a, b in zip(out["responses"], base["responses"])
             if not (a.interpolated or b.interpolated)]
    log(f"sharded: shards={out['n_shards']} frames={len(trace)} "
        f"coverage={out['coverage']} base_coverage={base['coverage']} "
        f"fresh_in_both={len(fresh)} wall_s={wall} (smoke) "
        f"compile_s_total={clog.seconds}")
    if out["coverage"] != 1.0 or base["coverage"] != 1.0:
        raise AssertionError("coverage below 1.0")
    if not fresh:
        raise AssertionError("no frame was detected by both runs")
    stack = lambda rs: tuple(np.stack([getattr(r, k) for r in rs])
                             for k in ("boxes", "scores", "classes",
                                       "valid"))
    worst, moved = match_detections(stack([a for a, _ in fresh]),
                                     stack([b for _, b in fresh]),
                                     "sharded vs one shard")
    # the SPMD program itself: one 8-frame micro-batch split 2 per chip
    x = np.stack([f.image for f in trace[:8]])
    spmd = sharded._shared_detect(x)
    devices = {s.device for s in spmd[0].addressable_shards}
    if len(devices) != 4 or spmd[0].sharding.is_fully_replicated:
        raise AssertionError(f"SPMD outputs on {devices}, replicated="
                             f"{spmd[0].sharding.is_fully_replicated}")
    one = DetectionEngine(**kw)
    w2, m2 = match_detections(spmd, one._infer(x), "SPMD batch")
    log(f"sharded: max_abs_diff={max(worst, w2)} atol={ATOL} "
        f"moved_slots={moved + m2} output_devices={len(devices)} "
        f"per_shard_streams={[s['streams'] for s in out['per_shard']]}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-shard mesh phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the detector weights and kernel inputs")
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        raise SystemExit(f"{SRC / 'repro'} not found: run chip_smoke.py "
                         "from a checkout of the repository")
    sys.path.insert(0, str(SRC))
    import jax

    dev = jax.devices()[0]
    count = len(jax.devices())
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={count}")
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform} devices")
    if count < args.chips:
        raise SystemExit(f"--chips {args.chips} but {count} devices")

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    clog = CompileLog(jax)
    from repro.detector import SSDConfig, init_ssd
    cfg = SSDConfig()
    params = init_ssd(cfg, jax.random.PRNGKey(args.seed))

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_check(cfg, params, clog)
    else:
        eng, trace = served_trace(cfg, params, clog)
        reference_check(eng, trace, cfg)
        kernel_check(args.seed)
    log(f"total: wall_s={time.perf_counter() - t0} "
        f"compile_s={clog.seconds} cache_hits={clog.hits} "
        f"cache_misses={clog.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
