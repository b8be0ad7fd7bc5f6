#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips and starts no other.  It exits
non-zero, with no result line, where JAX finds no TPU or fewer chips
than the cell asks for.  Otherwise the last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared for
``correct`` beside its limit.  The same checks are the last lines of
standard error.

A run:

1. makes the detector weights from ``--seed`` on the device (the
   family's ``make_params``) and renders the mix's frame pool;
2. builds the cell's engine -- ``DetectionEngine`` on one chip,
   ``ShardedDetectionEngine`` over a 4-chip serving mesh -- on the
   family's ``program_config``, with the configuration's serving
   keywords and every other option at the program's default, and
   drives it through ``ServingRuntime``;
3. warms up: the detect program at micro-batch buckets 1/2/4/8, then
   about a second of the cell's own traffic (tracker tick at the cell's
   camera count);
4. opens the window and drives it open-loop on the host clock: frames
   are ingested when due, ``advance`` runs what is sealed, and every
   ``emit_period_s`` an ``epoch_boundary`` flushes the frames due in
   the period just ended.  A frame's emit time is the return of that
   boundary; a frame the boundary's report does not account for is
   ``failed``;
5. after the window, ``drain()``; then the peak device memory is read,
   the engine is freed and the plain reference checks a seeded sample
   of what the window served (``compare.py``) against the limits in the
   configuration's file.

Nothing here depends on the detector's architecture.  A configuration
file names its family, and ``bench/families/<family>.py`` gives the
weights, the program's configuration, the plain reference and its
suppression rule, and the operation and byte counts
(``bench/families/__init__.py``); they moved there from this file,
``reference.py`` and ``flops.py`` with their arithmetic unchanged.  The
limits of ``correct`` sit in the configuration's own file, under
``limits``, with the values the shared ``limits.json`` held.  What the harness asks
of the program: ``DetectionEngine(cfg=...)`` builds the detector from
the family's ``program_config``, its detect program is ``_infer(images)``
compiled as ``jit_infer``, and the scopes that program names are what
``Summary.scopes`` counts.

``setup_s`` runs from the start of this script to the window's opening.
With ``--trace 1`` the last seconds of the window are traced by the JAX
profiler, and the per-layer metrics are read by the files in
``bench/metrics/`` (one per metric, found by name).  Once the window has
closed, the detect program's compiled text is read at every micro-batch
bucket (from the in-memory cache: nothing compiles) to name the scopes
of its device ops (``trace_reduce.parse_hlo``).  A reader may leave a
note in ``ctx["notes"]`` (which bound binds a roofline); the result
line carries them under ``notes``.

``--control`` puts the lower-precision control (the reference one
precision step down) in the program's place: ``checks`` and ``correct``
then come from the control's outputs, and the program's own numbers
are reported beside them under ``program``.  The benchmark's own runs
never pass it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import compare, generator, reference  # noqa: E402
from bench import trace_reduce  # noqa: E402
from bench.stats import percentile  # noqa: E402

WARM_SECONDS = 1.0
BUCKETS = (1, 2, 4, 8)
DET_SAMPLE = 64          # detected frames the reference recomputes
TRACK_SAMPLE = 8         # cameras whose whole sequence it replays
TRACE_SECONDS = 3.0      # traced part of the window (--trace 1)
CACHE_DIR = ROOT / ".jax_cache"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration and mix."""

    def __init__(self, name: str, cameras: int | None = None):
        self.bench = load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.w = cells[name]
        self.name = name
        conf = {c["name"]: c for c in self.bench["configs"]}[self.w["config"]]
        self.config = load_json(ROOT / conf["file"])
        self.family = load_module("families", self.config["family"])
        self.family.check(self.config)
        d = load_json(BENCH / "traffic" / f"{self.w['traffic']}.json")
        if cameras is not None:
            d["cameras"] = cameras
        self.mix = generator.Mix.from_dict(self.w["traffic"], d)
        self.chips = int(self.w["chips"])
        self.serve = self.config["serving"]
        self.limits = self.config["limits"]

    def metrics(self, kind: str):
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]


def setup_jax():
    """Before anything compiles: the persistent compilation cache in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), with every
    program cached however fast it compiled.  The engine's detect
    program closes over its weights, which XLA embeds as constants, so
    weights from a new seed make a new program: each run compiles it
    at its four micro-batch buckets and reads the rest from the cache."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def device_check(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")


def build_engine(cell: Cell, params):
    from repro.serving import DetectionEngine, ShardedDetectionEngine
    kw = dict(cfg=cell.family.program_config(cell.config), params=params,
              track_and_interpolate=True, **cell.serve)
    if cell.chips == 1:
        return DetectionEngine(**kw)
    from repro.launch.mesh import make_serving_mesh
    return ShardedDetectionEngine(n_shards=cell.chips,
                                  mesh=make_serving_mesh(cell.chips), **kw)


def warm_buckets(eng, size: int):
    """Compile the detect program at every micro-batch bucket.  On one
    chip the buckets compile side by side in threads: XLA compiles
    without holding the interpreter lock, and a new seed's weights make
    every bucket a new program."""
    if hasattr(eng, "engines"):
        eng.warmup()
        return
    import jax

    def one(b):
        return jax.block_until_ready(
            eng._infer(np.zeros((b, size, size, 3), np.float32)))

    # a program that embeds one seed's weights is never read back, so
    # it is not written to the persistent cache either
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with ThreadPoolExecutor(len(BUCKETS)) as pool:
            for fut in [pool.submit(one, b) for b in BUCKETS]:
                fut.result()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


def runtime(eng, cameras: int):
    from repro.serving import ServingRuntime
    if hasattr(eng, "engines"):
        return ServingRuntime(eng, streams=list(range(cameras)))
    return ServingRuntime(eng)


class Frames:
    """The frames due in ``[0, seconds)``, as requests, in due order."""

    def __init__(self, mix: generator.Mix, seconds: float, pool, offsets):
        from repro.serving import FrameRequest
        self.due, self.stream, self.k = generator.due_times(
            mix.fps, mix.cameras, seconds)
        self.idx = generator.pool_index(offsets, self.stream, self.k,
                                        mix.pool_frames)
        self.reqs = [FrameRequest(rid, pool[i], float(t), stream_id=int(s))
                     for rid, (i, t, s) in enumerate(
                         zip(self.idx, self.due, self.stream))]
        self.segs = generator.split_by_boundary(self.due, mix.emit_period_s)
        for j, sl in enumerate(self.segs):
            if sorted(self.stream[sl]) != list(range(mix.cameras)):
                raise ValueError(f"emit period {mix.emit_period_s} s does "
                                 f"not flush one frame per camera (boundary "
                                 f"{j + 1})")


class Drive:
    """What the open loop saw, on the host clock (seconds since the
    window opened)."""

    def __init__(self, n: int, n_seg: int):
        self.ingest_t = np.zeros(n)
        self.emit_t = np.zeros(n_seg)
        self.emitted = np.zeros(n_seg, np.int64)
        self.detected = np.zeros(n_seg, np.int64)
        self.failed = np.zeros(n_seg, np.int64)
        self.ingest_advance_s = 0.0
        self.boundary_s = 0.0
        self.trace_from = None


def drive(rt, fr: Frames, period: float, *, trace_dir=None,
          trace_from=float("inf")) -> Drive:
    """The open loop over ``fr``: ingest when due, advance, and flush at
    every emit boundary."""
    import jax
    span = (jax.profiler.TraceAnnotation if trace_dir is not None
            else lambda name: contextlib.nullcontext())
    due, reqs, segs = fr.due, fr.reqs, fr.segs
    n = len(reqs)
    d = Drive(n, len(segs))
    clock = time.perf_counter
    window = None
    i = j = 0
    t0 = clock()
    while j < len(segs):
        now = clock() - t0
        if (trace_dir is not None and d.trace_from is None
                and now >= trace_from):
            jax.profiler.start_trace(trace_dir)
            window = span(trace_reduce.WINDOW_SPAN)
            window.__enter__()
            d.trace_from = clock() - t0
        if window is not None and now >= d.trace_from + TRACE_SECONDS:
            stop_trace(window)
            window = None
        tb = (j + 1) * period
        if now >= tb:
            hi = segs[j].stop
            a = clock()
            if i < hi:
                d.ingest_t[i:hi] = a - t0
                with span("bench.ingest"):
                    rt.ingest(reqs[i:hi])
                i = hi
            with span("bench.advance"):
                rt.advance(tb)
            b = clock()
            with span("bench.boundary"):
                rep = rt.epoch_boundary()
            c = clock()
            d.ingest_advance_s += b - a
            d.boundary_s += c - b
            account(d, j, rep, reqs[segs[j]])
            d.emit_t[j] = c - t0
            j += 1
            continue
        hi = int(np.searchsorted(due, now, side="right"))
        if hi > i:
            a = clock()
            d.ingest_t[i:hi] = a - t0
            with span("bench.ingest"):
                rt.ingest(reqs[i:hi])
            with span("bench.advance"):
                rt.advance(now)
            d.ingest_advance_s += clock() - a
            i = hi
        nxt = min(due[i] if i < n else float("inf"), tb)
        wait = nxt - (clock() - t0)
        if wait > 0:
            with span("bench.wait"):
                time.sleep(wait)
    if window is not None:
        stop_trace(window)
    return d


def stop_trace(window):
    import jax
    window.__exit__(None, None, None)
    jax.profiler.stop_trace()


def account(d: Drive, j: int, rep, seg_reqs):
    """Check one boundary's report against the frames it flushed."""
    resp = rep["responses"]
    if isinstance(resp, list):
        got = {r.rid for r in resp}
        d.failed[j] = sum(f.rid not in got for f in seg_reqs)
        n_resp = len(resp)
    else:
        n_resp = int(resp)
        d.failed[j] = max(len(seg_reqs) - n_resp, 0)
    d.emitted[j] = len(seg_reqs) - d.failed[j]
    d.detected[j] = n_resp - int(rep["interpolated"])


class CompileCount:
    """Backend compiles from construction to ``stop()`` (JAX's
    monitoring events)."""

    def __init__(self, jax):
        self.n = 0
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def stop(self):
        self.on = False


def final_tracks(eng) -> dict:
    """Camera -> the track row the engine exported at its last emit
    boundary (its portable track state)."""
    out = {}
    for e in getattr(eng, "engines", [eng]):
        out.update(e._exported_tracks)
    return out


def warm_tracker(eng, cameras: int):
    """Compile the tracker's coast and output programs at the cell's
    width: only a dropped frame needs them, and the warm-up second may
    drop none."""
    import jax
    from repro import tracking
    from repro.sharding.serving_rules import shard_streams
    engines = getattr(eng, "engines", [eng])
    shard_of = shard_streams(range(cameras), len(engines))
    for h, e in enumerate(engines):
        width = sum(v == h for v in shard_of.values())
        st = tracking.init_state(width, e.tracker_cfg)
        jax.block_until_ready(tracking.output(
            tracking.coast(st, e.tracker_cfg), e.tracker_cfg))


def peak_memory(jax, chips: int) -> int:
    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def reference_check(cell: Cell, seed: int, responses, finals, fr: Frames,
                    pool, control: bool):
    """The numbers of ``compare.py`` for what the window served, and the
    count of detections the reference tracker associated; with
    ``control`` also the numbers of the lower-precision control, its
    outputs in the program's place."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 12]))
    fam, cfg, serve = cell.family, cell.config, cell.serve
    n_win = len(fr.reqs)
    served = [r for r in responses if r.rid < n_win]
    fresh = [r for r in served if not r.interpolated]
    pick = sorted(rng.choice(len(fresh), min(DET_SAMPLE, len(fresh)),
                             replace=False)) if fresh else []
    frames = [fresh[i] for i in pick]
    params = fam.make_params(cfg, seed)
    images = np.stack([pool[fr.idx[r.rid]] for r in frames]) if frames \
        else pool[:0]
    cands = fam.candidates(cfg, params, images, "highest")
    nums = compare.detector_numbers(
        [(r.boxes, r.scores, r.classes, r.valid) for r in frames], cands,
        serve, fam.survivors)
    cams = sorted(rng.choice(cell.mix.cameras,
                             min(TRACK_SAMPLE, cell.mix.cameras),
                             replace=False).tolist())
    streams = {s: sorted((r for r in served if r.stream_id == s),
                         key=lambda r: r.rid) for s in cams}
    prm = reference.TrackerParams()
    trk_nums, matched = compare.tracker_numbers(
        streams, {s: finals[s] for s in cams if s in finals}, prm)
    nums.update(trk_nums)
    if not control:
        return nums, None, matched
    ctl_served = []
    m = serve["max_out"]
    for cand in fam.candidates(cfg, params, images, "high"):
        rows = fam.survivors(cand, serve)
        k = len(rows.anchor)
        bx = np.zeros((m, 4))
        sc = np.zeros(m)
        cl = np.zeros(m, np.int64)
        va = np.zeros(m, bool)
        bx[:k], sc[:k] = rows.boxes, rows.scores
        cl[:k], va[:k] = rows.cls, True
        ctl_served.append((bx, sc, cl, va))
    ctl = compare.detector_numbers(ctl_served, cands, serve, fam.survivors)
    import ml_dtypes
    ctl.update(compare.tracker_numbers(
        *compare.replay_tracker(streams, prm, ml_dtypes.bfloat16), prm)[0])
    return nums, ctl, matched


def detect_hlo(eng, size: int):
    """The detect program's compiled text at every micro-batch bucket,
    read once the window has closed: the buckets were compiled in
    set-up, so this only looks them up.  The mesh path's program is not
    reachable from here (no texts: no scopes)."""
    if not hasattr(eng, "_infer"):
        return []
    return [eng._infer.lower(np.zeros((b, size, size, 3), np.float32))
            .compile().as_text() for b in BUCKETS]


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded by its path: a family or a
    metric reader that a later change adds needs no edit here."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, ctx: dict):
    return load_module("metrics", name).read(ctx)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             cameras: int | None = None, control: bool = False,
             check_device: bool = True, log=print) -> dict:
    jax = setup_jax()
    cell = Cell(name, cameras)
    if check_device:
        device_check(jax, cell.chips)
    mix, fam = cell.mix, cell.family
    size = fam.image_size(cell.config)
    dev = jax.devices()[0]
    params = fam.make_params(cell.config, seed)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    pool = generator.render_pool(mix, size)
    offsets = generator.camera_offsets(rng, mix.cameras, mix.pool_frames)
    eng = build_engine(cell, params)
    warm_buckets(eng, size)
    warm = Frames(mix, WARM_SECONDS, pool, offsets)
    rt = runtime(eng, mix.cameras)
    drive(rt, warm, mix.emit_period_s)
    rt.drain()
    warm_tracker(eng, mix.cameras)
    fr = Frames(mix, seconds, pool, offsets)
    rt = runtime(eng, mix.cameras)
    # what set-up made stays: keep the collector off it in the window
    gc.collect()
    gc.freeze()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles = CompileCount(jax)
    setup_s = time.perf_counter() - T_PROCESS
    d = drive(rt, fr, mix.emit_period_s, trace_dir=trace_dir,
              trace_from=max(seconds - TRACE_SECONDS, 0.0))
    compiles.stop()
    gc.unfreeze()
    rep = rt.drain()
    mem = peak_memory(jax, cell.chips)
    responses = rep["responses"]
    finals = final_tracks(eng)
    hlo = detect_hlo(eng, size) if trace else []
    del rt, eng, rep
    gc.collect()

    seg_of = np.repeat(np.arange(len(fr.segs)),
                       [s.stop - s.start for s in fr.segs])
    lat_ms = (d.emit_t[seg_of] - fr.due) * 1e3
    emitted_ok = d.failed[seg_of] == 0
    span_s = max(seconds, float(d.emit_t[-1]))
    detected = int(d.detected.sum())
    ctx = {
        "cell": cell.w, "config": cell.config, "family": fam, "mix": mix,
        "chips": cell.chips,
        "seconds": seconds, "span_s": span_s,
        "frames": len(fr.reqs), "detected": detected,
        "emitted": int(d.emitted.sum()),
        "detected_fps": detected / span_s,
        "emit_ms": lat_ms[emitted_ok],
        "lateness_ms": (d.ingest_t - fr.due) * 1e3,
        "ingest_advance_s": d.ingest_advance_s,
        "boundary_s": d.boundary_s,
        "flops_per_frame": fam.flops_per_frame(cell.config),
        "peaks": load_json(BENCH / "peaks.json").get(dev.device_kind),
        "device_kind": dev.device_kind,
        "setup_s": setup_s,
        "trace": None,
        "notes": {},
    }
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result_extra = {}
    if trace:
        summ = trace_reduce.summarize(trace_reduce.load(trace_dir),
                                      load_json(BENCH / "layers.json"), hlo)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = summ
        t_lo = d.trace_from
        t_hi = t_lo + summ.window_s
        segs_in = (d.emit_t >= t_lo) & (d.emit_t < t_hi)
        ctx["trace_detected"] = int(d.detected[segs_in].sum())
        ctx["trace_boundaries"] = int(segs_in.sum())
        device["busy_s"] = summ.busy_s
        device["window_s"] = summ.window_s
        result_extra["breakdown"] = {"device_ops": summ.device_ops,
                                     "idle_gaps": summ.idle_gaps}
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(kind):
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    nums, ctl, matched = reference_check(cell, seed, responses, finals, fr,
                                         pool, control)
    failed = int(d.failed.sum())
    checks = {"failed": {"value": failed, "limit": 0}}
    for k, v in (nums if ctl is None else ctl).items():
        checks[k] = {"value": v, "limit": cell.limits.get(k)}
    correct = all(c["limit"] is None or c["value"] <= c["limit"]
                  for c in checks.values()) and detected > 0
    out = {"correct": bool(correct), "attempted": len(fr.reqs),
           "failed": failed, "metrics": metrics, "device": device,
           **result_extra}
    thirds = np.array_split(np.flatnonzero(emitted_ok), 3)
    out["window"] = {
        "cameras": mix.cameras, "offered_fps": mix.cameras * mix.fps,
        "detected": detected, "interpolated": int(d.emitted.sum()) - detected,
        "span_s": span_s, "compiles_in_window": compiles.n,
        "track_matches": matched,
        "emit_p50_first_third_ms": percentile(lat_ms[thirds[0]], 50),
        "emit_p50_last_third_ms": percentile(lat_ms[thirds[-1]], 50),
    }
    if ctx["notes"]:
        out["notes"] = ctx["notes"]
    if ctl is not None:
        out["program"] = nums
    out["checks"] = checks
    log(f"compiles inside the window: {compiles.n}")
    for k, v in ctx["notes"].items():
        log(f"note {k}: {v}")
    for k, c in checks.items():
        log(f"check {k}: {c['value']} limit {c['limit']}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   control=args.control,
                   log=lambda s: print(s, file=sys.stderr, flush=True))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
