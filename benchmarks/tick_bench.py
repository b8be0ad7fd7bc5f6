"""Fused serve-tick benchmark: the one-jit donated-buffer tracker tick
(``serving.pipeline._fused_tick``) and the one-launch-per-window scan
(``serving.pipeline.fused_window``) vs the staged ``step`` + ``output``
launch chain, with bit-identity asserted and the >= 1.2x speedup gate
on ``tracker_step_ms`` enforced.

  PYTHONPATH=src python benchmarks/tick_bench.py [--smoke] [--out PATH]

Emits ``BENCH_tick.json`` with

* ``staged``       — per-tick latency of the pre-refactor two-dispatch
  chain (``trk.step``, det_tid sync, ``trk.output``, outputs
  materialized — the interpolation replay's drop-bearing tick);
* ``fused``        — the ONE-launch-per-tick program (associate ->
  Kalman update/birth -> output, track table donated), same
  materialization;
* ``fused_window`` — the whole K-tick window as ONE ``lax.scan``
  launch (the replay knows every tick's detections up front), stacked
  det_tid/outputs materialized once at the end.  This is the regime
  the >= 1.2x gate runs against: it amortizes the entire dispatch
  chain, so the margin is structural, not timer jitter;
* ``identity``     — all three regimes replayed over the same K random
  detection ticks (including detection-free ticks, which the fused
  programs run as all-invalid rows): every ``TrackerState`` field,
  ``det_tid`` and the output tuple must match bit for bit;
* ``roofline``     — the fused tick program's ``cost_analysis``
  FLOPs/bytes against the v5e-class peaks from
  ``benchmarks/roofline.py``: the compute/memory bounds in ms, the
  bound-side verdict, and the measured-over-bound ratio (on XLA-CPU
  the measured time is dispatch-dominated — exactly the overhead
  fusion removes).

Timing method: staged / fused / window reps are interleaved tick by
tick (shared-runner drift hits every regime equally) and the per-tick
MINIMUM across reps is summed — noise only ever adds time, so the sum
of per-tick floors is the stable latency estimate.

Acceptance (CI-gated): ``fused_bit_identical`` and
``fused_speedup_ge_1_2`` (staged vs ``fused_window``) must both be
true; the process exits nonzero otherwise.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.pipeline import _fused_tick, fused_window
from repro.tracking import (TrackerConfig, coast, export_rows, init_state,
                            output, rows_to_state, step)

try:
    from benchmarks.roofline import HBM_BW, PEAK_FLOPS
except ImportError:   # standalone run: benchmarks/ itself is on sys.path
    from roofline import HBM_BW, PEAK_FLOPS


def make_ticks(rng, B, D, K):
    """K random detection ticks; every 5th is detection-free (the
    interpolation path's coast tick — fused runs it as an all-invalid
    row)."""
    ticks = []
    for k in range(K):
        if k % 5 == 4:
            ticks.append((jnp.zeros((B, D, 4), jnp.float32),
                          jnp.zeros((B, D), jnp.float32),
                          jnp.zeros((B, D), jnp.int32),
                          jnp.zeros((B, D), bool)))
            continue
        tl = rng.uniform(0, 400, (B, D, 2))
        wh = rng.uniform(10, 60, (B, D, 2))
        ticks.append((
            jnp.asarray(np.concatenate([tl, tl + wh], -1), jnp.float32),
            jnp.asarray(rng.uniform(0.5, 1.0, (B, D)), jnp.float32),
            jnp.asarray(rng.integers(0, 3, (B, D)), jnp.int32),
            jnp.asarray(rng.random((B, D)) > 0.2)))
    return ticks


def warm_rows(cfg, ticks, B):
    """Portable rows of a table warmed over the first ticks — each
    timing rep rebuilds fresh buffers from them (the fused programs
    DONATE their input state; reps must never share buffers)."""
    state = init_state(B, cfg)
    for t in ticks[:3]:
        state, _ = step(state, *t, cfg)
    return export_rows(state)


def time_regimes(cfg, rows, ticks, reps):
    """Interleaved per-tick-min timing of the three regimes.  Each rep
    threads fresh states (donation safety) through the same K ticks;
    staged and fused alternate within every tick so runner drift is
    shared, and the window launch is timed around the same rep.
    Returns per-tick ms floors ``(staged, fused, window)``."""
    K = len(ticks)
    stacked = tuple(jnp.stack([t[i] for t in ticks]) for i in range(4))
    smin = [float("inf")] * K
    fmin = [float("inf")] * K
    wmin = float("inf")
    for r in range(reps + 1):          # rep 0 warms the compile caches
        s_st = rows_to_state(rows, cfg)
        s_fu = rows_to_state(rows, cfg)
        s_wd = rows_to_state(rows, cfg)
        jax.block_until_ready((s_st, s_fu, s_wd))
        for k, t in enumerate(ticks):
            t0 = time.perf_counter()
            s_st, tid = step(s_st, *t, cfg)
            tid = np.asarray(tid)                  # per-tick det_tid sync
            out = tuple(np.asarray(a) for a in output(s_st, cfg))
            t1 = time.perf_counter()
            s_fu, tid, out = _fused_tick(s_fu, *t, cfg, False)
            tid = np.asarray(tid)
            out = tuple(np.asarray(a) for a in out)
            t2 = time.perf_counter()
            if r:
                smin[k] = min(smin[k], t1 - t0)
                fmin[k] = min(fmin[k], t2 - t1)
        t0 = time.perf_counter()
        s_wd, wtid, wout = fused_window(s_wd, *stacked, cfg)
        wtid = np.asarray(wtid)
        wout = tuple(np.asarray(a) for a in wout)
        t1 = time.perf_counter()
        if r:
            wmin = min(wmin, (t1 - t0) / K)
    return (sum(smin) / K * 1e3, sum(fmin) / K * 1e3, wmin * 1e3)


def check_identity(cfg, rows, ticks):
    """Replay all three regimes over the same ticks: every state field,
    the det_tid assignment and the output tuple must match bit for bit,
    and a detection-free fused tick must equal ``coast``."""
    s1 = rows_to_state(rows, cfg)
    s2 = rows_to_state(rows, cfg)
    tids, outs = [], []
    for k, t in enumerate(ticks):
        empty = not bool(np.asarray(t[3]).any())
        if empty:
            s1, tid1 = coast(s1, cfg), None
        else:
            s1, tid1 = step(s1, *t, cfg)
        o1 = output(s1, cfg)
        tids.append(None if tid1 is None else np.asarray(tid1))
        outs.append([np.asarray(a) for a in o1])
        s2, tid2, o2 = _fused_tick(s2, *t, cfg, False)
        if not empty and not np.array_equal(np.asarray(tid1),
                                            np.asarray(tid2)):
            return False
        for a, b in zip(o1, o2):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                return False
        for f in type(s1)._fields:
            if not np.array_equal(np.asarray(getattr(s1, f)),
                                  np.asarray(getattr(s2, f))):
                return False
    stacked = tuple(jnp.stack([t[i] for t in ticks]) for i in range(4))
    s3, wtid, wout = fused_window(rows_to_state(rows, cfg), *stacked, cfg)
    for f in type(s1)._fields:
        if not np.array_equal(np.asarray(getattr(s1, f)),
                              np.asarray(getattr(s3, f))):
            return False
    for k in range(len(ticks)):
        if tids[k] is not None and not np.array_equal(
                np.asarray(wtid)[k], tids[k]):
            return False
        for i, a in enumerate(wout):
            if not np.array_equal(np.asarray(a)[k], outs[k][i]):
                return False
    return True


def roofline_row(cfg, rows, tick, fused_ms):
    """Analytical bound of ONE fused tick vs the measured time."""
    state = rows_to_state(rows, cfg)
    compiled = jax.jit(
        lambda s, b, sc, c, v: _fused_tick(s, b, sc, c, v, cfg, False)
    ).lower(state, *tick).compile()
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    compute_ms = flops / PEAK_FLOPS * 1e3
    memory_ms = byts / HBM_BW * 1e3
    bound_ms = max(compute_ms, memory_ms)
    return {
        "flops": flops, "bytes": byts,
        "compute_ms": compute_ms, "memory_ms": memory_ms,
        "bound": "compute" if compute_ms >= memory_ms else "memory",
        "measured_fused_ms": fused_ms,
        # >> 1 on CPU: the tick is dispatch-overhead-bound, which is
        # the regime where collapsing the launch chain pays
        "measured_over_bound": (fused_ms / bound_ms if bound_ms
                                else float("inf")),
    }


def bench(B, D, K, reps, cfg):
    rng = np.random.default_rng(0)
    ticks = make_ticks(rng, B, D, K)
    rows = warm_rows(cfg, ticks, B)
    staged_ms, fused_ms, window_ms = time_regimes(cfg, rows, ticks, reps)
    return {
        "batch_streams": B, "det_capacity": D,
        "track_capacity": cfg.capacity, "ticks": K,
        "staged": {"launches_per_tick": 2, "tracker_step_ms": staged_ms},
        "fused": {"launches_per_tick": 1, "tracker_step_ms": fused_ms,
                  "speedup_vs_staged": staged_ms / fused_ms},
        "fused_window": {"launches_per_window": 1,
                         "tracker_step_ms": window_ms},
        "speedup": staged_ms / window_ms,
        "bit_identical": check_identity(cfg, rows, ticks),
        "roofline": roofline_row(cfg, rows, ticks[0], fused_ms),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / fewer reps (CI)")
    ap.add_argument("--out", default=str(
        Path(__file__).resolve().parents[1] / "BENCH_tick.json"))
    args = ap.parse_args()

    if args.smoke:
        row = bench(B=2, D=8, K=20, reps=4, cfg=TrackerConfig(capacity=16))
    else:
        row = bench(B=4, D=16, K=40, reps=8, cfg=TrackerConfig(capacity=32))

    out = {
        "bench": "fused_serve_tick",
        "backend": jax.default_backend(),
        "smoke": bool(args.smoke),
        **row,
        "acceptance": {
            "fused_bit_identical": row["bit_identical"],
            "fused_speedup_ge_1_2": row["speedup"] >= 1.2,
        },
    }
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps(out, indent=2))
    if not all(out["acceptance"].values()):
        failed = [k for k, v in out["acceptance"].items() if not v]
        print(f"ACCEPTANCE FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
