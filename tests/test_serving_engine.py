"""Serving-engine behaviour: real compute + the paper's scheduling
semantics over model replicas — token requests and micro-batched video
frames."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import (DetectionEngine, FrameRequest, Request,
                           ServingEngine)


def burst(cfg, n, rate, seed=0, new_tokens=3):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size - 1, 8)
                    .astype(np.int32), new_tokens, i / rate)
            for i in range(n)]


@pytest.fixture(scope="module")
def cfg():
    return get_config("minicpm-2b", preset="smoke")


def test_serving_responses_in_arrival_order(cfg):
    eng = ServingEngine(cfg, n_replicas=3, scheduler="fcfs", cache_len=32)
    out = eng.serve(burst(cfg, 9, rate=200.0))
    assert [r.rid for r in out["responses"]] == list(range(9))
    assert len(out["dropped"]) == 0
    assert all(len(r.tokens) == 3 for r in out["responses"])


def test_serving_deterministic_tokens_across_schedulers(cfg):
    """The scheduler decides placement/time, never the model output."""
    outs = {}
    for sched in ("fcfs", "rr"):
        eng = ServingEngine(cfg, n_replicas=2, scheduler=sched,
                            cache_len=32)
        outs[sched] = eng.serve(burst(cfg, 6, rate=100.0))
    for a, b in zip(outs["fcfs"]["responses"], outs["rr"]["responses"]):
        assert np.array_equal(a.tokens, b.tokens)


def test_replica_scaling_increases_throughput(cfg):
    rates = {}
    for n in (1, 4):
        eng = ServingEngine(cfg, n_replicas=n, scheduler="fcfs",
                            cache_len=32)
        rates[n] = eng.serve(burst(cfg, 12, rate=1e4))["throughput_rps"]
    assert rates[4] > 2.0 * rates[1]


def test_serve_empty_request_list(cfg):
    """Regression: ``serve([])`` used to crash in ``warmup`` on
    ``max()`` over an empty sequence; it must return an empty report,
    mirroring ``DetectionEngine``."""
    eng = ServingEngine(cfg, n_replicas=2, scheduler="fcfs", cache_len=32)
    out = eng.serve([])
    assert out["responses"] == [] and out["dropped"] == []
    assert out["throughput_rps"] == 0.0 and out["p50_latency"] == 0.0
    assert set(out["per_replica"]) == {0, 1}


def test_drop_when_busy_mode(cfg):
    eng = ServingEngine(cfg, n_replicas=1, scheduler="fcfs", cache_len=32,
                        drop_when_busy=True)
    out = eng.serve(burst(cfg, 12, rate=1e5))
    assert len(out["dropped"]) > 0
    assert len(out["dropped"]) + len(out["responses"]) == 12


# ---------------------------------------------- detection (frame) payloads
def frame_burst(n, rate, seed=0):
    from repro.core import SyntheticVideo
    from repro.core.stream import ETH_SUNNYDAY
    video = SyntheticVideo(ETH_SUNNYDAY)
    return [FrameRequest(i, video.pixels(i), i / rate) for i in range(n)]


def test_detection_engine_micro_batches_preserve_order():
    eng = DetectionEngine(n_replicas=2, micro_batch=4)
    out = eng.serve(frame_burst(10, rate=100.0))
    assert [r.rid for r in out["responses"]] == list(range(10))
    assert out["throughput_fps"] > 0
    for r in out["responses"]:
        assert r.boxes.shape[-1] == 4 and r.valid.dtype == bool
        assert r.scores.shape == r.valid.shape
    # every frame landed on a real replica
    assert sum(out["per_replica"].values()) == 10


def test_detection_engine_batching_matches_per_frame_results():
    """Micro-batch size must not change detections: the batched NMS is
    per-frame exact, so serving with mb=1 and mb=5 keeps the same
    detections (``valid``, ``classes``).  Boxes and scores agree to a
    few float32 ulps, not bit for bit: XLA's convolution does not
    promise equal summation order across batch shapes, on CPU or TPU."""
    import jax
    from repro.detector import SSDConfig, init_ssd
    cfg = SSDConfig()
    params = init_ssd(cfg, jax.random.PRNGKey(0))
    frames = frame_burst(5, rate=50.0)
    outs = {}
    for mb in (1, 5):
        eng = DetectionEngine(cfg=cfg, params=params, n_replicas=2,
                              micro_batch=mb)
        outs[mb] = eng.serve(frames)["responses"]
    for a, b in zip(outs[1], outs[5]):
        assert np.array_equal(a.valid, b.valid)
        assert np.array_equal(a.classes[a.valid], b.classes[b.valid])
        np.testing.assert_allclose(a.boxes[a.valid], b.boxes[b.valid],
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(a.scores[a.valid], b.scores[b.valid],
                                   rtol=0, atol=1e-6)
