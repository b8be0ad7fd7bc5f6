"""Percentiles over all frames, the analytic FLOP count, and the
``failed`` accounting of an emit boundary."""
import types

import numpy as np
import pytest

from bench import run, stats
from bench.flops import conv_flops_per_frame


def test_percentiles_are_exact_over_all_values():
    v = np.arange(1, 101, dtype=float)
    assert stats.percentile(v, 50) == 50.5
    assert stats.percentile(v, 99) == pytest.approx(99.01)
    assert stats.percentile(v[::-1], 99) == pytest.approx(99.01)
    assert stats.percentile([], 50) is None
    assert stats.percentile([7.0], 99) == 7.0


def test_flops_of_both_configurations():
    ssd416 = run.load_json(run.BENCH / "configs" / "ssd416-c80.json")["ssd"]
    mini = run.Cell("minissd64-eth14-steady").ssd
    # 208^2*9*3*16 + 104^2*9*16*32 + 52^2*9*32*64 + 26^2*9*64*128
    # + 13^2*9*128*256 + heads 26^2*9*128*170 + 13^2*9*256*170, times 2
    assert conv_flops_per_frame(ssd416) == 833264640
    assert conv_flops_per_frame(mini) == 8257536


def test_flops_agree_with_xla_cost_analysis():
    import jax
    from bench import reference
    ssd = run.Cell("minissd64-eth14-steady").ssd
    params = reference.make_params(ssd, 0)
    x = np.zeros((1, 64, 64, 3), np.float32)
    cost = reference.forward_fn(ssd).lower(params, x).compile() \
        .cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    # XLA leaves out the taps that fall on SAME padding; the analytic
    # count keeps them, as a dense convolution computes them
    xla = cost["flops"]
    assert 0.85 * conv_flops_per_frame(ssd) < xla < conv_flops_per_frame(ssd)


def _resp(rid, interp=False):
    return types.SimpleNamespace(rid=rid, interpolated=interp)


def _seg(rids):
    return [types.SimpleNamespace(rid=r) for r in rids]


def test_failed_counts_frames_a_boundary_did_not_return():
    d = run.Drive(10, 3)
    run.account(d, 0, {"responses": [_resp(0), _resp(1, True), _resp(2)],
                       "interpolated": 1}, _seg([0, 1, 2]))
    run.account(d, 1, {"responses": [_resp(3)], "interpolated": 0},
                _seg([3, 4, 5]))
    # a sharded boundary returns counts only
    run.account(d, 2, {"responses": 2, "interpolated": 1}, _seg([6, 7, 8]))
    assert list(d.failed) == [0, 2, 1]
    assert list(d.emitted) == [3, 1, 2]
    assert list(d.detected) == [2, 1, 1]
