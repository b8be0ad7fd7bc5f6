"""Percentiles over all frames, the analytic FLOP count, and the
``failed`` accounting of an emit boundary."""
import types

import numpy as np
import pytest

from bench import run, stats

SSD = run.load_module("families", "ssd")


def test_percentiles_are_exact_over_all_values():
    v = np.arange(1, 101, dtype=float)
    assert stats.percentile(v, 50) == 50.5
    assert stats.percentile(v, 99) == pytest.approx(99.01)
    assert stats.percentile(v[::-1], 99) == pytest.approx(99.01)
    assert stats.percentile([], 50) is None
    assert stats.percentile([7.0], 99) == 7.0


def test_flops_of_both_configurations():
    ssd416 = run.load_json(run.BENCH / "configs" / "ssd416-c80.json")
    mini = run.Cell("minissd64-eth14-steady").config
    # 208^2*9*3*16 + 104^2*9*16*32 + 52^2*9*32*64 + 26^2*9*64*128
    # + 13^2*9*128*256 + heads 26^2*9*128*170 + 13^2*9*256*170, times 2
    assert SSD.flops_per_frame(ssd416) == 833264640
    assert SSD.flops_per_frame(mini) == 8257536


def _xla_cost(cfg):
    import jax
    params = SSD.make_params(cfg, 0)
    size = SSD.image_size(cfg)
    x = np.zeros((1, size, size, 3), np.float32)
    cost = SSD.forward_fn(cfg["ssd"]).lower(params, x).compile() \
        .cost_analysis()
    return params, cost[0] if isinstance(cost, list) else cost


def test_flops_agree_with_xla_cost_analysis():
    cfg = run.Cell("minissd64-eth14-steady").config
    # XLA leaves out the taps that fall on SAME padding; the analytic
    # count keeps them, as a dense convolution computes them
    xla = _xla_cost(cfg)[1]["flops"]
    assert 0.85 * SSD.flops_per_frame(cfg) < xla < SSD.flops_per_frame(cfg)


@pytest.mark.parametrize("name", ["minissd64", "ssd416-c80"])
def test_backbone_counts_are_lower_bounds(name):
    """The scope's operations leave out the padding taps and the bias
    adds and ReLUs that XLA counts besides, so they lie just under its
    cost analysis; its bytes are the frame and every weight, once."""
    import jax
    cfg = run.load_json(run.BENCH / "configs" / f"{name}.json")
    params, cost = _xla_cost(cfg)
    flops = SSD.flops_by_scope(cfg)["backbone"]
    assert 0.97 * cost["flops"] < flops <= cost["flops"]
    assert flops < SSD.flops_per_frame(cfg)
    n_weights = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    size = SSD.image_size(cfg)
    for fpc in (1, 2.5, 8):
        assert SSD.bytes_by_scope(cfg, fpc)["backbone"] == \
            4 * (fpc * size * size * 3 + n_weights)


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
