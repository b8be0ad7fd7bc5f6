"""Ahead-of-time compiles of the frame path for a TPU v5e, with no chip
attached: the four Pallas kernels (``interpret=False``, so Mosaic lowers
them), the jitted ``decode_detections`` program and the fused tracker
tick, at the shapes the served path uses.  The TPU compiler refuses
here what it would refuse on the chip (block tiling, unsupported
primitives, VMEM), so these tests catch it without chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and a
worker that cannot load it skips these tests instead of failing
collection.  Code that asks ``kernels.ops._interpret()`` sees the CPU
backend here, so the program-level tests steer it to Mosaic themselves
and clear JAX's trace caches around that, so no compiled-for-TPU trace
reaches another test in the same process.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, A, MAX_OUT = 8, 160, 32          # micro-batch, anchors, NMS outputs
T, D = 64, 32                        # tracker slots, detections per frame
R, CROP = 4, 64                      # ROI windows per frame, crop size


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:             # no libtpu, or it is taken
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache here: keep it out
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def mosaic(monkeypatch):
    """Route ``kernels.ops`` to Mosaic for the duration of one test."""
    from repro.kernels import ops
    jax.clear_caches()
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    yield
    jax.clear_caches()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype), tree)


def _kernel_program(name, s):
    """(jitted kernel call, argument specs) at the served shapes."""
    from repro.kernels import association, nms, roi
    if name == "nms":
        return (lambda b, sc: nms.batched_nms_pallas(
            b, sc, interpret=False, score_thr=0.4, max_out=MAX_OUT,
            stop_at_zero=True),
            [_spec(s, (B, A, 4)), _spec(s, (B, A))])
    if name == "association":
        return (lambda *a: association.greedy_assign_pallas(
            *a, interpret=False),
            [_spec(s, (B, T, 4)), _spec(s, (B, D, 4)),
             _spec(s, (B, T), bool), _spec(s, (B, D), bool),
             _spec(s, (B, T), jnp.int32), _spec(s, (B, D), jnp.int32)])
    if name == "crop":
        return (lambda im, r: roi.crop_resize_pallas(
            im, r, out_size=CROP, interpret=False),
            [_spec(s, (B, 64, 64, 3)), _spec(s, (B, R, 4))])
    return (lambda bx, r: roi.uncrop_boxes_pallas(
        bx, r, bounds=(64, 64), crop_size=CROP, interpret=False),
        [_spec(s, (B, R, MAX_OUT, 4)), _spec(s, (B, R, 1, 4))])


@pytest.mark.parametrize("name", ["nms", "association", "crop", "uncrop"])
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_program(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla_nms", "pallas_nms"])
def test_decode_detections_compiles_for_v5e(one_chip, mosaic, use_pallas):
    from repro.detector import (SSDConfig, decode_detections, init_ssd,
                                make_anchors)
    cfg = SSDConfig()
    params = _on(one_chip, jax.eval_shape(
        lambda: init_ssd(cfg, jax.random.PRNGKey(0))))
    anchors = _spec(one_chip, make_anchors(cfg).shape)
    assert anchors.shape == (A, 4)
    images = _spec(one_chip, (B, cfg.image_size, cfg.image_size, 3))
    compiled = jax.jit(lambda p, a, x: decode_detections(
        p, cfg, x, a, max_out=MAX_OUT, use_pallas=use_pallas)).lower(
        params, anchors, images).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla_assoc", "pallas_assoc"])
def test_fused_tracker_tick_compiles_for_v5e(one_chip, mosaic, use_pallas):
    from repro.serving.pipeline import _fused_tick
    from repro.tracking import TrackerConfig, init_state
    cfg = TrackerConfig(capacity=T)
    state = _on(one_chip, jax.eval_shape(lambda: init_state(B, cfg)))
    compiled = _fused_tick.lower(
        state, _spec(one_chip, (B, D, 4)), _spec(one_chip, (B, D)),
        _spec(one_chip, (B, D), jnp.int32), _spec(one_chip, (B, D), bool),
        cfg, use_pallas).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas
