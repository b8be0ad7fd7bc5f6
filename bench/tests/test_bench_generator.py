"""The traffic generator: due times, boundaries, seeded pool offsets and
the renderer copy."""
import numpy as np
import pytest

from bench import generator


def test_due_times_follow_the_phase_stagger():
    due, s, k = generator.due_times(30.0, 4, 1.0)
    assert len(due) == 120
    np.testing.assert_allclose(due, (k + s / 4) / 30.0)
    assert (np.diff(due) > 0).all() and due[-1] < 1.0
    assert list(s[:5]) == [0, 1, 2, 3, 0] and list(k[:5]) == [0, 0, 0, 0, 1]


@pytest.mark.parametrize("fps,cams", [(30.0, 7), (14.0, 250)])
def test_each_boundary_flushes_one_frame_per_camera(fps, cams):
    due, s, k = generator.due_times(fps, cams, 2.0)
    segs = generator.split_by_boundary(due, 1.0 / fps)
    assert len(segs) == int(np.ceil(2.0 * fps))
    for j, sl in enumerate(segs):
        assert sorted(s[sl]) == list(range(cams))
        assert (k[sl] == j).all()
        assert (due[sl] < (j + 1) / fps).all()


def test_pool_offsets_are_seeded():
    off = lambda seed: generator.camera_offsets(
        np.random.default_rng(np.random.SeedSequence([seed, 7])), 50, 128)
    assert (off(3) == off(3)).all()
    assert (off(3) != off(2**31 + 9)).any()
    assert ((off(5) >= 0) & (off(5) < 128)).all()
    idx = generator.pool_index(np.array([126, 0]), np.array([0, 0, 1]),
                               np.array([1, 3, 2]), 128)
    assert list(idx) == [127, 1, 2]


def test_renderer_matches_the_program_scene():
    from repro.core.stream import ETH_SUNNYDAY, SyntheticVideo
    spec = generator.VideoSpec(**{f: getattr(ETH_SUNNYDAY, f) for f in (
        "name", "fps", "n_frames", "width", "height", "moving_camera",
        "n_objects", "seed", "obj_speed", "cam_speed")})
    ours, theirs = generator.SyntheticVideo(spec), SyntheticVideo(ETH_SUNNYDAY)
    for i in (0, 17, 300):
        np.testing.assert_array_equal(ours.pixels(i, 64),
                                      theirs.pixels(i, 64))
