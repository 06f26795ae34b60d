"""The open loop's arrivals: one fixed cycle for every seed, rotated."""
import numpy as np
import pytest

from bench.traffic.generate import generate

CAM = {"width": 64, "height": 48, "fx": 60.0, "fy": 60.0, "cx": 32.0,
       "cy": 24.0}
MIX = {"loop": "open", "cameras": 4, "rate_per_s": 6.2,
       "recording_windows": 24, "accuracy_windows_per_camera": 1,
       "recordings_seed": 2017,
       "events_per_window": 200, "window_dt": 0.02, "jerk_prob": 0.2,
       "jerk_scale": 0.5, "imu_noise": 0.03,
       "scenes": [{"name": "poster", "n_features": 20, "omega_scale": 3.5,
                   "noise_px": 0.35}]}
SECONDS = 5.0


def cycle(tr, seconds=SECONDS):
    """The (gap, camera) pairs of a run, the gap to the window's end
    last, with the camera that pair holds left unknown (-1)."""
    t = np.concatenate([[0.0], tr.schedule, [seconds]])
    return np.diff(t), np.concatenate([tr.schedule_cam, [-1]])


def rotation_of(a, b):
    """The k for which b is a rotated by k, or None."""
    for k in range(len(a)):
        if np.allclose(np.roll(a, -k), b, rtol=0, atol=1e-9):
            return k
    return None


def test_same_seed_same_arrivals():
    a = generate(MIX, 2 ** 31 + 5, SECONDS, CAM)
    b = generate(MIX, 2 ** 31 + 5, SECONDS, CAM)
    np.testing.assert_array_equal(a.schedule, b.schedule)
    np.testing.assert_array_equal(a.schedule_cam, b.schedule_cam)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 17, 2 ** 33 + 3])
def test_seeds_rotate_one_cycle(seed):
    base = generate(MIX, 0, SECONDS, CAM)
    other = generate(MIX, seed, SECONDS, CAM)
    n = int(round(MIX["rate_per_s"] * SECONDS))
    assert len(base.schedule) == len(other.schedule) == n
    assert np.all(np.diff(other.schedule) > 0)
    assert 0 < other.schedule[0] and other.schedule[-1] < SECONDS
    g0, c0 = cycle(base)
    g1, c1 = cycle(other)
    k = rotation_of(g0, g1)
    assert k is not None, "the gaps are not a rotation of one cycle"
    # each gap keeps its camera; the pair rotated to the end has none
    c0r = np.roll(c0, -k)
    keep = (c0r >= 0) & (c1 >= 0)
    np.testing.assert_array_equal(c0r[keep], c1[keep])
    counts = np.bincount(other.schedule_cam, minlength=MIX["cameras"])
    assert counts.max() - counts.min() <= 2


def test_seeds_start_the_cycle_at_other_places():
    starts = {round(float(generate(MIX, s, SECONDS, CAM).schedule[0]), 9)
              for s in range(8)}
    assert len(starts) > 1
