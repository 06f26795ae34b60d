"""The roofline count against a hand count, and its independence from
the engine."""
import json
import os

import pytest

from bench import roofline

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_hand_count_one_stage():
    # 100 events, keep ratio 1/2, a 4x8 grid (camera 16x8 at s = 1/2),
    # 3 blur taps, 2 passes
    cam = {"width": 16, "height": 8}
    stage = {"scale": 0.5, "keep_ratio": 0.5, "blur_taps": 3}
    flops, nbytes = roofline.window_work(100, [2], cam, [stage])
    kept = 50
    per_event = 43 + 75               # warp + vote
    per_pixel = 2 * 4 * (2 * 3 - 1) + 12   # blur of 4 channels + sums
    assert flops == 2 * (kept * per_event + 4 * 8 * per_pixel)
    assert nbytes == kept * 16 + 2 * 32


def test_same_count_for_both_engines():
    cfgs = [json.load(open(os.path.join(BENCH, "configs", f"{n}.json")))
            for n in ("cmax240-ref", "cmax240-mk")]
    assert cfgs[0]["engine"] != cfgs[1]["engine"]
    works = [roofline.window_work(40000, [5, 7, 9], c["camera"], c["stages"])
             for c in cfgs]
    assert works[0] == works[1]


def test_least_time_and_bound():
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.least_time_s(197e12, 1.0, peak)
    assert t == pytest.approx(1.0) and bound == "compute"
    t, bound = roofline.least_time_s(1.0, 819e9, peak)
    assert t == pytest.approx(1.0) and bound == "memory"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
