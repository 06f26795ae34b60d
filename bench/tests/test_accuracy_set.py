"""The accuracy set behind `rmse_fixed_rad_s`: every camera's first K
windows, the same for every seed and every count served."""
import json
import os
import time
import types

import numpy as np
import pytest

from bench import harness
from bench.traffic.generate import arrival_cycle, generate, rotated_schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAM = {"width": 64, "height": 48, "fx": 60.0, "fy": 60.0, "cx": 32.0,
       "cy": 24.0}


def run_seconds():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)["run_seconds"]


def light(mix: str, **over) -> dict:
    """The mix's file with few events per window: which windows a camera
    plays does not depend on how many events they hold."""
    m = harness.load_json(os.path.join(BENCH, "traffic", f"{mix}.json"))
    m.update(events_per_window=64, **over)
    for sc in m["scenes"]:
        sc["n_features"] = 8
    return m


class FakeService:
    """Answers every window on the next poll with its truth plus a planted
    error, and holds each camera to one window in flight."""

    def __init__(self, traffic, error=lambda stream_id, seq: np.zeros(3)):
        self.truth = {cam.name: cam.omega_true for cam in traffic.cameras}
        self.error = error
        self.seq: dict = {}
        self.queue: list = []
        self.submitted: list = []

    def submit(self, stream_id, window, omega_hint=None):
        assert all(sid != stream_id for sid, _ in self.queue), \
            f"{stream_id} submitted while in flight"
        seq = self.seq.get(stream_id, 0)
        self.seq[stream_id] = seq + 1
        self.queue.append((stream_id, seq))
        self.submitted.append((stream_id, seq))
        return seq

    def poll(self):
        out = []
        for sid, seq in self.queue:
            truth = self.truth[sid]
            out.append(types.SimpleNamespace(
                stream_id=sid, seq=seq, status="ok",
                omega=truth[seq % len(truth)] + self.error(sid, seq),
                t_done=time.monotonic()))
        self.queue = []
        return out

    def drain(self):
        return self.poll()


def closed_traffic(cameras=4, windows=6, k=4):
    mix = light("backlog", cameras=cameras, order_group=2,
                windows_per_camera=windows, accuracy_windows_per_camera=k)
    return generate(mix, 2 ** 31 + 11, 1.0, CAM)


def serve(drv, c, n):
    """Camera c's next n windows, one after another."""
    for _ in range(n):
        drv.submit(c, time.monotonic())
        drv.responses.extend(drv.svc.poll())


@pytest.mark.parametrize("mix", ["backlog", "poisson", "one-camera"])
def test_accuracy_set_is_the_same_for_every_seed(mix):
    m = light(mix)
    k, seg = m["accuracy_windows_per_camera"], m["recording_windows"]
    sets, truths = set(), set()
    for seed in range(12):
        tr = generate(m, seed, run_seconds(), CAM)
        # (data stream, recording, place in it) of every window in the set
        pairs = sorted((cam.stream, j // seg, j % seg)
                       for cam in tr.cameras for j in range(k))
        assert len(pairs) == k * m["cameras"]
        sets.add(tuple(pairs))
        truth = np.concatenate([cam.omega_true[:k] for cam in tr.cameras])
        truths.add(tuple(sorted(map(tuple, truth.tolist()))))
    assert len(sets) == 1 and len(truths) == 1


def test_every_poisson_camera_has_k_arrivals_under_every_rotation():
    m = harness.load_json(os.path.join(BENCH, "traffic", "poisson.json"))
    k, seconds = m["accuracy_windows_per_camera"], run_seconds()
    gaps, cams = arrival_cycle(m, seconds)
    least = min(
        np.bincount(rotated_schedule(gaps, cams, r, seconds)[1],
                    minlength=m["cameras"]).min()
        for r in range(len(gaps)))
    assert least >= k
    # so at the benchmark's length every window of the set is scheduled,
    # and the generator makes no window beyond the schedule
    tr = generate(light("poisson"), 2 ** 31 + 3, seconds, CAM)
    assert [cam.n_windows for cam in tr.cameras] == list(
        np.bincount(tr.schedule_cam, minlength=m["cameras"]))


def test_completion_serves_only_cameras_below_k_and_stops_at_k():
    tr = closed_traffic()
    svc = FakeService(tr)
    drv = harness.Client(svc, tr)
    for c, n in enumerate([0, 2, 4, 5]):
        serve(drv, c, n)
    before = len(svc.submitted)
    harness.complete_set(drv, 4)
    added = sorted(svc.submitted[before:])
    assert added == [("cam000", s) for s in range(4)] + \
        [("cam001", 2), ("cam001", 3)]
    assert drv.next_seq == [4, 4, 4, 5]
    assert len(drv.responses) == len(drv.sub)


def test_rmse_fixed_is_the_rmse_of_every_cameras_first_k_windows():
    planted = {("cam000", 0): [0.3, 0.4, 0.0], ("cam000", 1): [0.0, 0.0, 1.2],
               ("cam001", 0): [0.0, 0.0, 0.0], ("cam001", 1): [0.1, 0.2, 0.2]}

    def error(sid, seq):
        # windows past the set are far off, and must not count
        return np.array(planted.get((sid, seq), [9.0, 9.0, 9.0]))

    tr = closed_traffic(cameras=2, windows=4, k=2)
    drv = harness.Client(FakeService(tr, error), tr)
    serve(drv, 0, 4)
    serve(drv, 1, 1)
    # camera 1 has one window of its two: no reading
    assert "rmse_fixed_rad_s" not in harness.end_to_end(drv, 0.0, 1.0, 1.0)
    serve(drv, 1, 1)
    out = harness.end_to_end(drv, 0.0, 1.0, 1.0)
    # squared norms 0.25, 1.44, 0, 0.09 over 4 windows
    assert out["rmse_fixed_rad_s"] == pytest.approx(np.sqrt(1.78 / 4),
                                                    rel=1e-6)


def test_windows_completed_after_the_window_do_not_count_in_windows_per_s():
    tr = closed_traffic()
    drv = harness.Client(FakeService(tr), tr)
    for c in range(4):
        serve(drv, c, 3)
    t0 = time.monotonic() - 100.0
    for j, r in enumerate(drv.responses):
        r.t_done = t0 + 1.0 + 0.5 * (j // 4)    # a batch of 4 every 0.5 s
    # completions at 1.0, 1.5, 2.0 s: 8 after the first, over 1 s
    assert harness.end_to_end(drv, t0, 3.0, 1.0)["windows_per_s"] == 8.0
    harness.complete_set(drv, 5)
    late = drv.responses[12:]
    assert len(late) == 8 and all(r.t_done > t0 + 3.0 for r in late)
    assert harness.end_to_end(drv, t0, 3.0, 1.0)["windows_per_s"] == 8.0
