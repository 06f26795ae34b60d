"""The benchmark's one traffic generator: a mix file and a seed in, the
cameras' windows, ground truth and arrival schedule out.

A mix is a JSON file beside this one (`<mix>.json`) of parameters only:

  loop                "closed" (each camera submits its next window when
                      the last one returns) or "open" (windows are due on
                      a schedule, whatever the service does)
  cameras             number of cameras (streams)
  windows_per_camera  closed loop: windows generated per camera; a camera
                      that has served them all starts again from its first
                      window (sequence numbers keep counting)
  rate_per_s          open loop: aggregate arrival rate, windows/s
  recording_windows   windows per recording: a camera plays recordings one
                      after another, each with a motion of its own
  accuracy_windows_per_camera
                      K: the accuracy set is every camera's first K
                      windows (sequence numbers 0..K-1), whatever the run
                      served besides; a closed loop keeps K within
                      windows_per_camera, an open loop's schedule gives
                      every camera at least K arrivals at the benchmark's
                      run length (bench/harness.py completes the set
                      where a run served fewer)
  recordings_seed     seed of every window's data (see below)
  order_group         closed loop: cameras whose recordings the seed deals
                      among themselves
  events_per_window   events in every window
  window_dt, jerk_prob, jerk_scale, imu_noise
                      the camera motion, as in the scene model below
  scenes              list of scene parameter sets (name, n_features,
                      omega_scale, noise_px); data stream s takes scene
                      s % len(scenes)

The scene model is a copy of the repository's synthetic DVS generator
(`src/repro/data/events.py`, `make_sequence`), vectorised over windows,
so that the benchmark's inputs do not move when that file changes: M point
features with polarity, a smooth sum-of-sinusoids rotation with random
velocity steps ("jerks"), events drawn along each feature's rotational
flow with pixel quantisation and noise, and an IMU reading that is the
truth plus noise. Jerks accumulate, so a recording is kept short (24
windows, as `make_sequence` makes them) and a long stream is a chain of
recordings, as a camera's data would be a chain of sequences.

What the seed draws, and what it does not: every window (its true
rotation, its features and every event) and every IMU reading is drawn
from the mix's `recordings_seed`, so every run serves the same windows;
`--seed` draws their order where the order does not change the work. In
a closed loop it deals the recordings to the cameras within each group of
`order_group` cameras (the cameras that submit together and so share a
batch; a group's batch and its place among the groups stay as they are,
since a batch runs until its slowest window saturates). In an open loop
it draws where a fixed cycle of arrivals starts: n = round(rate_per_s *
seconds) arrivals over the window, spaced as a Poisson process given its
count (n + 1 exponential gaps, drawn from `recordings_seed`, normalised
to the window), each gap paired with a camera (every camera as often,
give or take one); the seed rotates this cycle of (gap, camera) pairs,
and the pair rotated into the last place is the gap to the window's end.
So every seed offers the same bunches of arrivals, to the same cameras,
at other times of the window; a single camera's windows keep their
order. Which of a camera's windows falls in which bunch still follows
the seed, so the tail of the latency does too (PERF.md). How
hard a window is for the controller depends on its events, and how long
a batch takes on its slowest window: where the seed drew the events, the
work changed from run to run (by 5% in windows/s and 13% in RMSE over 6
seeds on a TPU v5e), and where it dealt recordings across groups or
reordered a camera's recordings, by up to 13% in windows/s
(`cmax240-mk.backlog`) and 200% in RMSE (`cmax240-ref.one-camera`).
Because the seed only deals recordings within a group, every camera's
first K windows are the same K windows of the same recordings under
every seed, each warm-started from its own predecessor: the accuracy set
does not move with the seed, nor with how many windows a run serves
(later windows of a recording are harder, as jerks accumulate, so an
RMSE over every served window rose with throughput).
All arrays are made on the host from `numpy.random.SeedSequence`, so one
seed gives the same inputs anywhere.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

MARGIN_PX = 18.0          # features stay this far from the border


@dataclasses.dataclass
class CameraStream:
    """One camera's generated windows (host arrays, float32 / bool)."""
    name: str
    stream: int            # the data stream whose recordings it plays
    scene: str
    x: np.ndarray          # (K, N) pixel column
    y: np.ndarray          # (K, N) pixel row
    t: np.ndarray          # (K, N) seconds
    p: np.ndarray          # (K, N) polarity +-1
    valid: np.ndarray      # (K, N) inside the sensor
    omega_true: np.ndarray  # (K, 3) rad/s
    omega_imu: np.ndarray   # (K, 3) rad/s

    @property
    def n_windows(self) -> int:
        return self.x.shape[0]


@dataclasses.dataclass
class Traffic:
    mix: dict
    cameras: List[CameraStream]
    #: open loop: due times in s from the window's start, sorted, and the
    #: camera each is sent to; None for a closed loop
    schedule: Optional[np.ndarray] = None
    schedule_cam: Optional[np.ndarray] = None


def omega_trajectory(n: int, window_dt: float, omega_scale: float,
                     jerk_prob: float, jerk_scale: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Per-window constant rotation rate, (n, 3): a sum of three
    sinusoids per axis plus random velocity steps that persist."""
    t = (np.arange(n) + 0.5) * window_dt
    out = np.zeros((n, 3))
    for j in range(3):
        amps = rng.uniform(0.3, 1.0, size=3) * omega_scale
        freqs = rng.uniform(0.1, 0.9, size=3)
        phases = rng.uniform(0, 2 * np.pi, size=3)
        out[:, j] = sum(a * np.sin(2 * np.pi * f * t + ph)
                        for a, f, ph in zip(amps, freqs, phases)) / 3.0
    steps = rng.random(n) < jerk_prob
    steps[0] = False
    jumps = rng.normal(0, jerk_scale * omega_scale, size=(n, 3))
    out += np.cumsum(np.where(steps[:, None], jumps, 0.0), axis=0)
    return out


def rotational_flow(x, y, omega, cam: dict):
    """Image-plane flow (u, v) of a rotating camera at pixel (x, y)."""
    xn = (x - cam["cx"]) / cam["fx"]
    yn = (y - cam["cy"]) / cam["fy"]
    wx, wy, wz = omega[..., 0:1], omega[..., 1:2], omega[..., 2:3]
    u = cam["fx"] * (xn * yn * wx - (1.0 + xn * xn) * wy + yn * wz)
    v = cam["fy"] * ((1.0 + yn * yn) * wx - xn * yn * wy - xn * wz)
    return u, v


def recording(n: int, scene: dict, mix: dict, cam: dict,
              rng: np.random.Generator):
    """One recording of n windows: the true rotation (n, 3) and, per
    window, the features' positions and polarities (n, M)."""
    m = int(scene["n_features"])
    omega = omega_trajectory(n, float(mix["window_dt"]),
                             float(scene["omega_scale"]),
                             float(mix["jerk_prob"]),
                             float(mix["jerk_scale"]), rng)
    W, H = cam["width"], cam["height"]
    fx = rng.uniform(MARGIN_PX, W - MARGIN_PX, size=(n, m))
    fy = rng.uniform(MARGIN_PX, H - MARGIN_PX, size=(n, m))
    fp = rng.choice([-1.0, 1.0], size=(n, m))
    return omega, fx, fy, fp


def events(omega, fx, fy, fp, scene: dict, mix: dict, cam: dict,
           rng: np.random.Generator):
    """Every event of K windows, drawn along the features' flow:
    (x, y, t, p, valid), each (K, N)."""
    K, M = fx.shape
    N = int(mix["events_per_window"])
    wdt = float(mix["window_dt"])
    u, v = rotational_flow(fx, fy, omega, cam)                 # (K, M)
    # faster edges fire more events: each event's feature is drawn with
    # probability proportional to the flow speed at the feature
    rate = np.sqrt(u * u + v * v) + 5.0
    counts = rng.multinomial(N, rate / rate.sum(axis=1, keepdims=True))
    fid = np.repeat(np.tile(np.arange(M), K), counts.ravel()).reshape(K, N)
    fid = rng.permuted(fid, axis=1)
    # event times: sorted uniforms over the window, as normalised
    # cumulative sums of exponentials (no sort needed)
    gaps = rng.standard_exponential((K, N + 1), dtype=np.float32)
    cum = np.cumsum(gaps, axis=1)
    dt = (cum[:, :N] / cum[:, N:]) * np.float32(wdt)
    take = lambda a: np.take_along_axis(a.astype(np.float32), fid, axis=1)
    noise = rng.standard_normal((2, K, N), dtype=np.float32) \
        * np.float32(scene["noise_px"])
    ex = np.round(take(fx) + dt * take(u) + noise[0])
    ey = np.round(take(fy) + dt * take(v) + noise[1])
    valid = (ex >= 0) & (ex < cam["width"]) & (ey >= 0) & (ey < cam["height"])
    t = np.arange(K, dtype=np.float32)[:, None] * np.float32(wdt) + dt
    return (ex.astype(np.float32), ey.astype(np.float32),
            t.astype(np.float32), take(fp), valid)


def make_camera(c: int, stream: int, n_windows: int, mix: dict, cam: dict
                ) -> CameraStream:
    """Camera c, playing data stream `stream`: its recordings one after
    another, each recording's windows and events drawn from the mix's
    recordings_seed."""
    scene = mix["scenes"][stream % len(mix["scenes"])]
    seg = int(mix["recording_windows"])
    base = int(mix["recordings_seed"])
    n_rec = -(-n_windows // seg)
    parts = []
    for r in range(n_rec):
        rec = np.random.default_rng(np.random.SeedSequence([base, stream, r]))
        omega, fx, fy, fp = recording(seg, scene, mix, cam, rec)
        ev = events(omega, fx, fy, fp, scene, mix, cam, rec)
        imu = omega + rec.normal(0, float(mix["imu_noise"]), omega.shape)
        parts.append(ev + (omega, imu))
    x, y, t, p, valid, omega, imu = (np.concatenate(a)[:n_windows]
                                     for a in zip(*parts))
    return CameraStream(
        name=f"cam{c:03d}", stream=stream, scene=scene.get("name", ""),
        x=x, y=y, t=t, p=p, valid=valid,
        omega_true=omega.astype(np.float32),
        omega_imu=imu.astype(np.float32))


def arrival_cycle(mix: dict, seconds: float):
    """An open loop's fixed cycle of n + 1 (gap, camera) pairs, the same
    for every seed: n = round(rate_per_s * seconds) arrivals of a Poisson
    process over the window, given their count, are spaced as n + 1
    exponential gaps normalised to it."""
    n_cam = int(mix["cameras"])
    n = int(round(float(mix["rate_per_s"]) * float(seconds)))
    fixed = np.random.default_rng(np.random.SeedSequence(
        [int(mix["recordings_seed"]), n_cam, n]))
    gaps = fixed.standard_exponential(n + 1)
    cams = fixed.permutation(np.arange(n + 1) % n_cam)
    return gaps, cams


def rotated_schedule(gaps, cams, k: int, seconds: float):
    """The cycle rotated by k: due times (s from the window's start) and
    cameras of its first n pairs; the last pair's gap ends the window."""
    gaps, cams = np.roll(gaps, -k), np.roll(cams, -k)
    sched = np.cumsum(gaps)[:-1] / np.sum(gaps) * float(seconds)
    return sched, cams[:-1]


def generate(mix: dict, seed: int, seconds: float, cam: dict) -> Traffic:
    """The cell's traffic for one run. `cam` holds the camera intrinsics
    (width, height, fx, fy, cx, cy) of the configuration served."""
    n_cam = int(mix["cameras"])
    k_acc = int(mix["accuracy_windows_per_camera"])
    rng = np.random.default_rng(np.random.SeedSequence([seed, n_cam]))
    sched = sched_cam = None
    streams = np.arange(n_cam)
    if mix["loop"] == "closed":
        per_cam = [int(mix["windows_per_camera"])] * n_cam
        if k_acc > per_cam[0]:
            raise ValueError(f"accuracy_windows_per_camera {k_acc} exceeds "
                             f"windows_per_camera {per_cam[0]}")
        g = int(mix["order_group"])
        streams = np.concatenate([rng.permutation(grp)
                                  for grp in streams.reshape(-1, g)])
    elif mix["loop"] == "open":
        gaps, cams = arrival_cycle(mix, seconds)
        k = int(rng.integers(len(gaps)))
        sched, sched_cam = rotated_schedule(gaps, cams, k, seconds)
        # a camera scheduled fewer than K windows (a run shorter than the
        # benchmark's) still has its first K, for the accuracy set
        per_cam = [max(k_acc, int(np.sum(sched_cam == c)))
                   for c in range(n_cam)]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    cams = [make_camera(c, int(streams[c]), max(1, per_cam[c]), mix, cam)
            for c in range(n_cam)]
    return Traffic(mix=mix, cameras=cams, schedule=sched,
                   schedule_cam=sched_cam)
