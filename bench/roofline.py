"""The benchmark's roofline yardstick: the work that contrast maximisation
needs, counted from the algorithm's shapes, and the chip's peaks.

The count is the algorithm's own and not an engine's: it is the same for
the reference engine, the megakernel, and whatever replaces them.

  events   real (unpadded, valid) events of the window times the stage's
           keep ratio rho_s: what Alg. 3 keeps
  passes   engine passes the window used at the stage: its entry pass plus
           one per controller iteration, as the decision log records
  FLOPs    per kept event and pass: the warp with its omega Jacobian
           (WARP_FLOPS) and the bilinear vote of 4 taps x 4 channels
           (VOTE_FLOPS); per stage pixel and pass: the separable blur of
           4 channels (2 x 4 x (2k - 1) for k taps) and the Eq. 12 sums
           (STATS_FLOPS)
  bytes    each kept event read once per window and stage (x, y, t, p as
           float32: EVENT_BYTES), and the eight float32 sums written once
           per pass

Padding, batch-fill slots, lockstep passes of windows that have already
converged, and an engine's own extra work (a dense one-hot vote, binning
sorts) are not counted: they are waste, and the share shows them as such.
"""
from __future__ import annotations

import math

#: warp of one event at one omega: normalised coordinates (4), dt (1),
#: 1 + xn^2, 1 + yn^2, xn yn (5), flow u and v (12), warped and scaled
#: coordinates (6), floor fractions (2), s dt and the two Jacobian rows
#: (1 + 12)
WARP_FLOPS = 43
#: bilinear vote of one event: tap weights (6), their omega derivatives
#: (4 taps x 3 components x 3), amplitude (1), amplitude times the 16
#: values (16), and the 16 accumulations
VOTE_FLOPS = 6 + 36 + 1 + 16 + 16
#: Eq. 12 sums per pixel: S1 (1), S2 (2), G (6), T (3)
STATS_FLOPS = 12
#: x, y, t, p of one event as float32
EVENT_BYTES = 16
#: the eight float32 sums one pass writes
STATS_BYTES = 32

#: Published peaks, keyed by `jax.Device.device_kind`. Source for the TPU
#: v5e: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
#: bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    """The peak row of a device; a device missing from the table is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/roofline.py "
                       f"with their source")
    return PEAKS[device_kind]


def stage_grid(camera: dict, scale: float):
    return (math.ceil(scale * camera["height"]),
            math.ceil(scale * camera["width"]))


def window_work(n_events: int, passes, camera: dict, stages) -> tuple:
    """(FLOPs, bytes) one window needs: `n_events` real events, and
    `passes[s]` engine passes at stage s."""
    flops = 0.0
    nbytes = 0.0
    for st, n_pass in zip(stages, passes):
        kept = n_events * float(st["keep_ratio"])
        hs, ws = stage_grid(camera, float(st["scale"]))
        k = int(st["blur_taps"])
        per_pass = (kept * (WARP_FLOPS + VOTE_FLOPS)
                    + hs * ws * (2 * 4 * (2 * k - 1) + STATS_FLOPS))
        flops += n_pass * per_pass
        nbytes += kept * EVENT_BYTES + n_pass * STATS_BYTES
    return flops, nbytes


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the least time at the peaks, and which bounds."""
    t_f = flops / peak["flops_per_s"]
    t_b = nbytes / peak["bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
