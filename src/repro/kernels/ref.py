"""Pure-jnp oracles for the Pallas kernels.

These re-state the kernels' math with plain XLA ops; tests assert the
Pallas implementations (run in interpret mode on CPU) match these
bit-closely across shape/dtype sweeps.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.geometry import warp_events
from repro.core.iwe import accumulate
from repro.core.contrast import streaming_stats, gaussian_taps
from repro.core.types import Camera, EventWindow


def iwe_accum_ref(ev: EventWindow, omega: jax.Array, cam: Camera,
                  scale: float, weights: Optional[jax.Array] = None
                  ) -> jax.Array:
    """Oracle for kernels.iwe_accum: the reference scatter-add datapath.
    Returns the (4, H_s, W_s) channel stack."""
    w = warp_events(ev, omega, cam, scale)
    return accumulate(w, ev.p, cam.grid(scale), weights=weights)


def blur_stats_ref(channels: jax.Array, num_taps: int,
                   sigma: float) -> jax.Array:
    """Oracle for kernels.blur_stats: the eight running sums
    [S1, S2, Gx, Gy, Gz, Tx, Ty, Tz] of Eq. 12 computed by materializing
    the blurred images (which the kernel never does)."""
    taps = gaussian_taps(num_taps, sigma, jnp.float32)
    return streaming_stats(channels.astype(jnp.float32), taps)


def batched_engine_stats_ref(ev: EventWindow, omega: jax.Array, cam: Camera,
                             scale: float, num_taps: int, sigma: float,
                             weights: jax.Array) -> jax.Array:
    """Oracle for kernels.batched_engine_stats, and its slow path: the
    (B, 8) Eq. 12 sums of a (B, N) batch through the reference scatter-add
    datapath, window by window."""
    return jax.vmap(lambda x, y, t, p, v, om, wt: blur_stats_ref(
        iwe_accum_ref(EventWindow(x, y, t, p, v), om, cam, scale,
                      weights=wt), num_taps, sigma))(
        ev.x, ev.y, ev.t, ev.p, ev.valid, omega, weights)
