"""jit'd public wrappers around the Pallas kernels.

`iwe_accum`           : host-side tap expansion + tile sort + capacity
                        packing (the Alg.-3 analogue at VMEM-tile
                        granularity), then the tile_accumulate kernel,
                        then spatial reassembly.
`blur_stats`          : pad + lane-align the channel stack, then the
                        streaming blur/statistics kernel.
`batched_engine_pass` : the batched megakernel — slab-binning prologue
                        (Alg. 3 at row-slab granularity, vmapped over the
                        batch) + ONE (batch, slab)-grid pallas_call fusing
                        vote/accumulate/blur/stats, then Eq. 12; windows
                        that overflow a slab take the reference slow path.
                        Device scopes: `cmax.bin_taps` (the prologue),
                        `cmax.megakernel` (the pallas_call) and
                        `cmax.spill_slow_path` (the slow path's branch).

The kernels compile on a TPU and are interpreted on the CPU
(kernels/backend.py decides). The oracles live in ref.py; tests sweep
shapes/dtypes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.contrast import gaussian_taps, stats_to_objective
from repro.core.geometry import warp_events
from repro.core.iwe import TAP_OFFSETS, event_deltas
from repro.core.types import Camera, EventWindow

from .blur_stats import blur_stats_streaming, stats_from_block
from .iwe_accum import CHUNK, tile_accumulate
from .megakernel import megakernel_stats
from .ref import batched_engine_stats_ref


class IweAccumOut(NamedTuple):
    channels: jax.Array   # (4, H_s, W_s) f32
    spilled: jax.Array    # () int32 — taps dropped by capacity (0 if enough)


def _ceil_to(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _expand_taps(w, deltas):
    """The 4 bilinear taps of every event as independent contributions:
    (rows (4N,), cols (4N,), channel deltas (4, 4N)) in TAP_OFFSETS
    order."""
    rows = jnp.concatenate([w.y0 + dy for dy, _ in TAP_OFFSETS])
    cols = jnp.concatenate([w.x0 + dx for _, dx in TAP_OFFSETS])
    dv = jnp.concatenate([deltas[:, ti, :].T
                          for ti in range(len(TAP_OFFSETS))], axis=1)
    return rows, cols, dv


def _pack(key, n_bins: int, cap: int, slots: int):
    """Counting-sort packing: stable-sort taps by bin id `key` (n_bins =
    dump bin) and lay each bin's first `cap` taps into `slots` >= cap
    slots. Returns (order, src (n_bins, slots) source index into the
    sorted taps, in_cap mask, per-bin counts, per-bin sorted offsets)."""
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    cnt = jax.ops.segment_sum(jnp.ones_like(key_s), key_s,
                              num_segments=n_bins + 1)[:n_bins]
    offset = jnp.concatenate([jnp.zeros((1,), cnt.dtype),
                              jnp.cumsum(cnt)[:-1]])
    slot = jnp.arange(slots)[None, :]
    in_cap = slot < jnp.minimum(cnt, cap)[:, None]
    src = jnp.clip(offset[:, None] + slot, 0, key.shape[0] - 1)
    return order, src.astype(jnp.int32), in_cap, cnt, offset


@functools.partial(
    jax.jit, static_argnames=("cam", "scale", "tile", "capacity", "dtype"))
def iwe_accum(ev: EventWindow, omega: jax.Array, cam: Camera, scale: float,
              weights: Optional[jax.Array] = None,
              tile: Tuple[int, int] = (8, 128), capacity: int = 1024,
              dtype=jnp.float32) -> IweAccumOut:
    """Fused warp + bilinear vote + tile-partitioned accumulation.

    capacity is the fixed per-tile tap budget (the HW outlier-FIFO-depth
    analogue); `spilled` reports the taps over it, which the wrapper then
    adds through the slow path, so the result is exact for any capacity.
    """
    Hs, Ws = cam.grid(scale)
    TH, TW = tile
    nty, ntx = -(-Hs // TH), -(-Ws // TW)
    T = nty * ntx
    N = ev.n

    w = warp_events(ev, omega, cam, scale)
    deltas = event_deltas(w, ev.p, weights)                  # (N,4,4)
    ty, tx, dv = _expand_taps(w, deltas)
    valid = jnp.concatenate([w.in_range] * 4)

    tile_id = jnp.where(valid, (ty // TH) * ntx + tx // TW, T)
    pix_local = jnp.where(valid, (ty % TH) * TW + tx % TW, -1)

    slots = _ceil_to(capacity, CHUNK)
    order, src, in_cap, cnt, offset = _pack(tile_id, T, capacity, slots)
    pix_tile = jnp.where(in_cap, pix_local[order][src], -1)      # (T, S)
    dv_tile = jnp.where(in_cap[None], dv[:, order][:, src], 0)   # (4, T, S)

    tiles = tile_accumulate(pix_tile[:, None, :].astype(jnp.int32),
                            dv_tile.transpose(1, 0, 2).astype(dtype),
                            p_tile=TH * TW)

    # reassemble (T, 4, P_TILE) -> (4, Hs, Ws)
    img = tiles.reshape(nty, ntx, 4, TH, TW)
    img = img.transpose(2, 0, 3, 1, 4).reshape(4, nty * TH, ntx * TW)
    img = img[:, :Hs, :Ws]

    # spill pass: taps beyond the per-tile capacity take the slow path
    # (XLA scatter-add), exactly like the hardware drains its outlier FIFO
    # through the commit port — the kernel is exact for ANY capacity and
    # `spilled` becomes a telemetry counter for capacity tuning.
    tid_s = tile_id[order]
    rank = jnp.arange(4 * N, dtype=jnp.int32) - offset[jnp.clip(
        tid_s, 0, T - 1)].astype(jnp.int32)
    spill_mask = (tid_s < T) & (rank >= capacity)
    sy = jnp.clip(ty[order], 0, nty * TH - 1)
    sx = jnp.clip(tx[order], 0, ntx * TW - 1)
    sdelta = jnp.where(spill_mask[None], dv[:, order], 0).astype(jnp.float32)
    pad = jnp.zeros((4, nty * TH, ntx * TW), jnp.float32)
    pad = pad.at[:, sy, sx].add(sdelta)
    img = img + pad[:, :Hs, :Ws]

    spilled = jnp.sum(jnp.maximum(cnt - capacity, 0)).astype(jnp.int32)
    return IweAccumOut(channels=img, spilled=spilled)


@functools.partial(jax.jit, static_argnames=("num_taps", "sigma", "rb"))
def blur_stats(channels: jax.Array, num_taps: int, sigma: float,
               rb: int = 16) -> jax.Array:
    """Streaming separable Gaussian + Eq.-12 running sums. channels is the
    (4, H, W) stack; returns (8,) f32 [S1,S2,Gx,Gy,Gz,Tx,Ty,Tz]."""
    _, H, W = channels.shape
    k = num_taps
    half = k // 2
    n_blocks = -(-(H + half) // rb)
    Hp = n_blocks * rb
    Wp = _ceil_to(W + half, 128)
    ch = jnp.zeros((Hp, 4, Wp), jnp.float32)
    ch = ch.at[:H, :, :W].set(
        channels.astype(jnp.float32).transpose(1, 0, 2))
    taps = gaussian_taps(k, sigma, jnp.float32)
    return stats_from_block(blur_stats_streaming(ch, taps, rb=rb, k=k, H=H,
                                                 W=W))


@functools.partial(
    jax.jit,
    static_argnames=("cam", "scale", "num_taps", "sigma", "tile",
                     "capacity"))
def fused_engine_pass(ev: EventWindow, omega: jax.Array, cam: Camera,
                      scale: float, num_taps: int, sigma: float,
                      weights: Optional[jax.Array] = None,
                      tile: Tuple[int, int] = (8, 128),
                      capacity: int = 1024):
    """Full kernel-path engine pass: accumulate + streaming stats ->
    (variance, grad) — the drop-in replacement for
    pipeline.make_engine_pass."""
    acc = iwe_accum(ev, omega, cam, scale, weights=weights, tile=tile,
                    capacity=capacity)
    Hs, Ws = cam.grid(scale)
    stats = blur_stats(acc.channels, num_taps, sigma)
    var, grad = stats_to_objective(stats, Hs * Ws)
    return var, grad, acc.spilled


# ---------------------------------------------------------------------------
# Batched megakernel wrappers
# ---------------------------------------------------------------------------


class BatchedEngineOut(NamedTuple):
    stats: jax.Array     # (B, 8) f32 Eq. 12 running sums per window
    spilled: jax.Array   # (B,) int32 — contributing taps over capacity


def _bin_taps_one(ev: EventWindow, omega: jax.Array, weights: jax.Array,
                  cam: Camera, scale: float, rb: int, n_slabs: int, Wp: int,
                  cap: int):
    """Slab-binning prologue for one window (vmapped over the batch):
    warp once, expand the 4 bilinear taps with their channel deltas, bin
    contributing taps by destination row slab (row // rb) and pack each
    slab's records into CAP slots — the Alg.-3 pixel-group sort at the
    megakernel's tile granularity. Zero-weight taps (subsampling-dropped
    or out-of-range events) carry identically-zero deltas, so they are
    routed to the dump slab instead of burning capacity.

    Returns ((NS, 1, CAP) slab-local pixel ids, (NS, 4, CAP) deltas) and
    the spill count."""
    w = warp_events(ev, omega, cam, scale)
    pw = ev.p.astype(jnp.float32) * weights.astype(jnp.float32)
    contributing = w.in_range & (pw != 0.0)
    deltas = event_deltas(w, ev.p, weights.astype(jnp.float32))
    rows, cols, dv = _expand_taps(w, deltas)
    live = jnp.concatenate([contributing] * 4)

    slab = jnp.where(live, rows // rb, n_slabs)
    pix = (rows - slab * rb) * Wp + cols
    order, src, in_cap, cnt, _ = _pack(slab, n_slabs, cap, cap)
    pix_p = jnp.where(in_cap, pix[order][src], -1).astype(jnp.int32)
    dv_p = jnp.where(in_cap[None], dv[:, order][:, src], 0.0)
    spilled = jnp.sum(jnp.maximum(cnt - cap, 0)).astype(jnp.int32)
    return (pix_p[:, None, :], dv_p.transpose(1, 0, 2)), spilled


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@functools.partial(
    jax.jit,
    static_argnames=("cam", "scale", "num_taps", "sigma", "rb", "capacity",
                     "dtype"))
def batched_engine_stats(ev: EventWindow, omega: jax.Array, cam: Camera,
                         scale: float, num_taps: int, sigma: float,
                         weights: Optional[jax.Array] = None,
                         rb: int = 8, capacity: int = 4096,
                         dtype=jnp.float32) -> BatchedEngineOut:
    """Full batched engine pass -> (B, 8) Eq. 12 stats in ONE pallas_call.

    `ev` arrays are (B, N) with padded slots carrying valid=False; `omega`
    is (B, 3). `capacity` is the fixed per-(window, slab) tap budget (the
    HW outlier-FIFO-depth analogue, rounded up to a whole number of MXU
    chunks); `spilled` counts, per window, the contributing taps that did
    not fit. A window that spilled takes the slow path: the reference
    datapath (scatter-add vote, materialized blur) recomputes its stats,
    as `iwe_accum` drains its spilled taps through a scatter. The result
    is exact for any capacity; `spilled` says how often the slow path ran,
    and capacity is sized so that it does not."""
    Hs, Ws = cam.grid(scale)
    k = num_taps
    half = k // 2
    n_slabs = _ceil_div(Hs + half, rb)
    Wp = _ceil_to(Ws + half, 128)
    cap = _ceil_to(max(capacity, CHUNK), CHUNK)
    if weights is None:
        weights = jnp.ones_like(ev.x, dtype=jnp.float32)
    omega = omega.astype(jnp.float32)

    with jax.named_scope("cmax.bin_taps"):
        (pix, dv), spilled = jax.vmap(
            lambda x, y, t, p, v, om, wt: _bin_taps_one(
                EventWindow(x, y, t, p, v), om, wt, cam, scale, rb, n_slabs,
                Wp, cap))(ev.x, ev.y, ev.t, ev.p, ev.valid, omega, weights)

    fir = gaussian_taps(k, sigma, jnp.float32)
    with jax.named_scope("cmax.megakernel"):
        block = megakernel_stats(pix, dv.astype(dtype), fir, rb=rb, k=k,
                                 H=Hs, W=Ws, Wp=Wp)
    stats = stats_from_block(block)

    def slow_path(stats):
        with jax.named_scope("cmax.spill_slow_path"):
            ref = batched_engine_stats_ref(ev, omega, cam, scale, k, sigma,
                                           weights.astype(jnp.float32))
            return jnp.where((spilled > 0)[:, None], ref, stats)

    stats = jax.lax.cond(jnp.any(spilled > 0), slow_path, lambda s: s,
                         stats)
    return BatchedEngineOut(stats=stats, spilled=spilled)


@functools.partial(
    jax.jit,
    static_argnames=("cam", "scale", "num_taps", "sigma", "rb", "capacity",
                     "dtype"))
def batched_engine_pass(ev: EventWindow, omega: jax.Array, cam: Camera,
                        scale: float, num_taps: int, sigma: float,
                        weights: Optional[jax.Array] = None,
                        rb: int = 8, capacity: int = 4096,
                        dtype=jnp.float32):
    """Batched megakernel engine pass -> (variance (B,), grad (B, 3),
    spilled (B,)) — the drop-in batched replacement for
    pipeline.make_engine_pass on a whole window batch."""
    out = batched_engine_stats(ev, omega, cam, scale, num_taps, sigma,
                               weights=weights, rb=rb, capacity=capacity,
                               dtype=dtype)
    Hs, Ws = cam.grid(scale)
    var, grad = stats_to_objective(out.stats, Hs * Ws)
    return var, grad, out.spilled
