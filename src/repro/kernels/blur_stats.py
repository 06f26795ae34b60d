"""Pallas TPU kernel: streaming separable Gaussian + on-the-fly statistics.

The paper's final engine stage (§4 "Streaming Gaussian Smoothing with
On-the-Fly Statistics"): blur the 4 accumulated channels and reduce the
blurred pixels directly into the eight running sums of Eq. 12 —
[S1, S2, Gx, Gy, Gz, Tx, Ty, Tz] — without ever writing a blurred image
back to memory.

TPU realization: a row-block-streaming kernel with a *line buffer in VMEM
scratch*, the direct analogue of the hardware's 36 line buffers:

  * grid step i loads RB image rows; each row is a (4, Wp) channel-major
    tile (channels on sublanes, the padded W axis on lanes),
  * horizontal 1-D FIR: lane rotations (`pltpu.roll`) of each row,
  * the last (K-1) horizontally-blurred rows of the previous block are
    carried in VMEM scratch; with the current block they give a valid
    vertical window for RB output rows (lagged by K//2 rows),
  * each emitted blurred row is masked to the valid HxW region and added
    into two (4, Wp) running-sum tiles (VMEM scratch): the channels
    [I, Dx, Dy, Dz] and their products with I [I^2, I*Dx, I*Dy, I*Dz],
  * the final grid step reduces those tiles over the lanes and writes the
    eight sums — the only HBM output.

The horizontal FIR needs no edge mask: rows are zero beyond column W and
Wp >= W + K//2, so every rotated-in value at an edge is a zero, and the
taps are symmetric, so the result does not depend on the rotation's
direction.

HBM traffic: read the channel stack once, write the stats block. The
paper's claim "removes an entire writeback/readback pass" is structural
here. `blur_rows_into_stats` and `emit_stats` are shared with the
megakernel (kernels/megakernel.py), which feeds them rows it voted itself.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret


def blur_rows_into_stats(rows: Sequence[jax.Array], i, taps_ref, lb_ref,
                         acc_ref, *, k: int, H: int, W: int) -> None:
    """Blur RB channel rows (each (4, Wp) f32) and fold them into the
    running sums.

    `i` is the block index: block i holds image rows [i*RB, (i+1)*RB) and
    emits blurred rows [i*RB - K//2, (i+1)*RB - K//2). taps_ref: (K,) FIR
    in SMEM; lb_ref: (K-1, 4, Wp) line buffer; acc_ref: (2, 4, Wp) running
    sums. Both scratches must be zeroed before block 0."""
    rb = len(rows)
    half = k // 2
    Wp = rows[0].shape[-1]
    taps = [taps_ref[j] for j in range(k)]

    def hblur(row):
        out = taps[half] * row
        for j in range(k):
            if j != half:
                # rolled[x] = row[x + j - half]
                shift = (half - j) % Wp
                out = out + taps[j] * pltpu.roll(row, shift, 1)
        return out

    win = [lb_ref[j] for j in range(k - 1)] + [hblur(r) for r in rows]
    for j in range(k - 1):
        lb_ref[j] = win[rb + j]

    col_ok = jax.lax.broadcasted_iota(jnp.int32, (1, Wp), 1) < W
    s1 = acc_ref[0]
    s2 = acc_ref[1]
    for r in range(rb):
        vb = taps[0] * win[r]
        for j in range(1, k):
            vb = vb + taps[j] * win[r + j]
        row = i * rb - half + r
        ok = col_ok & (row >= 0) & (row < H)
        m = jnp.where(ok, vb, 0.0)                 # [I, Dx, Dy, Dz]
        s1 = s1 + m
        s2 = s2 + m * m[0:1]                       # [I^2, I*Dx, I*Dy, I*Dz]
    acc_ref[0] = s1
    acc_ref[1] = s2


def emit_stats(acc_ref, out_ref) -> None:
    """Reduce the running-sum tiles over the lanes into the (8, 128) stats
    block: row c holds sum(channel c) for c < 4 and sum(I * channel c) at
    row 4 + c, broadcast along the lanes (`stats_from_block` decodes it)."""
    lanes = out_ref.shape[-1]
    for a in range(2):
        tot = jnp.sum(acc_ref[a], axis=1, keepdims=True)    # (4, 1)
        out_ref[4 * a:4 * a + 4, :] = jnp.broadcast_to(tot, (4, lanes))


def stats_from_block(block: jax.Array) -> jax.Array:
    """(..., 8, 128) stats blocks -> (..., 8) Eq. 12 sums
    [S1, S2, Gx, Gy, Gz, Tx, Ty, Tz]."""
    r = block[..., 0]
    return jnp.stack([r[..., 0], r[..., 4], r[..., 5], r[..., 6],
                      r[..., 7], r[..., 1], r[..., 2], r[..., 3]], axis=-1)


def _kernel(ch_ref, taps_ref, out_ref, lb_ref, acc_ref, *,
            rb: int, k: int, H: int, W: int, n_blocks: int):
    """One grid step: process RB rows of all 4 channels."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        lb_ref[...] = jnp.zeros_like(lb_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = [ch_ref[r].astype(jnp.float32) for r in range(rb)]
    blur_rows_into_stats(rows, i, taps_ref, lb_ref, acc_ref, k=k, H=H, W=W)

    @pl.when(i == n_blocks - 1)
    def _emit():
        emit_stats(acc_ref, out_ref)


@functools.partial(jax.jit,
                   static_argnames=("rb", "k", "H", "W", "interpret"))
def blur_stats_streaming(channels: jax.Array, taps: jax.Array, *, rb: int,
                         k: int, H: int, W: int,
                         interpret: Optional[bool] = None) -> jax.Array:
    """channels: (Hp, 4, Wp) row-major zero-padded stack (Hp = n_blocks*RB
    >= H + K//2, Wp >= W + K//2 and a multiple of 128); taps: (k,) FIR.
    Returns the (8, 128) stats block (`stats_from_block` decodes it)."""
    Hp, _, Wp = channels.shape
    if Hp % rb or Hp < H + k // 2:
        raise ValueError(f"pad rows to a multiple of rb={rb} covering "
                         f"H + k//2 = {H + k // 2} (got {Hp})")
    if Wp % 128 or Wp < W + k // 2:
        raise ValueError(f"pad columns to a multiple of 128 covering "
                         f"W + k//2 = {W + k // 2} (got {Wp})")
    n_blocks = Hp // rb
    kern = functools.partial(_kernel, rb=rb, k=k, H=H, W=W,
                             n_blocks=n_blocks)
    return pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((rb, 4, Wp), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((k - 1, 4, Wp), jnp.float32),     # line buffer
            pltpu.VMEM((2, 4, Wp), jnp.float32),         # running sums
        ],
        interpret=resolve_interpret(interpret),
    )(channels, taps)
